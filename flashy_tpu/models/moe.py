# Mixture-of-Experts MLP with expert parallelism. Beyond reference
# parity (SURVEY §2.3: EP absent there) but first-class here: expert
# weight tables are sharded over the mesh's 'expert' axis, and the
# dense dispatch/combine einsums below are exactly the patterns XLA's
# SPMD partitioner turns into all-to-alls over ICI — the
# Switch-Transformer/GShard construction, compiler-scheduled instead of
# hand-written.
"""MoEMLP: top-1/top-2 routed experts with capacity-based dense dispatch;
`expert_layer`: the one dropless expert layer of the serving path."""
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from .quantize import is_quantized, kernel_operand, postscale


def moe_aux_loss(mutated_collections: tp.Mapping[str, tp.Any]) -> jax.Array:
    """Sum the load-balancing losses sown by every MoEMLP in a model.

    Use with `logits, mutated = model.apply(vars, x, mutable=['losses'])`
    then add `weight * moe_aux_loss(mutated)` to the training loss.
    """
    leaves = jax.tree_util.tree_leaves(mutated_collections.get("losses", {}))
    if not leaves:
        return jnp.zeros(())
    return sum(jnp.sum(leaf) for leaf in leaves)


class MoEMLP(nn.Module):
    """Routed mixture-of-experts MLP over [B, T, D] activations.

    Dense-dispatch formulation: tokens are routed to `num_experts`
    experts with per-expert capacity `capacity_factor * T_tokens /
    num_experts`; overflowing tokens pass through with zero expert
    contribution (standard Switch behavior). The load-balancing auxiliary
    loss is exposed via `self.sow('losses', 'moe_aux', ...)` — fetch it
    with `mutable=['losses']` and add `aux_weight * mean` to your loss.

    Args:
        dim: model width.
        hidden: per-expert MLP hidden width.
        num_experts: expert count (shard over the 'expert' mesh axis).
        top_k: 1 (Switch) or 2 (GShard-style) experts per token.
        capacity_factor: slack over perfectly-balanced routing.
        dtype: activation/compute dtype.
    """

    dim: int
    hidden: int
    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    dtype: tp.Any = jnp.bfloat16
    dispatch: str = "einsum"   # 'einsum': one-hot [N,E,C] dispatch whose
    #   contractions lower to all-to-alls under expert sharding (use on
    #   expert-parallel meshes); 'sorted': argsort-based scatter/gather,
    #   O(N) dispatch memory instead of O(N*E*C) (use for large
    #   token-count, replicated-expert training); 'dropless': NO capacity
    #   limit at all — tokens sort by expert and run through a pallas
    #   grouped matmul (megablocks construction), every token always
    #   reaches its top-k experts (use for replicated-expert training
    #   where routing overflow hurts quality); 'dropless_ep': the
    #   expert-parallel hybrid — explicit capacity-bounded all-to-all
    #   between the mesh's expert shards (requires `mesh`), grouped
    #   matmul on each shard's local expert slab (see
    #   parallel/moe_ep.py for the exchange construction).
    mesh: tp.Any = None        # required by 'dropless_ep'
    expert_axis: str = "expert"
    token_axes: tp.Tuple[str, ...] = ("data",)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        batch, seq, dim = x.shape
        n_tokens = batch * seq
        # Capacity scales with top_k: there are N*k assignments to fill.
        capacity = max(1, int(self.capacity_factor * n_tokens * self.top_k
                              / self.num_experts))
        x_flat = x.reshape(n_tokens, dim)
        if self.dispatch == "sorted":
            return self._sorted_moe(x_flat, capacity).reshape(batch, seq, dim)
        if self.dispatch == "dropless":
            return self._dropless_moe(x_flat).reshape(batch, seq, dim)
        if self.dispatch == "dropless_ep":
            return self._dropless_ep_moe(x_flat).reshape(batch, seq, dim)
        if self.dispatch != "einsum":
            raise ValueError(f"unknown dispatch {self.dispatch!r}")

        probs, w_up, w_down = self._router_and_weights(x_flat)  # [N, E]
        round_experts, round_gates = self._route(probs)         # [k, N]

        combine = jnp.zeros((n_tokens, self.num_experts, capacity),
                            dtype=jnp.float32)
        # Slots already handed out per expert by earlier top-k rounds, so
        # a second-choice token never collides with a first-choice one.
        # All slot bookkeeping is integer: a float32 cumsum loses exact
        # integer positions past 2^24 routed tokens, silently colliding
        # capacity slots on very large global batches.
        expert_counts = jnp.zeros((self.num_experts,), jnp.int32)
        for expert_index, gate in zip(round_experts, round_gates):
            mask = jax.nn.one_hot(expert_index, self.num_experts,
                                  dtype=jnp.int32)                 # [N, E]
            # Position of each token inside its expert's buffer, offset
            # by the slots used in previous rounds.
            position = ((jnp.cumsum(mask, axis=0) - 1)
                        + expert_counts[None, :]) * mask           # [N, E]
            within = (position < capacity).astype(jnp.int32)
            mask = mask * within
            slot = jax.nn.one_hot(position.sum(axis=-1), capacity)  # [N, C]
            combine = combine + gate[:, None, None] \
                * mask.astype(jnp.float32)[:, :, None] * slot[:, None, :]
            expert_counts = expert_counts + mask.sum(axis=0)

        dispatch = (combine > 0.0).astype(self.dtype)          # [N, E, C]
        # Exposed for tests/debugging (dead-code-eliminated unless the
        # caller requests mutable=['intermediates']).
        self.sow("intermediates", "dispatch", dispatch)

        # Dispatch -> per-expert batches; these einsums become the
        # all-to-alls when x is batch-sharded and w_* expert-sharded.
        expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                               x_flat.astype(self.dtype))
        h = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        out = jnp.einsum("nec,ecd->nd", combine.astype(self.dtype), expert_out)
        return out.reshape(batch, seq, dim)

    def _router_and_weights(self, x_flat: jax.Array):
        """Single definition of the router (f32 softmax) and the expert
        weight tables [E, ...] (shard dim 0 over 'expert'); shared by
        all dispatch modes so their parameter trees stay identical."""
        probs = jax.nn.softmax(
            nn.Dense(self.num_experts, use_bias=False, dtype=jnp.float32,
                     name="router")(x_flat.astype(jnp.float32)), axis=-1)
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (self.num_experts, x_flat.shape[-1], self.hidden),
                          jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (self.num_experts, self.hidden, x_flat.shape[-1]),
                            jnp.float32)
        return probs, w_up, w_down

    def _route(self, probs: jax.Array):
        """Sequential top-k argmax routing, shared by all dispatch modes
        (one implementation: `parallel.moe_ep._topk_route`, which the
        EP exchange also uses — parity across modes depends on it): per
        round r, each token picks its best not-yet-used expert with the
        raw softmax probability as the gate. Sows the Switch
        load-balancing aux loss (eq. 4: E * sum_e f_e * p_e). Returns
        (expert_index [k, N] int, gate [k, N] f32)."""
        from ..parallel.moe_ep import _topk_route
        expert_ids, gates, hard_density = _topk_route(
            probs, self.num_experts, self.top_k)
        density = jnp.mean(probs, axis=0)
        aux = self.num_experts * jnp.sum(density * hard_density / self.top_k)
        self.sow("losses", "moe_aux", aux)
        return expert_ids, gates

    def _dropless_moe(self, x_flat: jax.Array) -> jax.Array:
        """Dropless dispatch: sort token-expert assignments by expert and
        run ONE grouped matmul per projection (pallas megablocks `gmm`,
        differentiable via its custom VJP). No capacity buffers, no
        dropped tokens; compute is exactly sum_e n_e * d * f.

        Replicated-expert meshes only, by a real constraint rather than
        a TODO: expert-parallel dropless needs a RAGGED all-to-all
        (per-destination token counts are data-dependent), which XLA's
        `all_to_all` does not expose — every static-shape EP exchange
        necessarily reintroduces a capacity bound — `dispatch=
        'dropless_ep'` is exactly that hybrid. On GSPMD expert-sharded
        meshes without the explicit exchange, dispatch='einsum' remains
        the static a2a pattern SPMD can partition."""
        from ..parallel.moe_ep import _grouped_mlp
        n_tokens, dim = x_flat.shape
        probs, w_up, w_down = self._router_and_weights(x_flat)
        round_experts, round_gates = self._route(probs)            # [k, N]

        assignment_expert = round_experts.reshape(-1)              # [N*k]
        assignment_gate = round_gates.reshape(-1)                  # [N*k]
        assignment_token = jnp.tile(jnp.arange(n_tokens), self.top_k)

        order = jnp.argsort(assignment_expert, stable=True)
        token_sorted = assignment_token[order]
        group_sizes = jnp.bincount(assignment_expert,
                                   length=self.num_experts).astype(jnp.int32)

        x_sorted = x_flat[token_sorted].astype(self.dtype)         # [N*k, D]
        y = _grouped_mlp(x_sorted, w_up, w_down, group_sizes, self.dtype)

        out = jnp.zeros((n_tokens, dim), jnp.float32)
        out = out.at[token_sorted].add(
            y * assignment_gate[order][:, None])
        return out.astype(self.dtype)

    def _dropless_ep_moe(self, x_flat: jax.Array) -> jax.Array:
        """Expert-parallel dropless hybrid: routing and parameters are
        declared here (identical tree to the other modes); the
        capacity-bounded shard exchange + per-shard grouped matmul live
        in `parallel.moe_ep.ep_dropless_moe`. The aux loss comes back
        from the exchange (densities pmean'd over all tokens) and is
        sown under the same name as the other modes."""
        from ..parallel.moe_ep import ep_dropless_moe
        if self.mesh is None:
            raise ValueError("dispatch='dropless_ep' needs the mesh "
                             "(MoEMLP(mesh=...)); use 'dropless' for "
                             "replicated-expert training")
        probs, w_up, w_down = self._router_and_weights(x_flat)
        out, aux = ep_dropless_moe(
            x_flat, probs, w_up, w_down, mesh=self.mesh,
            num_experts=self.num_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor, axis=self.expert_axis,
            token_axes=self.token_axes, dtype=self.dtype)
        self.sow("losses", "moe_aux", aux)
        return out

    def _sorted_moe(self, x_flat: jax.Array, capacity: int) -> jax.Array:
        """Sorted dispatch: identical routing/keep decisions to the
        einsum path (stable sort preserves token order within an expert,
        so slot positions match the cumulative-sum assignment), but the
        buffers are O(N): tokens scatter into per-expert [E*C, D] slabs
        by computed destination index and gather back out.
        """
        n_tokens, dim = x_flat.shape
        probs, w_up, w_down = self._router_and_weights(x_flat)
        round_experts, round_gates = self._route(probs)            # [k, N]

        expert_counts = jnp.zeros((self.num_experts,), jnp.int32)
        # The per-round slot offsets (expert_counts) make the
        # destinations disjoint, so all rounds share ONE slab and the
        # expert MLP runs once.
        slab = jnp.zeros((self.num_experts * capacity, dim), self.dtype)
        rounds = []
        for expert_index, gate in zip(round_experts, round_gates):
            order = jnp.argsort(expert_index, stable=True)
            idx_sorted = expert_index[order]
            # first sorted position of each expert's group
            starts = jnp.searchsorted(idx_sorted, jnp.arange(self.num_experts))
            pos_in_expert = (jnp.arange(n_tokens) - starts[idx_sorted]
                             + expert_counts[idx_sorted])
            keep = pos_in_expert < capacity
            # OOB destination for dropped tokens; scatter mode='drop'
            # discards them, gather mode='fill' zeroes them.
            dest = jnp.where(keep, idx_sorted * capacity + pos_in_expert,
                             self.num_experts * capacity)
            slab = slab.at[dest].set(x_flat[order].astype(self.dtype),
                                     mode="drop")
            rounds.append((order, dest, gate[order] * keep))

            expert_counts = expert_counts + jnp.bincount(
                jnp.where(keep, idx_sorted, self.num_experts),
                length=self.num_experts + 1)[:-1].astype(jnp.int32)

        # Routing record for tests/debugging (cf. the einsum path's
        # 'dispatch' sow): destinations per round, stacked [top_k, N].
        self.sow("intermediates", "dispatch_dest",
                 jnp.stack([dest for _, dest, _ in rounds]))

        h = jnp.einsum("ecd,edf->ecf",
                       slab.reshape(self.num_experts, capacity, dim),
                       w_up.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        flat_out = expert_out.reshape(self.num_experts * capacity, dim)

        out = jnp.zeros((n_tokens, dim), jnp.float32)
        for order, dest, gate_kept in rounds:
            y_sorted = flat_out.at[dest].get(
                mode="fill", fill_value=0).astype(jnp.float32)
            out = out + (y_sorted * gate_kept[:, None])[jnp.argsort(order)]
        return out.astype(self.dtype)


# ----------------------------------------------------------------------
# The one expert layer of the decode / paged steps (and of the
# full-sequence forward of a `n_routed > 0` config): functions over raw
# parameters. Dropless: every assignment that lands on an expert held
# HERE is computed, sorted by expert, through a grouped matrix product;
# an assignment to an expert held elsewhere contributes nothing (its
# chip adds that part). Two routers share it: the softmax router of
# MoEMLP above (gelu experts, all held) and the sigmoid group-limited
# router with a correction bias (gated-silu experts, a held range, a
# shared expert).
# ----------------------------------------------------------------------
def softmax_route(logits: jax.Array, top_k: int
                  ) -> tp.Tuple[jax.Array, jax.Array]:
    """MoEMLP's rule (`parallel.moe_ep._topk_route`): f32 softmax, per
    round the best unused expert at its raw probability. Returns
    (ids [N, k], gates [N, k])."""
    from ..parallel.moe_ep import _topk_route
    probs = jax.nn.softmax(logits, axis=-1)
    ids, gates, _ = _topk_route(probs, probs.shape[-1], top_k)
    return ids.T, gates.T


def sigmoid_group_route(logits: jax.Array, bias: jax.Array, *, top_k: int,
                        n_group: int, topk_group: int, scale: float
                        ) -> tp.Tuple[jax.Array, jax.Array]:
    """Sigmoid scores s; choice scores s + bias; the experts in
    `n_group` equal groups, a group's score the sum of its two best
    choice scores; the `topk_group` best groups stay; the `top_k` best
    choice scores among them win (ties to the lower index, groups and
    experts alike). Gates are s (not s + bias) at the winners, divided
    by their sum, times `scale`. f32. Returns (ids [N, k], gates)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores + bias.astype(jnp.float32)
    tokens, experts = scores.shape
    grouped = choice.reshape(tokens, n_group, experts // n_group)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, topk_group)            # [N, g]
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf)
    _, ids = jax.lax.top_k(masked.reshape(tokens, experts), top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * scale
    return ids, gates


def _tile(size: int, most: int) -> int:
    """The largest tile of whole 128-lane columns up to `most` that
    divides `size` (the whole dimension when none does): 1,024 for the
    widths that are powers of two times a small odd number, 896 for an
    expert 2,688 = 21 x 128 wide, where a power of two leaves 128."""
    for tile in range(most - most % 128, 0, -128):
        if size % tile == 0:
            return tile
    return size


def _grouped_matmul(rows: jax.Array, w: tp.Any, group_sizes: jax.Array,
                    row_group: jax.Array, dtype) -> jax.Array:
    """rows [m, K], sorted by group, times w [G, K, N] group by group
    (f32 out). Rows past sum(group_sizes) belong to no group: their
    output is unspecified and the caller masks it. On a TPU the Pallas
    megablox `gmm` (visits only (group, row tile) pairs that exist);
    elsewhere XLA's `ragged_dot`. A quantized w ({"q", "scale"}) gives
    its int8 payload to the product and its per-output-channel scale to
    the result, row by row."""
    rhs, scale = (w["q"], w["scale"]) if is_quantized(w) else (w, None)
    rhs = rhs.astype(dtype)
    m = rows.shape[0]
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        tm = min(128, -(-m // 16) * 16)
        pad = (-m) % tm
        if pad:
            rows = jnp.concatenate(
                [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)])
        out = megablox.gmm(rows, rhs, group_sizes, jnp.float32,
                           (tm, _tile(rhs.shape[1], 1024),
                            _tile(rhs.shape[2], 1024)))[:m]
    else:
        out = jax.lax.ragged_dot(rows, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * jnp.take(scale[:, 0, :], row_group, axis=0)
    return out


def routed_experts(x_flat: jax.Array, ids: jax.Array, gates: jax.Array,
                   w_up: tp.Any, w_down: tp.Any, *, first: int,
                   act: str, dtype
                   ) -> tp.Tuple[jax.Array, tp.Tuple[jax.Array, jax.Array]]:
    """sum_k gate_k expert_k(x) over the experts held here.

    `ids`, `gates` [N, k] are the router's picks over ALL experts;
    `w_up` [count, D, F or 2F] and `w_down` [count, F, D] are experts
    `first .. first + count - 1` (D the width of `x_flat`: the hidden
    state, or the latent the experts live in). Assignments sort by
    expert (those to experts held elsewhere last, outside every group),
    run the two grouped products and return to their tokens. `act`:
    'silu' = the up product is [gate | value] and the hidden
    silu(gate) * value; 'gelu' and 'relu2' are not gated: gelu(up),
    relu(up)^2. Returns (y [N, D] f32, (assignments that landed here,
    held experts that got at least one))."""
    tokens, top_k = ids.shape
    count = (w_up["q"] if is_quantized(w_up) else w_up).shape[0]
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
        jnp.int32)
    landed = jnp.sum(group_sizes)
    row_group = jnp.minimum(key[order], count - 1)
    rows = x_flat[order // top_k].astype(dtype)
    up = _grouped_matmul(rows, w_up, group_sizes, row_group, dtype)
    if act == "silu":
        gate, value = jnp.split(up, 2, axis=-1)
        hidden = jax.nn.silu(gate) * value
    elif act == "relu2":
        hidden = jnp.square(jax.nn.relu(up))
    elif act == "gelu":
        hidden = jax.nn.gelu(up)
    else:
        raise ValueError(f"an expert's activation is 'silu' (gated), "
                         f"'relu2' or 'gelu', got {act!r}")
    y = _grouped_matmul(hidden.astype(dtype), w_down, group_sizes,
                        row_group, dtype)
    y = jnp.where((jnp.arange(y.shape[0]) < landed)[:, None], y, 0.0)
    y = y[jnp.argsort(order)].reshape(tokens, top_k, -1)
    out = jnp.sum(y * gates.astype(jnp.float32)[:, :, None], axis=1)
    return out, (landed, jnp.sum(group_sizes > 0))


def expert_layer(cfg, mp: tp.Dict, x: jax.Array
                 ) -> tp.Tuple[jax.Array, tp.Tuple[jax.Array, jax.Array]]:
    """The block's expert layer on pre-normed x [B, T, D]: router,
    routed experts held here, shared expert. `mp` is MoEMLP's tree
    (`cfg.moe_experts`: softmax router, gelu experts, all held) or
    ExpertMLP's (`cfg.n_routed`: sigmoid group-limited router, experts
    `cfg.held_experts` of activation `cfg.expert_act`, `cfg.n_shared`
    shared). With `latent_down` / `latent_up` leaves (`cfg.
    expert_latent`) the routed experts live in a latent: ONE
    down-projection before the dispatch, one up-projection after the
    weighted sum of this chip's experts; the router and the shared
    expert read the full hidden state. Returns (y [B, T, D] in
    cfg.dtype, (assignments, experts hit))."""
    batch, seq, dim = x.shape
    x_flat = x.reshape(batch * seq, dim)
    with jax.named_scope("router"):
        logits = (x_flat.astype(jnp.float32)
                  @ mp["router"]["kernel"].astype(jnp.float32))
        if cfg.n_routed > 0:
            ids, gates = sigmoid_group_route(
                logits, mp["router_bias"], top_k=cfg.expert_top_k,
                n_group=cfg.expert_groups, topk_group=cfg.expert_topk_groups,
                scale=cfg.expert_scale)
        else:
            ids, gates = softmax_route(logits, cfg.moe_top_k)
    act = cfg.expert_act if cfg.n_routed > 0 else "gelu"
    rows = x_flat
    if "latent_down" in mp:
        with jax.named_scope("latent_down"):
            rows = jnp.dot(x_flat.astype(cfg.dtype),
                           mp["latent_down"]["kernel"].astype(cfg.dtype))
    with jax.named_scope("experts"):
        out, stats = routed_experts(
            rows, ids, gates, mp["w_up"], mp["w_down"],
            first=cfg.held_experts[0] if cfg.n_routed > 0 else 0,
            act=act, dtype=cfg.dtype)
    if "latent_up" in mp:
        with jax.named_scope("latent_up"):
            out = jnp.dot(out.astype(cfg.dtype),
                          mp["latent_up"]["kernel"].astype(cfg.dtype))
    out = out.reshape(batch, seq, dim).astype(cfg.dtype)
    if "shared" in mp:
        with jax.named_scope("shared_expert"):
            shared = relu2_mlp if act == "relu2" else gated_mlp
            out = out + shared(mp["shared"], x, cfg.dtype)
    return out, stats


def gated_mlp(mp: tp.Dict, normed: jax.Array, dtype) -> jax.Array:
    """SwiGLU MLP on pre-normed input from the raw `up` [D, 2F] (gate |
    value) and `down` [F, D] kernels (quantized kernels supported): the
    dense block's MLP and the shared expert."""
    up_w, up_s = kernel_operand(mp["up"]["kernel"], dtype)
    up = postscale(jnp.einsum("btd,df->btf", normed, up_w), up_s)
    gate, value = jnp.split(up, 2, axis=-1)
    down_w, down_s = kernel_operand(mp["down"]["kernel"], dtype)
    return postscale(
        jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * value, down_w),
        down_s)


def relu2_mlp(mp: tp.Dict, normed: jax.Array, dtype) -> jax.Array:
    """down(relu(up x)^2), not gated, from the raw `up` [D, F] and
    `down` [F, D] kernels: the shared expert of an `expert_act='relu2'`
    config."""
    up = jnp.einsum("btd,df->btf", normed, mp["up"]["kernel"].astype(dtype))
    return jnp.einsum("btf,fd->btd", jnp.square(jax.nn.relu(up)),
                      mp["down"]["kernel"].astype(dtype))


class Leaf(nn.Module):
    """One named parameter leaf, `<module name>/<leaf>`, for modules
    whose arithmetic is a function over the raw tree."""

    @nn.compact
    def __call__(self, leaf, init, shape, dtype):
        return self.param(leaf, init, shape, dtype)


class GatedLeaves(nn.Module):
    """The `up` [D, 2F] (gate | value) and `down` [F, D] kernels of a
    gated MLP as a raw tree (`gated_mlp` reads it); `up` [D, F] where
    it is not gated (`relu2_mlp`)."""

    @nn.compact
    def __call__(self, dim: int, hidden: int, dtype, gated: bool = True):
        dense = nn.initializers.lecun_normal()
        return {"up": {"kernel": Leaf(name="up")(
                    "kernel", dense, (dim, (1 + gated) * hidden), dtype)},
                "down": {"kernel": Leaf(name="down")(
                    "kernel", dense, (hidden, dim), dtype)}}


class ExpertMLP(nn.Module):
    """The expert layer of a `n_routed > 0` config as a Flax module: it
    declares the parameters (`router/kernel` [D, n_routed],
    `router_bias` [n_routed], `w_up` [count, D, 2F], `w_down`
    [count, F, D], `shared/{up,down}/kernel`; with `expert_latent` L
    the experts' D is L and `latent_down/kernel` [D, L],
    `latent_up/kernel` [L, D] stand around them; `expert_act='relu2'`
    halves every `up`) and calls `expert_layer`, the same function the
    decode and paged steps call."""

    config: tp.Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        first, count = cfg.held_experts
        count = count or cfg.n_routed  # count 0: every expert is held
        width, pd = cfg.expert_hidden, cfg.param_dtype
        gated = cfg.expert_act == "silu"
        inner = cfg.expert_latent or cfg.dim  # what an expert reads
        if not (0 <= first and first + count <= cfg.n_routed
                and cfg.n_routed % cfg.expert_groups == 0):
            raise ValueError(
                f"held_experts {cfg.held_experts} must lie inside the "
                f"{cfg.n_routed} routed experts, which {cfg.expert_groups} "
                f"groups must divide")
        per_expert = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", batch_axis=(0,))
        dense = nn.initializers.lecun_normal()
        mp = {
            "router": {"kernel": Leaf(name="router")(
                "kernel", dense, (cfg.dim, cfg.n_routed), pd)},
            # zeros as published; a learned balance term in deployment:
            # drawn small so that it takes part in the choice
            "router_bias": self.param(
                "router_bias", nn.initializers.normal(0.01),
                (cfg.n_routed,), jnp.float32),
            "w_up": self.param("w_up", per_expert,
                               (count, inner, (1 + gated) * width), pd),
            "w_down": self.param("w_down", per_expert,
                                 (count, width, inner), pd),
        }
        if cfg.expert_latent:
            mp["latent_down"] = {"kernel": Leaf(name="latent_down")(
                "kernel", dense, (cfg.dim, inner), pd)}
            mp["latent_up"] = {"kernel": Leaf(name="latent_up")(
                "kernel", dense, (inner, cfg.dim), pd)}
        if cfg.n_shared:
            mp["shared"] = GatedLeaves(name="shared")(
                cfg.dim, cfg.shared_hidden or width * cfg.n_shared, pd,
                gated)
        return expert_layer(cfg, mp, x)[0]
