# Expert-parallel dropless MoE: the hybrid of the two dispatch worlds.
#
# Pure dropless (models/moe.py `_dropless_moe`) cannot be expert-sharded
# as-is: per-destination token counts are data-dependent and XLA's
# `all_to_all` has no ragged form, so any static-shape exchange must
# bound tokens-per-destination. This module makes that bound explicit —
# a capacity-bounded all-to-all BETWEEN expert shards (Switch-style
# overflow drop at the shard granularity, looser than per-expert
# capacity: a hot expert borrows slack from its shard siblings) — while
# the compute ON each shard stays dropless: received tokens sort by
# local expert and run through the megablocks grouped matmul (`gmm`), so
# no FLOPs are spent on capacity padding, only wire bytes.
#
# Layout (inside one shard_map over the mesh):
#   tokens  sharded over (token_axes..., axis)  — every device owns a slice
#   router  replicated
#   w_up/w_down sharded over `axis` dim 0       — E_local experts per shard
#
# Exchange: [e, C, D] send buffers, `lax.all_to_all` over `axis` (rides
# ICI within each expert-shard group), results return by the mirror
# all_to_all and combine at the source with the gates.
"""Expert-parallel dropless MoE via capacity-bounded a2a + grouped matmul."""
import typing as tp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _topk_route(probs: jax.Array, num_experts: int, top_k: int):
    """Sequential top-k argmax routing (the moe.MoEMLP._route rule,
    functional): per round each token takes its best unused expert at
    the raw softmax probability. Returns (expert_ids [k, N], gates
    [k, N], hard_density [E] — local mean of one-hot picks)."""
    remaining = probs
    hard_density = jnp.zeros((num_experts,), jnp.float32)
    ids, gates = [], []
    for _ in range(top_k):
        expert_index = jnp.argmax(remaining, axis=-1)               # [N]
        gate = jnp.take_along_axis(
            remaining, expert_index[:, None], axis=-1)[:, 0]
        one_hot = jax.nn.one_hot(expert_index, num_experts)
        hard_density = hard_density + jnp.mean(one_hot, axis=0)
        ids.append(expert_index)
        gates.append(gate)
        remaining = remaining * (1.0 - one_hot)
    return jnp.stack(ids), jnp.stack(gates), hard_density


def _grouped_mlp(xs: jax.Array, w_up: jax.Array, w_down: jax.Array,
                 group_sizes: jax.Array, dtype) -> jax.Array:
    """gelu-MLP over expert-sorted rows via megablocks gmm (both
    projections grouped; pads the row dim to the 128 tile, extra rows
    joining the last group — zeros in, zeros out)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, dim = xs.shape
    hidden = w_up.shape[-1]
    m_pad = (-m) % 128
    if m_pad:
        xs = jnp.concatenate([xs, jnp.zeros((m_pad, dim), xs.dtype)], axis=0)
        group_sizes = group_sizes.at[-1].add(m_pad)

    def tile(size: int) -> int:
        for candidate in (128, 64, 32, 16, 8, 4, 2, 1):
            if size % candidate == 0:
                return candidate
        return 1

    interpret = jax.default_backend() == "cpu"
    h = jax.nn.gelu(megablox.gmm(
        xs, w_up.astype(dtype), group_sizes, jnp.float32,
        (128, tile(dim), tile(hidden)), interpret=interpret).astype(dtype))
    return megablox.gmm(
        h, w_down.astype(dtype), group_sizes, jnp.float32,
        (128, tile(hidden), tile(dim)), interpret=interpret)[:m]


def ep_dropless_moe(x_flat: jax.Array, probs: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, *, mesh: Mesh, num_experts: int,
                    top_k: int = 1, capacity_factor: float = 1.25,
                    axis: str = "expert",
                    token_axes: tp.Sequence[str] = ("data",),
                    dtype=jnp.bfloat16) -> tp.Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE MLP over globally-[N, D] tokens.

    Arguments are GLOBAL arrays inside an enclosing jit: `x_flat` [N, D]
    and the router softmax `probs` [N, E] (both resharded over
    `(token_axes..., axis)` on entry — routing itself is plain
    matmul+softmax, so it is computed OUTSIDE the shard_map by the
    caller and partitions like any dense layer), `w_up` [E, D, F] /
    `w_down` [E, F, D] sharded over `axis` on dim 0
    (E % mesh.shape[axis] == 0 required). Returns
    `(out [N, D], aux)` — `aux` is the Switch load-balancing loss
    (eq. 4, E * sum_e density_e * hard_density_e / k) with densities
    averaged over ALL tokens via pmean, so it equals the replicated
    computation exactly.

    Per-(source, destination-shard) capacity is
    `ceil(capacity_factor * top_k * N_local / e)`: assignments beyond it
    pass through with zero expert contribution (Switch overflow
    behavior, at shard granularity).
    """
    e = mesh.shape[axis]
    if num_experts % e:
        raise ValueError(f"num_experts={num_experts} not divisible by "
                         f"mesh axis {axis!r} of size {e}")
    e_local = num_experts // e
    all_axes = tuple(token_axes) + (axis,)

    def local_fn(x_loc, probs_loc, w_up_loc, w_down_loc):
        n_loc, dim = x_loc.shape
        capacity = max(1, -(-int(capacity_factor * top_k * n_loc) // e))

        expert_ids, gates, hard_density = _topk_route(
            probs_loc, num_experts, top_k)
        # Global (all-token) densities: the aux loss must not depend on
        # how tokens are sharded.
        density = jax.lax.pmean(jnp.mean(probs_loc, axis=0), all_axes)
        hard_density = jax.lax.pmean(hard_density, all_axes)
        aux = num_experts * jnp.sum(density * hard_density / top_k)

        assignment_expert = expert_ids.reshape(-1)                  # [k*n]
        assignment_gate = gates.reshape(-1)                         # [k*n]
        assignment_token = jnp.tile(jnp.arange(n_loc), top_k)       # [k*n]
        dest_shard = assignment_expert // e_local                   # [k*n]

        # Slot within the destination shard's buffer: running count of
        # assignments to each destination, first-come-first-served in
        # (round, token) order.
        dest_one_hot = jax.nn.one_hot(dest_shard, e, dtype=jnp.int32)
        position = (jnp.cumsum(dest_one_hot, axis=0) - 1)           # [k*n, e]
        slot = jnp.take_along_axis(
            position, dest_shard[:, None], axis=-1)[:, 0]           # [k*n]
        keep = slot < capacity
        flat_dest = jnp.where(keep, dest_shard * capacity + slot,
                              e * capacity)                         # OOB=drop

        send_x = jnp.zeros((e * capacity, dim), dtype).at[flat_dest].set(
            x_loc[assignment_token].astype(dtype), mode="drop")
        # Local-expert id per slot; sentinel e_local marks empty slots.
        send_eid = jnp.full((e * capacity,), e_local, jnp.int32).at[
            flat_dest].set((assignment_expert % e_local).astype(jnp.int32),
                           mode="drop")

        recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0,
                                    concat_axis=0, tiled=True)
        recv_eid = jax.lax.all_to_all(send_eid, axis, split_axis=0,
                                      concat_axis=0, tiled=True)

        # Dropless compute on the local expert slab: sort by local
        # expert, grouped matmul, unsort. Empty (sentinel) slots hold
        # zero rows — fold them into the last real group (zeros in,
        # zeros out) so group_sizes matches the slab's e_local groups.
        group_eid = jnp.minimum(recv_eid, e_local - 1)
        order = jnp.argsort(recv_eid, stable=True)
        xs = recv_x[order]
        group_sizes = jnp.bincount(group_eid[order],
                                   length=e_local).astype(jnp.int32)
        ys = _grouped_mlp(xs, w_up_loc, w_down_loc, group_sizes, dtype)
        y = jnp.zeros_like(ys).at[order].set(ys)                    # unsort

        back_x = jax.lax.all_to_all(y.astype(dtype), axis, split_axis=0,
                                    concat_axis=0, tiled=True)

        # Combine at the source: each kept assignment reads its slot
        # back and scales by its gate; dropped assignments add zero.
        y_assign = back_x.at[flat_dest].get(
            mode="fill", fill_value=0).astype(jnp.float32)          # [k*n, D]
        out = jnp.zeros((n_loc, dim), jnp.float32).at[assignment_token].add(
            y_assign * (assignment_gate * keep)[:, None])
        return out.astype(dtype), aux[None]

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(all_axes, None), P(all_axes, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(all_axes, None), P(all_axes)),
        check_vma=False,  # pallas gmm cannot propagate varying-axis types
    )(x_flat, probs, w_up, w_down)
    # every shard returned the same pmean'd aux; take one
    return out, aux[0]
