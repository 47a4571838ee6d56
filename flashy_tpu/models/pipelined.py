# Pipeline-parallel execution of a scan-stacked TransformerLM: the
# block stack (params with leading [num_layers] dim, see
# TransformerConfig.scan_layers) is split into `pipe` stages; embedding
# and head replicate while activations stream through the stages under
# a selectable schedule — GPipe fill-drain (the differentiable
# reference), 1F1B/interleaved (flashy_tpu.parallel.pipeline's
# explicit forward/backward program: O(stages) activation memory and a
# bubble divided by the interleave factor), or packed 1F1B (training
# only: F and B co-scheduled into one tick, ~halving the step's ticks
# with bit-identical gradients).
"""pipelined_apply / pipelined_value_and_grad: scan-stacked TransformerLM
over the 'pipe' axis under GPipe or 1F1B schedules."""
import typing as tp

import jax
import jax.numpy as jnp

from ..parallel.schedules import KNOWN_SCHEDULES as SCHEDULES
from .transformer import Block, TransformerLM, rmsnorm as _rmsnorm


def _chunked_stage(model: TransformerLM, variables: tp.Mapping,
                   num_chunks: int):
    """Mesh-independent stage plumbing: the per-chunk stage function and
    the [num_chunks, layers_per_chunk, ...] stacked block params."""
    cfg = model.config
    if cfg.layer_pattern:
        raise ValueError("pipelined_apply stacks one block body: the layers "
                         "of a layer_pattern differ in kind and parameters "
                         "and are not pipelined")
    if not cfg.scan_layers:
        raise ValueError("pipelined_apply needs TransformerConfig.scan_layers=True")
    if cfg.attn_kind != "mha" or cfg.n_routed > 0 or not cfg.tie_head:
        raise ValueError("pipelined_apply stacks one MHA block body: latent "
                         "attention, n_routed expert layers and an untied "
                         "head are not pipelined")
    layers_per_chunk = cfg.num_layers // num_chunks
    moe = cfg.moe_experts > 0

    block_params = variables["params"]["blocks"]["block"]  # stacked [L, ...]
    stage_params = jax.tree_util.tree_map(
        lambda a: a.reshape(num_chunks, layers_per_chunk, *a.shape[1:]),
        block_params)

    block = Block(cfg)

    def stage_fn(local_params, h):
        # h: [mb, T, D]; local_params leaves: [layers_per_chunk, ...]
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32)[None, :], h.shape[:2])

        def body(carry, layer_params):
            if moe:
                out, mutated = block.apply(
                    {"params": layer_params}, carry, positions,
                    mutable=["losses"])
                from .moe import moe_aux_loss
                return out, moe_aux_loss(mutated)
            out = block.apply({"params": layer_params}, carry, positions)
            return out, None

        h, aux = jax.lax.scan(body, h, local_params)
        if moe:
            return h, jnp.sum(aux)
        return h

    return stage_fn, stage_params, moe


def _pipe_setup(model: TransformerLM, variables: tp.Mapping, mesh,
                interleave: int):
    """Mesh-aware stage plumbing: validate the layer split against the
    'pipe' axis and build the chunked stage function."""
    cfg = model.config
    from ..parallel.mesh import default_mesh
    mesh = mesh or default_mesh()
    num_stages = mesh.shape["pipe"]
    num_chunks = num_stages * interleave
    if interleave < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")
    if cfg.num_layers % num_stages:
        raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                         f"pipe={num_stages}")
    if cfg.num_layers % num_chunks:
        layers_per_stage = cfg.num_layers // num_stages
        raise ValueError(
            f"interleave={interleave} must divide the per-device layer "
            f"count: {cfg.num_layers} layers over pipe={num_stages} give "
            f"{layers_per_stage} layers/device, not splittable into "
            f"{interleave} virtual stages. Use interleave in "
            f"{[v for v in range(1, layers_per_stage + 1) if layers_per_stage % v == 0]} "
            f"or change num_layers.")
    stage_fn, stage_params, moe = _chunked_stage(model, variables, num_chunks)
    return mesh, num_stages, stage_fn, stage_params, moe


def _head_logits(x, embedding, norm_scale, cfg):
    """The LM head shared by every schedule path: final rmsnorm + tied
    vocab projection (compute-dtype operands, f32 accumulate — the
    TransformerLM.__call__ scheme the pipe=1 loss-parity tests pin)."""
    x = _rmsnorm(x, norm_scale, cfg.dtype)
    return jnp.einsum("btd,vd->btv", x, embedding.astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def pipelined_apply(model: TransformerLM, variables: tp.Mapping,
                    tokens: jax.Array, *, mesh=None,
                    num_microbatches: tp.Optional[int] = None,
                    schedule: str = "gpipe", interleave: int = 1):
    """Forward a scan-stacked TransformerLM with pipeline parallelism.

    Requirements: `config.scan_layers=True`, `num_layers` divisible by
    the mesh's 'pipe' size (and by pipe*interleave), no dropout
    (eval-mode blocks).

    `schedule='gpipe'` (default) streams the microbatches through the
    fill-drain schedule; gradients flow (wrap in jax.grad) but peak
    activation residency is O(num_microbatches). `schedule='1f1b'`
    routes the forward through the interleaved virtual-stage placement
    of :func:`flashy_tpu.parallel.pipeline_1f1b` — for TRAINING under
    the 1F1B schedule (O(stages) activation memory) use
    :func:`pipelined_value_and_grad` instead, which runs forward and
    backward in one interleaved program.

    Returns logits, or `(logits, moe_aux)` for MoE models: the sown
    per-layer load-balancing losses are summed inside each pipeline
    stage and across microbatches, then averaged over microbatches —
    each microbatch computes its own router densities, so the value is
    the mean of per-microbatch aux losses rather than the single
    full-batch aux of the unpipelined path (same estimator, averaged
    over smaller token sets; the expert *outputs* are unaffected).
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, "
                         f"got {schedule!r}")
    if schedule == "packed_1f1b":
        raise ValueError(
            "schedule='packed_1f1b' has no forward-only spelling: packing "
            "pairs each forward tick with a backward, which is meaningless "
            "without a backward lane. Use schedule='1f1b' for pipelined "
            "forwards, or pipelined_value_and_grad(schedule='packed_1f1b') "
            "for training.")
    if schedule == "gpipe" and interleave != 1:
        raise ValueError(
            "interleave>1 (virtual stages) is a 1F1B-family feature; "
            "GPipe streams each device's layers as one stage. Use "
            "schedule='1f1b' (or pipelined_value_and_grad for training).")
    cfg = model.config
    mesh, num_stages, stage_fn, stage_params, moe = _pipe_setup(
        model, variables, mesh, interleave)
    params = variables["params"]
    embedding = params["embed"]
    x = jnp.take(embedding, tokens, axis=0).astype(cfg.dtype)

    if schedule == "gpipe":
        from ..parallel.pipeline import pipeline
        result = pipeline(stage_fn, stage_params, x, mesh=mesh,
                          num_microbatches=num_microbatches, has_aux=moe)
    else:
        from ..parallel.pipeline import pipeline_1f1b
        result = pipeline_1f1b(stage_fn, stage_params, x, mesh=mesh,
                               num_microbatches=num_microbatches,
                               interleave=interleave, has_aux=moe)
    if moe:
        x, aux_sum = result
        num_micro = num_microbatches or num_stages
        if num_stages == 1:
            num_micro = 1  # degenerate path runs the full batch at once
        aux = aux_sum / num_micro
    else:
        x = result

    logits = _head_logits(x, embedding, params["norm_f"]["scale"], cfg)
    if moe:
        return logits, aux
    return logits


def sequential_value_and_grad(model: TransformerLM, *,
                              num_microbatches: int,
                              aux_weight: float = 0.0) -> tp.Callable:
    """Per-microbatch sequential reference grad-fn (no shard_map).

    Chains the whole layer stack microbatch by microbatch and averages
    the per-microbatch CE (+ aux) — the exact gradient estimator both
    pipeline schedules compute, spelled without any collective, so it
    runs on a single device and differentiates on every jax version.
    This is the triangulation oracle for the schedule tests, and the
    demo's fallback when `jax.grad` through the GPipe shard_map rejects
    the MoE stage body (pre-existing on jax < 0.5: the legacy shard_map
    transpose `_SpecError`s on the sown-losses block — the exact
    training path `pipelined_value_and_grad(schedule='1f1b')` restores,
    since its VJP is explicit and never transposes a shard_map).
    """
    cfg = model.config
    moe = cfg.moe_experts > 0
    M = num_microbatches

    def objective(variables: tp.Mapping, tokens: jax.Array):
        import optax
        stage_fn, stage_params, _ = _chunked_stage(model, variables, 1)
        chunk = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        params = variables["params"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        xm = x.reshape(M, x.shape[0] // M, *x.shape[1:])
        tm = tokens.reshape(M, tokens.shape[0] // M, tokens.shape[1])
        ce_total, aux_total = 0.0, 0.0
        for m in range(M):
            if moe:
                h, aux = stage_fn(chunk, xm[m])
                aux_total = aux_total + aux
            else:
                h = stage_fn(chunk, xm[m])
            logits = _head_logits(h, params["embed"],
                                  params["norm_f"]["scale"], cfg)
            ce_total = ce_total + \
                optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], tm[m][:, 1:]).mean()
        return ce_total / M + aux_weight * aux_total / M

    return jax.value_and_grad(objective)


def pipelined_value_and_grad(model: TransformerLM, *, mesh=None,
                             num_microbatches: tp.Optional[int] = None,
                             interleave: int = 1, schedule: str = "1f1b",
                             aux_weight: float = 0.0,
                             overlap: tp.Optional[bool] = None
                             ) -> tp.Callable:
    """Build a pipelined LM training grad-fn in the
    `jax.value_and_grad` convention: `fn(variables, tokens) -> (loss,
    grads)` with `loss = ce + aux_weight * moe_aux` and `grads`
    matching the `variables` pytree.

    `schedule='1f1b'` runs the one-forward-one-backward interleaved
    program of :func:`flashy_tpu.parallel.pipeline_1f1b`: activations
    stashed in a fixed O(stages) ring (recompute-VJP backward), the
    embedding gradient assembled from both its uses (the input lookup
    via the returned d/dx, the tied head via the loss-parameter
    gradient). `schedule='packed_1f1b'` co-schedules the steady
    state's F and B into one tick — `schedules.packed_ticks(S, M, v)`
    total instead of `2(vM+S-1)`, gradients bit-identical to '1f1b' —
    and `overlap` (default: auto, on for tpu/gpu at interleave=1)
    double-buffers the ring so the `ppermute` hops hide under stage
    compute. `schedule='gpipe'` is `jax.value_and_grad` over
    :func:`pipelined_apply` — the differentiation-of-the-scan oracle
    the 1F1B gradients are gated against.

    The signature composes with the rest of the parallel stack:
    `with_grad_accumulation(pipelined_value_and_grad(model, ...), k)`
    accumulates whole pipeline flushes, and `zero_update(grad_fn, opt)`
    reduce-scatters the returned gradient once per step — after the
    last backward tick, not per microbatch.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, "
                         f"got {schedule!r}")
    cfg = model.config
    moe = cfg.moe_experts > 0

    if schedule == "gpipe":
        def loss_fn(variables, tokens):
            import optax
            out = pipelined_apply(model, variables, tokens, mesh=mesh,
                                  num_microbatches=num_microbatches)
            logits, aux = out if moe else (out, 0.0)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()
            return ce + aux_weight * aux if moe else ce

        return jax.value_and_grad(loss_fn)

    def grad_fn(variables: tp.Mapping, tokens: jax.Array):
        import optax
        from ..parallel.pipeline import pipeline_1f1b
        pipe_mesh, num_stages, stage_fn, stage_params, _ = _pipe_setup(
            model, variables, mesh, interleave)
        params = variables["params"]
        embedding = params["embed"]
        x = jnp.take(embedding, tokens, axis=0).astype(cfg.dtype)
        loss_params = {"embed": embedding,
                       "norm_scale": params["norm_f"]["scale"]}

        def micro_loss(lp, h, tokens_micro):
            logits = _head_logits(h, lp["embed"], lp["norm_scale"], cfg)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens_micro[:, 1:]).mean()

        result = pipeline_1f1b(
            stage_fn, stage_params, x, loss_fn=micro_loss,
            loss_params=loss_params, targets=tokens, mesh=pipe_mesh,
            num_microbatches=num_microbatches, interleave=interleave,
            has_aux=moe, aux_weight=aux_weight if moe else 0.0,
            packed=(schedule == "packed_1f1b"), overlap=overlap)
        if moe:
            (ce, aux), grads = result
            loss = ce + aux_weight * aux
        else:
            ce, grads = result
            loss = ce
        # Reassemble the variables-shaped gradient. The embedding is
        # used twice — the input lookup and the tied head — so its
        # gradient is the head leg plus the scatter-add of d/dx over
        # the token ids (the VJP of jnp.take).
        d_blocks = jax.tree_util.tree_map(
            lambda g, p: g.reshape(p.shape),
            grads["stage_params"], params["blocks"]["block"])
        d_embed = grads["loss_params"]["embed"] + \
            jnp.zeros_like(embedding).at[tokens].add(
                grads["x"].astype(embedding.dtype))
        g_vars = {"params": {
            "embed": d_embed,
            "blocks": {"block": d_blocks},
            "norm_f": {"scale": grads["loss_params"]["norm_scale"]},
        }}
        return loss, g_vars

    return grad_fn
