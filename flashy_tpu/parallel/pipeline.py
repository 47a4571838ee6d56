# Pipeline parallelism: microbatch streaming over the mesh's 'pipe'
# axis. Beyond reference parity (SURVEY §2.3: PP absent there), built
# the shard_map way: every pipeline stage is one slice of the 'pipe'
# axis holding its layers' parameters (a leading stacked dim), and
# activations hop stage-to-stage with `lax.ppermute` — a neighbor
# transfer that rides ICI. Two schedule families live here:
#
# * `pipeline` — the classic GPipe fill-drain, differentiated as one
#   `lax.scan`, kept as the REFERENCE ORACLE: with S stages and M
#   microbatches its bubble fraction is (S-1)/(M+S-1), but every
#   microbatch's activations live until the backward pass — peak
#   residency O(M), capping exactly the knob that shrinks the bubble.
# * `pipeline_1f1b` — PipeDream-flush (1F1B) with optional interleaved
#   virtual stages: an explicit per-tick forward/backward program driven
#   by host-generated schedule tables (flashy_tpu.parallel.schedules),
#   recompute-based VJP stage steps with a fixed O(S)-deep activation
#   stash ring per device, and `interleave=v` non-adjacent layer chunks
#   per device shrinking the bubble to (S-1)/(v*M+S-1). Gradients match
#   the GPipe oracle to f32 allclose (summation order differs); the
#   whole schedule is one fixed-shape jit program — the tick index is
#   data, never a shape.
"""GPipe + 1F1B/interleaved pipeline schedules over the 'pipe' mesh axis."""
import functools
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience import chaos
from jax.sharding import Mesh, PartitionSpec as P

from .schedules import (PACKED_FORWARD_ERROR, PipelineSchedule,
                        build_1f1b_schedule, ring_perms,
                        validate_pipeline_args)


def _stage_body(stage_fn, params, x_micro, axis, num_stages, num_micro,
                has_aux):
    """Per-device schedule; runs under shard_map with `axis` bound.

    x_micro: [M, mb, ...] microbatched input (replicated over `axis`).
    Returns (outputs [1, M, mb, ...], aux [1]): only the LAST stage's
    output leg holds the pipeline's result; aux is this stage's summed
    auxiliary scalar over its valid (stage, microbatch) ticks.
    """
    stage = jax.lax.axis_index(axis)
    perm, _ = ring_perms(num_stages)
    ticks = num_micro + num_stages - 1

    # The input is replicated over the pipe axis but everything computed
    # from the (stage-varying) params is device-varying; mark the whole
    # dataflow varying up front so the scan carry types are stable.
    x_micro = jax.lax.pcast(x_micro, (axis,), to="varying")
    zero = jnp.zeros_like(x_micro[0])
    outputs0 = jnp.zeros_like(x_micro)

    def tick(carry, t):
        incoming, outputs, aux_sum = carry
        # Stage 0 injects microbatch t (clamped; masked when t >= M).
        fresh = jax.lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, num_micro - 1), keepdims=False)
        x_in = jnp.where(stage == 0, fresh, incoming)
        if has_aux:
            y, aux = stage_fn(params, x_in)
            # Stage s works on microbatch t - s at tick t; count its aux
            # only when that microbatch index is real (fill/drain ticks
            # run on garbage activations).
            micro_index = t - stage
            valid = jnp.logical_and(micro_index >= 0, micro_index < num_micro)
            aux_sum = aux_sum + jnp.where(valid, aux.astype(jnp.float32), 0.0)
        else:
            y = stage_fn(params, x_in)
        # Last stage banks its result at output slot t - (S-1).
        slot = t - (num_stages - 1)
        write = jnp.logical_and(stage == num_stages - 1, slot >= 0)
        outputs = jax.lax.cond(
            write,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(slot, 0), 0),
            lambda o: o, outputs)
        # Ship activations one hop down the ring.
        incoming = jax.lax.ppermute(y, axis, perm)
        return (incoming, outputs, aux_sum), None

    aux0 = jax.lax.pcast(jnp.zeros(()), (axis,), to="varying")
    (_, outputs, aux_sum), _ = jax.lax.scan(
        tick, (zero, outputs0, aux0), jnp.arange(ticks))
    return outputs[None], aux_sum[None]  # leading stage dim for P(axis)


def pipeline(stage_fn: tp.Callable, stage_params: tp.Any, x: jax.Array, *,
             mesh: tp.Optional[Mesh] = None, axis: str = "pipe",
             num_microbatches: tp.Optional[int] = None,
             has_aux: bool = False):
    """Run a shape-preserving stage function as a GPipe pipeline.

    Args:
        stage_fn: `(params_slice, activations) -> activations`, SAME
            input/output shape (e.g. a stack of transformer blocks).
            With `has_aux=True`: `-> (activations, aux_scalar)`; the
            scalars are summed over every stage and microbatch and
            returned alongside the output (MoE load-balancing losses).
        stage_params: pytree whose leaves have a leading `num_stages`
            dim; stage s uses `leaf[s]`. Shard with `P('pipe', ...)`.
        x: the batch [B, ...], replicated over the 'pipe' axis.
        num_microbatches: how finely to split B (must divide it);
            defaults to the number of stages.

    Returns activations after all stages (shape of `x`), or
    `(activations, aux_total)` with `has_aux=True`.

    Differentiable: the whole schedule is lax.scan + ppermute, so
    jax.grad pipelines the backward in reverse automatically — at the
    cost of O(M) live activations. For O(S) activation memory and
    sub-GPipe bubbles see :func:`pipeline_1f1b`.
    """
    from .mesh import default_mesh
    mesh = mesh or default_mesh()
    num_stages = mesh.shape[axis]
    if num_stages == 1:
        # Degenerate single-stage pipeline: apply the only stage.
        only = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return stage_fn(only, x)
    num_micro = num_microbatches or num_stages
    batch = x.shape[0]
    # Validate up front (divisibility with actionable alternatives)
    # instead of failing mid-reshape deep inside the schedule build.
    validate_pipeline_args(num_stages, num_micro, batch)
    x_micro = x.reshape(num_micro, batch // num_micro, *x.shape[1:])

    body = functools.partial(_stage_body, axis=axis, num_stages=num_stages,
                             num_micro=num_micro, has_aux=has_aux)

    # params sharded on their stacked leading dim; input replicated over
    # 'pipe'. Output comes back stacked over stages; the last stage's
    # slice is the pipeline result, the aux scalars sum over stages.
    out_stacked, aux_stacked = jax.shard_map(
        lambda params, xm: body(
            stage_fn, jax.tree_util.tree_map(lambda p: p[0], params), xm),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis)),
    )(stage_params, x_micro)
    out = out_stacked[-1]  # [M, mb, ...] from the final stage
    out = out.reshape(batch, *x.shape[1:])
    if has_aux:
        return out, aux_stacked.sum()
    return out


# ---------------------------------------------------------------------------
# 1F1B + interleaved virtual stages
# ---------------------------------------------------------------------------

def _check_chunk_params(stage_params: tp.Any, num_chunks: int,
                        interleave: int, num_stages: int) -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(stage_params):
        shape = np.shape(leaf)
        if not shape or shape[0] != num_chunks:
            name = jax.tree_util.keystr(path)
            raise ValueError(
                f"pipeline_1f1b stage_params leaves need a leading "
                f"[num_stages*interleave]={num_chunks} chunk dim "
                f"(S={num_stages}, interleave={interleave}); leaf "
                f"{name} has shape {shape}. Restack the layer params "
                f"into {num_chunks} equal chunks (chunk c = layers "
                f"[c*L/C, (c+1)*L/C)).")


def _to_device_layout(stage_params: tp.Any, num_stages: int,
                      interleave: int) -> tp.Any:
    """[C, ...] chunk-major params -> [S, v, ...]: device d holds the
    NON-ADJACENT chunks {d, d+S, ..., d+(v-1)S} (virtual stages)."""
    def rearrange(a):
        a = a.reshape(interleave, num_stages, *a.shape[1:])
        return jnp.swapaxes(a, 0, 1)

    return jax.tree_util.tree_map(rearrange, stage_params)


def _from_device_layout(tree: tp.Any, num_chunks: int) -> tp.Any:
    """Inverse of `_to_device_layout`: [S, v, ...] -> [C, ...]."""
    def rearrange(a):
        a = jnp.swapaxes(a, 0, 1)
        return a.reshape(num_chunks, *a.shape[2:])

    return jax.tree_util.tree_map(rearrange, tree)


def _tree_index(tree: tp.Any, index) -> tp.Any:
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False),
        tree)


def pipeline_1f1b(stage_fn: tp.Callable, stage_params: tp.Any, x: jax.Array,
                  *, loss_fn: tp.Optional[tp.Callable] = None,
                  loss_params: tp.Any = None, targets: tp.Any = None,
                  mesh: tp.Optional[Mesh] = None, axis: str = "pipe",
                  num_microbatches: tp.Optional[int] = None,
                  interleave: int = 1, has_aux: bool = False,
                  aux_weight: float = 0.0, packed: bool = False,
                  overlap: tp.Optional[bool] = None,
                  _schedule: tp.Optional[PipelineSchedule] = None):
    """Run a stage function under the 1F1B (PipeDream-flush) schedule.

    The schedule is an explicit per-tick program (one `lax.scan` over
    `flashy_tpu.parallel.schedules` tables): each device banks arriving
    activations into a fixed `[stash_depth]` ring buffer, runs at most
    one forward and one backward per tick, and ships activations (+1
    ring hop) and cotangents (-1 ring hop) via `lax.ppermute`. Backward
    steps recompute the stage forward from the stashed INPUT
    (rematerialization), so peak live-activation residency is the ring —
    O(S·mb) at interleave=1, flat in the microbatch count — instead of
    GPipe's O(M·mb). `interleave=v > 1` places v non-adjacent layer
    chunks per device (virtual stages), cutting the bubble fraction to
    (S-1)/(v·M+S-1).

    Args:
        stage_fn: `(chunk_params, activations) -> activations` (or
            `-> (activations, aux_scalar)` with `has_aux=True`), SAME
            input/output shape, applied per virtual-stage chunk.
        stage_params: pytree with a leading `[num_stages*interleave]`
            chunk dim; chunk c holds layers `[c*L/C, (c+1)*L/C)`.
            Shard with `P('pipe', ...)` (the function rearranges chunks
            onto devices round-robin internally).
        x: the batch `[B, ...]`, replicated over the 'pipe' axis.
        loss_fn: `loss_params, final_activations[, targets] -> scalar`
            per-microbatch loss, which MUST be mean-reduced over its
            microbatch (the per-microbatch means average into exactly
            the full-batch mean, the `with_grad_accumulation`
            convention). `None` selects the forward-only schedule
            (inference through the same chunk placement).
        loss_params: pytree of parameters the loss closes over (e.g. the
            LM head); their gradient is returned.
        targets: optional pytree with leading batch dim, microbatched
            alongside `x` and passed per-microbatch to `loss_fn`.
        num_microbatches: M (>= num_stages; a multiple of num_stages
            when interleave > 1). Defaults to num_stages.
        aux_weight: weight of the summed per-(chunk, microbatch) aux
            scalars in the differentiated objective
            `mean_m loss + aux_weight * mean_m (sum_c aux)`.
        packed: co-schedule the steady state's forward and backward
            into one tick (train only): the schedule tables set `f_do`
            and `b_do` together, so the always-both-lanes SPMD body
            does useful work in both lanes and the step shrinks from
            `2(vM+S-1)` to `schedules.packed_ticks(S, M, v)` ticks.
            Gradients are BIT-IDENTICAL to the unpacked schedule (same
            per-microbatch compute, same f32 accumulation order per
            chunk); the in-flight bound grows to ~2S (still O(S), flat
            in M). Requires `loss_fn` — packing is meaningless without
            a backward lane.
        overlap: double-buffer the ring (packed, interleave=1 only):
            each tick's `ppermute` hops are issued from the PREVIOUS
            tick's banked outputs and their results banked after this
            tick's stage compute, so on backends with async collectives
            the hop latency hides under the stage matmuls. Costs one
            extra latency tick per hop in the schedule
            (`M + 4(S-1)` total). Default `None` resolves to True on
            tpu/gpu backends (whose async start/done collective pairs
            can run under compute) and False on cpu (hops serialize
            regardless, so the extra fill ticks would be a pure loss).

    Returns:
        Forward mode (`loss_fn=None`): the final activations `[B, ...]`
        (`(out, aux_total)` with `has_aux=True` — same convention as
        :func:`pipeline`).
        Training mode: `(loss, grads)` — or `((loss, aux), grads)` with
        `has_aux=True`, both per-microbatch means — where `grads` is
        `{'stage_params': [C, ...], 'loss_params': ..., 'x': [B, ...]}`,
        the full gradient of the objective above, f32-accumulated and
        cast back to the parameter dtypes. Matches
        `jax.grad(loss_fn ∘ pipeline)` to f32 allclose.
    """
    from .mesh import default_mesh
    mesh = mesh or default_mesh()
    num_stages = mesh.shape[axis]
    num_chunks = num_stages * interleave
    mode = "forward" if loss_fn is None else "train"
    if packed and mode == "forward":
        # checked up front (not via validate_pipeline_args, whose other
        # checks need real shapes) so the rejection stays uniform even
        # on the degenerate single-stage path below
        raise ValueError(PACKED_FORWARD_ERROR)
    if overlap is None:
        overlap = default_overlap(packed, interleave, mesh)
    if overlap and not packed:
        raise ValueError("overlap=True double-buffers the PACKED ring; "
                         "pass packed=True as well (the unpacked 1F1B "
                         "tables stay at hop latency 1)")
    _check_chunk_params(stage_params, num_chunks, interleave, num_stages)
    if num_stages == 1:
        return _single_stage_1f1b(stage_fn, stage_params, x, loss_fn,
                                  loss_params, targets, interleave, has_aux,
                                  aux_weight)
    num_micro = num_microbatches or num_stages
    batch = x.shape[0]
    validate_pipeline_args(num_stages, num_micro, batch,
                           interleave=interleave,
                           require_fill=(mode == "train"),
                           schedule="packed_1f1b" if packed else "1f1b",
                           mode=mode)
    if _schedule is not None:
        # Audit hook (tests + flashy_tpu.analysis.trace): drive the
        # jitted body with an EXPLICIT schedule — e.g. a deliberately
        # corrupted tick table — so the FT102 model check's verdict can
        # be cross-examined against the bitwise gradient gate on the
        # same executable. Shape facts must match; the tables need not.
        if (_schedule.num_stages, _schedule.num_micro, _schedule.interleave,
                _schedule.mode) != (num_stages, num_micro, interleave, mode):
            raise ValueError(
                f"_schedule override is for (S={num_stages}, M={num_micro}, "
                f"v={interleave}, mode={mode!r}); got (S="
                f"{_schedule.num_stages}, M={_schedule.num_micro}, "
                f"v={_schedule.interleave}, mode={_schedule.mode!r})")
        schedule = _schedule
    else:
        schedule = build_1f1b_schedule(num_stages, num_micro, interleave,
                                       mode, packed=packed, overlap=overlap)
    # Deterministic host-side fault site: one tick per schedule launch
    # (trace time under jit; every call when driven eagerly). A fault
    # here surfaces as a clean typed failure before any device program
    # runs — never a hang inside the collective schedule.
    chaos.fault_point("pipeline.tick", mode=mode,
                      ticks=schedule.num_ticks)
    if packed:
        # same contract as pipeline.tick, distinct site: chaos drills
        # can target the packed timeline without touching 1f1b runs
        chaos.fault_point("pipeline.packed_tick", mode=mode,
                          ticks=schedule.num_ticks,
                          overlap=bool(overlap))
    x_micro = x.reshape(num_micro, batch // num_micro, *x.shape[1:])
    targets_micro = jax.tree_util.tree_map(
        lambda t: t.reshape(num_micro, t.shape[0] // num_micro,
                            *t.shape[1:]), targets)
    params_dev = _to_device_layout(stage_params, num_stages, interleave)
    tables = {name: jnp.asarray(table)
              for name, table in schedule.tables.items()}

    body = functools.partial(
        _1f1b_device_body, stage_fn=stage_fn, loss_fn=loss_fn, axis=axis,
        schedule=schedule, has_aux=has_aux, aux_weight=aux_weight)

    if mode == "forward":
        out_st, aux_st = jax.shard_map(
            lambda p, xm, cols: body(
                jax.tree_util.tree_map(lambda a: a[0], p), xm, None, None,
                cols),
            mesh=mesh,
            in_specs=(P(axis), P(), {name: P(None, axis) for name in tables}),
            out_specs=(P(axis), P(axis)),
        )(params_dev, x_micro, tables)
        out = out_st[-1][:num_micro].reshape(batch, *x.shape[1:])
        if has_aux:
            return out, aux_st.sum()
        return out

    if loss_params is None:
        loss_params = {}
    gs_st, glp_st, gx_st, loss_st, aux_st = jax.shard_map(
        lambda p, xm, lp, tgt, cols: body(
            jax.tree_util.tree_map(lambda a: a[0], p), xm, lp, tgt, cols),
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(),
                  {name: P(None, axis) for name in tables}),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
    )(params_dev, x_micro, loss_params, targets_micro, tables)

    grads_stage = _from_device_layout(gs_st, num_chunks)
    grads_stage = jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), grads_stage, stage_params)
    # Only the device holding the last chunk accumulated loss-param
    # grads / the loss; everyone else contributed exact zeros.
    grads_lp = jax.tree_util.tree_map(
        lambda g, p: g.sum(axis=0).astype(jnp.asarray(p).dtype),
        glp_st, loss_params)
    grad_x = gx_st[0][:num_micro].reshape(batch, *x.shape[1:]) \
        .astype(x.dtype)
    loss = loss_st.sum() / num_micro
    aux = aux_st.sum() / num_micro
    grads = {"stage_params": grads_stage, "loss_params": grads_lp,
             "x": grad_x}
    if has_aux:
        return (loss, aux), grads
    return loss, grads


def _single_stage_1f1b(stage_fn, stage_params, x, loss_fn, loss_params,
                       targets, interleave, has_aux, aux_weight):
    """Degenerate pipe=1 path: chain the chunks sequentially; training
    mode differentiates the full-batch objective directly (identical by
    the mean-reduction contract on `loss_fn`)."""
    def apply_chunks(params, xx):
        h, aux_total = xx, jnp.zeros((), jnp.float32)
        for c in range(interleave):
            chunk = jax.tree_util.tree_map(lambda a, c=c: a[c], params)
            if has_aux:
                h, aux = stage_fn(chunk, h)
                aux_total = aux_total + aux.astype(jnp.float32)
            else:
                h = stage_fn(chunk, h)
        return h, aux_total

    if loss_fn is None:
        out, aux_total = apply_chunks(stage_params, x)
        return (out, aux_total) if has_aux else out

    if loss_params is None:
        loss_params = {}

    def objective(params, lp, xx):
        h, aux_total = apply_chunks(params, xx)
        loss = loss_fn(lp, h, targets) if targets is not None \
            else loss_fn(lp, h)
        return loss + aux_weight * aux_total, (loss, aux_total)

    (_, (loss, aux)), (gs, glp, gx) = jax.value_and_grad(
        objective, argnums=(0, 1, 2), has_aux=True)(
            stage_params, loss_params, x)
    grads = {"stage_params": gs, "loss_params": glp, "x": gx}
    if has_aux:
        return (loss, aux), grads
    return loss, grads


def _1f1b_device_body(local_params, x_micro, loss_params, targets_micro,
                      cols, *, stage_fn, loss_fn, axis,
                      schedule: PipelineSchedule, has_aux, aux_weight):
    """One device's 1F1B program: a fixed-shape scan over schedule ticks.

    Every tick issues the ring hops FIRST — `ppermute` of the previous
    tick's banked outputs, carried pre-hop so the collective and the
    stage compute share no data edge until the bank point — then banks
    the arrivals into their ring-buffer slots (sentinel row when idle),
    runs one (possibly masked) forward from the stash, and — in
    training mode — one recompute-VJP backward seeded either from the
    arrived cotangent or, on the last chunk, from the loss. At hop
    latency 1 the arrivals bank BEFORE the compute (the steady state
    consumes same-tick arrivals); at hop latency 2 (packed overlap)
    they bank AFTER it, so the hop's result is not needed until the
    tick's very end and the collective can run under the stage matmuls
    on backends with async collective-permute. All indices come from
    the schedule tables as DATA; garbage lanes are routed to sentinel
    rows and zero-masked, never shape-special-cased, so the executable
    is identical for every (tick, device).
    """
    S = schedule.num_stages
    M = schedule.num_micro
    Ds, Db = schedule.stash_depth, schedule.brx_depth
    train = schedule.mode == "train"
    bank_late = schedule.hop_latency > 1
    # latency-2 schedules are packed, and packed is train-only — the
    # forward-mode path below may therefore assume early banking
    assert not (bank_late and not train), \
        "overlap (hop latency 2) schedules are train-only"
    perm_fwd, perm_bwd = ring_perms(S)
    f32 = jnp.float32

    def varying(a):
        if axis in jax.typeof(a).vma:
            return a
        return jax.lax.pcast(a, (axis,), to="varying")

    def pcast_tree(tree):
        return jax.tree_util.tree_map(varying, tree)

    x_micro = pcast_tree(x_micro)
    if train:
        loss_params = pcast_tree(loss_params)
        targets_micro = pcast_tree(targets_micro)
    cols = {name: col.reshape(col.shape[0]) for name, col in cols.items()}

    mb_zero = jnp.zeros_like(x_micro[0])
    act0 = jnp.zeros((Ds + 1,) + mb_zero.shape, mb_zero.dtype) + mb_zero
    # The carry holds the PRE-hop outputs ("y", and "dxm" in train):
    # tick t permutes tick t-1's output itself, so the hop is issued at
    # the top of the body and its result is consumed only at the bank
    # point — before the compute at hop latency 1 (the same dataflow as
    # permuting at the previous tick's end), after it at latency 2.
    carry = {
        "act": act0,
        "y": mb_zero,
        "aux": varying(jnp.zeros((), f32)),
    }
    if train:
        carry.update({
            "brx": jnp.zeros((Db + 1,) + mb_zero.shape, mb_zero.dtype)
                   + mb_zero,
            "dxm": mb_zero,
            "gs": jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, f32) + p * 0, local_params),
            "glp": jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), f32) + p * 0, loss_params),
            "dx": jnp.zeros((M + 1,) + mb_zero.shape, mb_zero.dtype)
                  + mb_zero,
            "loss": varying(jnp.zeros((), f32)),
        })
    else:
        carry["out"] = jnp.zeros((M + 1,) + mb_zero.shape,
                                 mb_zero.dtype) + mb_zero

    def bank_f(act, fmsg, col):
        # bank the arrived activation (sentinel row Ds when idle)
        return jax.lax.dynamic_update_index_in_dim(
            act, fmsg,
            jnp.where(col["rxf_do"] == 1, col["rxf_slot"], Ds), 0)

    def bank_b(brx, bmsg, col):
        # bank the arrived cotangent (sentinel row Db when idle)
        return jax.lax.dynamic_update_index_in_dim(
            brx, bmsg,
            jnp.where(col["rxb_do"] == 1, col["rxb_slot"], Db), 0)

    def tick(carry, col):
        # 1. issue this tick's ring hops from the previous tick's
        #    outputs. At hop latency 2 nothing below reads fmsg/bmsg
        #    until the very end of the body, so an async
        #    collective-permute runs under the whole tick's compute.
        fmsg = jax.lax.ppermute(carry["y"], axis, perm_fwd)
        bmsg = jax.lax.ppermute(carry["dxm"], axis, perm_bwd) if train \
            else None
        act = carry["act"]
        if not bank_late:
            # hop latency 1: the steady state consumes same-tick
            # arrivals, so bank before the compute reads the ring
            act = bank_f(act, fmsg, col)
        # 2. forward: input from the stash ring or the microbatched x
        f_on = col["f_do"] == 1
        x_f = jnp.where(
            col["f_from_x"] == 1,
            jax.lax.dynamic_index_in_dim(x_micro, col["f_micro"],
                                         keepdims=False),
            jax.lax.dynamic_index_in_dim(act, col["f_slot"],
                                         keepdims=False))
        # idle lanes compute on zeros — finite garbage that masking can
        # drop (NaN from stale buffers would survive a 0-mask).
        x_f = jnp.where(f_on, x_f, jnp.zeros_like(x_f))
        act = jax.lax.dynamic_update_index_in_dim(
            act, x_f,
            jnp.where(jnp.logical_and(f_on, col["f_from_x"] == 1),
                      col["f_slot"], Ds), 0)
        p_f = _tree_index(local_params, col["f_chunk"])
        if has_aux:
            y, aux_f = stage_fn(p_f, x_f)
        else:
            y = stage_fn(p_f, x_f)
            aux_f = jnp.zeros((), f32)
        out = {"act": act,
               "aux": carry["aux"] + jnp.where(f_on, aux_f.astype(f32), 0.0),
               "y": y}
        if not train:
            out["out"] = jax.lax.dynamic_update_index_in_dim(
                carry["out"], y,
                jnp.where(jnp.logical_and(f_on, col["f_last"] == 1),
                          col["f_micro"], M), 0)
            return out, None

        # 3. the arrived cotangent (banked now at hop latency 1, at the
        #    end of the tick at latency 2 — the backward then reads the
        #    ring as carried, which the schedule's consumer slack makes
        #    exact)
        brx = carry["brx"] if bank_late else bank_b(carry["brx"], bmsg, col)
        # 4. backward: recompute the chunk forward from the stashed
        #    input and pull (dp, dx) out of one VJP. The loss leg runs
        #    under a cond, so the (potentially head-sized) loss forward
        #    + VJP is paid only on last-chunk ticks — 1/(S·v) of the
        #    backward ticks — not on every tick of every device.
        b_on = col["b_do"] == 1
        is_last = col["b_last"] == 1
        x_b = jax.lax.dynamic_index_in_dim(out["act"], col["b_slot"],
                                           keepdims=False)
        x_b = jnp.where(b_on, x_b, jnp.zeros_like(x_b))
        p_b = _tree_index(local_params, col["b_chunk"])
        tgt_b = _tree_index(targets_micro, col["b_micro"])

        def stage_only(p, xx):
            if has_aux:
                return stage_fn(p, xx)
            return stage_fn(p, xx), varying(jnp.zeros((), f32))

        (h_b, aux_b), vjp_stage = jax.vjp(stage_only, p_b, x_b)

        def loss_leg(operands):
            lp, h, tgt = operands

            def lfn(lp_, h_):
                return loss_fn(lp_, h_, tgt) if targets_micro is not None \
                    else loss_fn(lp_, h_)

            loss_val, vjp_loss = jax.vjp(lfn, lp, h)
            # the loss is device-varying (lp and h are), so its
            # cotangent seed must carry the same varying-axes type
            dlp_, dy_ = vjp_loss(varying(
                jnp.full((), 1.0 / M, loss_val.dtype)))
            return loss_val.astype(f32), dy_, dlp_

        def no_loss_leg(operands):
            # both cond branches must agree on varying-ness: fresh zeros
            # are invariant until marked
            lp, h, _ = operands
            return pcast_tree((
                jnp.zeros((), f32), jnp.zeros_like(h),
                jax.tree_util.tree_map(
                    lambda a: jnp.zeros(jnp.shape(a),
                                        jnp.asarray(a).dtype), lp)))

        loss_b, dy_loss, dlp = jax.lax.cond(
            jnp.logical_and(b_on, is_last), loss_leg, no_loss_leg,
            (loss_params, h_b, tgt_b))
        dy = jax.lax.dynamic_index_in_dim(brx, col["b_rx"], keepdims=False)
        dy_ct = jnp.where(is_last, dy_loss, dy.astype(h_b.dtype))
        daux_ct = jnp.where(b_on, aux_weight / M, 0.0).astype(aux_b.dtype)
        dp, dx = vjp_stage((dy_ct, daux_ct))
        dp = jax.tree_util.tree_map(
            lambda g: jnp.where(b_on, g, jnp.zeros_like(g)), dp)
        dx = jnp.where(b_on, dx, jnp.zeros_like(dx))
        # accumulate dp into its chunk row (masked dp is exact zeros, so
        # the idle-lane write at row 0 is `row += 0` — a no-op)
        cur = _tree_index(carry["gs"], col["b_chunk"])
        out["gs"] = jax.tree_util.tree_map(
            lambda a, c, g: jax.lax.dynamic_update_index_in_dim(
                a, c + g.astype(f32), col["b_chunk"], 0),
            carry["gs"], cur, dp)
        # dlp and loss_b are exact zeros off the cond's taken branch —
        # the (b_on & is_last) gate already ran
        out["glp"] = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(f32), carry["glp"], dlp)
        out["loss"] = carry["loss"] + loss_b
        out["dx"] = jax.lax.dynamic_update_index_in_dim(
            carry["dx"], dx.astype(carry["dx"].dtype),
            jnp.where(jnp.logical_and(b_on, col["b_first"] == 1),
                      col["b_micro"], M), 0)
        if bank_late:
            # hop latency 2: the hop results were not needed by any
            # compute above — bank them for consumers at tick t+1 on
            out["act"] = bank_f(out["act"], fmsg, col)
            brx = bank_b(brx, bmsg, col)
        out["brx"] = brx
        out["dxm"] = dx
        return out, None

    carry, _ = jax.lax.scan(tick, carry, cols)
    if train:
        return (jax.tree_util.tree_map(lambda a: a[None], carry["gs"]),
                jax.tree_util.tree_map(lambda a: a[None], carry["glp"]),
                carry["dx"][None], carry["loss"][None], carry["aux"][None])
    return carry["out"][None], carry["aux"][None]


def default_overlap(packed: bool, interleave: int = 1,
                    mesh: tp.Optional[Mesh] = None) -> bool:
    """The `overlap=None` resolution of :func:`pipeline_1f1b`: packed
    ring double-buffering pays off only where async collective-permute
    exists (tpu/gpu) and only at interleave=1 (see
    `schedules.build_1f1b_schedule`). The decision keys off the
    platform of the mesh the pipeline actually runs on (a CPU
    virtual-device mesh on a GPU host must NOT pay the latency-2 fill),
    falling back to the default backend when no mesh is given.
    Exported so stats reporters can name the exact schedule the
    executable will run."""
    if not packed or interleave != 1:
        return False
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    return platform in ("tpu", "gpu")


# ---------------------------------------------------------------------------
# Measurement harness: `python -m flashy_tpu.parallel.pipeline` (`make
# pipeline-demo`) runs this — GPipe vs 1F1B vs interleaved
# vs packed 1F1B on a small (MoE) LM over a virtual-device 'pipe' mesh.
# Gates: 1F1B gradients allclose to the GPipe oracle (MoE aux
# included), packed gradients BIT-identical to unpacked at equal
# (S, M, v), packed step_ms strictly below unpacked, the stash ring
# flat in M while GPipe's residency grows, interleaved bubble strictly
# below GPipe at equal M, zero post-warm-up recompiles.
# ---------------------------------------------------------------------------

def _pipeline_leg(*, moe: bool, mesh, pipe: int, steps: int, num_micro: int,
                  interleave: int, dim: int, num_layers: int, num_heads: int,
                  vocab_size: int, seq: int, batch: int, watchdog
                  ) -> tp.Dict[str, tp.Any]:
    """One model's worth of schedule measurement: GPipe vs 1F1B vs
    interleaved-1F1B grad steps, timed and drift-gated.

    The oracle is the differentiated GPipe pipeline itself (``oracle``
    in the record names it).
    """
    import time

    from ..models import TransformerConfig, TransformerLM
    from ..models.pipelined import pipelined_value_and_grad
    from ..observability import get_telemetry
    from .schedules import (gpipe_bubble_fraction, gpipe_stash_bytes,
                            schedule_stats)

    aux_weight = 0.01 if moe else 0.0
    cfg = TransformerConfig(
        vocab_size=vocab_size, dim=dim, num_layers=num_layers,
        num_heads=num_heads, attention="dense", scan_layers=True,
        moe_experts=4 if moe else 0, moe_top_k=2 if moe else 1,
        moe_capacity_factor=8.0)
    model = TransformerLM(cfg)
    variables = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    rng = np.random.default_rng(0)
    batches = [jnp.asarray(rng.integers(0, vocab_size, (batch, seq)),
                           jnp.int32) for _ in range(max(steps, 2))]
    mb_shape = (batch // num_micro, seq, dim)
    tag = "moe" if moe else "dense"

    legs = {
        "gpipe": dict(schedule="gpipe", interleave=1),
        "1f1b": dict(schedule="1f1b", interleave=1),
        f"1f1b-int{interleave}": dict(schedule="1f1b",
                                      interleave=interleave),
        "packed_1f1b": dict(schedule="packed_1f1b", interleave=1),
        f"packed_1f1b-int{interleave}": dict(schedule="packed_1f1b",
                                             interleave=interleave),
    }
    # packed legs must be bit-identical to their unpacked twin at
    # equal (S, M, v) — same per-microbatch compute, same f32
    # accumulation order — and strictly faster (fewer ticks, same
    # per-tick cost: the SPMD body always pays both lanes)
    packed_pairs = {
        "packed_1f1b": "1f1b",
        f"packed_1f1b-int{interleave}": f"1f1b-int{interleave}",
    }
    leg: tp.Dict[str, tp.Any] = {"moe": moe, "oracle": "gpipe",
                                 "schedules": {}}
    grads_by_leg: tp.Dict[str, tp.Any] = {}
    loss_by_leg: tp.Dict[str, float] = {}
    telemetry = get_telemetry()
    for name, spec in legs.items():
        packed = spec["schedule"] == "packed_1f1b"
        grad_fn = pipelined_value_and_grad(
            model, mesh=mesh, num_microbatches=num_micro,
            interleave=spec["interleave"], schedule=spec["schedule"],
            aux_weight=aux_weight)
        step_fn = watchdog.watch(jax.jit(grad_fn),
                                 name=f"pipeline:{tag}:{name}")
        if spec["schedule"] == "gpipe":
            stats = {
                "schedule": "gpipe", "num_stages": pipe,
                "num_micro": num_micro, "interleave": 1,
                "bubble_frac": round(
                    gpipe_bubble_fraction(pipe, num_micro), 6),
                "peak_stash_bytes": gpipe_stash_bytes(
                    pipe, num_micro, mb_shape),
            }
            loss, grads = step_fn(variables, batches[0])
        else:
            overlap = default_overlap(packed, spec["interleave"], mesh)
            stats = schedule_stats(
                pipe, num_micro, spec["interleave"], packed=packed,
                overlap=overlap, microbatch_shape=mb_shape)
            # FT104's scalar: the FLOP-priced idle-lane fraction (the
            # SPMD body pays both lanes every tick; masked lanes are
            # real matmuls on zeros). Packing exists to narrow this —
            # the demo gate tracks it.
            from ..analysis.trace.dead_compute import dead_compute_stats
            from .schedules import build_1f1b_schedule
            stats["dead_compute_frac"] = round(dead_compute_stats(
                build_1f1b_schedule(pipe, num_micro, spec["interleave"],
                                    packed=packed, overlap=overlap)
            )["dead_frac"], 6)
            loss, grads = step_fn(variables, batches[0])
        jax.block_until_ready(loss)  # compile + warm step done
        grads_by_leg[name] = jax.tree_util.tree_map(np.asarray, grads)
        loss_by_leg[name] = float(loss)
        begin = time.perf_counter()
        for index in range(steps):
            loss, grads = step_fn(variables, batches[index % len(batches)])
        jax.block_until_ready(loss)
        stats["step_ms"] = round(
            (time.perf_counter() - begin) / steps * 1e3, 2)
        if name != "gpipe":
            ref = grads_by_leg["gpipe"]
            drift = max(
                float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-8))
                for a, b in zip(jax.tree_util.tree_leaves(grads_by_leg[name]),
                                jax.tree_util.tree_leaves(ref)))
            stats["grad_drift"] = drift
            stats["loss_delta"] = abs(loss_by_leg[name]
                                      - loss_by_leg["gpipe"])
        if name in packed_pairs:
            twin = packed_pairs[name]
            stats["grads_bitwise_vs_unpacked"] = bool(
                loss_by_leg[name] == loss_by_leg[twin] and all(
                    np.array_equal(a, b) for a, b in zip(
                        jax.tree_util.tree_leaves(grads_by_leg[name]),
                        jax.tree_util.tree_leaves(grads_by_leg[twin]))))
            stats["step_ms_vs_unpacked"] = round(
                stats["step_ms"]
                / max(leg["schedules"][twin]["step_ms"], 1e-9), 4)
        if telemetry is not None and "idle_ticks_per_device" in stats:
            telemetry.counter("pipeline/bubble",
                              idle_ticks_per_device=float(
                                  stats["idle_ticks_per_device"]),
                              bubble_frac=float(stats["bubble_frac"]))
            telemetry.record({"type": "pipeline_schedule", "leg": tag,
                              **{k: v for k, v in stats.items()
                                 if not isinstance(v, dict)}})
        leg["schedules"][name] = stats

    # tick_efficiency: realized step_ms / the schedule-theoretic tick
    # bound (num_ticks x per-tick cost). The calibration is the
    # unpacked 1f1b leg AT THE SAME interleave — per-tick cost depends
    # on the chunk size (v chunks of L/vS layers), but not on packing
    # (the SPMD body pays both lanes every tick either way). 1.0 = the
    # tick count fully explains the wall clock; a packed leg above 1.0
    # quantifies the counted-vs-realized gap this metric exists to
    # track. GPipe's differentiated scan executes the same 2(M+S-1)
    # tick-equivalents as unpacked 1f1b, so it calibrates against it.
    per_tick_ms = {}
    for name, stats in leg["schedules"].items():
        if name.startswith("1f1b") and stats.get("step_ms") \
                and stats.get("num_ticks"):
            per_tick_ms[stats["interleave"]] = (stats["step_ms"]
                                                / stats["num_ticks"])
    for name, stats in leg["schedules"].items():
        ticks = stats.get("num_ticks") or (
            2 * (num_micro + pipe - 1) if name == "gpipe" else None)
        cal = per_tick_ms.get(stats.get("interleave"))
        if ticks and cal and stats.get("step_ms"):
            stats["tick_efficiency"] = round(
                stats["step_ms"] / (ticks * cal), 4)
    return leg


def run_pipeline_bench(steps: int = 3, *, num_micro: int = 8,
                       interleave: int = 2, dim: int = 48,
                       num_layers: int = 8, num_heads: int = 4,
                       vocab_size: int = 128, seq: int = 24,
                       batch: int = 16, moe: bool = True,
                       pipe: tp.Optional[int] = None
                       ) -> tp.Dict[str, tp.Any]:
    """Measure the five pipeline schedules on dense and MoE LMs.

    Returns a record with per-schedule ``bubble_frac``,
    ``peak_stash_bytes``, ``step_ms``, ``grad_drift`` (vs the GPipe
    oracle; MoE aux in the objective on the ``moe`` leg) and
    ``tick_efficiency`` (realized step_ms over the schedule-theoretic
    tick bound, per-tick cost calibrated on the unpacked 1f1b leg),
    plus ``grads_bitwise_vs_unpacked`` / ``step_ms_vs_unpacked`` on the
    packed legs, ``recompiles`` (watchdog total past warm-up) and the
    stash-flatness probe (the 1F1B ring at M vs 2M microbatches against
    GPipe's O(M) growth).
    """
    from ..observability import RecompileWatchdog
    from .mesh import make_mesh
    from .schedules import gpipe_stash_bytes, schedule_stats

    n_devices = len(jax.devices())
    pipe = pipe or (4 if n_devices % 4 == 0 else 2)
    if n_devices % pipe:
        raise ValueError(
            f"pipeline bench needs a device count divisible by pipe={pipe} "
            f"(got {n_devices}); run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU.")
    mesh = make_mesh({"pipe": pipe, "data": -1})
    watchdog = RecompileWatchdog(warmup=1)
    common = dict(mesh=mesh, pipe=pipe, steps=steps, num_micro=num_micro,
                  interleave=interleave, dim=dim, num_layers=num_layers,
                  num_heads=num_heads, vocab_size=vocab_size, seq=seq,
                  batch=batch, watchdog=watchdog)
    mb_shape = (batch // num_micro, seq, dim)
    result: tp.Dict[str, tp.Any] = {
        "n_devices": n_devices, "pipe": pipe, "num_micro": num_micro,
        "interleave": interleave, "batch": batch, "seq": seq,
        "dense": _pipeline_leg(moe=False, **common),
    }
    if moe:
        result["moe"] = _pipeline_leg(moe=True, **common)

    # Memory flatness probe: the 1F1B ring at M vs 2M (static, exact),
    # GPipe's residency bound at the same points.
    stash_m = schedule_stats(pipe, num_micro, 1, microbatch_shape=mb_shape)
    stash_2m = schedule_stats(pipe, 2 * num_micro, 1,
                              microbatch_shape=mb_shape)
    result["stash_bytes_at_m"] = stash_m["peak_stash_bytes"]
    result["stash_bytes_at_2m"] = stash_2m["peak_stash_bytes"]
    result["gpipe_stash_bytes_at_m"] = gpipe_stash_bytes(
        pipe, num_micro, mb_shape)
    result["gpipe_stash_bytes_at_2m"] = gpipe_stash_bytes(
        pipe, 2 * num_micro, mb_shape)
    result["stash_flat_in_m"] = (result["stash_bytes_at_2m"]
                                 == result["stash_bytes_at_m"])

    # tensor x pipe composition probe (the 3D-mesh claim, kept cheap):
    # the same 1F1B schedule with the stage weights ALSO column-split
    # over 'tensor' must reproduce the pipe-only run's loss and grads —
    # the megatron partial-sum reduction and the stage ppermute ring
    # compose in one jit, or this delta says where they stopped.
    if n_devices % 4 == 0:
        from jax.sharding import NamedSharding

        tmesh = make_mesh({"pipe": 2, "tensor": 2, "data": -1})
        w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16),
                                    jnp.float32)
        x = jnp.ones((4, 16), jnp.float32)

        def _compose_run(spec: P) -> tp.Tuple[float, tp.List[np.ndarray]]:
            params = jax.device_put({"w": w}, NamedSharding(tmesh, spec))
            loss, grads = pipeline_1f1b(
                lambda p, h: jnp.tanh(h @ p["w"]), params, x,
                loss_fn=lambda lp, h: (h ** 2).mean(), mesh=tmesh,
                num_microbatches=2)
            return (float(loss),
                    [np.asarray(g)
                     for g in jax.tree_util.tree_leaves(grads)])

        base_loss, base_grads = _compose_run(P("pipe"))
        tp_loss, tp_grads = _compose_run(P("pipe", None, "tensor"))
        grad_delta = max(float(np.max(np.abs(a - b)))
                         for a, b in zip(tp_grads, base_grads))
        result["tensor_compose"] = {
            "ok": bool(tp_loss == base_loss and grad_delta < 1e-6),
            "loss_delta": abs(tp_loss - base_loss),
            "grad_delta": grad_delta,
        }

    result["recompiles"] = sum(watchdog.summary().values())
    return result


def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    """`python -m flashy_tpu.parallel.pipeline [--steps N]`: run the
    five-schedule measurement and print one JSON line; exit 1 when the
    1F1B gradients drift from the GPipe oracle, the packed gradients
    are not bit-identical to unpacked 1F1B at equal (S, M, v), packed
    realized step_ms is not strictly below unpacked, the stash ring
    grows with M, the interleaved bubble does not beat GPipe at equal
    M, or any post-warm-up recompile was reported."""
    import argparse
    import json
    import sys
    import tempfile

    parser = argparse.ArgumentParser(
        prog="python -m flashy_tpu.parallel.pipeline",
        description="GPipe vs 1F1B vs interleaved-1F1B schedule bench.")
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--micro", type=int, default=8,
                        help="microbatches per step (M)")
    parser.add_argument("--interleave", type=int, default=2)
    parser.add_argument("--seq", type=int, default=24)
    parser.add_argument("--no-moe", action="store_true",
                        help="drop the MoE blocks (pure dense LM)")
    args = parser.parse_args(argv)

    from ..observability import enable_telemetry, disable_telemetry

    with tempfile.TemporaryDirectory() as tmp:
        telemetry = enable_telemetry(folder=tmp)
        try:
            result = run_pipeline_bench(
                steps=args.steps, num_micro=args.micro,
                interleave=args.interleave, seq=args.seq,
                moe=not args.no_moe)
            trace = telemetry.export().read_text()
            jsonl = (telemetry.tracer.jsonl_path.read_text()
                     if telemetry.tracer.jsonl_path.exists() else "")
            result["bubble_track_recorded"] = (
                "pipeline/bubble" in trace
                and "pipeline_schedule" in jsonl)
        finally:
            disable_telemetry()

    print(json.dumps(result), flush=True)
    problems = []
    if result["recompiles"]:
        problems.append(f"{result['recompiles']} post-warm-up recompiles")
    for tag in ("dense", "moe"):
        leg = result.get(tag)
        if leg is None:
            continue
        gpipe = leg["schedules"]["gpipe"]
        for name, stats in leg["schedules"].items():
            if name == "gpipe":
                continue
            if stats["grad_drift"] > 1e-2:
                problems.append(
                    f"{tag}/{name} gradients drifted "
                    f"{stats['grad_drift']:.2e} from the "
                    f"{leg['oracle']} oracle")
            if not name.startswith("packed") and \
                    stats["interleave"] >= 2 and \
                    stats["bubble_frac"] >= gpipe["bubble_frac"]:
                problems.append(
                    f"{tag}/{name} bubble {stats['bubble_frac']} did not "
                    f"improve on GPipe's {gpipe['bubble_frac']} at equal M")
            if name.startswith("packed"):
                twin = leg["schedules"].get(name.replace("packed_", ""))
                if twin and not (stats.get("dead_compute_frac", 1.0)
                                 < twin.get("dead_compute_frac", 0.0)):
                    problems.append(
                        f"{tag}/{name} dead-compute fraction "
                        f"{stats.get('dead_compute_frac')} is not below "
                        f"the unpacked schedule's "
                        f"{twin.get('dead_compute_frac')} — packing "
                        f"stopped narrowing the masked-lane waste")
                if not stats.get("grads_bitwise_vs_unpacked"):
                    problems.append(
                        f"{tag}/{name} gradients are not bit-identical "
                        f"to the unpacked schedule at equal (S, M, v)")
                if not stats.get("step_ms_vs_unpacked", 2.0) < 1.0:
                    problems.append(
                        f"{tag}/{name} realized step_ms did not beat the "
                        f"unpacked schedule: ratio "
                        f"{stats.get('step_ms_vs_unpacked')}")
                if "tick_efficiency" not in stats:
                    problems.append(
                        f"{tag}/{name} tick_efficiency missing (bench "
                        f"bookkeeping bug)")
    if not result["stash_flat_in_m"]:
        problems.append(
            f"1F1B stash grew with M: {result['stash_bytes_at_m']} -> "
            f"{result['stash_bytes_at_2m']} bytes (expected flat)")
    if result["gpipe_stash_bytes_at_2m"] <= result["gpipe_stash_bytes_at_m"]:
        problems.append("GPipe residency bound failed to grow with M "
                        "(bench bookkeeping bug)")
    if not result["bubble_track_recorded"]:
        problems.append("pipeline/bubble counter track missing from "
                        "telemetry.jsonl")
    compose = result.get("tensor_compose")
    if compose is not None and not compose["ok"]:
        problems.append(
            f"tensor x pipe composition diverged from the pipe-only "
            f"run: loss delta {compose['loss_delta']:.2e}, grad delta "
            f"{compose['grad_delta']:.2e}")
    for problem in problems:
        print(f"pipeline bench FAILED: {problem}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
