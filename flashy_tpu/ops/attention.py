# Attention ops. The reference has no attention (it is model-agnostic,
# SURVEY §5 long-context: absent); flashy_tpu ships it because the
# north-star workload (Transformer LM solver, BASELINE.json configs[4])
# needs a TPU-efficient attention path:
#
#  * `dot_product_attention` — plain XLA implementation; correct
#    everywhere, O(T^2) memory. XLA already fuses the softmax chain.
#  * `flash_attention` — pallas TPU kernels: tiles Q/K/V blocks through
#    VMEM with the online-softmax recurrence so the TxT score matrix
#    never hits HBM, in forward AND backward. The forward kernel also
#    emits the per-row logsumexp; the backward recomputes P blockwise
#    from it. Two backward spellings share every tile formula:
#      - fused (default): ONE kernel sweeps (k-tile, q-tile) once,
#        accumulating dK/dV in VMEM and a head's whole float32 dQ in
#        VMEM beside them ([T_q, D], written back once a head) — each
#        Q/K/V/dO block is read from HBM once;
#      - split (the oracle): two kernels (dQ with k-tiles innermost;
#        dK/dV with q-tiles innermost), reading everything twice.
#    Both are O(T) in sequence memory — the FlashAttention-2
#    decomposition, laid out for the MXU — and bit-identical to each
#    other (tests pin it), so the split path doubles as the
#    interpret-mode oracle for the fused one.
#
# The schedule — what a grid step holds, fetches and masks — lives here
# and nowhere else, a rule over the call's shapes (`flash_schedule`). No
# environment variable, file or process-wide cache decides which kernel
# compiles.
#  * Tiles: a call's `block_q` / `block_k` arguments (how parity tests
#    and `tools/flash_sweep.py` reach other tilings), else `TILES`, the
#    winners of a sweep on the v5e at [8, 2048, 16, 128] causal bf16
#    (PERF.md section 6, PR 36): forward 2048 x 2048, 1.56 ms a call
#    (256 x 256, every run through PR 35: 7.3), backward 1024 x 1024,
#    3.10 ms (10.8 with its fold). Every call states `VMEM_LIMIT`.
#  * A grid step past the diagonal names the block the step before it
#    held, so the pipeline copies nothing for it.
#  * Every visited causal tile is masked and guarded, under the diagonal
#    too: masking only where the diagonal crosses is a second tile body
#    that loses 0.4 ms a step at `TILES` (PERF.md section 6, PR 36).
#
# Array convention: [batch, time, heads, head_dim] (flax-style).
# The logsumexp rows are carried broadcast across a 128-wide lane dim
# ([BH, T, 128]) — the layout the public TPU kernels use, native to the
# f32 vector tile.
"""Attention: XLA reference implementation + pallas flash kernel."""
import functools
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _guarded_probs(scores: jax.Array, ref: jax.Array) -> jax.Array:
    """exp(scores - ref) with fully-masked rows forced to zero.

    `ref` is a per-row statistic (running max or logsumexp) that sits at
    ~NEG_INF when the row saw no visible key. There exp(scores - ref)
    would be exp(-1e30 - (-1e30)) = exp(0) = 1 — f32 absorbs the log
    term — silently weighting every masked key equally. The convention
    here (shared by forward, backward and the ring fallback) is that a
    query with no visible keys attends to nothing: output and gradients
    are zero.
    """
    return jnp.where(ref > NEG_INF * 0.5, jnp.exp(scores - ref), 0.0)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = False,
                          mask: tp.Optional[jax.Array] = None) -> jax.Array:
    """Plain attention over [B, T, H, D] arrays; scores in f32.

    Queries with no visible key (possible when `causal` with t_k < t_q,
    or under a fully-masked `mask` row) produce zero output — the same
    convention as `flash_attention` — rather than softmax's uniform
    average over masked keys.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        causal_mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        scores = jnp.where(causal_mask[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    probs = _guarded_probs(scores, m)
    denom = jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
    probs = probs / denom
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# pallas flash attention (TPU)
# ---------------------------------------------------------------------------

LANES = 128  # native f32 lane width; row-stat tensors ride it
VMEM_LIMIT = 48 * 2 ** 20  # scoped VMEM every flash call states (v5e: 128 MiB)
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


class FlashSchedule(tp.NamedTuple):
    """What a grid step of one flash kernel holds (`flash_schedule`)."""
    block_q: int     # query rows of a score tile
    block_k: int     # key rows of a score tile
    steps: int       # grid steps of the call
    working: int     # of them, steps that visit a tile
    vmem_bytes: int  # estimate of the call's scoped VMEM


def _causal_visible(qi, ki, block_q: int, block_k: int, offset: int):
    """Whether k-tile `ki` holds any key visible to q-tile `qi`."""
    return ki * block_k <= qi * block_q + block_q - 1 + offset


def _last_visible_k(qi, block_q: int, block_k: int, offset: int):
    """The last k-tile `_causal_visible` to q-tile `qi` (0 if none is)."""
    return jnp.maximum(qi * block_q + block_q - 1 + offset, 0) // block_k


def _first_visible_q(ki, block_q: int, block_k: int, offset: int):
    """The first q-tile to which k-tile `ki` is `_causal_visible`."""
    return jnp.maximum(ki * block_k - offset, 0) // block_q


def _block_scores(q, k, qi, ki, *, scale, causal, block_q, block_k, offset):
    """The masked score tile [block_q, block_k] on the MXU.

    Operands stay in their input dtype (bf16 normally) with f32
    accumulation — the MXU's fast path; a pre-cast to f32 would force
    multi-pass f32 matmuls at a fraction of the bf16 rate.
    """
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        scores = jnp.where(q_pos + offset >= k_pos, scores, NEG_INF)
    return scores


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  offset: int):
    """Forward: one (batch*head, q-tile, k-tile) grid step.

    The TPU grid iterates the last dimension fastest, so for a fixed
    q-tile the k-tiles arrive sequentially and the VMEM scratch
    (running max / normalizer / accumulator) carries the online-softmax
    state across them. Output and the per-row logsumexp (the backward's
    softmax residual) are written on the final k-tile.

    `offset = t_k - t_q` aligns causal masking bottom-right (query i
    attends keys j <= i + offset), matching `dot_product_attention`'s
    tril(k=t_k-t_q) — the self-attention case has offset 0.
    """
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile():
        scores = _block_scores(q_ref[0], k_ref[0], qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)

        # All row statistics stay 2-D [block_q, 1] — the Mosaic-friendly
        # layout (no 1-D vector intermediates).
        m_prev = m_scr[:, :1]                      # [block_q, 1]
        block_max = scores.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        alpha = jnp.exp(m_prev - m_new)
        # _guarded_probs: rows whose running max is still ~NEG_INF have
        # no visible key in any tile so far (mixed q-tiles when offset
        # < 0); exp(scores - m_new) would be exp(0) = 1 there and the
        # row would silently average V over masked keys.
        probs = _guarded_probs(scores, m_new)      # [block_q, block_k]
        l_new = l_scr[:, :1] * alpha + probs.sum(axis=-1, keepdims=True)
        # P cast to V's dtype for the MXU fast path (FA2 practice);
        # the row-sum normalizer above keeps full f32 precision.
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            probs.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # Scratch rows are 128 lanes wide (the native f32 tile); the
        # scalar running stats live broadcast across the lane dim.
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Tiles past the diagonal contribute nothing and are never
        # visited (roughly halves causal attention FLOPs).
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(tile)
    else:
        tile()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)   # [block_q, 1]
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # logsumexp of each score row; rows with no visible key (can only
        # happen for padding layouts) would be -inf, clamp via denom.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dq_scr, *, scale: float, causal: bool, block_q: int,
                     block_k: int, offset: int):
    """Backward dQ: grid (batch*head, q-tile, k-tile), k innermost.

    For a fixed q-tile, k-tiles stream by while the dQ accumulator
    lives in VMEM; P is recomputed from the forward's logsumexp (no TxT
    residual). dS = P * (dP - D) with D = rowsum(dO*O) precomputed.
    """
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile():
        scores = _block_scores(q_ref[0], k_ref[0], qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)
        # Rows with no visible key (offset < 0 cross-attention) carry an
        # lse at the clamp floor; the forward emitted zeros for them and
        # the backward must emit zero grads, not exp(0)-weighted ones.
        probs = _guarded_probs(scores, lse_ref[0, :, :1])
        dp = jax.lax.dot_general(                  # dO V^T [block_q, block_k]
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = probs * (dp - delta_ref[0, :, :1]) * scale
        # dS cast to K's dtype: bf16 operands + f32 accumulation is the
        # MXU fast path; dS itself is an exp-derived quantity with the
        # same dynamic range as P.
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(tile)
    else:
        tile()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _backward_tile(q, k, v, do, lse, delta, qi, ki, dk_scr, dv_scr, *,
                   scale, causal, block_q, block_k, offset):
    """One (q, k) tile of the dK/dV sweep: dV += P^T dO and dK += dS^T Q
    into the VMEM accumulators; returns dS in K's dtype for the caller's
    dQ product. The split dK/dV kernel and the fused kernel share it, op
    for op, so their accumulators march through identical f32 values.

    P / dS are cast to the operand dtype for bf16 MXU passes with f32
    accumulation (same rationale as the forward / dQ kernels).
    """
    scores = _block_scores(q, k, qi, ki, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, offset=offset)
    # Same empty-row guard as _flash_dq_kernel.
    probs = _guarded_probs(scores, lse)            # [block_q, block_k]
    dv_scr[:] = dv_scr[:] + jax.lax.dot_general(   # P^T dO [block_k, D]
        probs.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(                      # dO V^T [block_q, block_k]
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = probs * (dp - delta) * scale
    dk_scr[:] = dk_scr[:] + jax.lax.dot_general(   # dS^T Q [block_k, D]
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return ds.astype(k.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                      causal: bool, block_q: int, block_k: int, offset: int):
    """Backward dK/dV: grid (batch*head, k-tile, q-tile), q innermost.

    For a fixed k-tile, q-tiles stream by accumulating
    dV += P^T dO and dK += dS^T Q in VMEM.
    """
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile():
        _backward_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                       lse_ref[0, :, :1], delta_ref[0, :, :1], qi, ki,
                       dk_scr, dv_scr, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k, offset=offset)

    if causal:
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(tile)
    else:
        tile()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr, *,
                            scale: float, causal: bool, block_q: int,
                            block_k: int, offset: int):
    """Fused backward: grid (batch*head, k-tile, q-tile), q innermost.

    One pass over the (k, q) tile grid computes everything the two
    split kernels compute, reading each Q/K/V/dO/lse/D block from HBM
    once instead of twice: for a fixed k-tile the q-tiles stream by
    accumulating dK/dV in VMEM (exactly the split dK/dV kernel's
    order), and the dQ contribution of the (q, k) pair — whose dS the
    dK accumulation already paid for — is added into rows `qi *
    block_q` of a head's whole float32 dQ, which stays in VMEM from the
    head's first step to its last ([T_q, D]: 1 MB at 2048 x 128) and is
    written back once. A q-tile's additions arrive in k order from zero
    — the split dQ kernel's scratch sequence — so the two paths agree
    bitwise.
    """
    ki, qi = pl.program_id(1), pl.program_id(2)
    last_q = qi == pl.num_programs(2) - 1

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((qi == 0) & (ki == 0))
    def _init_head():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile():
        ds = _backward_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                            lse_ref[0, :, :1], delta_ref[0, :, :1], qi, ki,
                            dk_scr, dv_scr, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, offset=offset)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_scr[rows, :] = dq_scr[rows, :] + jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),    # dS K [block_q, D]
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(tile)
    else:
        tile()

    @pl.when(last_q)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(last_q & (ki == pl.num_programs(1) - 1))
    def _finalize_head():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fold(x: jax.Array) -> jax.Array:
    """[B, T, H, D] -> [B*H, T, D] (batch and heads become the grid axis)."""
    batch, t, heads, dim = x.shape
    return x.transpose(0, 2, 1, 3).reshape(batch * heads, t, dim)


def _unfold(x: jax.Array, batch: int, heads: int) -> jax.Array:
    """[B*H, T, D] -> [B, T, H, D]."""
    _, t, dim = x.shape
    return x.reshape(batch, heads, t, dim).transpose(0, 2, 1, 3)


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                   block_q: int, block_k: int, interpret: bool):
    """Returns (out [B,T,H,D], lse [B*H, T, LANES])."""
    batch, t_q, heads, dim = q.shape
    t_k = k.shape[1]
    scale = 1.0 / np.sqrt(dim)
    offset = t_k - t_q
    qf, kf, vf = _fold(q), _fold(k), _fold(v)

    def kv_block(b, qi, ki):
        if causal:
            # a tile past the diagonal is never visited: name the last
            # one that is, so the pipeline sees no new block and copies
            # nothing
            ki = jnp.minimum(
                ki, _last_visible_k(qi, block_q, block_k, offset))
        return b, ki, 0

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               offset=offset)
    # Inside shard_map the outputs vary over the same mesh axes as the
    # inputs; pallas_call requires that stated explicitly on out_shape.
    vma = jax.typeof(q).vma
    out, lse = pl.pallas_call(
        kernel,
        grid=(batch * heads, t_q // block_q, t_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, dim), kv_block),
            pl.BlockSpec((1, block_k, dim), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, t_q, dim), q.dtype,
                                 vma=vma),
            jax.ShapeDtypeStruct((batch * heads, t_q, LANES), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running normalizer
            pltpu.VMEM((block_q, dim), jnp.float32),    # output accumulator
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return _unfold(out, batch, heads), lse


def _row_delta(dof, out):
    """D = rowsum(dO * O), [BH, T_q, LANES]: cheap elementwise+reduce,
    left to XLA; the kernels read it broadcast over the lane dim like
    the lse. Callers invoking a backward once per block (ring attention)
    pass the precomputed value instead — D depends only on the global
    out/dO, so it is identical for every block."""
    delta = jnp.sum(dof.astype(jnp.float32)
                    * _fold(out).astype(jnp.float32), axis=-1)  # [BH, T_q]
    return jnp.broadcast_to(delta[:, :, None], delta.shape + (LANES,))


def _flash_backward(q, k, v, out, lse, grad_out, *, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    delta=None):
    batch, t_q, heads, dim = q.shape
    t_k = k.shape[1]
    scale = 1.0 / np.sqrt(dim)
    offset = t_k - t_q
    bh = batch * heads
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    dof = _fold(grad_out)
    if delta is None:
        delta = _row_delta(dof, out)

    row_specs = [
        pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),    # q
        pl.BlockSpec((1, block_k, dim), lambda b, qi, ki: (b, ki, 0)),    # k
        pl.BlockSpec((1, block_k, dim), lambda b, qi, ki: (b, ki, 0)),    # v
        pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),    # dO
        pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),  # lse
        pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),  # D
    ]
    vma = jax.typeof(q).vma
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, t_q // block_q, t_k // block_k),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, dim), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse, delta)

    col_specs = [
        pl.BlockSpec((1, block_q, dim), lambda b, ki, qi: (b, qi, 0)),    # q
        pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),    # k
        pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),    # v
        pl.BlockSpec((1, block_q, dim), lambda b, ki, qi: (b, qi, 0)),    # dO
        pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),  # lse
        pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),  # D
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, t_k // block_k, t_q // block_q),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, dim), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_k, dim), v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dim), jnp.float32),
            pltpu.VMEM((block_k, dim), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta)

    return (_unfold(dq, batch, heads), _unfold(dk, batch, heads),
            _unfold(dv, batch, heads))


def _flash_backward_fused(q, k, v, out, lse, grad_out, *, causal: bool,
                          block_q: int, block_k: int, interpret: bool,
                          delta=None):
    """One-pass flash backward (`_flash_bwd_fused_kernel`): half the
    HBM reads of `_flash_backward`, a head's float32 dQ summed in VMEM,
    bit-identical results (the split path is the oracle)."""
    batch, t_q, heads, dim = q.shape
    t_k = k.shape[1]
    scale = 1.0 / np.sqrt(dim)
    offset = t_k - t_q
    bh = batch * heads
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    dof = _fold(grad_out)
    if delta is None:
        # same XLA precompute as the split path (identical f32 values
        # feed both backends)
        delta = _row_delta(dof, out)

    def q_block(b, ki, qi):
        if causal:
            # a tile before the diagonal is never visited: name the first
            # one that is, so the pipeline copies it once and no other
            qi = jnp.maximum(qi, jnp.minimum(
                _first_visible_q(ki, block_q, block_k, offset),
                t_q // block_q - 1))
        return b, qi, 0

    def k_block(b, ki, qi):
        return b, ki, 0

    vma = jax.typeof(q).vma
    dk, dv, dq = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, t_k // block_k, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dim), q_block),   # q
            pl.BlockSpec((1, block_k, dim), k_block),   # k
            pl.BlockSpec((1, block_k, dim), k_block),   # v
            pl.BlockSpec((1, block_q, dim), q_block),   # dO
            pl.BlockSpec((1, block_q, LANES), q_block),  # lse
            pl.BlockSpec((1, block_q, LANES), q_block),  # D
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, dim), k_block),
            pl.BlockSpec((1, block_k, dim), k_block),
            # a head's dQ: one block, written back once a head
            pl.BlockSpec((1, t_q, dim), lambda b, ki, qi: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, dim), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_k, dim), v.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_q, dim), q.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dim), jnp.float32),
            pltpu.VMEM((block_k, dim), jnp.float32),
            pltpu.VMEM((t_q, dim), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_fused",
    )(qf, kf, vf, dof, lse, delta)
    return (_unfold(dq, batch, heads), _unfold(dk, batch, heads),
            _unfold(dv, batch, heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, forward, backward, interpret, fused):
    return _flash_fwd(q, k, v, causal, forward, backward, interpret, fused)[0]


def _flash_fwd(q, k, v, causal, forward, backward, interpret, fused):
    out, lse = _flash_forward(q, k, v, causal=causal,
                              block_q=forward.block_q,
                              block_k=forward.block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, forward, backward, interpret, fused, residuals,
               grad_out):
    q, k, v, out, lse = residuals
    run = _flash_backward_fused if fused else _flash_backward
    return run(q, k, v, out, lse, grad_out, causal=causal,
               block_q=backward.block_q, block_k=backward.block_k,
               interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _dividing_block(t: int, cap: int = 512) -> int:
    """Largest multiple of the 128-lane width (≤ `cap`; 512 is the VMEM
    comfort zone for the f32 score tile at the default scoped limit)
    that divides `t`, or 0 when `t` is not 128-aligned (the caller then
    keeps its non-dividing block and falls back to the dense path)."""
    for size in range(cap - cap % LANES, 0, -LANES):
        if t % size == 0:
            return size
    return 0


# The sweep's winners at [8, 2048, 16, 128] causal bf16 on the v5e
# (PERF.md section 6, PR 36; tools/flash_sweep.py): the (block_q,
# block_k) of a score tile. What a tile costs beside its products — the
# step, the statistics, the accumulator's rescale, the wait for the
# MXU's first result — is per tile, so the forward runs fastest as ONE
# tile a head (the whole masked T x T, twice the causal FLOPs, no
# online-softmax second tile), the backward at four tiles a head of
# which three are visited. The split backward (where a head's dQ does
# not fit VMEM, and the ring's) was not swept: it takes the fused one's.
TILES = {"fwd": (2048, 2048), "bwd": (1024, 1024),
         "bwd_split": (1024, 1024)}


def _vmem_estimate(kernel: str, block_q: int, block_k: int, t_q: int,
                   dim: int, itemsize: int) -> int:
    """Scoped VMEM of one call: the pipeline's two buffers of every
    operand block, the scratch, and what a tile keeps beside them: the
    float32 scores, P in the operands' dtype and, under `causal`, the
    mask's two int32 iotas (dP and dS too in the backward). A head's
    dQ, output block and float32 scratch, is the fused backward's
    ('bwd') alone; 'bwd_split' counts the dK/dV kernel, the larger of
    the two."""
    tile = block_q * block_k
    if kernel == "fwd":
        blocks = (2 * block_q * dim * itemsize + 2 * block_k * dim * itemsize
                  + block_q * LANES * 4)
        scratch = 2 * block_q * LANES * 4 + block_q * dim * 4
        return 2 * blocks + scratch + tile * (4 + itemsize)
    dq_rows = t_q if kernel == "bwd" else 0
    blocks = (2 * block_q * dim * itemsize + 2 * block_q * LANES * 4
              + 4 * block_k * dim * itemsize + dq_rows * dim * itemsize)
    scratch = 2 * block_k * dim * 4 + dq_rows * dim * 4
    return 2 * blocks + scratch + tile * (8 + 2 * itemsize)


def flash_schedule(kernel: str, batch_heads: int, t_q: int, t_k: int,
                   dim: int, itemsize: int, causal: bool, *,
                   block_q: tp.Optional[int] = None,
                   block_k: tp.Optional[int] = None
                   ) -> tp.Optional[FlashSchedule]:
    """The schedule of `kernel` ('fwd': `flash_fwd`, 'bwd':
    `flash_bwd_fused`, 'bwd_split': `flash_bwd_dq` and `flash_bwd_dkv`)
    for a call, from its shapes alone.

    Tiles are the caller's `block_q` / `block_k`, else `TILES`, halved
    while the estimate passes `VMEM_LIMIT` (wider heads, float32); they
    are clamped to the sequence, and a tile that does not divide it
    gives way to the largest multiple of 128 under it that does (T=384
    runs at 384). None when no 128-multiple divides a length: the call
    then takes `dot_product_attention`. T = 2048, D = 128 in bf16 is the
    one shape a benchmark cell checks; the parity tests keep the other
    shapes honest in interpret mode, and tests/test_latent_decode.py
    compiles float32 and D = 256 for the v5e.
    """
    want_q, want_k = TILES[kernel]
    while max(want_q, want_k) > LANES and _vmem_estimate(
            kernel, min(want_q, t_q), min(want_k, t_k), t_q, dim,
            itemsize) > VMEM_LIMIT:
        want_q, want_k = max(want_q // 2, LANES), max(want_k // 2, LANES)
    block_q = min(block_q or want_q, t_q)
    block_k = min(block_k or want_k, t_k)
    if t_q % block_q:
        block_q = _dividing_block(t_q, max(block_q, 512)) or block_q
    if t_k % block_k:
        block_k = _dividing_block(t_k, max(block_k, 512)) or block_k
    if t_q % block_q or t_k % block_k:
        return None
    tiles_q, tiles_k = t_q // block_q, t_k // block_k
    working = tiles_q * tiles_k if not causal else sum(
        _causal_visible(qi, ki, block_q, block_k, t_k - t_q)
        for qi in range(tiles_q) for ki in range(tiles_k))
    return FlashSchedule(
        block_q, block_k, batch_heads * tiles_q * tiles_k,
        batch_heads * working,
        _vmem_estimate(kernel, block_q, block_k, t_q, dim, itemsize))


def fused_backward_fits(t_q: int, dim: int, itemsize: int) -> bool:
    """Whether the fused backward fits `VMEM_LIMIT` at its smallest
    tiles, a head's float32 dQ [T_q, D] beside them; where it does not,
    the backward takes the split kernels, at their own schedule."""
    return _vmem_estimate("bwd", LANES, LANES, t_q, dim,
                          itemsize) <= VMEM_LIMIT


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, *,
                    block_q: tp.Optional[int] = None,
                    block_k: tp.Optional[int] = None,
                    interpret: tp.Optional[bool] = None,
                    fused_backward: tp.Optional[bool] = None) -> jax.Array:
    """Flash attention over [B, T, H, D]; pallas on TPU, XLA elsewhere.

    Forward and backward are pallas kernels (O(T) sequence memory; the
    backward recomputes P blockwise from the forward's logsumexp — the
    FlashAttention-2 decomposition). The backward defaults to the
    fused one-pass kernel (`fused_backward=None` -> True where a head's
    float32 dQ fits VMEM: each Q/K/V/dO block read from HBM once);
    `fused_backward=False` selects the split two-kernel path, kept as
    the bit-identical oracle (the paged-decode `--kernel gather`
    convention). Each kernel's schedule is `flash_schedule`'s, from the
    call's shapes alone; a caller's `block_q` / `block_k` are the tiles
    of both. Only when no 128-multiple divides T (T not 128-aligned),
    or on a GPU backend (the kernel is TPU-targeted), does
    `dot_product_attention` run instead.
    """
    batch, t_q, heads, dim = q.shape
    itemsize = q.dtype.itemsize
    if fused_backward is None:
        fused_backward = fused_backward_fits(t_q, dim, itemsize)
    shapes = (batch * heads, t_q, k.shape[1], dim, itemsize, causal)
    forward, backward = (
        flash_schedule(kernel, *shapes, block_q=block_q, block_k=block_k)
        for kernel in ("fwd", "bwd" if fused_backward else "bwd_split"))
    if forward is None or backward is None:
        # T not 128-aligned: no legal tile divides it, the XLA path runs
        return dot_product_attention(q, k, v, causal=causal)
    backend = jax.default_backend()
    if interpret is None:
        if backend == "cpu":
            interpret = True  # interpret mode: correct, testable on CPU
        elif backend in ("gpu", "cuda", "rocm"):
            # TPU-only kernel (pltpu scratch/Mosaic); XLA handles GPU.
            return dot_product_attention(q, k, v, causal=causal)
        else:
            # tpu, or TPU PJRT plugins under other names: real kernel.
            interpret = False
    return _flash(q, k, v, causal, forward, backward, interpret,
                  fused_backward)


def dividing_axes(size: int, mesh: Mesh,
                  axes: tp.Sequence[str]) -> tp.Tuple[str, ...]:
    """The mesh `axes` a dimension of `size` can be split over: taken
    in order, an axis is kept while the combined size still divides."""
    kept: tp.List[str] = []
    ways = 1
    for name in axes:
        if size % (ways * mesh.shape[name]) == 0:
            kept.append(name)
            ways *= mesh.shape[name]
    return tuple(kept)


def sharded_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            mesh: Mesh, causal: bool = False, *,
                            batch_axes: tp.Sequence[str] = ("data", "fsdp"),
                            head_axis: str = "tensor") -> jax.Array:
    """`flash_attention` for a jitted step whose arrays live on a mesh.

    GSPMD cannot partition a Mosaic kernel — lowering one inside a
    multi-device jit fails with "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — so the kernel
    runs per device under shard_map. Attention is local to a batch row
    and to a head: the batch splits over `batch_axes`, the heads over
    `head_axis`, no collective is needed, and the remaining mesh axes
    see replicated operands.
    """
    batch = dividing_axes(q.shape[0], mesh, batch_axes)
    heads = dividing_axes(q.shape[2], mesh, (head_axis,))
    spec = P(batch or None, None, heads or None, None)
    # check_vma=False: pallas interpret mode (the CPU test path) cannot
    # propagate varying-axis types through its block slicing
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)(q, k, v)
