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
#    from it. Two backward spellings share every block formula:
#      - fused (default): ONE kernel sweeps (k-block, q-block) once,
#        accumulating dK/dV in VMEM and emitting per-k-block f32 dQ
#        partials that a fixed-order fold reduces outside — each
#        Q/K/V/dO block is read from HBM once;
#      - split (the oracle): two kernels (dQ with K-blocks innermost;
#        dK/dV with Q-blocks innermost), reading everything twice.
#    Both are O(T) in sequence memory — the FlashAttention-2
#    decomposition, laid out for the MXU — and bit-identical to each
#    other (tests pin it), so the split path doubles as the
#    interpret-mode oracle for the fused one.
#
# Tile choice lives here and nowhere else: a call's blocks are its
# `block_q` / `block_k` arguments (how parity tests and a builder's
# sweep script reach other tilings), else DEFAULT_BLOCK, clamped to the
# sequence and made to divide it (`_dividing_block`). No environment
# variable, file or process-wide cache decides which kernel compiles.
# 256 x 256 is what every training run in PERF_LEDGER.jsonl compiled
# (PERF.md section 5: `flash_fwd` 113.5 ms and `flash_bwd_fused` 71.1 ms
# a step at [8, 2048, 16, 128]); no sweep of other tiles has run on the
# chip. One that does fixes its winner here as a rule over shapes, its
# numbers in PERF.md (the `paged_decode.walk_shape` precedent).
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
DEFAULT_BLOCK = 256  # query and key rows a tile (header: tile choice)


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


def _causal_visible(qi, ki, block_q: int, block_k: int, offset: int):
    """Whether k-block `ki` holds any key visible to q-block `qi`."""
    return ki * block_k <= qi * block_q + block_q - 1 + offset


def _block_scores(q_ref, k_ref, qi, ki, *, scale, causal, block_q, block_k,
                  offset):
    """Recompute the masked score block [block_q, block_k] on the MXU.

    Operands stay in their input dtype (bf16 normally) with f32
    accumulation — the MXU's fast path; a pre-cast to f32 would force
    multi-pass f32 matmuls at a fraction of the bf16 rate.
    """
    scores = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
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
    """Forward: one (batch*head, q-block, k-block) grid step.

    The TPU grid iterates the last dimension fastest, so for a fixed
    q-block the k-blocks arrive sequentially and the VMEM scratch
    (running max / normalizer / accumulator) carries the online-softmax
    state across them. Output and the per-row logsumexp (the backward's
    softmax residual) are written on the final k-block.

    `offset = t_k - t_q` aligns causal masking bottom-right (query i
    attends keys j <= i + offset), matching `dot_product_attention`'s
    tril(k=t_k-t_q) — the self-attention case has offset 0.
    """
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)

    def _accumulate():
        scores = _block_scores(q_ref, k_ref, qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)

        # All row statistics stay 2-D [block_q, 1] — the Mosaic-friendly
        # layout (no 1-D vector intermediates).
        m_prev = m_scr[:, :1]                      # [block_q, 1]
        block_max = scores.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        alpha = jnp.exp(m_prev - m_new)
        # _guarded_probs: rows whose running max is still ~NEG_INF have
        # no visible key in any block so far (mixed q-blocks when
        # offset < 0); exp(scores - m_new) would be exp(0) = 1 there and
        # the row would silently average V over masked keys.
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
        # Fully-future blocks contribute nothing; skip their MXU work
        # entirely (roughly halves causal attention FLOPs).
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)   # [block_q, 1]
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # logsumexp of each score row; rows with no visible key (can only
        # happen for padding layouts) would be -inf, clamp via denom.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dq_scr, *, scale: float, causal: bool, block_q: int,
                     block_k: int, offset: int):
    """Backward dQ: grid (batch*head, q-block, k-block), k innermost.

    For a fixed q-block, k-blocks stream by while the dQ accumulator
    lives in VMEM; P is recomputed from the forward's logsumexp (no TxT
    residual). dS = P * (dP - D) with D = rowsum(dO*O) precomputed.
    """
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate():
        scores = _block_scores(q_ref, k_ref, qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)
        lse = lse_ref[0, :, :1]                    # [block_q, 1]
        # Rows with no visible key (offset < 0 cross-attention) carry an
        # lse at the clamp floor; the forward emitted zeros for them and
        # the backward must emit zero grads, not exp(0)-weighted ones.
        probs = _guarded_probs(scores, lse)        # [block_q, block_k]
        dp = jax.lax.dot_general(                  # dO V^T [block_q, block_k]
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0, :, :1]                # [block_q, 1]
        ds = probs * (dp - delta) * scale
        # dS cast to K's dtype: bf16 operands + f32 accumulation is the
        # MXU fast path; dS itself is an exp-derived quantity with the
        # same dynamic range as P.
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                      causal: bool, block_q: int, block_k: int, offset: int):
    """Backward dK/dV: grid (batch*head, k-block, q-block), q innermost.

    For a fixed k-block, q-blocks stream by accumulating
    dV += P^T dO and dK += dS^T Q in VMEM.
    """
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate():
        scores = _block_scores(q_ref, k_ref, qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)
        lse = lse_ref[0, :, :1]
        # Same empty-row guard as _flash_dq_kernel.
        probs = _guarded_probs(scores, lse)        # [block_q, block_k]
        # P / dS cast to the operand dtype for bf16 MXU passes with f32
        # accumulation (same rationale as the forward / dQ kernels).
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(   # P^T dO [block_k, D]
            probs.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0, :, :1]
        ds = probs * (dp - delta) * scale          # [block_q, block_k]
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(   # dS^T Q [block_k, D]
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_visible(qi, ki, block_q, block_k, offset))(_accumulate)
    else:
        _accumulate()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, dqp_ref, dk_scr, dv_scr, *,
                            scale: float, causal: bool, block_q: int,
                            block_k: int, offset: int):
    """Fused backward: grid (batch*head, k-block, q-block), q innermost.

    One pass over the (k, q) block grid computes everything the two
    split kernels compute, reading each Q/K/V/dO/lse/D block from HBM
    once instead of twice: for a fixed k-block the q-blocks stream by
    accumulating dK/dV in VMEM (exactly the split dK/dV kernel's
    order), and the dQ contribution of the (q, k) pair — whose dS the
    dK accumulation already paid for — is emitted as a per-k-block f32
    partial. A TPU grid cannot revisit an output block
    non-consecutively, so the split dQ kernel's qi-major VMEM
    accumulation is impossible here; instead the partials land in a
    [BH, nk_blocks, T_q, D] buffer (each block written exactly once;
    causally skipped blocks write exact zeros) and are reduced outside
    in k order — the same f32 addition sequence as the split kernel's
    scratch, so the two paths agree bitwise.
    """
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate():
        scores = _block_scores(q_ref, k_ref, qi, ki, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, offset=offset)
        lse = lse_ref[0, :, :1]
        # Same empty-row guard as the split kernels.
        probs = _guarded_probs(scores, lse)        # [block_q, block_k]
        # P / dS cast to the operand dtype for bf16 MXU passes with f32
        # accumulation; op-for-op the split kernels' formulas, in the
        # split dK/dV kernel's order (dV, dP, dS, dK), so the VMEM
        # accumulators march through identical f32 values.
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(   # P^T dO [block_k, D]
            probs.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                  # dO V^T [block_q, block_k]
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        delta = delta_ref[0, :, :1]
        ds = probs * (dp - delta) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(   # dS^T Q [block_k, D]
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dqp_ref[0, 0] = jax.lax.dot_general(           # dS K [block_q, D]
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        visible = _causal_visible(qi, ki, block_q, block_k, offset)
        pl.when(visible)(_accumulate)

        @pl.when(jnp.logical_not(visible))
        def _skipped():
            # every (k, q) output block is written exactly once; a
            # causally skipped pair must contribute exact zeros to the
            # dQ fold, not stale VMEM garbage
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    else:
        _accumulate()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


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
    qf, kf, vf = _fold(q), _fold(k), _fold(v)

    grid = (batch * heads, t_q // block_q, t_k // block_k)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               offset=t_k - t_q)
    # Inside shard_map the outputs vary over the same mesh axes as the
    # inputs; pallas_call requires that stated explicitly on out_shape.
    vma = jax.typeof(q).vma
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dim), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, qi, ki: (b, ki, 0)),
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
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return _unfold(out, batch, heads), lse


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
        # D = rowsum(dO * O): cheap elementwise+reduce, leave it to XLA;
        # the kernels read it broadcast over the lane dim like the lse.
        # Callers invoking this once per block (ring attention) pass the
        # precomputed [BH, T_q, LANES] value instead — D depends only on
        # the global out/dO, so it is identical for every block.
        delta = jnp.sum(dof.astype(jnp.float32)
                        * _fold(out).astype(jnp.float32), axis=-1)  # [BH, T_q]
        delta = jnp.broadcast_to(delta[:, :, None], (bh, t_q, LANES))

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
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta)

    return (_unfold(dq, batch, heads), _unfold(dk, batch, heads),
            _unfold(dv, batch, heads))


def _flash_backward_fused(q, k, v, out, lse, grad_out, *, causal: bool,
                          block_q: int, block_k: int, interpret: bool,
                          delta=None):
    """One-pass flash backward (`_flash_bwd_fused_kernel`): half the
    HBM reads of `_flash_backward` at the cost of nk_blocks f32 dQ
    partials, bit-identical results (the split path is the oracle)."""
    batch, t_q, heads, dim = q.shape
    t_k = k.shape[1]
    scale = 1.0 / np.sqrt(dim)
    offset = t_k - t_q
    bh = batch * heads
    nk = t_k // block_k
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    dof = _fold(grad_out)

    if delta is None:
        # D = rowsum(dO * O): same XLA precompute as the split path
        # (identical f32 values feed both backends).
        delta = jnp.sum(dof.astype(jnp.float32)
                        * _fold(out).astype(jnp.float32), axis=-1)  # [BH, T_q]
        delta = jnp.broadcast_to(delta[:, :, None], (bh, t_q, LANES))

    col_specs = [
        pl.BlockSpec((1, block_q, dim), lambda b, ki, qi: (b, qi, 0)),    # q
        pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),    # k
        pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),    # v
        pl.BlockSpec((1, block_q, dim), lambda b, ki, qi: (b, qi, 0)),    # dO
        pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),  # lse
        pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),  # D
    ]
    vma = jax.typeof(q).vma
    dk, dv, dqp = pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, nk, t_q // block_q),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, 1, block_q, dim),
                         lambda b, ki, qi: (b, ki, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, dim), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_k, dim), v.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, nk, t_q, dim), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dim), jnp.float32),
            pltpu.VMEM((block_k, dim), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
    )(qf, kf, vf, dof, lse, delta)

    # Reduce the dQ partials with an explicit left fold in k order —
    # the exact f32 addition sequence of the split kernel's VMEM
    # accumulator (which starts from zeros and adds k-blocks in order),
    # so fused and split dQ agree bitwise. jnp.sum's reduction order
    # would be XLA's choice, not ours.
    dq = jnp.zeros((bh, t_q, dim), jnp.float32)
    for i in range(nk):
        dq = dq + dqp[:, i]
    dq = dq.astype(q.dtype)
    return (_unfold(dq, batch, heads), _unfold(dk, batch, heads),
            _unfold(dv, batch, heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, fused):
    out, _ = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, fused):
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, fused, residuals,
               grad_out):
    q, k, v, out, lse = residuals
    backward = _flash_backward_fused if fused else _flash_backward
    return backward(q, k, v, out, lse, grad_out, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _dividing_block(t: int) -> int:
    """Largest multiple of the 128-lane width (≤ 512, the VMEM comfort
    zone for the f32 score tile) that divides `t`, or 0 when `t` is not
    128-aligned (the caller then keeps its non-dividing block and falls
    back to the dense path)."""
    for size in (512, 384, 256, 128):
        if t % size == 0:
            return size
    return 0


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
    fused one-pass kernel (`fused_backward=None` -> True: each
    Q/K/V/dO block read from HBM once); `fused_backward=False` selects
    the split two-kernel path, kept as the bit-identical oracle (the
    paged-decode `--kernel gather` convention). Blocks are the caller's
    `block_q` / `block_k`, else `DEFAULT_BLOCK`, and the backward runs
    at the forward's; they are clamped to the sequence length, and when
    the requested block does not divide T, the largest dividing
    multiple of 128 (up to 512) is used instead, so e.g. T=384 runs the
    kernel at 384 rather than falling back. Only when no
    128-multiple divides T (T not 128-aligned), or on a GPU backend
    (the kernel is TPU-targeted), does `dot_product_attention` run
    instead.
    """
    t_q, t_k = q.shape[1], k.shape[1]
    block_q = min(block_q or DEFAULT_BLOCK, t_q)
    block_k = min(block_k or DEFAULT_BLOCK, t_k)
    if t_q % block_q:
        block_q = _dividing_block(t_q) or block_q
    if t_k % block_k:
        block_k = _dividing_block(t_k) or block_k
    if t_q % block_q or t_k % block_k:
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
    if fused_backward is None:
        fused_backward = True
    return _flash(q, k, v, causal, block_q, block_k, interpret,
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
