# Pallas-fused paged decode. The gather-based read path
# (ops/paged_attention.py) asks XLA to fuse three steps — block-table
# gather, int8 dequant, softmax(QK^T)V — and XLA obliges with an
# unfused program: the gather materializes each slot's logical
# [max_len] K/V view in HBM-sized intermediates every step (what that
# costs on the chip: not measured). Decode is bandwidth-bound:
# the win is reading every pool byte exactly once, straight from the
# physical blocks, with no logical view in between. This module is
# that read path as ONE Pallas TPU kernel:
#
#  * The per-slot block table `[max_blocks]` and the base positions are
#    SCALAR-PREFETCH operands (SMEM): the grid iterates physical table
#    entries directly, and each entry's BlockSpec index map reads
#    `table[slot, entry]` to aim the next pipelined DMA at the physical
#    pool block — no gathered copy, no logical view, exactly one HBM
#    read per live block. Entries past the slot's causal horizon are
#    clamped onto the last live block in the index map (the pipeline
#    skips the re-fetch of an unchanged block) and their compute is
#    `pl.when`-skipped, so a short slot in a long table costs its live
#    blocks, not its table width.
#  * int8 pools dequantize IN the kernel under the FT203 scale-folding
#    identity: the per-(row, head) K scales multiply the SCORES between
#    the q.k contraction and the softmax, the V scales multiply the
#    PROBS between the softmax and the probs.v contraction — each
#    exactly once (`(q . k_int8) * s == q . (k_int8 * s)`, the scale is
#    constant over the contracted head_dim). The numerics auditor
#    verifies this placement structurally on THIS kernel's traced
#    program (models/audit.py registers it; `make analyze-numerics`),
#    so a rewrite that double-, un- or wrong-side-scales fails CI
#    before it ever decodes garbage.
#  * Online softmax across table entries (the ops/attention.py
#    recurrence: running max / normalizer / f32 accumulator in VMEM
#    scratch), so the [T, max_len] score matrix never exists.
#
# One kernel serves every multi-token read the engine has, because they
# all share one contract — T query rows at CONSECUTIVE positions
# `base..base+T-1` per slot:
#    decode           T = 1
#    speculative      T = k+1   (the [S, k+1] verify scoring forward —
#                     verify stops paying the gather+dequant round trip
#                     per draft token)
#    chunked prefill  T = chunk
#
# Sentinel/unassigned table entries need no special casing beyond the
# in-kernel causal mask: a sentinel entry at table index j only covers
# logical positions [j*bs, (j+1)*bs), all beyond the slot's horizon
# until a real block replaces it (the ops/paged_attention.py proof),
# so `key_pos <= q_pos` masks it — and all-sentinel warm-up tables
# attend nothing real by construction.
#
# The gather implementation stays as the interpret-mode oracle (the
# ops/attention.py convention: pallas interpret mode on CPU, XLA
# gather as the reference): token-exactness tests drive both through
# the same engine and compare streams.
"""Fused paged-attention decode + speculative-verify Pallas kernels."""
import functools
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import paged_attention

NEG_INF = -1e30
LANES = 128  # native f32 lane width; row stats ride it (attention.py)


def fused_kernel_unsupported_reason() -> tp.Optional[str]:
    """None when the fused kernel can genuinely RUN here (compiled on
    TPU, interpret mode on CPU); else the human-readable reason. The
    engine consults this to reject an explicit `kernel='fused'` LOUDLY
    instead of letting the gather fallback masquerade as the kernel —
    a demo/bench gate that reports 'fused' must have run it.
    """
    backend = jax.default_backend()
    if backend in ("gpu", "cuda", "rocm"):
        return (f"the fused kernel is TPU-targeted and the backend is "
                f"{backend!r} (XLA's gather path handles GPU)")
    return None


def default_kernel() -> str:
    """The engine's `kernel='auto'` resolution: 'fused' on TPU (or TPU
    PJRT plugins under other names), 'gather' on cpu/gpu — CPU runs
    opt in to the fused kernel explicitly (interpret mode), the way
    the demo and the parity tests do."""
    if fused_kernel_unsupported_reason() is not None \
            or jax.default_backend() == "cpu":
        return "gather"
    return "fused"


def _default_head_block(num_heads: int, quantized: bool = False) -> int:
    """Largest power-of-two divisor of H not above 8 — enough rows
    (H*T) to fill a sublane tile at T=1 without blowing the VMEM
    scratch at long T, and power-of-two so the row block lands on the
    8-sublane tile boundary instead of forcing pad rows per grid step.

    int8 pools take every head in one block: their `[block_size, H]`
    scale blocks carry the heads in the LANE position, where Mosaic
    accepts only the whole dimension (or a multiple of 128) — a
    head_block of 8 out of 16 heads is refused at lowering."""
    if quantized:
        return num_heads
    cand = 8
    while cand > 1 and num_heads % cand:
        cand //= 2
    return cand


def _fused_body(base_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                acc_scr, k_scale_ref, v_scale_ref, *, block_size: int,
                queries: int, head_block: int, head_dim: int,
                scale: float):
    """One (slot, head-block, table-entry) grid step.

    The entry axis iterates fastest, so for a fixed (slot, head block)
    the VMEM scratch (running max / normalizer / f32 accumulator,
    rows = head_block * queries) carries the online-softmax state
    across the slot's physical blocks; output lands on the final
    entry. Rows with no visible key yet keep the _guarded_probs
    convention (attention.py): exp is forced to zero while the running
    max still sits at ~NEG_INF.
    """
    slot = pl.program_id(0)
    entry = pl.program_id(2)
    entries = pl.num_programs(2)

    @pl.when(entry == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    base = base_ref[slot]

    def _accumulate():
        # [T, hb, Dh] -> [hb, T, Dh]: heads become the dot batch dim
        qh = q_ref[0].transpose(1, 0, 2)
        kh = k_ref[0].transpose(1, 0, 2)          # [hb, bs, Dh]
        scores = jax.lax.dot_general(             # [hb, T, bs], f32
            qh, kh.astype(qh.dtype), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if k_scale_ref is not None:
            # K scales fold into the SCORES pre-softmax — the FT203
            # placement; [bs, hb] -> [hb, 1, bs] broadcast over queries
            scores = scores * k_scale_ref[0].transpose(1, 0)[:, None, :]
        q_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (queries, block_size), 0)
        k_pos = entry * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (queries, block_size), 1)
        # the ONE mask: causal AND sentinel/unassigned (sentinel entries
        # only cover logical positions beyond the slot's horizon)
        scores = jnp.where((k_pos <= q_pos)[None], scores, NEG_INF)

        rows = scores.reshape(head_block * queries, block_size)
        m_prev = m_scr[:, :1]                     # [rows, 1]
        m_new = jnp.maximum(m_prev, rows.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.where(m_new > NEG_INF * 0.5,
                          jnp.exp(rows - m_new), 0.0)
        l_new = l_scr[:, :1] * alpha + probs.sum(axis=-1, keepdims=True)
        p3 = probs.reshape(head_block, queries, block_size)
        if v_scale_ref is not None:
            # V scales fold into the PROBS post-softmax (FT203)
            p3 = p3 * v_scale_ref[0].transpose(1, 0)[:, None, :]
        vh = v_ref[0].transpose(1, 0, 2)          # [hb, bs, Dh]
        if v_scale_ref is None:
            # P cast to V's dtype for the MXU fast path (attention.py)
            p3 = p3.astype(vh.dtype)
        else:
            # int8 V: the payload casts up instead (scale already in P)
            vh = vh.astype(qh.dtype)
            p3 = p3.astype(qh.dtype)
        pv = jax.lax.dot_general(                 # [hb, T, Dh]
            p3, vh, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha \
            + pv.reshape(head_block * queries, head_dim)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # entries whose whole block sits past the last query's horizon
    # contribute nothing — skip their MXU work (their DMA was already
    # skipped by the index-map clamp onto the last live block)
    pl.when(entry * block_size <= base + queries - 1)(_accumulate)

    @pl.when(entry == entries - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        out = (acc_scr[:] / denom).reshape(head_block, queries, head_dim)
        o_ref[0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


def _fused_kernel_quant(table_ref, base_ref, q_ref, k_ref, ks_ref, v_ref,
                        vs_ref, o_ref, m_scr, l_scr, acc_scr, **kw):
    del table_ref  # consumed by the index maps, not the body
    _fused_body(base_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                acc_scr, ks_ref, vs_ref, **kw)


def _fused_kernel_dense(table_ref, base_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, **kw):
    del table_ref
    _fused_body(base_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                acc_scr, None, None, **kw)


def _fused_call(q, entry, table, base, *, head_block: int,
                interpret: bool):
    batch, queries, heads, dim = q.shape
    entries = table.shape[1]
    block_size = entry["k"].shape[-3]
    quant = "k_scale" in entry
    scale = 1.0 / np.sqrt(dim)
    hb = head_block

    def block_index(b, h, e, table_ref, base_ref):
        # Clamp dead entries onto the last live block: the pipeline
        # recognizes an unchanged block index and skips the DMA, so a
        # slot pays HBM reads for its live blocks only. Parked slots
        # (base == max_seq_len) clamp to the table's end like the
        # gather path attends their all-sentinel view — garbage either
        # way, discarded by the engine's active mask.
        last = jnp.minimum(
            jnp.maximum(base_ref[b] + queries - 1, 0) // block_size,
            entries - 1)
        return (table_ref[b, jnp.minimum(e, last)], 0, h, 0)

    def scale_index(b, h, e, table_ref, base_ref):
        return block_index(b, h, e, table_ref, base_ref)[:3]

    def q_index(b, h, e, *_):
        return (b, 0, h, 0)

    in_specs = [pl.BlockSpec((1, queries, hb, dim), q_index),
                pl.BlockSpec((1, block_size, hb, dim), block_index)]
    operands = [q, entry["k"]]
    if quant:
        in_specs.append(pl.BlockSpec((1, block_size, hb), scale_index))
        operands.append(entry["k_scale"])
    in_specs.append(pl.BlockSpec((1, block_size, hb, dim), block_index))
    operands.append(entry["v"])
    if quant:
        in_specs.append(pl.BlockSpec((1, block_size, hb), scale_index))
        operands.append(entry["v_scale"])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the block table + the base positions
        grid=(batch, heads // hb, entries),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, queries, hb, dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((hb * queries, LANES), jnp.float32),  # running max
            pltpu.VMEM((hb * queries, LANES), jnp.float32),  # normalizer
            pltpu.VMEM((hb * queries, dim), jnp.float32),    # accumulator
        ],
    )
    kernel = functools.partial(
        _fused_kernel_quant if quant else _fused_kernel_dense,
        block_size=block_size, queries=queries, head_block=hb,
        head_dim=dim, scale=scale)
    vma = jax.typeof(q).vma
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, queries, heads, dim),
                                       q.dtype, vma=vma),
        interpret=interpret,
        name="paged_decode_fused",
    )(table, base, *operands)


def fused_paged_attention(q: jax.Array, entry: tp.Dict, table: jax.Array,
                          positions: jax.Array, *, head_dim: int, dtype,
                          head_block: tp.Optional[int] = None,
                          interpret: tp.Optional[bool] = None
                          ) -> jax.Array:
    """Fused paged decode read: `paged_attention`'s contract, one kernel.

    Args match `ops.paged_attention.paged_attention` — q `[B, T, H, Dh]`
    (rotary-applied), one layer's pool `entry`, `[B, max_blocks]`
    tables, `[B, T]` absolute positions — with ONE extra contract:
    every row's positions must be CONSECUTIVE (`positions[:, t] ==
    positions[:, 0] + t`), which every engine read path satisfies
    (decode T=1, verify `base + arange(k+1)`, chunked prefill
    `start + arange(chunk)`). The kernel derives the causal mask from
    `positions[:, 0]` alone; arbitrary per-row position patterns need
    the gather path.

    int8 pools fold the K scales into the scores pre-softmax and the V
    scales into the probs post-softmax IN-kernel — the same identity
    the gather path spells and FT203 structurally audits — so the
    fused and gather int8 paths compute the same fold, not merely
    close numbers.

    `head_block` tiles heads per grid step (VMEM scratch vs pipeline
    depth); defaults to the per-`device_kind` tuned winner when
    `ops.tuning.tune_paged_blocks` has recorded one, else a divisor of
    H capped at 8. `interpret=None` resolves like `flash_attention`:
    interpret mode on CPU, the real kernel on TPU, and the gather
    fallback on GPU (the kernel is TPU-targeted).
    """
    if interpret is None:
        backend = jax.default_backend()
        if backend == "cpu":
            interpret = True
        elif backend in ("gpu", "cuda", "rocm"):
            return paged_attention(q, entry, table, positions,
                                   head_dim=head_dim, dtype=dtype)
        else:
            interpret = False
    heads = q.shape[2]
    if head_block is None:
        from .tuning import lookup_tuned_paged_blocks
        quantized = "k_scale" in entry
        head_block = lookup_tuned_paged_blocks(
            q.shape[0], q.shape[1], heads, head_dim,
            block_size=entry["k"].shape[-3], entries=table.shape[1],
            quantized=quantized, dtype=dtype)
        if head_block is None or heads % head_block:
            # no winner (or a corrupt cache entry): keep the default —
            # a tuned pick must never be able to break correctness
            head_block = _default_head_block(heads, quantized)
    elif heads % head_block:
        raise ValueError(f"head_block {head_block} must divide "
                         f"num_heads {heads}")
    base = jax.lax.slice_in_dim(positions, 0, 1, axis=1)[:, 0]
    q = q.astype(dtype)
    return _fused_call(q, entry, table, base.astype(jnp.int32),
                       head_block=int(head_block), interpret=interpret)


def fused_speculative_verify(q: jax.Array, entry: tp.Dict,
                             table: jax.Array, positions: jax.Array, *,
                             head_dim: int, dtype,
                             head_block: tp.Optional[int] = None,
                             interpret: tp.Optional[bool] = None
                             ) -> jax.Array:
    """The `[S, k+1]` speculative-verify scoring read, fused.

    Identical kernel to `fused_paged_attention` at T = k+1 >= 2: the
    verify forward scores the last emitted token plus k drafts per
    slot against the SAME physical pools in one pass, so verify stops
    paying the per-draft-token gather+dequant round trip. Split out so
    the verify contract (multi-row consecutive positions) has a named
    audit/test surface (models/audit.py registers this spelling).
    """
    if q.shape[1] < 2:
        raise ValueError(f"speculative verify scores k+1 >= 2 rows per "
                         f"slot, got T={q.shape[1]} (plain decode is "
                         f"fused_paged_attention at T=1)")
    return fused_paged_attention(q, entry, table, positions,
                                 head_dim=head_dim, dtype=dtype,
                                 head_block=head_block,
                                 interpret=interpret)


def decode_read_bytes_per_token(cfg, context_len: int,
                                kv_dtype: str = "model") -> int:
    """HBM bytes ONE decode token must stream from the KV pools.

    Decode is bandwidth-bound: each step reads every live K/V byte of
    the slot's context (plus the int8 scales) across all layers, and
    tok/s is capped at ~bandwidth / this number. Pure host arithmetic
    (the bench records it beside the measured tok/s so the
    bandwidth-bound story is a number, not an assertion).
    """
    from .paged_attention import block_bytes
    return block_bytes(cfg, 1, kv_dtype) * context_len
