# State-space-duality (SSD) chunked scan. One linear-attention layer
# admits two provably-equivalent evaluation orders over the recurrence
#
#     S_t = a_t * S_{t-1} + v_t (x) b_t        y_t = S_t . c_t
#
# (per-head scalar decay a_t in (0, 1], state S [Dh, Dstate] per head):
#
#  * the CHUNKED form (training / prefill): split T into chunks of C
#    tokens; within a chunk the pairwise decay products become a dense
#    [C, C] mask over (c . b) scores — two MXU matmuls per chunk — and
#    the recurrence survives only BETWEEN chunks, as a lax.scan whose
#    carry is the f32 state (FT201: scan carries that accumulate must
#    be f32). FLOPs stay O(T*C) instead of O(T^2), and the per-step
#    working set is matmul-shaped, exactly what the MXU wants;
#  * the RECURRENT form (serve/decode): advance the recurrence one
#    token at a time against a resident [B, H, Dh, Dstate] f32 state —
#    constant bytes per slot whatever the context length, which is the
#    whole O(1)-cache story the serving engine builds on.
#
# Both forms are the same polynomial in the inputs, evaluated in a
# different association order, so they agree to f32-accumulation
# tolerance on the same weights (and bit-identically where the chunk
# boundary math allows) — the dual-form parity gate tests assert it.
#
# All decay bookkeeping is computed as DIRECT masked sums (triangular
# matmuls against the log-decays), never as differences of cumulative
# sums: a segment-reset boundary sets log a_t = SSD_LOG_RESET (-1e30),
# and `exp(L_t - L_s)` spelled as a cumsum difference would
# catastrophically cancel the -1e30 terms into garbage, where the
# direct sum underflows cleanly to the intended exact 0.
#
# The fused Pallas kernel keeps the established seam
# (ops/paged_decode.py): kernel='auto'|'gather'|'fused', where 'gather'
# names the XLA chunked reference (the interpret-mode bit-oracle the
# fused kernel is tested against), an explicit 'fused' refuses to run
# where it cannot (fused_ssd_unsupported_reason). The chunk is the
# caller's `chunk` argument (the model passes `cfg.ssd_chunk`; parity
# tests and a builder's sweep script pass others), else the rule over
# the sequence length in `default_chunk`: no environment variable, file
# or process-wide cache decides which kernel compiles.
#
# b and c come BY GROUP, `[B, T, G, N]` with G dividing the heads (head
# h reads group h // (H / G); G == H is a projection a head): the
# kernel's index map names the group as stored and nothing is broadcast
# to the heads in HBM; the XLA forms repeat them (a reference's cost).
#
# On the chip (v5e, PR 33, PERF.md section 6; H=128, P=64, N=128, G=8,
# bf16 b and c, f32 v: one layer of the cell `nemotron3s-reason-closed`).
# A 512-token slice: the kernel at chunk 64 | 128 | 256 | 512 takes
# 0.694 | 0.450 | 0.425 | 0.557 ms, XLA's chunked form 0.424 (128) and
# 0.403 (256): `default_chunk` takes the largest candidate that divides
# T, 256 there, and the kernel is no faster than XLA at this shape — a
# (head, chunk) grid step is four small float32 products, two of them
# the triangular decay sums it rebuilds every step (ROADMAP S14); it
# stays the TPU path because it never materialises b and c a head. One
# decode token a row against 129 resident states (`ssd_state_update`):
# XLA's gather, update and scatter 5.84 ms a layer; the kernel 1.67
# (`tools/ssd_update_sweep.py`: 2.11 with a lane broadcast of the decay
# and of v and a lane sum for y per head), where copies alone take
# 1.65-1.74 ms however they are issued and the bytes at the HBM's peak
# 1.31: the copies set the pace. `UPDATE_HEADS` 32 (64 and 128 heads a
# step: under 1% less).
"""SSD/linear-attention dual forms: chunked scan + recurrent step."""
import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Log-decay value that RESETS the state across a segment boundary:
# exp(-1e30) underflows to exactly 0.0 in f32, and any masked sum
# containing it stays ~-1e30 (f32 max is ~3.4e38, so no overflow), so
# every decay product spanning a boundary is exactly zero.
SSD_LOG_RESET = -1e30

# Chunk lengths `default_chunk` chooses among: the largest one
# dividing T (the header has the chip's sweep: 256 at T = 512).
CHUNK_CANDIDATES: tp.Tuple[int, ...] = (16, 32, 64, 128, 256)


def fused_ssd_unsupported_reason() -> tp.Optional[str]:
    """None when the fused chunked-scan kernel can genuinely RUN here
    (compiled on TPU, interpret mode on CPU); else the human-readable
    reason — the `fused_kernel_unsupported_reason` convention, so an
    explicit kernel='fused' fails loudly instead of silently running
    the XLA reference under a fused label."""
    backend = jax.default_backend()
    if backend in ("gpu", "cuda", "rocm"):
        return (f"the fused SSD kernel is TPU-targeted and the backend "
                f"is {backend!r} (XLA's chunked path handles GPU)")
    return None


def default_ssd_kernel() -> str:
    """kernel='auto' resolution: 'fused' on TPU, 'gather' (the XLA
    chunked reference) on cpu/gpu — CPU runs opt in to the fused kernel
    explicitly (interpret mode), the ops/paged_decode.py convention."""
    if fused_ssd_unsupported_reason() is not None \
            or jax.default_backend() == "cpu":
        return "gather"
    return "fused"


def default_chunk(seq_len: int) -> int:
    """Largest candidate chunk dividing `seq_len`; else the largest
    candidate that fits (the sub-chunk tail chains exactly); else the
    sequence itself (one chunk) — short prompts and odd tail slices
    still evaluate in the chunked form."""
    for cand in sorted(CHUNK_CANDIDATES, reverse=True):
        if seq_len % cand == 0:
            return cand
    for cand in sorted(CHUNK_CANDIDATES, reverse=True):
        if cand < seq_len:
            return cand
    return seq_len


def _to_heads_first(x: jax.Array) -> jax.Array:
    """[B, T, H, *] -> [B, H, T, *] (the scan-internal layout)."""
    return jnp.swapaxes(x, 1, 2)


def _per_head(x: jax.Array, heads: int) -> jax.Array:
    """b or c heads-first [B, G, T, N] -> [B, H, T, N]: head h reads
    group h // (H / G). The XLA forms only (the reference, the CPU): the
    kernels index the group as stored."""
    groups = x.shape[1]
    if groups == heads:
        return x
    if heads % groups:
        raise ValueError(f"{groups} groups of b and c do not divide "
                         f"{heads} heads")
    return jnp.repeat(x, heads // groups, axis=1)


def _masked_inputs(b: jax.Array, log_a: jax.Array,
                   token_mask: tp.Optional[jax.Array]
                   ) -> tp.Tuple[jax.Array, jax.Array]:
    """Null out padded tokens: a masked token must neither decay the
    state (log a := 0) nor contribute to it (b := 0 kills both its
    score column and its outer-product write). `token_mask` is [B, T]
    bool, True on real tokens."""
    if token_mask is None:
        return b, log_a
    m = token_mask[:, :, None]
    return (jnp.where(m[..., None], b, jnp.zeros_like(b)),
            jnp.where(m, log_a, jnp.zeros_like(log_a)))


def _chunk_body(c, b, v, la, state):
    """One chunk of the chunked form, heads-first f32 decay math.

    c/b: [B, H, C, N]; v: [B, H, C, Dh]; la: [B, H, C] f32 log-decays;
    state: [B, H, Dh, N] f32 carried in. Returns (y [B, H, C, Dh] f32,
    new_state f32). Every decay exponent is a DIRECT masked sum of la
    (see module docstring), so segment-reset sentinels stay exact.
    """
    csize = la.shape[-1]
    # seg[t, s] = sum_{r=s+1..t} la_r for t >= s (else unused): built
    # as one triangular matmul pair, never as a cumsum difference.
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (csize, csize), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (csize, csize), 1)
    incl_tril = (t_idx >= s_idx).astype(jnp.float32)   # r <= t
    strict = (t_idx > s_idx).astype(jnp.float32)       # r > s
    # contrib[r, s] = la_r when r > s
    contrib = la[..., :, None] * strict                # [B, H, C, C]
    seg = jnp.einsum("tr,bhrs->bhts", incl_tril, contrib,
                     preferred_element_type=jnp.float32)
    decay = jnp.where(t_idx >= s_idx, jnp.exp(seg), 0.0)
    # incl[t] = sum_{r<=t} la_r ; suffix[s] = sum_{r>s} la_r ; both
    # direct sums (no subtraction), all-negative terms -> no overflow.
    incl = jnp.einsum("tr,bhr->bht", incl_tril, la,
                      preferred_element_type=jnp.float32)
    suffix = jnp.einsum("sr,bhr->bhs", strict.T, la,
                        preferred_element_type=jnp.float32)
    total = jnp.sum(la, axis=-1)                       # [B, H]

    scores = jnp.einsum("bhtn,bhsn->bhts", c, b,
                        preferred_element_type=jnp.float32) * decay
    y_intra = jnp.einsum("bhts,bhsd->bhtd", scores, v.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    y_inter = jnp.exp(incl)[..., None] * jnp.einsum(
        "bhtn,bhdn->bhtd", c, state, preferred_element_type=jnp.float32)
    weighted_b = b.astype(jnp.float32) * jnp.exp(suffix)[..., None]
    new_state = jnp.exp(total)[..., None, None] * state + jnp.einsum(
        "bhsd,bhsn->bhdn", v.astype(jnp.float32), weighted_b,
        preferred_element_type=jnp.float32)
    return y_intra + y_inter, new_state


def _chunked_reference(c, b, v, la, state, chunk: int):
    """The XLA chunked form: intra-chunk dense matmuls, inter-chunk
    recurrence through lax.scan with the f32 state as carry."""
    batch, heads, seq, _ = c.shape
    n_chunks = seq // chunk

    def split(x):
        # [B, H, T, *] -> [J, B, H, C, *] (scan iterates the chunk axis)
        parts = x.reshape(x.shape[:2] + (n_chunks, chunk) + x.shape[3:])
        return jnp.moveaxis(parts, 2, 0)

    def body(carry, xs):
        c_j, b_j, v_j, la_j = xs
        y_j, carry = _chunk_body(c_j, b_j, v_j, la_j, carry)
        return carry, y_j

    state, ys = jax.lax.scan(body, state, (split(c), split(b), split(v),
                                           split(la)))
    ys = jnp.moveaxis(ys, 0, 2)  # [J, B, H, C, Dh] -> [B, H, J, C, Dh]
    return ys.reshape(batch, heads, seq, v.shape[-1]), state


# ----------------------------------------------------------------------
# fused Pallas chunked-scan kernel
# ----------------------------------------------------------------------
def _fused_ssd_body(c_ref, b_ref, v_ref, la_ref, s0_ref, y_ref, sout_ref,
                    state_scr, *, chunk: int):
    """One (batch, head, chunk) grid step.

    The chunk axis iterates fastest, so for a fixed (batch, head) the
    VMEM scratch carries the f32 state across the sequence's chunks —
    the lax.scan carry of the reference, materialized as kernel-resident
    scratch. Decay exponents are the same triangular matmuls as the
    reference (direct sums only; segment-reset sentinels stay exact).
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state_scr[:] = s0_ref[0, 0].astype(jnp.float32)

    la_col = la_ref[0, 0].astype(jnp.float32)          # [C, 1]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    incl_tril = (t_idx >= s_idx).astype(jnp.float32)
    strict = (t_idx > s_idx).astype(jnp.float32)
    contrib = la_col * strict                          # [C, C] (= la_r at [r, s])
    seg = jax.lax.dot(incl_tril, contrib,
                      preferred_element_type=jnp.float32)
    decay = jnp.where(t_idx >= s_idx, jnp.exp(seg), 0.0)
    incl = jax.lax.dot(incl_tril, la_col,
                       preferred_element_type=jnp.float32)    # [C, 1]
    suffix = jax.lax.dot(strict.T, la_col,
                         preferred_element_type=jnp.float32)  # [C, 1]
    total = jnp.sum(la_col)

    ch = c_ref[0, 0]                                   # [C, N]
    bh = b_ref[0, 0]                                   # [C, N]
    vh = v_ref[0, 0]                                   # [C, Dh]
    scores = jax.lax.dot_general(                      # [C, C] f32
        ch, bh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * decay
    y = jax.lax.dot(scores, vh.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    state = state_scr[:]                               # [Dh, N] f32
    y = y + jnp.exp(incl) * jax.lax.dot_general(
        ch.astype(jnp.float32), state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    weighted_b = bh.astype(jnp.float32) * jnp.exp(suffix)
    state_scr[:] = jnp.exp(total) * state + jax.lax.dot_general(
        vh.astype(jnp.float32), weighted_b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        sout_ref[0, 0] = state_scr[:]


def _fused_call(c, b, v, la, state, *, chunk: int, interpret: bool):
    batch, groups, seq, dstate = c.shape
    heads, dim = v.shape[1], v.shape[-1]
    n_chunks = seq // chunk
    share = heads // groups  # heads that read one group's b and c

    def tok_index(bi, hi, j):
        return (bi, hi, j, 0)

    def group_index(bi, hi, j):
        # head hi reads group hi // share as stored: b and c are never
        # broadcast to the heads in HBM
        return (bi, hi // share, j, 0)

    def state_index(bi, hi, j):
        return (bi, hi, 0, 0)

    vma = jax.typeof(v).vma
    kernel = functools.partial(_fused_ssd_body, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(batch, heads, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dstate), group_index),
            pl.BlockSpec((1, 1, chunk, dstate), group_index),
            pl.BlockSpec((1, 1, chunk, dim), tok_index),
            # log-decays ride a trailing unit dim: a (1, 1, chunk) block
            # of the [B, H, T] array is a 1-row tile Mosaic refuses, and
            # the body wants the [C, 1] column anyway
            pl.BlockSpec((1, 1, chunk, 1), tok_index),
            pl.BlockSpec((1, 1, dim, dstate), state_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dim), tok_index),
            pl.BlockSpec((1, 1, dim, dstate), state_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, seq, dim), v.dtype,
                                 vma=vma),
            jax.ShapeDtypeStruct((batch, heads, dim, dstate), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((dim, dstate), jnp.float32)],
        interpret=interpret,
        name="ssd_scan_fused",
    )(c, b, v, la[..., None], state)


# ----------------------------------------------------------------------
# public dual forms
# ----------------------------------------------------------------------
def ssd_chunked_scan(c: jax.Array, b: jax.Array, v: jax.Array,
                     log_decay: jax.Array, *,
                     state: tp.Optional[jax.Array] = None,
                     chunk: tp.Optional[int] = None,
                     token_mask: tp.Optional[jax.Array] = None,
                     kernel: str = "gather",
                     interpret: tp.Optional[bool] = None
                     ) -> tp.Tuple[jax.Array, jax.Array]:
    """The matmul-friendly CHUNKED form: [B, T] tokens -> outputs plus
    the final state, equivalent to running the recurrence token by
    token.

    Args:
        c: [B, T, G, Dstate] output projections (the "C" of SSD); G
            divides H and head h reads group h // (H / G) — G == H is a
            projection a head.
        b: [B, T, G, Dstate] state input projections (the "B").
        v: [B, T, H, Dh] values.
        log_decay: [B, T, H] per-token log decays, <= 0 (use
            `SSD_LOG_RESET` at segment boundaries to zero the carried
            state exactly).
        state: optional [B, H, Dh, Dstate] f32 carried-in state (a
            streaming prefill's previous chunks); zeros when None.
        chunk: intra-chunk length. Defaults to `default_chunk(T)`. T
            need not be a multiple: the
            tail shorter than `chunk` is evaluated as one final chunk
            against the carried state, which chains EXACTLY (the scan
            carry IS the chained state) — so any partitioning of a
            token stream at multiples of `chunk` is bit-identical to
            one whole-stream call, the property the serving engine's
            chunked prefill leans on for token-exactness.
        token_mask: optional [B, T] bool, True on real tokens — padded
            tokens neither decay nor feed the state (their outputs are
            garbage the caller discards, the right-padding convention).
        kernel: 'gather' = the XLA reference (and the interpret-mode
            bit-oracle), 'fused' = the Pallas chunked-scan kernel,
            'auto' = `default_ssd_kernel()`.
        interpret: fused only; None resolves like `flash_attention` —
            interpret mode on CPU, compiled on TPU, gather fallback on
            GPU.

    Returns:
        (y [B, T, H, Dh] in v's dtype, final state [B, H, Dh, Dstate]
        f32).
    """
    if kernel not in ("auto", "gather", "fused"):
        raise ValueError(f"kernel must be 'auto', 'gather' or 'fused', "
                         f"got {kernel!r}")
    if kernel == "auto":
        kernel = default_ssd_kernel()
    batch, seq, _, dstate = c.shape
    heads, dim = v.shape[2], v.shape[-1]
    b, log_decay = _masked_inputs(b, log_decay, token_mask)
    if chunk is None:
        chunk = default_chunk(seq)
    elif chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    chunk = min(int(chunk), seq)
    if state is None:
        state = jnp.zeros((batch, heads, dim, dstate), jnp.float32)
    state = state.astype(jnp.float32)

    ch = _to_heads_first(c)
    bh = _to_heads_first(b)
    vh = _to_heads_first(v)
    lah = _to_heads_first(log_decay[..., None])[..., 0].astype(jnp.float32)

    if kernel == "fused" and interpret is None:
        backend = jax.default_backend()
        if backend == "cpu":
            interpret = True
        elif backend in ("gpu", "cuda", "rocm"):
            kernel = "gather"
        else:
            interpret = False

    # compiled, the kernel's blocks are whole (sublanes, 128) tiles of
    # the narrowest operand; a shorter piece (a sub-chunk tail, a tail
    # slice of a few tokens, an init trace) is a few rows of work: XLA's
    tile = 32 // min(jnp.dtype(x.dtype).itemsize for x in (c, b, v))

    def run(c_p, b_p, v_p, la_p, state_p, chunk_p):
        if kernel == "fused" and (interpret or chunk_p % tile == 0):
            return _fused_call(c_p, b_p, v_p, la_p, state_p, chunk=chunk_p,
                               interpret=bool(interpret))
        return _chunked_reference(_per_head(c_p, heads),
                                  _per_head(b_p, heads), v_p, la_p,
                                  state_p, chunk_p)

    # Full chunks first, then the sub-chunk tail as one final chunk
    # against the carried state — exact chaining (see `chunk` above).
    full = (seq // chunk) * chunk
    if full == 0:
        y, final = run(ch, bh, vh, lah, state, seq)
    elif full == seq:
        y, final = run(ch, bh, vh, lah, state, chunk)
    else:
        cut = lambda x, lo, hi: x[:, :, lo:hi]
        y0, mid = run(cut(ch, 0, full), cut(bh, 0, full),
                      cut(vh, 0, full), lah[:, :, :full], state, chunk)
        y1, final = run(cut(ch, full, seq), cut(bh, full, seq),
                        cut(vh, full, seq), lah[:, :, full:], mid,
                        seq - full)
        y = jnp.concatenate([y0, y1], axis=2)
    return _to_heads_first(y).astype(v.dtype), final


def ssd_recurrent_scan(c: jax.Array, b: jax.Array, v: jax.Array,
                       log_decay: jax.Array, state: jax.Array
                       ) -> tp.Tuple[jax.Array, jax.Array]:
    """The RECURRENT form: advance the state one token at a time.

    Same argument shapes as `ssd_chunked_scan` (b and c by group) plus the mandatory
    [B, H, Dh, Dstate] f32 `state`; T is usually 1 (a decode step) but
    any T runs — a lax.scan over time with the f32 state as carry (the
    recurrent reference the dual-form parity gate compares against).
    Returns (y [B, T, H, Dh] in v's dtype, new state f32).
    """
    heads = v.shape[2]
    ch = _per_head(_to_heads_first(c), heads).astype(jnp.float32)
    bh = _per_head(_to_heads_first(b), heads).astype(jnp.float32)
    vh = _to_heads_first(v).astype(jnp.float32)
    lah = _to_heads_first(log_decay[..., None])[..., 0].astype(jnp.float32)
    state = state.astype(jnp.float32)

    def step(carry, xs):
        c_t, b_t, v_t, la_t = xs          # [B, H, N/N/Dh/-]
        carry = (jnp.exp(la_t)[..., None, None] * carry
                 + v_t[..., :, None] * b_t[..., None, :])
        y_t = jnp.einsum("bhdn,bhn->bhd", carry, c_t,
                         preferred_element_type=jnp.float32)
        return carry, y_t

    to_time = lambda x: jnp.moveaxis(x, 2, 0)  # [B, H, T, *] -> [T, B, H, *]
    state, ys = jax.lax.scan(
        step, state, (to_time(ch), to_time(bh), to_time(vh), to_time(lah)))
    y = jnp.moveaxis(ys, 0, 2)                 # [B, H, T, Dh]
    return _to_heads_first(y).astype(v.dtype), state


# ----------------------------------------------------------------------
# one token a row against a table of resident states (the decode run)
# ----------------------------------------------------------------------
# Heads a grid step of the update kernel carries: 32 x [64, 128] f32 is
# 1 MB in and 1 MB out a step, double-buffered 4 MB (PERF.md section 6
# has the sweeps).
UPDATE_HEADS = 32
# The lanes of a vector register: v and y travel as rows this wide.
LANES = 128


def _update_body(rows_ref, decay_ref, v_ref, b_ref, c_ref, state_ref,
                 y_ref, out_ref, *, heads: int, share: int):
    """One (row, head block) grid step: `heads` states [P, N] of the
    row's table entry advanced in place. The decay arrives as the
    block's scalars in SMEM; v and y as lane-dense rows [1, W] that hold
    W / P heads' P values each; b and c as the groups' rows [1, N].

    A transpose is the only cross-lane work: one turns a row of v into
    its heads' columns, each value along the lanes, and one turns those
    heads' `new * c` [W, N] into [N, W], whose sum over the sublanes is
    their row of y. A head then costs its state's vector arithmetic,
    which hides under the copies of its entry (PERF.md section 6)."""
    del rows_ref  # consumed by the index maps
    dim, dstate = state_ref.shape[2], state_ref.shape[3]
    width = y_ref.shape[2]
    per_row = width // dim
    for r in range(heads // per_row):
        v_cols = jnp.transpose(jnp.broadcast_to(
            v_ref[0, r:r + 1, :], (dstate, width)))         # [W, N]
        products = []
        for part in range(per_row):
            j = r * per_row + part
            group = j // share  # within the block's groups (0: one group)
            new = (decay_ref[0, 0, j] * state_ref[0, j]
                   + v_cols[part * dim:(part + 1) * dim]
                   * b_ref[0, group])                      # [P, N]
            out_ref[0, j] = new
            products.append(new * c_ref[0, group])
        y_ref[0, r:r + 1, :] = jnp.sum(
            jnp.transpose(jnp.concatenate(products)), axis=0, keepdims=True)


def _update_call(state, rows, decay, v, b, c, *, heads_per_step: int,
                 interpret: bool):
    batch, heads, dim = v.shape
    groups, dstate = b.shape[1], b.shape[2]
    share = heads // groups
    hb = min(heads_per_step, heads)
    if heads % hb or (hb % share and share % hb):
        raise ValueError(f"{hb} heads a step must divide {heads} heads and "
                         f"be whole groups of {share} (or divide one)")
    blocks, per_block = heads // hb, max(1, hb // share)
    # a lane-dense row of v or y: as many of the step's heads as fill
    # the lanes
    per_row = math.gcd(hb, max(1, LANES // dim))
    width = per_row * dim

    def group_index(bi, hi, rows):
        return (bi, hi * hb // (share * per_block), 0, 0)

    lane_rows = pl.BlockSpec((1, hb // per_row, width),
                             lambda bi, hi, rows: (bi, hi, 0))
    # one [1, hb] row of scalars a block: a block's last two dimensions
    # are whole tiles or the array's own
    scalars = pl.BlockSpec((1, 1, hb),
                           lambda bi, hi, rows: (bi * blocks + hi, 0, 0),
                           memory_space=pltpu.SMEM)
    group = pl.BlockSpec((1, per_block, 1, dstate), group_index)
    entry = pl.BlockSpec((1, hb, dim, dstate),
                         lambda bi, hi, rows: (rows[bi], hi, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_update_body, heads=hb, share=share),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, blocks),
            in_specs=[scalars, lane_rows, group, group, entry],
            out_specs=[lane_rows, entry]),
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads // per_row, width),
                                 jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count the prefetched rows: the table is operand 5
        input_output_aliases={5: 1},
        interpret=interpret,
        name="ssd_state_update",
    )(rows, decay.reshape(batch * blocks, 1, hb),
      v.reshape(batch, heads // per_row, width), b[:, :, None, :],
      c[:, :, None, :], state)
    return y.reshape(batch, heads, dim), state


def ssd_state_update(state: jax.Array, rows: jax.Array, decay: jax.Array,
                     v: jax.Array, b: jax.Array, c: jax.Array, *,
                     kernel: str = "auto",
                     heads_per_step: tp.Optional[int] = None,
                     interpret: tp.Optional[bool] = None
                     ) -> tp.Tuple[jax.Array, jax.Array]:
    """The recurrence advanced ONE token a row, in place, against a
    table of resident states: the serving engine's decode run.

    Args:
        state: [R, H, Dh, Dstate] f32, one entry a slot (and whatever
            row parked rows are pointed at); donated by the caller, so
            the update is in place.
        rows: [B] int32, the entry each batch row advances. Rows that
            name the same entry (parked rows at a sentinel) leave it
            with one of their writes.
        decay: [B, H] f32, a_t in (0, 1] (exp of the log decay).
        v: [B, H, Dh] f32; b, c: [B, G, Dstate] f32 by group.
        kernel: 'gather' = XLA (rows gathered, advanced, scattered
            back), 'fused' = the Pallas kernel `ssd_state_update`, which
            reads and writes each touched entry once through the
            prefetched `rows`; 'auto' as `default_ssd_kernel()`.
        heads_per_step: the kernel's heads a grid step (`UPDATE_HEADS`).

    Returns (y [B, H, Dh] f32 = new state . c, the table).
    """
    if kernel not in ("auto", "gather", "fused"):
        raise ValueError(f"kernel must be 'auto', 'gather' or 'fused', "
                         f"got {kernel!r}")
    if kernel == "auto":
        kernel = default_ssd_kernel()
    batch, heads, dim = v.shape
    groups = b.shape[1]
    if kernel == "fused":
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        return _update_call(
            state, rows.astype(jnp.int32), decay.astype(jnp.float32),
            v.astype(jnp.float32), b.astype(jnp.float32),
            c.astype(jnp.float32),
            heads_per_step=heads_per_step or UPDATE_HEADS,
            interpret=bool(interpret))
    by_group = (batch, groups, heads // groups)
    new = (decay.reshape(by_group + (1, 1))
           * state[rows].reshape(by_group + state.shape[2:])
           + v.reshape(by_group + (dim, 1)) * b[:, :, None, None, :])
    y = jnp.einsum("bghpn,bgn->bghp", new, c,
                   preferred_element_type=jnp.float32)
    return (y.reshape(batch, heads, dim),
            state.at[rows].set(new.reshape((batch,) + state.shape[1:])))


def ssd_state_bytes(num_heads: int, head_dim: int, dstate: int) -> int:
    """Bytes of ONE layer's per-sequence SSD state: the [H, Dh, Dstate]
    f32 recurrence carry — independent of context length, which is the
    number the serving capacity math builds on."""
    return num_heads * head_dim * dstate * 4
