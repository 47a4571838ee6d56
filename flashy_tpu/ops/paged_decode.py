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
#    SCALAR-PREFETCH operands (SMEM) and the pools never leave HBM as
#    operands: the kernel copies the physical blocks itself. One grid
#    step is one (slot, head block, query tile) and holds the whole
#    walk: an in-kernel loop whose every step attends a GROUP of pool
#    blocks (16 x 16 tokens at T=1) — their async copies, aimed by
#    `table[slot, step * group + g]`, land in one half of a
#    double-buffered VMEM tile while the other half is attended — and
#    whose trip count is `ceil(live_blocks / group)` from the slot's
#    base position. No gathered copy, no logical view, one HBM read per
#    live block, and no step at all for table entries past the horizon:
#    a short slot in a long table costs its live blocks (the chip's
#    word on that: PERF.md, PR 26 — the cost of this read is per step,
#    not per byte). A parked slot walks one block.
#  * Few query rows (decode, speculative verify) attend in a FLAT
#    layout: the `[group * bs, H, Dh]` tile is read as the pool stores
#    it, `[group * bs * H, Dh]`, and one 2-D dot of the `[T * H, Dh]`
#    queries against it gives every (query head, key head) pair, of
#    which the mask keeps the same-head ones. The spare MXU columns are
#    free at T=1 (the MXU waits on weight loads either way); what it
#    buys is no K/V relayout and H rows a pass instead of one. A prefill
#    chunk has the rows already and takes the per-head batched dot (the
#    tile transposed to `[H, group * bs, Dh]`), its T rows split into
#    query tiles when the score tile would outgrow VMEM. `walk_shape`
#    picks group, query tile and layout from the shapes alone, under an
#    explicit VMEM budget. That rule (and `latent_walk_shape` /
#    `grouped_walk_shape` for a latent / grouped pool) is the one owner
#    of tile choice: its constants below were fixed from a builder's
#    sweeps on the chip (PERF.md section 6, PR 26, PR 28 and PR 32),
#    `head_block` is the caller's argument (how the
#    parity tests and a sweep script reach other tilings) else
#    `_default_head_block`, and no environment variable, file or
#    process-wide cache decides which kernel compiles.
#  * A copy the kernel issues needs whole (8, 128) tiles (Mosaic), so
#    pools of narrower heads (`head_dim % 128 != 0`) or of fewer than 8
#    heads a step keep the walk the GRID makes: one block a grid step through a BlockSpec index map
#    that reads the table, every table entry a step, dead ones clamped
#    and skipped. For the same reason an int8 pool STORES a block's
#    scales as one lane-dense `[bs * H]` row (`ops.paged_attention.
#    pool_spec`: the leaf is `[N, 1, bs * H]`, whole (1, 128) tiles on
#    the device, never reshaped or relaid out): the flat layout copies
#    that row beside its block, as stored; the per-head layout and the
#    grid's walk take the rows gathered through the table beforehand, a
#    step's `[H, keys]` slab at a time (`_step_scales`; Mosaic has no
#    `[1, bs * H] -> [bs, H]` reshape to do it in the kernel). Same
#    arithmetic in all of them (`_attend_tile`).
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
#  * Online softmax across the walk's steps (the ops/attention.py
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
# A LATENT pool (models/mla.py: one normed latent `c` [rank] a token, key
# AND value of every head, and one rotated key `kr`) takes the same walk
# with other operands (`fused_latent_attention`, kernel
# `latent_decode_fused`): table and bases prefetched, whole `c`
# [bs, rank] and `kr` [bs, 128] blocks copied into double-buffered
# tiles, `ceil(live_blocks / group)` steps, the same online softmax. All
# heads share the key row, so a slot brings H query rows a position and
# a step is two 2-D dots for the scores and one for the values — no
# per-head loop, no layout choice, no "same head" mask. It has only this
# walk: a latent pool whose blocks are not whole tiles is refused
# (`fused_kernel_unsupported_reason`) and read by the XLA table gather.
#
# A GROUPED pool (models/gqa.py: a full-attention layer's row holds its
# Hkv KV heads side by side, `k` [Hkv * Dk] and `v` [Hkv * Dv], keys
# wider than values, fewer KV heads than query heads) takes it too
# (`fused_grouped_attention`, kernel `grouped_decode_fused`): whole K and
# V blocks copied as stored, the same steps and softmax. Query head h
# reads KV head h // (H / Hkv), by the form the step's rows pick
# (`head_parts`): at most 8 rows a slot lay the query heads
# block-diagonally over the row's lanes and keep their own KV head's
# values of every head's — no `[.., Hkv, Dk]` relayout exists; a slice
# has the rows to fill the MXU and attends a KV head at a time, its
# queries over the window of whole 128 lanes that holds the head's keys.
# `kernel='fused'` on such a pool means that the full-attention layers
# walk: a window layer's ring (ops/paged_attention.py: `ring_view`) is
# no table's view and keeps the masked dense read. As for a latent pool,
# blocks that are not whole tiles are refused and read by the gather.
#
# The gather implementations stay as the interpret-mode oracles (the
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

from .paged_attention import paged_attention, scale_rows

NEG_INF = -1e30
LANES = 128  # native f32 lane width; row stats ride it (attention.py)
SUBLANES = 8
VMEM_BUDGET = 16 * 2 ** 20  # scoped VMEM of one kernel on the v5e
KEYS_PER_STEP = 256   # keys one compute step attends at T=1
QUERY_TILE = 128      # query rows a grid step keeps when T must split
FLAT_ROWS = 128       # at most this many (query, head) rows: flat layout


def fused_kernel_unsupported_reason(cfg: tp.Any = None,
                                    block_size: tp.Optional[int] = None
                                    ) -> tp.Optional[str]:
    """None when the fused kernel can genuinely RUN here (compiled on
    TPU, interpret mode on CPU) for `cfg`'s pool; else the
    human-readable reason. The engine consults this to reject an
    explicit `kernel='fused'` LOUDLY instead of letting the gather
    fallback masquerade as the kernel — a demo/bench gate that reports
    'fused' must have run it. A latent pool (`attn_kind='mla'`) has no
    walk but the one whose copies the kernel issues itself, so its `c`
    and `kr` blocks must be whole (sublanes, 128) tiles: `block_size`,
    when the caller knows it, is held to the sublanes of `cfg.dtype`.
    A grouped pool (`attn_kind='gqa'`) likewise: 'fused' there means
    that the FULL-attention layers walk their tables
    (`fused_grouped_attention`; a window layer's ring keeps the masked
    dense read either way), and the kernel copies their K `[block_size,
    Hkv * Dk]` and V `[block_size, Hkv * Dv]` blocks as stored, so both
    rows must be whole lanes.
    """
    if getattr(cfg, "attn_kind", "mha") == "gqa":
        from ..models import gqa
        sublanes = SUBLANES * 4 // jnp.dtype(cfg.dtype).itemsize
        dk, dv = gqa.key_dim(cfg), gqa.value_dim(cfg)
        full = sorted({kind.kv_heads for kind in gqa.layer_kinds(cfg)
                       if not kind.window})
        if not full:
            return ("the fused kernel walks a grouped pool's full-attention "
                    "layers through their block tables and this one has "
                    "only window layers, whose rings the masked dense form "
                    "reads")
        if (any(kv * dk % LANES or kv * dv % LANES for kv in full)
                or (block_size or 0) % sublanes):
            return (f"the fused kernel copies whole ({sublanes}, {LANES}) "
                    f"tiles of a grouped pool's full-attention blocks and "
                    f"this one's are [{block_size or 'block_size'}, "
                    f"{' | '.join(map(str, full))} KV heads of {dk} | {dv}] "
                    f"under {cfg.num_heads} query heads (a row's keys and "
                    f"its values must each be a multiple of {LANES} wide, "
                    f"block_size of {sublanes}); the XLA table gather reads "
                    f"any grouped pool")
    if getattr(cfg, "attn_kind", "mha") == "mla":
        sublanes = SUBLANES * 4 // jnp.dtype(cfg.dtype).itemsize
        if cfg.kv_lora_rank % LANES or (block_size or 0) % sublanes:
            return (f"the fused kernel copies whole ({sublanes}, {LANES}) "
                    f"tiles of a latent pool's blocks and this one's are "
                    f"[{block_size or 'block_size'}, {cfg.kv_lora_rank}] "
                    f"(kv_lora_rank must be a multiple of {LANES}, "
                    f"block_size of {sublanes}); the XLA table gather "
                    f"reads any latent pool")
    backend = jax.default_backend()
    if backend in ("gpu", "cuda", "rocm"):
        return (f"the fused kernel is TPU-targeted and the backend is "
                f"{backend!r} (XLA's gather path handles GPU)")
    return None


def default_kernel(cfg: tp.Any = None,
                   block_size: tp.Optional[int] = None) -> str:
    """The engine's `kernel='auto'` resolution: 'fused' on TPU (or TPU
    PJRT plugins under other names) for every pool the kernel can walk
    — K/V pools, and latent pools and grouped pools (their
    full-attention layers) whose blocks are whole tiles — 'gather' on
    cpu/gpu and for the rest. CPU runs opt in to the fused
    kernel explicitly (interpret mode), the way the demo and the parity
    tests do."""
    if fused_kernel_unsupported_reason(cfg, block_size) is not None \
            or jax.default_backend() == "cpu":
        return "gather"
    return "fused"


def _default_head_block(num_heads: int, quantized: bool = False) -> int:
    """Heads per grid step when the caller names none. int8 pools take
    every head in one step: a block's scales are one `[block_size * H]`
    row (row-in-block major, head minor), and the flat layout's score
    columns have that order only with every head in the step.
    Dense pools take the largest power-of-two divisor of H not above 8,
    so the row block lands on the 8-sublane tile boundary."""
    if quantized:
        return num_heads
    cand = 8
    while cand > 1 and num_heads % cand:
        cand //= 2
    return cand


class Walk(tp.NamedTuple):
    """How one call walks a slot's block table — all from shapes."""
    group: int        # pool blocks one compute step attends
    head_block: int   # heads per grid step
    query_tile: int   # query rows per grid step
    flat: bool        # few rows: all heads in one 2-D score tile
    dma: bool         # the kernel fetches the blocks itself (else the
                      # grid does, one block a grid step)


def _vmem_estimate(queries: int, head_block: int, head_dim: int,
                   block_size: int, group: int, *, flat: bool,
                   pool_itemsize: int, q_itemsize: int) -> int:
    """Bytes of scoped VMEM one grid step of `_dma_walk_body` needs,
    counted from its own buffers: the pipelined q and o blocks, the
    softmax state, the double-buffered K/V tiles of `group` pool blocks,
    their upcast copies, the scale rows, and the score-shaped f32
    temporaries (scores, probs, the mask, the select). An estimate to
    choose by — Mosaic's own count is what refuses."""
    rows = queries * head_block
    keys = group * block_size
    cols = keys * head_block if flat else keys
    tile = keys * head_block * head_dim
    sublane_pad = 2 if pool_itemsize == 1 and head_block < 32 else 1
    total = 2 * 2 * rows * head_dim * q_itemsize          # q, o blocks
    total += rows * (2 * LANES + head_dim) * 4            # m, l, acc
    total += 2 * 2 * tile * pool_itemsize * sublane_pad   # K, V tiles x2
    total += 2 * tile * (4 + q_itemsize)                  # upcast K, V
    total += 2 * 2 * 8 * max(cols, LANES) * 4             # scale rows
    total += 4 * rows * cols * 4                          # score-shaped
    return total


def walk_shape(queries: int, heads: int, head_dim: int, block_size: int,
               entries: int, *, quantized: bool, pool_itemsize: int,
               q_itemsize: int, head_block: tp.Optional[int] = None
               ) -> Walk:
    """The table walk of one call, from its shapes alone.

    A copy the kernel issues itself needs whole (8, 128) tiles of the
    pool's `[head_block, head_dim]` rows: pools of narrower heads, or of
    fewer than 8 heads a step, keep the grid's walk (one block a grid
    step, every table entry a step).

    Otherwise decode (T=1) wants many blocks a step — its cost is per
    step, not per byte — so the group grows until a step attends
    `KEYS_PER_STEP` keys. A prefill chunk (T=256, 512) has the rows to
    fill the MXU already and its `[hb, tq, group * bs]` score tile is
    what grows, so the query tile halves first (each tile walks its own
    causal prefix) and then the group, until `_vmem_estimate` fits
    `VMEM_BUDGET`. The group is at least 1; a table it does not divide
    ends in a partial last group.
    """
    if head_block is None:
        head_block = _default_head_block(heads, quantized)
    if head_dim % LANES or head_block % SUBLANES:
        return Walk(1, head_block, queries, False, False)

    def flat(tile):
        # few (query, head) rows, and an int8 pool's scale rows whole
        # and lane-dense: `[bs * H]` is then one copy window per block
        return tile * head_block <= FLAT_ROWS and (
            not quantized or (head_block == heads
                              and (block_size * heads) % LANES == 0))

    def fits(group, tile):
        return _vmem_estimate(
            tile, head_block, head_dim, block_size, group, flat=flat(tile),
            pool_itemsize=pool_itemsize, q_itemsize=q_itemsize
        ) <= VMEM_BUDGET

    group = max(1, min(KEYS_PER_STEP // block_size, entries))
    tile = queries
    while not fits(group, tile):
        if tile > QUERY_TILE and tile % 2 == 0:
            tile //= 2
        elif group > 1:
            group //= 2
        elif tile > 8 and tile % 2 == 0:
            tile //= 2
        else:
            break  # the smallest walk there is; Mosaic has the last word
    return Walk(group, head_block, tile, flat(tile), True)


def call_walk(queries: int, heads: int, head_dim: int, *,
              block_size: int, entries: int, quantized: bool, dtype,
              head_block: tp.Optional[int] = None) -> Walk:
    """The walk `fused_paged_attention` takes for a call of these shapes
    (the engine asks too, for its `kv_steps` counter): `walk_shape` at
    the caller's `head_block`, else at `_default_head_block`."""
    itemsize = jnp.dtype(dtype).itemsize
    return walk_shape(queries, heads, head_dim, block_size, entries,
                      quantized=quantized, q_itemsize=itemsize,
                      pool_itemsize=1 if quantized else itemsize,
                      head_block=head_block)


def _live_blocks(base, last, block_size: int, entries: int, xp):
    """Table entries a walk attends for query rows `base..last`: the
    blocks up to the last row's horizon, clamped into the table. A parked
    slot (`base` at the table's end: the engine parks idle slots at
    max_seq_len) has no context; it walks one block — garbage like its
    whole all-sentinel view was, discarded by the engine's active mask.
    One formula for the kernel (jnp scalars) and the host's counters
    (numpy arrays)."""
    live = xp.clip(last // block_size + 1, 1, entries)
    return xp.where(base >= entries * block_size, 1, live)


def walk_counts(bases, queries: int, walk: Walk, block_size: int,
                entries: int) -> tp.Tuple[int, int]:
    """(kv_blocks, kv_steps) of one call and layer, on the host: pool
    blocks the walk attends and compute steps it runs for them, summed
    over the slots whose first query positions are `bases` (and over a
    split call's query tiles). Their ratio nears `walk.group` when the
    contexts are long beside a group; a step of the grid's walk is one
    block."""
    bases = np.asarray(bases, np.int64)
    blocks = steps = 0
    for rows in range(walk.query_tile, queries + 1, walk.query_tile):
        last = bases + rows - 1  # each query tile walks its own prefix
        if walk.dma:
            live = _live_blocks(bases, last, block_size, entries, np)
        else:  # the grid's walk clamps parked slots to the table's end
            live = np.clip(last // block_size + 1, 1, entries)
        blocks += int(live.sum())
        steps += int((-(-live // walk.group)).sum())
    return blocks, steps


def _attend_tile(q, k, v, k_scale, v_scale, visible, m_scr, l_scr,
                 acc_scr, *, flat: bool, scale: float):
    """One online-softmax step over a `[keys, hb, Dh]` K/V tile.

    Per head (`flat=False`): `q` is `[hb, T, Dh]`, the tile transposes
    to `[hb, keys, Dh]` and the scores are `[hb, T, keys]` — a batched
    dot with the heads as batch. Flat: `q` is `[T * hb, Dh]`, the tile
    is read as the pool stores it, `[keys * hb, Dh]`, and ONE 2-D dot
    gives `[T * hb, keys * hb]` scores of which `visible` keeps the
    same-head ones: no K/V relayout, and T * hb rows on the MXU instead
    of T. `k_scale` / `v_scale` broadcast against the scores (None for
    dense pools), `visible` is the ONE mask. State (running max,
    normalizer, f32 accumulator) is `[rows, ...]` VMEM scratch; rows
    with no visible key yet keep the _guarded_probs convention
    (attention.py): exp is forced to zero while the running max still
    sits at ~NEG_INF.
    """
    keys, hb, dim = k.shape
    quant = k_scale is not None
    if flat:
        contract = (((1,), (1,)), ((), ()))
        kq = k.reshape(keys * hb, dim)
    else:
        contract = (((2,), (2,)), ((0,), (0,)))
        kq = k.transpose(1, 0, 2)
    scores = jax.lax.dot_general(q, kq.astype(q.dtype), contract,
                                 preferred_element_type=jnp.float32) * scale
    if quant:
        # K scales fold into the SCORES pre-softmax — the FT203 placement
        scores = scores * k_scale
    scores = jnp.where(visible, scores, NEG_INF)

    rows = scores.reshape(m_scr.shape[0], -1)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, rows.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.where(m_new > NEG_INF * 0.5, jnp.exp(rows - m_new), 0.0)
    l_new = l_scr[:, :1] * alpha + probs.sum(axis=-1, keepdims=True)

    probs = probs.reshape(scores.shape)
    if quant:
        # V scales fold into the PROBS post-softmax (FT203); the int8
        # payload casts up instead of P casting down
        probs = probs * v_scale
        mxu = q.dtype
    else:
        # P cast to V's dtype for the MXU fast path (attention.py)
        mxu = v.dtype
    if flat:
        contract = (((1,), (0,)), ((), ()))
        vq = v.reshape(keys * hb, dim)
    else:
        contract = (((2,), (1,)), ((0,), (0,)))
        vq = v.transpose(1, 0, 2)
    pv = jax.lax.dot_general(probs.astype(mxu), vq.astype(mxu), contract,
                             preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * alpha + pv.reshape(acc_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _write_out(o_ref, l_scr, acc_scr, flat: bool):
    _, tq, hb, dim = o_ref.shape
    out = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
    if flat:
        out = out.reshape(tq, hb, dim)
    else:
        out = out.reshape(hb, tq, dim).transpose(1, 0, 2)
    o_ref[0] = out.astype(o_ref.dtype)


def _dma_walk_body(table_ref, base_ref, q_ref, k_hbm, v_hbm, ks_ref, vs_ref,
                   o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                   ks_buf=None, vs_buf=None, *, block_size: int,
                   group: int, entries: int, flat: bool, scale: float):
    """One (slot, head-block, query-tile) grid step: the whole walk.

    The pools stay in HBM. A compute step attends `group` pool blocks:
    their async copies land in one half of a double-buffered VMEM tile
    while the other half is attended, and the loop runs
    `ceil(live_blocks / group)` times — the slot's live context, not the
    table's width. The output is written once, after the loop.
    """
    slot, head, qtile = (pl.program_id(i) for i in range(3))
    _, tq, hb, dim = q_ref.shape
    quant = ks_ref is not None
    keys = group * block_size
    all_heads = hb == k_hbm.shape[2]

    first = base_ref[slot] + qtile * tq        # this tile's first q pos
    live = _live_blocks(base_ref[slot], first + tq - 1, block_size,
                        entries, jnp)
    steps = (live + group - 1) // group

    pools = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    if ks_buf is not None:
        pools += [(ks_ref, ks_buf, 2), (vs_ref, vs_buf, 3)]

    def tile_copies(step, half, start: bool):
        for g in range(group):
            # a partial last group re-reads the last live block, never an
            # entry past the live range (its keys sit past the horizon
            # and are masked by position like any other); a wait needs
            # the copy's shape, not its source
            block = table_ref[slot, jnp.minimum(step * group + g,
                                                live - 1)] if start else 0
            for src, dst, sem in pools:
                src = src.at[block]
                if not all_heads:
                    src = src.at[:, pl.ds(head * hb, hb)]
                copy = pltpu.make_async_copy(src, dst.at[half, g],
                                             sems.at[sem, half])
                copy.start() if start else copy.wait()

    _init_state(m_scr, l_scr, acc_scr)
    tile_copies(0, 0, start=True)

    # loop-invariant: how far each (query row, key column) pair is from
    # the causal diagonal when the walk is at step 0. The ONE mask —
    # causal AND sentinel/unassigned (a sentinel entry only covers
    # positions past the slot's horizon) AND, in the flat layout, "same
    # head" — is `ahead <= first - step * keys`.
    if flat:
        # rows (t, h), columns (block, row-in-block, h)
        shape = (tq * hb, keys * hb)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ahead = jnp.where(row % hb == col % hb,
                          col // hb - row // hb, 2 ** 30)
        q = q_ref[0].reshape(tq * hb, dim)
    else:
        shape = (tq, keys)
        ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 0))[None]
        # [T, hb, Dh] -> [hb, T, Dh]: heads become the dot batch dim
        q = q_ref[0].transpose(1, 0, 2)

    def scales(ref, buf, step, half):
        if not quant:
            return None
        if flat:
            # one lane-dense row per block, in the columns' order
            return jnp.concatenate([buf[half, g] for g in range(group)],
                                   axis=-1)
        # the step's [hb, keys] slab of the slot's gathered scales
        return ref[0, step][:, None, :]

    def attend(step, carry):
        half = step % 2

        @pl.when(step + 1 < steps)
        def _prefetch():
            tile_copies(step + 1, 1 - half, start=True)

        tile_copies(step, half, start=False)
        _attend_tile(q, k_buf[half].reshape(keys, hb, dim),
                     v_buf[half].reshape(keys, hb, dim),
                     scales(ks_ref, ks_buf, step, half),
                     scales(vs_ref, vs_buf, step, half),
                     ahead <= first - step * keys, m_scr, l_scr, acc_scr,
                     flat=flat, scale=scale)
        return carry

    jax.lax.fori_loop(0, steps, attend, 0)
    _write_out(o_ref, l_scr, acc_scr, flat)


def _grid_walk_body(table_ref, base_ref, q_ref, k_ref, v_ref, ks_ref,
                    vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                    block_size: int, scale: float):
    """One (slot, head-block, table-entry) grid step: one pool block.

    The walk of pools whose rows are narrower than a copy window: each
    entry's BlockSpec index map reads `table[slot, entry]` to aim the
    pipeline's next copy at the physical block. The entry axis iterates
    fastest, so the VMEM state carries across a slot's blocks and the
    output lands on the final entry. Entries past the horizon are
    clamped onto the last live block by the index map (an unchanged
    index skips the copy) and skip their arithmetic here, but each
    still costs a grid step.
    """
    del table_ref  # consumed by the index maps, not the body
    slot, entry = pl.program_id(0), pl.program_id(2)
    queries = q_ref.shape[1]
    base = base_ref[slot]

    @pl.when(entry == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    @pl.when(entry * block_size <= base + queries - 1)
    def _live():
        shape = (queries, block_size)
        visible = (entry * block_size
                   + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                   <= base + jax.lax.broadcasted_iota(jnp.int32, shape, 0))

        def scales(ref):  # this entry's [hb, bs] of the gathered scales
            return None if ref is None else ref[0, 0][:, None, :]

        _attend_tile(q_ref[0].transpose(1, 0, 2), k_ref[0], v_ref[0],
                     scales(ks_ref), scales(vs_ref), visible[None], m_scr,
                     l_scr, acc_scr, flat=False, scale=scale)

    @pl.when(entry == pl.num_programs(2) - 1)
    def _finalize():
        _write_out(o_ref, l_scr, acc_scr, False)


def _kernel(body, quant: bool, **static):
    """`body` with the pallas_call's positional refs put in its order:
    table, base, q, then K, V, K scales, V scales (None for dense
    pools), then the rest."""
    def kernel(table_ref, base_ref, q_ref, *refs):
        if quant:
            k, ks, v, vs, *rest = refs
        else:
            (k, v, *rest), ks, vs = refs, None, None
        body(table_ref, base_ref, q_ref, k, v, ks, vs, *rest, **static)
    return kernel


def _step_scales(scales: jax.Array, table: jax.Array, group: int,
                 heads: int) -> jax.Array:
    """`[N, 1, bs * H]` pool scales -> `[B, steps, H, group * bs]`: each
    slot's rows gathered through its table and laid out a compute step
    at a time, heads on sublanes and keys on lanes, the way the per-head
    score tile wants them. (The kernel can copy a block's row but not
    turn it into `[H, bs]`; the scales are 3% of the bytes.) A table
    `group` does not divide is padded with the sentinel."""
    batch, entries = table.shape
    steps = -(-entries // group)
    table = jnp.pad(table, ((0, 0), (0, steps * group - entries)))
    gathered = scale_rows(scales, table.reshape(batch, steps, group), heads)
    return gathered.transpose(0, 1, 4, 2, 3).reshape(
        batch, steps, heads, -1)


@functools.partial(jax.jit, static_argnames=("walk", "interpret"))
def _fused_call(q, entry, table, base, walk: Walk, *, interpret: bool):
    # jitted so that a model's layers, which all make this call at the
    # same shapes, trace and lower the kernel once between them: unrolled
    # over 16 layers the walk's copies were most of an engine's warm-up
    # (PERF.md, PR 26)
    batch, queries, heads, dim = q.shape
    entries = table.shape[1]
    block_size = entry["k"].shape[-3]
    quant = "k_scale" in entry
    group, hb, tq, flat, dma = walk
    scale = 1.0 / np.sqrt(dim)
    state = [pltpu.VMEM((hb * tq, LANES), jnp.float32),  # running max
             pltpu.VMEM((hb * tq, LANES), jnp.float32),  # normalizer
             pltpu.VMEM((hb * tq, dim), jnp.float32)]    # accumulator
    names = ("k", "k_scale", "v", "v_scale") if quant else ("k", "v")
    operands = [entry[name] for name in names]

    if dma:
        def q_index(b, h, t, *_):
            return (b, t, h, 0)

        grid = (batch, heads // hb, queries // tq)
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)
        specs = [hbm] * len(operands)
        tile = (2, group, block_size, hb, dim)
        scratch = [pltpu.VMEM(tile, entry["k"].dtype),
                   pltpu.VMEM(tile, entry["v"].dtype),
                   pltpu.SemaphoreType.DMA((4, 2))] + state
        if quant and flat:
            # the leaf as stored: one lane-dense row per pool block, in
            # the (row, head) order of the flat score columns, copied
            # beside its K/V block
            row = operands[1].shape[1:]
            scratch += [pltpu.VMEM((2, group) + row, jnp.float32)] * 2
        elif quant:
            for i in (1, 3):
                operands[i] = _step_scales(operands[i], table, group, heads)
                specs[i] = pl.BlockSpec(
                    (1, operands[i].shape[1], hb, group * block_size),
                    lambda b, h, t, *_: (b, 0, h, 0))
        kernel = _kernel(_dma_walk_body, quant, block_size=block_size,
                         group=group, entries=entries, flat=flat,
                         scale=scale)
    else:
        def q_index(b, h, e, *_):
            return (b, 0, h, 0)

        def live_entry(b, e, base_ref):
            # Clamp dead entries onto the last live one: the pipeline
            # recognizes an unchanged block index and skips the copy.
            # Parked slots (base == max_seq_len) clamp to the table's
            # end like the gather path attends their all-sentinel view.
            last = jnp.minimum(
                jnp.maximum(base_ref[b] + queries - 1, 0) // block_size,
                entries - 1)
            return jnp.minimum(e, last)

        def block_index(b, h, e, table_ref, base_ref):
            return (table_ref[b, live_entry(b, e, base_ref)], 0, h, 0)

        def scale_index(b, h, e, table_ref, base_ref):
            return (b, live_entry(b, e, base_ref), h, 0)

        grid = (batch, heads // hb, entries)
        blocks = pl.BlockSpec((1, block_size, hb, dim), block_index)
        specs = [blocks, blocks]
        if quant:
            # each entry's `[hb, bs]` of the slot's gathered scales
            for i in (1, 3):
                operands[i] = _step_scales(operands[i], table, 1, heads)
            scales = pl.BlockSpec((1, 1, hb, block_size), scale_index)
            specs = [blocks, scales, blocks, scales]
        scratch = state
        kernel = _kernel(_grid_walk_body, quant, block_size=block_size,
                         scale=scale)

    q_spec = pl.BlockSpec((1, tq, hb, dim), q_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the block table + the base positions
        grid=grid, in_specs=[q_spec] + specs, out_specs=q_spec,
        scratch_shapes=scratch,
    )
    vma = jax.typeof(q).vma
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, queries, heads, dim),
                                       q.dtype, vma=vma),
        interpret=interpret,
        name="paged_decode_fused",
    )(table, base, q, *operands)


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
    depth); defaults to `_default_head_block`: every head of an int8
    pool, else a divisor of H capped at 8. `interpret=None` resolves
    like `flash_attention`:
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
    if head_block is not None and heads % head_block:
        raise ValueError(f"head_block {head_block} must divide "
                         f"num_heads {heads}")
    walk = call_walk(q.shape[1], heads, head_dim,
                     block_size=entry["k"].shape[-3], entries=table.shape[1],
                     quantized="k_scale" in entry, dtype=dtype,
                     head_block=head_block)
    base = jax.lax.slice_in_dim(positions, 0, 1, axis=1)[:, 0]
    return _fused_call(q.astype(dtype), entry, table,
                       base.astype(jnp.int32), walk, interpret=interpret)


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


# ----------------------------------------------------------------------
# the same walk over a latent pool (models/mla.py's cached form)
# ----------------------------------------------------------------------
LATENT_ROWS = 2048       # (query, head) rows a grid step keeps
LATENT_SCORES = 2 ** 20  # score-tile elements a compute step aims at
LATENT_KEYS = 1024       # at most this many keys a compute step
# The slice's walk is bound by the MXU and loses a fifth of its speed in
# the 16 MiB every kernel gets by default (rows x keys 1024 x 256 against
# 2048 x 512: 5.0 against 4.0 ms a layer at offset 3,584; PERF.md, PR
# 28), so this kernel states its own limit — of the v5e's 128 MiB.
LATENT_VMEM_LIMIT = 48 * 2 ** 20


def _latent_vmem_estimate(queries: int, heads: int, rank: int, lanes: int,
                          block_size: int, group: int, itemsize: int) -> int:
    """`_vmem_estimate` for `_latent_walk_body`, from its own buffers:
    the pipelined q_lat, q_rope and o blocks, the softmax state, the
    double-buffered `c` and `kr` tiles, the score-shaped temporaries
    (the mask's distances, scores, probs and their cast) and the value
    product. High by a fifth at the cell's slice (35.9 MB where Mosaic
    counts 29.4): an estimate to choose by."""
    rows, keys = queries * heads, group * block_size
    total = 2 * rows * (2 * rank + lanes) * itemsize      # q_lat, q_rope, o
    total += rows * (2 * LANES + rank) * 4                # m, l, acc
    total += 2 * keys * (rank + lanes) * itemsize         # c, kr tiles x2
    total += rows * keys * (3 * 4 + itemsize)             # score-shaped
    total += rows * rank * 4                              # probs . c
    return total


def latent_walk_shape(queries: int, heads: int, rank: int, lanes: int,
                      block_size: int, entries: int, *, itemsize: int
                      ) -> Walk:
    """The walk of one latent read, from its shapes alone. Every head
    shares the key row, so a slot brings `heads` query rows a position:
    the MXU has its rows at T=1 and the group is what grows, to
    `LATENT_KEYS` keys a step; a slice's T splits into query tiles of
    at most `LATENT_ROWS` rows, each walking its own causal prefix,
    against fewer keys a step so that a score tile stays
    `LATENT_SCORES` elements. Then group and tile halve in turn until
    `_latent_vmem_estimate` fits `LATENT_VMEM_LIMIT`. `head_block` is
    all the heads, the layout flat, the copies the kernel's own."""
    tile = max(1, min(queries, LATENT_ROWS // heads))
    while queries % tile:
        tile -= 1

    def group_for(tile):
        keys = min(LATENT_KEYS, LATENT_SCORES // (tile * heads))
        return max(1, min(keys // block_size, entries))

    def fits(group, tile):
        return _latent_vmem_estimate(tile, heads, rank, lanes, block_size,
                                     group, itemsize) <= LATENT_VMEM_LIMIT

    group = group_for(tile)
    while not fits(group, tile):
        if group > 1:
            group //= 2
        elif tile > 1 and tile % 2 == 0:
            tile //= 2
            group = group_for(tile)
        else:
            break  # the smallest walk there is; Mosaic has the last word
    return Walk(group, heads, tile, True, True)


def latent_call_walk(queries: int, heads: int, entry: tp.Dict, *,
                     entries: int) -> Walk:
    """The walk `fused_latent_attention` takes for `queries` rows a slot
    against a latent pool `entry` — its `c` and `kr` leaves, or anything
    with their `shape` and `dtype` (the engine asks too, for its
    `kv_steps` counter, with `jax.ShapeDtypeStruct`s of the pool's
    spec)."""
    c, kr = entry["c"], entry["kr"]
    return latent_walk_shape(queries, heads, c.shape[-1], kr.shape[-1],
                             c.shape[-2], entries,
                             itemsize=jnp.dtype(c.dtype).itemsize)


def _walk_copies(table_ref, slot, live, group: int, pools, sems, window):
    """(start_copies(step, half), wait_copies(half)) of a walk whose
    kernel copies whole pool blocks itself: `pools` are (HBM array,
    double-buffered VMEM tile, row of the DMA semaphores `sems`)
    triples, and `window(dst, half, g)` is where block g of a group
    lands."""
    def start_copies(step, half):
        for g in range(group):
            # as in `_dma_walk_body`: a partial last group re-reads the
            # last live block, never an entry past the live range
            block = table_ref[slot, jnp.minimum(step * group + g, live - 1)]
            for src, dst, sem in pools:
                pltpu.make_async_copy(src.at[block], window(dst, half, g),
                                      sems.at[sem, half]).start()

    def wait_copies(half):
        # one wait an array: a DMA semaphore counts bytes, so a copy
        # the size of the whole half — never started, the half lends it
        # both shapes — waits for the group's (decode: 1.06 -> 0.97 ms
        # a layer; PERF.md, PR 28)
        for _, dst, sem in pools:
            pltpu.make_async_copy(dst.at[half], dst.at[half],
                                  sems.at[sem, half]).wait()

    return start_copies, wait_copies


def _softmax_step(scores, values, m_ref, l_ref, acc_ref):
    """One online-softmax step of a walk whose every query sees key 0
    (no guard): masked float32 `scores` [rows, keys] against `values`
    [keys, width] into the running max, normaliser and accumulator."""
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(scores - m_new)
    l_new = l_ref[:, :1] * alpha + probs.sum(axis=-1, keepdims=True)
    # P cast to the pool's dtype for the MXU, f32 accumulation
    pv = jnp.dot(probs.astype(values.dtype), values,
                 preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _latent_walk_body(table_ref, base_ref, ql_ref, qr_ref, c_hbm, kr_hbm,
                      o_ref, c_buf, kr_buf, sems, m_scr, l_scr, acc_scr, *,
                      block_size: int, group: int, entries: int,
                      scale: float):
    """One (slot, query-tile) grid step: the whole walk, `_dma_walk_body`
    for a pool whose row is one latent `c` [rank] — key AND value of
    every head — and one rotated key `kr` [lanes]. The `[tq * H, rank]`
    and `[tq * H, lanes]` queries attend a `[group * bs, rank | lanes]`
    tile in two 2-D dots whose sum is the scores, and the value product
    reads the `c` half again: no per-head loop, no "same head" mask —
    only `key_pos <= q_pos`, which also hides sentinel entries and the
    padding of a partial last group. Every query sees key 0, so no row
    is ever without a visible key and the softmax needs no guard."""
    slot, qtile = pl.program_id(0), pl.program_id(1)
    _, tq, heads, rank = ql_ref.shape
    rows, keys = tq * heads, group * block_size

    first = base_ref[slot] + qtile * tq        # this tile's first q pos
    live = _live_blocks(base_ref[slot], first + tq - 1, block_size,
                        entries, jnp)
    steps = (live + group - 1) // group

    start_copies, wait_copies = _walk_copies(
        table_ref, slot, live, group,
        ((c_hbm, c_buf, 0), (kr_hbm, kr_buf, 1)), sems,
        lambda dst, half, g: dst.at[half, g])

    _init_state(m_scr, l_scr, acc_scr)
    start_copies(0, 0)

    # rows (t, h), columns (block, row-in-block): how far each pair is
    # from the causal diagonal when the walk is at step 0
    shape = (rows, keys)
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 0) // heads)
    q_lat = ql_ref[0].reshape(rows, rank)
    q_rope = qr_ref[0].reshape(rows, qr_ref.shape[-1])
    nt = (((1,), (1,)), ((), ()))

    def attend(step, carry):
        half = step % 2

        @pl.when(step + 1 < steps)
        def _prefetch():
            start_copies(step + 1, 1 - half)

        wait_copies(half)
        c = c_buf[half].reshape(keys, rank)
        kr = kr_buf[half].reshape(keys, kr_buf.shape[-1])
        scores = (jax.lax.dot_general(q_lat, c, nt,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(q_rope, kr, nt,
                                        preferred_element_type=jnp.float32))
        scores = jnp.where(ahead <= first - step * keys, scores * scale,
                           NEG_INF)
        _softmax_step(scores, c, m_scr, l_scr, acc_scr)
        return carry

    jax.lax.fori_loop(0, steps, attend, 0)
    out = acc_scr[:] / l_scr[:, :1]
    o_ref[0] = out.reshape(tq, heads, rank).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("walk", "scale", "interpret"))
def _latent_call(q_lat, q_rope, entry, table, base, walk: Walk, *,
                 scale: float, interpret: bool):
    # jitted for the reason `_fused_call` is: the layers trace it once
    batch, queries, heads, rank = q_lat.shape
    lanes = entry["kr"].shape[-1]
    block_size = entry["c"].shape[-2]
    group, _, tq, _, _ = walk
    dtype = entry["c"].dtype

    def q_spec(width):
        return pl.BlockSpec((1, tq, heads, width),
                            lambda b, t, *_: (b, t, 0, 0))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the block table + the base positions
        grid=(batch, queries // tq),
        in_specs=[q_spec(rank), q_spec(lanes), hbm, hbm],
        out_specs=q_spec(rank),
        scratch_shapes=[
            pltpu.VMEM((2, group, block_size, rank), dtype),
            pltpu.VMEM((2, group, block_size, lanes), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((heads * tq, LANES), jnp.float32),  # running max
            pltpu.VMEM((heads * tq, LANES), jnp.float32),  # normalizer
            pltpu.VMEM((heads * tq, rank), jnp.float32)],  # accumulator
    )
    kernel = functools.partial(
        _latent_walk_body, block_size=block_size, group=group,
        entries=table.shape[1], scale=scale)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype,
                                       vma=jax.typeof(q_lat).vma),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=LATENT_VMEM_LIMIT),
        name="latent_decode_fused",
    )(table, base, q_lat, q_rope, entry["c"], entry["kr"])


def fused_latent_attention(cfg, q_lat: jax.Array, q_rope: jax.Array,
                           entry: tp.Dict, table: jax.Array,
                           positions: jax.Array, *,
                           interpret: tp.Optional[bool] = None
                           ) -> jax.Array:
    """`ops.paged_attention.latent_paged_attention`'s contract, one
    kernel: the absorbed queries q_lat [B, T, H, rank] and rotated
    q_rope [B, T, H, rope] against one layer's latent pool `entry`
    ({c, kr}, this step's rows already written) through `[B,
    max_blocks]` tables, causal by `positions` [B, T], which must be
    CONSECUTIVE per row as for `fused_paged_attention`. Neither the
    gathered `[B, L, rank]` view nor the `[B, H, T, L]` scores exist: a
    slot's live blocks are copied once a query tile and attended under
    an online softmax. Precision is the gather read's: operands in the
    pool's dtype, float32 scores and softmax state, probabilities cast
    to the pool's dtype for the value product, float32 accumulation,
    o_lat [B, T, H, rank] in `cfg.dtype`. `interpret=None` resolves as
    `fused_paged_attention` does."""
    from ..models.mla import softmax_scale
    from .paged_attention import latent_paged_attention
    if interpret is None:
        backend = jax.default_backend()
        if backend in ("gpu", "cuda", "rocm"):
            return latent_paged_attention(cfg, q_lat, q_rope, entry, table,
                                          positions)
        interpret = backend == "cpu"
    dtype = entry["c"].dtype
    lanes = entry["kr"].shape[-1] - q_rope.shape[-1]
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, lanes),))
    walk = latent_call_walk(q_lat.shape[1], q_lat.shape[2], entry,
                            entries=table.shape[1])
    base = jax.lax.slice_in_dim(positions, 0, 1, axis=1)[:, 0]
    out = _latent_call(q_lat.astype(dtype), q_rope.astype(dtype), entry,
                       table, base.astype(jnp.int32), walk,
                       scale=float(softmax_scale(cfg)), interpret=interpret)
    return out.astype(cfg.dtype)


# ----------------------------------------------------------------------
# the same walk over a grouped pool (models/gqa.py's full-attention
# layers: a row's KV heads side by side, keys wider than values)
# ----------------------------------------------------------------------
GROUPED_ROWS = 1024  # (query, head) rows of one part a grid step keeps
GROUPED_KEYS = 1024  # keys a compute step attends
GROUPED_VMEM_LIMIT = 48 * 2 ** 20  # stated, as LATENT_VMEM_LIMIT is


class HeadParts(tp.NamedTuple):
    """How a grouped read lays its query heads over a pool row's lanes:
    `len(k_starts)` parts of `heads` consecutive query heads each, a
    part's queries laid over the `k_width` K lanes from its `k_starts`
    and taking the values of the `v_width` V lanes from its `v_starts`
    — windows of whole 128 lanes, so no copy or slice in the kernel is
    narrower than the lanes."""
    heads: int
    k_width: int
    v_width: int
    k_starts: tp.Tuple[int, ...]
    v_starts: tp.Tuple[int, ...]


def head_parts(heads: int, kv_heads: int, dk: int, dv: int,
               flat: bool) -> HeadParts:
    """Query head h reads KV head `h // (heads / kv_heads)`, by one of
    two forms (`models/gqa.py:FLAT_QUERY_ROWS` says which). Flat — few
    rows, decode and verify: ONE part, every query head laid
    block-diagonally over the row's `kv_heads * dk` lanes, taking every
    KV head's values and keeping its own; the `kv_heads`-fold surplus of
    products is nothing beside the bytes. Split — a slice, which has
    the rows to fill the MXU: a part a KV head, its queries over the
    narrowest window of whole lanes that holds the head's keys (4 heads
    of 192: lanes 0-255, 128-383, 384-639, 512-767, the 64 lanes that
    are a neighbour's met by zeros), and its own values' lanes when
    those are whole (else the row's, kept as in the flat form)."""
    if flat:
        return HeadParts(heads, kv_heads * dk, kv_heads * dv, (0,), (0,))
    first = [kv * dk // LANES * LANES for kv in range(kv_heads)]
    width = max(-(-((kv + 1) * dk - start) // LANES) * LANES
                for kv, start in enumerate(first))
    k_starts = tuple(min(start, kv_heads * dk - width) for start in first)
    if dv % LANES:
        return HeadParts(heads // kv_heads, width, kv_heads * dv, k_starts,
                         (0,) * kv_heads)
    return HeadParts(heads // kv_heads, width, dv, k_starts,
                     tuple(kv * dv for kv in range(kv_heads)))


def _grouped_vmem_estimate(queries: int, parts: HeadParts, k_lanes: int,
                           v_lanes: int, block_size: int, group: int,
                           itemsize: int) -> int:
    """`_vmem_estimate` for `_grouped_walk_body`, from its own buffers:
    the pipelined q and o blocks of every part, the softmax state, the
    double-buffered K and V tiles, one part's score-shaped temporaries
    (the mask's distances, scores, probs and their cast) and its value
    product."""
    count = len(parts.k_starts)
    rows, keys = queries * parts.heads, group * block_size
    total = 2 * count * rows * (parts.k_width + parts.v_width) * itemsize
    total += count * rows * (2 * LANES + parts.v_width) * 4   # m, l, acc
    total += 2 * keys * (k_lanes + v_lanes) * itemsize        # K, V x2
    total += rows * keys * (3 * 4 + itemsize)                 # score-shaped
    total += rows * parts.v_width * 4                         # probs . v
    return total


def grouped_walk_shape(queries: int, heads: int, kv_heads: int, dk: int,
                       dv: int, block_size: int, entries: int, *,
                       itemsize: int) -> Walk:
    """The walk of one grouped read, from its shapes alone, as
    `latent_walk_shape`: at most `FLAT_QUERY_ROWS` query rows a slot
    take the flat form whole; a slice splits the heads and its T into
    query tiles of at most `GROUPED_ROWS` rows a part — whole sublanes
    of them, each tile walking its own causal prefix. Either attends
    `GROUPED_KEYS` keys a step (the cost of a decode read is per step
    and per copy, not per byte; a slice's step pays its state's update
    once, whatever the keys). Then group and tile halve in turn until
    `_grouped_vmem_estimate` fits `GROUPED_VMEM_LIMIT`. `head_block` is
    all the heads, the copies the kernel's own."""
    from ..models.gqa import FLAT_QUERY_ROWS
    flat = queries <= FLAT_QUERY_ROWS
    parts = head_parts(heads, kv_heads, dk, dv, flat)
    sublanes = SUBLANES * 4 // itemsize

    def largest(tile):
        # a q block is whole sublanes of rows, or all the rows
        return next((t for t in range(tile, 0, -1) if queries % t == 0
                     and (t * parts.heads) % sublanes == 0), queries)

    def fits(group, tile):
        return _grouped_vmem_estimate(
            tile, parts, kv_heads * dk, kv_heads * dv, block_size, group,
            itemsize) <= GROUPED_VMEM_LIMIT

    tile = queries if flat else largest(
        max(1, min(queries, GROUPED_ROWS // parts.heads)))
    group = max(1, min(GROUPED_KEYS // block_size, entries))
    while not fits(group, tile):
        if group > 1:
            group //= 2
        elif not flat and largest(tile // 2) < tile:
            tile = largest(tile // 2)
        else:
            break  # the smallest walk there is; Mosaic has the last word
    return Walk(group, heads, tile, flat, True)


def grouped_call_walk(cfg, kind, queries: int, *, block_size: int,
                      entries: int) -> Walk:
    """The walk `fused_grouped_attention` takes for `queries` rows a slot
    against the pool entry of a full-attention layer of kind `kind`
    (the engine asks too, for its `kv_steps` counter): a grouped pool is
    stored in `cfg.dtype`, its rows `kind.kv_heads` heads of
    `gqa.key_dim` | `gqa.value_dim`."""
    from ..models import gqa
    return grouped_walk_shape(
        queries, cfg.num_heads, kind.kv_heads, gqa.key_dim(cfg),
        gqa.value_dim(cfg), block_size, entries,
        itemsize=jnp.dtype(cfg.dtype).itemsize)


def _grouped_walk_body(table_ref, base_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                       block_size: int, group: int, entries: int,
                       parts: HeadParts, scale: float):
    """One (slot, query-tile) grid step: the whole walk, `_latent_walk_body`
    for a pool whose row holds every KV head's key `[Hkv * Dk]` and value
    `[Hkv * Dv]`. Whole K and V blocks land in `[group * bs, lanes]`
    tiles as stored; each of `parts` attends its window of the K tile's
    lanes with its `[tq * heads, k_width]` queries (rows (t, h), laid
    over the window by `_lay_queries`) in one 2-D dot, keeps its own
    online-softmax state and multiplies its window of the V tile. One
    mask, `key_pos <= q_pos`, for every part: it also hides sentinel
    entries and the padding of a partial last group. Every query sees
    key 0, so the softmax needs no guard."""
    slot, qtile = pl.program_id(0), pl.program_id(1)
    rows, keys = q_ref.shape[2], group * block_size
    tq = rows // parts.heads

    first = base_ref[slot] + qtile * tq        # this tile's first q pos
    live = _live_blocks(base_ref[slot], first + tq - 1, block_size,
                        entries, jnp)
    steps = (live + group - 1) // group

    start_copies, wait_copies = _walk_copies(
        table_ref, slot, live, group,
        ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)), sems,
        lambda dst, half, g: dst.at[half, pl.ds(g * block_size, block_size)])

    _init_state(m_scr, l_scr, acc_scr)
    start_copies(0, 0)

    # rows (t, h), columns (block, row-in-block): how far each pair is
    # from the causal diagonal when the walk is at step 0
    shape = (rows, keys)
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 0) // parts.heads)
    nt = (((1,), (1,)), ((), ()))

    def attend(step, carry):
        half = step % 2

        @pl.when(step + 1 < steps)
        def _prefetch():
            start_copies(step + 1, 1 - half)

        wait_copies(half)
        visible = ahead <= first - step * keys
        for part, (k_start, v_start) in enumerate(zip(parts.k_starts,
                                                      parts.v_starts)):
            k = k_buf[half, :, k_start:k_start + parts.k_width]
            v = v_buf[half, :, v_start:v_start + parts.v_width]
            scores = jax.lax.dot_general(
                q_ref[0, part], k, nt,
                preferred_element_type=jnp.float32) * scale
            _softmax_step(jnp.where(visible, scores, NEG_INF), v,
                          m_scr.at[part], l_scr.at[part], acc_scr.at[part])
        return carry

    jax.lax.fori_loop(0, steps, attend, 0)
    for part in range(len(parts.k_starts)):
        o_ref[0, part] = (acc_scr[part] / l_scr[part, :, :1]
                          ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("walk", "parts", "scale",
                                             "interpret"))
def _grouped_call(q, entry, table, base, walk: Walk, parts: HeadParts, *,
                  scale: float, interpret: bool):
    # jitted for the reason `_fused_call` is: the layers trace it once
    batch, count, rows, _ = q.shape
    k_lanes, v_lanes = entry["k"].shape[-1], entry["v"].shape[-1]
    block_size = entry["k"].shape[-2]
    keys = walk.group * block_size
    tile = walk.query_tile * parts.heads
    dtype = entry["k"].dtype

    def spec(width):
        return pl.BlockSpec((1, count, tile, width),
                            lambda b, t, *_: (b, 0, t, 0))

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # the block table + the base positions
        grid=(batch, rows // tile),
        in_specs=[spec(parts.k_width), hbm, hbm],
        out_specs=spec(parts.v_width),
        scratch_shapes=[
            pltpu.VMEM((2, keys, k_lanes), dtype),
            pltpu.VMEM((2, keys, v_lanes), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((count, tile, LANES), jnp.float32),  # running max
            pltpu.VMEM((count, tile, LANES), jnp.float32),  # normalizer
            pltpu.VMEM((count, tile, parts.v_width), jnp.float32)],  # acc
    )
    kernel = functools.partial(
        _grouped_walk_body, block_size=block_size, group=walk.group,
        entries=table.shape[1], parts=parts, scale=scale)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (batch, count, rows, parts.v_width), q.dtype,
            vma=jax.typeof(q).vma),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=GROUPED_VMEM_LIMIT),
        name="grouped_decode_fused",
    )(table, base, q, entry["k"], entry["v"])


def _lay_queries(q: jax.Array, parts: HeadParts, kv_heads: int) -> jax.Array:
    """q [B, T, H, Dk] -> [B, parts, T * heads a part, k_width]: each
    head's query on the lanes its KV head's keys hold within its part's
    window, zeros beside; rows (t, h)."""
    batch, queries, heads, dk = q.shape
    group = heads // kv_heads
    each = parts.heads // group  # KV heads a part covers
    laid = []
    for part, start in enumerate(parts.k_starts):
        kvs = range(part * each, (part + 1) * each)
        laid.append(jnp.concatenate([jnp.pad(
            q[:, :, kv * group:(kv + 1) * group],
            ((0, 0),) * 3 + ((kv * dk - start,
                              parts.k_width - (kv + 1) * dk + start),))
            for kv in kvs], axis=2))
    return jnp.stack(laid, axis=1).reshape(
        batch, len(laid), queries * parts.heads, parts.k_width)


def _own_values(out: jax.Array, parts: HeadParts, queries: int,
                kv_heads: int, dv: int) -> jax.Array:
    """The kernel's [B, parts, T * heads a part, v_width] -> [B, T, H,
    Dv]: a part whose window was the whole row took every KV head's
    values for every query head; its own are kept."""
    batch, count = out.shape[:2]
    heads = count * parts.heads
    out = out.reshape(batch, count, queries, parts.heads, -1)
    out = jnp.moveaxis(out, 1, 2).reshape(batch, queries, heads, -1)
    if parts.v_width == dv:  # each part took its own lanes
        return out
    own = (jnp.arange(heads)[:, None] // (heads // kv_heads)
           == jnp.arange(kv_heads)[None, :])                  # [H, Hkv]
    every = out.reshape(batch, queries, heads, kv_heads, -1)
    return jnp.sum(jnp.where(own[None, None, :, :, None], every,
                             jnp.zeros((), every.dtype)), axis=3)


def fused_grouped_attention(cfg, kind, q: jax.Array, entry: tp.Dict,
                            table: jax.Array, positions: jax.Array, *,
                            interpret: tp.Optional[bool] = None
                            ) -> jax.Array:
    """`models.gqa.attend` over `ops.paged_attention.grouped_table_view`
    for a FULL-attention layer of kind `kind`, one kernel: q [B, T, H,
    Dk] (rotated) against one layer's grouped pool `entry` ({k: [N, bs,
    Hkv * Dk], v: [N, bs, Hkv * Dv]}, this step's rows already written)
    through `[B, max_blocks]` tables, causal by `positions` [B, T],
    which must be CONSECUTIVE per row as for `fused_paged_attention`.
    Neither the gathered `[B, L, Hkv * Dk]` view nor the `[B, H, T, L]`
    scores exist: a slot's live blocks are copied once a query tile and
    attended under an online softmax, the heads laid out by
    `head_parts`. Precision is the gather read's: operands in the pool's
    dtype, float32 scores (scaled by `Dk ** -0.5` after the product) and
    softmax state, probabilities cast to the pool's dtype for the value
    product, float32 accumulation, [B, T, H, Dv] in `cfg.dtype`; the
    values are read as stored (scaled before they were cached). A window
    layer's ring is not a table's view: its read is `gqa.attend`'s.
    `interpret=None` resolves as `fused_paged_attention` does."""
    from ..models import gqa
    from .paged_attention import grouped_table_view
    if kind.window or kind.sink:
        raise ValueError(f"the grouped walk reads a full-attention layer "
                         f"through its block table, got {kind}")
    if interpret is None:
        backend = jax.default_backend()
        if backend in ("gpu", "cuda", "rocm"):
            return gqa.attend(cfg, kind, {}, q,
                              *grouped_table_view(entry, table), positions)
        interpret = backend == "cpu"
    queries, heads, dk = q.shape[1:]
    dv = gqa.value_dim(cfg)
    walk = grouped_call_walk(cfg, kind, queries,
                             block_size=entry["k"].shape[-2],
                             entries=table.shape[1])
    parts = head_parts(heads, kind.kv_heads, dk, dv, walk.flat)
    base = jax.lax.slice_in_dim(positions, 0, 1, axis=1)[:, 0]
    out = _grouped_call(
        _lay_queries(q.astype(entry["k"].dtype), parts, kind.kv_heads),
        entry, table, base.astype(jnp.int32), walk, parts,
        scale=float(dk ** -0.5), interpret=interpret)
    return _own_values(out, parts, queries, kind.kv_heads,
                       dv).astype(cfg.dtype)
