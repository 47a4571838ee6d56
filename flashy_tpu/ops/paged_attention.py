# Paged KV attention — the block-pool counterpart of the dense slab
# reads/writes in models/decoding.py. The dense serving cache reserves
# every slot's worst case ([S, max_seq_len]) up front, so HBM — not the
# MXU — caps concurrency. Here K and V live in one global pool of
# fixed-size blocks `[num_blocks, block_size, heads, head_dim]`, and
# each slot owns a per-slot BLOCK TABLE `[max_blocks]` of pool
# indices: logical position p of a slot maps to physical row
# `(table[p // block_size], p % block_size)`. Liveness, table contents
# and positions are all INPUTS — never shapes — so the ONE-executable-
# per-shape serving invariant survives: the same compiled decode/verify
# step runs whatever mix of slots, tables and shared blocks is live.
#
# Two proofs carry over from the dense path:
#  * Sentinel right-padding: physical block 0 is reserved as the
#    sentinel; unassigned table entries point at it. A sentinel
#    entry at table index j
#    covers logical positions [j*bs, (j+1)*bs), all beyond the slot's
#    causal horizon until a real block replaces it — so its content is
#    never attended, exactly the dense right-padding proof. Writes from
#    parked slots (position == max_seq_len) and verify-overshoot rows
#    redirect to the sentinel (a where on the block id, not a wider
#    table) and are garbage-by-design, the paged spelling of
#    mode="drop".
#  * Purity of K/V rows: a cached row is a pure function of
#    (token, position, params) — no dependence on neighbouring tokens —
#    which is what makes cross-request prefix sharing and partial-block
#    copy-on-write forks exact (serve/paged.py).
#
# Reads are gather-based: each slot gathers its table's blocks into a
# logical [max_len] view and attends it under the ordinary causal
# mask. XLA lowers the gather + (optional int8 dequant) into the
# attention operand read; nothing dense is materialized per step beyond
# the gathered keys the dense path would read anyway — the logical view
# is exactly the dense slab's size.
"""Gather-based paged attention over a block-pool KV cache."""
import typing as tp

import jax
import jax.numpy as jnp

from ..models.quantize import dequantize_kv, quantize_kv

# Physical block 0 is the sentinel: never allocated by the serve-side
# BlockPool, target of every out-of-coverage write, content never
# attended (sentinel table entries only cover logical positions beyond
# the causal horizon).
SENTINEL_BLOCK = 0


def pool_spec(num_blocks: int, block_size: int, num_heads: int,
              head_dim: int, dtype, kv_dtype: str
              ) -> tp.Dict[str, tp.Tuple[tp.Tuple[int, ...], tp.Any]]:
    """Leaf name -> (shape, dtype) of ONE layer's pool entry.

    `kv_dtype='int8'` stores int8 payloads plus per-(row, head) f32
    scales beside them (models/quantize.quantize_kv); any other value
    stores dense K/V in the model's compute dtype. A block's scales are
    ONE row of `block_size * num_heads` values in (row-in-block, head)
    order, `[N, 1, block_size * H]`: the rows the fused read copies
    beside its K/V blocks, as stored. `[N, block_size, H]` put N on the
    lanes of the v5e (LANES below) and every decode step and prefill
    slice paid three whole-leaf relayout copies a scale leaf a layer
    (PERF.md section 6, PR 30); `scale_rows` / `write_scale_rows` are
    the only readers and writers of the order.
    """
    shape = (num_blocks, block_size, num_heads, head_dim)
    if kv_dtype == "int8":
        rows = (num_blocks, 1, block_size * num_heads)
        return {"k": (shape, jnp.int8), "v": (shape, jnp.int8),
                "k_scale": (rows, jnp.float32),
                "v_scale": (rows, jnp.float32)}
    return {"k": (shape, dtype), "v": (shape, dtype)}


# A TPU stores an array in (8, 128) tiles. A leaf whose minor dimension
# is not whole lanes gets another dimension on the lanes ([N, 16, 64]
# and [N, 16, 576] are laid out with N minor: seen by compiling for the
# v5e), and every step then pays relayout copies around the gather.
LANES = 128


def latent_pool_spec(num_blocks: int, block_size: int, rank: int,
                     rope_dim: int, dtype
                     ) -> tp.Dict[str, tp.Tuple[tp.Tuple[int, ...], tp.Any]]:
    """ONE layer's entry of a latent pool (models/mla.py): `c`
    [N, bs, rank], the normed latent (key AND value of every head),
    and `kr` [N, bs, rope_dim rounded up to whole lanes], the rotated
    shared key, zero beyond rope_dim. Two leaves so that a block of each
    is whole (8, 128) tiles a kernel can copy, and the value is a leaf
    of its own, not a slice of a 576-wide row. The padding is stored,
    so the spec — and `pool_bytes` from it — counts it."""
    return {"c": ((num_blocks, block_size, rank), dtype),
            "kr": ((num_blocks, block_size, -(-rope_dim // LANES) * LANES),
                   dtype)}


def cfg_pool_spec(cfg, num_blocks: int, block_size: int, kv_dtype: str
                  ) -> tp.Dict[str, tp.Tuple[tp.Tuple[int, ...], tp.Any]]:
    """The pool entry of one layer of `cfg`: latent for
    `attn_kind='mla'`, per-head K/V otherwise. Everything that sizes,
    allocates or copies a pool follows this spec."""
    if getattr(cfg, "attn_kind", "mha") == "mla":
        if kv_dtype != "model":
            raise ValueError(
                f"kv_dtype={kv_dtype!r} keeps a scale per row and head; a "
                f"latent pool (attn_kind='mla') has no heads in its rows: "
                f"use kv_dtype='model'")
        return latent_pool_spec(num_blocks, block_size, cfg.kv_lora_rank,
                                cfg.qk_rope_head_dim, cfg.dtype)
    return pool_spec(num_blocks, block_size, cfg.num_heads, cfg.head_dim,
                     cfg.dtype, kv_dtype)


def grouped_pool_spec(num_blocks: int, block_size: int, kv_heads: int,
                      key_dim: int, value_dim: int, dtype
                      ) -> tp.Dict[str, tp.Tuple[tp.Tuple[int, ...], tp.Any]]:
    """ONE layer's entry of a grouped-attention pool (models/gqa.py):
    `k` [N, bs, Hkv * Dk] and `v` [N, bs, Hkv * Dv], a row's heads side
    by side on the lanes. The layer's own KV head count, and a key width
    that need not be the value's nor whole lanes: 4 heads of 192 are 768
    lanes, whole (8, 128) tiles as stored, where [N, bs, 4, 192] would
    be padded to 256 a head or relaid out (`LANES` above)."""
    return {"k": ((num_blocks, block_size, kv_heads * key_dim), dtype),
            "v": ((num_blocks, block_size, kv_heads * value_dim), dtype)}


def ring_blocks(window: int, rows: int, block_size: int) -> int:
    """Blocks of one slot's ring in a window layer: room for the
    `window - 1` positions a query sees behind it and the `rows`
    positions the largest step writes (a prefill slice, the verify
    step's drafts), so that no row a query of the step still sees is
    overwritten by a later row of the same step."""
    return -(-(window - 1 + rows) // block_size)


def layer_pool_specs(cfg, num_blocks: int, block_size: int, kv_dtype: str,
                     *, slots: int = 0, ring: int = 0) -> tp.List[tp.Dict]:
    """Per layer, the pool entry `cfg_pool_spec` gives every layer
    alike — or, for `attn_kind='gqa'`, the entry of the layer's kind:
    a full-attention layer pages through the block table as every K/V
    layer does (`num_blocks` blocks of its own head count), a window
    layer holds `[1 + slots, ring * block_size, F]`: a sentinel and one
    static ring of `ring` blocks' rows a slot (`ring_address`),
    whatever the context. Under a `layer_pattern` only the '*' layers
    hold K and V; a Mamba-2 layer ('M') holds the third kind of state a
    slot: `state` f32 `[1 + slots, H, P, N]` and `conv` `[1 + slots,
    taps - 1, channels]` (`models.mamba2.state_spec`), indexed by slot
    and not by position, entry 0 the sentinel that parked rows write;
    an expert layer ('E') holds nothing."""
    pattern = getattr(cfg, "layer_pattern", "")
    grouped = getattr(cfg, "attn_kind", "mha") == "gqa"
    if not grouped and not pattern:
        return [cfg_pool_spec(cfg, num_blocks, block_size, kv_dtype)
                ] * cfg.num_layers
    specs: tp.List[tp.Dict] = [{}] * cfg.num_layers
    if grouped:
        from ..models import gqa
        if kv_dtype != "model":
            raise ValueError(
                f"kv_dtype={kv_dtype!r} keeps a scale per row and head in "
                f"rows laid out for one head count and one head width; a "
                f"grouped pool (attn_kind='gqa') has a head count a layer "
                f"kind and unequal key and value widths: use "
                f"kv_dtype='model'")
        specs = [grouped_pool_spec(
                     *((1 + slots, ring * block_size) if kind.window
                       else (num_blocks, block_size)),
                     kind.kv_heads, gqa.key_dim(cfg), gqa.value_dim(cfg),
                     cfg.dtype)
                 for kind in gqa.layer_kinds(cfg)]
    if pattern:
        from ..models import mamba2
        specs = [spec if kind == "*" else
                 mamba2.state_spec(cfg, 1 + slots) if kind == "M" else {}
                 for spec, kind in zip(specs, pattern)]
    return specs


def init_pool(cfg, num_blocks: int, block_size: int,
              kv_dtype: str = "model", *, slots: int = 0,
              ring: int = 0) -> tp.Dict:
    """Allocate the block-pool cache pytree for a TransformerLM config.

    Mirrors models/decoding.init_cache's structure so the rest of the
    decode step is layout-agnostic: per-layer models get one entry per
    block_i (`layer_pool_specs`: a window layer's is `slots` rings of
    `ring` blocks); scan-stacked models get stacked [L, N, bs, H, Dh]
    leaves scanned together with the stacked parameters. Block 0 is the
    sentinel (ops-level convention; serve/paged.BlockPool never hands
    it out).
    """
    if cfg.scan_layers:
        spec = cfg_pool_spec(cfg, num_blocks, block_size, kv_dtype)
        return {name: jnp.zeros((cfg.num_layers,) + shape, dt)
                for name, (shape, dt) in spec.items()}
    specs = layer_pool_specs(cfg, num_blocks, block_size, kv_dtype,
                             slots=slots, ring=ring)
    return {f"block_{i}": {name: jnp.zeros(shape, dt)
                           for name, (shape, dt) in spec.items()}
            for i, spec in enumerate(specs)}


def _physical(table: jax.Array, positions: jax.Array, block_size: int
              ) -> tp.Tuple[jax.Array, jax.Array]:
    """Logical positions [B, T] -> (pool block [B, T], offset [B, T]).

    Positions past the table's coverage (a parked slot at max_seq_len,
    a verify overshoot row) redirect to the SENTINEL block — not a
    clamp onto a real block's final row, which would corrupt the last
    genuinely-written position. This is the paged spelling of the
    dense path's mode="drop", as data instead of as an extra table
    column (a wider table would make every attention gather read
    block_size more keys than the dense layout for nothing).
    """
    index = positions // block_size
    block = jnp.take_along_axis(
        table, jnp.minimum(index, table.shape[-1] - 1), axis=-1)
    block = jnp.where(index >= table.shape[-1], SENTINEL_BLOCK, block)
    return block, positions % block_size


def latent_paged_write(entry: tp.Dict, c_kv: jax.Array, k_rope: jax.Array,
                       table: jax.Array, positions: jax.Array) -> tp.Dict:
    """`paged_write` for a latent pool ({c, kr}): the rows' normed
    latents `[B, T, rank]` and rotated shared keys `[B, T, rope]`, the
    key padded to the lanes it is stored on."""
    block, offset = _physical(table, positions, entry["c"].shape[-2])
    lanes = entry["kr"].shape[-1] - k_rope.shape[-1]
    k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, lanes)))
    return {name: entry[name].at[block, offset].set(
                new.astype(entry[name].dtype))
            for name, new in (("c", c_kv), ("kr", k_rope))}


def ring_address(slots: jax.Array, positions: jax.Array, cells: int
                 ) -> tp.Tuple[jax.Array, jax.Array]:
    """Positions [B, T] of slots [B] -> (ring [B, T], cell [B, T]) in a
    window layer's entry `[1 + slots, cells, F]`: position p of slot s
    lies in ring `1 + s` at cell `p % cells`, from the positions the
    step already has — no allocation, no free, no table. A row that
    belongs to no slot (-1: a parked row of the decode step, whose slot
    may be mid-prefill) goes to the sentinel, ring 0."""
    return (jnp.broadcast_to(1 + slots[:, None], positions.shape),
            positions % cells)


def ring_view(entry: tp.Dict, slots: jax.Array, positions: jax.Array
              ) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    """The rings of `slots` [B] out of a window layer's entry, as
    (k [B, cells, Hkv * Dk], v [B, cells, Hkv * Dv], the position each
    cell holds [B, cells]) once the step at `positions` is written:
    cell c holds the positions p = c (mod cells), `ring_positions` says
    which."""
    k_view = entry["k"][1 + slots]
    return k_view, entry["v"][1 + slots], ring_positions(
        positions, k_view.shape[1])


def ring_positions(positions: jax.Array, cells: int) -> jax.Array:
    """The position each of a ring's `cells` rows holds once the step
    whose rows are at `positions` [B, T] is written: the largest
    p <= the step's last row with p = c (mod cells); negative where the
    slot's request has not come that far (what lies there is an earlier
    request's). [B, cells]."""
    last = positions[:, -1:]
    return last - (last - jnp.arange(cells, dtype=positions.dtype)) % cells


def grouped_write(entry: tp.Dict, new_k: jax.Array, new_v: jax.Array,
                  block: jax.Array, offset: jax.Array) -> tp.Dict:
    """Write fresh rows k [B, T, Hkv, Dk], v [B, T, Hkv, Dv] into a
    grouped pool entry ({k, v}: `grouped_pool_spec`) at (block, offset)
    [B, T] — or (ring, cell) of a window layer's — a row's heads side by
    side."""
    return {name: entry[name].at[block, offset].set(
                new.reshape(new.shape[:2] + (-1,)).astype(entry[name].dtype))
            for name, new in (("k", new_k), ("v", new_v))}


def grouped_table_view(entry: tp.Dict, table: jax.Array
                       ) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    """Each row's logical view of a full-attention layer's entry through
    its block table [B, E]: (k [B, E * bs, Hkv * Dk], v [B, E * bs,
    Hkv * Dv], the position each row holds [B, E * bs]: row s holds
    position s); sentinel entries past every causal horizon, as
    `gather_kv`'s."""
    def view(leaf):
        g = leaf[table]                                 # [B, E, bs, F]
        return g.reshape(g.shape[0], -1, g.shape[-1])
    k_view = view(entry["k"])
    return k_view, view(entry["v"]), jnp.broadcast_to(
        jnp.arange(k_view.shape[1], dtype=table.dtype), k_view.shape[:2])


def scale_rows(scales: jax.Array, blocks: jax.Array, num_heads: int
               ) -> jax.Array:
    """The scales of pool blocks `blocks` [...] out of a scale leaf
    `[N, 1, block_size * H]` (`pool_spec`), as `[..., block_size, H]`.
    Each block's row is a `dynamic_slice` naming all three dimensions:
    the v5e's compiler gathers those from the leaf as stored, where
    `scales[blocks]` (an index for the first dimension alone) had it
    relay the whole leaf out to (8, 128) tiles first, a slice's copy a
    layer (seen by compiling `chunk_paged` for the chip, PR 30)."""
    rows = jax.vmap(lambda block: jax.lax.dynamic_slice_in_dim(
        scales, block, 1))(blocks.reshape(-1))
    return rows.reshape(blocks.shape + (-1, num_heads))


@jax.jit
def write_scale_rows(scales: jax.Array, new: jax.Array, block: jax.Array,
                     offset: jax.Array) -> jax.Array:
    """Write fresh rows' scales `new` [B, T, H] into a scale leaf
    `[N, 1, block_size * H]`: row (block, offset) owns the H values from
    `offset * H` of its block's row. The unit the device can move
    without relaying the leaf out is a block's whole row, so this is a
    read-modify-write of rows: each fresh row gathers its block's row,
    takes into it EVERY row this call writes to that block (a chunk puts
    `block_size` rows in one block, so the rows written for them are
    equal and their order cannot matter), and the rows go back whole
    (`_scatter_rows`). A scatter of the H-wide windows themselves is a
    loop of B * T updates of 3 us on the v5e: it made a 256-token slice
    27 ms longer (PERF.md section 6, PR 30). Blocks written by two batch
    rows at once are the sentinel's alone: garbage by design. Jitted
    for the reason `paged_decode._fused_call` is: a model's layers trace
    and lower the kernel once between them (unrolled, 32 lowerings an
    executable were 2 s of the chat cell's 7 s engine set-up)."""
    heads = new.shape[-1]
    old = scale_rows(scales, block, heads)              # [B, T, bs, H]
    in_block = jnp.arange(old.shape[2], dtype=offset.dtype)
    # hit[b, t, j, u]: row u of slot b writes offset j of row t's block
    hit = ((block[:, :, None, None] == block[:, None, None, :])
           & (offset[:, None, None, :] == in_block[None, None, :, None]))
    fresh = jax.vmap(lambda rows, at: rows[at])(new.astype(scales.dtype),
                                                jnp.argmax(hit, axis=-1))
    rows = jnp.where(hit.any(axis=-1)[..., None], fresh, old)
    return _scatter_rows(scales, rows.reshape((-1,) + scales.shape[1:]),
                         block.reshape(-1))


def _scatter_rows(leaf: jax.Array, rows: jax.Array, blocks: jax.Array
                  ) -> jax.Array:
    """`leaf[blocks[r]] = rows[r]` for whole rows `[R, 1, W]` of a scale
    leaf `[N, 1, W]`, in place. On a TPU the leaf lies in (1, 128) tiles
    (`pool_spec`), where XLA has no scatter of its own: it relays the
    whole leaf out to (8, 128) tiles and back around one, or runs a
    loop of updates. A kernel that copies the rows does neither
    (`_row_copies`); elsewhere XLA's scatter is the write."""
    if jax.default_backend() in ("cpu", "gpu", "cuda", "rocm"):
        return leaf.at[blocks].set(rows)
    return _row_copies(leaf, rows, blocks, interpret=False)


def _row_copies(leaf: jax.Array, rows: jax.Array, blocks: jax.Array, *,
                interpret: bool) -> jax.Array:
    """`_scatter_rows` as a Pallas TPU kernel: the leaf stays in HBM,
    aliased to the output, and every row is one async copy out of VMEM,
    all in flight together. Rows that name one block are equal or the
    sentinel's (`write_scale_rows`), so the copies need no order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(block_ref, rows_ref, leaf_ref, out_ref, sem):
        del leaf_ref  # aliased to out_ref: rows no copy names keep theirs

        def copy(row):
            return pltpu.make_async_copy(
                rows_ref.at[row], out_ref.at[block_ref[row]], sem)

        def start(row, carry):
            copy(row).start()
            return carry

        def wait(row, carry):
            copy(row).wait()
            return carry

        jax.lax.fori_loop(0, rows_ref.shape[0], start, 0)
        jax.lax.fori_loop(0, rows_ref.shape[0], wait, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the rows' blocks
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), hbm],
        out_specs=hbm, scratch_shapes=[pltpu.SemaphoreType.DMA(())])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        input_output_aliases={2: 0}, interpret=interpret,
        name="paged_scale_write",
    )(blocks.astype(jnp.int32), rows, leaf)


def paged_write(entry: tp.Dict, new_k: jax.Array, new_v: jax.Array,
                table: jax.Array, positions: jax.Array) -> tp.Dict:
    """Write fresh K/V rows `[B, T, H, Dh]` through the block tables.

    `entry` is one layer's pool dict ({k, v} or {k, v, k_scale,
    v_scale}); `table` is [B, max_blocks]; `positions` [B, T] are
    the rows' ABSOLUTE positions (every row lands at its own physical
    (block, offset) — the per-row write path decode, verify and chunked
    prefill all share). int8 pools quantize at the write (per-row
    absmax, models/quantize.quantize_kv) so the pool never holds a
    dense copy.
    """
    block, offset = _physical(table, positions, entry["k"].shape[-3])
    out = dict(entry)
    for name, new in (("k", new_k), ("v", new_v)):
        if f"{name}_scale" in entry:
            q, scale = quantize_kv(new)
            out[name] = entry[name].at[block, offset].set(q)
            out[f"{name}_scale"] = write_scale_rows(
                entry[f"{name}_scale"], scale, block, offset)
        else:
            out[name] = entry[name].at[block, offset].set(
                new.astype(entry[name].dtype))
    return out


def gather_kv(entry: tp.Dict, table: jax.Array, dtype
              ) -> tp.Tuple[jax.Array, jax.Array]:
    """Gather one layer's logical K/V views for a batch of tables.

    Returns (k, v) of shape [B, max_blocks * bs, H, Dh] in
    `dtype`: each slot's blocks concatenated in logical order,
    sentinel entries included (they sit past every causal horizon, so
    the attention mask — not the gather — keeps them out). int8 pools
    dequantize inline; XLA fuses gather + convert + scale into the
    attention operand read.
    """
    batch, entries = table.shape

    def view(name):
        g = entry[name][table]              # [B, E, bs, H, Dh]
        if f"{name}_scale" in entry:
            g = dequantize_kv(g, scale_rows(entry[f"{name}_scale"], table,
                                            g.shape[3]), dtype)
        return g.astype(dtype).reshape(batch, entries * g.shape[2],
                                       *g.shape[3:])

    return view("k"), view("v")


def paged_attention(q: jax.Array, entry: tp.Dict, table: jax.Array,
                    positions: jax.Array, *, head_dim: int,
                    dtype) -> jax.Array:
    """Causal attention of queries against a slot-paged KV pool.

    Args:
        q: [B, T, H, Dh] queries (already rotary-embedded).
        entry: one layer's pool dict (K/V already written for this
            step's rows — mirrors the dense path, where the cache write
            precedes the attend so a query sees itself).
        table: [B, max_blocks] int32 block tables.
        positions: [B, T] absolute query positions (drive the causal
            mask: key logical position <= query position, which also
            masks every sentinel entry — sentinels only occupy logical
            positions beyond the slot's horizon).
        head_dim: cfg.head_dim (scores scale).
        dtype: compute dtype for the gathered K/V and the probs @ V.

    Returns [B, T, H, Dh] attention outputs, f32 score accumulation —
    the dense `_cached_self_attention` math over the same logical
    rows. int8 pools fold the per-row scales into the SCORES (for K)
    and the PROBS (for V) rather than dequantizing the gathered view:
    the scale is constant over the contracted head_dim, so
    `(q . k_int8) * s == q . (k_int8 * s)` up to float rounding, and
    the multiply shrinks from a [B, L, H, Dh] tensor to the
    [B, H, T, L] scores — 1/head_dim the work on the bandwidth-bound
    read path. This placement is a CONTRACT, not an implementation
    detail: the FT203 numerics auditor structurally verifies it
    against this function's jaxpr (each scale applied exactly once, K
    pre-softmax, V post-softmax), so a fused/Pallas rewrite that
    double-, un- or wrong-side-scales fails `make analyze-numerics`.
    """
    batch, entries = table.shape

    def view(name):
        g = entry[name][table]              # [B, E, bs, H, Dh]
        g = g.reshape(batch, entries * g.shape[2], *g.shape[3:])
        s = entry.get(f"{name}_scale")
        if s is not None:
            # [B, E, bs, H] -> [B, H, 1, L] to broadcast over scores
            s = scale_rows(s, table, g.shape[2]).reshape(
                batch, g.shape[1], g.shape[2])
            s = s.transpose(0, 2, 1)[:, :, None, :]
        return g.astype(dtype), s

    k_view, k_scale = view("k")
    v_view, v_scale = view("v")
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_view,
                        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        scores = scores * k_scale
    key_pos = jnp.arange(k_view.shape[1])[None, :]
    mask = key_pos[None] <= positions[:, :, None]
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dtype), v_view)


def latent_paged_attention(cfg, q_lat: jax.Array, q_rope: jax.Array,
                           entry: tp.Dict, table: jax.Array,
                           positions: jax.Array) -> jax.Array:
    """The cached form of latent attention against a latent pool: each
    row's table gathers its blocks into the logical views c [B, L, rank]
    and kr [B, L, rope] (sentinel entries past every causal horizon, as
    above), and `models.mla.cached_attention` attends them — the same
    function the dense slab calls, query tiles and all. The rotated
    queries are zero-padded to the `kr` leaf's stored width (the scores
    are the same; the gathered view is not sliced). This step's rows
    are already written. Returns o_lat [B, T, H, rank]. The XLA read
    of a latent pool: what `kernel='gather'` runs, the oracle of
    `ops.paged_decode.fused_latent_attention` (which never builds the
    views or the scores) and the read of pools that kernel refuses."""
    from ..models.mla import cached_attention
    batch, entries = table.shape

    def view(name):
        g = entry[name][table]              # [B, E, bs, width]
        return g.reshape(batch, entries * g.shape[2], g.shape[3])

    lanes = entry["kr"].shape[-1] - q_rope.shape[-1]
    q_rope = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, lanes),))
    return cached_attention(cfg, q_lat, q_rope, view("c"), view("kr"),
                            positions)


def slot_kv(entry: tp.Dict, table_row, length: int, dtype=jnp.float32
            ) -> tp.Tuple[jax.Array, jax.Array]:
    """Read back one slot's logical K/V rows [length, H, Dh].

    The test/debug readback: gathers the slot's table through the same
    path the attention uses, truncated to the live prefix — what the
    bit-identity proofs (paged vs fresh prefill, COW isolation) compare.
    """
    k, v = gather_kv(entry, jnp.asarray(table_row, jnp.int32)[None], dtype)
    return k[0, :length], v[0, :length]


def pool_bytes(cfg, num_blocks: int, block_size: int,
               kv_dtype: str = "model", *, slots: int = 0,
               ring: int = 0) -> int:
    """Total HBM bytes of the pool across layers (capacity planning),
    each layer by its kind: with `slots` rings of `ring` blocks, window
    layers count those and their sentinel ring (`window_bytes`) and no
    block of the `num_blocks` that grow.

    Pure host arithmetic — the scheduler consults it every step for
    the bytes-per-token gauge, so no jnp ops belong here.
    """
    return sum(map(_entry_bytes, layer_pool_specs(
        cfg, num_blocks, block_size, kv_dtype, slots=slots, ring=ring)))


def _entry_bytes(spec: tp.Dict) -> int:
    """Bytes of one layer's entry, from its spec."""
    import math

    import numpy as np
    return sum(np.dtype(dt).itemsize * math.prod(shape)
               for shape, dt in spec.values())


def window_bytes(cfg, block_size: int, *, slots: int, ring: int) -> int:
    """HBM bytes of the window layers' rings (and sentinels): fixed,
    whatever the contexts. 0 for a config without window layers."""
    return (pool_bytes(cfg, 0, block_size, slots=slots, ring=ring)
            - state_bytes(cfg, slots) if slots * ring else 0)


def state_bytes(cfg, slots: int) -> int:
    """HBM bytes of the recurrent layers' entries (`state` and `conv`,
    the sentinel's too) for `slots` slots: fixed, whatever the
    contexts. 0 for a config without Mamba-2 layers."""
    pattern = getattr(cfg, "layer_pattern", "")
    if "M" not in pattern:
        return 0
    from ..models import mamba2
    return pattern.count("M") * _entry_bytes(
        mamba2.state_spec(cfg, 1 + slots))


def token_bytes(cfg, kv_dtype: str = "model") -> tp.Tuple[int, int]:
    """Bytes as stored that ONE cached token costs (the layers whose
    pool grows with the context, the window layers): a block of one
    row, and one row `[Hkv * width]` of each leaf of each window
    layer's entry."""
    import numpy as np

    from ..models import gqa
    rows = 0
    if gqa.has_window(cfg):
        specs = layer_pool_specs(cfg, 1, 1, kv_dtype, slots=1, ring=1)
        rows = sum(np.dtype(dt).itemsize * shape[-1]
                   for spec, kind in zip(specs, gqa.layer_kinds(cfg))
                   if kind.window for shape, dt in spec.values())
    return block_bytes(cfg, 1, kv_dtype), rows


def block_bytes(cfg, block_size: int, kv_dtype: str = "model") -> int:
    """HBM bytes ONE block of the pool that grows costs across the
    layers that page through the table (admission accounting): every
    layer, but for a window layer, which is counted by `window_bytes`."""
    return (pool_bytes(cfg, 1, block_size, kv_dtype)
            - pool_bytes(cfg, 0, block_size, kv_dtype))
