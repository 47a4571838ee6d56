# The fused walk over a grouped pool (ops/paged_decode.py:
# fused_grouped_attention) in Pallas interpret mode on the CPU, against
# the read it replaces on a TPU in a full-attention layer — the XLA
# gather of the table's view (ops/paged_attention.py:grouped_table_view)
# under models/gqa.py:attend, the oracle — at the benchmark cell's head
# geometry (64 query heads over 4 KV heads, keys 192, values 128, blocks
# of 16) over small tables; then through a toy engine whose blocks are
# whole tiles (compiled — not run — for the v5e at the cell's shapes in
# tests/test_latent_decode.py, the one file that loads the TPU's
# compiler). Every tolerance states its reason.
"""The grouped pool's fused read against the gather read."""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.harness import model_mimo
from flashy_tpu.models import TransformerConfig, TransformerLM, gqa
from flashy_tpu.models.decoding import generate
from flashy_tpu.ops import paged_decode
from flashy_tpu.ops.paged_attention import (grouped_pool_spec,
                                            grouped_table_view)
from flashy_tpu.ops.paged_decode import (HeadParts, Walk,
                                         fused_grouped_attention,
                                         grouped_call_walk, head_parts,
                                         walk_counts)
from flashy_tpu.serve import (ContinuousBatchingScheduler, DecodeEngine,
                              NGramDraft)
from tests.test_hybrid_attention import TOY

HEADS, KV_HEADS, DK, DV, BLOCK, ENTRIES = 64, 4, 192, 128, 16, 8
LENGTH = ENTRIES * BLOCK
# float32: the same products summed a tile at a time under a running
# maximum; bfloat16: one rounding of the probabilities and of the output
# (2^-8 of values of a few units), the other walks' tests' tolerance
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def cell_cfg(dtype, length=LENGTH):
    """One full-attention layer of the cell's head geometry."""
    return TransformerConfig(
        attn_kind="gqa", attention="dense", num_heads=HEADS,
        num_kv_heads=KV_HEADS, qk_head_dim=DK, v_head_dim=DV, dim=64,
        num_layers=1, dtype=dtype, max_seq_len=length)


def _pool(dtype, blocks, seed=0):
    rng = np.random.default_rng(seed)
    spec = grouped_pool_spec(blocks, BLOCK, KV_HEADS, DK, DV, dtype)
    return {name: jnp.asarray(rng.normal(size=shape), dt)
            for name, (shape, dt) in spec.items()}, rng


def _both(cfg, entry, table, bases, queries, rng):
    kind, = gqa.layer_kinds(cfg)
    q = jnp.asarray(rng.normal(size=(table.shape[0], queries, HEADS, DK)),
                    cfg.dtype)
    positions = (jnp.asarray(bases, jnp.int32)[:, None]
                 + jnp.arange(queries, dtype=jnp.int32)[None])
    want = gqa.attend(cfg, kind, {}, q, *grouped_table_view(entry, table),
                      positions)
    got = fused_grouped_attention(cfg, kind, q, entry, table, positions)
    assert got.shape == want.shape == (table.shape[0], queries, HEADS, DV)
    assert got.dtype == want.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _small_tiles(monkeypatch):
    """8 query positions a tile (a part keeps 8 x 16 rows) and 3 blocks
    a step: a slice splits into query tiles that straddle blocks, and no
    live range is whole groups."""
    monkeypatch.setattr(paged_decode, "GROUPED_ROWS", 8 * HEADS // KV_HEADS)
    monkeypatch.setattr(paged_decode, "GROUPED_KEYS", 3 * BLOCK)


@pytest.mark.parametrize("queries", [1, 4, 5, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("tiles", ["whole", "small"])
def test_fused_grouped_read_matches_the_gather(monkeypatch, dtype, queries,
                                               tiles):
    # Ragged contexts: a slot on its first block (a slice at offset 0),
    # one mid-table at a position no block or tile boundary divides, one
    # whose last row is the table's last (a late slice), and a parked
    # slot (base == max_seq_len) whose rows the engine discards. T = 1,
    # 4 and 5 (decode, the tail slice, verify) take the block-diagonal
    # form, the 32-row slice splits the heads; 'small' also splits it
    # into query tiles and ends every walk in a partial group.
    if tiles == "small":
        _small_tiles(monkeypatch)
    cfg = cell_cfg(dtype)
    entry, rng = _pool(dtype, 1 + 4 * ENTRIES)
    table = jnp.asarray(1 + rng.permutation(4 * ENTRIES).reshape(4, ENTRIES),
                        jnp.int32)
    bases = [0, 13 + 2 * BLOCK, LENGTH - queries, LENGTH]
    got, want = _both(cfg, entry, table, bases, queries, rng)
    walk = grouped_call_walk(cfg, gqa.layer_kinds(cfg)[0], queries,
                             block_size=BLOCK, entries=ENTRIES)
    assert walk.flat == (queries <= gqa.FLAT_QUERY_ROWS)
    if tiles == "small":
        assert walk.group == 3
        assert walk.query_tile == {1: 1, 4: 4, 5: 5, 32: 8}[queries]
    else:
        assert walk == Walk(ENTRIES, HEADS, queries, queries <= 8, True)
    np.testing.assert_allclose(got[:3], want[:3], atol=TOL[dtype])
    assert np.isfinite(got).all()  # the parked slot's rows too


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_all_sentinel_tables_and_forked_prefixes_read_like_the_gather(
        monkeypatch, dtype):
    _small_tiles(monkeypatch)
    cfg = cell_cfg(dtype)
    entry, rng = _pool(dtype, 12, seed=1)
    # warm-up: every entry the sentinel, positions 0
    table = jnp.zeros((2, ENTRIES), jnp.int32)
    got, want = _both(cfg, entry, table, [0, 0], 5, rng)
    np.testing.assert_allclose(got, want, atol=TOL[dtype])
    # a copy-on-write fork: slots 0 and 1 share blocks 3 and 7, slot 1's
    # third block is its own copy (block 9) of slot 0's (block 5) with
    # rows the fork wrote since; slot 2 shares only the first block
    for name in ("k", "v"):
        entry[name] = entry[name].at[9, :3].set(entry[name][5, :3])
    table = jnp.asarray([[3, 7, 5, 2, 0, 0, 0, 0], [3, 7, 9, 0, 0, 0, 0, 0],
                         [3, 10, 0, 0, 0, 0, 0, 0]], jnp.int32)
    bases = [3 * BLOCK + 2, 2 * BLOCK + 4, BLOCK + 1]
    for queries in (1, 3):
        got, want = _both(cfg, entry, table, bases, queries, rng)
        np.testing.assert_allclose(got, want, atol=TOL[dtype])


@pytest.mark.parametrize("dk,dv,kv_heads,want", [
    # the cell: a KV head's 192 key lanes inside a window of 256 whole
    # lanes (its neighbour's 64 met by zeros), its 128 value lanes its own
    (192, 128, 4, HeadParts(16, 256, 128, (0, 128, 384, 512),
                            (0, 128, 256, 384))),
    # whole-lane heads: no surplus
    (128, 128, 2, HeadParts(32, 128, 128, (0, 128), (0, 128))),
    # narrow heads: two share a window; values narrower than the lanes
    # are taken as the flat form takes them, the whole row
    (64, 32, 4, HeadParts(16, 128, 128, (0, 0, 128, 128), (0, 0, 0, 0))),
    # a window that would pass the row's end starts earlier
    (96, 128, 4, HeadParts(16, 256, 128, (0, 0, 128, 128),
                           (0, 128, 256, 384))),
])
def test_head_parts_are_windows_of_whole_lanes(dk, dv, kv_heads, want):
    parts = head_parts(HEADS, kv_heads, dk, dv, False)
    assert parts == want
    for kv, start in enumerate(parts.k_starts):
        assert start % 128 == 0 and start <= kv * dk
        assert (kv + 1) * dk <= start + parts.k_width <= kv_heads * dk
    assert head_parts(HEADS, kv_heads, dk, dv, True) == HeadParts(
        HEADS, kv_heads * dk, kv_heads * dv, (0,), (0,))


@pytest.mark.parametrize("dk,dv,kv_heads", [(64, 32, 4), (96, 128, 4),
                                            (128, 128, 1)])
def test_other_head_geometries_read_like_the_gather(monkeypatch, dk, dv,
                                                    kv_heads):
    # heads that share a window, values narrower than the lanes, one KV
    # head for all: the slice's split form and the flat form in float32
    monkeypatch.setattr(paged_decode, "GROUPED_KEYS", 3 * 8)
    heads, block = 8, 8
    cfg = TransformerConfig(
        attn_kind="gqa", attention="dense", num_heads=heads,
        num_kv_heads=kv_heads, qk_head_dim=dk, v_head_dim=dv, dim=64,
        num_layers=1, dtype=jnp.float32, max_seq_len=ENTRIES * block)
    kind, = gqa.layer_kinds(cfg)
    rng = np.random.default_rng(2)
    entry = {name: jnp.asarray(rng.normal(size=shape), dt)
             for name, (shape, dt) in grouped_pool_spec(
                 1 + 2 * ENTRIES, block, kv_heads, dk, dv,
                 jnp.float32).items()}
    table = jnp.asarray(1 + rng.permutation(2 * ENTRIES).reshape(2, ENTRIES),
                        jnp.int32)
    for queries in (2, 16):
        q = jnp.asarray(rng.normal(size=(2, queries, heads, dk)), jnp.float32)
        positions = (jnp.asarray([3, 41], jnp.int32)[:, None]
                     + jnp.arange(queries, dtype=jnp.int32)[None])
        want = gqa.attend(cfg, kind, {}, q,
                          *grouped_table_view(entry, table), positions)
        got = fused_grouped_attention(cfg, kind, q, entry, table, positions)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def test_grouped_walk_counts_match_a_hand_count():
    # 64 heads over a [16, 768] + [16, 512] bf16 pool, a 1,088-entry
    # table (the benchmark cell's): decode walks 64 blocks a step, a
    # 512-token slice 128 positions a tile, a tile's walk its own causal
    # prefix
    cfg = cell_cfg(jnp.bfloat16, 1088 * 16)
    kind, = gqa.layer_kinds(cfg)
    decode = grouped_call_walk(cfg, kind, 1, block_size=16, entries=1088)
    # contexts of 1, 16, 17 and 12,000 tokens, and a parked slot
    bases = [0, 15, 16, 11999, 17408]
    blocks = [1, 1, 2, 750, 1]
    assert walk_counts(bases, 1, decode, 16, 1088) == (
        sum(blocks), sum(-(-b // decode.group) for b in blocks))
    chunk = grouped_call_walk(cfg, kind, 512, block_size=16, entries=1088)
    assert not chunk.flat and 512 % chunk.query_tile == 0
    # a slice at offset 2,048: tile i's last row is 2048 + (i + 1) * tq
    # - 1, and it walks the blocks up to that row
    tiles = 512 // chunk.query_tile
    live = [(2048 + (i + 1) * chunk.query_tile - 1) // 16 + 1
            for i in range(tiles)]
    assert walk_counts([2048], 512, chunk, 16, 1088) == (
        sum(live), sum(-(-b // chunk.group) for b in live))
    assert max(live) == 160  # of the table's 1,088
    # the walk is the live context's, not the table's
    first, _ = walk_counts([0], 512, chunk, 16, 1088)
    last, _ = walk_counts([16384], 512, chunk, 16, 1088)
    assert last > 30 * first


# ----------------------------------------------------------------------
# through the engine: a toy whose full-attention blocks are whole tiles
# ----------------------------------------------------------------------
# the hybrid toy (tests/test_hybrid_attention.py) with 2 | 4 KV heads of
# 64 | 64: a full layer's row is 128 key lanes and 128 value lanes, a
# block of 8 float32 rows whole (8, 128) tiles
WHOLE = dict(TOY, head_dim=64, swa_head_dim=64, v_head_dim=64,
             swa_v_head_dim=64, partial_rotary_factor=0.25)


@pytest.fixture(scope="module")
def toy():
    config = dict(WHOLE, held_experts=[0, 8], n_routed_experts=8)
    cfg = model_mimo.transformer_config(config, attention="dense",
                                        dtype=jnp.float32)
    model = TransformerLM(cfg)
    return model, model_mimo.seeded_params(model, 3)


PROMPTS = [np.random.default_rng(1).integers(0, 64, n).astype(np.int32)
           for n in (37, 9, 50, 3)] + [
               np.tile(np.asarray([5, 9, 11], np.int32), 7)]


@pytest.fixture(scope="module")
def served(toy):
    """Both reads of the toy serve PROMPTS once: slices (16-token chunks
    and a tail), decode, the spec_k verify step, and a request preempted
    mid-flight that starts over. kernel -> (engine, outputs, the span
    events of its tracer)."""
    from flashy_tpu.observability import Tracer
    model, params = toy
    out = {}
    for kernel in ("fused", "gather"):
        tracer = Tracer()
        engine = DecodeEngine(
            model, {"params": params}, slots=3, max_seq_len=64,
            cache_layout="paged", block_size=8, chunk=16, kernel=kernel,
            spec_k=2, tracer=tracer, cache_scope=f"grouped_{kernel}")
        engine.warmup()
        assert engine.kernel == kernel
        scheduler = ContinuousBatchingScheduler(
            engine, max_queue=8, draft=NGramDraft(3, k=2, ngram=2))
        handles = [scheduler.submit(p, 6) for p in PROMPTS]
        for _ in range(4):
            scheduler.step()
        assert scheduler.preempt(handles[0].slot) is handles[0]
        scheduler.run()
        engine._pool.check()
        assert engine.pool_stats()["preemptions"] == 1
        assert engine.compile_cache.stats()["recompiles"] == 0
        out[kernel] = (engine, [np.asarray(h.output) for h in handles],
                       [(e["name"], e["args"]) for e in tracer.events
                        if e.get("ph") == "X"])
    return out


def test_engine_streams_agree_between_the_fused_and_the_gather_read(toy,
                                                                    served):
    # the same prompts give the same tokens through either read of the
    # float32 toy model, and both give `generate`'s (any mismatch is a
    # walk bug: float32 leaves no near-ties here)
    for fused, gather in zip(served["fused"][1], served["gather"][1]):
        np.testing.assert_array_equal(fused, gather)
    model, params = toy
    want = generate(model, {"params": params}, jnp.asarray(PROMPTS[0])[None],
                    max_new_tokens=6)[0]  # the preempted request
    np.testing.assert_array_equal(served["fused"][1][0], want)


@pytest.mark.parametrize("name,queries", [("serve/verify", 3),
                                          ("serve/prefill_chunk", 16)])
def test_spans_carry_the_walks_counts_beside_the_bytes(served, name,
                                                       queries):
    # what says that the walk engaged and how many blocks a step
    # carried: `kv_blocks` / `kv_steps` on serve/verify (every step of a
    # scheduler with a draft) and serve/prefill_chunk exactly when the
    # read is the fused one, equal to `walk_counts` of a full layer's
    # walk; `kv_bytes` / `kv_bytes_window` are the attended rows'
    # whichever read serves them
    spans = {kernel: [stats for span, stats in served[kernel][2]
                      if span == name] for kernel in served}
    assert spans["fused"] and len(spans["fused"]) == len(spans["gather"])
    for fused, gather in zip(spans["fused"], spans["gather"]):
        assert "kv_blocks" not in gather and "kv_bytes_window" in gather
        assert {key: fused[key] for key in gather} == gather
    cfg = served["fused"][0]._cfg
    walk = grouped_call_walk(cfg, gqa.layer_kinds(cfg)[0], queries,
                             block_size=8, entries=8)
    per_token = 2 * (2 * 64 + 2 * 64) * 4  # two full layers' K and V rows
    for stats in spans["fused"]:
        assert 1 <= stats["kv_steps"] <= stats["kv_blocks"]
        if name == "serve/prefill_chunk":
            # one slot's slice: its base follows from the rows attended
            rows = (stats["kv_bytes"] - stats["kv_bytes_window"]) // per_token
            assert (stats["kv_blocks"], stats["kv_steps"]) == walk_counts(
                [rows - queries], queries, walk, 8, 8)


def test_the_decode_step_counts_a_full_layers_walk(served):
    # serve/decode's stats, from the engine's own mirror: 41 tokens are
    # 6 blocks of 8 in one step; two parked slots walk one block each
    cfg = served["fused"][0]._cfg
    walk = grouped_call_walk(cfg, gqa.layer_kinds(cfg)[0], 1, block_size=8,
                             entries=8)
    got = served["fused"][0]._kv_read_stats(1, [40, 64, 64])
    assert (got["kv_blocks"], got["kv_steps"]) == walk_counts(
        [40, 64, 64], 1, walk, 8, 8) == (6 + 1 + 1, 3)
    assert "kv_blocks" not in served["gather"][0]._kv_read_stats(
        1, [40, 64, 64])


def test_the_grouped_kernel_carries_its_name_under_the_global_scope(served):
    # what the trace readers find it by: `pallas_call(name=)` under
    # `attn/global`, once a full-attention layer, in the decode
    # executable; the window layers' reads are no kernel
    from tests.test_latent_decode import decode_args, kernel_stacks
    engine = served["fused"][0]
    stacks = kernel_stacks(engine._build_decode(), *decode_args(engine))
    full = sum(not kind.window for kind in gqa.layer_kinds(engine._cfg))
    assert [name for name, _ in stacks] == ["grouped_decode_fused"] * full
    assert all("attn/global" in stack for _, stack in stacks), stacks


def test_a_window_layer_is_not_the_walks_to_read():
    cfg = TransformerConfig(
        attn_kind="gqa", attention="dense", num_heads=8, num_kv_heads=2,
        qk_head_dim=64, dim=64, num_layers=2, window=8,
        window_layers=(0, 1), dtype=jnp.float32, max_seq_len=64)
    window = gqa.layer_kinds(cfg)[1]
    with pytest.raises(ValueError, match="full-attention layer"):
        fused_grouped_attention(cfg, window, None, {}, None, None)
