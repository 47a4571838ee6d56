# The fused walk over a latent pool (ops/paged_decode.py:
# fused_latent_attention) in Pallas interpret mode on the CPU, against
# the XLA table gather it replaces on a TPU
# (ops/paged_attention.py:latent_paged_attention, the oracle), then
# through the engine, then compiled — not run — for the v5e at the
# benchmark cell's widths (the grouped pool's walk, the training
# cell's flash kernels and the recurrent cell's state update too: one
# file holds the tests that load the TPU's compiler). Every tolerance
# states its reason.
"""The latent pool's fused read against the gather read."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import model_dots
from flashy_tpu.models import TransformerConfig, TransformerLM, gqa
from flashy_tpu.ops import paged_decode
from flashy_tpu.ops.paged_attention import (latent_paged_attention,
                                            latent_pool_spec)
from flashy_tpu.ops.paged_decode import (Walk, fused_latent_attention,
                                         latent_call_walk, walk_counts)
from flashy_tpu.serve import (ContinuousBatchingScheduler, DecodeEngine,
                              NGramDraft)
from tests.test_latent_experts import TOY

HEADS, RANK, ROPE, ENTRIES = 4, 128, 4, 8
# float32: the same products summed a tile at a time under a running
# maximum; bfloat16: one rounding of the probabilities and of the output
# (2^-8 of values of a few units), the K/V kernel's tests' tolerance
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _cfg(dtype, block_size):
    return TransformerConfig(
        attn_kind="mla", num_heads=HEADS, kv_lora_rank=RANK,
        qk_rope_head_dim=ROPE, qk_nope_head_dim=8, v_head_dim=8,
        q_lora_rank=16, dtype=dtype, max_seq_len=ENTRIES * block_size)


def _pool(dtype, block_size, blocks, seed=0):
    rng = np.random.default_rng(seed)
    spec = latent_pool_spec(blocks, block_size, RANK, ROPE, dtype)
    entry = {name: jnp.asarray(rng.normal(size=shape), dt)
             for name, (shape, dt) in spec.items()}
    # lanes past the rotated key's width are stored zeros
    entry["kr"] = entry["kr"].at[..., ROPE:].set(0)
    return entry, rng


def _both(cfg, entry, table, bases, queries, rng):
    batch = table.shape[0]
    q_lat = jnp.asarray(rng.normal(size=(batch, queries, HEADS, RANK)),
                        cfg.dtype)
    q_rope = jnp.asarray(rng.normal(size=(batch, queries, HEADS, ROPE)),
                         cfg.dtype)
    positions = (jnp.asarray(bases, jnp.int32)[:, None]
                 + jnp.arange(queries, dtype=jnp.int32)[None])
    want = latent_paged_attention(cfg, q_lat, q_rope, entry, table, positions)
    got = fused_latent_attention(cfg, q_lat, q_rope, entry, table, positions)
    assert got.shape == want.shape and got.dtype == want.dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("queries", [1, 5, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("walk", ["whole", "split"])
def test_fused_latent_read_matches_the_gather(monkeypatch, dtype, queries,
                                              walk):
    # Ragged contexts: a slot on its first block, one mid-table at a
    # position no block or tile boundary divides, one whose last row is
    # the table's last, and a parked slot (base == max_seq_len) whose
    # rows the engine discards. 'split': 4 query positions a tile and 3
    # blocks a step, so a chunk's tiles straddle block boundaries (base
    # 13, blocks of 8 or 16) and no live range is whole groups.
    block_size = 16 if dtype == jnp.bfloat16 else 8
    if walk == "split":
        monkeypatch.setattr(paged_decode, "LATENT_ROWS", 4 * HEADS)
        monkeypatch.setattr(paged_decode, "LATENT_KEYS", 3 * block_size)
    cfg = _cfg(dtype, block_size)
    length = ENTRIES * block_size
    entry, rng = _pool(dtype, block_size, 1 + 4 * ENTRIES)
    table = jnp.asarray(1 + rng.permutation(4 * ENTRIES).reshape(4, ENTRIES),
                        jnp.int32)
    bases = [0, 13 + 2 * block_size, length - queries, length]
    got, want = _both(cfg, entry, table, bases, queries, rng)
    shape = latent_call_walk(queries, HEADS, entry, entries=ENTRIES)
    if walk == "split":
        # the largest divisor of T at most 4: 5 rows go one at a time
        assert shape.group == 3
        assert shape.query_tile == {1: 1, 5: 1, 16: 4}[queries]
    np.testing.assert_allclose(got[:3], want[:3], atol=TOL[dtype])
    assert np.isfinite(got).all()  # the parked slot's rows too


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_sentinel_tables_and_forked_prefixes_read_like_the_gather(dtype):
    block_size = 16 if dtype == jnp.bfloat16 else 8
    cfg = _cfg(dtype, block_size)
    entry, rng = _pool(dtype, block_size, 12, seed=1)
    # warm-up: every entry the sentinel, positions 0
    table = jnp.zeros((2, ENTRIES), jnp.int32)
    got, want = _both(cfg, entry, table, [0, 0], 5, rng)
    np.testing.assert_allclose(got, want, atol=TOL[dtype])
    # a copy-on-write fork: slots 0 and 1 share blocks 3 and 7, slot 1's
    # third block is its own copy (block 9) of slot 0's (block 5) with
    # rows the fork wrote since; slot 2 shares only the first block
    entry["c"] = entry["c"].at[9, :3].set(entry["c"][5, :3])
    entry["kr"] = entry["kr"].at[9, :3].set(entry["kr"][5, :3])
    table = jnp.asarray([[3, 7, 5, 2, 0, 0, 0, 0], [3, 7, 9, 0, 0, 0, 0, 0],
                         [3, 10, 0, 0, 0, 0, 0, 0]], jnp.int32)
    bases = [3 * block_size + 2, 2 * block_size + 4, block_size + 1]
    for queries in (1, 3):
        got, want = _both(cfg, entry, table, bases, queries, rng)
        np.testing.assert_allclose(got, want, atol=TOL[dtype])


def test_latent_walk_counts_match_a_hand_count():
    # 128 heads of a [16, 512] + [16, 128] bf16 pool, a 288-entry table
    # (the benchmark cell's): decode walks 64 blocks a step, a 512-token
    # slice 16 positions a tile, a tile's walk its own causal prefix
    spec = {name: jax.ShapeDtypeStruct(*leaf) for name, leaf in
            latent_pool_spec(2, 16, 512, 64, jnp.bfloat16).items()}
    decode = latent_call_walk(1, 128, spec, entries=288)
    assert decode == Walk(64, 128, 1, True, True)
    # contexts of 1, 16, 17 and 4,000 tokens, and a parked slot
    bases = [0, 15, 16, 3999, 4608]
    blocks = [1, 1, 2, 250, 1]
    assert walk_counts(bases, 1, decode, 16, 288) == (
        sum(blocks), sum(-(-b // decode.group) for b in blocks))
    chunk = latent_call_walk(512, 128, spec, entries=288)
    assert chunk == Walk(32, 128, 16, True, True)
    # a slice at offset 1,024: tile i's last row is 1024 + (i + 1) * tq
    # - 1, and it walks the blocks up to that row
    tiles = 512 // chunk.query_tile
    live = [(1024 + (i + 1) * chunk.query_tile - 1) // 16 + 1
            for i in range(tiles)]
    assert walk_counts([1024], 512, chunk, 16, 288) == (
        sum(live), sum(-(-b // chunk.group) for b in live))
    # the walk is the live context's, not the table's: a first slice
    # costs a fraction of a last one
    first, _ = walk_counts([0], 512, chunk, 16, 288)
    last, _ = walk_counts([3584], 512, chunk, 16, 288)
    assert last > 7 * first


def _toy_engine(kernel, **kwargs):
    config = dict(TOY, kv_lora_rank=RANK, held_experts=[4, 8],
                  n_routed_experts=8)
    cfg = model_dots.transformer_config(config, attention="dense",
                                        dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model_dots.seeded_params(model, 3)
    engine = DecodeEngine(model, {"params": params}, slots=3, max_seq_len=64,
                          cache_layout="paged", block_size=8, chunk=16,
                          kernel=kernel, **kwargs)
    engine.warmup()
    return engine


def test_engine_streams_agree_between_the_fused_and_the_gather_read():
    # Slices (a 16-token chunk and a tail), decode, a prefix hit with a
    # copy-on-write fork and the spec_k verify step: the same prompts
    # give the same tokens through either read of the float32 toy model
    # (any mismatch is a walk bug: float32 leaves no near-ties here).
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 64, 20).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 64, n).astype(
        np.int32)]) for n in (5, 9, 13, 3)]
    prompts.append(np.tile(np.asarray([5, 9, 11], np.int32), 7))
    streams = {}
    for kernel in ("fused", "gather"):
        engine = _toy_engine(kernel, spec_k=2)
        assert engine.kernel == kernel
        scheduler = ContinuousBatchingScheduler(
            engine, max_queue=8, draft=NGramDraft(3, k=2, ngram=2))
        handles = [scheduler.submit(p, 6) for p in prompts]
        scheduler.run()
        engine._pool.check()
        stats = engine.pool_stats()
        assert stats["prefix_hit_rate"] > 0 and stats["cow_forks"] >= 1
        assert engine.compile_cache.stats()["recompiles"] == 0
        streams[kernel] = [np.asarray(h.output) for h in handles]
    for fused, gather in zip(streams["fused"], streams["gather"]):
        np.testing.assert_array_equal(fused, gather)


def kernel_stacks(step, *args):
    """(kernel name, name stack) of every `pallas_call` of `step`'s
    jaxpr; a nested jit's equations carry their stack from its call on."""
    stacks = []

    def walk(jaxpr, outer=""):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                stacks.append((eqn.params["name"], stack))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner, stack)

    walk(jax.make_jaxpr(step)(*args).jaxpr)
    return stacks


def decode_args(engine):
    return (engine._params, engine._cache, *engine._layout_args(),
            engine._tokens, engine._positions, engine._active,
            jnp.zeros((2,), jnp.uint32))


def test_the_latent_kernel_carries_its_name_under_the_attn_scope():
    # what the trace readers find it by: `pallas_call(name=)` and the
    # `attn` named scope of the paged step, once a layer, in the decode
    # and in the slice executable
    engine = _toy_engine("fused")
    stacks = kernel_stacks(engine._build_decode(), *decode_args(engine))
    layers = engine._cfg.num_layers
    assert [name for name, _ in stacks] == ["latent_decode_fused"] * layers
    assert all("attn/" in stack for _, stack in stacks), stacks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots,queries", [(48, 1), (48, 3), (1, 4),
                                           (1, 512)])
def test_the_cells_latent_reads_compile_for_the_v5e(one_chip, slots,
                                                    queries):
    # Mosaic's word on the walk `latent_walk_shape` picks at the
    # benchmark cell's widths (128 heads, rank 512, rope lanes 128,
    # blocks of 16, 288 entries, bf16): decode, a verify step, the tail
    # slice and the 512-token slice. Compiled, not run: it says nothing
    # about results or times.
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    entry = {name: sds(shape, dt) for name, (shape, dt) in latent_pool_spec(
        48 * 288 + 1, 16, 512, 64, jnp.bfloat16).items()}
    walk = latent_call_walk(queries, 128, entry, entries=288)

    def read(q_lat, q_rope, entry, table, base):
        return paged_decode._latent_call(q_lat, q_rope, entry, table, base,
                                         walk, scale=0.1, interpret=False)

    compiled = jax.jit(read).lower(
        sds((slots, queries, 128, 512), jnp.bfloat16),
        sds((slots, queries, 128, 128), jnp.bfloat16), entry,
        sds((slots, 288), jnp.int32), sds((slots,), jnp.int32)).compile()
    assert "latent_decode_fused" in compiled.as_text()


@pytest.mark.parametrize("slots,queries", [(32, 1), (32, 5), (1, 4),
                                           (1, 512)])
def test_the_cells_grouped_reads_compile_for_the_v5e(one_chip, slots,
                                                     queries):
    # the same for the walk `grouped_walk_shape` picks at the
    # window/full cell's widths (64 heads over 4 KV heads of 192 | 128,
    # blocks of 16, 1,088 entries, bf16, the [32, 1088] table whole in
    # scalar memory); here because one file holds the tests that load
    # the TPU's compiler (tests/test_grouped_decode.py has the rest)
    from flashy_tpu.ops.paged_attention import grouped_pool_spec
    from tests.test_grouped_decode import cell_cfg

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = cell_cfg(jnp.bfloat16, 1088 * 16)
    kind, = gqa.layer_kinds(cfg)
    entry = {name: sds(shape, dt) for name, (shape, dt) in grouped_pool_spec(
        32 * 1088 + 1, 16, 4, 192, 128, jnp.bfloat16).items()}
    walk = paged_decode.grouped_call_walk(cfg, kind, queries, block_size=16,
                                          entries=1088)
    parts = paged_decode.head_parts(64, 4, 192, 128, walk.flat)

    def read(q, entry, table, base):
        return paged_decode._grouped_call(q, entry, table, base, walk, parts,
                                          scale=0.07, interpret=False)

    compiled = jax.jit(read).lower(
        sds((slots, len(parts.k_starts), queries * parts.heads,
             parts.k_width), jnp.bfloat16),
        entry, sds((slots, 1088), jnp.int32),
        sds((slots,), jnp.int32)).compile()
    assert "grouped_decode_fused" in compiled.as_text()


@pytest.mark.parametrize("seq_len,dim,dtype,fused", [
    (2048, 128, jnp.bfloat16, True), (2048, 128, jnp.bfloat16, False),
    (4096, 128, jnp.bfloat16, True), (2048, 128, jnp.float32, True),
    (2048, 256, jnp.bfloat16, True), (4096, 256, jnp.float32, True),
    (2048, 256, jnp.float32, False), (2048, 512, jnp.bfloat16, True)],
    ids=["cell", "split_backward", "several_tiles", "float32", "wide_heads",
         "float32_wide_heads", "float32_wide_heads_split",
         "estimate_at_the_limit"])
def test_the_flash_kernels_compile_for_the_v5e(one_chip, seq_len, dim, dtype,
                                               fused):
    # the same for the training cell's attention, forward and backward
    # at the schedule `ops.attention.flash_schedule` gives its shapes
    # (8 x 16 heads of 128, bf16, causal: ONE 2048 x 2048 forward tile a
    # head and the 16 MB float32 scores it keeps, a head's dQ in VMEM),
    # for the split backward the ring path calls, at twice the length,
    # where tiles lie under, on and past the diagonal and a skipped
    # step's index map names the block before it, and in float32 and at
    # wider heads, where the rule's estimate comes nearest `VMEM_LIMIT`
    # (float32 at 128: 94% of it; bf16 at 512: all of it, which Mosaic
    # takes, so the estimate is no lower than Mosaic's own count) or
    # halves the tiles; here because one file holds the tests that load
    # the TPU's compiler (tests/test_ops.py has the values)
    from flashy_tpu.ops import attention
    batch, heads = 8 * 2048 // seq_len, 16 * 128 // dim
    x = jax.ShapeDtypeStruct((batch, seq_len, heads, dim), dtype,
                             sharding=one_chip)

    def loss(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True, interpret=False,
            fused_backward=fused).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    backward = ["flash_bwd_fused"] if fused else ["flash_bwd_dq",
                                                  "flash_bwd_dkv"]
    assert all(name in text for name in ["flash_fwd"] + backward)


@pytest.mark.parametrize("heads_per_step", [16, 32, 64])
def test_the_cells_state_update_compiles_for_the_v5e(one_chip,
                                                     heads_per_step):
    # the recurrent cell's decode-run update (ops/ssd_scan.py:
    # _update_call) at its widths: 128 rows against 129 entries of 128
    # heads x [64, 128] float32, b and c by 8 groups, the table aliased in
    # place; the decay a block's scalars in SMEM, v and y lane-dense rows
    # of two heads, at the rule's 32 heads a step and either side of it
    from flashy_tpu.ops import ssd_scan

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def update(state, rows, decay, v, b, c):
        return ssd_scan._update_call(state, rows, decay, v, b, c,
                                     heads_per_step=heads_per_step,
                                     interpret=False)

    compiled = jax.jit(update, donate_argnums=0).lower(
        sds((129, 128, 64, 128)), sds((128,), jnp.int32), sds((128, 128)),
        sds((128, 128, 64)), sds((128, 8, 128)), sds((128, 8, 128))).compile()
    assert "ssd_state_update" in compiled.as_text()
