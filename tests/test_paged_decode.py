# Fused paged decode (ops/paged_decode.py): interpret-mode parity of
# the Pallas kernel against the gather oracle — direct kernel calls
# (model dtype and int8, decode/verify/chunk row counts, sentinel
# tables) and token-exactness through the SAME engine on both kernels
# across block-boundary prompt lengths, COW-forked tables, speculative
# verify and all-sentinel warm-up — plus the satellites: the ops
# namespace shadowing regression, the models/audit registry entries and
# the FT203 gate anchoring INSIDE the pallas_call body (a double-scaling
# rewrite must be caught, not vacuously clean).
import numpy as np
import pytest

from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine, \
    NGramDraft


def _tiny_model(vocab=32, max_seq_len=32, scan_layers=False):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=vocab, dim=16, num_layers=2,
                            num_heads=2, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32,
                            scan_layers=scan_layers)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
    return model, params


def _pool_entry(k, v, kv_dtype):
    """One layer's pool entry holding rows `k`, `v` [N, bs, H, Dh], every
    leaf in the shape and dtype `pool_spec` gives it: an int8 pool's
    per-(row, head) scales are one (row-in-block, head) row a block."""
    import jax.numpy as jnp
    from flashy_tpu.models.quantize import quantize_kv
    from flashy_tpu.ops.paged_attention import pool_spec

    leaves = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    if kv_dtype == "int8":
        for name in ("k", "v"):
            leaves[name], leaves[f"{name}_scale"] = quantize_kv(leaves[name])
    spec = pool_spec(*k.shape, jnp.float32, kv_dtype)
    assert set(spec) == set(leaves)
    return {name: leaves[name].reshape(shape).astype(dtype)
            for name, (shape, dtype) in spec.items()}


def _pool_fixture(kv_dtype="model", num_blocks=6, block_size=4, heads=2,
                  head_dim=8, seed=0):
    """A random pool + tables + consecutive positions for direct calls."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    shape = (num_blocks, block_size, heads, head_dim)
    k = rng.normal(size=shape).astype(np.float32)
    v = rng.normal(size=shape).astype(np.float32)
    table = jnp.asarray([[1, 2, 3, 0, 0], [4, 5, 0, 0, 0]], jnp.int32)
    return _pool_entry(k, v, kv_dtype), table


def _serve_stream(model, params, workload, kernel, *, kv_dtype="model",
                  spec_k=None, slots=2, block_size=4, prefix_cache=True,
                  num_blocks=None):
    """Serve `workload` through a paged engine; returns the token
    streams and the engine (for pool/compile assertions)."""
    engine = DecodeEngine(
        model, params, slots=slots, cache_layout="paged",
        block_size=block_size, kv_dtype=kv_dtype, kernel=kernel,
        num_blocks=num_blocks, prefix_cache=prefix_cache,
        spec_k=spec_k, cache_scope=f"t_{kernel}_{kv_dtype}_{spec_k}")
    engine.warmup()
    warm = engine.compile_cache.stats()["misses"]
    draft = (NGramDraft(slots=slots, k=spec_k, ngram=3)
             if spec_k else None)
    scheduler = ContinuousBatchingScheduler(engine, draft=draft,
                                            max_queue=len(workload))
    handles = [scheduler.submit(p, m) for p, m in workload]
    scheduler.run()
    stats = engine.compile_cache.stats()
    assert stats["recompiles"] == 0, stats
    assert stats["misses"] == warm, "post-warm-up build on the " + kernel
    return [h.output for h in handles], engine


# ----------------------------------------------------------------------
# direct kernel parity vs the gather oracle
# ----------------------------------------------------------------------
def _ragged_fixture(kv_dtype, queries, seed=5, heads=8, head_block=None):
    """Wide pool rows (the walk that copies its own blocks) and a ragged
    batch the walk must get right: contexts of 1, one short of a group,
    a group, one past it and the full table, a parked slot, tables whose
    live entries are scattered over the pool out of order, and a table
    width the group does not divide."""
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_decode import call_walk

    bs, dim, entries = 16, 128, 40
    walk = call_walk(queries, heads, dim, block_size=bs, entries=entries,
                     quantized=kv_dtype == "int8", dtype=jnp.float32,
                     head_block=head_block)
    span = walk.group * bs
    assert walk.dma, walk
    max_seq_len = entries * bs
    # context = tokens the LAST query row attends (its position + 1)
    contexts = [queries, span - 1, span, span + 1, max_seq_len]
    base = np.asarray([c - queries for c in contexts] + [max_seq_len])
    blocks = [-(-c // bs) for c in contexts] + [0]
    rng = np.random.default_rng(seed)
    order = rng.permutation(np.arange(1, sum(blocks) + 9))
    table = np.zeros((len(base), entries), np.int32)
    for slot, n in enumerate(blocks):
        table[slot, :n], order = order[:n], order[n:]
    shape = (sum(blocks) + 9, bs, heads, dim)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    entry = _pool_entry(k, v, kv_dtype)
    q = jnp.asarray(rng.normal(size=(len(base), queries, heads, dim)),
                    jnp.float32)
    return entry, jnp.asarray(table), q, jnp.asarray(base, jnp.int32), walk


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
@pytest.mark.parametrize("queries", [1, 3, 5, "ragged-1", "ragged-3",
                                     "ragged-5", "ragged-chunk",
                                     "grid-3-hb1", "ragged-3-hb8"])
def test_fused_kernel_matches_gather_oracle(kv_dtype, queries):
    # every entry is made from `pool_spec` (`_pool_entry`): an int8
    # pool's scales are the stored rows, read by the grid's walk (the
    # toy pool), the flat layout (ragged decode and verify rows) and the
    # per-head layout (a chunk), at every head a step and, '-hb', at a
    # caller's smaller head block
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_attention import paged_attention
    from flashy_tpu.ops.paged_decode import fused_paged_attention

    live, head_block = slice(None), None
    if isinstance(queries, int) or queries == "grid-3-hb1":
        if queries == "grid-3-hb1":
            queries, head_block = 3, 1
        entry, table = _pool_fixture(kv_dtype)
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, queries, 2, 8)), jnp.float32)
        base = jnp.asarray([9, 2], jnp.int32)
    elif queries == "ragged-3-hb8":
        # two head blocks of a 16-head pool: few rows, but an int8
        # pool's scale rows are flat only with every head in a step
        queries, head_block = 3, 8
        entry, table, q, base, walk = _ragged_fixture(
            kv_dtype, queries, heads=16, head_block=head_block)
        assert walk.dma and walk.head_block == 8, walk
        assert walk.flat == (kv_dtype == "model"), walk
        live = slice(0, -1)
    else:
        queries = {"1": 1, "3": 3, "5": 5, "chunk": 32}[queries[7:]]
        entry, table, q, base, walk = _ragged_fixture(kv_dtype, queries)
        # decode and verify rows ride the flat layout, a chunk the
        # per-head one; int8 scales flat only with every head in a step
        assert walk.flat == (queries < 32) and 40 % walk.group, walk
        live = slice(0, -1)  # the last slot is parked: garbage by design
    positions = base[:, None] + jnp.arange(queries, dtype=jnp.int32)[None]
    want = paged_attention(q, entry, table, positions,
                           head_dim=q.shape[-1], dtype=jnp.float32)
    got = fused_paged_attention(q, entry, table, positions,
                                head_dim=q.shape[-1], dtype=jnp.float32,
                                head_block=head_block, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("quantized", [True, False])
@pytest.mark.parametrize("queries", [1, 5, 256, 512])
def test_walk_shape_keeps_its_vmem_estimate_under_the_budget(queries,
                                                             quantized):
    # counts, not rates: at the benchmark's widths (H=16, Dh=128, bs=16)
    # the chooser's own estimate fits the scoped VMEM for decode, verify
    # and both chunk sizes, and the walk it returns is a legal one
    from flashy_tpu.ops import paged_decode

    sizes = dict(pool_itemsize=1 if quantized else 2, q_itemsize=2)
    walk = paged_decode.walk_shape(queries, 16, 128, 16, 128,
                                   quantized=quantized, **sizes)
    assert walk.dma and walk.group >= 1
    assert queries % walk.query_tile == 0 and 16 % walk.head_block == 0
    assert paged_decode._vmem_estimate(
        walk.query_tile, walk.head_block, 128, 16, walk.group,
        flat=walk.flat, **sizes) <= paged_decode.VMEM_BUDGET
    # decode and verify attend many blocks a step in the flat layout
    assert walk.flat == (queries <= 5)
    if queries <= 5:
        assert walk.group * 16 == paged_decode.KEYS_PER_STEP
    # a table the group does not divide, or shorter than a group
    for entries in (100, 3, 1):
        short = paged_decode.walk_shape(queries, 16, 128, 16, entries,
                                        quantized=quantized, **sizes)
        assert 1 <= short.group <= entries
    # rows narrower than a copy window keep the grid's walk
    for heads, dim in ((16, 64), (4, 128)):
        narrow = paged_decode.walk_shape(queries, heads, dim, 16, 128,
                                         quantized=quantized, **sizes)
        assert narrow == (1, narrow.head_block, queries, False, False)


def test_walk_counts_are_blocks_and_ceil_steps():
    from flashy_tpu.ops.paged_decode import Walk, walk_counts

    bs, entries = 16, 128
    positions = np.asarray([0, 15, 16, 255, 256, 2047, 2048])
    live = np.asarray([1, 1, 2, 16, 17, 128, 1])  # parked slot: 1 block
    for group in (1, 8, 16):
        walk = Walk(group, 16, 1, True, True)
        blocks, steps = walk_counts(positions, 1, walk, bs, entries)
        assert blocks == live.sum()
        assert steps == sum(-(-n // group) for n in live)
    # a chunk split into query tiles walks each tile's own prefix
    walk = Walk(8, 16, 128, False, True)
    blocks, steps = walk_counts([1536], 256, walk, bs, entries)
    assert blocks == (1536 + 128) // bs + (1536 + 256) // bs
    assert steps == 13 + 14
    # the grid's walk: a step is a block, parked slots see the table
    grid = Walk(1, 16, 1, False, False)
    assert walk_counts([2048, 31], 1, grid, bs, entries) == (130, 130)


def test_walk_query_tiles_match_the_oracle(monkeypatch):
    # a chunk too large for one grid step splits into query tiles that
    # each walk their own causal prefix: force the split at a toy size
    import jax.numpy as jnp
    from flashy_tpu.ops import paged_decode
    from flashy_tpu.ops.paged_attention import paged_attention

    for name, value in (("QUERY_TILE", 8), ("FLAT_ROWS", 8),
                        ("VMEM_BUDGET", 3 * 2 ** 20)):
        monkeypatch.setattr(paged_decode, name, value)
    entry, table, q, base, walk = _ragged_fixture("int8", 32)
    assert walk == (4, 8, 8, False, True), walk  # four tiles of 8 rows
    positions = base[:, None] + jnp.arange(32, dtype=jnp.int32)[None]
    want = paged_attention(q, entry, table, positions, head_dim=128,
                           dtype=jnp.float32)
    got = paged_decode.fused_paged_attention(
        q, entry, table, positions, head_dim=128, dtype=jnp.float32,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:-1], np.asarray(want)[:-1],
                               rtol=2e-5, atol=2e-6)


def test_fused_kernel_head_block_tiling_matches():
    # head_block=1 (one head per grid step) must equal head_block=H
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_decode import fused_paged_attention

    entry, table = _pool_fixture("int8")
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 2, 2, 8)), jnp.float32)
    positions = jnp.asarray([[6, 7], [1, 2]], jnp.int32)
    full = fused_paged_attention(q, entry, table, positions, head_dim=8,
                                 dtype=jnp.float32, head_block=2,
                                 interpret=True)
    tiled = fused_paged_attention(q, entry, table, positions, head_dim=8,
                                  dtype=jnp.float32, head_block=1,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(tiled), np.asarray(full),
                               rtol=1e-6, atol=1e-7)


def test_fused_kernel_all_sentinel_table_is_finite():
    # the warm-up case: every entry sentinel, nothing real written —
    # output must be finite (the zero pool's uniform softmax), exactly
    # like the gather oracle's view of the same table
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_attention import paged_attention
    from flashy_tpu.ops.paged_decode import fused_paged_attention

    entry = {"k": jnp.zeros((4, 4, 2, 8), jnp.float32),
             "v": jnp.zeros((4, 4, 2, 8), jnp.float32)}
    table = jnp.zeros((2, 3), jnp.int32)
    q = jnp.ones((2, 1, 2, 8), jnp.float32)
    positions = jnp.asarray([[0], [5]], jnp.int32)
    got = fused_paged_attention(q, entry, table, positions, head_dim=8,
                                dtype=jnp.float32, interpret=True)
    want = paged_attention(q, entry, table, positions, head_dim=8,
                           dtype=jnp.float32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_fused_verify_wrapper_validates_row_count():
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_decode import fused_speculative_verify

    entry, table = _pool_fixture()
    q = jnp.ones((2, 1, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="k\\+1 >= 2"):
        fused_speculative_verify(q, entry, table,
                                 jnp.zeros((2, 1), jnp.int32),
                                 head_dim=8, dtype=jnp.float32,
                                 interpret=True)


def test_fused_kernel_rejects_non_dividing_head_block():
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_decode import fused_paged_attention

    entry, table = _pool_fixture()
    q = jnp.ones((2, 1, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="head_block"):
        fused_paged_attention(q, entry, table,
                              jnp.zeros((2, 1), jnp.int32), head_dim=8,
                              dtype=jnp.float32, head_block=3,
                              interpret=True)


# ----------------------------------------------------------------------
# token-exactness through the engine: fused vs the gather oracle
# ----------------------------------------------------------------------
def test_fused_engine_token_exact_at_block_boundaries():
    # prompt lengths straddling the block boundary (1, bs-1, bs, bs+1):
    # the positions where a table-entry off-by-one would first diverge
    model, params = _tiny_model()
    bs = 4
    rng = np.random.default_rng(3)
    workload = [(rng.integers(0, 32, n).astype(np.int32), bs + 2)
                for n in (1, bs - 1, bs, bs + 1)]
    gather, _ = _serve_stream(model, params, workload, "gather",
                              block_size=bs)
    fused, _ = _serve_stream(model, params, workload, "fused",
                             block_size=bs)
    for g, f in zip(gather, fused):
        assert np.array_equal(g, f), (g.tolist(), f.tolist())


def _wide_model(max_seq_len=512):
    """One layer of eight 128-wide heads: pool rows the kernel can copy
    itself, so the engine drives the grouped walk (the toy model's two
    8-wide heads keep the grid's)."""
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=32, dim=1024, num_layers=1,
                            num_heads=8, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 4), jnp.int32))


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_fused_engine_token_exact_on_the_grouped_walk(kv_dtype):
    # 32 table entries walked 16 blocks a step: contexts inside one
    # group, across the group boundary (255 -> 257 while decoding) and
    # deep in the second group, two slots at once, one parked at times
    from flashy_tpu.ops.paged_decode import call_walk
    import jax.numpy as jnp

    model, params = _wide_model()
    walk = call_walk(1, 8, 128, block_size=16, entries=32,
                     quantized=kv_dtype == "int8", dtype=jnp.float32)
    assert walk.dma and walk.flat and walk.group == 16, walk
    rng = np.random.default_rng(8)
    workload = [(rng.integers(0, 32, n).astype(np.int32), 4)
                for n in (20, 254, 300)]
    gather, _ = _serve_stream(model, params, workload, "gather",
                              kv_dtype=kv_dtype, block_size=16,
                              prefix_cache=False)
    fused, engine = _serve_stream(model, params, workload, "fused",
                                  kv_dtype=kv_dtype, block_size=16,
                                  prefix_cache=False)
    for g, f in zip(gather, fused):
        assert np.array_equal(g, f), (g.tolist(), f.tolist())
    engine._pool.check()


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_fused_engine_token_exact_speculative(kv_dtype):
    # the [S, k+1] verify forward through the fused kernel: token
    # streams must equal the gather-int8 oracle bit-for-bit (both fold
    # the same scales) on a repetitive workload where drafts accept
    model, params = _tiny_model()
    rng = np.random.default_rng(4)
    workload = []
    for n in (6, 9, 11, 5):
        pattern = rng.integers(0, 32, 3)
        workload.append((np.tile(pattern, n // 3 + 1)[:n].astype(np.int32),
                         8))
    gather, _ = _serve_stream(model, params, workload, "gather",
                              kv_dtype=kv_dtype, spec_k=3)
    fused, _ = _serve_stream(model, params, workload, "fused",
                             kv_dtype=kv_dtype, spec_k=3)
    for g, f in zip(gather, fused):
        assert np.array_equal(g, f), (g.tolist(), f.tolist())


def test_fused_engine_token_exact_on_cow_forked_tables():
    # shared system prompt whose length is NOT block-aligned: every
    # later admission COW-forks the partially shared block; the fused
    # read must see the forked table identically to the gather read
    model, params = _tiny_model()
    bs = 4
    rng = np.random.default_rng(5)
    system = rng.integers(0, 32, bs + bs // 2).astype(np.int32)
    workload = [(np.concatenate([system,
                                 rng.integers(0, 32, 3).astype(np.int32)]),
                 6) for _ in range(4)]

    def run(kernel):
        out, engine = _serve_stream(model, params, workload, kernel,
                                    block_size=bs, slots=2)
        pool = engine.pool_stats()
        assert pool["cow_forks"] >= 1, "COW path never exercised"
        assert pool["prefix_hit_rate"] > 0
        engine._pool.check()
        return out

    gather = run("gather")
    fused = run("fused")
    for g, f in zip(gather, fused):
        assert np.array_equal(g, f), (g.tolist(), f.tolist())


def test_fused_engine_scan_layers_token_exact():
    model, params = _tiny_model(scan_layers=True)
    rng = np.random.default_rng(6)
    workload = [(rng.integers(0, 32, n).astype(np.int32), 5)
                for n in (3, 7)]
    gather, _ = _serve_stream(model, params, workload, "gather")
    fused, _ = _serve_stream(model, params, workload, "fused")
    for g, f in zip(gather, fused):
        assert np.array_equal(g, f)


def test_fused_engine_warmup_all_sentinel_zero_builds():
    # warm-up runs decode + verify + the chunk pair against all-
    # sentinel tables; everything traffic touches must be compiled
    # there — the serving gate asserted engine-level (the demo gates
    # the full lifetime)
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, cache_layout="paged",
                          block_size=4, kv_dtype="int8", kernel="fused",
                          spec_k=2, cache_scope="warm_fused")
    assert (engine._table_host == 0).all()  # all-sentinel at warm-up
    engine.warmup()
    assert engine.compile_cache.stats()["recompiles"] == 0
    assert len(engine.compile_cache) >= 4  # chunk pair+decode+verify+copy


# ----------------------------------------------------------------------
# engine kernel selection
# ----------------------------------------------------------------------
def test_engine_kernel_validation_and_auto():
    import jax
    model, params = _tiny_model()
    with pytest.raises(ValueError, match="kernel"):
        DecodeEngine(model, params, slots=1, kernel="bogus")
    with pytest.raises(ValueError, match="fused"):
        DecodeEngine(model, params, slots=1, kernel="fused")  # dense
    paged = DecodeEngine(model, params, slots=1, cache_layout="paged",
                         block_size=4, kernel="auto",
                         cache_scope="auto_probe")
    # auto resolves per backend: gather on this CPU container, fused
    # only on TPU-like backends
    want = "gather" if jax.default_backend() in ("cpu", "gpu") else "fused"
    assert paged.kernel == want
    dense = DecodeEngine(model, params, slots=1, cache_scope="auto_dense")
    assert dense.kernel == "gather"


# ----------------------------------------------------------------------
# satellites: ops namespace, audit registry
# ----------------------------------------------------------------------
def test_ops_namespace_module_vs_function_shadowing():
    # the PR-8 hazard, pinned for the new module: importing the ops
    # package must leave BOTH submodules reachable as modules, and the
    # paged_decode FUNCTIONS reachable from the package without any
    # name shadowing a submodule attribute
    import importlib
    import types

    import flashy_tpu.ops as ops
    import flashy_tpu.ops.paged_attention as pa_mod
    import flashy_tpu.ops.paged_decode as pd_mod

    assert isinstance(ops.paged_attention, types.ModuleType)
    assert ops.paged_attention is pa_mod
    assert isinstance(ops.paged_decode, types.ModuleType)
    assert ops.paged_decode is pd_mod
    # the function spellings
    assert callable(ops.fused_paged_attention)
    assert callable(ops.fused_speculative_verify)
    assert ops.fused_paged_attention is pd_mod.fused_paged_attention
    with pytest.raises(AttributeError):
        ops.no_such_export
    # and a fresh import of the submodule does not flip the attribute
    importlib.reload(ops)
    assert isinstance(ops.paged_attention, types.ModuleType)
    assert isinstance(ops.paged_decode, types.ModuleType)


def test_engine_rejects_fused_where_the_kernel_cannot_run(monkeypatch):
    # explicit kernel='fused' on a backend where the silent gather
    # fallback would run instead must fail LOUDLY: a gate that reports
    # 'fused' must have executed the kernel
    import jax

    model, params = _tiny_model()
    monkeypatch.setattr(jax, "default_backend", lambda: "cuda")
    with pytest.raises(ValueError, match="cannot run here"):
        DecodeEngine(model, params, slots=1, cache_layout="paged",
                     block_size=4, kernel="fused",
                     cache_scope="gpu_fused")
    # auto still resolves quietly to gather there
    engine = DecodeEngine(model, params, slots=1, cache_layout="paged",
                          block_size=4, kernel="auto",
                          cache_scope="gpu_auto")
    assert engine.kernel == "gather"


def test_models_audit_registers_fused_programs():
    from flashy_tpu.models.audit import numerics_audit_programs

    labels = {e["label"] for e in numerics_audit_programs()}
    assert "attention/paged-int8-fused" in labels
    assert "attention/paged-int8-fused-verify" in labels
    assert "attention/paged-int8" in labels  # the gather oracle stays


def test_ft203_anchors_inside_the_fused_kernel():
    # the gate is only worth having if it (a) passes on the shipped
    # kernel and (b) anchors INSIDE the pallas_call — a vacuous pass
    # (skeleton not found) is itself a finding by FT203's design
    from flashy_tpu.analysis.numerics.core import NumericsProgram
    from flashy_tpu.analysis.numerics.quant_scale import QuantScaleAuditor
    from flashy_tpu.models.audit import numerics_audit_programs

    auditor = QuantScaleAuditor()
    seen = 0
    for entry in numerics_audit_programs():
        if "fused" not in entry["label"]:
            continue
        if entry.get("quant_roles") == {}:
            # an explicit opt-out (the paged-int8-write convention):
            # the ssd fused scan carries no int8 payloads or scales, so
            # there is no quantized contraction to anchor against
            continue
        seen += 1
        program = NumericsProgram(**entry)
        findings = list(auditor.audit(program))
        assert findings == [], findings
        graph = program.graph()
        roles = {role: program.invars_matching(needle)
                 for role, needle in program.quant_roles.items()}
        skeleton = auditor._skeleton(program, graph, roles)
        assert isinstance(skeleton, tuple), skeleton  # anchored, not a
        # structure finding: scores dot, softmax exp and out dot were
        # all located inside the kernel body
    assert seen == 2


def test_ft203_catches_double_scaled_fused_rewrite():
    # the classic fused-rewrite bug the auditor exists for: dequantize
    # the payload AND keep the folded multiply — scale applied twice
    import jax.numpy as jnp

    from flashy_tpu.analysis.numerics.core import NumericsProgram
    from flashy_tpu.analysis.numerics.quant_scale import QuantScaleAuditor
    from flashy_tpu.ops.paged_decode import fused_paged_attention

    entry, table = _pool_fixture("int8")
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 1, 2, 8)), jnp.float32)
    positions = jnp.asarray([[5], [2]], jnp.int32)

    def double_scaled(q_in, entry_in, table_in, positions_in):
        broken = {
            "k": entry_in["k"],
            # pre-scaled dense V copy, scales still handed to the fold
            "v": (entry_in["v"].astype(jnp.float32)
                  * entry_in["v_scale"].reshape(
                      entry_in["v"].shape[:-1])[..., None]),
            "k_scale": entry_in["k_scale"],
            "v_scale": entry_in["v_scale"],
        }
        return fused_paged_attention(q_in, broken, table_in,
                                     positions_in, head_dim=8,
                                     dtype=jnp.float32, interpret=True)

    program = NumericsProgram(label="attention/broken-double-scale",
                              fn=double_scaled,
                              example_args=(q, entry, table, positions))
    keys = {f.key for f in QuantScaleAuditor().audit(program)}
    assert "double-scale:v" in keys, keys
