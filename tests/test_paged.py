# Paged KV cache: block-pool bookkeeping (free list, refcounts,
# reservation accounting, LRU eviction), the prefix index (full-block
# chain matches, partial-block COW forks, the len-1 cap), token-exact
# serving through the paged engine (greedy, int8 K/V, speculative
# verify, chunked prefill, scan-stacked layouts), the bit-level
# isolation proofs (COW writer never mutates a shared block; stale
# draft rows beyond the accepted position are rewritten identically by
# a fresh prefill), refcounted free-on-retire, and the pool/prefix
# metrics fan-out into summary/serve.json/info.
import heapq
import json
import logging

import numpy as np
import pytest

from flashy_tpu.serve import (
    BlockPool, ContinuousBatchingScheduler, DecodeEngine, NGramDraft,
    PoolExhausted, PrefixIndex, ServeMetrics,
)


def _tiny_model(vocab=32, max_seq_len=32, scan_layers=False, layers=2):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=vocab, dim=16, num_layers=layers,
                            num_heads=2, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32,
                            scan_layers=scan_layers)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
    return model, params


def _generate(model, params, prompt, max_new):
    from flashy_tpu.models.decoding import generate
    return np.asarray(generate(model, params,
                               np.asarray(prompt, np.int32)[None],
                               max_new_tokens=max_new))[0]


def _paged_engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_layout", "paged")
    kw.setdefault("block_size", 4)
    engine = DecodeEngine(model, params, **kw)
    engine.warmup()
    return engine


def _slot_kv(engine, slot, length):
    """Logical K/V rows of one slot's first layer, [length, H, Dh]."""
    from flashy_tpu.ops.paged_attention import slot_kv

    cache = engine._cache
    entry = cache if "k" in cache else cache["block_0"]
    if "k" in cache and engine._cfg.scan_layers:
        entry = {name: leaf[0] for name, leaf in cache.items()}
    k, v = slot_kv(entry, engine._table_host[slot], length)
    return np.asarray(k), np.asarray(v)


# ----------------------------------------------------------------------
# BlockPool bookkeeping
# ----------------------------------------------------------------------
def test_block_pool_reserves_and_frees():
    pool = BlockPool(num_blocks=9, block_size=4, max_seq_len=16)
    plan = pool.plan(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    assert plan.reserve_blocks == 2  # ceil((5 + 3) / 4)
    assert plan.fresh_needed == 2 and plan.matched_tokens == 0
    row, start, cow = pool.commit(plan, slot=0)
    assert start == 0 and cow is None
    assert row.tolist()[:2] == [1, 2] and set(row[2:]) == {0}
    assert pool.free_blocks == 6 and pool.in_use_blocks == 2
    freed = pool.release(0)
    # no prefix registration happened (on_live never called): all freed
    assert sorted(freed) == [1, 2]
    assert pool.free_blocks == 8
    pool.check()


def test_block_pool_headroom_and_exhaustion():
    pool = BlockPool(num_blocks=5, block_size=4, max_seq_len=16,
                     prefix_cache=False)
    prompt = np.arange(1, 9, dtype=np.int32)
    row, _, _ = pool.commit(pool.plan(prompt, 8), slot=0)  # 4 blocks
    assert pool.headroom == 0
    assert not pool.can_admit(prompt, 8)
    with pytest.raises(PoolExhausted):
        pool.commit(pool.plan(prompt, 8), slot=1)
    pool.check()  # the failed commit changed nothing
    pool.release(0)
    assert pool.can_admit(prompt, 8)


def test_block_pool_spec_overshoot_reserved():
    pool = BlockPool(num_blocks=17, block_size=4, max_seq_len=32,
                     spec_overshoot=4)
    # 5 prompt + 3 new = 2 blocks dense; +4 overshoot rows -> 3 blocks
    assert pool.reserve_blocks_for(5, 3) == 3
    # capped at the table width whatever the overshoot
    assert pool.reserve_blocks_for(29, 3) == 8


def test_block_pool_double_reservation_rejected():
    pool = BlockPool(num_blocks=9, block_size=4, max_seq_len=16)
    pool.commit(pool.plan(np.arange(4, dtype=np.int32), 2), slot=0)
    with pytest.raises(ValueError, match="already holds"):
        pool.commit(pool.plan(np.arange(4, dtype=np.int32), 2), slot=0)


# ----------------------------------------------------------------------
# PrefixIndex: chain matches, partial matches, eviction
# ----------------------------------------------------------------------
def test_prefix_index_full_chain_match():
    index = PrefixIndex()
    prompt = np.arange(10, dtype=np.int32)
    index.register(prompt, blocks=[3, 4], block_size=4)
    full, partial = index.match(prompt, 4)
    assert [e.block for e in full] == [3, 4]
    # the 2-token tail was never registered (only FULL blocks are), so
    # nothing partial chains off block 4
    assert partial is None
    # a different continuation after one shared block
    other = np.concatenate([np.arange(4), [9, 9, 9, 9]]).astype(np.int32)
    full, partial = index.match(other, 4)
    assert [e.block for e in full] == [3]
    assert partial is None  # second block shares no leading token


def test_prefix_index_partial_longest_match():
    index = PrefixIndex()
    index.register(np.asarray([1, 2, 3, 4], np.int32), [5], 4)
    index.register(np.asarray([1, 2, 9, 9], np.int32), [6], 4)
    full, partial = index.match(np.asarray([1, 2, 3, 7], np.int32), 4)
    assert full == [] and partial[0].block == 5 and partial[1] == 3


def test_prefix_index_register_keeps_existing_entry():
    index = PrefixIndex()
    prompt = np.arange(4, dtype=np.int32)
    assert index.register(prompt, [3], 4) == [3]
    # a twin block registers nothing — the cached entry wins
    assert index.register(prompt, [7], 4) == []
    assert index.match(prompt, 4)[0][0].block == 3


def test_block_pool_evicts_lru_cached_blocks():
    pool = BlockPool(num_blocks=5, block_size=4, max_seq_len=16)
    a = np.asarray([1, 1, 1, 1, 9], np.int32)
    b = np.asarray([2, 2, 2, 2, 9], np.int32)
    for slot, prompt in enumerate((a, b)):
        pool.commit(pool.plan(prompt, 2), slot)
        pool.on_live(slot)
    pool.release(0)
    pool.release(1)
    # both prompts' full blocks stay cached at refcount 0
    assert pool.free_blocks == 2 and pool.cached_blocks == 2
    assert pool.headroom == 4
    # a 3-block admission must evict the LRU cached block (prompt a's)
    row, _, _ = pool.commit(pool.plan(np.full(9, 7, np.int32), 3), slot=0)
    assert pool.evictions == 1
    assert pool.index.match(b[:4], 4)[0], "MRU entry survived"
    assert not pool.index.match(a[:4], 4)[0], "LRU entry evicted"
    pool.check()


def test_block_pool_never_evicts_its_own_matched_chain():
    """An admission whose matched prefix blocks are the only evictable
    cached blocks must REFUSE (they only look evictable because their
    refcount bump happens at commit) — evicting them would leave the
    new table referencing freed blocks. With an unrelated cached block
    available, the same admission succeeds and the chain survives."""
    pool = BlockPool(num_blocks=8, block_size=4, max_seq_len=16)
    shared = np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32)
    pool.commit(pool.plan(shared, 4), slot=0)   # 4 blocks
    pool.on_live(0)
    pool.release(0)                             # 2 full blocks cached
    # live reservations pin the remaining 5 free blocks (reserves cap
    # at max_blocks=4 per slot, so it takes two)
    pool.commit(pool.plan(np.full(13, 7, np.int32), 3), slot=1)  # 4 blocks
    pool.commit(pool.plan(np.full(2, 8, np.int32), 2), slot=3)   # 1 block
    assert pool.free_blocks == 0 and pool.cached_blocks == 2
    # matches both cached blocks, needs 2 fresh — only "evictable"
    # blocks ARE the matched chain: must refuse, not self-cannibalize
    assert not pool.can_admit(shared, 4)
    with pytest.raises(PoolExhausted):
        pool.commit(pool.plan(shared, 4), slot=2)
    pool.check()
    assert pool.index.match(shared, 4)[0], "matched chain survived"
    # once unrelated blocks free up, the same admission goes through
    pool.release(1)
    row, start, _ = pool.commit(pool.plan(shared, 4), slot=2)
    assert start == 8  # both cached blocks served from the index
    assert pool.index.match(shared, 4)[0]
    pool.check()


def _evict_afresh(pool, need, protect):
    """The loop `BlockPool._evict_for` replaced: pick the least recently
    used evictable leaf afresh, by a whole scan, for every block."""
    while pool.free_blocks < need:
        candidates = [e for e in pool.index.evictable(pool.refcount)
                      if e.block not in protect]
        if not candidates:
            raise PoolExhausted("nothing left to evict")
        block = pool.index.evict(candidates[0])
        pool._cached.discard(block)
        heapq.heappush(pool._free, block)


@pytest.mark.parametrize("seed", range(6))
def test_eviction_takes_the_victims_the_rescanning_loop_took(seed,
                                                              monkeypatch):
    """One scan and a heap against a fresh scan per block, on what the
    heap could get wrong: long chains whose leaves appear one at a time,
    chains that fork (a parent becomes a leaf only when its LAST child
    goes), prefixes matched again so that a parent was used after its
    child, a live slot holding part of a chain, and protected blocks in
    the middle of one. Same victims, in the same order."""
    rng = np.random.default_rng(seed)

    def build():
        pool = BlockPool(num_blocks=641, block_size=4, max_seq_len=256)
        draws = np.random.default_rng(seed)
        trunk = draws.integers(0, 50, 80).astype(np.int32)
        prompts = []
        for _ in range(10):
            keep = 4 * int(draws.integers(0, 20))  # fork off the trunk
            own = draws.integers(50, 99, int(draws.integers(40, 240 - keep)))
            prompts.append(np.concatenate([trunk[:keep],
                                           own.astype(np.int32)]))
        for slot, prompt in enumerate(prompts):
            pool.commit(pool.plan(prompt, 2), slot)
            pool.on_live(slot)
        for slot in draws.permutation(10)[:8]:  # two stay live
            pool.release(int(slot))
        for prompt in draws.permutation(10)[:3]:  # used again, parents
            pool.index.match(prompts[prompt][:12], 4)  # after children
        return pool, prompts

    old, prompts = build()
    new, _ = build()
    matched = prompts[int(rng.integers(10))]
    protect = {e.block for e in new.index.match(matched, 4)[0][:3]}
    old.index.match(matched, 4)  # the same clock ticks on both sides
    victims = {id(old): [], id(new): []}
    evict = PrefixIndex.evict

    def spy(index, entry):
        owner = old if index is old.index else new
        victims[id(owner)].append(entry.block)
        return evict(index, entry)

    monkeypatch.setattr(PrefixIndex, "evict", spy)
    need = new.free_blocks + new.cached_blocks // 2
    assert need >= new.free_blocks + 80, "long enough to walk whole chains"
    new._evict_for(need, protect)
    _evict_afresh(old, need, protect)
    assert victims[id(new)] == victims[id(old)]
    assert len(victims[id(new)]) == len(set(victims[id(new)])) >= 80
    assert not protect & set(victims[id(new)])
    new.check()
    with pytest.raises(PoolExhausted):
        new._evict_for(new.free_blocks + new.cached_blocks + 1, protect)


def test_block_pool_ttl_expired_request_leaks_nothing():
    """A queued request shed by TTL never held blocks; a served one
    frees its private blocks on retirement (refcounted free-on-retire,
    with only index-cached prompt blocks staying resident)."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params)
    scheduler = ContinuousBatchingScheduler(engine)
    pool = engine._pool
    prompt = np.arange(1, 10, dtype=np.int32)
    served = scheduler.submit(prompt, 4)
    expired = scheduler.submit(prompt, 4, ttl=1e-4)
    scheduler.step()  # admits `served` into slot 0; slot 1 free
    import time
    time.sleep(2e-3)
    scheduler.run()
    assert served.done and expired.finish_reason == "expired"
    # expired never touched the pool; served freed all but its two
    # index-cached full prompt blocks
    assert pool.in_use_blocks == pool.cached_blocks == 2
    pool.check()


# ----------------------------------------------------------------------
# token-exactness through the paged engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("scan_layers", [False, True])
def test_paged_greedy_token_exact(scan_layers):
    model, params = _tiny_model(scan_layers=scan_layers)
    engine = _paged_engine(model, params, slots=3)
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(0)
    system = rng.integers(0, 32, 6).astype(np.int32)
    handles = []
    for n in range(6):
        tail = rng.integers(0, 32, 1 + n % 3).astype(np.int32)
        handles.append(scheduler.submit(np.concatenate([system, tail]),
                                        4 + n % 5))
    scheduler.run()
    for h in handles:
        want = _generate(model, params, h.prompt, h.max_new_tokens)
        np.testing.assert_array_equal(h.output, want)
    # the shared system prompt was served from the index
    assert engine._pool.prefix_hit_rate > 0.2
    assert engine.compile_cache.stats()["recompiles"] == 0


def test_paged_int8_greedy_token_exact():
    """int8 K/V quantization keeps greedy output token-identical to
    generate() on this fixed workload (near-tie argmax flips are a
    random-init artifact; the seed below has comfortable margins —
    what matters is that paging/sharing adds NOTHING beyond the
    quantization itself)."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params, slots=2, kv_dtype="int8")
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(0)
    handles = [scheduler.submit(rng.integers(0, 32, 5 + i).astype(np.int32),
                                5) for i in range(4)]
    scheduler.run()
    for h in handles:
        want = _generate(model, params, h.prompt, h.max_new_tokens)
        np.testing.assert_array_equal(h.output, want)


def test_paged_speculative_verify_token_exact():
    """Speculative verify through the block tables stays token-exact
    whatever the draft proposes, with zero post-warm-up compiles."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params, slots=2, spec_k=3)
    warm = engine.compile_cache.stats()["misses"]
    draft = NGramDraft(slots=2, k=3, ngram=2)
    scheduler = ContinuousBatchingScheduler(engine, draft=draft)
    rng = np.random.default_rng(1)
    handles = []
    for i in range(4):
        pattern = rng.integers(0, 32, 2).astype(np.int32)
        prompt = np.tile(pattern, 4)[:6 + i % 2]
        handles.append(scheduler.submit(prompt, 8))
    scheduler.run()
    for h in handles:
        want = _generate(model, params, h.prompt, h.max_new_tokens)
        np.testing.assert_array_equal(h.output, want)
    stats = engine.compile_cache.stats()
    assert stats["recompiles"] == 0 and stats["misses"] == warm


def test_paged_rollback_rows_bit_identical_to_fresh_prefill():
    """The rollback-is-free proof against block tables: after a verify
    step whose drafts were (partly) rejected, the slot's LIVE K/V rows
    [0, position) are bit-identical to a fresh prefill of the emitted
    tokens — the stale draft rows beyond the position sit past every
    causal horizon and are simply overwritten later."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params, slots=2, spec_k=3)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    slot = engine.acquire_slot()
    engine.admit(slot, prompt, 8)
    start = 0
    while True:
        start, first = engine.prefill_chunk(slot, prompt, start)
        if first is not None:
            break
    # garbage drafts: mostly rejected, stale rows written past the
    # accepted position in the slot's blocks
    drafts = np.asarray([[7, 7, 7], [0, 0, 0]], np.int32)
    out, accepted = engine.decode_speculative(drafts)
    emitted = [first] + [int(t) for t in out[slot, :int(accepted[slot]) + 1]]
    length = engine.slot_length(slot)
    assert length == prompt.size + int(accepted[slot]) + 1
    k_live, v_live = _slot_kv(engine, slot, length)

    # fresh prefill of the SAME logical sequence in the second slot
    other = engine.acquire_slot()
    replay = np.concatenate([prompt, emitted[:-1]]).astype(np.int32)
    engine.admit(other, replay, 4)
    start = 0
    while True:
        start, first2 = engine.prefill_chunk(other, replay, start)
        if first2 is not None:
            break
    k_fresh, v_fresh = _slot_kv(engine, other, length)
    np.testing.assert_array_equal(k_live, k_fresh)
    np.testing.assert_array_equal(v_live, v_fresh)


def test_paged_chunked_prefill_boundary_exact():
    """Prompt lengths straddling chunk boundaries (chunk-1, chunk,
    chunk+1, 2*chunk) all prefill token-exactly on the paged layout."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params, slots=2, block_size=4,
                           prefix_cache=False)
    assert engine.chunk == 4  # paged default: chunk == block_size
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(2)
    handles = [scheduler.submit(rng.integers(0, 32, n).astype(np.int32), 5)
               for n in (3, 4, 5, 8)]
    scheduler.run()
    for h in handles:
        want = _generate(model, params, h.prompt, h.max_new_tokens)
        np.testing.assert_array_equal(h.output, want)


# ----------------------------------------------------------------------
# the int8 pool's scale rows (PR 30)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("scan_layers", [False, True])
def test_int8_pool_keeps_a_blocks_scales_in_one_row(scan_layers):
    # one lane-dense row of block_size * H float32 values a block, the
    # shape the fused read copies as stored; the payloads and a pool in
    # the model's dtype are what they were, and the bytes are counted
    # from the same spec
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_attention import (init_pool, pool_bytes,
                                                pool_spec)

    spec = pool_spec(9, 16, 4, 8, jnp.bfloat16, "int8")
    assert spec["k"] == spec["v"] == ((9, 16, 4, 8), jnp.int8)
    assert spec["k_scale"] == spec["v_scale"] == ((9, 1, 64), jnp.float32)
    assert pool_spec(9, 16, 4, 8, jnp.bfloat16, "model") == {
        "k": ((9, 16, 4, 8), jnp.bfloat16),
        "v": ((9, 16, 4, 8), jnp.bfloat16)}
    model, _ = _tiny_model(scan_layers=scan_layers)
    cfg = model.config
    pool = init_pool(cfg, 5, 4, "int8")
    entry = pool if scan_layers else pool["block_0"]
    lead = (cfg.num_layers,) if scan_layers else ()
    assert entry["k_scale"].shape == lead + (5, 1, 4 * cfg.num_heads)
    assert entry["k"].shape == lead + (5, 4, cfg.num_heads, cfg.head_dim)
    per_block = 2 * 4 * cfg.num_heads * (cfg.head_dim + 4)
    assert pool_bytes(cfg, 5, 4, "int8") == 5 * per_block * cfg.num_layers


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_paged_write_then_gather_round_trips_scattered_rows(kv_dtype):
    # rows written at scattered (block, offset) pairs through
    # out-of-order tables come back at their logical positions, every
    # other row stays as it was, and a position past the table's
    # coverage lands in the sentinel block — the payload AND, for int8,
    # the H scales at `offset * H` of the block's row
    import jax.numpy as jnp
    from flashy_tpu.models.quantize import dequantize_kv, quantize_kv
    from flashy_tpu.ops.paged_attention import (gather_kv, paged_write,
                                                pool_spec)

    blocks, bs, heads, dim = 9, 4, 3, 8
    entry = {name: jnp.zeros(shape, dtype) for name, (shape, dtype)
             in pool_spec(blocks, bs, heads, dim, jnp.float32,
                          kv_dtype).items()}
    table = jnp.asarray([[7, 2, 5], [3, 8, 1]], jnp.int32)
    # slot 0: first row, a block's last row, mid-block, past the table;
    # slot 1: the table's last row, two neighbours, past the table
    positions = jnp.asarray([[0, 7, 9, 12], [11, 4, 5, 100]], jnp.int32)
    rng = np.random.default_rng(3)
    new_k = jnp.asarray(rng.normal(size=(2, 4, heads, dim)), jnp.float32)
    new_v = jnp.asarray(rng.normal(size=(2, 4, heads, dim)), jnp.float32)
    # the overshoot rows both land on the sentinel's (0, 0): make them
    # one row, so the duplicate write has one answer
    new_k = new_k.at[1, 3].set(new_k[0, 3])
    new_v = new_v.at[1, 3].set(new_v[0, 3])
    out = paged_write(entry, new_k, new_v, table, positions)

    def stored(x):
        return dequantize_kv(*quantize_kv(x)) if kv_dtype == "int8" else x

    want_k = np.zeros((2, 3 * bs, heads, dim), np.float32)
    want_v = np.zeros_like(want_k)
    for slot in range(2):
        for row in range(3):
            pos = int(positions[slot, row])
            want_k[slot, pos] = stored(new_k[slot, row])
            want_v[slot, pos] = stored(new_v[slot, row])
    got_k, got_v = gather_kv(out, table, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    # the sentinel block took the overshoot rows at offset 0, and
    # nothing else in the pool moved
    sent_k, _ = gather_kv(out, jnp.zeros((1, 1), jnp.int32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(sent_k)[0, 0],
                                  stored(new_k[0, 3]))
    assert not np.asarray(sent_k)[0, 1:].any()
    assert not np.asarray(out["k"])[[4, 6]].any()
    if kv_dtype == "int8":
        # physical placement: position 9 of slot 0 is block 5, offset 1
        _, scale = quantize_kv(new_k[0, 2])
        row = np.asarray(out["k_scale"])[5, 0]
        np.testing.assert_array_equal(row[heads:2 * heads], scale)
        assert not row[:heads].any() and not row[2 * heads:].any()
        assert out["k_scale"].shape == entry["k_scale"].shape


@pytest.mark.parametrize("rows", [1, 5, 40])
def test_scale_row_copies_match_the_xla_scatter(rows):
    # the TPU's write of whole scale rows (a kernel of async copies, in
    # interpret mode here) against the scatter every other backend runs:
    # scattered blocks, rows that name one block twice with equal
    # content (a chunk's rows of one block), every other row untouched
    import jax.numpy as jnp
    from flashy_tpu.ops.paged_attention import _row_copies

    rng = np.random.default_rng(rows)
    leaf = jnp.asarray(rng.normal(size=(23, 1, 128)), jnp.float32)
    blocks = rng.permutation(np.arange(1, 23)).astype(np.int32)
    blocks = np.resize(blocks[:-(-rows // 2)], rows)  # each block twice
    fresh = rng.normal(size=(23, 1, 128)).astype(np.float32)[blocks]
    want = leaf.at[jnp.asarray(blocks)].set(jnp.asarray(fresh))
    got = _row_copies(leaf, jnp.asarray(fresh), jnp.asarray(blocks),
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    untouched = np.setdiff1d(np.arange(23), blocks)
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(leaf)[untouched])


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("how", ["handoff", "preempt"])
def test_int8_slot_survives_handoff_and_preemption(how, kernel):
    # the scale rows travel with their blocks: a slot handed from a
    # prefill engine to a decode engine over one pool, and a slot
    # evicted mid-decode and admitted again, emit the tokens an int8
    # engine that did neither emits
    model, params = _tiny_model()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 32, n).astype(np.int32) for n in (5, 9, 6)]
    alone = _paged_engine(model, params, slots=2, kv_dtype="int8",
                          kernel=kernel, cache_scope=f"alone_{how}_{kernel}")
    scheduler = ContinuousBatchingScheduler(alone)
    want = [scheduler.submit(p, 6) for p in prompts]
    scheduler.run()
    if how == "handoff":
        from flashy_tpu.serve.fleet import DisaggregatedPair
        pair = DisaggregatedPair(model, params, prefill_slots=1,
                                 decode_slots=2, block_size=4,
                                 kernel=kernel, kv_dtype="int8")
        pair.warmup(prompt_lengths=[len(p) for p in prompts])
        outputs = pair.serve(prompts, max_new_tokens=6)
        assert len(pair.handoffs) == len(prompts)
        for handle, out in zip(want, outputs):
            np.testing.assert_array_equal(handle.generated, out)
        pair.pool.check()
        return
    engine = _paged_engine(model, params, slots=2, kv_dtype="int8",
                           kernel=kernel,
                           cache_scope=f"preempted_{kernel}")
    engine.warmup(prompt_lengths=range(4, 16))
    scheduler = ContinuousBatchingScheduler(engine)
    handles = [scheduler.submit(p, 6) for p in prompts]
    for _ in range(3):
        scheduler.step()
    victim = scheduler.preempt(handles[0].slot)
    assert victim is handles[0] and 0 < len(victim.generated) < 6
    scheduler.run()
    assert victim.preemptions == 1
    for handle, got in zip(want, handles):
        np.testing.assert_array_equal(handle.output, got.output)
    engine._pool.check()


# ----------------------------------------------------------------------
# pools without scale leaves compile to the programs they were (PR 30)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def untouched_programs():
    from tests.data.record_untouched_pool_programs import programs
    return programs()


@pytest.mark.parametrize("engine", ["latent/gather", "latent/fused",
                                    "kv-toy/gather", "kv-toy/fused",
                                    "kv-wide/fused"])
def test_programs_of_pools_without_scales_are_untouched(untouched_programs,
                                                        engine):
    # the lowered text of decode, both prefill slices, verify and the
    # COW copy of a latent engine and of K/V engines in the model's
    # dtype, byte-equal (by sha256) to what was recorded by
    # tests/data/record_untouched_pool_programs.py: verify and the copy
    # as commit 61bbbe0 lowered them before PR 30's first edit; decode
    # and the slices as PR 34 left them (they return the advanced slot
    # state: the recorder's header says why)
    import os
    recorded = os.path.join(os.path.dirname(__file__), "data",
                            "untouched_pool_programs.json")
    with open(recorded) as f:
        want = {name: digest for name, digest in json.load(f).items()
                if name.startswith(engine + "/")}
    got = {name: digest for name, digest in untouched_programs.items()
           if name.startswith(engine + "/")}
    assert len(want) == 5 and got == want


# ----------------------------------------------------------------------
# COW fork isolation
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_cow_fork_never_mutates_the_shared_block(kv_dtype):
    """Two slots sharing a prefix: the second slot's COW fork and all
    its later writes leave the first slot's (and the index's) block
    bytes untouched — asserted on the raw pool arrays, an int8 pool's
    scale rows among them (`copy_block_fn` follows the pool's spec)."""
    model, params = _tiny_model()
    engine = _paged_engine(model, params, slots=2, block_size=4,
                           kv_dtype=kv_dtype)
    pool = engine._pool
    base = np.asarray([1, 2, 3, 4, 5, 6, 7, 8], np.int32)  # 2 full blocks

    scheduler = ContinuousBatchingScheduler(engine)
    first = scheduler.submit(base, 6)
    scheduler.run()
    shared_block = pool.index.match(base, 4)[0][1].block  # 2nd block
    cache = engine._cache
    entry = cache if "k" in cache else cache["block_0"]
    before = {name: np.asarray(leaf[..., shared_block, :, :, :]
                               if name in ("k", "v")
                               else leaf[..., shared_block, :, :])
              for name, leaf in entry.items()}

    # same first full block, diverging inside the second -> full-block
    # share, then a COW fork of the partially matching second block
    second = scheduler.submit(
        np.asarray([1, 2, 3, 4, 5, 6, 9, 9], np.int32), 6)
    scheduler.run()
    assert pool.cow_forks == 1
    entry = engine._cache if "k" in engine._cache \
        else engine._cache["block_0"]
    for name, leaf in entry.items():
        after = np.asarray(leaf[..., shared_block, :, :, :]
                           if name in ("k", "v")
                           else leaf[..., shared_block, :, :])
        np.testing.assert_array_equal(before[name], after)
    # and both outputs stayed exact: the forked copy holds what a pool
    # that never shared a block holds (int8 rounds both alike)
    if kv_dtype == "model":
        want = [_generate(model, params, h.prompt, h.max_new_tokens)
                for h in (first, second)]
    else:
        alone = ContinuousBatchingScheduler(_paged_engine(
            model, params, slots=2, block_size=4, kv_dtype=kv_dtype,
            prefix_cache=False, cache_scope="cow_alone"))
        want = [alone.submit(h.prompt, h.max_new_tokens)
                for h in (first, second)]
        alone.run()
        want = [h.output for h in want]
    for h, tokens in zip((first, second), want):
        np.testing.assert_array_equal(h.output, tokens)


def test_paged_admission_backpressure_under_tiny_pool():
    """A pool too small for two concurrent requests serializes them
    (head-of-line wait, not PoolExhausted, not over-commit)."""
    model, params = _tiny_model()
    # 5 real blocks: one 8+8-token request needs 4; two need 8 > 5
    engine = _paged_engine(model, params, slots=2, block_size=4,
                           num_blocks=6, prefix_cache=False)
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(4)
    h1 = scheduler.submit(rng.integers(0, 32, 8).astype(np.int32), 8)
    h2 = scheduler.submit(rng.integers(0, 32, 8).astype(np.int32), 8)
    scheduler.step()
    assert engine.live_count == 1 and h2.state == "queued"
    scheduler.run()
    assert h1.done and h2.done
    assert engine._pool.peak_in_use <= engine._pool.capacity
    for h in (h1, h2):
        want = _generate(model, params, h.prompt, h.max_new_tokens)
        np.testing.assert_array_equal(h.output, want)


# ----------------------------------------------------------------------
# metrics / serve.json / info
# ----------------------------------------------------------------------
def test_paged_metrics_summary_and_serve_json(tmp_path):
    model, params = _tiny_model()
    engine = _paged_engine(model, params, kv_dtype="int8")
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 32, 9).astype(np.int32)
    # sequential, so each later request finds the prompt registered
    # (registration happens at prefill COMPLETION, not admission)
    held = []  # pool bytes a live token, while any is live
    for _ in range(3):
        scheduler.submit(prompt, 4)
        while not scheduler.idle:
            scheduler.step()
            held.append(engine.pool_stats()["kv_bytes_per_token"])
    summary = scheduler.metrics.summary()
    assert 0 < summary["pool_occupancy_p95"] <= 1
    assert summary["prefix_hit_rate"] > 0.3
    assert summary["prefix_hit_requests"] == 2
    # the pool's own number, asked of the pool: no list samples it
    assert max(held) > 0 and held[-1] == 0
    assert "kv_bytes_per_token_p50" not in summary

    path = scheduler.metrics.write_status(tmp_path)
    status = json.loads(path.read_text())
    assert status["cache_layout"] == "paged"
    assert status["kv_dtype"] == "int8"

    from flashy_tpu.info import format_serve_status
    line = format_serve_status(status)
    assert "cache=paged/int8" in line
    assert "prefix_hit=" in line and "pool_p95=" in line


def test_dense_engine_summary_untouched(tmp_path):
    """The dense layout reports no pool/prefix keys (reference path
    unchanged) but still labels its layout in serve.json."""
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2)
    engine.warmup(prompt_lengths=[4])
    scheduler = ContinuousBatchingScheduler(engine)
    scheduler.submit(np.arange(1, 5, dtype=np.int32), 3)
    scheduler.run()
    summary = scheduler.metrics.summary()
    assert "pool_occupancy_p95" not in summary
    assert "prefix_hit_rate" not in summary
    status = json.loads(scheduler.metrics.write_status(tmp_path).read_text())
    assert status["cache_layout"] == "dense"


def test_paged_pool_counters_reach_tracer():
    """Pool occupancy / prefix samples fan out as tracer counter
    tracks."""
    class _Recorder:
        def __init__(self):
            self.counters = []

        def counter(self, kind, **values):
            self.counters.append((kind, values))

        def instant(self, *a, **k):
            pass

        def record(self, *a, **k):
            pass

    tracer = _Recorder()
    metrics = ServeMetrics(tracer=tracer)
    metrics.on_pool(occupancy=0.5, in_use=4, capacity=8, cached=1)
    metrics.on_prefix(6, 8)
    kinds = {kind for kind, _ in tracer.counters}
    assert {"serve/pool_occupancy", "serve/prefix_hit"} == kinds


def test_paged_engine_validation():
    model, params = _tiny_model()
    with pytest.raises(ValueError, match="cache_layout"):
        DecodeEngine(model, params, slots=1, cache_layout="virtual")
    with pytest.raises(ValueError, match="int8"):
        DecodeEngine(model, params, slots=1, kv_dtype="int8")
    with pytest.raises(ValueError, match="divide"):
        DecodeEngine(model, params, slots=1, cache_layout="paged",
                     block_size=5)
    engine = DecodeEngine(model, params, slots=1, cache_layout="paged",
                          block_size=4)
    with pytest.raises(ValueError, match="chunks"):
        engine.prefill(0, np.arange(4, dtype=np.int32))


@pytest.mark.slow
def test_paged_demo_leg(caplog):
    from flashy_tpu.serve.__main__ import run_paged_demo
    with caplog.at_level(logging.INFO):
        assert run_paged_demo(requests=12, dense_slots=3, paged_slots=8,
                              stagger=6) == 0
