# Grouped attention by layer kind (`attn_kind='gqa'`): window and full
# layers with their own KV head counts, keys wider than values, partial
# rotary at a base a kind, a value scale, a learned sink in the window
# layers; the cache whose window layers keep a ring a slot beside the
# full layers' block pool; the expert layer without a shared expert or
# a group limit — at toy widths that keep the shape (hidden 64, 8 query
# heads, 2 | 4 KV heads, keys 24 with 8 rotated, values 16, window 8,
# pattern [0,1,1,0], 8 experts of which 4 held), on the CPU, against the
# plain reference the benchmark brings (benchmarks/harness/
# reference_mimo.py, written from the equations). Every tolerance states
# its reason.
"""The third model family through model, decode step, ring, pool, engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import model_mimo, reference_dots, reference_mimo
from flashy_tpu.models import TransformerConfig, TransformerLM, gqa, moe
from flashy_tpu.models.decoding import generate
from flashy_tpu.ops.paged_attention import (block_bytes, init_pool,
                                            pool_bytes, ring_blocks,
                                            ring_positions, token_bytes,
                                            window_bytes)
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from flashy_tpu.serve.engine import state_bytes_per_slot

# float32 toy runs differ from the float32 reference only by the order
# of sums (grouped heads in one product, sorted experts, the softmax's
# denominator): a few ulps of logits whose spread is ~0.15
F32_TOL = 2e-5

TOY = {
    "num_attention_heads": 8, "swa_num_attention_heads": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
    "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "attention_value_scale": 0.707, "sliding_window": 8,
    "sliding_window_size": 8, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "layernorm_epsilon": 1e-5, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "held_experts": [0, 8], "n_routed_experts": 8,
    "n_routed_experts_published": 8, "vocab_size": 64, "hidden_size": 64,
    "num_hidden_layers": 4, "max_position_embeddings": 256,
    "intermediate_size": 96, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": None,
    "n_shared_experts": None, "moe_intermediate_size": 32,
    "torch_dtype": "float32"}


def _toy(held=(0, 8), **changes):
    config = dict(TOY, held_experts=list(held), n_routed_experts=held[1],
                  **changes)
    cfg = model_mimo.transformer_config(config, attention="dense",
                                        dtype=jnp.float32)
    model = TransformerLM(cfg)
    return config, cfg, model, model_mimo.seeded_params(model, 3)


def _tokens(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 64, shape),
                       jnp.int32)


def _engine(model, params, **kwargs):
    kwargs = {"slots": 3, "max_seq_len": 256, "cache_layout": "paged",
              "block_size": 4, "chunk": 8, **kwargs}
    engine = DecodeEngine(model, {"params": params}, **kwargs)
    engine.warmup()
    return engine


def test_the_config_maps_to_layer_kinds_and_a_tree_of_its_own():
    config, cfg, model, params = _toy()
    full, window = gqa.LayerKind(0, 2, 1e7, False), gqa.LayerKind(
        8, 4, 1e4, True)
    assert gqa.layer_kinds(cfg) == (full, window, window, full)
    assert (gqa.key_dim(cfg), gqa.value_dim(cfg), cfg.rotary_dim) == (
        24, 16, 8)  # int(0.334 x 24) = 8 of 24 dimensions rotate
    assert cfg.norm_eps == 1e-5 and cfg.dense_layers == 1
    # one fused [q | k | v] leaf by the layer's own KV heads; a sink a
    # query head in the window layers alone, float32
    assert params["block_0"]["attn"]["in_proj"]["kernel"].shape == (
        64, (8 + 2) * 24 + 2 * 16)
    assert params["block_1"]["attn"]["in_proj"]["kernel"].shape == (
        64, (8 + 4) * 24 + 4 * 16)
    assert params["block_1"]["attn"]["out"]["kernel"].shape == (8, 16, 64)
    assert params["block_1"]["attn"]["sink"].shape == (8,)
    assert params["block_1"]["attn"]["sink"].dtype == jnp.float32
    assert "sink" not in params["block_0"]["attn"]
    assert "mlp" in params["block_0"] and "shared" not in params[
        "block_1"]["moe"]
    # the catalog's real row maps too: 64 | 4 | 8 heads, 192 | 128, 64
    # rotated, the dense layer first
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "mimo-v2.5-ep16-7l.json")
    with open(path) as f:
        real = model_mimo.transformer_config(json.load(f), attention="dense")
    kinds = gqa.layer_kinds(real)
    assert [bool(k.window) for k in kinds] == [False] + [True] * 5 + [False]
    assert (kinds[0].kv_heads, kinds[1].kv_heads, real.rotary_dim,
            real.qk_head_dim, real.v_head_dim, real.window) == (
        4, 8, 64, 192, 128, 128)
    assert kinds[0].theta == 1e7 and kinds[1].theta == 1e4
    assert real.held_experts == (0, 16) and real.n_routed == 256


def test_full_and_window_layers_match_the_reference():
    # the full-sequence forward, the dense cache step and `generate()`:
    # grouped heads, partial rotary at two bases, the value scale, the
    # window and its sink, through all four layers
    from flashy_tpu.models.decoding import _apply_step, init_cache
    config, cfg, model, params = _toy()
    tokens = _tokens((2, 40))  # five windows long
    want = reference_mimo.logits(params, tokens, config)
    got = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    cache = init_cache(cfg, 2, 48)
    assert cache["block_0"]["k"].shape == (2, 48, 2, 24)
    assert cache["block_1"]["v"].shape == (2, 48, 4, 16)
    positions = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    logits, _ = _apply_step(model, {"params": params}, cfg, tokens,
                            positions, cache, jnp.int32(0))
    np.testing.assert_allclose(logits, want, atol=F32_TOL)
    out = generate(model, {"params": params}, tokens[:, :20],
                   max_new_tokens=10)
    greedy = reference_mimo.logits(params, out, config)[:, 19:-1].argmax(-1)
    np.testing.assert_array_equal(out[:, 20:], greedy)


@pytest.mark.parametrize("fault", [
    {"add_swa_attention_sink_bias": False},   # the sink dropped
    {"sliding_window": 4096},                 # the window dropped
    {"swa_rope_theta": 10000000},             # one base for both kinds
    {"attention_value_scale": 1.0},
    {"partial_rotary_factor": 1.0},           # the whole head rotated
], ids=["sink", "window", "second-base", "value-scale", "partial-rotary"])
def test_a_dropped_term_is_seen(fault):
    # the controls of the comparison above: a reference that leaves one
    # mechanism out is a thousand tolerances from the program
    config, cfg, model, params = _toy()
    tokens = _tokens((2, 40))
    got = model.apply({"params": params}, tokens)
    bad = reference_mimo.logits(params, tokens, dict(config, **fault))
    assert float(jnp.max(jnp.abs(got - bad))) > 1000 * F32_TOL


def test_query_tiles_change_nothing(monkeypatch):
    config, cfg, model, params = _toy()
    tokens = _tokens((1, 32))
    whole = model.apply({"params": params}, tokens)
    # a score block of 8 heads x 32 keys x 4 B a query: tiles of 8 rows
    monkeypatch.setattr(gqa, "SCORE_BLOCK_BYTES", 8 * 8 * 32 * 4)
    tiled = model.apply({"params": params}, tokens)
    # the same rows and sums in products of another shape: float32 ulps
    np.testing.assert_allclose(whole, tiled, atol=2e-6)


def test_a_ring_says_which_position_each_cell_holds():
    # 16 cells, the step's last row at position 21: cells 0..5 hold
    # 16..21, cells 6..15 hold 6..15; at position 3 cells 4.. hold
    # nothing of this request yet
    got = ring_positions(jnp.asarray([[19, 20, 21], [1, 2, 3]]), 16)
    assert got[0].tolist() == [16, 17, 18, 19, 20, 21] + list(range(6, 16))
    assert got[1].tolist() == [0, 1, 2, 3] + list(range(-12, 0))
    # a ring holds the window's 7 rows behind a query and a step's rows
    assert ring_blocks(8, 8, 4) == 4 and ring_blocks(128, 512, 16) == 40


def test_slices_then_decoding_through_ring_and_pool_match_the_reference():
    # The engine's own path: a 37-token prompt (window 8: several
    # windows) in slices of 8, 8, 8, 8 and 5 — a ring of 16 cells, so
    # every slice boundary falls inside a window and the context wraps
    # the ring more than twice — then 12 decode steps; slot 2's ring,
    # rows of slot 0 parked beside it. The logits of every step against
    # the reference's full forward over the final sequence. Share (4, 4)
    # of 8 experts: the chip's cut is in both.
    from flashy_tpu.serve.paged import paged_apply_step
    config, cfg, model, params = _toy(held=(4, 4))
    ring = ring_blocks(cfg.window, 8, 4)
    pool = init_pool(cfg, 14, 4, "model", slots=3, ring=ring)
    assert pool["block_0"]["k"].shape == (14, 4, 2 * 24)   # paged
    assert pool["block_1"]["k"].shape == (1 + 3, 16, 4 * 24)  # rings
    assert pool["block_1"]["v"].shape == (1 + 3, 16, 4 * 16)
    table = jnp.asarray([[3, 1, 4, 2, 8, 5, 7, 6, 9, 10, 11, 12, 13]],
                        jnp.int32)
    sequence = _tokens((1, 49), seed=4)
    got = []
    slices = ((0, 8), (8, 8), (16, 8), (24, 8), (32, 5))
    for start, size in slices + tuple((t, 1) for t in range(37, 49)):
        positions = (start + jnp.arange(size, dtype=jnp.int32))[None]
        logits, pool = paged_apply_step(
            model, {"params": params}, cfg, sequence[:, start:start + size],
            positions, pool, table, slots=jnp.asarray([2], jnp.int32))
        got.append(logits[0])
    want = reference_mimo.logits(params, sequence, config)[0]
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=F32_TOL)
    # the other slots' rings and the sentinel were never written
    assert not np.asarray(pool["block_1"]["k"][:3]).any()
    with pytest.raises(ValueError, match="addressed by slot"):
        paged_apply_step(model, {"params": params}, cfg, sequence[:, :1],
                         jnp.zeros((1, 1), jnp.int32), pool, table)


def test_window_layers_cache_bytes_do_not_grow_with_the_context():
    config, cfg, model, params = _toy()
    kinds = gqa.layer_kinds(cfg)
    row = (24 + 16) * 4                     # float32 toy: K and V a head
    full = sum(k.kv_heads for k in kinds if not k.window) * row
    window = sum(k.kv_heads for k in kinds if k.window) * row
    assert (full, window) == (2 * 2 * row, 2 * 4 * row)
    assert token_bytes(cfg) == (full, window)
    # one block of the pool that grows is the full layers' alone
    assert block_bytes(cfg, 4) == 4 * full
    ring = ring_blocks(cfg.window, 8, 4)
    assert window_bytes(cfg, 4, slots=3, ring=ring) == (1 + 3) * 16 * window
    assert pool_bytes(cfg, 50, 4, slots=3, ring=ring) == (
        50 * 4 * full + (1 + 3) * 16 * window)
    # a slot's state: its table's blocks and its rings; the window
    # layers' part is equal at 2 x window and at 20 x window
    for length in (16, 160):
        assert state_bytes_per_slot(
            cfg, length, "paged", block_size=4, ring=ring) == (
            length * full + 16 * window)
    assert state_bytes_per_slot(cfg, 160, "dense") == 160 * (full + window)
    engine = _engine(model, params)
    assert engine.ring == ring and engine.kernel == "gather"
    assert engine.cache_bytes() == pool_bytes(cfg, engine.num_blocks, 4,
                                              slots=3, ring=ring)
    assert engine.state_bytes_per_slot() == 256 * full + 16 * window
    stats = engine.pool_stats()
    assert stats["window_bytes"] == (1 + 3) * 16 * window
    assert stats["window_ring_blocks"] == ring
    assert stats["capacity"] == 3 * 64  # the full layers' blocks alone
    before = [leaf.shape for leaf in jax.tree_util.tree_leaves(engine._cache)]
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    handle = scheduler.submit(np.arange(150, dtype=np.int32) % 64, 40)
    scheduler.step()
    live = engine.pool_stats()
    # 190 tokens reserved: 48 blocks in the pool that grows; the rings
    # are the slot's whatever it holds
    assert live["in_use"] == 48 and live["window_bytes"] == stats[
        "window_bytes"]
    scheduler.run()
    assert len(handle.generated) == 40
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(
        engine._cache)] == before
    engine._pool.check()


def test_engine_serves_token_exact_and_checks_both_kinds():
    # staggered requests longer than many windows through the scheduler,
    # three slots, slices of 8 over rings of 16 cells; float32 and
    # kv_dtype='model': any mismatch is a ring or paging bug
    config, cfg, model, params = _toy(held=(4, 4))
    engine = _engine(model, params, keep_logits=True)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=8)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 64, 24).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 64, n).astype(
        np.int32)]) for n in (13, 46, 1, 66, 5)]
    handles = [scheduler.submit(p, 20) for p in prompts]
    scheduler.run()
    for prompt, handle in zip(prompts, handles):
        want = generate(model, {"params": params}, jnp.asarray(prompt)[None],
                        max_new_tokens=20)[0]
        np.testing.assert_array_equal(np.asarray(handle.output), want)
    engine._pool.check()
    stats = engine.pool_stats()
    # a shared 24-token prefix, six blocks of it, and no hit: the window
    # layers' rows of its last tokens are a ring's to overwrite
    assert stats["prefix_hit_rate"] == 0 and stats["cow_forks"] == 0
    assert stats["cached"] == 0 and not engine._pool.prefix_cache
    assert engine.compile_cache.stats()["recompiles"] == 0
    # the check's window half: a ring too small for a step is refused
    engine._pool.step_rows = 64
    with pytest.raises(AssertionError, match="ring of 4 blocks"):
        engine._pool.check()


def test_speculative_verify_and_preemption_work_on_both_kinds():
    from flashy_tpu.serve import NGramDraft
    config, cfg, model, params = _toy()
    engine = _engine(model, params, spec_k=3)
    assert engine.ring == ring_blocks(8, 8, 4)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4,
                                            draft=NGramDraft(3, k=3, ngram=2))
    prompt = np.tile(np.asarray([5, 9, 11], np.int32), 9)
    handle = scheduler.submit(prompt, 30)
    scheduler.run()
    want = generate(model, {"params": params}, jnp.asarray(prompt)[None],
                    max_new_tokens=30)[0]
    np.testing.assert_array_equal(np.asarray(handle.output), want)
    # a preempted slot's request starts over from position 0 (no prefix
    # is matched) in whatever slot it gets, over a ring another request
    # has left full
    slot = engine.acquire_slot()
    start = engine.admit(slot, prompt, 8)
    assert start == 0
    start, _ = engine.prefill_chunk(slot, prompt, start)
    engine.preempt_slot(slot)
    handle = scheduler.submit(prompt[:20], 12)
    scheduler.run()
    want = generate(model, {"params": params}, jnp.asarray(prompt[:20])[None],
                    max_new_tokens=12)[0]
    np.testing.assert_array_equal(np.asarray(handle.output), want)
    engine._pool.check()
    assert engine.pool_stats()["preemptions"] == 1


def test_refusals_name_their_reason():
    config, cfg, model, params = _toy()
    variables = {"params": params}
    paged = dict(slots=2, max_seq_len=64, cache_layout="paged",
                 block_size=4)
    with pytest.raises(ValueError, match="a head count a layer kind"):
        DecodeEngine(model, variables, kv_dtype="int8", **paged)
    # the walk of the full-attention layers copies whole tiles: the
    # toy's rows (2 KV heads of 24 | 16: 48 | 32 lanes) are refused by
    # their shape, and so is a pool of rings alone
    with pytest.raises(ValueError, match=r"\[4, 2 KV heads of 24 \| 16\] "
                                         r"under 8 query heads"):
        DecodeEngine(model, variables, kernel="fused", **paged)
    from flashy_tpu.ops.paged_decode import (default_kernel,
                                             fused_kernel_unsupported_reason)
    assert default_kernel(cfg, 16) == "gather"
    assert "table gather" in fused_kernel_unsupported_reason(cfg, 16)
    rings = dataclasses.replace(cfg, window_layers=(1,) * cfg.num_layers)
    assert "only window layers" in fused_kernel_unsupported_reason(rings, 16)
    whole = dataclasses.replace(cfg, qk_head_dim=64, v_head_dim=64,
                                rotary_dim=16)
    assert fused_kernel_unsupported_reason(whole, 8) is None
    assert "[4, 2 KV heads of 64 | 64]" in fused_kernel_unsupported_reason(
        whole, 4)
    # the hand-off is a list of block ids: it knows no ring
    donor = DecodeEngine(model, variables, **paged)
    with pytest.raises(ValueError, match="a block list does not hand over"):
        DecodeEngine(model, variables, pool=donor.pool,
                     cache_box=donor.cache_box, pool_slot_base=2, **paged)
    for attention in ("flash", "ring", "ring_fused"):
        with pytest.raises(ValueError, match="no window, sink or grouped"):
            TransformerLM(dataclasses.replace(cfg, attention=attention)
                          ).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="no packed-batch path"):
        model.apply(variables, jnp.zeros((1, 4), jnp.int32),
                    segment_ids=jnp.ones((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="not stacked"):
        TransformerLM(dataclasses.replace(cfg, scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="window_layers"):
        gqa.layer_kinds(dataclasses.replace(cfg, window_layers=(0, 1)))
    with pytest.raises(ValueError, match="attn_kind='gqa''s"):
        gqa.layer_kinds(TransformerConfig())
    assert not gqa.has_window(TransformerConfig())
    # the harness refuses what the program cannot express
    with pytest.raises(ValueError, match="a sink in the full-attention"):
        model_mimo.transformer_config(
            dict(config, add_full_attention_sink_bias=True))
    with pytest.raises(ValueError, match="dense layers after"):
        model_mimo.transformer_config(
            dict(config, moe_layer_freq=[0, 1, 0, 1]))


def test_the_dense_layout_serves_it_with_whole_slabs():
    # the dense layout masks a window and bounds nothing: same tokens
    config, cfg, model, params = _toy()
    engine = DecodeEngine(model, {"params": params}, slots=2,
                          max_seq_len=64, chunk=8)
    engine.warmup()
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    prompt = np.asarray(_tokens((27,), seed=5))
    handle = scheduler.submit(prompt, 10)
    scheduler.run()
    want = generate(model, {"params": params}, jnp.asarray(prompt)[None],
                    max_new_tokens=10)[0]
    np.testing.assert_array_equal(np.asarray(handle.output), want)
    row = (24 + 16) * 4
    assert engine.state_bytes_per_slot() == 64 * (2 * 2 + 2 * 4) * row


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    # The share test: the layer's result over shares (first, 2) of 8
    # experts, routed parts summed — there is no shared expert to count
    # once — is the uncut reference layer. float32 sums in another order.
    config, cfg, model, params = _toy()
    mp = params["block_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)),
                    jnp.float32)
    want = reference_mimo._expert_layer(mp, x[0], config, jnp.float32)
    whole, (landed, hit) = moe.expert_layer(cfg, mp, x)
    np.testing.assert_allclose(whole[0], want, atol=F32_TOL)
    assert int(landed) == 40 * 2 and 1 <= int(hit) <= 8
    total, assignments = 0.0, 0
    for first in (0, 2, 4, 6):
        share = dict(mp, w_up=mp["w_up"][first:first + 2],
                     w_down=mp["w_down"][first:first + 2])
        part, (landed, _) = moe.expert_layer(
            dataclasses.replace(cfg, held_experts=(first, 2)), share, x)
        # a share alone is what the reference gives when handed it
        alone = reference_mimo._expert_layer(
            share, x[0], dict(config, held_experts=[first, 2]), jnp.float32)
        np.testing.assert_allclose(part[0], alone, atol=F32_TOL)
        total, assignments = total + part[0], assignments + int(landed)
    assert assignments == 40 * 2  # every assignment lands on one share
    np.testing.assert_allclose(total, want, atol=F32_TOL)


def test_router_with_one_group_and_no_shared_expert_breaks_ties_alike():
    # 8 experts in ONE group (no group limit), top 2, gates times 1.0
    # (routed_scaling_factor null). Scores built to tie: ties go to the
    # lower index in both implementations; the bias (on expert 5) moves
    # the CHOICE, never the gate.
    logits = np.full((4, 8), -4.0, np.float32)
    logits[0, [2, 3, 6]] = 1.5                       # three tie for two
    logits[1] = 0.0                 # all tie, but for the bias on 5
    logits[2] = np.linspace(-1, 1, 8)
    logits[3, [1, 4, 5]] = [1.0, 1.0, 0.9]           # the bias lifts 5
    bias = np.zeros(8, np.float32)
    bias[5] = 0.2
    cfg = {"n_group": 1, "topk_group": 1, "num_experts_per_tok": 2,
           "routed_scaling_factor": 1.0, "norm_topk_prob": True}
    want = np.asarray(reference_dots.route(jnp.asarray(logits),
                                           jnp.asarray(bias), cfg))
    ids, gates = moe.sigmoid_group_route(
        jnp.asarray(logits), jnp.asarray(bias), top_k=2, n_group=1,
        topk_group=1, scale=1.0)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(ids), np.asarray(gates), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6)  # one division each
    np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-6)
    assert sorted(np.nonzero(got[0])[0]) == [2, 3]
    assert sorted(np.nonzero(got[1])[0]) == [0, 5]
    # row 2: sigmoid(0.43) + 0.2 lifts expert 5 over expert 6's 0.67
    assert sorted(np.nonzero(got[2])[0]) == [5, 7]
    # row 3: expert 5's choice score 0.711 + 0.2 leads, expert 1 wins
    # the tie with 4; the gate of 5 is sigmoid(0.9)'s share
    assert sorted(np.nonzero(got[3])[0]) == [1, 5]
    sigmoid = lambda v: 1.0 / (1.0 + np.exp(-v))
    np.testing.assert_allclose(got[3, 5] / got[3, 1],
                               sigmoid(0.9) / sigmoid(1.0), rtol=1e-6)


def test_spans_carry_the_bytes_by_kind_and_the_expert_counts():
    from flashy_tpu.observability import Tracer
    config, cfg, model, params = _toy(held=(4, 4))
    tracer = Tracer()
    engine = _engine(model, params, tracer=tracer)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    scheduler.submit(np.arange(20, dtype=np.int32), 3)
    scheduler.run()
    by_name = {}
    for event in tracer.events:
        if event.get("ph") == "X":
            by_name.setdefault(event["name"], []).append(event["args"])
    full, window = token_bytes(cfg)
    decode, chunk = by_name["serve/decode"], by_name["serve/prefill_chunk"]
    # a decode step at context 21: a full layer attends 21 rows, a
    # window layer min(21, 8)
    assert decode[0]["kv_bytes_window"] == 8 * window
    assert decode[0]["kv_bytes"] == 21 * full + 8 * window
    # slices of 8 at 0 and 8 and a tail of 4 at 16: the window layers
    # see min(context, 7 + rows)
    assert [s["kv_bytes_window"] // window for s in chunk] == [8, 15, 11]
    assert [(s["kv_bytes"] - s["kv_bytes_window"]) // full
            for s in chunk] == [8, 16, 20]
    assert all("kv_blocks" not in s for s in decode + chunk)  # the gather
    counts = by_name["serve/decode/moe"]
    assert len(counts) == len(decode)
    # three slots' rows (parked ones route too), three expert layers, top 2
    assert all(0 <= c["moe_experts_hit"] <= c["moe_assignments"] <= 3 * 3 * 2
               for c in counts)
    assert len(by_name["serve/prefill_chunk/moe"]) == 1


def test_the_device_scopes_name_both_reads():
    # the lowered decode step carries attn/window and attn/global, no
    # shared_expert, and the scopes the accepted readers go by
    from flashy_tpu.serve.paged import paged_apply_step
    config, cfg, model, params = _toy()
    pool = init_pool(cfg, 9, 4, "model", slots=2, ring=4)
    table = jnp.zeros((2, 8), jnp.int32)
    step = jax.jit(lambda p, c: paged_apply_step(
        model, {"params": p}, cfg, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2, 1), jnp.int32), c, table,
        slots=jnp.arange(2, dtype=jnp.int32), stats=[]))
    text = step.lower(params, pool).as_text(debug_info=True)
    for scope in ("qkv", "rotary", "kv_write", "attn/window", "attn/global",
                  "out_proj", "mlp/router", "mlp/experts", "head"):
        assert f"/{scope}/" in text, scope
    assert "shared_expert" not in text


def test_bfloat16_leaves_come_from_the_one_jitted_init():
    config, _, _, _ = _toy()
    cfg = model_mimo.transformer_config(dict(config, torch_dtype="bfloat16"),
                                        attention="dense", dtype=jnp.bfloat16)
    model = TransformerLM(cfg)
    params = model_mimo.seeded_params(model, 5)
    dtypes = {"/".join(str(getattr(k, "key", k)) for k in path): leaf.dtype
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  params)[0]}
    float32 = {name for name, dt in dtypes.items() if dt == jnp.float32}
    # norm scales, the router's bias and the sinks stay float32
    assert all(name.endswith(("scale", "router_bias", "sink"))
               for name in float32), float32
    assert any(name.endswith("sink") for name in float32)
    assert dtypes["block_1/attn/in_proj/kernel"] == jnp.bfloat16
    tokens = _tokens((1, 24))
    want = reference_mimo.logits(params, tokens, dict(
        config, torch_dtype="bfloat16"))
    got = model.apply({"params": params}, tokens)
    # bf16 operands under float32 accumulation against the float32
    # reference: the median position's rms error is rounding, a few
    # hundredths of the logits' spread (a router near-tie moves a few)
    rms = jnp.sqrt(jnp.mean((got - want) ** 2, -1)) / jnp.std(want, -1)
    assert float(jnp.median(rms)) < 0.05
    # a decode step's read (few query rows: K and V as stored against
    # block-diagonal queries) in bfloat16 too, on whatever backend runs
    out = generate(model, {"params": params}, tokens[:, :16],
                   max_new_tokens=3)
    assert out.shape == (1, 19) and int(out.max()) < 64
