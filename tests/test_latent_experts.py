# Latent attention, the latent paged pool, the sigmoid group-limited
# expert layer with a held range, bf16 leaves — at toy widths on the
# CPU, against the plain reference the benchmark brings
# (benchmarks/harness/reference_dots.py, written from the equations).
# Every tolerance states its reason.
"""The second model family through model, decode step, pool and engine."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import model_dots, reference_dots
from flashy_tpu.models import TransformerConfig, TransformerLM, mla, moe
from flashy_tpu.models.decoding import generate
from flashy_tpu.ops.paged_attention import (block_bytes, init_pool,
                                            pool_bytes)
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine

# float32 toy runs differ from the float32 reference only by the order
# of sums (absorbed projections, sorted experts): a few ulps of logits
# whose spread is ~0.1
F32_TOL = 2e-5

TOY = {
    "num_attention_heads": 4, "num_key_value_heads": 4, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "moe_layer_freq": 1, "held_experts": [0, 16],
    "n_routed_experts": 16, "n_routed_experts_published": 16,
    "vocab_size": 64, "hidden_size": 32, "num_hidden_layers": 3,
    "max_position_embeddings": 64, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 10000, "first_k_dense_replace": 1, "intermediate_size": 48,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "n_shared_experts": 1,
    "moe_intermediate_size": 16, "torch_dtype": "float32"}


def _toy(held=(0, 16), **changes):
    config = dict(TOY, held_experts=list(held), n_routed_experts=held[1],
                  **changes)
    cfg = model_dots.transformer_config(config, attention="dense",
                                        dtype=jnp.float32)
    model = TransformerLM(cfg)
    return config, cfg, model, model_dots.seeded_params(model, 3)


def _tokens(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 64, shape),
                       jnp.int32)


def test_yarn_frequencies_match_a_hand_computed_table():
    # dim 8, base 10000, factor 4 over 16 original positions, beta 2 / 1:
    # correction dims 8 ln(16 / (2 pi b)) / (2 ln 10000) = 0.105 (b = 2)
    # and 0.406 (b = 1) -> low 0, high 1: dimension 0 keeps its
    # frequency 1, dimensions 1.. are interpolated, 10000^(-i/4) / 4
    got = mla.yarn_inv_freq(8, 10000.0, 4.0, 16, 2.0, 1.0)
    want = [1.0, 0.1 / 4, 0.01 / 4, 0.001 / 4]
    np.testing.assert_allclose(got, want, rtol=1e-6)  # float32 rounding
    np.testing.assert_allclose(
        reference_dots.yarn_frequencies(8, 10000.0, {
            "factor": 4, "original_max_position_embeddings": 16,
            "beta_fast": 2, "beta_slow": 1}), want, rtol=1e-6)
    assert mla.yarn_mscale(40.0, 1.0) == pytest.approx(1.3688879454)
    # a default config states the table `_rotary` computes itself
    assert mla.plain_rotary(TransformerConfig())
    assert not mla.plain_rotary(TransformerConfig(rope_theta=5e5))


def test_router_matches_the_reference_on_ties():
    # 16 experts in 4 groups of 4, keep 2 groups, top 4. Scores built
    # so that groups tie on their two-best sum and, inside the kept
    # groups, choice scores tie: ties go to the lower index in both
    # implementations. The bias (on expert 9) moves the CHOICE and the
    # group's score, never the gate.
    logits = np.full((4, 16), -4.0, np.float32)
    logits[0, [4, 5, 8, 10]] = [2.0, 1.0, 2.0, 1.0]   # group 1 ties 2
    logits[0, [12, 13]] = 2.5                         # group 3 leads
    logits[1, [0, 1, 2, 3]] = 1.5                             # all tie
    logits[1, [12, 13]] = [1.5, 1.5]
    logits[2] = np.linspace(-1, 1, 16)
    logits[3, [4, 5, 8, 9]] = [1.0, 1.0, 1.0, 0.9]   # the bias opens 2
    bias = np.zeros(16, np.float32)
    bias[9] = 0.2
    cfg = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True}
    want = np.asarray(reference_dots.route(jnp.asarray(logits),
                                           jnp.asarray(bias), cfg))
    ids, gates = moe.sigmoid_group_route(
        jnp.asarray(logits), jnp.asarray(bias), top_k=4, n_group=4,
        topk_group=2, scale=2.5)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(ids), np.asarray(gates), axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6)  # one division each
    assert (got > 0).sum(-1).tolist() == [4, 4, 4, 4]
    np.testing.assert_allclose(got.sum(-1), 2.5, rtol=1e-6)  # norm, scale
    # row 0: group 3 and group 1, the lower of the tied pair, stay
    assert sorted(np.nonzero(got[0])[0]) == [4, 5, 12, 13]
    assert sorted(np.nonzero(got[1])[0]) == [0, 1, 2, 3]
    # row 3: without the bias group 1 (1.0 + 1.0) beats group 2
    # (1.0 + 0.9); with it group 2 leads and expert 9 is the best
    # choice, yet its gate is sigmoid(0.9)'s share, not sigmoid(0.9)+0.2
    assert sorted(np.nonzero(got[3])[0]) == [4, 5, 8, 9]
    sigmoid = lambda v: 1.0 / (1.0 + np.exp(-v))
    np.testing.assert_allclose(got[3, 9] / got[3, 8],
                               sigmoid(0.9) / sigmoid(1.0), rtol=1e-6)


def test_latent_attention_plain_and_cached_forms_match_the_reference():
    config, cfg, model, params = _toy()
    tokens = _tokens((2, 24))
    want = reference_dots.logits(params, tokens, config)
    plain = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(plain, want, atol=F32_TOL)
    # cached form: prefill 16 then 8 single tokens through the dense
    # latent slabs (`generate`'s path), logits at every position
    from flashy_tpu.models.decoding import _apply_step, init_cache
    cache = init_cache(cfg, 2, 24)
    assert cache["block_0"]["c"].shape == (2, 24, 1, 8)
    positions = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    got, cache = _apply_step(model, {"params": params}, cfg, tokens[:, :16],
                             positions, cache, jnp.int32(0))
    np.testing.assert_allclose(got, want[:, :16], atol=F32_TOL)
    for t in range(16, 24):
        got, cache = _apply_step(
            model, {"params": params}, cfg, tokens[:, t:t + 1],
            jnp.full((2, 1), t, jnp.int32), cache, jnp.int32(t))
        np.testing.assert_allclose(got[:, 0], want[:, t], atol=F32_TOL)


def test_query_tiles_of_the_cached_form_change_nothing(monkeypatch):
    config, cfg, model, params = _toy()
    ap = params["block_0"]["attn"]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 12, 32)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    q_nope, q_rope = mla.queries(cfg, ap, x, positions)
    c_kv, k_rope = mla.latents(cfg, ap, x, positions)
    q_lat = mla.absorb_queries(cfg, ap, q_nope)
    whole = mla.cached_attention(cfg, q_lat, q_rope, c_kv, k_rope, positions)
    # a limit of 2 queries' worth of scores: tiles of 2 along T
    monkeypatch.setattr(mla, "SCORE_BLOCK_BYTES", 2 * 2 * 4 * 12 * 4)
    tiled = mla.cached_attention(cfg, q_lat, q_rope, c_kv, k_rope, positions)
    # the same rows and sums in products of another shape: float32 ulps
    np.testing.assert_allclose(whole, tiled, atol=2e-6)


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    # The share test: the layer's result over shares (first, 4) of 16
    # experts, routed parts summed and the shared expert counted once,
    # is the uncut reference layer. Sums of float32 in another order.
    config, cfg, model, params = _toy()
    mp = params["block_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 32)),
                    jnp.float32)
    want = reference_dots._expert_layer(mp, x[0], config, jnp.float32)
    whole, (landed, hit) = moe.expert_layer(cfg, mp, x)
    np.testing.assert_allclose(whole[0], want, atol=F32_TOL)
    assert int(landed) == 40 * 4 and 1 <= int(hit) <= 16
    shared_once = reference_dots._gated_mlp(mp["shared"], x[0], jnp.float32)
    routed = {k: v for k, v in mp.items() if k != "shared"}
    total, assignments = 0.0, 0
    for first in (0, 4, 8, 12):
        share = dict(routed, w_up=mp["w_up"][first:first + 4],
                     w_down=mp["w_down"][first:first + 4])
        part, (landed, _) = moe.expert_layer(
            dataclasses.replace(cfg, held_experts=(first, 4)), share, x)
        # a share alone, with the shared expert every chip computes, is
        # what the reference gives when handed that share
        alone = reference_dots._expert_layer(
            dict(share, shared=mp["shared"]), x[0],
            dict(config, held_experts=[first, 4]), jnp.float32)
        np.testing.assert_allclose(part[0] + shared_once, alone,
                                   atol=F32_TOL)
        total, assignments = total + part[0], assignments + int(landed)
    assert assignments == 40 * 4  # every assignment lands on one share
    np.testing.assert_allclose(total + shared_once, want, atol=F32_TOL)


@pytest.mark.parametrize("tokens,quantized", [
    (6, False), (80, False), (6, True), (80, True)])
def test_expert_layer_softmax_kind_matches_moemlp(tokens, quantized):
    # `_moe_forward`'s cases on the one expert layer: MoEMLP's tree
    # (softmax router, gelu experts, all held) at a decode-sized and a
    # prefill-sized token count (its old gather and scan orders), dense
    # and int8 leaves, against MoEMLP's einsum dispatch with room for
    # every assignment. float32; int8 leaves differ by their rounding
    # on both sides alike, so the tolerance stays float32's.
    from flashy_tpu.models.quantize import _quantize, dequantize
    cfg = TransformerConfig(dim=16, moe_experts=4, moe_top_k=2,
                            dtype=jnp.float32)
    layer = moe.MoEMLP(dim=16, hidden=32, num_experts=4, top_k=2,
                       capacity_factor=8.0, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(tokens).normal(
        size=(1, tokens, 16)), jnp.float32)
    mp = layer.init(jax.random.PRNGKey(1), x)["params"]
    dense = dict(mp)
    if quantized:
        mp = dict(mp, w_up=_quantize(mp["w_up"], (1,)),
                  w_down=_quantize(mp["w_down"], (1,)))
        dense = dict(mp, w_up=dequantize(mp["w_up"]),
                     w_down=dequantize(mp["w_down"]))
    want = layer.apply({"params": dense}, x)
    got, (landed, hit) = moe.expert_layer(cfg, mp, x)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert int(landed) == 2 * tokens and int(hit) <= 4


def _engine(model, params, **kwargs):
    engine = DecodeEngine(model, {"params": params}, slots=3, max_seq_len=64,
                          cache_layout="paged", block_size=4, chunk=8,
                          **kwargs)
    engine.warmup()
    return engine


def test_latent_pool_spec_bytes_and_kernel_choice():
    config, cfg, model, params = _toy()
    pool = init_pool(cfg, 5, 4, "model")
    entry = pool["block_0"]
    # the rotated key's 4 values sit in whole 128-lane rows, as stored
    assert entry["c"].shape == (5, 4, 8) and entry["kr"].shape == (5, 4, 128)
    per_token = (8 + 128) * 4  # float32 toy
    assert block_bytes(cfg, 4, "model") == 3 * 4 * per_token
    assert pool_bytes(cfg, 5, 4, "model") == 5 * block_bytes(cfg, 4, "model")
    engine = _engine(model, params)
    assert engine.kernel == "gather"  # auto, on the CPU
    assert engine.cache_bytes() == pool_bytes(cfg, engine.num_blocks, 4,
                                              "model")
    assert engine.state_bytes_per_slot() == 16 * block_bytes(cfg, 4, "model")


def test_refusals_name_their_reason():
    config, cfg, model, params = _toy()
    with pytest.raises(ValueError, match="no heads in its rows"):
        DecodeEngine(model, {"params": params}, slots=2, max_seq_len=64,
                     cache_layout="paged", kv_dtype="int8")
    # the fused walk copies whole (sublanes, 128) tiles of a latent
    # pool's blocks: the toy's 8-wide latent is refused by its shape...
    with pytest.raises(ValueError, match=r"whole \(8, 128\) tiles.*"
                                         r"\[8, 8\]"):
        DecodeEngine(model, {"params": params}, slots=2, max_seq_len=64,
                     cache_layout="paged", block_size=8, kernel="fused")
    from flashy_tpu.ops.paged_decode import fused_kernel_unsupported_reason
    assert "kv_lora_rank" in fused_kernel_unsupported_reason(cfg)
    # ... and so is a block that is not whole sublanes of the dtype,
    # while whole-lane ranks in whole-sublane blocks are accepted
    wide = dataclasses.replace(cfg, kv_lora_rank=128)
    assert fused_kernel_unsupported_reason(wide) is None
    assert fused_kernel_unsupported_reason(wide, 8) is None
    assert "[4, 128]" in fused_kernel_unsupported_reason(wide, 4)
    half = dataclasses.replace(wide, dtype=jnp.bfloat16)
    assert "(16, 128)" in fused_kernel_unsupported_reason(half, 8)
    assert fused_kernel_unsupported_reason(half, 16) is None
    assert fused_kernel_unsupported_reason(TransformerConfig()) is None
    with pytest.raises(ValueError, match="not stacked"):
        TransformerLM(dataclasses.replace(cfg, scan_layers=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    from flashy_tpu.models.pipelined import _chunked_stage
    stacked = TransformerConfig(scan_layers=True, tie_head=False)
    with pytest.raises(ValueError, match="not pipelined"):
        _chunked_stage(TransformerLM(stacked), {"params": {}}, 1)
    with pytest.raises(ValueError, match="state one"):
        TransformerLM(dataclasses.replace(cfg, moe_experts=4)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="must lie inside"):
        TransformerLM(dataclasses.replace(cfg, held_experts=(12, 8))).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_slices_then_decoding_through_the_latent_pool_match_the_reference():
    # The engine's own path: a 21-token prompt in slices of 8 (and a
    # tail), then 10 decode steps through the latent paged pool; the
    # logits the steps produce against the reference's full forward
    # over the final sequence. Share (4, 8) of 16 experts: the chip's
    # cut is in both. float32 sums in another order.
    from flashy_tpu.serve.paged import paged_apply_step
    config, cfg, model, params = _toy(held=(4, 8))
    pool = init_pool(cfg, 9, 4, "model")
    table = jnp.asarray([[3, 1, 4, 2, 8, 5, 7, 6]], jnp.int32)
    sequence = _tokens((1, 31), seed=4)
    got = []
    for start, size in ((0, 8), (8, 8), (16, 5)) + tuple(
            (t, 1) for t in range(21, 31)):
        positions = (start + jnp.arange(size, dtype=jnp.int32))[None]
        stats = []
        logits, pool = paged_apply_step(
            model, {"params": params}, cfg, sequence[:, start:start + size],
            positions, pool, table, stats=stats)
        got.append(logits[0])
        assert len(stats) == 2  # one pair an expert layer
        assert all(0 <= int(n) <= size * 4 for n, _ in stats)
    want = reference_dots.logits(params, sequence, config)[0]
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=F32_TOL)


def test_engine_serves_token_exact_with_prefix_hits_and_copy_on_write():
    config, cfg, model, params = _toy(held=(4, 8))
    engine = _engine(model, params, spec_k=2)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=8)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 64, 10).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 64, n).astype(
        np.int32)]) for n in (5, 9, 13, 3)]
    handles = [scheduler.submit(p, 6) for p in prompts]
    scheduler.run()
    for prompt, handle in zip(prompts, handles):
        want = generate(model, {"params": params}, jnp.asarray(prompt)[None],
                        max_new_tokens=6)[0]
        # float32 and kv_dtype='model': any mismatch is a paging bug
        np.testing.assert_array_equal(np.asarray(handle.output), want)
    engine._pool.check()
    stats = engine.pool_stats()
    assert stats["prefix_hit_rate"] > 0 and stats["cow_forks"] >= 1
    cache = engine.compile_cache.stats()
    assert cache["recompiles"] == 0


def test_the_tap_hands_out_the_logits_the_engines_steps_sampled_from():
    # keep_logits: the engine's own decode step, all three slots live,
    # and its own prefill slices, against the reference's full forward
    # over each served sequence. float32 sums in another order.
    config, cfg, model, params = _toy(held=(4, 8))
    engine = _engine(model, params, keep_logits=True)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    rng = np.random.default_rng(2)
    handles = [scheduler.submit(rng.integers(0, 64, n).astype(np.int32), 7)
               for n in (21, 9, 14)]
    rows = {handle.uid: [] for handle in handles}
    while not all(handle.done for handle in handles):
        had = [len(handle.generated) for handle in handles]
        scheduler.step()
        for handle, before in zip(handles, had):
            if before == 0 and handle.generated:
                rows[handle.uid].append(
                    np.asarray(engine.tapped["prefill_chunk"])[0])
                before = 1
            if len(handle.generated) > before:
                rows[handle.uid].append(
                    np.asarray(engine.tapped["decode"])[handle.slot])
    for handle in handles:
        got = np.stack(rows[handle.uid])
        assert got.dtype == np.float32 and len(got) == 7
        np.testing.assert_array_equal(got.argmax(-1), handle.generated)
        want = reference_dots.logits(
            params, jnp.asarray(handle.output)[None], config)[0]
        at = handle.prompt.size - 1 + np.arange(7)
        np.testing.assert_allclose(got, want[at], atol=F32_TOL)


def test_the_tap_is_off_by_default_and_paged_only():
    config, cfg, model, params = _toy()
    engine = _engine(model, params)
    slot = engine.acquire_slot()
    prompt = np.arange(5, dtype=np.int32)
    engine.prefill_chunk(slot, prompt, engine.admit(slot, prompt, 2))
    engine.decode()
    assert engine.tapped == {}
    with pytest.raises(ValueError, match="paged executables only"):
        DecodeEngine(model, {"params": params}, slots=2, max_seq_len=64,
                     keep_logits=True)


def test_speculative_verify_and_handoff_work_on_a_latent_pool():
    from flashy_tpu.serve import NGramDraft
    config, cfg, model, params = _toy()
    engine = _engine(model, params, spec_k=2)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4,
                                            draft=NGramDraft(3, k=2, ngram=2))
    prompt = np.tile(np.asarray([5, 9, 11], np.int32), 5)
    handle = scheduler.submit(prompt, 8)
    scheduler.run()
    want = generate(model, {"params": params}, jnp.asarray(prompt)[None],
                    max_new_tokens=8)[0]
    np.testing.assert_array_equal(np.asarray(handle.output), want)
    # the hand-off is a list of block ids: nothing in it knows the pool's
    # leaves
    slot = engine.acquire_slot()
    start = engine.admit(slot, prompt, 4)
    while True:
        start, first = engine.prefill_chunk(slot, prompt, start)
        if first is not None:
            break
    packet = engine.release_for_handoff(slot)
    assert packet["position"] == prompt.size and packet["blocks"]


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_spans_carry_the_expert_counts_and_the_latent_bytes(kernel):
    from flashy_tpu.observability import Tracer
    # a latent 128 wide in blocks of 8: a pool either read can serve
    config, cfg, model, params = _toy(held=(4, 8), kv_lora_rank=128)
    tracer = Tracer()
    engine = DecodeEngine(model, {"params": params}, slots=3, max_seq_len=64,
                          cache_layout="paged", block_size=8, chunk=8,
                          kernel=kernel, tracer=tracer)
    engine.warmup()
    assert engine.kernel == kernel
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    scheduler.submit(np.arange(20, dtype=np.int32), 3)
    scheduler.run()
    events = [e for e in tracer.events if e.get("ph") == "X"]
    by_name = {}
    for event in events:
        by_name.setdefault(event["name"], []).append(event["args"])
    per_token = block_bytes(cfg, 8, "model") // 8
    decode = by_name["serve/decode"]
    chunk = by_name["serve/prefill_chunk"]
    # `kv_bytes` whichever read serves the pool ...
    assert decode[0]["kv_bytes"] == (20 + 1) * per_token
    assert chunk[0]["kv_bytes"] == 8 * per_token
    # ... and the walk's counts exactly when the read is the fused one:
    # 21 tokens are 3 blocks of 8 in one step (two parked slots walk one
    # block each); the first slice's 8 rows are one block
    for spans in (decode, chunk, by_name["serve/prefill_chunk"][1:]):
        assert all(("kv_blocks" in s) == ("kv_steps" in s)
                   == (kernel == "fused") for s in spans)
    if kernel == "fused":
        assert (decode[0]["kv_blocks"], decode[0]["kv_steps"]) == (5, 3)
        assert [s["kv_blocks"] for s in chunk] == [1, 2, 3]
    counts = by_name["serve/decode/moe"]
    assert len(counts) == len(decode)
    # three slots' rows (parked ones route too), two expert layers, top 4
    assert all(0 <= c["moe_experts_hit"] <= c["moe_assignments"] <= 3 * 2 * 4
               for c in counts)
    # only the final slice's token is read back, its counts with it
    assert len(by_name["serve/prefill_chunk/moe"]) == 1


def test_bfloat16_leaves_come_from_the_one_jitted_init():
    config, _, _, _ = _toy()
    cfg = model_dots.transformer_config(
        dict(config, torch_dtype="bfloat16"), attention="dense")
    model = TransformerLM(cfg)
    params = model_dots.seeded_params(model, 1)
    flat = jax.tree_util.tree_leaves_with_path(params)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        small = "norm" in name or "router_bias" in name
        assert leaf.dtype == (jnp.float32 if small else jnp.bfloat16), name
    # no float32 (or wider) tensor of a matrix leaf's shape exists in the
    # init program: the leaves are drawn in bfloat16
    init = jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))
    text = init.lower(jax.random.PRNGKey(0)).as_text()
    for name in ("embed", "head"):
        shape = "x".join(map(str, params[name].shape))
        assert f"tensor<{shape}xbf16>" in text
        assert f"tensor<{shape}xf32>" not in text
    shape = "x".join(map(str, params["block_1"]["moe"]["w_up"].shape))
    assert f"tensor<{shape}xf32>" not in text
    out = model.apply({"params": params}, _tokens((1, 8)))
    assert out.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(out)))


@pytest.fixture(scope="module")
def olmo_toy():
    # the recorder is the one definition of the steps: run on a checkout
    # of the parent it wrote the .npz, run here it gives what to compare
    data = os.path.join(os.path.dirname(__file__), "data")
    spec = importlib.util.spec_from_file_location(
        "record_olmo_toy_parent_logits",
        os.path.join(data, "record_olmo_toy_parent_logits.py"))
    recorder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(recorder)
    recorded = np.load(os.path.join(data, "olmo_toy_parent_logits.npz"))
    return recorded, recorder.steps()


@pytest.mark.parametrize("key", [
    "model/chunk0", "model/chunk1", "model/decode", "model/verify",
    "int8/chunk0", "int8/chunk1", "int8/decode", "int8/verify",
    "hash/chunk", "hash/decode"])
def test_default_config_steps_are_the_parents_bit_for_bit(olmo_toy, key):
    # A config that states none of the new keys runs the program it
    # always ran: logits recorded from commit 73d3e70 on this sandbox's
    # CPU, and (machine-independent) the sha256 of the lowered chunk and
    # decode programs. Equality, no tolerance: nothing may have changed.
    recorded, now = olmo_toy
    np.testing.assert_array_equal(now[key], recorded[key])
