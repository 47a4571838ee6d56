# One mixer a layer (`layer_pattern`): Mamba-2 layers whose state is a
# third kind of per-slot entry beside the block pool, latent experts
# (not gated, relu^2, one shared down- and up-projection), attention
# without rotary — at toy widths that keep the shape (hidden 64, 8 Mamba
# heads of 16 with state 16 in 2 groups, conv 4, 8 query heads over 2 KV
# heads of 16, 8 experts of width 24 in a latent of 32, 3 a token, a
# shared expert of 48, pattern MEM*E), on the CPU, against the plain
# reference the benchmark brings (benchmarks/harness/
# reference_nemotron.py, written from the equations: the state by a loop
# over tokens). Every tolerance states its reason.
"""The fourth model family through model, scan, decode step, pool,
engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import model_nemotron, reference_nemotron
from flashy_tpu.models import TransformerLM, mamba2, moe
from flashy_tpu.models.decoding import _apply_step, generate, init_cache
from flashy_tpu.ops import ssd_scan
from flashy_tpu.ops.paged_attention import (block_bytes, init_pool,
                                            pool_bytes, state_bytes,
                                            window_bytes)
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from flashy_tpu.serve.engine import state_bytes_per_slot

# float32 toy runs differ from the float32 reference only by the order
# of sums (the chunked form against the token loop, sorted experts, the
# grouped heads in one product): a few ulps of logits whose spread is
# ~0.16
F32_TOL = 2e-5

TOY = {
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "hidden_size": 64, "expand": 2, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 128, "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "use_conv_bias": True, "use_bias": False,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "sliding_window": None,
    "mlp_hidden_act": "relu2", "mlp_bias": False,
    "moe_intermediate_size": 24, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "n_routed_experts": 8, "n_routed_experts_published": 8,
    "held_experts": [0, 8], "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
    "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5,
    "residual_in_fp32": False, "tie_word_embeddings": False,
    "vocab_size": 64, "max_position_embeddings": 256,
    "torch_dtype": "float32"}


def _toy(held=(0, 8), **changes):
    config = dict(TOY, held_experts=list(held), n_routed_experts=held[1])
    cfg = model_nemotron.transformer_config(
        config, attention="dense", dtype=jnp.float32, ssd_chunk=8, **changes)
    model = TransformerLM(cfg)
    return config, cfg, model, model_nemotron.seeded_params(model, 3)


def _tokens(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 64, shape),
                       jnp.int32)


def _generate(model, params, prompt, new):
    """`generate()` under one jit a (prompt length, budget)."""
    run = jax.jit(lambda p, t: generate(model, {"params": p}, t,
                                        max_new_tokens=new))
    return np.asarray(run(params, jnp.asarray(prompt)[None])[0])


def _engine(model, params, **kwargs):
    kwargs = {"slots": 3, "max_seq_len": 64, "cache_layout": "paged",
              "block_size": 8, "chunk": 8, **kwargs}
    engine = DecodeEngine(model, {"params": params}, **kwargs)
    engine.warmup()
    return engine


def test_the_config_maps_to_one_mixer_a_layer_and_a_tree_of_its_own():
    config, cfg, model, params = _toy()
    assert cfg.layer_pattern == "MEM*E" and not cfg.rope
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssd_state_dim,
            cfg.ssm_groups, cfg.ssm_conv) == (8, 16, 16, 2, 4)
    assert (cfg.expert_latent, cfg.expert_act, cfg.shared_hidden) == (
        32, "relu2", 48)
    shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
    # a layer is a norm and its one mixer: nothing else
    assert set(shapes["block_0"]) == {"norm1", "ssm"}
    assert set(shapes["block_1"]) == {"norm2", "moe"}
    assert set(shapes["block_3"]) == {"norm1", "attn"}
    assert shapes["block_0"]["ssm"]["in_proj"]["kernel"] == (
        64, 128 + (128 + 2 * 2 * 16) + 8)  # [z | x B C | dt]
    assert shapes["block_0"]["ssm"]["conv"] == {"kernel": (4, 192),
                                                "bias": (192,)}
    moe_shapes = shapes["block_1"]["moe"]
    assert moe_shapes["w_up"] == (8, 32, 24)  # in the latent, not gated
    assert moe_shapes["w_down"] == (8, 24, 32)
    assert moe_shapes["latent_down"]["kernel"] == (64, 32)
    assert moe_shapes["latent_up"]["kernel"] == (32, 64)
    assert moe_shapes["shared"]["up"]["kernel"] == (64, 48)
    assert shapes["block_3"]["attn"]["in_proj"]["kernel"] == (
        64, (8 + 2 * 2) * 16)
    # Mamba-2's published init: A in [1, 16], dt in [1e-3, 1e-1], D ones
    ssm = params["block_0"]["ssm"]
    a = np.exp(np.asarray(ssm["A_log"]))
    dt = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 1e-3 - 1e-6 and dt.max() <= 1e-1 + 1e-6
    np.testing.assert_array_equal(ssm["D"], 1.0)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_whole_forward_matches_the_reference(seed):
    config, cfg, model, params = _toy()
    tokens = _tokens((2, 37), seed)
    mine = model.apply({"params": params}, tokens)
    want = reference_nemotron.logits(params, tokens, config)
    np.testing.assert_allclose(mine, want, atol=F32_TOL)


@pytest.mark.parametrize("fault", [
    {"conv_kernel": 1}, {"fault_no_dt_input": True},
    {"fault_state_reset_every": 16}, {"mlp_hidden_act": "silu"}])
def test_a_dropped_term_is_seen(fault):
    # the controls' planted faults move the reference by far more than
    # the tolerance the program is held to
    config, cfg, model, params = _toy()
    tokens = _tokens((1, 40))
    want = reference_nemotron.logits(params, tokens, config)
    other = reference_nemotron.logits(params, tokens, dict(config, **fault))
    assert float(jnp.abs(other - want).max()) > 100 * F32_TOL


@pytest.mark.parametrize("kernel", ["gather", "fused"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_is_the_recurrence_is_the_token_loop(kernel, chunk):
    # at G < H: B and C by group, never broadcast to the heads; a length
    # that is no multiple of the chunk; a carried-in state
    rng = np.random.default_rng(0)
    batch, seq, heads, groups, dim, state = 2, 27, 8, 2, 4, 16
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    c, b = draw(batch, seq, groups, state), draw(batch, seq, groups, state)
    v, s0 = draw(batch, seq, heads, dim), draw(batch, heads, dim, state)
    log_a = -jax.nn.softplus(draw(batch, seq, heads))
    y_rec, s_rec = ssd_scan.ssd_recurrent_scan(c, b, v, log_a, s0)
    y, s = ssd_scan.ssd_chunked_scan(c, b, v, log_a, state=s0, chunk=chunk,
                                     kernel=kernel)
    # float32 sums in another order, values of a few units
    np.testing.assert_allclose(y, y_rec, atol=2e-5)
    np.testing.assert_allclose(s, s_rec, atol=2e-5)
    # the plain loop, written out: head h reads group h // 4
    h, want = np.asarray(s0, np.float64), []
    cn, bn = np.repeat(c, 4, axis=2), np.repeat(b, 4, axis=2)
    for t in range(seq):
        h = (np.exp(log_a[:, t])[..., None, None] * h
             + np.asarray(v[:, t])[..., None] * bn[:, t][..., None, :])
        want.append(np.einsum("bhpn,bhn->bhp", h, cn[:, t]))
    np.testing.assert_allclose(y, np.stack(want, axis=1), atol=2e-5)
    np.testing.assert_allclose(s, h, atol=2e-5)


@pytest.mark.parametrize("heads_per_step,heads,groups,dim,state", [
    pytest.param(2, 8, 2, 4, 16, id="2"), pytest.param(4, 8, 2, 4, 16, id="4"),
    pytest.param(8, 8, 2, 4, 16, id="8"),
    # the cell's own layout: 128 heads in 8 groups of 16, [64, 128] a
    # head, 32 heads a step spanning two groups
    pytest.param(32, 128, 8, 64, 128, id="cell")])
def test_the_state_update_kernel_advances_entries_in_place(
        heads_per_step, heads, groups, dim, state):
    rng = np.random.default_rng(1)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = draw(6, heads, dim, state)
    rows = jnp.asarray([4, 0, 2, 0], jnp.int32)  # two parked at entry 0
    decay = jax.nn.sigmoid(draw(4, heads))
    # c scaled so that y spreads as in the toy cases at any width
    v, b, c = draw(4, heads, dim), draw(4, groups, state), draw(
        4, groups, state) * np.sqrt(16 / state)
    want_y, want = ssd_scan.ssd_state_update(table, rows, decay, v, b, c,
                                             kernel="gather")
    y, out = ssd_scan.ssd_state_update(
        table, rows, decay, v, b, c, kernel="fused",
        heads_per_step=heads_per_step)
    live = np.asarray(rows) != 0
    np.testing.assert_allclose(y[live], want_y[live], atol=1e-5)
    np.testing.assert_allclose(out[1:], want[1:], atol=1e-5)
    # entries no row names are untouched, bit for bit
    for entry in (1, 3, 5):
        np.testing.assert_array_equal(out[entry], table[entry])
    # and it is the recurrence's one step
    y_rec, s_rec = ssd_scan.ssd_recurrent_scan(
        c[:, None], b[:, None], v[:, None], jnp.log(decay)[:, None],
        table[rows])
    np.testing.assert_allclose(y[live], y_rec[:, 0][live], atol=1e-5)
    np.testing.assert_allclose(out[4], s_rec[0], atol=1e-5)


def test_slices_of_uneven_length_then_decoding_match_the_whole_forward():
    # the dense decode step: the state and the conv tail go in and come
    # out of every call, whatever its length
    config, cfg, model, params = _toy()
    variables = {"params": params}
    tokens = _tokens((2, 31))
    want = reference_nemotron.logits(params, tokens, config)
    cache = init_cache(cfg, 2, 32)
    assert set(cache["block_0"]) == {"state", "conv"}
    assert cache["block_1"] == {} and set(cache["block_3"]) == {"k", "v"}
    positions = jnp.broadcast_to(jnp.arange(31)[None], (2, 31))
    got, at = [], 0
    step = jax.jit(lambda toks, pos, cache, at: _apply_step(
        model, variables, cfg, toks, pos, cache, at))
    for size in (10, 7, 5, 1, 1, 1, 1, 5):
        logits, cache = step(tokens[:, at:at + size],
                             positions[:, at:at + size], cache,
                             jnp.int32(at))
        got.append(logits)
        at += size
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want,
                               atol=F32_TOL)


def test_pads_of_a_slice_stay_out_of_state_and_tail():
    # a right-padded slice (`used` real tokens) ends on the state and
    # the 3 conv rows of its last real token
    config, cfg, model, params = _toy()
    sp = params["block_0"]["ssm"]
    u = jnp.asarray(np.random.default_rng(3).normal(size=(1, 16, 64)),
                    jnp.float32)
    spec = mamba2.state_spec(cfg, 1)
    zeros = [jnp.zeros(*spec[leaf]) for leaf in ("state", "conv")]
    padded = jax.jit(lambda used: mamba2.mixer(cfg, sp, u, *zeros, used=used))
    for used in (1, 2, 5, 11):
        _, state, tail = padded(jnp.asarray([used]))
        _, want_state, want_tail = jax.jit(
            lambda x: mamba2.mixer(cfg, sp, x, *zeros))(u[:, :used])
        # the chunk boundaries fall elsewhere, and the projection runs
        # at another length: sums in another order
        np.testing.assert_allclose(state, want_state, atol=1e-5)
        np.testing.assert_allclose(tail, want_tail, atol=1e-5)


def test_the_engine_serves_token_exact_through_state_entries_and_pool():
    # seven requests over three slots: slices of 8 and tails of 4 or
    # fewer, slots reused (a successor starts from zeros), decode runs
    # while other slots are parked or mid-prefill
    config, cfg, model, params = _toy()
    engine = _engine(model, params)
    assert engine.kernel == "gather"
    scheduler = ContinuousBatchingScheduler(engine, max_queue=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (5, 13, 21, 9, 30)]
    handles = [scheduler.submit(p, 8) for p in prompts]
    scheduler.run()
    for prompt, handle in zip(prompts, handles):
        np.testing.assert_array_equal(
            handle.output, _generate(model, params, prompt, 8))
    engine._pool.check()
    stats = engine.pool_stats()
    assert stats["prefix_hit_rate"] == 0 and stats["cow_forks"] == 0
    assert stats["state_bytes"] == state_bytes(cfg, 3) == 4 * 2 * (
        8 * 16 * 16 * 4 + 3 * 192 * 4)
    assert stats["window_bytes"] == 0
    cache_stats = engine.compile_cache.stats()
    assert cache_stats["misses"] == 4 and cache_stats["recompiles"] == 0


def test_parked_and_prefilling_slots_keep_their_state_across_decode_runs():
    config, cfg, model, params = _toy()
    engine = _engine(model, params, keep_logits=True)
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, 64, n).astype(np.int32)
                     for n in (11, 20))
    # slot 0 goes live, decodes; slot 1 prefills slice by slice between
    # slot 0's decode runs; slot 2 stays parked throughout
    slot0, slot1 = engine.acquire_slot(), engine.acquire_slot()
    start = engine.admit(slot0, first, 12)
    while start < len(first):
        start, token = engine.prefill_chunk(slot0, first, start)
    served = [token]
    state = lambda slot: np.asarray(
        engine._cache["block_0"]["state"][1 + slot])
    parked_before = state(2)
    start = engine.admit(slot1, second, 4)
    token1 = None
    while token1 is None:
        mine = state(slot1)
        served.append(int(engine.decode()[slot0]))
        # the decode run wrote slot 1's entry nowhere: it was parked
        np.testing.assert_array_equal(state(slot1), mine)
        start, token1 = engine.prefill_chunk(slot1, second, start)
    for _ in range(3):
        served.append(int(engine.decode()[slot0]))
    np.testing.assert_array_equal(state(2), parked_before)
    want = _generate(model, params, first, len(served))
    np.testing.assert_array_equal(served, want[len(first):])
    assert token1 == int(_generate(model, params, second, 1)[-1])
    # the tapped logits of the slice are the reference's at that position
    full = reference_nemotron.logits(params, jnp.asarray(second)[None],
                                     config)
    np.testing.assert_allclose(engine.tapped["prefill_chunk"][0],
                               full[0, -1], atol=F32_TOL)


def test_a_retired_slots_successor_starts_from_zeros():
    config, cfg, model, params = _toy()
    engine = _engine(model, params, slots=1)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in (19, 6)]
    handles = [scheduler.submit(p, 5) for p in prompts]
    scheduler.run()
    assert {h.slot for h in handles} == {0}
    for prompt, handle in zip(prompts, handles):
        np.testing.assert_array_equal(
            handle.output, _generate(model, params, prompt, 5))


def test_preemption_prefills_again_from_zeros():
    config, cfg, model, params = _toy()
    engine = _engine(model, params, slots=2)
    prompt = np.random.default_rng(7).integers(0, 64, 14).astype(np.int32)
    slot = engine.acquire_slot()
    start = engine.admit(slot, prompt, 6)
    while start < len(prompt):
        start, _ = engine.prefill_chunk(slot, prompt, start)
    engine.decode()
    engine.preempt_slot(slot)
    engine._pool.check()
    again = engine.acquire_slot()
    start = engine.admit(again, prompt, 6)
    assert start == 0  # no prefix is shared under recurrent layers
    token = None
    while token is None:
        start, token = engine.prefill_chunk(again, prompt, start)
    assert token == int(_generate(model, params, prompt, 1)[-1])


def test_state_bytes_do_not_grow_with_the_context():
    config, cfg, model, params = _toy()
    row = 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4)  # two Mamba layers
    assert state_bytes(cfg, 0) == row
    assert block_bytes(cfg, 8) == 8 * 2 * 2 * 16 * 4  # the one '*' layer
    for length in (64, 128, 256):
        assert state_bytes_per_slot(cfg, length, "paged", block_size=8) == (
            row + length // 8 * block_bytes(cfg, 8))
    assert pool_bytes(cfg, 9, 8, slots=3) == (
        4 * row + 9 * block_bytes(cfg, 8))
    assert window_bytes(cfg, 8, slots=3, ring=0) == 0
    pool = init_pool(cfg, 9, 8, "model", slots=3)
    assert pool["block_0"]["state"].shape == (4, 8, 16, 16)
    assert pool["block_0"]["state"].dtype == jnp.float32
    assert pool["block_0"]["conv"].shape == (4, 3, 192)
    assert pool["block_1"] == {} and pool["block_3"]["k"].shape == (9, 8, 32)


def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    # The share test: over shares (first, 2) of 8 experts, each share
    # its own experts' part of the sum and then W_up, with shared(u)
    # counted ONCE, the parts add up to the uncut reference layer.
    config, cfg, model, params = _toy()
    mp = params["block_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)),
                    jnp.float32)
    want = reference_nemotron._experts(mp, x[0], config, jnp.float32)
    whole, (landed, hit) = moe.expert_layer(cfg, mp, x)
    np.testing.assert_allclose(whole[0], want, atol=F32_TOL)
    assert int(landed) == 40 * 3 and 1 <= int(hit) <= 8
    shared = moe.relu2_mlp(mp["shared"], x, jnp.float32)[0]
    total, assignments = shared, 0
    for first in (0, 2, 4, 6):
        share = dict(mp, w_up=mp["w_up"][first:first + 2],
                     w_down=mp["w_down"][first:first + 2])
        part, (landed, _) = moe.expert_layer(
            dataclasses.replace(cfg, held_experts=(first, 2)), share, x)
        alone = reference_nemotron._experts(
            share, x[0], dict(config, held_experts=[first, 2]), jnp.float32)
        np.testing.assert_allclose(part[0], alone, atol=F32_TOL)
        total, assignments = total + (part[0] - shared), assignments + int(
            landed)
    assert assignments == 40 * 3  # every assignment lands on one share
    np.testing.assert_allclose(total, want, atol=F32_TOL)


REFUSALS = {
    "flash": (lambda cfg: dataclasses.replace(cfg, attention="flash"),
              "runs attention='dense'"),
    "ring": (lambda cfg: dataclasses.replace(cfg, attention="ring"),
             "runs attention='dense'"),
    "scan_layers": (lambda cfg: dataclasses.replace(cfg, scan_layers=True),
                    "scan_layers stacks one"),
    "mixer": (lambda cfg: dataclasses.replace(cfg, mixer="ssd"),
              "`mixer` cycles blocks"),
    "length": (lambda cfg: dataclasses.replace(cfg, layer_pattern="ME*"),
               "names each of the 5 layers"),
    "kinds": (lambda cfg: dataclasses.replace(cfg, layer_pattern="MEMAE"),
              "names each of the 5 layers"),
    "attention_kind": (lambda cfg: dataclasses.replace(cfg, attn_kind="mha"),
                       "attn_kind='gqa'"),
    "experts": (lambda cfg: dataclasses.replace(cfg, n_routed=0),
                "state n_routed"),
    "groups": (lambda cfg: dataclasses.replace(cfg, ssm_groups=3),
               "ssm_groups dividing the heads"),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_a_config_that_cannot_be_one_mixer_a_layer_is_refused(what):
    config, cfg, model, params = _toy()
    change, sentence = REFUSALS[what]
    bad = TransformerLM(change(cfg))
    with pytest.raises(ValueError, match=sentence):
        bad.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("what, kwargs, sentence", [
    ("dense", {"cache_layout": "dense"}, "cache_layout='paged'"),
    ("spec_k", {"spec_k": 2}, "roll a state back"),
    ("int8", {"kv_dtype": "int8"}, "use kv_dtype='model'"),
    ("pool_slot_base", {"pool_slot_base": 4}, "a state a slot beside"),
])
def test_the_engine_refuses_what_a_recurrent_state_cannot_do(what, kwargs,
                                                             sentence):
    config, cfg, model, params = _toy()
    with pytest.raises(ValueError, match=sentence):
        _engine(model, params, **kwargs)


def test_the_pipeline_and_a_verify_step_refuse_it():
    from flashy_tpu.models.pipelined import pipelined_apply
    from flashy_tpu.serve.paged import paged_apply_step
    config, cfg, model, params = _toy()
    with pytest.raises(ValueError, match="layer_pattern"):
        pipelined_apply(model, {"params": params}, _tokens((2, 8)),
                        mesh=None, num_microbatches=2)
    pool = init_pool(cfg, 9, 8, "model", slots=2)
    with pytest.raises(ValueError, match="one token a row"):
        paged_apply_step(model, {"params": params}, cfg, _tokens((2, 3)),
                         jnp.zeros((2, 3), jnp.int32), pool,
                         jnp.zeros((2, 8), jnp.int32),
                         slots=jnp.arange(2, dtype=jnp.int32))


def test_the_pool_holds_reservations_to_the_state_entries():
    from flashy_tpu.serve.paged import BlockPool
    pool = BlockPool(num_blocks=9, block_size=8, max_seq_len=64,
                     state_slots=2)
    assert not pool.prefix_cache
    prompt = np.arange(20, dtype=np.int32)
    pool.commit(pool.plan(prompt, 4), 1)
    pool.check()
    pool.commit(pool.plan(prompt, 4), 5)  # a key no entry stands behind
    with pytest.raises(AssertionError, match="outside the 2 state entries"):
        pool.check()


def test_spans_carry_the_state_bytes_and_the_expert_counts():
    from flashy_tpu.observability import Tracer
    config, cfg, model, params = _toy(held=(4, 4))
    tracer = Tracer()
    engine = _engine(model, params, tracer=tracer)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4)
    scheduler.submit(np.arange(20, dtype=np.int32), 6)
    scheduler.submit(np.arange(9, dtype=np.int32), 6)
    scheduler.run()
    by_name = {}
    for event in tracer.events:
        if event.get("ph") == "X":
            by_name.setdefault(event["name"], []).append(event["args"])
    row = state_bytes(cfg, 0)
    decode, chunk = by_name["serve/decode"], by_name["serve/prefill_chunk"]
    # a decode step reads and writes the entries of the rows that advance
    assert [d["ssm_state_bytes"] for d in decode] == [
        2 * d["running"] * row for d in decode]
    assert {d["running"] for d in decode} == {1, 2}
    assert all(s["ssm_state_bytes"] == 2 * row for s in chunk)
    # K/V bytes stay the attention layer's: 2 KV heads x 16 x 2 x 4 B
    first = next(d for d in decode if d["running"] == 2)
    assert first["kv_bytes"] % (2 * 2 * 16 * 4) == 0
    counts = by_name["serve/decode/moe"]
    assert len(counts) == len(decode)
    # three slots' rows (parked ones route too), two expert layers, top 3
    assert all(0 <= c["moe_experts_hit"] <= c["moe_assignments"] <= 3 * 2 * 3
               for c in counts)


@pytest.mark.parametrize("step", ["decode", "slice"])
def test_the_device_scopes_name_the_mixers_parts(step):
    from flashy_tpu.serve.paged import paged_apply_step
    from tests.test_spans import pallas_calls
    config, cfg, model, params = _toy(ssd_kernel="fused")
    pool = init_pool(cfg, 9, 8, "model", slots=2)
    table = jnp.zeros((2, 8), jnp.int32)
    length = {"decode": 1, "slice": 8}[step]
    carried = ({} if step == "decode" else
               {"used": jnp.asarray([8, 3]), "fresh": jnp.asarray([True,
                                                                   False])})

    def run(p, c):
        return paged_apply_step(
            model, {"params": p}, cfg, jnp.zeros((2, length), jnp.int32),
            jnp.zeros((2, length), jnp.int32), c, table,
            slots=jnp.arange(2, dtype=jnp.int32), stats=[], **carried)

    text = jax.jit(run).lower(params, pool).as_text(debug_info=True)
    for scope in ("ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm",
                  "ssm/out_proj", "mlp/router", "mlp/latent_down",
                  "mlp/experts", "mlp/latent_up", "mlp/shared_expert",
                  "qkv", "kv_write", "attn/global", "out_proj", "head"):
        assert f"/{scope}/" in text, scope
    assert "/rotary/" not in text  # no position embedding in this family
    names = {eqn.params["name"] for eqn in pallas_calls(
        jax.make_jaxpr(run)(params, pool).jaxpr)}
    assert names == {"decode": {"ssd_state_update"},
                     "slice": {"ssd_scan_fused"}}[step]


def test_the_scan_falls_to_xla_on_a_piece_that_is_no_whole_tile(monkeypatch):
    # compiled, a chunk of 8 bfloat16 rows is half a tile: such a piece
    # (a tail slice, an init trace) takes XLA's form, a whole one the
    # kernel
    from tests.test_spans import pallas_calls
    sds = jax.ShapeDtypeStruct
    args = (sds((1, 8, 2, 16), jnp.bfloat16), sds((1, 8, 2, 16), jnp.bfloat16),
            sds((1, 8, 4, 8), jnp.float32), sds((1, 8, 4), jnp.float32))
    scan = lambda c, b, v, a: ssd_scan.ssd_chunked_scan(
        c, b, v, a, kernel="fused", interpret=False)
    assert not list(pallas_calls(jax.make_jaxpr(scan)(*args).jaxpr))
    whole = tuple(sds((1, 32) + a.shape[2:], a.dtype) for a in args)
    assert len(list(pallas_calls(jax.make_jaxpr(scan)(*whole).jaxpr))) == 1


def test_gmm_tiles_divide_an_experts_width():
    # 2,688 = 21 x 128 has no power-of-two tile above 128: the rule takes
    # whole 128-lane columns; the widths of the earlier families keep
    # the tiles they had
    assert moe._tile(2688, 1024) == 896
    assert [moe._tile(size, 1024) for size in (1024, 2048, 4096, 7168)] == [
        1024] * 4
    assert moe._tile(96, 1024) == 96
