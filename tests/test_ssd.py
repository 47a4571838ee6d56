# The SSD mixer subsystem: the state-space duality itself (chunked
# training form == recurrent decode form), exact chunk chaining (the
# engine's token-exactness mechanism), segment severing, the fused
# Pallas kernel against its gather bit-oracle, hybrid stacks, and the
# serving contract — cache_layout='ssd' slots hold ONE fixed
# [H, Dh, Dstate] state whose bytes are independent of max_seq_len, so
# streaming sessions run token-exact past the attention-layout ceiling
# with zero post-warm-up compiles.
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.models import TransformerConfig, TransformerLM
from flashy_tpu.models.decoding import generate
from flashy_tpu.models.transformer import mixer_pattern
from flashy_tpu.ops.ssd_scan import (
    SSD_LOG_RESET, default_chunk, ssd_chunked_scan, ssd_recurrent_scan,
    ssd_state_bytes,
)
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from flashy_tpu.serve.engine import state_bytes_per_slot


def _inputs(batch=2, seq=29, heads=2, head_dim=8, dstate=4, seed=0,
            dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    kc, kb, kv, ka = jax.random.split(key, 4)
    c = jax.random.normal(kc, (batch, seq, heads, dstate), dtype)
    b = jax.random.normal(kb, (batch, seq, heads, dstate), dtype)
    v = jax.random.normal(kv, (batch, seq, heads, head_dim), dtype)
    log_a = -jax.nn.softplus(
        jax.random.normal(ka, (batch, seq, heads), jnp.float32))
    return c, b, v, log_a


# ----------------------------------------------------------------------
# the duality: chunked == recurrent
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [4, 8, 16, 32])
def test_dual_form_parity(chunk):
    # THE subsystem invariant: the MXU-friendly chunked form and the
    # one-token recurrence compute the same outputs and final state
    c, b, v, log_a = _inputs()
    state0 = jnp.zeros((2, 2, 8, 4), jnp.float32)
    y_rec, s_rec = ssd_recurrent_scan(c, b, v, log_a, state0)
    y_chunk, s_chunk = ssd_chunked_scan(c, b, v, log_a, state=state0,
                                        chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_rec),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(s_rec),
                               atol=1e-4, rtol=1e-4)


def test_chunk_chaining_is_bit_exact():
    # splitting a stream at a chunk multiple and passing the state
    # between calls must be BIT-identical to one whole-stream call —
    # this, not an approximation argument, is why the engine's
    # chunk-at-a-time prefill matches generate()'s single call
    c, b, v, log_a = _inputs(seq=32)
    y_whole, s_whole = ssd_chunked_scan(c, b, v, log_a, chunk=8)
    y_a, s_a = ssd_chunked_scan(c[:, :16], b[:, :16], v[:, :16],
                                log_a[:, :16], chunk=8)
    y_b, s_b = ssd_chunked_scan(c[:, 16:], b[:, 16:], v[:, 16:],
                                log_a[:, 16:], state=s_a, chunk=8)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([y_a, y_b], axis=1)),
        np.asarray(y_whole))
    np.testing.assert_array_equal(np.asarray(s_b), np.asarray(s_whole))


def test_token_mask_padding_is_exact():
    # padded tokens zero b AND log_a, so a right-padded call carries
    # exactly the state of the unpadded one (bit-equal) — the prefill
    # bucket / partial tail chunk correctness argument
    c, b, v, log_a = _inputs(seq=16)
    pad = 5
    mask = jnp.arange(16)[None, :] < (16 - pad)
    mask = jnp.broadcast_to(mask, (2, 16))
    _, s_masked = ssd_chunked_scan(c, b, v, log_a, chunk=8,
                                   token_mask=mask)
    _, s_short = ssd_chunked_scan(c[:, :-pad], b[:, :-pad], v[:, :-pad],
                                  log_a[:, :-pad], chunk=8)
    np.testing.assert_array_equal(np.asarray(s_masked),
                                  np.asarray(s_short))


def test_segment_reset_severs_state():
    # a SSD_LOG_RESET sentinel at a segment start must make the second
    # segment's outputs identical to running it alone from zero state
    # (exp underflows to an exact 0 — direct log-sums, no inf - inf)
    c, b, v, log_a = _inputs(seq=12)
    log_a = log_a.at[:, 6].set(SSD_LOG_RESET)
    y, _ = ssd_chunked_scan(c, b, v, log_a, chunk=4)
    y_alone, _ = ssd_chunked_scan(c[:, 6:], b[:, 6:], v[:, 6:],
                                  jnp.where(
                                      jnp.arange(6)[None, :, None] == 0,
                                      SSD_LOG_RESET, log_a[:, 6:]),
                                  chunk=4)
    np.testing.assert_allclose(np.asarray(y[:, 6:]), np.asarray(y_alone),
                               atol=1e-5)
    assert np.isfinite(np.asarray(y)).all()


# The Pallas chunked kernel in interpret mode against the XLA gather
# reference (the ops/attention.py oracle convention), stated as the two
# things XLA:CPU can still prove: jax 0.9.0 does not round dots of
# different shapes alike, and the reference batches over (batch, head,
# chunk) the products the kernel runs one (batch, head) pair at a time.
# The bound, in float32 ulps at a row's largest magnitude: 1.7 is the
# most seen over six seeds and four chunkings, and a wrong decay or
# carry moves a row by its own size.
SSD_ULPS = 4


@pytest.mark.parametrize("shapes", ["one_pair", "batched"])
def test_fused_kernel_matches_gather(shapes):
    # one_pair: a single (batch, head) pair, so the reference's batched
    # dots ARE the kernel's 2-D dots: outputs and final state bit-equal.
    # batched: 2 x 2 pairs, other dot shapes: within SSD_ULPS.
    pairs = 1 if shapes == "one_pair" else 2
    c, b, v, log_a = _inputs(batch=pairs, heads=pairs, seq=16)
    state0 = jax.random.normal(jax.random.PRNGKey(9), (pairs, pairs, 8, 4),
                               jnp.float32)
    y_ref, s_ref = ssd_chunked_scan(c, b, v, log_a, state=state0,
                                    chunk=8, kernel="gather")
    y_fused, s_fused = ssd_chunked_scan(c, b, v, log_a, state=state0,
                                        chunk=8, kernel="fused",
                                        interpret=True)
    for got, want in ((y_fused, y_ref), (s_fused, s_ref)):
        got, want = np.asarray(got), np.asarray(want)
        if shapes == "one_pair":
            np.testing.assert_array_equal(got, want)
        else:
            bound = SSD_ULPS * np.finfo(np.float32).eps * np.abs(want).max(
                axis=-1, keepdims=True)
            assert (np.abs(got - want) <= bound).all()


def test_default_chunk_and_state_bytes():
    assert default_chunk(256) == 256
    assert default_chunk(48) == 16  # largest candidate dividing 48
    assert default_chunk(7) == 7    # shorter than every candidate
    assert ssd_state_bytes(4, 8, 16) == 4 * 8 * 16 * 4  # f32 always


# ----------------------------------------------------------------------
# model level: patterns, generate(), shardings
# ----------------------------------------------------------------------
def _ssd_model(mixer="ssd", max_seq_len=64, scan_layers=False,
               ssd_chunk=0, seed=0):
    cfg = TransformerConfig(vocab_size=64, dim=32, num_layers=2,
                            num_heads=4, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32,
                            mixer=mixer, ssd_state_dim=8,
                            ssd_chunk=ssd_chunk, scan_layers=scan_layers)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))
    return model, params


def test_mixer_pattern_cycles_and_validates():
    cfg = TransformerConfig(vocab_size=8, dim=8, num_layers=4,
                            num_heads=2, mixer="ssd,attention")
    assert mixer_pattern(cfg) == ("ssd", "attention", "ssd", "attention")
    bad = TransformerConfig(vocab_size=8, dim=8, num_layers=2,
                            num_heads=2, mixer="ssd,mamba")
    with pytest.raises(ValueError, match="mixer"):
        mixer_pattern(bad)


def test_scan_layers_requires_uniform_pattern():
    with pytest.raises(ValueError, match="scan_layers"):
        _ssd_model(mixer="ssd,attention", scan_layers=True)


def test_shardings_cover_ssd_params():
    from flashy_tpu.models import transformer_shardings

    _, params = _ssd_model()
    specs = transformer_shardings(params)
    flat = {"/".join(str(k.key) for k in path): spec
            for path, spec in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))[0]}
    cbv = [s for p, s in flat.items() if "ssd/cbv" in p]
    out = [s for p, s in flat.items() if "ssd/out" in p]
    bias = [s for p, s in flat.items() if "ssd/dt_bias" in p]
    assert cbv and out and bias
    assert all(s == jax.sharding.PartitionSpec("fsdp", "tensor", None)
               for s in cbv)
    assert all(s == jax.sharding.PartitionSpec("tensor", None, "fsdp")
               for s in out)
    assert all(s == jax.sharding.PartitionSpec("tensor",) for s in bias)


@pytest.mark.slow
def test_greedy_generate_ssd_matches_naive():
    model, params = _ssd_model()
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)
    tokens = prompt
    for _ in range(6):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_greedy_generate_hybrid_matches_naive():
    model, params = _ssd_model(mixer="ssd,attention")
    prompt = jnp.asarray(
        np.random.default_rng(6).integers(0, 64, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)
    tokens = prompt
    for _ in range(6):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_greedy_generate_ssd_scan_stacked():
    model, params = _ssd_model(scan_layers=True)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (1, 4)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=4)
    tokens = prompt
    for _ in range(4):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_pure_ssd_generate_streams_past_max_seq_len():
    # a pure-SSD stack has no positional ceiling: generate() past
    # cfg.max_seq_len must run (and stay finite) where an attention
    # stack would raise
    model, params = _ssd_model(max_seq_len=16)
    prompt = jnp.asarray(
        np.random.default_rng(8).integers(0, 64, (1, 6)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=20)  # 26 > 16
    assert out.shape == (1, 26)
    attn_model, attn_params = _ssd_model(mixer="attention",
                                         max_seq_len=16)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(attn_model, attn_params, prompt, max_new_tokens=20)


# ----------------------------------------------------------------------
# engine contract
# ----------------------------------------------------------------------
def test_engine_layout_validation():
    model, params = _ssd_model()
    with pytest.raises(ValueError, match="cache_layout='ssd'"):
        DecodeEngine(model, params, slots=2)  # dense layout, ssd layers
    with pytest.raises(ValueError, match="cache_layout='ssd'"):
        DecodeEngine(model, params, slots=2, cache_layout="paged")
    attn_model, attn_params = _ssd_model(mixer="attention")
    with pytest.raises(ValueError, match="SSD layer"):
        DecodeEngine(attn_model, attn_params, slots=2,
                     cache_layout="ssd")
    with pytest.raises(ValueError, match="speculative"):
        DecodeEngine(model, params, slots=2, cache_layout="ssd",
                     spec_k=2)


def test_state_bytes_per_slot_o1_gate():
    # THE capacity claim, as host arithmetic: ssd state bytes are
    # CONSTANT across max_seq_len while paged-int8 grows linearly, and
    # a fixed HBM budget holds strictly more ssd slots at 64k
    cfg = TransformerConfig(vocab_size=64, dim=32, num_layers=2,
                            num_heads=4, attention="dense",
                            max_seq_len=65536, dtype=jnp.float32,
                            mixer="ssd", ssd_state_dim=8)
    attn = TransformerConfig(vocab_size=64, dim=32, num_layers=2,
                             num_heads=4, attention="dense",
                             max_seq_len=65536, dtype=jnp.float32)
    lens = (1024, 8192, 65536)
    ssd = [state_bytes_per_slot(cfg, n, "ssd") for n in lens]
    paged = [state_bytes_per_slot(attn, n, "paged", kv_dtype="int8",
                                  block_size=16) for n in lens]
    assert len(set(ssd)) == 1  # O(1): no max_seq_len term at all
    assert paged[0] < paged[1] < paged[2]
    assert paged[1] == 8 * paged[0] and paged[2] == 64 * paged[0]
    budget = 16 * paged[-1]
    assert budget // ssd[-1] > 16  # more concurrent slots, same HBM
    # hybrid accounting: the attention layer's dense slab reinstates
    # the max_seq_len term, the ssd layer's contribution stays fixed
    hybrid = TransformerConfig(vocab_size=64, dim=32, num_layers=2,
                               num_heads=4, attention="dense",
                               max_seq_len=65536, dtype=jnp.float32,
                               mixer="ssd,attention", ssd_state_dim=8)
    h = [state_bytes_per_slot(hybrid, n, "ssd") for n in lens]
    kv_slab = [state_bytes_per_slot(attn, n, "dense") // 2 for n in lens]
    assert [a - b for a, b in zip(h, kv_slab)] == [ssd[0] // 2] * 3


@pytest.mark.slow
def test_engine_ssd_streams_token_exact_past_ceiling():
    # the tentpole gate, in-suite: chunked prefill + recurrent decode
    # through a ceiling-64 engine, sessions finishing PAST the ceiling,
    # token-exact vs generate(), zero post-warm-up builds. cfg.ssd_chunk
    # pins the model's chunk to the engine's, so engine chunking is
    # bit-identical to generate()'s whole-prompt call.
    model, params = _ssd_model(max_seq_len=4096, ssd_chunk=8)
    engine = DecodeEngine(model, params, slots=2, max_seq_len=64,
                          chunk=8, cache_layout="ssd")
    assert engine.unbounded
    assert engine.state_bytes_per_slot() == 2 * ssd_state_bytes(4, 8, 8)
    engine.warmup()
    warm_misses = engine.compile_cache.stats()["misses"]

    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(11)
    workload = [(rng.integers(0, 64, 11).astype(np.int32), 70),
                (rng.integers(0, 64, 23).astype(np.int32), 60),
                (rng.integers(0, 64, 7).astype(np.int32), 80)]
    handles = [scheduler.submit(p, m) for p, m in workload]
    scheduler.run()

    stats = engine.compile_cache.stats()
    assert stats["misses"] == warm_misses and stats["recompiles"] == 0
    for handle, (prompt, max_new) in zip(handles, workload):
        assert handle.done
        assert len(prompt) + max_new > engine.max_seq_len  # past it
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(handle.output, want)


@pytest.mark.slow
def test_engine_ssd_retire_and_readmit_resets_state():
    # slot reuse: the chunk executable zeroes the slot's SSD leaves at
    # start == 0, so a re-admitted request must not see the previous
    # occupant's state
    model, params = _ssd_model(max_seq_len=256, ssd_chunk=8)
    engine = DecodeEngine(model, params, slots=1, max_seq_len=64,
                          chunk=8, cache_layout="ssd")
    engine.warmup()
    scheduler = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(12)
    first = scheduler.submit(rng.integers(0, 64, 20).astype(np.int32), 8)
    scheduler.run()
    assert first.done
    prompt = rng.integers(0, 64, 13).astype(np.int32)
    second = scheduler.submit(prompt, 8)
    scheduler.run()
    want = np.asarray(generate(model, params, prompt[None],
                               max_new_tokens=8))[0]
    np.testing.assert_array_equal(second.output, want)


@pytest.mark.slow
def test_engine_hybrid_token_exact_and_bounded():
    # a hybrid stack serves through the same 'ssd' layout (attention
    # layers keep dense slabs in the cache pytree) but is NOT
    # unbounded: one slab reinstates the ceiling at the submit door
    model, params = _ssd_model(mixer="ssd,attention", max_seq_len=64,
                               ssd_chunk=8)
    engine = DecodeEngine(model, params, slots=2, chunk=8,
                          cache_layout="ssd")
    assert not engine.unbounded
    engine.warmup()
    scheduler = ContinuousBatchingScheduler(engine)
    with pytest.raises(ValueError, match="max_seq_len"):
        scheduler.submit(np.arange(8, dtype=np.int32), 80)
    rng = np.random.default_rng(13)
    workload = [(rng.integers(0, 64, 9).astype(np.int32), 10),
                (rng.integers(0, 64, 17).astype(np.int32), 12)]
    handles = [scheduler.submit(p, m) for p, m in workload]
    scheduler.run()
    for handle, (prompt, max_new) in zip(handles, workload):
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(handle.output, want)


@pytest.mark.slow
def test_engine_ssd_speculative_raises():
    model, params = _ssd_model(ssd_chunk=8)
    engine = DecodeEngine(model, params, slots=2, chunk=8,
                          cache_layout="ssd")
    engine.warmup()
    with pytest.raises(ValueError, match="speculative"):
        engine.decode_speculative(np.zeros((2, 2), np.int32))


@pytest.mark.slow
def test_static_info_publishes_state_bytes(tmp_path):
    # satellite contract: the scheduler publishes the per-slot state
    # bytes into static_info, write_status lands it in serve.json, and
    # `python -m flashy_tpu.info` renders it
    from flashy_tpu.info import format_serve_status

    model, params = _ssd_model(ssd_chunk=8)
    engine = DecodeEngine(model, params, slots=2, chunk=8,
                          cache_layout="ssd")
    engine.warmup()
    scheduler = ContinuousBatchingScheduler(engine)
    want = engine.state_bytes_per_slot()
    assert scheduler.metrics.static_info["state_bytes_per_slot"] == want
    scheduler.metrics.write_status(tmp_path)
    status = json.loads((tmp_path / "serve.json").read_text())
    assert status["state_bytes_per_slot"] == want
    assert f"state_bytes_per_slot={want}" in format_serve_status(status)
