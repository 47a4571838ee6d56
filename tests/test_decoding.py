# KV-cache decoding must agree with the training-path forward: greedy
# generation via the cache equals the naive re-run-the-whole-prefix
# argmax loop.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.models import TransformerConfig, TransformerLM
from flashy_tpu.models.decoding import generate


def _model_and_params(attention="dense"):
    cfg = TransformerConfig(vocab_size=64, dim=32, num_layers=2, num_heads=4,
                            attention=attention, max_seq_len=64)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    return model, params


@pytest.mark.slow
def test_greedy_generate_matches_naive():
    model, params = _model_and_params()
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 5)), jnp.int32)

    out = generate(model, params, prompt, max_new_tokens=6)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))

    # naive: rerun full sequence each step, take argmax
    tokens = prompt
    for _ in range(6):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


def test_generate_jittable():
    model, params = _model_and_params()
    prompt = jnp.ones((1, 4), jnp.int32)
    fn = jax.jit(lambda p, t: generate(model, p, t, max_new_tokens=3))
    out = fn(params, prompt)
    assert out.shape == (1, 7)


def test_sampled_generate_valid_tokens():
    model, params = _model_and_params()
    prompt = jnp.ones((2, 4), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=5, temperature=1.0,
                   top_k=10, rng=jax.random.PRNGKey(7))
    arr = np.asarray(out)
    assert arr.shape == (2, 9)
    assert ((arr >= 0) & (arr < 64)).all()
    # different keys -> (almost surely) different samples
    out2 = generate(model, params, prompt, max_new_tokens=5, temperature=1.0,
                    top_k=10, rng=jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(out2), arr)


@pytest.mark.slow
def test_greedy_generate_scan_stacked_matches_naive():
    cfg = TransformerConfig(vocab_size=64, dim=32, num_layers=3, num_heads=4,
                            attention="dense", max_seq_len=64, scan_layers=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 8), jnp.int32))
    # stacked layout: leading [L] dim on block params
    qkv = params["params"]["blocks"]["block"]["attn"]["qkv"]["kernel"]
    assert qkv.shape[0] == 3

    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)

    tokens = prompt
    for _ in range(6):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


def _moe_model(scan_layers=False):
    # capacity_factor high enough that the training dispatch never drops
    # a token, so the (dropless) decode path agrees exactly. f32, not
    # the bf16 default: this random-init model's top-2-gated logits
    # carry near-ties below bf16's ~2^-8 step, and CPU-emulated bf16
    # rounds the [B, T] training forward and the [B, 1] cached step
    # differently at equal math — the argmax comparison needs logits
    # whose margins dominate shape-dependent rounding, which f32's
    # 2^-24 step restores.
    cfg = TransformerConfig(vocab_size=64, dim=32, num_layers=2, num_heads=4,
                            attention="dense", max_seq_len=64,
                            moe_experts=4, moe_top_k=2, dtype=jnp.float32,
                            moe_capacity_factor=8.0, scan_layers=scan_layers)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(2), jnp.ones((1, 8), jnp.int32))
    params = {"params": params["params"]}  # drop sown collections
    return model, params


@pytest.mark.slow
def test_greedy_generate_moe_matches_naive():
    model, params = _moe_model()
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, (2, 5)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)

    tokens = prompt
    for _ in range(6):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_greedy_generate_moe_scan_stacked():
    model, params = _moe_model(scan_layers=True)
    prompt = jnp.asarray(
        np.random.default_rng(3).integers(0, 64, (1, 4)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=4)

    tokens = prompt
    for _ in range(4):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_moe_prefill_of_a_long_prompt():
    # a prefill-sized token count through the one expert layer
    # (`moe.expert_layer`: sorted by expert, grouped product) must agree
    # with the training forward exactly like a decode step's few tokens.
    model, params = _moe_model()
    prompt = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, (2, 40)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=2)

    tokens = prompt
    for _ in range(2):
        logits = model.apply(params, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tokens))


@pytest.mark.slow
def test_generate_jitted_with_sharded_params():
    # sharded inference: TP/FSDP-sharded params through the jitted
    # KV-cache decoder. Greedy token chains can legitimately diverge at
    # argmax near-ties (TP matmuls reduce in a different order), so the
    # oracle is the prefill logits within tolerance + a valid decode.
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flashy_tpu.models import transformer_shardings
    from flashy_tpu.models.decoding import _apply_step, init_cache
    from flashy_tpu.parallel import make_mesh

    model, params = _model_and_params()
    mesh = make_mesh({"tensor": 2, "fsdp": 2, "data": 2})
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), transformer_shardings(params),
        is_leaf=lambda x: isinstance(x, P))
    sharded = jax.device_put(params, shardings)
    prompt = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (2, 5)), jnp.int32)

    cfg = model.config
    positions = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32)[None], (2, 5))

    def prefill_logits(p):
        cache = init_cache(cfg, 2, 16)
        logits, _ = _apply_step(model, p, cfg, prompt, positions, cache,
                                jnp.int32(0))
        return logits

    ref = prefill_logits(params)
    out = jax.jit(prefill_logits)(sharded)
    # activations are bf16 (eps ~8e-3): sharded matmuls reduce in a
    # different order, so agreement is at bf16 granularity, not f32.
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-2)

    tokens = jax.jit(lambda p, t: generate(model, p, t, max_new_tokens=6))(
        sharded, prompt)
    arr = np.asarray(tokens)
    assert arr.shape == (2, 11)
    np.testing.assert_array_equal(arr[:, :5], np.asarray(prompt))
    assert ((arr >= 0) & (arr < 64)).all()


def test_generate_requires_rng_when_sampling():
    # the docstring always said rng is required for temperature > 0; the
    # code used to silently substitute PRNGKey(0), making "sampled"
    # outputs identical across calls — now it raises up front.
    model, params = _model_and_params()
    prompt = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="rng"):
        generate(model, params, prompt, max_new_tokens=3, temperature=0.8)
    # greedy needs no key
    out = generate(model, params, prompt, max_new_tokens=2)
    assert out.shape == (1, 6)


def test_generate_eos_token_pins_tail():
    # once a row emits eos_token, every later token of that row is
    # pinned to it (mask-based, inside the scan — shapes stay static).
    model, params = _model_and_params()
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (2, 5)), jnp.int32)
    free = np.asarray(generate(model, params, prompt, max_new_tokens=8))
    # use a token the free run actually emits mid-stream as the EOS id
    eos = int(free[0, 5 + 2])
    out = np.asarray(generate(model, params, prompt, max_new_tokens=8,
                              eos_token=eos))
    assert out.shape == free.shape  # static shapes: still 8 new tokens
    for row in range(2):
        gen, ref = out[row, 5:], free[row, 5:]
        hits = np.nonzero(ref == eos)[0]
        if hits.size:  # prefix up to the first EOS agrees; tail pinned
            first = hits[0]
            np.testing.assert_array_equal(gen[:first + 1], ref[:first + 1])
            assert (gen[first:] == eos).all()
        else:  # a row that never emits EOS is untouched
            np.testing.assert_array_equal(gen, ref)


def test_generate_eos_token_jittable():
    model, params = _model_and_params()
    prompt = jnp.ones((1, 4), jnp.int32)
    fn = jax.jit(lambda p, t: generate(model, p, t, max_new_tokens=3,
                                       eos_token=7))
    assert fn(params, prompt).shape == (1, 7)


def test_nucleus_filter_keeps_smallest_top_mass_prefix():
    from flashy_tpu.models.decoding import nucleus_filter

    # hand-built distribution: probs [0.5, 0.3, 0.15, 0.05]
    probs = np.array([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.asarray(np.log(probs), jnp.float32)

    def surviving(top_p):
        out = np.asarray(nucleus_filter(logits, top_p))[0]
        return set(np.nonzero(out > -1e29)[0].tolist())

    assert surviving(0.5) == {0}          # argmax alone reaches 0.5
    assert surviving(0.6) == {0, 1}       # 0.5 < 0.6 -> token 1 joins
    assert surviving(0.81) == {0, 1, 2}   # 0.8 < 0.81 -> token 2 joins
    assert surviving(1.0) == {0, 1, 2, 3}
    assert surviving(0.01) == {0}         # argmax ALWAYS survives

    # per-row independence: two rows with different shapes
    two = jnp.asarray(np.log(np.array([[0.5, 0.3, 0.15, 0.05],
                                       [0.25, 0.25, 0.25, 0.25]])),
                      jnp.float32)
    out = np.asarray(nucleus_filter(two, 0.55))
    assert set(np.nonzero(out[0] > -1e29)[0].tolist()) == {0, 1}
    # uniform row: every token ties with the cutoff logit, and ties
    # all stay eligible (dropping an arbitrary subset of
    # equally-likely tokens would bias the distribution)
    assert (out[1] > -1e29).sum() == 4


def test_nucleus_filter_rejects_out_of_range_top_p():
    # top_p <= 0 used to mask EVERY logit to -1e30 (near-uniform
    # sampling), contradicting the argmax-always-survives contract —
    # concrete out-of-range values are rejected loudly instead.
    from flashy_tpu.models.decoding import nucleus_filter

    logits = jnp.asarray(np.log(np.array([[0.5, 0.3, 0.15, 0.05]])),
                         jnp.float32)
    for bad in (0.0, -0.5, 1.5, np.float32(0.0), np.float64(1.5)):
        with pytest.raises(ValueError, match="top_p"):
            nucleus_filter(logits, bad)
    # a traced top_p can't be range-checked, but the argmax still
    # survives by construction
    out = np.asarray(jax.jit(nucleus_filter)(logits, jnp.float32(0.0)))[0]
    assert set(np.nonzero(out > -1e29)[0].tolist()) == {0}


def test_generate_with_top_p_stays_in_nucleus():
    # near-deterministic logits via a rigged vocab-64 distribution is
    # impractical on a random-init model, so assert the API contract:
    # jit-compatible, valid token range, and deterministic per key.
    model, params = _model_and_params()
    prompt = jnp.ones((2, 4), jnp.int32)
    fn = jax.jit(lambda p, t, k: generate(
        model, p, t, max_new_tokens=5, temperature=1.0, top_p=0.9, rng=k))
    out = fn(params, prompt, jax.random.PRNGKey(0))
    arr = np.asarray(out)
    assert arr.shape == (2, 9)
    assert ((arr >= 0) & (arr < 64)).all()
    np.testing.assert_array_equal(
        arr, np.asarray(fn(params, prompt, jax.random.PRNGKey(0))))
