"""The plain float32 reference against TransformerLM at a toy size."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.harness import model as model_lib, reference

TOY = {"hidden_size": 64, "intermediate_size": 256, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 256,
       "max_position_embeddings": 128, "rope_theta": 10000.0,
       "hidden_act": "silu", "tie_word_embeddings": True}


@pytest.fixture(scope="module")
def setup():
    from flashy_tpu.models import TransformerLM
    model = TransformerLM(model_lib.transformer_config(
        TOY, attention="dense", dtype=jnp.float32))
    params = model_lib.seeded_params(model, 2 ** 31 + 3)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 48)).astype(np.int32)
    return model, params, tokens


def test_logits_and_loss_match_the_program_in_float32(setup):
    # both sides float32 on the CPU: what is left is the order of the
    # additions, so the tolerance is a few float32 roundings (1e-4 of
    # logits of order one); a wrong mask, rotary pairing or a dropped
    # layer moves logits by tenths
    model, params, tokens = setup
    want = np.asarray(model.apply({"params": params}, tokens))
    got = np.asarray(reference.logits(params, tokens, TOY))
    assert np.max(np.abs(got - want)) < 1e-4 * max(1.0, np.abs(want).max())
    loss = float(optax.softmax_cross_entropy_with_integer_labels(
        want[:, :-1], tokens[:, 1:]).mean())
    assert float(reference.next_token_loss(params, tokens, TOY)) \
        == pytest.approx(loss, rel=1e-5)


def test_reference_is_causal_and_position_aware(setup):
    _, params, tokens = setup
    base = np.asarray(reference.logits(params, tokens[:1], TOY))
    changed = tokens[:1].copy()
    changed[0, -1] = (changed[0, -1] + 1) % 256
    later = np.asarray(reference.logits(params, changed, TOY))
    assert np.array_equal(base[:, :-1], later[:, :-1])
    rolled = np.roll(tokens[:1], 1, axis=1)
    assert not np.allclose(
        np.asarray(reference.logits(params, rolled, TOY))[0, 1:],
        base[0, :-1], atol=1e-3)


def test_served_token_gaps(setup):
    _, params, tokens = setup
    logits = np.asarray(reference.logits(params, tokens[:1], TOY))[0]
    positions = np.array([10, 11, 12], np.int32)
    best = logits[positions].argmax(-1).astype(np.int32)
    gap, spread = reference.served_token_gaps(
        params, tokens[:1], positions, best, TOY)
    assert np.allclose(np.asarray(gap), 0.0, atol=1e-5)
    worst = logits[positions].argmin(-1).astype(np.int32)
    gap, _ = reference.served_token_gaps(
        params, tokens[:1], positions, worst, TOY)
    assert np.all(np.asarray(gap) > np.asarray(spread))


def test_config_mapping_refuses_what_the_block_cannot_express():
    with pytest.raises(ValueError, match="grouped KV"):
        model_lib.transformer_config(dict(TOY, num_key_value_heads=2))
    with pytest.raises(ValueError, match="MLP ratio"):
        model_lib.transformer_config(dict(TOY, intermediate_size=200))
