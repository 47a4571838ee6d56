# Weights-only int8 quantization (models/quantize.py) + its decode
# integration (models/decoding.py). Oracles: dequantize round-trip
# error bounded by the per-channel step size, and quantized decode
# logits closely tracking the full-precision decode.
"""Tests for int8 weights-only quantized decoding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.models import (TransformerConfig, TransformerLM, generate,
                               quantize_lm_params, dequantize_lm_params,
                               is_quantized)
from flashy_tpu.models.decoding import _apply_step, init_cache


def _model(scan_layers=False, moe=0):
    cfg = TransformerConfig(vocab_size=128, dim=64, num_layers=2, num_heads=2,
                            attention="dense", max_seq_len=64,
                            scan_layers=scan_layers, moe_experts=moe,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (2, 16)), jnp.int32)
    params = {"params": model.init(jax.random.PRNGKey(1), tokens)["params"]}
    return cfg, model, params, tokens


@pytest.mark.slow
def test_roundtrip_error_bounded_by_channel_step():
    _, _, params, _ = _model()
    qp = quantize_lm_params(params)
    dq = dequantize_lm_params(qp)

    # Per-leaf: |w - dq| <= scale/2 + eps everywhere a leaf was quantized.
    def check(path, orig, deq):
        err = jnp.abs(orig.astype(jnp.float32) - deq.astype(jnp.float32))
        assert float(err.max()) < float(
            jnp.abs(orig).max() / 127.0 + 1e-6), path

    kernels = 0
    flat_q = jax.tree_util.tree_leaves_with_path(
        qp, is_leaf=is_quantized)
    for path, leaf in flat_q:
        if is_quantized(leaf):
            kernels += 1
    assert kernels >= 2 * 4 + 1  # 2 blocks x (qkv,out,up,down) + embed

    jax.tree_util.tree_map(
        lambda a, b: check("leaf", a, b), params, dq)


@pytest.mark.parametrize("scan_layers,moe", [
    (False, 0),
    pytest.param(True, 0, marks=pytest.mark.slow),
    pytest.param(False, 2, marks=pytest.mark.slow)])
def test_quantized_decode_tracks_full_precision(scan_layers, moe):
    cfg, model, params, tokens = _model(scan_layers, moe)
    qp = quantize_lm_params(params)

    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape)
    cache_f = init_cache(cfg, 2, 32)
    cache_q = init_cache(cfg, 2, 32)
    logits_f, _ = _apply_step(model, params, cfg, tokens, positions,
                              cache_f, jnp.int32(0))
    logits_q, _ = _apply_step(model, qp, cfg, tokens, positions,
                              cache_q, jnp.int32(0))
    a = np.asarray(logits_f, np.float64).reshape(-1)
    b = np.asarray(logits_q, np.float64).reshape(-1)
    cos = np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12)
    assert cos > 0.999, cos


@pytest.mark.parametrize("scan_layers,moe", [
    (False, 0),
    pytest.param(True, 0, marks=pytest.mark.slow),
    pytest.param(False, 2, marks=pytest.mark.slow)])
def test_quantized_generate_runs_all_layouts(scan_layers, moe):
    cfg, model, params, tokens = _model(scan_layers, moe)
    qp = quantize_lm_params(params)
    out_f = generate(model, params, tokens, max_new_tokens=8)
    out_q = generate(model, qp, tokens, max_new_tokens=8)
    assert out_q.shape == out_f.shape == (2, 24)
    # Prompt is echoed verbatim; new tokens mostly agree (ties on a
    # random-init model can flip argmax, so not bit-exact).
    assert bool((out_q[:, :16] == tokens).all())
    agreement = float((out_f[:, 16:] == out_q[:, 16:]).mean())
    assert agreement >= 0.5, agreement


def test_quantized_tree_is_plain_pytree():
    # Checkpoint compatibility: only dicts + arrays, no custom nodes.
    _, _, params, _ = _model()
    qp = quantize_lm_params(params)
    leaves = jax.tree_util.tree_leaves(qp)
    assert all(hasattr(leaf, "dtype") for leaf in leaves)
    assert any(leaf.dtype == jnp.int8 for leaf in leaves)
    # int8 payload actually dominates: embed + 4 kernels per block.
    n_int8 = sum(leaf.size for leaf in leaves if leaf.dtype == jnp.int8)
    n_total = sum(leaf.size for leaf in leaves)
    assert n_int8 / n_total > 0.9


def test_router_and_norms_stay_dense():
    _, _, params, _ = _model(moe=2)
    qp = quantize_lm_params(params)["params"]
    assert not is_quantized(qp["block_0"]["moe"]["router"]["kernel"])
    assert qp["block_0"]["norm1"]["scale"].dtype == jnp.float32
    assert is_quantized(qp["block_0"]["moe"]["w_up"])


def test_keep_embed_dense_escape_hatch():
    # The tied embedding/head table feeds the softmax directly, so int8
    # error there lands on the output distribution; keep_embed_dense
    # leaves it full precision while still quantizing the block kernels.
    cfg, model, params, tokens = _model()
    qp = quantize_lm_params(params, keep_embed_dense=True)
    inner = qp["params"]
    assert not is_quantized(inner["embed"])
    assert inner["embed"].dtype == params["params"]["embed"].dtype
    assert is_quantized(inner["block_0"]["mlp"]["up"]["kernel"])
    # the mixed tree decodes through the same step path, and a dense
    # head tracks the full-precision logits strictly better than the
    # fully-quantized tree does
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape)

    def cos_to_ref(tree):
        logits_f, _ = _apply_step(model, params, cfg, tokens, positions,
                                  init_cache(cfg, 2, 32), jnp.int32(0))
        logits_q, _ = _apply_step(model, tree, cfg, tokens, positions,
                                  init_cache(cfg, 2, 32), jnp.int32(0))
        a = np.asarray(logits_f, np.float64).reshape(-1)
        b = np.asarray(logits_q, np.float64).reshape(-1)
        return np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12)

    full_q = quantize_lm_params(params)
    assert cos_to_ref(qp) >= cos_to_ref(full_q) - 1e-9
    assert cos_to_ref(qp) > 0.999


def test_quantize_kv_zero_rows_well_conditioned():
    # FT203's runtime complement: an all-zero K/V row (the paged pool's
    # sentinel block, a zero-init cache) must NOT produce an inf/NaN or
    # pathologically-tiny scale. Before the clamp, the zero-absmax
    # denominator only "worked" because sentinel rows sit past every
    # causal horizon; the contract now is (q=0, scale=1) exactly.
    from flashy_tpu.models.quantize import dequantize_kv, quantize_kv

    x = jnp.zeros((2, 3, 8), jnp.float32)
    q, scale = quantize_kv(x)
    assert np.all(np.isfinite(np.asarray(scale)))
    assert np.array_equal(np.asarray(scale), np.ones((2, 3), np.float32))
    assert np.array_equal(np.asarray(q), np.zeros((2, 3, 8), np.int8))
    assert np.array_equal(np.asarray(dequantize_kv(q, scale)),
                          np.zeros((2, 3, 8), np.float32))
    # the reciprocal path a fused kernel might take stays finite even
    # in bf16 — the failure mode the old ~8e-15 epsilon scale invited
    inv = 1.0 / jnp.asarray(scale, jnp.bfloat16)
    assert np.all(np.isfinite(np.asarray(inv, np.float32)))
    # mixed rows: zero rows get the unit scale, live rows keep absmax
    mixed = jnp.concatenate([jnp.zeros((1, 8)), jnp.full((1, 8), 0.5)])
    q2, scale2 = quantize_kv(mixed)
    assert np.asarray(scale2)[0] == 1.0
    assert np.isclose(np.asarray(scale2)[1], 0.5 / 127.0)
    assert np.allclose(np.asarray(dequantize_kv(q2, scale2))[1], 0.5,
                       rtol=1 / 127)


def test_quantize_weights_zero_channel_well_conditioned():
    # same clamp on the weights path: a dead output channel quantizes
    # to (q=0, scale=1) and round-trips to exact zeros
    from flashy_tpu.models.quantize import _quantize, dequantize

    w = jnp.concatenate([jnp.zeros((8, 1)), jnp.ones((8, 1))], axis=1)
    leaf = _quantize(w, contract_axes=(0,))
    scale = np.asarray(leaf["scale"])
    assert np.all(np.isfinite(scale))
    assert scale[0, 0] == 1.0
    back = np.asarray(dequantize(leaf))
    assert np.array_equal(back[:, 0], np.zeros(8, np.float32))
    assert np.allclose(back[:, 1], 1.0, rtol=1 / 127)


def test_paged_attention_finite_over_all_zero_pool():
    # end to end: attending a freshly-zeroed int8 pool (every gathered
    # row is a sentinel-style zero row) must produce finite outputs —
    # the inf/NaN scales this guards against would poison the softmax
    # even though masked positions contribute no weight
    from flashy_tpu.ops.paged_attention import (init_pool, paged_attention,
                                                paged_write, scale_rows)

    cfg = TransformerConfig(vocab_size=32, dim=16, num_layers=1,
                            num_heads=2, attention="dense",
                            max_seq_len=32, dtype=jnp.float32)
    pool = init_pool(cfg, num_blocks=4, block_size=4, kv_dtype="int8")
    entry = pool["block_0"]
    table = jnp.asarray([[1, 2, 0]], jnp.int32)
    positions = jnp.asarray([[0]], jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 8),
                            jnp.float32)
    entry = paged_write(entry, new, new, table, positions)
    # an ALL-ZERO row written through the quantize-on-write path (a
    # padded/parked slot's row) must land with the unit scale
    zero_row = jnp.zeros((1, 1, 2, 8), jnp.float32)
    entry = paged_write(entry, zero_row, zero_row, table,
                        jnp.asarray([[1]], jnp.int32))
    rows = scale_rows(entry["k_scale"], jnp.asarray(1), 2)  # block 1
    assert np.asarray(rows)[1].min() == 1.0
    out = paged_attention(new, entry, table, positions, head_dim=8,
                          dtype=jnp.float32)
    assert np.all(np.isfinite(np.asarray(out)))
