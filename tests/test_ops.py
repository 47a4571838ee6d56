# Tests for ops: flash attention (pallas interpret mode on CPU) against
# the XLA reference, gradients, fallbacks.
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.ops import dot_product_attention, flash_attention


def _rand_qkv(shape, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                 for _ in range(3))


# T of several tiles in both directions: tiles under, on and past the
# causal diagonal (one a row of tiles crossed by it at square tiles, two
# or four at oblong ones); a tile past it is skipped and its index map
# names the block before it
@pytest.mark.parametrize("t,blocks", [(128, (64, 64)), (256, (32, 32)),
                                      (256, (64, 32)), (256, (32, 128))])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal, t, blocks):
    q, k, v = _rand_qkv((2, t, 4, 32))
    out = flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                          block_k=blocks[1])
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# forward | backward tiles: equal (a caller's blocks are both kernels'),
# and differing as `flash_schedule` may give them (each kernel walks its
# own tiling of the same T x T scores)
@pytest.mark.parametrize("tiles", [((32, 32), (32, 32)), ((32, 16), (16, 64)),
                                   ((64, 64), (16, 16))])
def test_flash_gradients_match(tiles):
    from flashy_tpu.ops import attention
    q, k, v = _rand_qkv((1, 64, 2, 16), seed=1)
    forward, backward = (
        attention.flash_schedule(kernel, 2, 64, 64, 16, 4, True,
                                 block_q=blocks[0], block_k=blocks[1])
        for kernel, blocks in zip(("fwd", "bwd"), tiles))
    assert (forward.block_q, forward.block_k) == tiles[0]
    assert (backward.block_q, backward.block_k) == tiles[1]

    def flash_loss(q, k, v):
        return (attention._flash(q, k, v, True, forward, backward, True,
                                 True) ** 2).sum()

    def dense_loss(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    grads_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    grads_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads_flash, grads_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_fallback_on_indivisible_lengths():
    q, k, v = _rand_qkv((1, 48, 2, 16), seed=2)  # 48 % 256-clamped-to-48 == 0
    # force an indivisible block explicitly
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_dense_attention_mask():
    q, k, v = _rand_qkv((1, 8, 1, 8), seed=3)
    # mask out the last key entirely
    mask = jnp.ones((1, 1, 8, 8), bool).at[..., -1].set(False)
    out = dot_product_attention(q, k, v, mask=mask)
    # equivalent to dropping the last key/value
    ref = dot_product_attention(q, k[:, :-1], v[:, :-1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_dense_attention_bf16_inputs():
    q, k, v = _rand_qkv((1, 16, 2, 8), seed=4)
    out = dot_product_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                                v.astype(jnp.bfloat16), causal=True)
    assert out.dtype == jnp.bfloat16


def test_flash_causal_cross_length_matches_dense():
    # t_q != t_k: causal alignment is bottom-right (query i sees keys
    # j <= i + t_k - t_q), and the pallas path must agree with the dense
    # fallback it pairs with in the backward.
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_backward_cross_length():
    # gradients with t_q != t_k through the pallas backward kernels
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=32))
    dense = loss(lambda q, k, v: dot_product_attention(q, k, v, causal=True))
    ga = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("block_q", [16, 32])
def test_flash_empty_rows_zero(block_q, fused):
    # t_k < t_q with causal: offset = t_k - t_q < 0, so queries
    # i < t_q - t_k see NO keys at all. Convention: they attend to
    # nothing — zero output, zero gradients. Regressions this guards:
    #  * forward: a mixed q-block (block_q=32 here spans 16 empty + 16
    #    visible rows) has m_new = NEG_INF for empty rows, so unguarded
    #    probs = exp(0) = 1 silently averaged V over masked keys;
    #  * backward: the clamped lse makes unguarded probs = exp(0) = 1,
    #    producing garbage dq/dk/dv for those rows.
    # block_q=16 additionally covers the aligned case where the empty
    # rows form a whole skipped block.
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 16)).astype(np.float32))
    n_empty = q.shape[1] - k.shape[1]

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=block_q,
                                block_k=16, fused_backward=fused) ** 2).sum()

    def dense_loss(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    # the guard against exp(0), a compare with NEG_INF / 2 = -5e+29, is
    # traced into the kernels
    assert "e+29" in str(jax.make_jaxpr(
        jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v))

    out = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=16)
    np.testing.assert_array_equal(np.asarray(out[:, :n_empty]), 0.0)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    ga = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g in ga:
        assert np.isfinite(np.asarray(g)).all()
    # empty q rows contribute nothing: dq there is exactly zero
    np.testing.assert_array_equal(np.asarray(ga[0][:, :n_empty]), 0.0)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_dense_attention_fully_masked_rows_zero():
    # the dense path shares the zeros convention for fully-masked rows
    q, k, v = _rand_qkv((1, 8, 2, 16), seed=10)
    mask = np.ones((1, 1, 8, 8), bool)
    mask[:, :, 3] = False                 # query 3 sees nothing
    out = dot_product_attention(q, k, v, mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(out[:, 3]), 0.0)
    assert np.isfinite(np.asarray(out)).all()
    # other rows unaffected by the masked row's existence
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out[:, :3]), np.asarray(ref[:, :3]),
                               rtol=1e-5, atol=1e-6)


def test_flash_backward_asymmetric_blocks_non_causal():
    q, k, v = _rand_qkv((2, 64, 2, 32), seed=7)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal=False,
                                block_q=32, block_k=64) ** 3).sum()

    def dense_loss(q, k, v):
        return (dot_product_attention(q, k, v, causal=False) ** 3).sum()

    ga = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_backward_bf16_dtype_and_close():
    q, k, v = _rand_qkv((1, 64, 2, 16), seed=8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
                .astype(jnp.float32) ** 2).sum()

    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(qb, kb, vb)
    assert all(g.dtype == jnp.bfloat16 for g in grads)
    ref = jax.grad(lambda q, k, v: (dot_product_attention(
        q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, ref):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.05)


def test_flash_auto_block_for_384():
    # 384 = 3*128 divides none of the default blocks; the auto-pick must
    # run the kernel at 384 instead of falling back to dense, and a
    # non-128-aligned length must still fall back (same numbers either
    # way — this pins the selection logic).
    from flashy_tpu.ops.attention import _dividing_block
    assert _dividing_block(384) == 384
    assert _dividing_block(640) == 128
    assert _dividing_block(768) == 384
    assert _dividing_block(1024) == 512
    assert _dividing_block(200) == 0

    q, k, v = _rand_qkv((1, 384, 2, 16), seed=13)
    out = flash_attention(q, k, v, causal=True)  # default 256 blocks
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


class TestChunkedCrossEntropy:
    def _setup(self):
        import optax
        from flashy_tpu.models import TransformerConfig, TransformerLM
        cfg = TransformerConfig(vocab_size=512, dim=64, num_layers=2,
                                num_heads=2, attention="dense",
                                dtype=jnp.float32)
        model = TransformerLM(cfg)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 512, (2, 96)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)
        return model, params, tokens

    @pytest.mark.parametrize(
        "chunk", [pytest.param(32, marks=pytest.mark.slow),
                  pytest.param(37, marks=pytest.mark.slow), 200])
    def test_matches_dense_loss_and_grads(self, chunk):
        # chunk=37 does not divide T-1=95 (internal padding path);
        # chunk=200 exceeds T (single padded chunk).
        from flashy_tpu.ops import lm_next_token_loss
        model, params, tokens = self._setup()

        ld, gd = jax.value_and_grad(
            lambda p: lm_next_token_loss(model, p, tokens, mode="dense")
        )(params)
        lc, gc = jax.value_and_grad(
            lambda p: lm_next_token_loss(model, p, tokens, mode="chunked",
                                         chunk_size=chunk))(params)
        np.testing.assert_allclose(float(ld), float(lc), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6), gd, gc)

    def test_per_token_values_match_direct(self):
        # Direct oracle on raw arrays (no model): loss[b, t] must equal
        # lse - correct computed from the dense logits.
        from flashy_tpu.ops import chunked_softmax_cross_entropy
        rng = np.random.default_rng(1)
        hidden = jnp.asarray(rng.normal(size=(2, 13, 8)), jnp.float32)
        head = jnp.asarray(rng.normal(size=(31, 8)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 31, (2, 13)), jnp.int32)
        loss = chunked_softmax_cross_entropy(hidden, head, labels,
                                             chunk_size=4)
        logits = hidden @ head.T
        ref = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_bad_mode_raises(self):
        from flashy_tpu.ops import lm_next_token_loss
        model, params, tokens = self._setup()
        with pytest.raises(ValueError, match="mode"):
            lm_next_token_loss(model, params, tokens, mode="bogus")


# ----------------------------------------------------------------------
# fused one-pass flash backward: BIT parity against the split
# dq/dkv-kernel oracle (the tp-demo gate). The fused kernel replays the
# split pair's accumulation order op for op, so np.array_equal — not
# allclose — is the contract; any nonzero delta is a kernel bug.
# ----------------------------------------------------------------------
# 2048 / block_q, 2048 / block_k of `flash_bwd_fused` in olmo1b-train-2k,
# at T = 256 here: a head's dQ is summed over the same count of k-tiles
CELL_RATIOS = (256 // 4, 256 // 4)


def _flash_grads(q, k, v, *, causal, block_q, block_k, fused):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, fused_backward=fused)
        return (out.astype(jnp.float32) ** 2).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape_q,shape_k,causal,blocks,dtype", [
    ((2, 128, 2, 64), (2, 128, 2, 64), True, (64, 64), jnp.float32),
    ((1, 64, 2, 32), (1, 128, 2, 32), False, (32, 64), jnp.float32),
    ((1, 128, 2, 32), (1, 128, 2, 32), True, (64, 32), jnp.bfloat16),
    # the training cell's tile-to-sequence ratios (CELL_RATIOS)
    ((1, 256, 2, 32), (1, 256, 2, 32), True, CELL_RATIOS, jnp.bfloat16),
    # more keys than queries (offset > 0): every row sees the first tiles
    ((1, 64, 2, 32), (1, 128, 2, 32), True, (32, 32), jnp.float32),
    ((1, 64, 1, 32), (1, 192, 1, 32), True, (16, 64), jnp.bfloat16),
    # fewer (offset < 0): the first rows are empty, the guard zeroes them
    ((1, 128, 2, 32), (1, 64, 2, 32), True, (32, 32), jnp.bfloat16),
    ((1, 128, 1, 32), (1, 32, 1, 32), True, (64, 16), jnp.float32),
    ((2, 128, 2, 32), (2, 128, 2, 32), False, (32, 32), jnp.bfloat16),
])
def test_flash_fused_backward_bit_identical_to_split(shape_q, shape_k,
                                                     causal, blocks, dtype):
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal(shape_q), dtype)
    k = jnp.asarray(rng.standard_normal(shape_k), dtype)
    v = jnp.asarray(rng.standard_normal(shape_k), dtype)
    block_q, block_k = blocks
    fused = _flash_grads(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, fused=True)
    split = _flash_grads(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, fused=False)
    for a, b in zip(fused, split):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# at the tiles every training run before PR 36 compiled (256 x 256), the
# kernels give the parent's values: the schedule changed, not the
# mathematics (tests/data/record_flash_parent_outputs.py is the one
# definition of the cases; run on a checkout of the parent it wrote the
# .npz, run here it gives what to compare, bit for bit)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def flash_at_256():
    import importlib.util
    import os
    data = os.path.join(os.path.dirname(__file__), "data")
    spec = importlib.util.spec_from_file_location(
        "record_flash_parent_outputs",
        os.path.join(data, "record_flash_parent_outputs.py"))
    recorder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(recorder)
    recorded = np.load(os.path.join(data, "flash_parent_outputs.npz"))
    return recorded, recorder.outputs()


@pytest.mark.parametrize("array", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", ["diagonal", "all_keys", "self",
                                  "longer_keys"])
def test_flash_at_256_tiles_is_the_parents(flash_at_256, case, array):
    recorded, now = flash_at_256
    np.testing.assert_array_equal(now[f"{case}/{array}"],
                                  recorded[f"{case}/{array}"])
