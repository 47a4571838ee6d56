# Seeded FT203 violations: four misplacements of the int8 K/V quant
# scales against a hand-rolled paged-attention body — exactly the
# rewrite mistakes a future fused (Pallas) kernel could make. The
# healthy placement (K scales into scores pre-softmax, V scales into
# probs post-softmax, each once) is the live `ops.paged_attention`;
# these variants each break the identity one way:
#   double   — dequantize the gathered K view AND keep the folded
#              scores multiply (scale applied twice -> magnitudes
#              squared in scale)
#   unfolded — dequantize the view INSTEAD of folding (numerically
#              equal, head_dim times the multiply work + a dense copy)
#   wrongside— apply the K scale after the softmax (exp(s*x) != s*exp(x))
#   unscaled — never apply either scale (absmax-denominated garbage)
"""Seeded FT203 violations: misplaced int8 K/V quant scales."""
import jax
import jax.numpy as jnp

EXPECT = {
    "fixtures/ft203-double": {("FT203", "double-scale:k")},
    "fixtures/ft203-unfolded": {("FT203", "unfolded-scale:k")},
    "fixtures/ft203-wrongside": {("FT203", "wrong-side:k")},
    "fixtures/ft203-unscaled": {("FT203", "unscaled:k"),
                                ("FT203", "unscaled:v")},
}

_HEAD_DIM = 8


def _attention_variant(mode):
    def fn(q, entry, table, positions):
        batch, entries = table.shape

        def view(name):
            g = entry[name][table]
            g = g.reshape(batch, entries * g.shape[2], *g.shape[3:])
            # a block's scales are one (row-in-block, head) row
            s = entry[f"{name}_scale"][table].reshape(
                batch, g.shape[1], g.shape[2])
            return g.astype(jnp.float32), s  # payload [B,L,H,Dh], s [B,L,H]

        k_view, k_s = view("k")
        v_view, v_s = view("v")
        k_bhql = k_s.transpose(0, 2, 1)[:, :, None, :]
        v_bhql = v_s.transpose(0, 2, 1)[:, :, None, :]
        if mode in ("double", "unfolded"):
            k_view = k_view * k_s[..., None]  # dequantized view
        scale = 1.0 / jnp.sqrt(jnp.asarray(_HEAD_DIM, jnp.float32))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_view,
                            preferred_element_type=jnp.float32) * scale
        if mode == "double":
            scores = scores * k_bhql  # ...AND the folded multiply
        key_pos = jnp.arange(k_view.shape[1])[None, :]
        mask = key_pos[None] <= positions[:, :, None]
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        if mode == "wrongside":
            probs = probs * k_bhql  # K scale after the softmax
        if mode != "unscaled":
            probs = probs * v_bhql
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v_view)

    return fn


def programs():
    from flashy_tpu.ops.paged_attention import pool_spec

    num_blocks, block_size, heads = 4, 4, 2
    key = jax.random.PRNGKey(0)
    # the pool's own leaves: random int8 payloads, every scale 0.01
    entry = {
        name: (jax.random.randint(key, shape, -127, 127, jnp.int32
                                  ).astype(dtype) if dtype == jnp.int8
               else jnp.full(shape, 0.01, dtype))
        for name, (shape, dtype) in pool_spec(
            num_blocks, block_size, heads, _HEAD_DIM, jnp.float32,
            "int8").items()}
    q = jax.random.normal(key, (2, 1, heads, _HEAD_DIM), jnp.float32)
    table = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    positions = jnp.asarray([[5], [2]], jnp.int32)
    return [{"label": f"fixtures/ft203-{mode}",
             "fn": _attention_variant(mode),
             "example_args": (q, entry, table, positions)}
            for mode in ("double", "unfolded", "wrongside", "unscaled")]
