# The plain reference of the one block `TransformerLM` implements,
# written from its equations and not from its code: pre-norm, full
# multi-head attention with rotary embeddings over the whole head
# (split-half pairing, theta from the config), a gated-SiLU MLP, a tied
# output head, no biases. float32 throughout, every matmul at
# `jax.default_matmul_precision("highest")` (on a TPU an f32 matmul
# otherwise runs as bf16 passes), no kernels, no cache, no remat.
#
# Departure from the published OLMo-1B (also in the config files): the
# norm is the program's RMSNorm with a learned scale and eps 1e-6, not
# OLMo's non-parametric LayerNorm — the program has no other norm, and
# the reference follows what is run.
#
# It reads the program's parameter tree (flax names: embed, block_<i>/
# {norm1,attn/{qkv,out},norm2,mlp/{up,down}}, norm_f) so both sides
# compute from the same seeded weights.
"""Plain float32 reference of the decoder LM (forward, loss, margins)."""
import functools

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6


def _norm(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS)
    return x * scale


def _rotary(x, positions, theta):
    """x [B, T, H, Dh]; pairs (i, i + Dh/2) rotate by pos * theta^(-2i/Dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(params, tokens, config: dict):
    """tokens [B, T] int32 -> final normed hidden [B, T, D] in float32."""
    with jax.default_matmul_precision("highest"):
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        theta = float(config.get("rope_theta", 10000.0))
        batch, length = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(length)[None], tokens.shape)
        causal = jnp.tril(jnp.ones((length, length), bool))
        x = f32(params["embed"])[tokens]
        for layer in range(config["num_hidden_layers"]):
            p = params[f"block_{layer}"]
            h = _norm(x, f32(p["norm1"]["scale"]))
            qkv = jnp.einsum("btd,dchk->cbthk", h,
                             f32(p["attn"]["qkv"]["kernel"]))
            q = _rotary(qkv[0], positions, theta)
            k = _rotary(qkv[1], positions, theta)
            scores = jnp.einsum("bqhk,bshk->bhqs", q, k)
            scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqs,bshk->bqhk", probs, qkv[2])
            x = x + jnp.einsum("bqhk,hkd->bqd", out,
                               f32(p["attn"]["out"]["kernel"]))
            h = _norm(x, f32(p["norm2"]["scale"]))
            up = h @ f32(p["mlp"]["up"]["kernel"])
            gate, value = jnp.split(up, 2, axis=-1)
            x = x + (jax.nn.silu(gate) * value) @ f32(
                p["mlp"]["down"]["kernel"])
        return _norm(x, f32(params["norm_f"]["scale"]))


def logits(params, tokens, config: dict):
    """tokens [B, T] -> logits [B, T, V] (tied head)."""
    hidden = hidden_states(params, tokens, config)
    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["embed"], jnp.float32).T


def next_token_loss(params, tokens, config: dict):
    """Mean cross-entropy of tokens[:, 1:] under logits[:, :-1]."""
    out = logits(params, tokens, config)[:, :-1]
    logz = jax.nn.logsumexp(out, axis=-1)
    picked = jnp.take_along_axis(out, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def served_token_gaps(params, tokens, positions, served, config: dict):
    """For one sequence tokens [1, T] (prompt + served output, padded),
    the reference logits at `positions` [G] (the position BEFORE each
    served token): returns (gap [G], spread [G]) where gap is the
    reference's largest logit minus its logit of the served token and
    spread the standard deviation of the logits over the vocabulary."""
    hidden = hidden_states(params, tokens, config)[0][positions]
    with jax.default_matmul_precision("highest"):
        out = hidden @ jnp.asarray(params["embed"], jnp.float32).T
    picked = jnp.take_along_axis(out, served[:, None], axis=-1)[:, 0]
    return jnp.max(out, axis=-1) - picked, jnp.std(out, axis=-1)
