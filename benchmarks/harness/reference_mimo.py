# The plain reference of the MiMo-V2 language model (`model_type`
# mimo_v2), written from the equations of ISSUE 31 and not from the
# program's code. float32 throughout, every product at
# `jax.default_matmul_precision("highest")`, no cache, no ring, no block
# table, no batching, no sorting of tokens by expert: a window is a mask
# on the full score block.
#
# Layer l, kind g = hybrid_layer_pattern[l] (0 full, 1 window); H query
# heads, Hkv KV heads of the kind, keys Dk wide, values Dv wide; every
# norm an RMSNorm with a learned scale, eps layernorm_epsilon:
#   attention   [q | k | v] = norm(x) W_in; the first R = int(
#               partial_rotary_factor * Dk) dimensions of each head of q
#               and k rotated, pairs (i, i + R/2), frequency
#               theta_g^(-2i/R) (rope_theta | swa_rope_theta); v scaled
#               by attention_value_scale;
#               s[h,t,j] = q[h,t].k[h // (H/Hkv), j] / sqrt(Dk), seen
#               iff j <= t and, in a window layer, t - j <
#               sliding_window; window layers with
#               add_swa_attention_sink_bias: a scalar b[h] joins the
#               softmax's denominator, p = exp(s - m) / (exp(b - m) +
#               sum_j exp(s_j - m)), m = max(b, max_j s); full layers
#               the plain softmax; o = sum_j p v; x += o W_o.
#   dense MLP   W_down(silu(W_gate n) * W_up n)   (moe_layer_freq[l] 0)
#   experts     sc = sigmoid(n W_r) in float32; the num_experts_per_tok
#               experts with the largest sc + bias; gates sc / their
#               sum (norm_topk_prob) * (routed_scaling_factor or 1);
#               x += sum over chosen AND HELD experts gate_e expert_e(n);
#               no shared expert.
# The chip's share: `held_experts` = [first, count] says which routed
# experts' weights exist here; the router still scores all
# `n_routed_experts_published`, and an assignment to an expert held
# elsewhere adds nothing. The vocabulary is the file's slice.
#
# It reads the program's parameter tree (embed, head, norm_f,
# block_<i>/{norm1, attn/{in_proj, out, sink}, norm2, mlp/{up,down} |
# moe/{router, router_bias, w_up, w_down}}), `in_proj` holding
# [q | k | v] and `up` [gate | value], so both sides compute from the
# same seeded weights. Leaves are upcast one at a time; attention goes
# one KV head's group of query heads and QUERY_BLOCK queries at a time,
# a wide hidden layer in column blocks, so that 17,408 positions fit
# beside the weights.
#
# `precision="bfloat16"` computes the same equations with every tensor,
# product, norm and softmax in bfloat16: the reading a too-low precision
# gives, which the cell's limits have to refuse.
"""Plain float32 reference of the window/full grouped-attention LM."""
import contextlib
import math

import jax
import jax.numpy as jnp

from .reference_dots import route

QUERY_BLOCK = 256
HIDDEN_BLOCK = 2048


def _norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(x.dtype)


def layer_shape(config: dict, layer: int) -> dict:
    """The sizes of layer `layer`'s attention, by its kind."""
    windowed = bool(config["hybrid_layer_pattern"][layer])
    pre = "swa_" if windowed else ""
    return {
        "windowed": windowed,
        "kv_heads": config[f"{pre}num_key_value_heads"],
        "key_dim": config[f"{pre}head_dim"],
        "value_dim": config[f"{pre}v_head_dim"],
        "theta": float(config[f"{pre}rope_theta"]),
        "sink": bool(config["add_swa_attention_sink_bias"] if windowed
                     else config["add_full_attention_sink_bias"]),
    }


def _rotate_halves(x, positions, theta, width):
    """x [T, heads, D]: dimensions i and i + width/2 (i < width/2) turned
    by position * theta^(-2i/width); dimensions from `width` on pass."""
    half = width // 2
    freqs = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / width)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, None, :].astype(x.dtype)
    first, second = x[..., :half], x[..., half:width]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, x[..., width:]], -1)


def _attention(p, x, config, layer, dt):
    """x [T, D] (normed) -> attention output [T, D]."""
    length = x.shape[0]
    shape = layer_shape(config, layer)
    heads, kv_heads = config["num_attention_heads"], shape["kv_heads"]
    dk, dv = shape["key_dim"], shape["value_dim"]
    group = heads // kv_heads
    rotary = int(config["partial_rotary_factor"] * dk)
    positions = jnp.arange(length)
    up = lambda w: jnp.asarray(w, dt)

    qkv = x @ up(p["in_proj"]["kernel"])
    q = qkv[:, :heads * dk].reshape(length, heads, dk)
    k = qkv[:, heads * dk:(heads + kv_heads) * dk].reshape(
        length, kv_heads, dk)
    v = qkv[:, (heads + kv_heads) * dk:].reshape(length, kv_heads, dv)
    v = v * jnp.asarray(config["attention_value_scale"], dt)
    q = _rotate_halves(q, positions, shape["theta"], rotary)
    k = _rotate_halves(k, positions, shape["theta"], rotary)
    sink = (jnp.asarray(p["sink"], dt).reshape(kv_heads, group)
            if shape["sink"] else None)

    block = math.gcd(length, QUERY_BLOCK)
    # [Hkv, blocks, G, block, Dk]: one KV head's queries, block by block
    q = q.reshape(length // block, block, kv_heads, group, dk).transpose(
        2, 0, 3, 1, 4)
    starts = jnp.arange(length // block) * block

    def one_kv_head(inputs):
        q_head, k_head, v_head, b = inputs    # [blocks,G,block,Dk] [T,Dk] ..

        def one_block(block_in):
            q_block, start = block_in                       # [G, block, Dk]
            t = start + jnp.arange(block)
            seen = positions[None, :] <= t[:, None]
            if shape["windowed"]:
                seen &= t[:, None] - positions[None, :] < config[
                    "sliding_window"]
            s = jnp.einsum("gtd,jd->gtj", q_block, k_head) / jnp.asarray(
                math.sqrt(dk), dt)
            s = jnp.where(seen[None], s, -jnp.inf)
            if b is None:
                probs = jax.nn.softmax(s, axis=-1)
            else:
                m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True),
                                b[:, None, None])
                e = jnp.exp(s - m)
                probs = e / (jnp.exp(b[:, None, None] - m)
                             + jnp.sum(e, axis=-1, keepdims=True))
            return jnp.einsum("gtj,jv->gtv", probs, v_head)

        return jax.lax.map(one_block, (q_head, starts))  # [blocks,G,block,Dv]

    out = jax.lax.map(one_kv_head, (q, k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2), sink))
    # [Hkv, blocks, G, block, Dv] -> [T, H, Dv], head h = kv * G + g
    out = out.transpose(1, 3, 0, 2, 4).reshape(length, heads * dv)
    return out @ up(p["out"]["kernel"]).reshape(heads * dv, -1)


def _gated_mlp(p, x, dt):
    """W_down(silu(W_gate x) * W_up x); `up` holds [gate | value]. The
    hidden width goes through in column blocks."""
    w_up, w_down = p["up"]["kernel"], p["down"]["kernel"]
    hidden = w_down.shape[0]
    block = math.gcd(hidden, HIDDEN_BLOCK)

    def one(index):
        at = index * block
        gate = x @ jnp.asarray(jax.lax.dynamic_slice_in_dim(
            w_up, at, block, 1), dt)
        value = x @ jnp.asarray(jax.lax.dynamic_slice_in_dim(
            w_up, hidden + at, block, 1), dt)
        return (jax.nn.silu(gate) * value) @ jnp.asarray(
            jax.lax.dynamic_slice_in_dim(w_down, at, block, 0), dt)

    out, _ = jax.lax.scan(lambda total, index: (total + one(index), None),
                          jnp.zeros_like(x), jnp.arange(hidden // block))
    return out


def _expert_layer(p, x, config, dt):
    """The held experts' part of sum_k gate_k expert_k(x)."""
    first, count = config["held_experts"]
    router_dt = jnp.float32 if dt == jnp.float32 else dt
    gates = route(
        x.astype(router_dt) @ jnp.asarray(p["router"]["kernel"], router_dt),
        p["router_bias"],
        dict(config, routed_scaling_factor=config["routed_scaling_factor"]
             or 1.0)).astype(dt)
    width = p["w_down"].shape[1]
    out = jnp.zeros_like(x)
    for local in range(count):  # every token through every held expert
        hidden = x @ jnp.asarray(p["w_up"][local], dt)
        y = (jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ jnp.asarray(
            p["w_down"][local], dt)
        out = out + gates[:, first + local, None] * y
    return out


def hidden_states(params, tokens, config: dict, precision: str = "float32"):
    """tokens [B, T] int32 -> final normed hidden [B, T, D]."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    context = (jax.default_matmul_precision("highest") if dt == jnp.float32
               else contextlib.nullcontext())
    eps = config["layernorm_epsilon"]
    with context:
        def one(sequence):
            x = jnp.asarray(params["embed"], dt)[sequence]
            for layer in range(config["num_hidden_layers"]):
                p = params[f"block_{layer}"]
                x = x + _attention(p["attn"],
                                   _norm(x, p["norm1"]["scale"], eps),
                                   config, layer, dt)
                h = _norm(x, p["norm2"]["scale"], eps)
                if config["moe_layer_freq"][layer]:
                    x = x + _expert_layer(p["moe"], h, config, dt)
                else:
                    x = x + _gated_mlp(p["mlp"], h, dt)
            return _norm(x, params["norm_f"]["scale"], eps)

        return jnp.stack([one(sequence) for sequence in tokens])


def _head(params, hidden, precision):
    if precision != "float32":
        return (hidden @ jnp.asarray(params["head"], hidden.dtype).T).astype(
            jnp.float32)
    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["head"], jnp.float32).T


def logits(params, tokens, config: dict, precision: str = "float32"):
    """tokens [B, T] -> logits [B, T, V] over the vocabulary slice
    (untied head)."""
    return _head(params, hidden_states(params, tokens, config, precision),
                 precision)


def logits_at(params, tokens, positions, config: dict,
              precision: str = "float32"):
    """For one sequence tokens [1, T] (prompt + served output, padded):
    float32 logits [G, V] at `positions` [G], the model computed in
    `precision`."""
    hidden = hidden_states(params, tokens, config, precision)[0][positions]
    return _head(params, hidden, precision)
