# The plain reference of the nemotron_h language model (Mamba-2 layers,
# latent experts, attention without rotary; one mixer a layer), written
# from the equations of ISSUE 33 and not from the program's code.
# float32 throughout, every product at
# `jax.default_matmul_precision("highest")`, the recurrent state by a
# `lax.scan` over TOKENS: no chunked form, no kernel, no cache, no block
# table, no batching, no sorting of tokens by expert.
#
# All norms RMSNorm with a learned scale, eps norm_eps; no bias anywhere
# but the conv; embedding and head untied; a final norm. Layer l of kind
# hybrid_override_pattern[l]:  x <- x + mixer_l(norm_l(x)), nothing else.
#
#   M  Mamba-2 (H mamba_num_heads, P mamba_head_dim, N ssm_state_size, G
#      n_groups, K conv_kernel; d_inner = H P, conv_dim = d_inner + 2GN):
#        [z | xBC | dt] = W_in u          (D -> d_inner + conv_dim + H)
#        xBC <- silu(conv1d_depthwise_causal(xBC, K) + b_conv)
#        x [H, P], B [G, N], C [G, N] = split(xBC)
#        dt <- softplus(dt + dt_bias);  A = -exp(A_log)      (per head)
#        h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t    [H, P, N];
#              head h reads group h // (H / G)'s B and C
#        y_t = h_t C_t + D x_t
#        y <- RMSNorm_grouped(y * silu(z)) * scale   (groups of d_inner / G;
#              gate first, then norm)
#        out = W_out y                    (d_inner -> D)
#   E  latent experts: sc = sigmoid(W_r u) in float32 on the FULL hidden
#      state (D -> n_routed_experts_published); the num_experts_per_tok
#      largest sc + bias; gates sc / their sum (norm_topk_prob) *
#      routed_scaling_factor; t = W_dn u (D -> moe_latent_size), ONE
#      projection for all routed experts; expert e: W2_e relu(W1_e t)^2
#      (latent -> moe_intermediate_size -> latent, NOT gated);
#      out = W_up (sum over chosen AND HELD experts g_e expert_e(t))
#            + S2 relu(S1 u)^2       (the shared expert at the full width)
#   *  attention: H query heads, Hkv KV heads, head_dim wide, causal,
#      scale head_dim^-0.5, NO rotary (the family takes its positions
#      from the Mamba layers); query head h reads KV head h // (H / Hkv).
#
# The chip's share: `held_experts` = [first, count] says which routed
# experts' weights exist here; the router still scores all
# `n_routed_experts_published`, an assignment to an expert held
# elsewhere adds nothing, W_up is applied to this chip's part of the
# sum, and the shared expert is what every chip computes alike. The
# vocabulary is the file's slice.
#
# It reads the program's parameter tree (embed, head, norm_f,
# block_<i>/{norm1, ssm/{in_proj, conv/{kernel [K, C], bias}, dt_bias,
# A_log, D, norm, out_proj} | norm2, moe/{router, router_bias,
# latent_down, w_up, w_down, latent_up, shared/{up, down}} | norm1,
# attn/{in_proj [q | k | v], out}}), so both sides compute from the same
# seeded weights. Leaves are upcast one at a time, the experts one at a
# time, attention one KV head's group of query heads at a time.
#
# `precision="bfloat16"` computes the same equations with every tensor,
# product, norm, softmax AND the recurrent state in bfloat16: the
# reading a too-low precision gives, which the cell's limits have to
# refuse. Planted faults, for the controls (keys a control lays over the
# configuration THIS FILE sees; the program never reads them):
# `conv_kernel` 1 (only the tap on the token itself: no conv),
# `fault_no_dt_input` (x not scaled by dt), `fault_state_reset_every` n
# (the state zeroed at every n-th position: a slice boundary),
# `mlp_hidden_act` "silu" (the routed experts' activation swapped).
"""Plain float32 reference of the Mamba-2 / latent-expert hybrid LM."""
import contextlib
import math

import jax
import jax.numpy as jnp

from .reference_dots import route


def _norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(x.dtype)


def _activation(name: str, x):
    return {"relu2": lambda: jnp.square(jax.nn.relu(x)),
            "silu": lambda: jax.nn.silu(x)}[name]()


def _mamba(p, u, config, dt_):
    """u [T, D] (normed) -> the mixer's output [T, D]."""
    length = u.shape[0]
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    nstate, groups = config["ssm_state_size"], config["n_groups"]
    inner = heads * dim
    width = inner + 2 * groups * nstate
    up = lambda w: jnp.asarray(w, dt_)

    zxd = u @ up(p["in_proj"]["kernel"])
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + width], zxd[
        :, inner + width:]
    kernel, taps = up(p["conv"]["kernel"]), config["conv_kernel"]
    stored = kernel.shape[0]  # tap stored - 1 multiplies the token itself
    conv = jnp.broadcast_to(up(p["conv"]["bias"]), xbc.shape)
    for back in range(taps):
        shifted = jnp.pad(xbc, ((back, 0), (0, 0)))[:length]
        conv = conv + kernel[stored - 1 - back] * shifted
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(length, heads, dim)
    b = xbc[:, inner:inner + groups * nstate].reshape(length, groups, nstate)
    c = xbc[:, inner + groups * nstate:].reshape(length, groups, nstate)
    dt = jax.nn.softplus(dt + up(p["dt_bias"]))                   # [T, H]
    decay = jnp.exp(dt * -jnp.exp(up(p["A_log"])))                # [T, H]
    written = x if config.get("fault_no_dt_input") else dt[:, :, None] * x
    reset = config.get("fault_state_reset_every", 0)
    share = heads // groups

    def token(h, inputs):
        a_t, v_t, b_t, c_t, t = inputs
        if reset:
            h = jnp.where(t % reset == 0, jnp.zeros_like(h), h)
        b_h, c_h = jnp.repeat(b_t, share, 0), jnp.repeat(c_t, share, 0)
        h = (a_t[:, None, None] * h
             + v_t[:, :, None] * b_h[:, None, :]).astype(dt_)
        return h, jnp.sum(h * c_h[:, None, :], axis=-1)           # [H, P]

    _, y = jax.lax.scan(token, jnp.zeros((heads, dim, nstate), dt_),
                        (decay, written, b, c, jnp.arange(length)))
    y = y + up(p["D"])[None, :, None] * x
    y = y.reshape(length, inner) * jax.nn.silu(z)
    y = y.reshape(length, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config["norm_eps"])
    y = y.reshape(length, inner) * up(p["norm"]["scale"])
    return y @ up(p["out_proj"]["kernel"])


def _experts(p, u, config, dt_):
    """W_up (the held experts' part of sum_k g_k expert_k(W_dn u)) +
    shared(u)."""
    first, count = config["held_experts"]
    router_dt = jnp.float32 if dt_ == jnp.float32 else dt_
    gates = route(
        u.astype(router_dt) @ jnp.asarray(p["router"]["kernel"], router_dt),
        p["router_bias"], config).astype(dt_)
    act = config["mlp_hidden_act"]
    latent = u @ jnp.asarray(p["latent_down"]["kernel"], dt_)

    def one(total, local):
        hidden = _activation(act, latent @ jnp.asarray(p["w_up"][local], dt_))
        y = hidden @ jnp.asarray(p["w_down"][local], dt_)
        gate = jax.lax.dynamic_index_in_dim(gates, first + local, axis=1)
        return total + gate * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent), jnp.arange(count))
    out = routed @ jnp.asarray(p["latent_up"]["kernel"], dt_)
    shared = p["shared"]
    hidden = _activation("relu2", u @ jnp.asarray(shared["up"]["kernel"], dt_))
    return out + hidden @ jnp.asarray(shared["down"]["kernel"], dt_)


def _attention(p, u, config, dt_):
    """u [T, D] (normed) -> attention output [T, D]; no rotary."""
    length = u.shape[0]
    heads, kv_heads = config["num_attention_heads"], config[
        "num_key_value_heads"]
    dim, group = config["head_dim"], heads // kv_heads
    qkv = u @ jnp.asarray(p["in_proj"]["kernel"], dt_)
    q = qkv[:, :heads * dim].reshape(length, kv_heads, group, dim)
    k = qkv[:, heads * dim:(heads + kv_heads) * dim].reshape(
        length, kv_heads, dim)
    v = qkv[:, (heads + kv_heads) * dim:].reshape(length, kv_heads, dim)
    seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]

    def one_kv_head(inputs):
        q_head, k_head, v_head = inputs        # [T, G, d], [T, d], [T, d]
        s = jnp.einsum("tgd,jd->gtj", q_head, k_head) / jnp.asarray(
            math.sqrt(dim), dt_)
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gtj,jd->tgd", probs, v_head)

    out = jax.lax.map(one_kv_head, (q.transpose(1, 0, 2, 3),
                                    k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))
    # [Hkv, T, G, d] -> [T, H d], head h = kv * G + g
    out = out.transpose(1, 0, 2, 3).reshape(length, heads * dim)
    return out @ jnp.asarray(p["out"]["kernel"], dt_).reshape(heads * dim, -1)


def hidden_states(params, tokens, config: dict, precision: str = "float32"):
    """tokens [B, T] int32 -> final normed hidden [B, T, D]."""
    dt_ = jnp.float32 if precision == "float32" else jnp.bfloat16
    context = (jax.default_matmul_precision("highest") if dt_ == jnp.float32
               else contextlib.nullcontext())
    eps = config["norm_eps"]
    with context:
        def one(sequence):
            x = jnp.asarray(params["embed"], dt_)[sequence]
            for layer, kind in enumerate(config["hybrid_override_pattern"]):
                p = params[f"block_{layer}"]
                if kind == "M":
                    x = x + _mamba(p["ssm"], _norm(x, p["norm1"]["scale"],
                                                   eps), config, dt_)
                elif kind == "E":
                    x = x + _experts(p["moe"], _norm(x, p["norm2"]["scale"],
                                                     eps), config, dt_)
                else:
                    x = x + _attention(p["attn"],
                                       _norm(x, p["norm1"]["scale"], eps),
                                       config, dt_)
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
