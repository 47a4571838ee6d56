# The plain reference of the dots.vlm1 / DeepSeek-V3-shaped language
# model, written from the published equations and not from the program's
# code. float32 throughout, every product at
# `jax.default_matmul_precision("highest")`, no cache, no kernels, no
# absorbed projections, no sorting of tokens by expert.
#
# One layer (x is the residual stream; every norm an RMSNorm with a
# learned scale, eps 1e-6):
#   attention   c_q = norm(x W_qa); [q_nope | q_rope] = c_q W_qb per head;
#               [c_kv | k_rope] = x W_kva, c_kv = norm(c_kv); q_rope and
#               k_rope rotated (yarn frequencies, dimensions paired
#               (2i, 2i+1); k_rope is one vector for all heads);
#               [k_nope | v] = c_kv W_kvb per head;
#               softmax((q_nope.k_nope + q_rope.k_rope) * d_qk^-0.5 * m^2),
#               m = 0.1 * mscale_all_dim * ln(factor) + 1, causal;
#               heads' outputs through W_o.
#   dense MLP   W_down(silu(W_gate x) * W_up x)       (leading layers)
#   experts     s = sigmoid(x W_r); choice = s + b; groups of equal
#               size scored by the sum of their two best choices; the
#               topk_group best groups stay; the num_experts_per_tok
#               best choices among them win; gates = s at the winners /
#               their sum * routed_scaling_factor;
#               y = shared(x) + sum_k gate_k expert_k(x).
# The chip's share: the file's `held_experts` = [first, count] says
# which routed experts' weights exist here; the router still scores all
# `n_routed_experts_published`, and an assignment to an expert held
# elsewhere adds nothing (its chip would add it). The vocabulary is the
# file's slice.
#
# It reads the program's parameter tree (embed, head, norm_f,
# block_<i>/{norm1, attn/{q_a,q_norm,q_b,kv_a,kv_norm,kv_b,out}, norm2,
# mlp/{up,down} | moe/{router,router_bias,w_up,w_down,shared/{up,down}}})
# so both sides compute from the same seeded weights; `up` kernels hold
# [gate | value]. Leaves are upcast one at a time, a wide hidden layer
# goes through in column blocks and attention a few heads at a time, so
# that a 4,608-token sequence fits beside the serving engine.
#
# `precision="bfloat16"` computes the same equations with every tensor,
# product and softmax in bfloat16: the reading a too-low precision
# gives, which the cell's margin has to refuse (PERF.md).
"""Plain float32 reference of the latent-attention, routed-expert LM."""
import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
HEADS_AT_A_TIME = 2
HIDDEN_BLOCK = 2304


def _norm(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS)
    return x * scale.astype(x.dtype)


def yarn_frequencies(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """dim // 2 frequencies: theta^(-2i/dim), divided by `factor` where
    the dimension turns fewer than beta_slow times over the original
    length, kept where it turns more than beta_fast times, blended
    linearly between."""
    factor, original = scaling["factor"], scaling[
        "original_max_position_embeddings"]

    def turns_to_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_to_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(scaling["beta_slow"])), dim - 1)
    freqs = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / ((high - low) or 1e-3), 0.0), 1.0)
        freqs.append(plain * (1.0 - ramp) + plain / factor * ramp)
    return np.asarray(freqs, np.float32)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotate_pairs(x, positions, freqs, scale):
    """x [..., T, D] with pairs (2i, 2i+1) rotated by pos * freqs[i]."""
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(angles) * scale).astype(x.dtype)
    sin = (jnp.sin(angles) * scale).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _attention(p, x, config, dt):
    """x [T, D] -> attention output [T, D], causal, a few heads at a
    time."""
    length = x.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank = config["kv_lora_rank"]
    scaling = config["rope_scaling"]
    freqs = jnp.asarray(yarn_frequencies(rope, float(config["rope_theta"]),
                                         scaling))
    m_all = _mscale(scaling["factor"], scaling["mscale_all_dim"])
    cos_sin = _mscale(scaling["factor"], scaling["mscale"]) / m_all
    positions = jnp.arange(length)
    up = lambda w: jnp.asarray(w, dt)

    c_q = _norm(x @ up(p["q_a"]["kernel"]), p["q_norm"]["scale"])
    kv = x @ up(p["kv_a"]["kernel"])
    c_kv = _norm(kv[:, :rank], p["kv_norm"]["scale"])
    k_rope = _rotate_pairs(kv[:, rank:], positions, freqs, cos_sin)  # [T, r]
    causal = positions[None, :] <= positions[:, None]
    softmax_scale = (nope + rope) ** -0.5 * m_all * m_all

    def some_heads(weights):
        w_qb, w_kvb, w_o = weights          # [rank_q,h,qk] [rank,h,n+v] [h,v,D]
        q = jnp.einsum("tr,rhk->htk", c_q, up(w_qb))
        q_nope = q[..., :nope]
        q_rope = _rotate_pairs(q[..., nope:], positions, freqs, cos_sin)
        kv_heads = jnp.einsum("tr,rhk->htk", c_kv, up(w_kvb))
        k_nope, value = kv_heads[..., :nope], kv_heads[..., nope:]
        scores = (jnp.einsum("htk,hsk->hts", q_nope, k_nope)
                  + jnp.einsum("htk,sk->hts", q_rope, k_rope))
        scores = jnp.where(causal[None], scores * softmax_scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hts,hsv->htv", probs, value)
        return jnp.einsum("htv,hvd->td", out, up(w_o))

    group = math.gcd(heads, HEADS_AT_A_TIME)
    split = lambda w, axis: jnp.moveaxis(
        w.reshape(w.shape[:axis] + (heads // group, group)
                  + w.shape[axis + 1:]), axis, 0)
    # a running sum: the groups' [T, D] parts are never held side by side
    out, _ = jax.lax.scan(
        lambda total, weights: (total + some_heads(weights), None),
        jnp.zeros_like(x), (split(p["q_b"]["kernel"], 1),
                            split(p["kv_b"]["kernel"], 1),
                            split(p["out"]["kernel"], 0)))
    return out


def _gated_mlp(p, x, dt):
    """W_down(silu(W_gate x) * W_up x); `up` holds [gate | value]. The
    hidden width goes through in column blocks."""
    w_up, w_down = p["up"]["kernel"], p["down"]["kernel"]
    hidden = w_down.shape[0]
    block = math.gcd(hidden, HIDDEN_BLOCK)
    count = hidden // block

    def one(index):
        at = index * block
        gate = x @ jnp.asarray(jax.lax.dynamic_slice_in_dim(
            w_up, at, block, 1), dt)
        value = x @ jnp.asarray(jax.lax.dynamic_slice_in_dim(
            w_up, hidden + at, block, 1), dt)
        return (jax.nn.silu(gate) * value) @ jnp.asarray(
            jax.lax.dynamic_slice_in_dim(w_down, at, block, 0), dt)

    out, _ = jax.lax.scan(lambda total, index: (total + one(index), None),
                          jnp.zeros_like(x), jnp.arange(count))
    return out


def route(logits, bias, config):
    """logits [T, E] -> gate matrix [T, E]: zero but at the chosen
    experts. Best-first by a stable sort: ties go to the lower index."""
    tokens, experts = logits.shape
    groups, kept = config["n_group"], config["topk_group"]
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(scores.dtype)
    per_group = choice.reshape(tokens, groups, experts // groups)
    best_two = -jnp.sort(-per_group, axis=-1)[..., :2]
    group_score = jnp.sum(best_two, axis=-1)                   # [T, G]
    group_rank = jnp.argsort(jnp.argsort(-group_score, axis=-1, stable=True),
                             axis=-1, stable=True)
    open_group = jnp.repeat(group_rank < kept, experts // groups, axis=-1)
    allowed = jnp.where(open_group, choice, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-allowed, axis=-1, stable=True),
                       axis=-1, stable=True)
    chosen = rank < top_k
    gates = jnp.where(chosen, scores, 0.0)
    if config.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * config["routed_scaling_factor"]


def _expert_layer(p, x, config, dt):
    """shared(x) + the held experts' part of sum_k gate_k expert_k(x)."""
    first, count = config["held_experts"]
    router_dt = jnp.float32 if dt == jnp.float32 else dt
    gates = route(x.astype(router_dt) @ jnp.asarray(p["router"]["kernel"],
                                                    router_dt),
                  p["router_bias"], config).astype(dt)
    out = (_gated_mlp(p["shared"], x, dt) if config["n_shared_experts"]
           else jnp.zeros_like(x))
    width = p["w_down"].shape[1]
    for local in range(count):  # every token through every held expert
        w_up = jnp.asarray(p["w_up"][local], dt)
        hidden = x @ w_up
        y = (jax.nn.silu(hidden[:, :width]) * hidden[:, width:]) @ jnp.asarray(
            p["w_down"][local], dt)
        out = out + gates[:, first + local, None] * y
    return out


def hidden_states(params, tokens, config: dict, precision: str = "float32"):
    """tokens [B, T] int32 -> final normed hidden [B, T, D]."""
    dt = jnp.float32 if precision == "float32" else jnp.bfloat16
    context = (jax.default_matmul_precision("highest") if dt == jnp.float32
               else contextlib.nullcontext())
    with context:
        def one(sequence):
            x = jnp.asarray(params["embed"], dt)[sequence]
            for layer in range(config["num_hidden_layers"]):
                p = params[f"block_{layer}"]
                x = x + _attention(p["attn"], _norm(x, p["norm1"]["scale"]),
                                   config, dt)
                h = _norm(x, p["norm2"]["scale"])
                if layer < config["first_k_dense_replace"]:
                    x = x + _gated_mlp(p["mlp"], h, dt)
                else:
                    x = x + _expert_layer(p["moe"], h, config, dt)
            return _norm(x, params["norm_f"]["scale"])

        return jnp.stack([one(sequence) for sequence in tokens])


def logits(params, tokens, config: dict, precision: str = "float32"):
    """tokens [B, T] -> logits [B, T, V] over the vocabulary slice
    (untied head)."""
    return _head(params, hidden_states(params, tokens, config, precision),
                 precision)


def _head(params, hidden, precision):
    if precision != "float32":
        return (hidden @ jnp.asarray(params["head"], hidden.dtype).T).astype(
            jnp.float32)
    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["head"], jnp.float32).T


def logits_at(params, tokens, positions, config: dict,
              precision: str = "float32"):
    """For one sequence tokens [1, T] (prompt + served output, padded):
    float32 logits [G, V] at `positions` [G], the model computed in
    `precision` ("bfloat16": every tensor, product, norm, softmax and
    the head in bfloat16, the reading of a too-low precision)."""
    hidden = hidden_states(params, tokens, config, precision)[0][positions]
    return _head(params, hidden, precision)
