# The arithmetic of the latent-attention, routed-expert configuration:
# parameters by part, and the operations and bytes one decode step
# needs. From shapes and counts only; peaks and `roofline_seconds` are
# harness/flops.py's.
"""Parameters, bytes and FLOPs of a dots.vlm1-shaped decode step."""
LANES = 128
BYTES = {"bfloat16": 2, "float32": 4}


def parts(config: dict) -> dict:
    """Parameters by part, as held on this chip: per layer `attention`,
    `dense_mlp`, `router`, `shared_expert`, one `routed_expert`; once
    `embedding` and `head` (the vocabulary slice); norm scales left out
    (under 0.1M in all)."""
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    rank_q, rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    value, width = config["v_head_dim"], config["moe_intermediate_size"]
    return {
        "attention": (dim * rank_q + rank_q * heads * (nope + rope)
                      + dim * (rank + rope) + rank * heads * (nope + value)
                      + heads * value * dim),
        "dense_mlp": 3 * dim * config["intermediate_size"],
        "router": dim * config["n_routed_experts_published"],
        "shared_expert": 3 * dim * width * config["n_shared_experts"],
        "routed_expert": 3 * dim * width,
        "embedding": config["vocab_size"] * dim,
        "head": config["vocab_size"] * dim,
    }


def layer_counts(config: dict) -> tuple:
    """(dense layers, expert layers) of the configuration as run."""
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def param_count(config: dict) -> int:
    """Parameters this chip holds: every layer's attention, the dense
    layers' MLP, the expert layers' router, shared expert and HELD
    routed experts, the embedding and the untied head."""
    p, (dense, sparse) = parts(config), layer_counts(config)
    held = config["held_experts"][1]
    return ((dense + sparse) * p["attention"] + dense * p["dense_mlp"]
            + sparse * (p["router"] + p["shared_expert"]
                        + held * p["routed_expert"])
            + p["embedding"] + p["head"])


def latent_bytes_per_token_layer(config: dict) -> int:
    """Bytes one cached token costs one layer as stored: the latent and
    the rotated key, the key's lanes rounded up to whole 128s."""
    rope = -(-config["qk_rope_head_dim"] // LANES) * LANES
    return (config["kv_lora_rank"] + rope) * BYTES[config["torch_dtype"]]


def latent_read_cost(config: dict, attended_tokens: float) -> tuple:
    """(FLOPs, bytes) of ONE layer's cached-form read of
    `attended_tokens` latent rows (summed over the slots): per row and
    head the scores over rank + rope and the values over rank, two
    operations a multiply-add; bytes as stored."""
    heads = config["num_attention_heads"]
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    per_row = heads * 2.0 * ((rank + rope) + rank)
    return (attended_tokens * per_row,
            attended_tokens * latent_bytes_per_token_layer(config))


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return parts(config)["routed_expert"] * BYTES[config["torch_dtype"]]


def decode_step_roofline_seconds(config: dict, peak: dict, *, slots: float,
                                 attended_tokens: float, assignments: float,
                                 experts_hit: float) -> float:
    """The least time one decode step of `slots` tokens could take: the
    sum over its parts of max(FLOPs / peak FLOP/s, bytes / peak
    bytes/s). Parts: the weights every step reads whole (attention,
    dense MLP, routers, shared experts, head: 2 FLOPs a parameter a
    token), the held experts that got a token (`experts_hit` and
    `assignments` summed over the expert layers), the latent read of
    `attended_tokens` rows in every layer, and the 48 embedding rows
    (nothing)."""
    from . import flops
    p, (dense, sparse) = parts(config), layer_counts(config)
    itemsize = BYTES[config["torch_dtype"]]
    whole = ((dense + sparse) * p["attention"] + dense * p["dense_mlp"]
             + sparse * (p["router"] + p["shared_expert"]) + p["head"])
    total = flops.roofline_seconds(2.0 * whole * slots, whole * itemsize,
                                   peak)
    total += flops.roofline_seconds(
        2.0 * p["routed_expert"] * assignments,
        experts_hit * p["routed_expert"] * itemsize, peak)
    read = latent_read_cost(config, attended_tokens)
    total += (dense + sparse) * flops.roofline_seconds(*read, peak)
    return total
