# The arithmetic of the window/full grouped-attention, routed-expert
# configuration: parameters by part, the K/V bytes a cached token costs
# by layer kind, and the operations and bytes of a decode step and of a
# prefill slice at an offset. From shapes and counts only; peaks and
# `roofline_seconds` are harness/flops.py's.
"""Parameters, bytes and FLOPs of a MiMo-V2-shaped step."""
BYTES = {"bfloat16": 2, "float32": 4}


def layer_kinds(config: dict) -> list:
    """Per layer of the configuration as run: (windowed, KV heads)."""
    return [(bool(kind), config["swa_num_key_value_heads" if kind
                                else "num_key_value_heads"])
            for kind in config["hybrid_layer_pattern"]]


def _attention_params(config: dict, kv_heads: int) -> int:
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    dk, dv = config["head_dim"], config["v_head_dim"]
    return dim * ((heads + kv_heads) * dk + kv_heads * dv) + heads * dv * dim


def parts(config: dict) -> dict:
    """Parameters by part, as held on this chip: per layer `attention`
    of a full layer and `window_attention` of a window layer (its sink's
    64 scalars left out), `dense_mlp`, `router`, one `routed_expert`;
    once `embedding` and `head` (the vocabulary slice); norm scales left
    out (under 0.1M in all)."""
    dim, width = config["hidden_size"], config["moe_intermediate_size"]
    return {
        "attention": _attention_params(config,
                                       config["num_key_value_heads"]),
        "window_attention": _attention_params(
            config, config["swa_num_key_value_heads"]),
        "dense_mlp": 3 * dim * config["intermediate_size"],
        "router": dim * config["n_routed_experts_published"],
        "routed_expert": 3 * dim * width,
        "embedding": config["vocab_size"] * dim,
        "head": config["vocab_size"] * dim,
    }


def layer_counts(config: dict) -> dict:
    """How many layers of each sort the configuration as run has."""
    kinds = layer_kinds(config)
    experts = sum(map(bool, config["moe_layer_freq"]))
    return {"full": sum(not w for w, _ in kinds),
            "window": sum(w for w, _ in kinds),
            "dense": len(kinds) - experts, "experts": experts}


def _whole(config: dict) -> int:
    """Parameters every step reads whole: attention, the dense MLP, the
    routers and the head."""
    p, n = parts(config), layer_counts(config)
    return (n["full"] * p["attention"] + n["window"] * p["window_attention"]
            + n["dense"] * p["dense_mlp"] + n["experts"] * p["router"]
            + p["head"])


def param_count(config: dict) -> int:
    """Parameters this chip holds: what every step reads whole, the
    expert layers' HELD routed experts and the embedding."""
    p, n = parts(config), layer_counts(config)
    return (_whole(config) + p["embedding"]
            + n["experts"] * config["held_experts"][1] * p["routed_expert"])


def kv_bytes_per_token(config: dict) -> dict:
    """Bytes one cached token costs as stored, over the layers of each
    kind: `full` (paged, grows with the context) and `window` (a ring:
    at most `sliding_window` rows of it are attended)."""
    row = ((config["head_dim"] + config["v_head_dim"])
           * BYTES[config["torch_dtype"]])
    out = {"full": 0, "window": 0}
    for windowed, kv_heads in layer_kinds(config):
        out["window" if windowed else "full"] += kv_heads * row
    return out


def kv_read_cost(config: dict, full_rows: float, window_rows: float
                 ) -> tuple:
    """(FLOPs, bytes) of the K/V read of `full_rows` attended rows in
    each full layer and `window_rows` in each window layer (summed over
    the slots, one query a row): per row and query head the score over
    the key width and the value over the value width, two operations a
    multiply-add; bytes as stored."""
    n, per = layer_counts(config), kv_bytes_per_token(config)
    per_row = config["num_attention_heads"] * 2.0 * (
        config["head_dim"] + config["v_head_dim"])
    return (per_row * (n["full"] * full_rows + n["window"] * window_rows),
            per["full"] * full_rows + per["window"] * window_rows)


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's three matrices."""
    return parts(config)["routed_expert"] * BYTES[config["torch_dtype"]]


def decode_step_roofline_seconds(config: dict, peak: dict, *, slots: float,
                                 full_rows: float, window_rows: float,
                                 assignments: float, experts_hit: float
                                 ) -> float:
    """The least time one decode step of `slots` tokens could take: the
    sum over its parts of max(FLOPs / peak FLOP/s, bytes / peak
    bytes/s). Parts: the weights every step reads whole (2 FLOPs a
    parameter a token), the held experts that got a token (`experts_hit`
    and `assignments` summed over the expert layers), and the K/V read
    by layer kind (`kv_read_cost`)."""
    from . import flops
    p, itemsize = parts(config), BYTES[config["torch_dtype"]]
    whole = _whole(config)
    total = flops.roofline_seconds(2.0 * whole * slots, whole * itemsize,
                                   peak)
    total += flops.roofline_seconds(
        2.0 * p["routed_expert"] * assignments,
        experts_hit * p["routed_expert"] * itemsize, peak)
    return total + flops.roofline_seconds(
        *kv_read_cost(config, full_rows, window_rows), peak)


def slice_attention_flops(config: dict, offset: int, rows: int) -> dict:
    """FLOPs of the attention of one prefill slice of `rows` queries at
    `offset`, by layer kind, all layers of the kind: a query at position
    t attends t + 1 keys in a full layer and min(t + 1, sliding_window)
    in a window layer."""
    n = layer_counts(config)
    per_pair = config["num_attention_heads"] * 2.0 * (
        config["head_dim"] + config["v_head_dim"])
    positions = range(offset, offset + rows)
    window = config["sliding_window"]
    return {"full": n["full"] * per_pair * sum(t + 1 for t in positions),
            "window": n["window"] * per_pair * sum(
                min(t + 1, window) for t in positions)}
