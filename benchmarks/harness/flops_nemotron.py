# The arithmetic of the Mamba-2 / latent-expert / attention hybrid
# configuration: parameters by part, the bytes a slot's recurrent state
# and a cached token cost, and the operations and bytes of the state
# update, the chunked scan, the latent expert stream, the K/V read and
# the whole decode step. From shapes and counts only; peaks and
# `roofline_seconds` are harness/flops.py's.
#
# The functions a reader asks for by the names below are the family's
# interface (readers/ssm_spans.py finds this module through the
# configuration file's `harness.flops`): `expert_bytes`, `kv_read_cost`,
# `kv_row_bytes`, `state_update_cost`, `chunked_scan_cost`,
# `decode_step_roofline_seconds`.
"""Parameters, bytes and FLOPs of a nemotron_h-shaped step."""
BYTES = {"bfloat16": 2, "float32": 4}
STATE_BYTES = 4  # the recurrent state is float32 whatever the weights


def mamba_dims(config: dict) -> dict:
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner = heads * dim
    return {"heads": heads, "dim": dim, "groups": groups, "state": state,
            "inner": inner, "conv": inner + 2 * groups * state,
            "taps": config["conv_kernel"]}


def layer_counts(config: dict) -> dict:
    """How many layers of each kind the configuration as run has."""
    pattern = config["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "experts": pattern.count("E"),
            "attention": pattern.count("*")}


def parts(config: dict) -> dict:
    """Parameters by part, as held on this chip: per layer `mamba` (in
    and out projection, conv taps and bias, dt_bias, A_log, D, the gated
    norm's scale), `attention`, `expert_shell` (router and its bias, the
    two latent projections, the shared expert), one `routed_expert`;
    once `embedding` and `head` (the vocabulary slice); the layers' own
    norm scales (4,096 each) left out."""
    dim, m = config["hidden_size"], mamba_dims(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    latent, width = config["moe_latent_size"], config["moe_intermediate_size"]
    routed = config["n_routed_experts_published"]
    return {
        "mamba": (dim * (m["inner"] + m["conv"] + m["heads"])
                  + (m["taps"] + 1) * m["conv"] + 3 * m["heads"]
                  + m["inner"] + m["inner"] * dim),
        "attention": (dim * (heads + 2 * kv) * config["head_dim"]
                      + heads * config["head_dim"] * dim),
        "expert_shell": (dim * routed + routed + 2 * dim * latent + 2 * dim
                         * config["moe_shared_expert_intermediate_size"]),
        "routed_expert": 2 * latent * width,
        "embedding": config["vocab_size"] * dim,
        "head": config["vocab_size"] * dim,
    }


def _whole(config: dict) -> int:
    """Parameters every step reads whole: the Mamba mixers, the
    attention, the experts' shell and the head."""
    p, n = parts(config), layer_counts(config)
    return (n["mamba"] * p["mamba"] + n["attention"] * p["attention"]
            + n["experts"] * p["expert_shell"] + p["head"])


def param_count(config: dict) -> int:
    """Parameters this chip holds: what every step reads whole, the
    expert layers' HELD routed experts and the embedding."""
    p, n = parts(config), layer_counts(config)
    return (_whole(config) + p["embedding"]
            + n["experts"] * config["held_experts"][1] * p["routed_expert"])


def state_row_bytes(config: dict) -> int:
    """Bytes of recurrent state and conv tail ONE slot holds over all
    Mamba layers: [H, P, N] float32 and (taps - 1) rows of the convolved
    stream in the weights' dtype, a layer."""
    m = mamba_dims(config)
    return layer_counts(config)["mamba"] * (
        m["heads"] * m["dim"] * m["state"] * STATE_BYTES
        + (m["taps"] - 1) * m["conv"] * BYTES[config["torch_dtype"]])


def state_update_cost(config: dict, state_bytes: float) -> tuple:
    """(FLOPs, bytes) of one decode step's state update over all Mamba
    layers, given the `ssm_state_bytes` the step read and wrote for the
    rows that advanced (2 x rows x `state_row_bytes`): per state element
    a decay, a write (v x b, added) and its share of y = h . c, five
    operations; the conv tail's operations are nothing beside them."""
    elements = state_bytes / 2.0 / STATE_BYTES
    return 5.0 * elements, state_bytes


def chunked_scan_cost(config: dict, tokens: int) -> tuple:
    """(FLOPs, bytes) of the chunked scan of ONE Mamba layer over a
    slice of `tokens` tokens at the published block `chunk_size`: per
    head and chunk of C tokens the scores (2 C C N), their values
    (2 C C P), the carried state's part of y and the state's update
    (2 C N P each); bytes: x and y (float32 as the program hands them
    over) and the log decay a head, B and C a group (read once: the
    heads of a group share them), the state in and out."""
    m, chunk = mamba_dims(config), config["chunk_size"]
    chunks = -(-tokens // chunk)
    per_chunk = (2.0 * chunk * chunk * (m["state"] + m["dim"])
                 + 4.0 * chunk * m["state"] * m["dim"])
    item = BYTES[config["torch_dtype"]]
    nbytes = (tokens * m["heads"] * (2 * m["dim"] * 4 + 4)
              + tokens * 2 * m["groups"] * m["state"] * item
              + 2 * m["heads"] * m["dim"] * m["state"] * STATE_BYTES)
    return m["heads"] * chunks * per_chunk, nbytes


def kv_row_bytes(config: dict) -> int:
    """Bytes one cached token costs as stored, over the attention
    layers: K and V of every KV head."""
    return (layer_counts(config)["attention"] * 2
            * config["num_key_value_heads"] * config["head_dim"]
            * BYTES[config["torch_dtype"]])


def kv_read_cost(config: dict, rows: float) -> tuple:
    """(FLOPs, bytes) of the K/V read of `rows` attended rows in each
    attention layer (summed over the slots, one query a row): per row
    and query head the score and the value over head_dim, two operations
    a multiply-add; bytes as stored."""
    per_row = config["num_attention_heads"] * 4.0 * config["head_dim"]
    return (layer_counts(config)["attention"] * per_row * rows,
            kv_row_bytes(config) * rows)


def expert_bytes(config: dict) -> int:
    """Bytes of one routed expert's two matrices (latent -> width ->
    latent)."""
    return parts(config)["routed_expert"] * BYTES[config["torch_dtype"]]


def decode_step_roofline_seconds(config: dict, peak: dict, *, slots: float,
                                 kv_rows: float, state_bytes: float,
                                 assignments: float, experts_hit: float
                                 ) -> float:
    """The least time one decode step of `slots` tokens could take: the
    sum over its parts of max(FLOPs / peak FLOP/s, bytes / peak
    bytes/s). Parts: the weights every step reads whole (2 FLOPs a
    parameter a token), the held experts that got a token (`experts_hit`
    and `assignments` summed over the expert layers), the recurrent
    state read and written (`state_update_cost`) and the K/V read
    (`kv_read_cost`)."""
    from . import flops
    p, itemsize = parts(config), BYTES[config["torch_dtype"]]
    whole = _whole(config)
    total = flops.roofline_seconds(2.0 * whole * slots, whole * itemsize,
                                   peak)
    total += flops.roofline_seconds(
        2.0 * p["routed_expert"] * assignments,
        experts_hit * p["routed_expert"] * itemsize, peak)
    total += flops.roofline_seconds(
        *state_update_cost(config, state_bytes), peak)
    return total + flops.roofline_seconds(*kv_read_cost(config, kv_rows),
                                          peak)
