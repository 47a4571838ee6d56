# The benchmark's own arithmetic: operations and bytes computed from
# shapes, and the table of peaks. Later PRs cannot change these, so a
# utilization or a roofline share means the same thing in every PR.
"""FLOPs, bytes and peaks: the yardstick for MFU and roofline shares."""
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown device raises."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}: add a row with its source")
    return table[device_kind]


def lm_param_count(config: dict) -> int:
    """Parameters of the decoder the config file describes (tied head,
    no biases, gated MLP, one learned norm scale per norm)."""
    dim, layers = config["hidden_size"], config["num_hidden_layers"]
    per_layer = (4 * dim * dim                        # qkv + out
                 + 3 * dim * config["intermediate_size"]  # gate, up, down
                 + 2 * dim)                           # two norm scales
    return config["vocab_size"] * dim + layers * per_layer + dim


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """6P + 6LTD (bench.py `_measure_lm_config`'s arithmetic): forward
    and backward matmuls over every parameter, plus causal attention's
    score and value products. Recomputation is not counted."""
    return (6.0 * lm_param_count(config)
            + 6.0 * config["num_hidden_layers"] * seq_len
            * config["hidden_size"])


def flash_attention_cost(batch_heads: int, seq_len: int, head_dim: int,
                         backward: bool, causal: bool = True,
                         itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one flash-attention call needs. Forward: QK^T and
    PV, 4*T*T*D per head, halved under the causal mask. Backward: the
    flash algorithm's five products (scores again, dV, dP, dQ, dK),
    2.5 times the forward. Bytes: q, k, v read and o written once
    (backward: q, k, v, o, do read, dq, dk, dv written)."""
    forward = 4.0 * batch_heads * seq_len * seq_len * head_dim
    if causal:
        forward *= 0.5
    tensor = batch_heads * seq_len * head_dim * itemsize
    if backward:
        return 2.5 * forward, 8.0 * tensor
    return forward, 4.0 * tensor


def kv_bytes_per_token_layer(config: dict, kv_dtype: str) -> int:
    """Bytes one cached token costs one layer's paged-decode read: K and
    V rows over every head, and for int8 pools one f32 scale per row and
    head for each."""
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    if kv_dtype == "int8":
        return 2 * heads * head_dim + 2 * heads * 4
    return 2 * heads * head_dim * 2


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
