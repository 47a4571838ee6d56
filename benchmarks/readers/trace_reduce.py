# Readers of the reduced device trace (harness/trace.py `reduce_profile`).
# Without a trace (`--trace 0`, or a profile with no device plane) every
# reader returns None.
"""Per-layer metric readers over the reduced device trace."""
import statistics

from ..harness import flops
from ..harness.trace import op_shapes


def idle_pct(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def module_median_ms(run: dict, module: str):
    """Median device time of one run of the executable whose module name
    contains `module` (line 'XLA Modules')."""
    trace = run.get("trace")
    if not trace:
        return None
    runs = [d for name, durations in trace["modules"].items()
            if module in name for d in durations]
    return 1e3 * statistics.median(runs) if runs else None


def flash_roofline_pct(run: dict):
    """Flash forward and backward custom calls: the time the chip needs
    at least for each call's operations and bytes (harness/flops.py,
    from the call's own shapes) over the time the calls took. The train
    step's only custom calls are the flash kernels; a forward call takes
    three [B*H, T, D] operands (q, k, v), a backward call more."""
    trace = run.get("trace")
    if not trace:
        return None
    host, least, took = run["host"], 0.0, 0.0
    want = f"[{host['batch_heads']},{host['seq_len']},{host['head_dim']}]"
    for text, seconds in trace["op_events"]:
        _, operands = op_shapes(text)
        tensors = [s for s in operands if s.endswith(want)]
        if len(tensors) < 3:
            continue
        cost = flops.flash_attention_cost(
            host["batch_heads"], host["seq_len"], host["head_dim"],
            backward=len(tensors) > 3)
        least += flops.roofline_seconds(*cost, run["peak"])
        took += seconds
    return 100.0 * least / took if took else None


def paged_decode_roofline_pct(run: dict):
    """Paged-decode custom calls of the decode step (one query row per
    slot): bytes of K, V and scales the cached tokens cost per call and
    layer, at the contexts the host saw during the traced steps, over
    the HBM peak, against the time the calls took."""
    trace = run.get("trace")
    if not trace:
        return None
    host, config = run["host"], run["config"]
    heads = config["num_attention_heads"]
    query = f"[{host['slots']},1,{heads},{config['hidden_size'] // heads}]"
    times = [seconds for text, seconds in trace["op_events"]
             if any(s.endswith(query) for s in op_shapes(text)[1])]
    since = host.get("trace_started_at") or 0.0
    contexts = [tick.context for tick in host["ticks"]
                if tick.begin >= since and tick.emitted > 0]
    if not times or not contexts:
        return None
    nbytes = statistics.fmean(contexts) * flops.kv_bytes_per_token_layer(
        config, host["kv_dtype"])
    least = flops.roofline_seconds(0.0, nbytes, run["peak"])
    return 100.0 * least / statistics.fmean(times)
