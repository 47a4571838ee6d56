# Readers of what the window/full grouped-attention path writes into the
# device trace: the scopes attn/window and attn/global (and router,
# experts) of the decode executable, the `kv_bytes` / `kv_bytes_window`
# stats of the serve/decode spans (bytes as stored that the step's reads
# attend, all layers | the window layers' part) and the
# `moe_assignments` / `moe_experts_hit` stats of their serve/decode/moe
# children. Found by name through readers/program_spans.py's reduction;
# the arithmetic is harness/flops_mimo.py's. A program without these
# scopes or stats (an earlier commit, another family) gives every reader
# nothing to read: it returns None and the metric is left out of the
# line.
"""Per-layer metrics of the two K/V reads and the expert stream."""
import statistics

from ..harness import flops, flops_mimo
from . import program_spans

MODULE = "decode_paged"
GROUPS = {"window": ("window",), "global": ("global",), "attn": ("attn",),
          "experts": ("experts",)}


def _scopes(run: dict) -> dict:
    trace = program_spans.program_trace(run)
    if not trace:
        return {}
    return program_spans.scope_ms_per_run(trace, MODULE, GROUPS)


def _attn_ms(run: dict):
    """Device ms per decode run under `attn`, its children included."""
    by_scope = _scopes(run)
    found = [by_scope[scope] for scope in ("window", "global", "attn")
             if scope in by_scope]
    return sum(found) if found else None


def _decode_spans(trace: dict) -> list:
    """The traced decode steps that say what their reads attended by
    layer kind."""
    return [s for s in trace["spans"] if s.name == "serve/decode"
            and "kv_bytes_window" in s.stats]


def _decode_means(run: dict):
    """Means over the traced window's decode steps of: the slots that
    emitted a token, the rows a full layer's and a window layer's read
    attended (summed over the slots), the routed assignments that
    landed on held experts and the held experts that got one (both
    summed over the expert layers). None where the program wrote no
    such stats."""
    trace = program_spans.program_trace(run)
    if not trace:
        return None
    decode = _decode_spans(trace)
    moe = [s for s in trace["spans"] if s.name == "serve/decode/moe"]
    if not decode or not moe:
        return None
    per = flops_mimo.kv_bytes_per_token(run["config"])
    mean = lambda spans, key: statistics.fmean(
        float(s.stats[key]) for s in spans)
    total, window = mean(decode, "kv_bytes"), mean(decode, "kv_bytes_window")
    return {"slots": mean(decode, "running"),
            "full_rows": (total - window) / per["full"],
            "window_rows": window / per["window"],
            "assignments": mean(moe, "moe_assignments"),
            "experts_hit": mean(moe, "moe_experts_hit")}


def decode_device_ms(run: dict, scope: str):
    """Device ms per decode run under `attn/window` or `attn/global`
    (scope 'window' | 'global')."""
    return _scopes(run).get(scope)


def kv_read_roofline_pct(run: dict):
    """The K/V read of the rows the live slots attend, by layer kind:
    max(score and value FLOPs over the bf16 peak, bytes as stored over
    the HBM peak), against the device time under `attn` per decode run —
    the same work whichever read serves it."""
    means, took = _decode_means(run), _attn_ms(run)
    if not means or not took or not run.get("peak"):
        return None
    cost = flops_mimo.kv_read_cost(run["config"], means["full_rows"],
                                   means["window_rows"])
    return 100.0 * flops.roofline_seconds(*cost, run["peak"]) / (took * 1e-3)


def kv_window_share_pct(run: dict):
    """`kv_bytes_window / kv_bytes` over the traced decode steps: the
    window layers' share of the K/V bytes a step attends. Small while
    the window is held in the cache and in the read."""
    trace = program_spans.program_trace(run)
    shares = [float(s.stats["kv_bytes_window"]) / float(s.stats["kv_bytes"])
              for s in (_decode_spans(trace) if trace else ())
              if float(s.stats["kv_bytes"])]
    return 100.0 * statistics.fmean(shares) if shares else None


def expert_stream_roofline_pct(run: dict):
    """Bytes of the held experts that had a token in a decode step
    (summed over the expert layers) over the HBM peak, against the
    device time under `experts` per decode run."""
    means, took = _decode_means(run), _scopes(run).get("experts")
    if not means or not took or not run.get("peak"):
        return None
    nbytes = means["experts_hit"] * flops_mimo.expert_bytes(run["config"])
    return 100.0 * flops.roofline_seconds(0.0, nbytes, run["peak"]) / (
        took * 1e-3)


def moe_tokens_per_expert(run: dict):
    """Assignments that landed on held experts over held experts that
    got one, over the traced decode steps."""
    means = _decode_means(run)
    if not means or not means["experts_hit"]:
        return None
    return means["assignments"] / means["experts_hit"]


def decode_step_mfu_pct(run: dict):
    """The least time the whole decode step could take (every part's
    max of FLOPs and bytes over the peaks: flops_mimo.
    decode_step_roofline_seconds) over the median device time of a
    decode run."""
    means, trace = _decode_means(run), run.get("trace")
    if not means or not trace or not run.get("peak"):
        return None
    runs = [d for name, durations in trace["modules"].items()
            if MODULE in name for d in durations]
    if not runs:
        return None
    least = flops_mimo.decode_step_roofline_seconds(
        run["config"], run["peak"], **means)
    return 100.0 * least / statistics.median(runs)
