# Readers of what the recurrent (Mamba-2) layers and the latent expert
# layer write into the device trace: the scope `ssm` and its children
# in_proj, conv, scan, gate_norm, out_proj, the scopes latent_down and
# latent_up beside router / experts / shared_expert, the kernel
# `ssd_scan_fused` of a prefill slice, the `ssm_state_bytes` and
# `kv_bytes` stats of the serve/decode spans and the `moe_assignments` /
# `moe_experts_hit` stats of their serve/decode/moe children. Found by
# name through readers/program_spans.py's reduction.
#
# The arithmetic is NOT imported here: it is the module the run's
# configuration file names under `harness.flops` (for this family
# harness/flops_nemotron.py, whose header lists the functions a reader
# may ask for), so that one reader serves every family that brings them.
# A program without these scopes or stats (an earlier commit), or a
# family whose arithmetic lacks a function, gives a reader nothing to
# read: it returns None and the metric is left out of the line.
"""Per-layer metrics of the recurrent state, the chunked scan, the
latent projections and the expert stream."""
import importlib
import statistics

from ..harness import flops
from ..harness.trace import short_op_name
from . import program_spans

DECODE, SLICE = "decode_paged", "chunk_paged"
SCAN_KERNEL = "ssd_scan_fused"


def _arithmetic(run: dict, *needs: str):
    """The configuration's own flops module, if it has every function
    in `needs`."""
    name = (run.get("config") or {}).get("harness", {}).get("flops")
    if not name:
        return None
    module = importlib.import_module(f"benchmarks.harness.{name}")
    return module if all(hasattr(module, need) for need in needs) else None


def device_ms(run: dict, module: str, scopes, under=()):
    """Device ms per run of the executable `module` in ops whose scope
    path has one of `scopes` (and every part of `under`). None where no
    op has."""
    trace = program_spans.program_trace(run)
    if not trace:
        return None
    total, runs, found = 0.0, 0, False
    for count, ops in program_spans.ops_of_runs(trace, module):
        runs += count
        for start, end, event in ops:
            parts = set(program_spans.scope_path(event))
            if parts.intersection(scopes) and parts.issuperset(under):
                total, found = total + end - start, True
    return total * 1e-6 / runs if found and runs else None


def _decode_means(run: dict):
    """Means over the traced window's decode steps of: the slots that
    emitted a token, the bytes of recurrent state they read and wrote,
    the bytes of K/V they attended, the routed assignments that landed
    on held experts and the held experts that got one (both summed over
    the expert layers). None where the program wrote no such stats."""
    trace = program_spans.program_trace(run)
    if not trace:
        return None
    decode = [s for s in trace["spans"] if s.name == "serve/decode"
              and "ssm_state_bytes" in s.stats]
    moe = [s for s in trace["spans"] if s.name == "serve/decode/moe"]
    if not decode or not moe:
        return None
    mean = lambda spans, key: statistics.fmean(
        float(s.stats.get(key, 0)) for s in spans)
    return {"slots": mean(decode, "running"),
            "state_bytes": mean(decode, "ssm_state_bytes"),
            "kv_bytes": mean(decode, "kv_bytes"),
            "assignments": mean(moe, "moe_assignments"),
            "experts_hit": mean(moe, "moe_experts_hit")}


def _share(least_s, took_ms):
    return 100.0 * least_s / (took_ms * 1e-3)


def state_roofline_pct(run: dict):
    """The recurrent state the advancing rows read and write
    (`ssm_state_bytes`, with the update's operations: bytes bind) over
    the peaks, against the device time under `ssm/scan` per decode run:
    the same work whatever implements the update."""
    lib = _arithmetic(run, "state_update_cost")
    means = _decode_means(run)
    took = device_ms(run, DECODE, ("scan",), under=("ssm",))
    if not lib or not means or not took or not run.get("peak"):
        return None
    cost = lib.state_update_cost(run["config"], means["state_bytes"])
    return _share(flops.roofline_seconds(*cost, run["peak"]), took)


def scan_kernel_roofline_pct(run: dict):
    """One layer's chunked scan of a whole slice, max(FLOPs over the
    bf16 peak, bytes over the HBM peak), against the mean device time of
    one `ssd_scan_fused` kernel in the prefill slices' runs. None where
    the slice ran no such kernel."""
    lib = _arithmetic(run, "chunked_scan_cost")
    trace = program_spans.program_trace(run)
    if not lib or not trace or not run.get("peak"):
        return None
    took = [end - start
            for _, ops in program_spans.ops_of_runs(trace, SLICE)
            for start, end, event in ops
            if short_op_name(event.name).startswith(SCAN_KERNEL)]
    sizes = [int(s.stats["size"]) for s in trace["spans"]
             if s.name == "serve/prefill_chunk" and "size" in s.stats]
    if not took or not sizes:
        return None
    cost = lib.chunked_scan_cost(run["config"], max(sizes))
    return _share(flops.roofline_seconds(*cost, run["peak"]),
                  statistics.fmean(took) * 1e-6)


def kv_read_roofline_pct(run: dict):
    """The K/V read of the rows the live slots attend in the attention
    layers, max(FLOPs over the bf16 peak, `kv_bytes` over the HBM
    peak), against the device time under `attn` per decode run."""
    lib = _arithmetic(run, "kv_read_cost", "kv_row_bytes")
    means, took = _decode_means(run), device_ms(run, DECODE, ("attn",))
    if not lib or not means or not took or not run.get("peak"):
        return None
    rows = means["kv_bytes"] / lib.kv_row_bytes(run["config"])
    cost = lib.kv_read_cost(run["config"], rows)
    return _share(flops.roofline_seconds(*cost, run["peak"]), took)


def expert_stream_roofline_pct(run: dict):
    """Bytes of the held experts that had a token in a decode step
    (summed over the expert layers) over the HBM peak, against the
    device time under `experts` per decode run."""
    lib = _arithmetic(run, "expert_bytes")
    means, took = _decode_means(run), device_ms(run, DECODE, ("experts",))
    if not lib or not means or not took or not run.get("peak"):
        return None
    nbytes = means["experts_hit"] * lib.expert_bytes(run["config"])
    return _share(flops.roofline_seconds(0.0, nbytes, run["peak"]), took)


def moe_tokens_per_expert(run: dict):
    """Assignments that landed on held experts over held experts that
    got one, over the traced decode steps."""
    means = _decode_means(run)
    if not means or not means["experts_hit"]:
        return None
    return means["assignments"] / means["experts_hit"]


def decode_step_mfu_pct(run: dict):
    """The least time the whole decode step could take (every part's
    max of FLOPs and bytes over the peaks: the family's
    `decode_step_roofline_seconds`: weights read whole, experts hit,
    state read and written, K/V attended) over the median device time of
    a decode run."""
    lib = _arithmetic(run, "decode_step_roofline_seconds", "kv_row_bytes")
    means, trace = _decode_means(run), run.get("trace")
    if not lib or not means or not trace or not run.get("peak"):
        return None
    runs = [d for name, durations in trace["modules"].items()
            if DECODE in name for d in durations]
    if not runs:
        return None
    least = lib.decode_step_roofline_seconds(
        run["config"], run["peak"], slots=means["slots"],
        kv_rows=means["kv_bytes"] / lib.kv_row_bytes(run["config"]),
        state_bytes=means["state_bytes"], assignments=means["assignments"],
        experts_hit=means["experts_hit"])
    return 100.0 * least / statistics.median(runs)
