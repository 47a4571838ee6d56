# Readers of what the latent-attention, routed-expert path writes into
# the device trace: the scopes mla_q, mla_kv, kv_write, attn, mla_out
# and router, experts, shared_expert of the decode executable (and of
# the prefill slice's, `chunk_paged`), the
# `kv_bytes` stat of the serve/decode spans and the `moe_assignments` /
# `moe_experts_hit` stats of their serve/decode/moe children. Found by
# name through readers/program_spans.py's reduction; the arithmetic is
# harness/flops_dots.py's. A program without these scopes or stats (an
# earlier commit, another family) gives every reader nothing to read:
# it returns None and the metric is left out of the line.
"""Per-layer metrics of the latent read and the expert stream."""
import statistics

from ..harness import flops, flops_dots
from . import program_spans

MODULE = "decode_paged"
# the copies XLA puts around the pool's scatter are named after the
# argument they copy, `cache[...]`, as program_spans.DECODE_GROUPS has it
GROUPS = {"mla_q": ("mla_q",), "mla_kv": ("mla_kv",),
          "kv_write": ("kv_write", "cache"), "attn": ("attn",),
          "mla_out": ("mla_out",), "router": ("router",),
          "experts": ("experts",), "shared_expert": ("shared_expert",)}
SUMS = {"mla": ("mla_q", "mla_kv", "kv_write", "attn", "mla_out"),
        "experts": ("router", "experts", "shared_expert")}


def _scopes(run: dict, module: str = MODULE) -> dict:
    trace = program_spans.program_trace(run)
    if not trace:
        return {}
    return program_spans.scope_ms_per_run(trace, module, GROUPS)


def _decode_means(run: dict):
    """Means over the traced window's decode steps of: the slots that
    emitted a token, the latent rows one layer's read attended, the
    routed assignments that landed on held experts and the held experts
    that got one (both summed over the expert layers). None where the
    program wrote no such stats."""
    trace = program_spans.program_trace(run)
    if not trace:
        return None
    config = run["config"]
    row_bytes = (flops_dots.latent_bytes_per_token_layer(config)
                 * config["num_hidden_layers"])
    decode = [s for s in trace["spans"] if s.name == "serve/decode"
              and "kv_bytes" in s.stats]
    moe = [s for s in trace["spans"] if s.name == "serve/decode/moe"]
    if not decode or not moe:
        return None
    mean = lambda spans, key: statistics.fmean(
        float(s.stats[key]) for s in spans)
    return {"slots": mean(decode, "running"),
            "attended_tokens": mean(decode, "kv_bytes") / row_bytes,
            "assignments": mean(moe, "moe_assignments"),
            "experts_hit": mean(moe, "moe_experts_hit")}


def decode_device_ms(run: dict, part: str):
    """Device ms per decode run under the scopes of `part`: 'mla' (the
    five latent scopes) or 'experts' (router, experts, shared_expert)."""
    by_scope = _scopes(run)
    if not any(scope in by_scope for scope in SUMS[part]):
        return None
    return sum(by_scope.get(scope, 0.0) for scope in SUMS[part])


def slice_device_ms(run: dict, scope: str):
    """Device ms per run of the prefill slice's executable
    (`chunk_paged`) under one scope: 'attn' is the cached-form read of
    the slot's whole table for the slice's queries."""
    return _scopes(run, "chunk_paged").get(scope)


def mla_read_roofline_pct(run: dict):
    """One layer's cached-form read of the live latent rows, max(FLOPs
    over the bf16 peak, bytes as stored over the HBM peak), times the
    layers, against the device time under `attn` per decode run."""
    means, took = _decode_means(run), _scopes(run).get("attn")
    if not means or not took or not run.get("peak"):
        return None
    cost = flops_dots.latent_read_cost(run["config"],
                                       means["attended_tokens"])
    least = (run["config"]["num_hidden_layers"]
             * flops.roofline_seconds(*cost, run["peak"]))
    return 100.0 * least / (took * 1e-3)


def expert_stream_roofline_pct(run: dict):
    """Bytes of the held experts that had a token in a decode step
    (summed over the expert layers) over the HBM peak, against the
    device time under `experts` per decode run."""
    means, took = _decode_means(run), _scopes(run).get("experts")
    if not means or not took or not run.get("peak"):
        return None
    nbytes = means["experts_hit"] * flops_dots.expert_bytes(run["config"])
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
    max of FLOPs and bytes over the peaks: flops_dots.
    decode_step_roofline_seconds) over the median device time of a
    decode run."""
    means, trace = _decode_means(run), run.get("trace")
    if not means or not trace or not run.get("peak"):
        return None
    runs = [d for name, durations in trace["modules"].items()
            if MODULE in name for d in durations]
    if not runs:
        return None
    least = flops_dots.decode_step_roofline_seconds(
        run["config"], run["peak"], **means)
    return 100.0 * least / statistics.median(runs)
