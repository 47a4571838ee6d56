# Readers of what the PROGRAM wrote into the device trace: its host
# spans (`flashy_tpu.observability.span`: serve/step and its children,
# on the profiler's clock) and the named scopes and kernel names of its
# device programs (the HLO op_name of every 'XLA Ops' event). The
# harness's reducer (harness/trace.py) reads only its own `bench/`
# spans; these readers open the run's .xplane.pb themselves
# (readers/xspace.py) and clip to `bench/traced_window`.
#
# A program that has no such span or scope (the parent of the PR that
# added them) gives every reader nothing to read: it returns None and
# the metric is left out of the line.
"""Per-layer metrics from the program's spans and named scopes."""
import bisect
import functools
import os
import re
import statistics

from ..harness import flops
from ..harness.trace import (CONTAINERS, DEVICE_PLANE, WINDOW_SPAN, _union,
                             short_op_name)
from . import xspace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEP_SPAN = "serve/step"
# innermost span at the middle of an idle gap -> the metric's bucket
IDLE_BUCKETS = {
    "admit": ("serve/admission", "serve/table_upload", "serve/gauges"),
    "dispatch": ("serve/prefill", "serve/prefill_chunk", "serve/decode",
                 "serve/decode/dispatch", "serve/verify",
                 "serve/verify/dispatch"),
    "readback": ("serve/decode/readback", "serve/prefill_chunk/readback",
                 "serve/verify/readback"),
    "retire": ("serve/retire",),
}
# The stat of an 'XLA Ops' event's metadata that carries the HLO op_name
# on libtpu 0.0.34 / jax 0.9.0: `tf_op`, as '<op_name>:<op_type>' (seen
# on the v5e: 'jit(decode_paged)/qkv/dot_general:'). `ProfileData` does
# not show metadata stats, hence readers/xspace.py.
SCOPE_STATS = ("tf_op", "op_name")


def scope_path(event) -> tuple:
    """The named-scope path of a device op as a tuple of its parts,
    wrappers of a transform opened: 'jit(f)/transpose(jvp(loss))/mul:'
    -> ('jit', 'f', 'transpose', 'jvp', 'loss', 'mul')."""
    for key in SCOPE_STATS:
        value = event.stats.get(key)
        if value:
            if isinstance(value, bytes):
                value = value.decode("utf-8", "replace")
            return tuple(_ARGUMENT.sub(r"\1", part)
                         for part in re.split(r"[/():]+", str(value)) if part)
    return ()


# a compiler-inserted copy of a program argument carries the argument's
# path as its op_name ("cache['block_5']['k_scale']"): its first name
_ARGUMENT = re.compile(r"^(\w+)\[.*$")


def program_trace(run: dict):
    """The run's own trace, reduced once and kept on the run record:
    window, per-device ops and module runs, and the program's spans."""
    if "program_trace" not in run:
        path = xspace.newest_trace_file(ROOT) if run.get("trace") else None
        run["program_trace"] = reduce(xspace.load(path)) if path else None
        report(run)  # once a run, on `[bench]` lines before the result
    return run["program_trace"]


def reduce(planes: dict):
    """{plane: {line: [Event]}} -> window (lo, hi) in ns; `devices`:
    per device its ops (clipped to the window, sorted) and module runs
    that began in the window; `spans`: the program's host spans that
    began in the window, sorted by start. None without a device."""
    host = [e for line in planes.get("/host:CPU", {}).values() for e in line]
    window = next(((e.start, e.end) for e in host if e.name == WINDOW_SPAN),
                  None)
    devices = []
    for name in sorted(n for n in planes if DEVICE_PLANE.match(n)):
        lines = planes[name]
        if window is None:
            every = lines.get("XLA Ops", [])
            if not every:
                continue
            window = (min(e.start for e in every), max(e.end for e in every))
        lo, hi = window
        ops = sorted((max(e.start, lo), min(e.end, hi), e)
                     for e in lines.get("XLA Ops", [])
                     if min(e.end, hi) > max(e.start, lo))
        modules = sorted((e.start, e.end, e.name.split("(")[0])
                         for e in lines.get("XLA Modules", [])
                         if lo <= e.start < hi)
        devices.append({"ops": ops, "modules": modules})
    if not devices:
        return None
    lo, hi = window
    spans = sorted((e for e in host
                    if e.name.startswith("serve/") and lo <= e.start < hi),
                   key=lambda e: (e.start, -e.end))
    return {"window": window, "devices": devices, "spans": spans}


def _holder(spans, starts, instant: float) -> str:
    """The innermost program span covering `instant`. Spans are sorted
    by start, so it is the latest-starting one that still covers it."""
    for i in range(bisect.bisect_right(starts, instant) - 1, -1, -1):
        if spans[i].end > instant:
            return spans[i].name
        if spans[i].name == STEP_SPAN:
            break  # its step ended before: outside every step
    return "(outside serve/step)"


def idle_by_span(trace: dict) -> dict:
    """The first device's idle time in the window, two ways. `gaps`:
    each gap whole to the innermost program span the host was in at the
    gap's middle (as the harness attributes gaps to its own spans; the
    metrics read this). `overlap`: each gap cut at every span boundary
    inside it, each piece to the span covering it — where the host
    really was while the device idled. Both {span name: ns}, with
    '(outside serve/step)' under no span. `in_modules`: the part of all
    of it that lay inside a running executable."""
    if "idle_by_span" in trace:
        return trace["idle_by_span"]
    lo, hi = trace["window"]
    spans, first = trace["spans"], trace["devices"][0]
    starts = [s.start for s in spans]
    edges = sorted(starts + [s.end for s in spans])
    module_starts = [m[0] for m in first["modules"]]
    gaps, overlap, inside, cursor = {}, {}, 0.0, lo
    busy = _union((start, end) for start, end, _ in first["ops"])
    for start, end in busy + [[hi, hi]]:
        if start > cursor:
            middle = (cursor + start) / 2
            name = _holder(spans, starts, middle)
            gaps[name] = gaps.get(name, 0.0) + start - cursor
            cuts = [cursor] + edges[bisect.bisect_right(edges, cursor):
                                    bisect.bisect_left(edges, start)] + [start]
            for a, b in zip(cuts, cuts[1:]):
                name = _holder(spans, starts, (a + b) / 2)
                overlap[name] = overlap.get(name, 0.0) + b - a
            m = bisect.bisect_right(module_starts, middle) - 1
            if m >= 0 and first["modules"][m][1] > middle:
                inside += start - cursor
        cursor = max(cursor, end)
    trace["idle_by_span"] = {"gaps": gaps, "overlap": overlap,
                             "in_modules": inside}
    return trace["idle_by_span"]


def _steps(trace: dict) -> int:
    return sum(1 for s in trace["spans"] if s.name == STEP_SPAN)


def serve_idle_ms(run: dict, bucket: str):
    """Device idle per scheduler step (ms) in gaps whose middle lay in
    the spans of `bucket` (IDLE_BUCKETS; innermost span wins), or
    'outside_step': in no serve/step at all (the driver's client loop)."""
    trace = program_trace(run)
    if not trace or not _steps(trace):
        return None
    gaps = idle_by_span(trace)["gaps"]
    if bucket == "outside_step":
        total = gaps.get("(outside serve/step)", 0.0)
    else:
        total = sum(gaps.get(name, 0.0) for name in IDLE_BUCKETS[bucket])
    return total * 1e-6 / _steps(trace)


def decoding_slots(run: dict):
    """Mean `running` stat of the serve/decode (and serve/verify) spans:
    the slots that emit a token in a step."""
    trace = program_trace(run)
    if not trace:
        return None
    running = [float(s.stats["running"]) for s in trace["spans"]
               if s.name in ("serve/decode", "serve/verify")
               and "running" in s.stats]
    return statistics.fmean(running) if running else None


def scope_ms_per_run(trace: dict, module: str, groups: dict) -> dict:
    """Device milliseconds per run of the executable whose module name
    contains `module`, by group: `groups` maps a group's name to the
    scope parts that put an op into it (the first group with a part on
    the op's scope path wins); ops of no group land in '(rest)'. Mean
    over devices; {} when the executable did not run whole in the
    window."""
    key = (module, tuple(sorted(groups)))
    cache = trace.setdefault("scope_ms", {})
    if key in cache:
        return cache[key]
    totals, runs = {}, 0
    for count, ops in ops_of_runs(trace, module):
        runs += count
        for start, end, event in ops:
            parts = set(scope_path(event))
            group = next((g for g, wanted in groups.items()
                          if parts.intersection(wanted)), "(rest)")
            totals[group] = totals.get(group, 0.0) + end - start
    cache[key] = ({g: ns * 1e-6 / runs for g, ns in totals.items()}
                  if runs else {})
    return cache[key]


def ops_of_runs(trace: dict, module: str):
    """For each device on which the executable whose module name
    contains `module` ran whole in the window: (its number of runs, the
    ops inside them). A `while`, `conditional` or `call` is left out:
    it spans its body's ops, which are events of their own."""
    hi = trace["window"][1]
    for device in trace["devices"]:
        whole = [m for m in device["modules"] if module in m[2] and m[1] <= hi]
        if not whole:
            continue
        begins = [m[0] for m in whole]
        inside = []
        for op in device["ops"]:
            i = bisect.bisect_right(begins, op[0]) - 1
            if (i >= 0 and op[0] < whole[i][1] and
                    short_op_name(op[2].name).split(" ")[0] not in CONTAINERS):
                inside.append(op)
        yield len(whole), inside


DECODE_GROUPS = {scope: (scope,) for scope in (
    "embed", "norm", "qkv", "rotary", "kv_write", "attn", "out_proj", "mlp",
    "head", "sample")}
# the layout copies XLA puts around the pool's scatter are named after
# the argument they copy, `cache[...]`: they exist because of the write
DECODE_GROUPS["kv_write"] = ("kv_write", "cache")
WEIGHT_SCOPES = ("qkv", "out_proj", "mlp", "head", "embed", "norm")
# the train step: a block's norm counts with the sublayer it feeds
TRAIN_GROUPS = {"head_loss": ("loss",), "optimizer": ("optimizer",),
                "attn": ("attn", "norm1", "ssd"),
                "mlp": ("mlp", "norm2", "moe")}


def decode_scope_ms(run: dict, scope: str, module: str = "decode_paged"):
    """Device ms per run of the decode executable under one scope."""
    trace = program_trace(run)
    if not trace:
        return None
    return scope_ms_per_run(trace, module, DECODE_GROUPS).get(scope)


def weight_stream_roofline_pct(run: dict, bytes_per_param: int,
                               module: str = "decode_paged"):
    """Bytes of parameters one decode step reads (every leaf once, the
    tied table once, at the bytes the harness's weights have) over the
    HBM peak, against the device time under the scopes that stream
    weights (WEIGHT_SCOPES) per run of the decode executable."""
    trace = program_trace(run)
    if not trace or not run.get("peak"):
        return None
    by_scope = scope_ms_per_run(trace, module, DECODE_GROUPS)
    took = sum(by_scope.get(scope, 0.0) for scope in WEIGHT_SCOPES) * 1e-3
    if not took:
        return None
    nbytes = flops.lm_param_count(run["config"]) * bytes_per_param
    return 100.0 * flops.roofline_seconds(0.0, nbytes, run["peak"]) / took


def train_scope_ms(run: dict, group: str, module: str = "train_step"):
    """Device ms per train step under one group of TRAIN_GROUPS
    (forward, recomputation and backward together)."""
    trace = program_trace(run)
    if not trace:
        return None
    return scope_ms_per_run(trace, module, TRAIN_GROUPS).get(group)


def hbm_peak_pct(run: dict):
    """Peak bytes in use on the fullest device over its HBM."""
    if not run.get("peak") or not run.get("memory_peak_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / run["peak"]["hbm_bytes"]


def clock_check(trace: dict, module: str = "decode_paged") -> dict:
    """How long after the decode executable ended on the device its
    `serve/decode/readback` span ended on the host, in ms: a host span
    on the device's clock ends shortly AFTER what it waited for."""
    runs = [m for m in trace["devices"][0]["modules"] if module in m[2]]
    begins = [m[0] for m in runs]
    lags = []
    for span in trace["spans"]:
        if span.name != "serve/decode/readback":
            continue
        # the run it waited for: the last one that began before it ended
        i = bisect.bisect_right(begins, span.end) - 1
        if i >= 0:
            lags.append((span.end - runs[i][1]) * 1e-6)
    if not lags:
        return {}
    return {"spans": len(lags), "median_ms": statistics.median(lags),
            "min_ms": min(lags), "max_ms": max(lags),
            "negative": sum(lag < 0 for lag in lags)}


def report(run: dict, say=functools.partial(print, flush=True)) -> None:
    """The `[bench]` lines: the per-span table, the clock check, the
    decode and train steps by scope."""
    trace = program_trace(run)
    if not trace:
        return
    lo, hi = trace["window"]
    idle = idle_by_span(trace)
    steps = _steps(trace)
    if steps:
        total = sum(idle["gaps"].values())
        by_name, stack = {}, []
        for span in trace["spans"]:  # sorted by start: a stack nests them
            while stack and stack[-1][0].end <= span.start:
                done, children = stack.pop()
                by_name.setdefault(done.name, []).append(
                    done.end - done.start - children)
            if stack:
                stack[-1][1] += span.end - span.start
            stack.append([span, 0.0])
        for done, children in stack:
            by_name.setdefault(done.name, []).append(
                done.end - done.start - children)
        say(f"[bench] program spans: {steps} serve/step in "
            f"{(hi - lo) * 1e-6:.1f} ms; device idle {total * 1e-6:.1f} ms, "
            f"{idle['in_modules'] * 1e-6:.1f} ms of it between the ops of a "
            f"running executable")
        for name in sorted(set(by_name) | set(idle["gaps"])
                           | set(idle["overlap"])):
            took = by_name.get(name, [])
            say(f"[bench]   {name}: count {len(took)}, self time median "
                f"{statistics.median(took) * 1e-6 if took else 0.0:.3f} mean "
                f"{statistics.fmean(took) * 1e-6 if took else 0.0:.3f} ms, "
                f"idle attributed {idle['gaps'].get(name, 0.0) * 1e-6:.2f} ms"
                f" ({idle['gaps'].get(name, 0.0) * 1e-6 / steps:.3f} a step)"
                f", idle while the host was in it "
                f"{idle['overlap'].get(name, 0.0) * 1e-6 / steps:.3f} a step")
        unowned = idle["gaps"].get(STEP_SPAN, 0.0)
        say(f"[bench] idle inside serve/step but in none of its children: "
            f"{unowned * 1e-6:.2f} ms, {100 * unowned / max(total, 1):.1f}% "
            f"of the idle time")
        say(f"[bench] clock check (serve/decode/readback end - decode_paged "
            f"end): {clock_check(trace)}")
    for module, groups in (("decode_paged", DECODE_GROUPS),
                           ("chunk_paged", DECODE_GROUPS),
                           ("train_step", TRAIN_GROUPS)):
        by_scope = scope_ms_per_run(trace, module, groups)
        if by_scope:
            say(f"[bench] {module} device ms per run by scope: " + ", ".join(
                f"{g} {ms:.3f}" for g, ms in sorted(
                    by_scope.items(), key=lambda item: -item[1])))
