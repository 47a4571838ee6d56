# Readers of the PIPELINED step (one step in flight since PR 34): the
# device no longer waits for the host, so the question is how far under
# the device's time the host's own is, and in which span. All of it is
# read from the program's `serve/` spans in the run's trace
# (readers/program_spans.py:program_trace), over the `serve/step` spans
# that begin AND end inside `bench/traced_window`. A span's self time
# is its duration minus what its children cover, so within a step the
# self times add up to the step's wall time:
#
#   host_slack_ms  the read-back spans: waiting for the device + the copy
#   host_busy_ms   everything else = host_ms.admit + .dispatch + .retire
#                  + .step (serve/step itself and any child no bucket names)
#
# A run without a trace, or a program without the spans, gives every
# reader nothing to read: it returns None and the metric is left out.
"""Per-layer metrics of the pipelined step: the host's work and slack."""
import bisect
import functools
import statistics

from .program_spans import IDLE_BUCKETS, STEP_SPAN, program_trace

# where the host WAITS: the read-backs, and the empty spans opened right
# after them to carry the expert counts they brought
SLACK_SPANS = IDLE_BUCKETS["readback"] + (
    "serve/decode/moe", "serve/prefill_chunk/moe", "serve/verify/moe")
# where the host WORKS, by the names of `serve_idle_ms.*`; the two spans
# of PR 35 are retirement: slots given back as their budget's last step
# is launched, and the bookkeeping of a first token
HOST_BUCKETS = {
    "admit": IDLE_BUCKETS["admit"],
    "dispatch": IDLE_BUCKETS["dispatch"],
    "retire": IDLE_BUCKETS["retire"] + ("serve/launch_out",
                                        "serve/first_token"),
}
_NAMED = frozenset(SLACK_SPANS).union(*HOST_BUCKETS.values())
READBACK_SPAN = "serve/decode/readback"


def whole_steps(trace: dict) -> list:
    """[{'wall': ns, span name: self ns summed over the step}] for each
    `serve/step` that begins and ends in the window, its own self time
    under its own name. Spans under no such step (a flush between steps,
    the children of a step that straddles an edge) are left out."""
    if "whole_steps" in trace:
        return trace["whole_steps"]
    hi = trace["window"][1]
    steps, stack = [], []  # stack of [span, ns its children cover, step]

    def close():
        span, covered, step = stack.pop()
        if step is not None:
            step[span.name] = (step.get(span.name, 0.0)
                               + span.end - span.start - covered)

    for span in trace["spans"]:  # sorted by start, the longer first
        while stack and stack[-1][0].end <= span.start:
            close()
        if span.name == STEP_SPAN:
            step = {"wall": span.end - span.start} if span.end <= hi else None
            if step is not None:
                steps.append(step)
        else:
            step = stack[-1][2] if stack else None
        if stack:
            stack[-1][1] += span.end - span.start
        stack.append([span, 0.0, step])
    while stack:
        close()
    trace["whole_steps"] = steps
    return steps


def _mean_ms(steps: list, names) -> float:
    return statistics.fmean(sum(step.get(name, 0.0) for name in names)
                            for step in steps) * 1e-6


def _steps_of(run: dict):
    trace = program_trace(run)
    steps = whole_steps(trace) if trace else None
    if steps and "pipeline_report" not in run:
        run["pipeline_report"] = True  # once a run, before the result
        report(trace)
    return steps


def host_slack_ms(run: dict):
    """Mean time a step inside the read-back spans: waiting for the
    device plus the copy. Near 0: the host is on the path."""
    steps = _steps_of(run)
    return _mean_ms(steps, SLACK_SPANS) if steps else None


def host_busy_ms(run: dict):
    """Mean wall of a step minus its read-back spans: the host's own
    work a step."""
    steps = _steps_of(run)
    if not steps:
        return None
    return _mean_ms(steps, ("wall",)) - _mean_ms(steps, SLACK_SPANS)


def host_ms(run: dict, bucket: str):
    """Self time a step (ms) of the spans of `bucket` (HOST_BUCKETS), or
    'step': `serve/step` itself plus any `serve/` span no bucket names —
    what the tree does not explain."""
    steps = _steps_of(run)
    if not steps:
        return None
    if bucket == "step":
        names = {name for step in steps for name in step} - _NAMED - {"wall"}
    else:
        names = HOST_BUCKETS[bucket]
    return _mean_ms(steps, names)


def readback_lags(trace: dict, module: str = "decode_paged") -> dict:
    """Per `serve/decode/readback` span, how long after the decode run
    it read ended on the device the span ended on the host, in ms. The
    run it read: the one that ENDED last at or before the span's end
    and after the read-back before it ended — with a step in flight the
    run that began last is the NEXT one, still running. `unpaired`:
    for each read-back with no such run, how long after it ended the
    next decode run ended (None: none did). The window's first
    read-backs read runs that began before the device's trace did and
    have none (about a step to the next run's end); any other, with a
    small number here, ended BEFORE its run on the trace's clock: the
    two clocks disagree by that much."""
    ends = sorted(m[1] for m in trace["devices"][0]["modules"]
                  if module in m[2])
    lags, unpaired, previous = [], [], float("-inf")
    for span in trace["spans"]:
        if span.name != READBACK_SPAN:
            continue
        i = bisect.bisect_right(ends, span.end) - 1
        if i >= 0 and ends[i] > previous:
            lags.append((span.end - ends[i]) * 1e-6)
        else:
            unpaired.append((ends[i + 1] - span.end) * 1e-6
                            if i + 1 < len(ends) else None)
        previous = span.end
    return {"lags": lags, "unpaired": unpaired}


def readback_lag_ms(run: dict):
    """Median of `readback_lags`: how long after the device had a
    step's tokens the host had them."""
    trace = program_trace(run)
    if not trace:
        return None
    lags = readback_lags(trace)["lags"]
    return statistics.median(lags) if lags else None


def report(trace: dict, say=functools.partial(print, flush=True)) -> None:
    """The `[bench]` lines: the mean step split into the host's work and
    its slack, the work by bucket and by span, the read-back lag."""
    steps = whole_steps(trace)
    if not steps:
        return
    wall, slack = _mean_ms(steps, ("wall",)), _mean_ms(steps, SLACK_SPANS)
    names = sorted({name for step in steps for name in step} - {"wall"})
    unnamed = [name for name in names if name not in _NAMED]
    say(f"[bench] pipeline spans: {len(steps)} whole serve/step in the "
        f"window, mean wall {wall:.4f} ms = host busy {wall - slack:.4f} + "
        f"slack in the read-backs {slack:.4f}")
    say("[bench]   host busy by bucket: " + ", ".join(
        f"{bucket} {_mean_ms(steps, spans):.4f}"
        for bucket, spans in HOST_BUCKETS.items())
        + f", step {_mean_ms(steps, unnamed):.4f} ({', '.join(unnamed)})")
    say("[bench]   self ms a step by span: " + ", ".join(
        f"{name} {_mean_ms(steps, (name,)):.4f}" for name in sorted(
            names, key=lambda name: -_mean_ms(steps, (name,)))))
    found = readback_lags(trace)
    if found["lags"]:
        lags = found["lags"]
        say(f"[bench] read-back lag ({READBACK_SPAN} end - end of the "
            f"decode_paged run it read): spans {len(lags)}, unpaired "
            f"{len(found['unpaired'])} (ms to the next run's end: "
            f"{found['unpaired']}), median {statistics.median(lags):.4f} min "
            f"{min(lags):.4f} max {max(lags):.4f} ms, negative "
            f"{sum(lag < 0 for lag in lags)}")
