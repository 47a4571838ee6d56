# Device trace: taking one (the tail of the measured window, under
# jax.profiler) and reducing the .xplane.pb to numbers. The reduction is
# the benchmark's, not the program's: every PR computes busy time,
# module times, per-op totals and gap attribution the same way.
#
# What a v5e trace looks like (chiprun, jax 0.9.0): a plane
# '/device:TPU:<n>' per chip with lines 'XLA Modules' (one event per
# executable run, named 'jit_<fn>(<id>)'), 'XLA Ops' (one event per HLO
# op, named by its HLO text) and 'Steps'; a plane '/host:CPU' whose
# lines are host threads, where jax.profiler.TraceAnnotation spans
# appear under their own names. All on one clock, in nanoseconds.
"""Take a device trace of a window's tail and reduce it to metrics."""
import contextlib
import glob
import os
import re
import time

SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# ops whose event spans the events of a body: counted through the body
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b([a-z]+\d+\[[\d,]*\])")


class Tracing:
    """Profiles the last `tail_seconds` of a window when enabled, and
    hands out host spans (free when no profiler is running)."""

    def __init__(self, out_dir: str, enabled: bool, tail_seconds: float):
        self.out_dir = out_dir
        self.enabled = enabled
        self.tail_seconds = tail_seconds
        self.started_at = None  # time.perf_counter() when the trace began
        self._window = None

    def span(self, name: str, **stats):
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)

    def poll(self, seconds_left: float) -> None:
        """Call once per loop turn: starts the profiler when the window
        has `tail_seconds` left."""
        if (not self.enabled or self.started_at is not None
                or seconds_left > self.tail_seconds):
            return
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # benchmark spans only, no frames
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.started_at = time.perf_counter()
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def end_window(self) -> None:
        """Closes the traced window's span (the profiler keeps running
        until `stop`, which takes seconds and so comes after whatever
        still has to be timed)."""
        if self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None

    def stop(self):
        """Stops the profiler; returns the reduced trace or None."""
        if self.started_at is None:
            return None
        import jax
        self.end_window()
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return reduce_file(files[-1]) if files else None


def short_op_name(hlo: str) -> str:
    """'%fusion.12 = bf16[8,256]{1,0:T(8,128)} fusion(...)' -> 'fusion
    bf16[8,256]': the instruction's name without its number, and its
    first output shape without layout."""
    head, _, rest = hlo.partition(" = ")
    stem = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    shape = _SHAPE.search(_result_type(rest)) if rest else None
    return f"{stem} {shape.group(1)}" if shape else stem


def _result_type(rest: str) -> str:
    """The result type at the start of an HLO right-hand side."""
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    return rest[:_matching_paren(rest, 0) + 1]


def _matching_paren(text: str, start: int) -> int:
    depth = 0
    for i in range(start, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            return i
    return len(text) - 1


def op_shapes(hlo: str):
    """(output shapes, operand shapes) of an HLO instruction's text, as
    lists of 'dtype[dims]' strings; ([], []) when it does not parse."""
    _, _, rest = hlo.partition(" = ")
    if not rest:
        return [], []
    result = _result_type(rest)
    call = rest.find("(", len(result))
    if call < 0:
        return _SHAPE.findall(result), []
    operands = rest[call:_matching_paren(rest, call) + 1]
    return _SHAPE.findall(result), _SHAPE.findall(operands)


def _union(intervals):
    """Merged, sorted list of (start, end) from overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce_file(path: str) -> dict:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def reduce_profile(profile) -> dict:
    """ProfileData -> {window_s, busy_s, devices, modules, ops,
    op_events, idle_gaps}; times in seconds.

    window: the `bench/traced_window` host span (else the extent of the
    device events). busy_s: per device, the union of its 'XLA Ops'
    intervals clipped to the window, averaged over devices. modules:
    name before '(' -> list of durations of runs that began inside the
    window. ops: short name -> [seconds, count] over all devices.
    (a `while`, `conditional` or `call` is left out: its body's ops are
    events of their own). op_events: (HLO text, seconds) of every custom
    call. idle_gaps: the
    window's idle time on the first device by the innermost benchmark
    span the host was in at the middle of each gap.
    """
    spans, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(e.name, e.start_ns, e.start_ns
                                  + e.duration_ns) for e in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            devices.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not devices:
        return {}
    window = next(((s, e) for name, s, e in spans if name == WINDOW_SPAN),
                  None)
    if window is None:
        every = [ev for dev in devices for ev in dev.get("XLA Ops", [])]
        if not every:
            return {}
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    busy, modules, ops, op_events, first_busy = [], {}, {}, [], None
    for dev in devices:
        clipped = []
        for name, start, end in dev.get("XLA Ops", []):
            start, end = max(start, lo), min(end, hi)
            if end <= start:
                continue
            clipped.append((start, end))
            short = short_op_name(name)
            if short.split(" ")[0] in CONTAINERS:
                continue
            entry = ops.setdefault(short, [0.0, 0])
            entry[0] += (end - start) * 1e-9
            entry[1] += 1
            if "custom-call(" in name or "custom_call" in name:
                op_events.append((name, (end - start) * 1e-9))
        merged = _union(clipped)
        if first_busy is None:
            first_busy = merged
        busy.append(sum(end - start for start, end in merged) * 1e-9)
        for name, start, end in dev.get("XLA Modules", []):
            if lo <= start < hi:
                modules.setdefault(name.split("(")[0], []).append(
                    (end - start) * 1e-9)
    gaps, cursor = {}, lo
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    for start, end in first_busy + [[hi, hi]]:
        if start > cursor:
            middle = (cursor + start) / 2
            holder = min((s for s in inner if s[1] <= middle < s[2]),
                         key=lambda s: s[2] - s[1], default=None)
            name = holder[0] if holder else "(outside any benchmark span)"
            gaps[name] = gaps.get(name, 0.0) + (start - cursor) * 1e-9
        cursor = max(cursor, end)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / len(busy), "devices": len(devices),
            "modules": modules, "ops": ops, "op_events": op_events,
            "idle_gaps": gaps}


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's `breakdown`: the device operations that took most
    time and the idle time by host span, at most `top` entries each."""
    ops = sorted(((name, entry[0]) for name, entry in trace["ops"].items()),
                 key=lambda item: -item[1])[:top]
    gaps = sorted(trace["idle_gaps"].items(), key=lambda item: -item[1])
    return {"device_ops": [[name, seconds] for name, seconds in ops],
            "idle_gaps": [[name, seconds] for name, seconds in gaps[:top]]}


@contextlib.contextmanager
def timed(record: dict, key: str):
    """Adds the wall seconds of the block to record[key]."""
    begin = time.perf_counter()
    try:
        yield
    finally:
        record[key] = record.get(key, 0.0) + time.perf_counter() - begin
