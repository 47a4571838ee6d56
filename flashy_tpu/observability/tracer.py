# Host-side event tracing. The reference flashy has no profiler at all
# (SURVEY §5: the per-stage `duration` metric is its only timing
# signal); `jax.profiler.trace` (solver.enable_profiling) covers the
# XLA/device side but says nothing about the host: data wait, python
# overhead, checkpoint IO. The Tracer is the host-side complement — a
# zero-dependency span recorder whose output loads straight into
# Perfetto / chrome://tracing (the Chrome trace-event JSON format), plus
# an append-only `telemetry.jsonl` journal of structured records (the
# per-rank event journaling the Orbax paper motivates for multi-host
# runs: a crash keeps every line written so far).
"""Tracer: host-side spans -> Chrome/Perfetto trace + telemetry.jsonl."""
from contextlib import contextmanager
from pathlib import Path
import functools
import json
import threading
import time
import typing as tp

from ..utils import AnyPath, write_and_rename


class JsonlJournal:
    """Append-only JSONL file with an optional size-capped rotation.

    The journal contract (a crash keeps every line written so far)
    plus a bound: when `max_bytes` is set and the next line would push
    the current file past it, the file is rotated to `<name>.1` (older
    generations shift to `.2..keep`, the oldest is dropped) and a fresh
    file is opened whose FIRST record documents the rotation — so a
    long-running serve job cannot fill the XP folder, and the cut
    points are themselves part of the record.

    Not thread-safe on its own: callers (Tracer, RequestTracer) hold
    their own lock around `write_line`.
    """

    def __init__(self, path: AnyPath, max_bytes: tp.Optional[int] = None,
                 keep: int = 3):
        if max_bytes is not None and max_bytes < 1024:
            raise ValueError(f"max_bytes must be >= 1024, got {max_bytes}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.path = Path(path)
        self.max_bytes = max_bytes
        self.keep = keep
        self.rotations = 0
        self._file: tp.Optional[tp.IO[str]] = None
        self._size = 0

    def write_line(self, line: str) -> None:
        """Append one line (flushed); rotates first when it would not fit."""
        data = line + "\n"
        if self._file is None:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._file = open(self.path, "a")
            self._size = self._file.tell()
        if (self.max_bytes is not None and self._size > 0
                and self._size + len(data) > self.max_bytes):
            self._rotate()
        self._file.write(data)
        self._file.flush()
        self._size += len(data)

    def _rotate(self) -> None:
        assert self._file is not None
        self._file.close()
        sibling = self.path.with_name
        oldest = sibling(f"{self.path.name}.{self.keep}")
        if oldest.exists():
            oldest.unlink()
        for i in range(self.keep - 1, 0, -1):
            src = sibling(f"{self.path.name}.{i}")
            if src.exists():
                src.rename(sibling(f"{self.path.name}.{i + 1}"))
        self.path.rename(sibling(f"{self.path.name}.1"))
        self.rotations += 1
        self._file = open(self.path, "a")
        self._size = 0
        note = json.dumps({"time": time.time(), "type": "journal_rotated",
                           "rotation": self.rotations, "keep": self.keep,
                           "max_bytes": self.max_bytes})
        self._file.write(note + "\n")
        self._file.flush()
        self._size = len(note) + 1

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


class _Open(threading.local):
    """What the span primitive keeps a thread: the dict of the
    `phases=` span open on it, and how many spans are open inside."""
    phases: tp.Optional[tp.Dict[str, float]] = None
    depth = 0


_open = _Open()


@contextmanager
def span(name: str, tracer: tp.Optional["Tracer"] = None,
         category: str = "host",
         phases: tp.Optional[tp.Dict[str, float]] = None, **stats: tp.Any):
    """THE span primitive: one named host interval, three sinks.

    It always enters `jax.profiler.TraceAnnotation(name, **stats)`: a
    few hundred nanoseconds while no profiler session runs, and under
    one (`solver.enable_profiling`, `jax.profiler.start_trace`) the span
    lands on `/host:CPU` of the `.xplane.pb`, on the same clock as the
    device's `XLA Ops` — so device idle time can be laid against the
    host phase that caused it. When `tracer` (or, if None, the active
    telemetry's tracer) exists, the same name and stats are also
    recorded as a Chrome 'X' event, as `Tracer.span` always did. A
    span given a dict as `phases` collects into it, from the same two
    clock reads: its own seconds under its own name when it closes, and
    before that the seconds of every span opened DIRECTLY inside it, on
    its thread, summed by name (the scheduler's record of a slow step).
    Yields that tracer (or None). Names follow the `sub/name` track
    convention (FT006's `TRACK_RE`).
    """
    import jax  # not at import time: the package loads without a backend
    if tracer is None:
        from .telemetry import get_telemetry
        telemetry = get_telemetry()
        tracer = telemetry.tracer if telemetry is not None else None
    into, depth = _open.phases, _open.depth
    if phases is not None:
        _open.phases, _open.depth = phases, 0
    elif into is not None:
        _open.depth = depth + 1
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **stats):
        try:
            yield tracer
        finally:
            took = time.perf_counter() - start
            if tracer is not None:
                tracer.complete(name, start, took, category=category, **stats)
            _open.phases, _open.depth = into, depth
            if phases is not None:
                phases[name] = took
            elif into is not None and depth == 0:
                into[name] = into.get(name, 0.0) + took


class Tracer:
    """Records host-side monotonic events and exports them.

    Spans nest naturally (the Chrome trace format infers nesting from
    time containment within one pid/tid); loader worker threads get
    their own tid lanes. All methods are thread-safe and cheap enough
    to leave in hot loops (~a dict append under a lock).

    Args:
        trace_path: where `export_chrome_trace()` writes by default.
        jsonl_path: the append-only journal; each `record()` call writes
            one JSON line and flushes, so a killed run keeps every
            record up to the crash.
        rank: process index, stamped as the trace `pid` and into every
            journal record.
        max_events: in-memory event cap; past it new spans are counted
            as dropped instead of recorded (the journal is unaffected).
        max_journal_bytes: size cap on `telemetry.jsonl`; past it the
            journal rotates to `.1..journal_keep` siblings (see
            :class:`JsonlJournal`). None (the default) keeps the
            unbounded append-only behavior.
        journal_keep: rotated generations retained beside the live file.
    """

    def __init__(self, trace_path: tp.Optional[AnyPath] = None,
                 jsonl_path: tp.Optional[AnyPath] = None,
                 rank: int = 0, max_events: int = 200_000,
                 max_journal_bytes: tp.Optional[int] = None,
                 journal_keep: int = 3):
        self.trace_path = Path(trace_path) if trace_path else None
        self.jsonl_path = Path(jsonl_path) if jsonl_path else None
        self.rank = rank
        self.max_events = max_events
        self.dropped = 0
        self._events: tp.List[tp.Dict[str, tp.Any]] = []
        self._lock = threading.Lock()
        self._journal = (JsonlJournal(self.jsonl_path,
                                      max_bytes=max_journal_bytes,
                                      keep=journal_keep)
                         if self.jsonl_path else None)
        self._t0 = time.perf_counter()
        self._add_meta("process_name", {"name": f"rank{rank}"})

    # ------------------------------------------------------------------
    # event recording
    # ------------------------------------------------------------------
    def _add_meta(self, name: str, args: tp.Dict[str, tp.Any]) -> None:
        with self._lock:
            self._events.append({"name": name, "ph": "M", "pid": self.rank,
                                 "tid": threading.get_ident() % (1 << 31),
                                 "args": args})

    def _add(self, event: tp.Dict[str, tp.Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(event)

    def complete(self, name: str, start: float, duration: float,
                 category: str = "host", **args: tp.Any) -> None:
        """Record a completed span from raw `time.perf_counter()` times.

        For callers that measured a phase themselves (StepTimer) — the
        span lands on the same clock as `span()` events.
        """
        self._add({"name": name, "cat": category, "ph": "X",
                   "ts": (start - self._t0) * 1e6, "dur": duration * 1e6,
                   "pid": self.rank, "tid": threading.get_ident() % (1 << 31),
                   "args": args})

    def span(self, name: str, category: str = "host", **args: tp.Any):
        """Context manager recording one complete ('X') event (and the
        same span on the profiler's clock: see module-level `span`)."""
        return span(name, tracer=self, category=category, **args)

    def wrap(self, fn: tp.Optional[tp.Callable] = None, *,
             name: tp.Optional[str] = None) -> tp.Callable:
        """Decorator form of `span`: `@tracer.wrap` or `@tracer.wrap(name=...)`."""
        if fn is None:
            return functools.partial(self.wrap, name=name)

        span_name = name or getattr(fn, "__qualname__", repr(fn))

        @functools.wraps(fn)
        def wrapped(*args: tp.Any, **kwargs: tp.Any) -> tp.Any:
            with self.span(span_name):
                return fn(*args, **kwargs)

        return wrapped

    def instant(self, name: str, category: str = "host", **args: tp.Any) -> None:
        """Record a zero-duration marker event."""
        self._add({"name": name, "cat": category, "ph": "i", "s": "p",
                   "ts": (time.perf_counter() - self._t0) * 1e6,
                   "pid": self.rank, "tid": threading.get_ident() % (1 << 31),
                   "args": args})

    def counter(self, name: str, **values: float) -> None:
        """Record a counter sample (rendered as a track in Perfetto)."""
        self._add({"name": name, "ph": "C",
                   "ts": (time.perf_counter() - self._t0) * 1e6,
                   "pid": self.rank, "args": dict(values)})

    # ------------------------------------------------------------------
    # async spans (request-scoped tracing)
    # ------------------------------------------------------------------
    def _async(self, ph: str, name: str, span_id: int, category: str,
               args: tp.Dict[str, tp.Any]) -> None:
        self._add({"name": name, "cat": category, "ph": ph,
                   "id": f"0x{span_id:x}",
                   "ts": (time.perf_counter() - self._t0) * 1e6,
                   "pid": self.rank,
                   "tid": threading.get_ident() % (1 << 31), "args": args})

    def async_begin(self, name: str, span_id: int, category: str = "serve",
                    **args: tp.Any) -> None:
        """Open an async ('b') span keyed by `(category, id)`.

        Async spans cross thread/stack boundaries — exactly the shape of
        a serving request, which is submitted in one call stack and
        retired many scheduler steps later. Perfetto groups every
        `async_*` event with the same category and id onto one track;
        nested begin/end pairs under the same id render as sub-phases.
        """
        self._async("b", name, span_id, category, args)

    def async_instant(self, name: str, span_id: int, category: str = "serve",
                      **args: tp.Any) -> None:
        """Drop an async instant ('n') marker into an open async span."""
        self._async("n", name, span_id, category, args)

    def async_end(self, name: str, span_id: int, category: str = "serve",
                  **args: tp.Any) -> None:
        """Close the async span opened by `async_begin` (same name + id)."""
        self._async("e", name, span_id, category, args)

    @property
    def events(self) -> tp.List[tp.Dict[str, tp.Any]]:
        """Snapshot of the recorded trace events (tests, inspection)."""
        with self._lock:
            return list(self._events)

    # ------------------------------------------------------------------
    # journal
    # ------------------------------------------------------------------
    def record(self, record: tp.Dict[str, tp.Any]) -> None:
        """Append one structured record to `telemetry.jsonl` (flushed).

        `time` (unix seconds) and `rank` are stamped in; the caller owns
        the rest of the schema (e.g. StepTimer's per-step records).
        """
        if self._journal is None:
            return
        line = json.dumps({"time": time.time(), "rank": self.rank, **record},
                          default=float)
        with self._lock:
            self._journal.write_line(line)

    @property
    def journal_rotations(self) -> int:
        """How many times the telemetry journal rotated (0 = never)."""
        return self._journal.rotations if self._journal is not None else 0

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def export_chrome_trace(self, path: tp.Optional[AnyPath] = None) -> Path:
        """Write the Chrome trace-event JSON (atomic full rewrite).

        Safe to call repeatedly (e.g. at every stage end): the file is
        always a complete valid trace of everything recorded so far —
        open it in https://ui.perfetto.dev or chrome://tracing.
        """
        target = Path(path) if path else self.trace_path
        if target is None:
            raise ValueError("no trace path: pass `path` or set `trace_path`")
        payload = {"traceEvents": self.events, "displayTimeUnit": "ms"}
        if self.dropped:
            payload["metadata"] = {"dropped_events": self.dropped}
        target.parent.mkdir(parents=True, exist_ok=True)
        with write_and_rename(target, "w") as f:
            json.dump(payload, f)
        return target

    def close(self) -> None:
        """Export the trace (when a path is set) and close the journal."""
        if self.trace_path is not None:
            self.export_chrome_trace()
        with self._lock:
            if self._journal is not None:
                self._journal.close()
