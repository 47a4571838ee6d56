# Serving metrics. The numbers an operator actually pages on: how long
# until a request's first token (TTFT — queue wait + prefill), how fast
# tokens stream after that (inter-token latency), how deep the admission
# queue is running, how full the slot pool is, and — under speculative
# decoding — whether the draft is earning its verify step (acceptance
# rate, drafted-vs-emitted, per-step accepted-token distribution).
# Collected as raw samples host-side (cheap appends), summarized as
# p50/p95 on demand, fanned out through the PR 1 Tracer (live Perfetto
# counter tracks + telemetry.jsonl records) and through ResultLogger to
# every experiment logging backend, and snapshotted to `serve.json` in
# the XP folder for `python -m flashy_tpu.info`.
"""ServeMetrics: TTFT / ITL / queue depth / occupancy / acceptance."""
import collections
import gc
import json
import logging
import statistics
import time
import typing as tp
from pathlib import Path

from ..observability import Tracer
from ..utils import percentile, write_and_rename
from ..xp import SERVE_STATUS_NAME, AnyPath

# Perfetto counter-track kinds for the serving path.
COUNTER_QUEUE = "serve/queue_depth"
COUNTER_OCCUPANCY = "serve/slot_occupancy"
COUNTER_ACCEPTANCE = "serve/acceptance"
COUNTER_POOL = "serve/pool_occupancy"
COUNTER_PREFIX = "serve/prefix_hit"
# the scheduler's step span: `on_step_end` is handed what it collected
SPAN_STEP = "serve/step"

logger = logging.getLogger(__name__)

# A scheduler step is SLOW when its wall time exceeds both: this many
# times the median of the steps before it (the last SLOW_STEP_WINDOW of
# them, once SLOW_STEP_HISTORY have been seen: a median of three says
# nothing), and the floor below which nobody would go looking.
SLOW_STEP_FACTOR = 5.0
SLOW_STEP_FLOOR = 0.050  # seconds
SLOW_STEP_WINDOW = 128
SLOW_STEP_HISTORY = 16
SLOW_STEP_RECORDS = 16  # slow steps kept, the oldest dropped

# The interpreter's collections, for the whole process: seconds spent in
# them, how many, and when the one now running began. One `gc.callbacks`
# hook, added at the first `gc_totals()`; it costs nothing between
# collections.
_gc = [0.0, 0, 0.0]


def _on_gc(phase: str, info: tp.Dict[str, int]) -> None:
    if phase == "start":
        _gc[2] = time.perf_counter()
    else:
        _gc[0] += time.perf_counter() - _gc[2]
        _gc[1] += 1


def gc_totals() -> tp.Tuple[float, int]:
    """(seconds inside garbage collections, collections) of this
    process since the first call: read at both ends of an interval, the
    difference is what the collector took of it."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return _gc[0], _gc[1]


class ServeMetrics:
    """Accumulates serving samples; summarizes and fans them out.

    All hooks are cheap (list appends + an optional tracer counter), so
    the scheduler calls them unconditionally. Times are seconds
    (`time.perf_counter` deltas); the summary reports milliseconds —
    serving latencies read naturally in ms, and the formatter
    (`flashy_tpu.logging.serve_formatter`) keys off the `_ms` suffix.

    Args:
        tracer: optional Tracer for counter tracks + journal records.
        percentiles: which percentiles `summary()` reports for every
            sampled distribution (p99 is where serving tail pain
            actually lives; p50/p95 alone hide it).
        slo: optional `observability.SLOEngine`; when attached, every
            TTFT / ITL / queue-wait / acceptance sample is ALSO fed to
            it (`ttft`, `itl`, `queue_wait`, `acceptance` budgets), so
            burn rates track live traffic with no extra plumbing.
    """

    def __init__(self, tracer: tp.Optional[Tracer] = None,
                 percentiles: tp.Sequence[float] = (50, 95, 99),
                 slo: tp.Optional[tp.Any] = None):
        if not percentiles or not all(0 < p < 100 for p in percentiles):
            raise ValueError(
                f"percentiles must be a non-empty sequence in (0, 100), "
                f"got {percentiles!r}")
        self.tracer = tracer
        self.percentiles = tuple(percentiles)
        self.slo = slo
        # non-numeric facts about the serving setup (cache layout, KV
        # dtype — filled by the scheduler from its engine); written to
        # serve.json beside the numeric summary so `flashy_tpu.info`
        # can show WHAT was serving, not just how fast
        self.static_info: tp.Dict[str, tp.Any] = {}
        self.submitted = 0
        self.completed = 0
        self.rejected = 0
        self.expired = 0
        self.preempted = 0
        self.tokens = 0
        self.finish_reasons: tp.Dict[str, int] = {}
        # per-tenant rollups: tenant -> {requests, completed, tokens,
        # shed, preempted}; "shed" counts rejections AND expiries — the
        # two ways a tenant's request leaves without running
        self.tenants: tp.Dict[str, tp.Dict[str, int]] = {}
        self.ttft: tp.List[float] = []
        self.itl: tp.List[float] = []
        self.latency: tp.List[float] = []
        self.queue_wait: tp.List[float] = []
        self.queue_depth: tp.List[int] = []
        self.occupancy: tp.List[float] = []
        # speculative decoding: proposal/acceptance accounting
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.accepted_per_step: tp.List[int] = []
        # paged KV cache: block-pool occupancy + prefix-cache hits
        self.pool_occupancy: tp.List[float] = []
        self.prefix_matched_tokens = 0
        self.prefix_prompt_tokens = 0
        self.prefix_admissions = 0
        self.prefix_hits = 0
        # the one-step pipeline: steps that started with a step still
        # unread, rows the decode steps advanced, and those of them
        # decoded for a request that had already ended (an EOS is seen
        # one step late; that token is dropped)
        self.steps_in_flight = 0
        self.rows_decoded = 0
        self.late_rows = 0
        # slow steps (`on_step_end`): how many, the slowest, the last
        # SLOW_STEP_RECORDS of them whole; judged against the wall times
        # of the steps before
        self.slow_steps = 0
        self.slowest_step = 0.0
        self.slow_step_records: tp.Deque[tp.Dict[str, tp.Any]] = \
            collections.deque(maxlen=SLOW_STEP_RECORDS)
        self._step_walls: tp.Deque[float] = collections.deque(
            maxlen=SLOW_STEP_WINDOW)

    # ------------------------------------------------------------------
    # scheduler hooks
    # ------------------------------------------------------------------
    def _tenant(self, tenant: tp.Optional[str]) -> tp.Dict[str, int]:
        return self.tenants.setdefault(
            tenant or "default",
            {"requests": 0, "completed": 0, "tokens": 0, "shed": 0,
             "preempted": 0})

    def on_submit(self, tenant: tp.Optional[str] = None) -> None:
        self.submitted += 1
        self._tenant(tenant)["requests"] += 1

    def on_reject(self, tenant: tp.Optional[str] = None) -> None:
        self.rejected += 1
        self._tenant(tenant)["shed"] += 1

    def on_expired(self, tenant: tp.Optional[str] = None) -> None:
        """A queued request shed past its TTL deadline (never ran)."""
        self.expired += 1
        self.finish_reasons["expired"] = \
            self.finish_reasons.get("expired", 0) + 1
        self._tenant(tenant)["shed"] += 1

    def on_preempt(self, tenant: tp.Optional[str] = None) -> None:
        """A running request evicted mid-decode for a higher-priority
        admission (it re-queues and resumes; nothing is lost)."""
        self.preempted += 1
        self._tenant(tenant)["preempted"] += 1

    def on_first_token(self, ttft_seconds: float) -> None:
        self.ttft.append(ttft_seconds)
        self.tokens += 1
        if self.slo is not None:
            self.slo.observe("ttft", ttft_seconds)

    def on_token(self, gap_seconds: float) -> None:
        self.itl.append(gap_seconds)
        self.tokens += 1
        if self.slo is not None:
            self.slo.observe("itl", gap_seconds)

    def on_queue_wait(self, wait_seconds: float) -> None:
        """Queue wait of one admitted request (submit -> slot)."""
        self.queue_wait.append(wait_seconds)
        if self.slo is not None:
            self.slo.observe("queue_wait", wait_seconds)

    def on_done(self, latency_seconds: float, reason: str,
                tenant: tp.Optional[str] = None,
                tokens: tp.Optional[int] = None) -> None:
        self.completed += 1
        self.latency.append(latency_seconds)
        self.finish_reasons[reason] = self.finish_reasons.get(reason, 0) + 1
        entry = self._tenant(tenant)
        entry["completed"] += 1
        if tokens:
            entry["tokens"] += int(tokens)

    def on_spec_step(self, drafted: int, accepted: tp.Sequence[int],
                     emitted: int) -> None:
        """One speculative verify step: `drafted` tokens proposed per
        live slot, `accepted` kept-draft counts per live slot, and
        `emitted` tokens actually delivered (accepted + bonus, minus
        any EOS/budget truncation)."""
        live = len(accepted)
        self.spec_steps += 1
        self.spec_drafted += drafted * live
        self.spec_accepted += int(sum(accepted))
        self.spec_emitted += emitted
        self.accepted_per_step.extend(int(a) for a in accepted)
        if self.slo is not None and drafted and live:
            self.slo.observe("acceptance",
                             sum(int(a) for a in accepted) / (drafted * live))
        if self.tracer is not None and self.spec_drafted:
            self.tracer.counter(
                COUNTER_ACCEPTANCE,
                rate=self.spec_accepted / self.spec_drafted)

    def on_prefix(self, matched_tokens: int, prompt_tokens: int) -> None:
        """One paged admission: `matched_tokens` of the prompt were
        served from the prefix cache (refcount bump / COW fork instead
        of prefill); a hit is any admission with matched > 0."""
        self.prefix_admissions += 1
        self.prefix_matched_tokens += matched_tokens
        self.prefix_prompt_tokens += prompt_tokens
        if matched_tokens > 0:
            self.prefix_hits += 1
        if self.tracer is not None and self.prefix_prompt_tokens:
            self.tracer.counter(
                COUNTER_PREFIX,
                hit_rate=self.prefix_matched_tokens
                / self.prefix_prompt_tokens)

    def on_pool(self, occupancy: float, in_use: int, capacity: int,
                cached: int) -> None:
        """Sample the block pool (once per step, paged layout only)."""
        self.pool_occupancy.append(occupancy)
        if self.tracer is not None:
            self.tracer.counter(COUNTER_POOL, in_use=in_use,
                                cached=cached, occupancy=occupancy)

    def on_step(self, in_flight: int) -> None:
        """One scheduler step began; `in_flight` (0 | 1): the step
        before it was still unread on the device."""
        self.steps_in_flight += in_flight

    def on_step_end(self, step: int, phases: tp.Dict[str, float],
                    cpu_seconds: float, gc_seconds: float,
                    gc_collections: int, **state: int) -> None:
        """One scheduler step ended. `phases`: what its `serve/step`
        span collected (`observability.span(phases=)`: the step's own
        seconds under its own name, the seconds in each direct child by
        name); `cpu_seconds`: `time.thread_time()` over it, which tells
        a host that worked from one that was blocked or descheduled;
        the collector's part of it; `state`: the scheduler's counts as
        the step began (`in_flight`, `queued`, `prefilling`,
        `running`). A slow step (SLOW_STEP_*) is counted, kept whole in
        `slow_step_records`, logged once at WARNING and journaled as
        `serve_slow_step`; any other step costs an append."""
        wall = phases[SPAN_STEP]
        walls = self._step_walls
        if wall > SLOW_STEP_FLOOR and len(walls) >= SLOW_STEP_HISTORY:
            median = statistics.median(walls)
            if wall > SLOW_STEP_FACTOR * median:
                # under the step's own name: what no child of it covers
                own = wall - (sum(phases.values()) - wall)
                spent = {name: seconds * 1e3 for name, seconds in sorted(
                    {**phases, SPAN_STEP: own}.items(),
                    key=lambda item: -item[1])}
                record = {"step": step, "wall_ms": wall * 1e3,
                          "cpu_ms": cpu_seconds * 1e3,
                          "median_ms": median * 1e3, "phases": spent,
                          "gc_ms": gc_seconds * 1e3,
                          "gc_collections": gc_collections, **state}
                self.slow_steps += 1
                self.slowest_step = max(self.slowest_step, wall)
                self.slow_step_records.append(record)
                logger.warning(
                    "serve: step %d took %.1f ms (median %.1f, cpu %.1f): "
                    "%s; gc %d in %.1f ms; %s", step, record["wall_ms"],
                    record["median_ms"], record["cpu_ms"],
                    ", ".join(f"{name} {ms:.1f}"
                              for name, ms in spent.items()),
                    gc_collections, record["gc_ms"],
                    ", ".join(f"{key} {value}"
                              for key, value in state.items()))
                if self.tracer is not None:
                    self.tracer.record({"type": "serve_slow_step", **record})
        walls.append(wall)

    def on_rows(self, decoded: int, late: int) -> None:
        """One decode step was read back: it advanced `decoded` rows,
        `late` of them for requests that had already ended."""
        self.rows_decoded += decoded
        self.late_rows += late

    def on_gauges(self, queue_depth: int, live: int, capacity: int) -> None:
        """Sample the queue depth + slot occupancy (once per step)."""
        occupancy = live / capacity if capacity else 0.0
        self.queue_depth.append(queue_depth)
        self.occupancy.append(occupancy)
        if self.tracer is not None:
            self.tracer.counter(COUNTER_QUEUE, depth=queue_depth)
            self.tracer.counter(COUNTER_OCCUPANCY, live=live,
                                occupancy=occupancy)

    # ------------------------------------------------------------------
    # fan-out
    # ------------------------------------------------------------------
    def summary(self) -> tp.Dict[str, float]:
        """Flat numeric snapshot (ms latencies, configurable percentiles)."""
        out: tp.Dict[str, float] = {
            "requests": self.submitted,
            "completed": self.completed,
            "rejected": self.rejected,
            "expired": self.expired,
            "preempted": self.preempted,
            "tokens": self.tokens,
            "slow_steps": self.slow_steps,
            "slowest_step_ms": self.slowest_step * 1e3,
        }
        for name, samples, scale in (("ttft_ms", self.ttft, 1e3),
                                     ("itl_ms", self.itl, 1e3),
                                     ("latency_ms", self.latency, 1e3),
                                     ("queue_wait_ms", self.queue_wait, 1e3),
                                     ("queue_depth", self.queue_depth, 1),
                                     ("occupancy", self.occupancy, 1)):
            for p in self.percentiles:
                out[f"{name}_p{p:g}"] = percentile(samples, p) * scale
        if self.rows_decoded:
            out["steps_in_flight"] = self.steps_in_flight
            out["late_rows"] = self.late_rows
            out["late_row_share"] = self.late_rows / self.rows_decoded
        if self.pool_occupancy:
            for p in self.percentiles:
                out[f"pool_occupancy_p{p:g}"] = percentile(
                    self.pool_occupancy, p)
        if self.prefix_admissions:
            out["prefix_hit_rate"] = (
                self.prefix_matched_tokens / self.prefix_prompt_tokens
                if self.prefix_prompt_tokens else 0.0)
            out["prefix_hit_requests"] = self.prefix_hits
        if self.spec_steps:
            out["spec_drafted"] = self.spec_drafted
            out["spec_emitted"] = self.spec_emitted
            out["acceptance_rate"] = (self.spec_accepted / self.spec_drafted
                                      if self.spec_drafted else 0.0)
            for p in self.percentiles:
                out[f"accepted_per_step_p{p:g}"] = percentile(
                    self.accepted_per_step, p)
        for reason, count in sorted(self.finish_reasons.items()):
            out[f"finish_{reason}"] = count
        return out

    def log_to(self, result_logger: tp.Any, step: tp.Optional[int] = None,
               extra: tp.Optional[tp.Dict[str, float]] = None) -> None:
        """Fan the summary out through a ResultLogger ('serve' stage)."""
        from ..logging import serve_formatter
        metrics = self.summary()
        if extra:
            metrics.update(extra)
        result_logger.log_metrics("serve", metrics, step=step,
                                  formatter=serve_formatter())

    def record(self) -> None:
        """Append the summary to telemetry.jsonl via the tracer."""
        if self.tracer is not None:
            self.tracer.record({"type": "serve_summary", **self.summary()})

    def write_status(self, folder: AnyPath,
                     extra: tp.Optional[tp.Dict[str, tp.Any]] = None) -> Path:
        """Snapshot the summary to `<folder>/serve.json` (atomic) for
        `python -m flashy_tpu.info`; returns the path. When an SLOEngine
        is attached its evaluation lands as the `slo` block (what
        `info --slo` renders); per-tenant request/token/shed rollups
        land as the `tenants` block."""
        target = Path(folder) / SERVE_STATUS_NAME
        payload: tp.Dict[str, tp.Any] = dict(self.static_info)
        payload.update(self.summary())
        if self.tenants:
            payload["tenants"] = {t: dict(counts) for t, counts
                                  in sorted(self.tenants.items())}
        if self.slo is not None:
            payload["slo"] = self.slo.evaluate()
        if extra:
            payload.update(extra)
        target.parent.mkdir(parents=True, exist_ok=True)
        with write_and_rename(target, "w") as f:
            json.dump(payload, f, indent=2, default=float)
            # kill window between tmp-write and rename (same site as
            # fleet.json — one atomic-status discipline, one fault):
            # a fault here must leave the old serve.json (or none),
            # never a torn one, and the next write self-heals.
            from ..resilience import fault_point
            fault_point("fleet.status", file=SERVE_STATUS_NAME)
        return target
