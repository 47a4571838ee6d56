# Tests for the runtime telemetry subsystem (flashy_tpu.observability):
# tracer span nesting + Chrome-trace schema, StepTimer's data-wait /
# host / device split, the recompile watchdog's post-warmup WARNING,
# heartbeat/straggler reporting from per-rank files, and the end-to-end
# acceptance oracle — a dummy-solver stage whose per-step records tile
# the logged stage duration to within 10%.
import json
import logging
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flashy_tpu
from flashy_tpu import observability
from flashy_tpu.data.loader import DataLoader
from flashy_tpu.observability import (
    Heartbeat, RecompileWatchdog, StepTimer, Tracer, straggler_report,
    format_straggler_report,
)
from flashy_tpu.solver import BaseSolver
from flashy_tpu.xp import temporary_xp


@pytest.fixture(autouse=True)
def _no_global_telemetry():
    """Keep the module-global telemetry switch from leaking across tests."""
    yield
    observability.disable_telemetry()


# ----------------------------------------------------------------------
# Tracer
# ----------------------------------------------------------------------
def test_tracer_span_nesting_and_chrome_schema(tmp_path):
    tracer = Tracer(trace_path=tmp_path / "trace.json",
                    jsonl_path=tmp_path / "telemetry.jsonl")
    with tracer.span("outer", epoch=1):
        time.sleep(0.01)
        with tracer.span("inner"):
            time.sleep(0.005)
    tracer.instant("test/marker", note="hi")
    path = tracer.export_chrome_trace()

    payload = json.loads(path.read_text())
    assert "traceEvents" in payload
    events = {e["name"]: e for e in payload["traceEvents"] if e["ph"] == "X"}
    assert set(events) == {"outer", "inner"}
    for event in events.values():  # Chrome trace-event schema
        for key in ("ph", "ts", "dur", "pid", "tid", "args"):
            assert key in event
    # children complete before parents, so inner is recorded FIRST and
    # must be contained in outer's [ts, ts+dur) window (that containment
    # is what Perfetto renders as nesting)
    outer, inner = events["outer"], events["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e3
    assert outer["dur"] >= inner["dur"]
    assert outer["args"] == {"epoch": 1}
    instants = [e for e in payload["traceEvents"] if e["ph"] == "i"]
    assert len(instants) == 1 and instants[0]["args"] == {"note": "hi"}


def test_tracer_decorator_and_journal(tmp_path):
    tracer = Tracer(jsonl_path=tmp_path / "telemetry.jsonl")

    @tracer.wrap(name="work")
    def work(x):
        return x + 1

    assert work(1) == 2
    tracer.record({"type": "custom", "value": 3})
    tracer.close()
    records = [json.loads(line)
               for line in (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    assert records and records[0]["type"] == "custom"
    assert records[0]["value"] == 3
    assert "time" in records[0] and "rank" in records[0]
    assert any(e["name"] == "work" for e in tracer.events)


def test_tracer_event_cap_counts_drops(tmp_path):
    tracer = Tracer(trace_path=tmp_path / "trace.json", max_events=3)
    for i in range(10):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.dropped > 0
    payload = json.loads(tracer.export_chrome_trace().read_text())
    assert payload["metadata"]["dropped_events"] == tracer.dropped
    assert len(payload["traceEvents"]) == 3


# ----------------------------------------------------------------------
# StepTimer
# ----------------------------------------------------------------------
class _SlowDataset:
    """Synthetic loader whose per-sample cost is a controlled sleep."""

    def __init__(self, n=24, dim=4, delay=0.004):
        self.data = np.zeros((n, dim), np.float32)
        self.delay = delay

    def __len__(self):
        return len(self.data)

    def __getitem__(self, index):
        time.sleep(self.delay)
        return self.data[index]


def test_steptimer_splits_data_wait_from_host():
    timer = StepTimer(stage="train")
    for _ in range(3):
        timer.begin_data()
        time.sleep(0.01)     # "loader" time
        timer.end_data()
        time.sleep(0.003)    # "host" time
    timer.finish()
    assert len(timer.records) == 3
    for record in timer.records:
        assert record["data_wait"] >= 0.009
        assert record["host"] >= 0.002
        assert record["total"] >= record["data_wait"] + record["host"] - 1e-9
    summary = timer.summary()
    assert summary["steps"] == 3
    assert summary["step_p50"] <= summary["step_p95"] <= summary["step_max"]
    # data-wait dominates this loop by construction
    assert summary["data_wait_frac"] > summary["host_frac"]


def test_steptimer_through_progress_bar_on_slow_loader():
    """The wired path: LogProgressBar drives the timer; a slow dataset
    shows up as data_wait, the loop body as host."""
    tracer_records = []

    class _Sink:
        def record(self, rec):
            tracer_records.append(rec)

        def complete(self, *a, **k):
            pass

    timer = StepTimer(stage="train", tracer=_Sink())
    loader = DataLoader(_SlowDataset(n=16, delay=0.004), batch_size=4)
    bar = flashy_tpu.LogProgressBar(
        logging.getLogger(__name__), loader, updates=0, step_timer=timer)
    for batch in bar:
        time.sleep(0.006)  # host work
        bar.update(loss=0.0)
    assert len(timer.records) == 4
    for record in timer.records:
        assert record["data_wait"] >= 0.012  # 4 samples x 4ms, minus jitter
        assert record["host"] >= 0.005
    assert [r["type"] for r in tracer_records] == ["step"] * 4
    assert {"data_wait", "host", "device", "total"} <= set(tracer_records[0])


def test_steptimer_device_time_via_observe():
    timer = StepTimer(stage="train")
    x = jnp.ones((256, 256))
    step = jax.jit(lambda a: a @ a)
    for _ in range(3):
        timer.begin_data()
        timer.end_data()
        out = step(x)
        timer.observe(out)
    timer.finish()
    assert len(timer.records) == 3
    # device is bounded (>= 0) and blocking happened at the boundary:
    # totals cover host + device exactly
    for record in timer.records:
        assert record["device"] >= 0.0
        assert record["total"] == pytest.approx(
            record["data_wait"] + record["host"] + record["device"])


def test_steptimer_charges_observe_wait_to_device(monkeypatch):
    """The canonical loop floats the observed outputs into an averager
    right after observe(); blocking at the observe call (not the next
    boundary) is what keeps the device wait out of host."""
    def slow_block(x):
        time.sleep(0.02)
        return x

    monkeypatch.setattr(jax, "block_until_ready", slow_block)
    timer = StepTimer(stage="train")
    timer.begin_data()
    timer.end_data()
    timer.observe(jnp.ones(()))
    time.sleep(0.005)        # post-observe host work (the averager)
    timer.finish()
    (record,) = timer.records
    assert record["device"] >= 0.019
    assert record["host"] < 0.019          # the block is NOT in host
    assert record["total"] == pytest.approx(
        record["data_wait"] + record["host"] + record["device"])


# ----------------------------------------------------------------------
# Recompile watchdog
# ----------------------------------------------------------------------
def test_recompile_watchdog_warns_on_shape_churn(caplog):
    watchdog = RecompileWatchdog(warmup=1)
    step = watchdog.watch(jax.jit(lambda x: x * 2), name="churn_step")
    with caplog.at_level(logging.WARNING,
                         logger="flashy_tpu.observability.watchdog"):
        step(jnp.zeros((4,)))    # warm-up compile: silent
        assert not caplog.records
        step(jnp.zeros((4,)))    # cache hit: silent
        assert not caplog.records
        step(jnp.zeros((5,)))    # shape churn -> recompile -> WARNING
    assert len(caplog.records) == 1
    message = caplog.records[0].getMessage()
    assert "churn_step" in message          # names the function
    assert "float32[5]" in message          # and the offending shapes
    assert watchdog.summary() == {"churn_step": 1}
    assert watchdog.counts["churn_step"]["compiles"] == 2
    assert watchdog.counts["churn_step"]["calls"] == 3


def test_recompile_watchdog_warmup_budget(caplog):
    # warmup=2 tolerates a train/eval shape pair without warning
    watchdog = RecompileWatchdog(warmup=2)
    step = watchdog.watch(jax.jit(lambda x: x + 1), name="two_shapes")
    with caplog.at_level(logging.WARNING,
                         logger="flashy_tpu.observability.watchdog"):
        step(jnp.zeros((8,)))
        step(jnp.zeros((2,)))
    assert not caplog.records
    assert watchdog.summary() == {}


def test_recompile_watchdog_rejects_plain_function():
    with pytest.raises(TypeError, match="jax.jit"):
        RecompileWatchdog().watch(lambda x: x)


# ----------------------------------------------------------------------
# Heartbeats + stragglers
# ----------------------------------------------------------------------
def test_heartbeat_write_throttle_and_read(tmp_path):
    hb = Heartbeat(tmp_path, rank=0, world_size=1, interval=60.0,
                   with_device_stats=False)
    assert hb.beat(step=1, stage="train", force=True)
    assert not hb.beat(step=2)          # throttled
    assert hb.beat(step=3, stage="train", force=True)  # forced boundary beat
    beats = observability.read_heartbeats(tmp_path)
    assert len(beats) == 1
    assert beats[0]["step"] == 3 and beats[0]["stage"] == "train"
    assert beats[0]["rank"] == 0 and "pid" in beats[0]


def test_straggler_report_from_fabricated_ranks(tmp_path):
    now = time.time()
    for rank, (step, age) in enumerate([(120, 1.0), (117, 2.0), (95, 300.0)]):
        (tmp_path / f"rank{rank}.json").write_text(json.dumps({
            "rank": rank, "world_size": 4, "time": now - age,
            "step": step, "epoch": 3, "stage": "train"}))
    report = straggler_report(tmp_path, now=now)
    assert report["ranks"] == 3
    assert report["expected"] == 4
    assert report["missing"] == [3]                 # rank 3 never beat
    assert report["max_step_skew"] == 25            # 120 - 95
    assert report["stalest_rank"] == 2
    assert report["stalest_age"] == pytest.approx(300.0, abs=1.0)
    text = format_straggler_report(report)
    assert "3/4 ranks" in text and "step skew 25" in text
    assert "missing 3" in text and "stalest rank 2" in text

    # corrupt file (mid-rewrite): skipped, not fatal
    (tmp_path / "rank9.json").write_text("{not json")
    assert straggler_report(tmp_path, now=now)["ranks"] == 3

    assert straggler_report(tmp_path / "nope") == {"ranks": 0}


def test_device_memory_stats_cpu_safe():
    stats = observability.device_memory_stats()
    # CPU backend exposes no memory_stats, but the call must not raise
    # and still lists the devices
    assert isinstance(stats, list) and stats
    assert {"id", "platform", "kind"} <= set(stats[0])


def test_info_cli_surfaces_straggler_report(tmp_path, capsys):
    from flashy_tpu import info

    xp_dir = tmp_path / "xps" / "abc12345"
    hb_dir = xp_dir / "heartbeats"
    hb_dir.mkdir(parents=True)
    (xp_dir / "history.json").write_text(json.dumps([{"train": {"loss": 1.0}}]))
    now = time.time()
    for rank, step in enumerate([10, 7]):
        (hb_dir / f"rank{rank}.json").write_text(json.dumps({
            "rank": rank, "world_size": 2, "time": now, "step": step}))
    assert info.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "heartbeats: 2/2 ranks" in out
    assert "step skew 3" in out


# ----------------------------------------------------------------------
# End-to-end: dummy solver with telemetry (the acceptance oracle)
# ----------------------------------------------------------------------
class _TelemetrySolver(BaseSolver):
    def __init__(self):
        super().__init__()
        self.w = jnp.ones((8, 8))
        self.register_stateful("w")
        self.loader = DataLoader(_SlowDataset(n=40, dim=8, delay=0.003),
                                 batch_size=4)
        self._step = jax.jit(lambda w, x: (w + 1e-3 * x.T @ x,
                                           jnp.mean(x @ w)))

    def do_train(self):
        average = flashy_tpu.averager()
        progress = self.log_progress("train", self.loader, updates=2)
        metrics = {}
        for batch in progress:
            self.w, loss = self._step(self.w, jnp.asarray(batch))
            progress.observe((self.w, loss))
            metrics = average({"loss": loss})
            progress.update(**metrics)
        return metrics


def test_dummy_solver_telemetry_end_to_end():
    with temporary_xp({"telemetry": 1}) as xp:
        solver = _TelemetrySolver()
        telemetry = solver.enable_telemetry(heartbeat_interval=0.0)
        solver._step = telemetry.watch(solver._step, name="train_step")
        metrics = solver.run_stage("train", solver.do_train)
        solver.commit()

        # StepTimer summary landed in the stage metrics (and history)
        assert metrics["steps"] == 10
        assert metrics["step_p50"] <= metrics["step_p95"] <= metrics["step_max"]
        assert solver.history[0]["train"]["step_p95"] == metrics["step_p95"]

        # valid Chrome-trace JSON with the stage span and step lanes
        trace = json.loads((xp.folder / "trace.json").read_text())
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"stage/train", "step/data_wait", "step/host",
                "data/fetch"} <= names

        # telemetry.jsonl: per-step records whose splits tile the stage
        # duration to within 10%
        records = [json.loads(line) for line in
                   (xp.folder / "telemetry.jsonl").read_text().splitlines()]
        steps = [r for r in records if r["type"] == "step"]
        assert len(steps) == 10
        for record in steps:
            assert {"data_wait", "host", "device"} <= set(record)
        covered = sum(r["data_wait"] + r["host"] + r["device"] for r in steps)
        assert covered == pytest.approx(metrics["duration"], rel=0.10)
        assert any(r["type"] == "stage" for r in records)

        # heartbeats were beaten with step/stage context
        report = straggler_report(xp.folder / "heartbeats")
        assert report["ranks"] == 1
        assert report["per_rank"][0]["stage"] == "train"


def test_dummy_solver_telemetry_recompile_warning(caplog):
    with temporary_xp({"telemetry": 2}):
        solver = _TelemetrySolver()
        telemetry = solver.enable_telemetry(heartbeat_interval=60.0)
        solver._step = telemetry.watch(solver._step, name="train_step")
        solver.run_stage("train", solver.do_train)
        with caplog.at_level(logging.WARNING,
                             logger="flashy_tpu.observability.watchdog"):
            # a stray non-static batch shape -> recompile -> named WARNING
            solver._step(solver.w, jnp.zeros((7, 8)))
        assert any("train_step" in r.getMessage() for r in caplog.records)
        # ...and the NEXT stage's metrics expose the recompile count
        metrics = solver.run_stage("extra", lambda: {})
        assert metrics["recompiles"] == 1
        # the metric is a per-stage delta: a recompile long ago must not
        # read as "recompiling every stage"
        metrics = solver.run_stage("extra2", lambda: {})
        assert "recompiles" not in metrics


def test_raising_stage_journals_inflight_step():
    """A step that crashes mid-stage is exactly the record you want
    post-mortem: run_stage's finally must finish the timer so the
    in-flight step reaches telemetry.jsonl before the export."""
    with temporary_xp({"telemetry": 5}) as xp:
        solver = _TelemetrySolver()
        solver.enable_telemetry(heartbeat_interval=60.0)

        def explode():
            progress = solver.log_progress("train", solver.loader, updates=2)
            for i, batch in enumerate(progress):
                solver.w, loss = solver._step(solver.w, jnp.asarray(batch))
                progress.observe((solver.w, loss))
                if i == 2:
                    raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            solver.run_stage("train", explode)
        records = [json.loads(line) for line in
                   (xp.folder / "telemetry.jsonl").read_text().splitlines()]
        steps = [r for r in records if r["type"] == "step"]
        assert len(steps) == 3 and steps[-1]["step"] == 2
        # the stage record and trace still exported despite the raise
        assert any(r["type"] == "stage" for r in records)
        # and the timer slot was cleared — the next stage starts fresh
        assert not solver._step_timers


def test_second_loader_in_stage_finishes_abandoned_timer():
    """A stage that abandons one progress bar mid-iteration and opens a
    second must not silently drop the first loader's in-flight step."""
    with temporary_xp({"telemetry": 6}) as xp:
        solver = _TelemetrySolver()
        solver.enable_telemetry(heartbeat_interval=60.0)

        def two_loaders():
            progress = solver.log_progress("train", solver.loader, updates=2)
            for i, batch in enumerate(progress):
                solver.w, loss = solver._step(solver.w, jnp.asarray(batch))
                progress.observe((solver.w, loss))
                if i == 1:
                    break               # abandoned with a step in flight
            progress = solver.log_progress("train", solver.loader, updates=2)
            for batch in progress:
                solver.w, loss = solver._step(solver.w, jnp.asarray(batch))
                progress.observe((solver.w, loss))
            return {}

        metrics = solver.run_stage("train", two_loaders)
        records = [json.loads(line) for line in
                   (xp.folder / "telemetry.jsonl").read_text().splitlines()]
        steps = [r for r in records if r["type"] == "step"]
        # 2 from the abandoned loader (incl. its in-flight step) + 10
        assert len(steps) == 12
        # the summary reflects the live (second) timer
        assert metrics["steps"] == 10


def test_telemetry_disabled_is_free():
    # without enable_telemetry, no timers attach and no artifacts appear
    with temporary_xp({"telemetry": 3}) as xp:
        solver = _TelemetrySolver()
        solver.run_stage("train", solver.do_train)
        assert not (xp.folder / "telemetry.jsonl").exists()
        assert not (xp.folder / "trace.json").exists()
        assert not (xp.folder / "heartbeats").exists()


def test_dummy_fixture_cli_with_telemetry(tmp_path):
    """The real tests/dummy fixture, driven through the CLI with
    `telemetry=true`: artifacts appear in the XP folder and the step
    records carry the split fields."""
    import os

    env = dict(os.environ)
    env["_FLASHY_TMDIR"] = str(tmp_path)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    subprocess.run(
        [sys.executable, "-m", "tests.dummy.train", "--clear",
         "telemetry=true", "stop_at=1"],
        check=True, env=env, timeout=300)
    (sig,) = (tmp_path / "xps").iterdir()
    trace = json.loads((sig / "trace.json").read_text())
    assert {"stage/train", "stage/valid", "step/host"} <= {
        e["name"] for e in trace["traceEvents"]}
    records = [json.loads(line)
               for line in (sig / "telemetry.jsonl").read_text().splitlines()]
    steps = [r for r in records if r["type"] == "step"]
    assert steps and all(
        {"data_wait", "host", "device"} <= set(r) for r in steps)
    assert straggler_report(sig / "heartbeats")["ranks"] == 1
    # history carries the step summaries for both stages
    history = json.loads((sig / "history.json").read_text())
    assert history[0]["train"]["steps"] > 0
    assert history[0]["valid"]["step_p95"] >= 0


# ----------------------------------------------------------------------
# CI guards: import hygiene + docs coverage
# ----------------------------------------------------------------------
def test_observability_import_is_tpu_free():
    """`import flashy_tpu.observability` must not pull TPU-only deps or
    initialize a JAX backend at module load (heartbeat device stats and
    block_until_ready import jax lazily, inside the functions that need
    devices). JAX_PLATFORMS=tpu in the child: a device query at import
    would fail loudly on this TPU-less host."""
    code = "\n".join([
        "import sys",
        "import flashy_tpu.observability",
        "banned = [m for m in sys.modules if m.split('.')[0] in",
        "          ('libtpu', 'torch', 'wandb', 'tensorboard', 'tensorboardX')]",
        "assert not banned, banned",
        "from jax._src import xla_bridge",
        "assert not xla_bridge._backends, 'backend initialized at import'",
    ])
    result = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        env={**__import__('os').environ, "JAX_PLATFORMS": "tpu"})
    assert result.returncode == 0, result.stderr


def test_gendocs_covers_observability(tmp_path):
    import tools.gendocs as gendocs

    rc = gendocs.main(["-o", str(tmp_path), "-p", "flashy_tpu.observability",
                       "-c", "flashy_tpu.observability",
                       "-c", "flashy_tpu.observability.tracer",
                       "-c", "flashy_tpu.observability.steptimer",
                       "-c", "flashy_tpu.observability.watchdog",
                       "-c", "flashy_tpu.observability.heartbeat",
                       "-c", "flashy_tpu.observability.telemetry"])
    assert rc == 0
    page = (tmp_path / "flashy_tpu.observability.html").read_text()
    for name in ("Tracer", "StepTimer", "RecompileWatchdog", "Heartbeat",
                 "enable_telemetry"):
        assert name in page
    # the check flag is a real guard: a bogus module fails the run
    assert gendocs.main(["-o", str(tmp_path), "-p", "flashy_tpu.observability",
                         "-c", "flashy_tpu.observability.nope"]) == 1