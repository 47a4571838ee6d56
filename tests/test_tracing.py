# Request-scoped tracing and SLO burn-rate alerting: lifecycle
# completeness (every submitted request reaches a terminal journal
# event with named phases, whatever its fate), the crash-closes-spans
# convention, deterministic sampling + the slow-tail retroactive
# capture, burn-rate alerts under injected latency (and silence on a
# clean run), and requests.jsonl rotation.
import json
import time

import numpy as np
import pytest

from flashy_tpu import observability
from flashy_tpu.observability import SLOBudget, SLOEngine, Tracer
from flashy_tpu.resilience import chaos
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from flashy_tpu.serve.metrics import ServeMetrics
from flashy_tpu.serve.tracing import (
    RequestTracer, SPAN_DECODE, SPAN_PREFILL, SPAN_QUEUED, SPAN_REQUEST,
)


@pytest.fixture(autouse=True)
def _no_global_state():
    """Keep module-global telemetry and chaos hooks from leaking."""
    yield
    observability.disable_telemetry()
    try:
        chaos.uninstall()
    except Exception:  # noqa: BLE001 — strict uninstall may raise
        pass


def _tiny_model(vocab=32, max_seq_len=32):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=vocab, dim=16, num_layers=2,
                            num_heads=2, attention="dense",
                            max_seq_len=max_seq_len, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32))
    return model, params


def _journal_events(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _traced_scheduler(tmp_path, slots=2, **tracer_kwargs):
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=slots)
    engine.warmup(prompt_lengths=[4, 6])
    tracer = Tracer(trace_path=tmp_path / "trace.json")
    tracing = RequestTracer(tracer=tracer,
                            journal_path=tmp_path / "requests.jsonl",
                            **tracer_kwargs)
    scheduler = ContinuousBatchingScheduler(engine, max_queue=4,
                                            tracing=tracing)
    return scheduler, tracing, tracer


# ----------------------------------------------------------------------
# lifecycle completeness
# ----------------------------------------------------------------------
def test_every_fate_lands_in_the_journal_with_phases(tmp_path):
    from flashy_tpu.serve import QueueFull

    scheduler, tracing, tracer = _traced_scheduler(tmp_path)
    prompt = np.arange(4, dtype=np.int32) % 32

    done = [scheduler.submit(prompt, max_new_tokens=2) for _ in range(3)]
    expired = scheduler.submit(prompt, max_new_tokens=2, ttl=1e-9)
    with pytest.raises(QueueFull):
        scheduler.submit(prompt, max_new_tokens=2)  # queue cap is 4
    time.sleep(0.005)  # let the tiny TTL lapse while still queued
    scheduler.run()
    tracing.close()
    tracer.close()

    events = _journal_events(tmp_path / "requests.jsonl")
    finished = {e["uid"]: e for e in events if e["event"] == "finished"}
    # every submitted request — completed or shed — reached a terminal
    # journal record carrying its named phases
    for handle in done:
        entry = finished[handle.uid]
        assert entry["reason"] in ("eos", "length")
        assert entry["tokens"] == len(handle.generated)
        assert entry["queue_wait_s"] >= 0.0
        assert entry["prefill_s"] >= 0.0
        assert entry["decode_s"] >= 0.0
        assert entry["ttft_s"] <= entry["latency_s"]
    assert finished[expired.uid]["reason"] == "expired"
    assert "prefill_s" not in finished[expired.uid]  # never admitted
    # the bounced submit has no uid (no Request was created) but is
    # still journaled with the queue depth that rejected it
    rejected = [e for e in events if e["event"] == "rejected"]
    assert len(rejected) == 1 and rejected[0]["queue_depth"] == 4
    assert tracing.rejected_count == 1
    assert tracing.finished_count == 4

    # the Perfetto side: one balanced async begin/end pair of the outer
    # request span per uid, and balanced phase spans underneath
    payload = json.loads((tmp_path / "trace.json").read_text())
    opened, closed = {}, {}
    for event in payload["traceEvents"]:
        if event.get("ph") == "b":
            opened[(event["name"], event["id"])] = \
                opened.get((event["name"], event["id"]), 0) + 1
        elif event.get("ph") == "e":
            closed[(event["name"], event["id"])] = \
                closed.get((event["name"], event["id"]), 0) + 1
    assert opened == closed
    for handle in done:
        for name in (SPAN_REQUEST, SPAN_QUEUED, SPAN_PREFILL, SPAN_DECODE):
            assert opened[(name, f"0x{handle.uid:x}")] == 1
    # the expired request opened (and closed) only queued + request
    assert (SPAN_PREFILL, f"0x{expired.uid:x}") not in opened


def test_crash_mid_step_closes_every_inflight_span(tmp_path):
    scheduler, tracing, tracer = _traced_scheduler(tmp_path)
    prompt = np.arange(4, dtype=np.int32) % 32
    handles = [scheduler.submit(prompt, max_new_tokens=8) for _ in range(2)]
    scheduler.step()  # admit + first tokens; the first decode is launched
    # one step stays in flight: the crash below finds it unread
    assert not scheduler.idle
    assert [len(h.generated) for h in handles] == [1, 1]
    assert [h.in_flight for h in handles] == [1, 1]

    injector = chaos.install()
    injector.act_at("serve.step", call=injector.counts.get("serve.step", 0)
                    + 1, action=lambda: (_ for _ in ()).throw(
                        RuntimeError("injected mid-step crash")))
    with pytest.raises(RuntimeError, match="injected"):
        scheduler.step()
    tracer.close()

    # no dangling spans: the trace is loadable and balanced, and the
    # journal says how far each request got
    payload = json.loads((tmp_path / "trace.json").read_text())
    begins = sum(1 for e in payload["traceEvents"] if e.get("ph") == "b")
    ends = sum(1 for e in payload["traceEvents"] if e.get("ph") == "e")
    assert begins == ends and begins > 0
    finished = {e["uid"]: e for e in
                _journal_events(tmp_path / "requests.jsonl")
                if e["event"] == "finished"}
    for handle in handles:
        assert finished[handle.uid]["reason"] == "crashed"
        assert finished[handle.uid]["latency_s"] > 0.0


# ----------------------------------------------------------------------
# sampling + slow tail
# ----------------------------------------------------------------------
def test_sampling_is_deterministic_and_near_rate():
    a = RequestTracer(sample_rate=0.5, seed=3)
    b = RequestTracer(sample_rate=0.5, seed=3)
    other = RequestTracer(sample_rate=0.5, seed=4)
    uids = range(2000)
    decisions = [a.sampled(u) for u in uids]
    assert decisions == [b.sampled(u) for u in uids]  # reproducible
    assert decisions != [other.sampled(u) for u in uids]  # seed matters
    assert 0.45 < sum(decisions) / len(decisions) < 0.55
    assert all(RequestTracer(sample_rate=1.0).sampled(u) for u in uids)
    assert not any(RequestTracer(sample_rate=0.0).sampled(u) for u in uids)


def test_slow_unsampled_request_is_captured_retroactively(tmp_path):
    # sampling=0 drops everything — EXCEPT a request finishing past the
    # slow threshold, which must still land in the journal and get its
    # historical phase spans in the trace
    scheduler, tracing, tracer = _traced_scheduler(
        tmp_path, sample_rate=0.0, slow_latency=1e-6)
    prompt = np.arange(4, dtype=np.int32) % 32
    handle = scheduler.submit(prompt, max_new_tokens=2)
    scheduler.run()
    tracing.close()
    tracer.close()

    assert tracing.sampled_count == 0 and tracing.slow_count == 1
    finished = [e for e in _journal_events(tmp_path / "requests.jsonl")
                if e["event"] == "finished"]
    assert len(finished) == 1
    assert finished[0]["uid"] == handle.uid
    assert finished[0]["slow"] is True and finished[0]["sampled"] is False
    payload = json.loads((tmp_path / "trace.json").read_text())
    slow_spans = [e for e in payload["traceEvents"]
                  if e.get("ph") == "X" and e["args"].get("slow")]
    assert {e["name"] for e in slow_spans} == {SPAN_QUEUED, SPAN_PREFILL,
                                              SPAN_DECODE}
    # historical, not emission-time: phases nest inside [submit, end]
    for span in slow_spans:
        assert span["dur"] >= 0


# ----------------------------------------------------------------------
# SLO burn-rate alerting
# ----------------------------------------------------------------------
def _serve_with_slo(injected_sleep_s):
    # an ITL sample is the whole scheduler step that produced the token,
    # the prefill it carried included (tens of ms for this toy on a
    # loaded CPU), so the budget sits well above a clean step
    budgets = (SLOBudget("itl", threshold=0.25, percentile=95.0),)
    slo = SLOEngine(budgets=budgets, min_samples=8)
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2)
    engine.warmup(prompt_lengths=[4])
    metrics = ServeMetrics(slo=slo)
    scheduler = ContinuousBatchingScheduler(engine, metrics=metrics)
    if injected_sleep_s:
        injector = chaos.install()
        injector.act_at("serve.step", call=1,
                        action=lambda: time.sleep(injected_sleep_s),
                        times=1000)
    prompt = np.arange(4, dtype=np.int32) % 32
    for _ in range(4):
        scheduler.submit(prompt, max_new_tokens=6)
    scheduler.run()
    return slo, metrics


def test_slo_alert_fires_under_injected_latency_and_not_clean():
    # a 300ms sleep injected into EVERY scheduler step blows a 250ms ITL
    # budget on nearly every sample: both burn windows saturate
    slo, metrics = _serve_with_slo(injected_sleep_s=0.3)
    assert slo.alerts() == ["itl"]
    report = slo.evaluate()
    entry = report["budgets"]["itl"]
    assert report["alerting"] and entry["alerting"]
    assert entry["burn_fast"] > slo.burn_threshold
    assert entry["burn_slow"] > slo.burn_threshold
    assert not entry["compliant"]
    chaos.uninstall()

    # the same budget on an uninjected run stays silent (a CPU step of
    # the tiny model, prefill included, is well under 250ms)
    slo, metrics = _serve_with_slo(injected_sleep_s=0)
    assert slo.alerts() == []
    report = slo.evaluate()
    assert not report["alerting"]
    assert report["budgets"]["itl"]["samples"] >= slo.min_samples
    # and the report rides the status snapshot ServeMetrics writes
    summary_report = metrics.slo.evaluate()
    assert set(summary_report["budgets"]) == {"itl"}


def test_slo_engine_multiwindow_rule_is_deterministic():
    # a burst of violations INSIDE the fast window alerts only once the
    # slow window confirms it — fed with explicit timestamps, no clock
    budget = SLOBudget("ttft", threshold=1.0, percentile=90.0)
    slo = SLOEngine(budgets=(budget,), fast_window=10.0, slow_window=100.0,
                    burn_threshold=2.0, min_samples=4)
    # 20 compliant samples spread over the slow window
    for i in range(20):
        slo.observe("ttft", 0.1, now=float(i))
    report = slo.evaluate(now=20.0)
    assert not report["alerting"]
    # violations only in the fast window: slow burn stays diluted
    for i in range(4):
        slo.observe("ttft", 5.0, now=20.0 + i)
    entry = slo.evaluate(now=24.0)["budgets"]["ttft"]
    assert entry["burn_fast"] > 2.0
    assert not entry["alerting"]  # slow window not burning yet
    # sustained violations: both windows burn -> alert
    for i in range(20):
        slo.observe("ttft", 5.0, now=25.0 + i)
    entry = slo.evaluate(now=45.0)["budgets"]["ttft"]
    assert entry["alerting"]


# ----------------------------------------------------------------------
# journal rotation
# ----------------------------------------------------------------------
def test_requests_journal_rotation_round_trip(tmp_path):
    class FakeRequest:
        def __init__(self, uid):
            self.uid = uid
            self.prompt = np.zeros(4, np.int32)
            self.max_new_tokens = 2
            self.submitted_at = time.perf_counter()
            self.generated = [1, 2]

    path = tmp_path / "requests.jsonl"
    tracing = RequestTracer(journal_path=path, max_journal_bytes=2048,
                            journal_keep=2)
    for uid in range(120):
        request = FakeRequest(uid)
        tracing.on_submit(request)
        tracing.on_admit(request, slot=0)
        tracing.on_first_token(request)
        tracing.on_finish(request, "length")
    tracing.close()

    assert tracing.journal_rotations > 0
    assert path.exists() and (tmp_path / "requests.jsonl.1").exists()
    # every surviving line — current file and rotated siblings — parses,
    # and the newest rotated-out data is contiguous with the live file
    siblings = sorted(tmp_path.glob("requests.jsonl*"))
    uids = []
    for file in siblings:
        for event in _journal_events(file):
            if event.get("event") == "finished":
                uids.append(event["uid"])
    # the rotation itself is journaled as the new file's first line
    notes = [e for e in _journal_events(path)
             if e.get("type") == "journal_rotated"]
    assert notes and notes[0]["rotation"] == tracing.journal_rotations
    # rotation drops only the OLDEST records: what survives is a
    # contiguous tail ending at the last request
    tail = sorted(uids)
    assert tail[-1] == 119
    assert tail == list(range(tail[0], 120))
