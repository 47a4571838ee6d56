# One step in flight: the scheduler dispatches step k and only then reads
# step k - 1 (serve/scheduler.py, serve/engine.py: dispatch / collect).
# What must hold, on toy engines of every pool the benchmark's cells run
# (K/V int8 paged, latent, grouped with window rings, recurrent state)
# plus dense, on the CPU: the tokens served are the lock-step parent's,
# bit for bit, sampling keys included; a late token reaches only the
# request that owned the row when its step was launched; an EOS costs one
# dropped row and never writes outside the request's reservation; whoever
# reads a request's tokens from outside the step finds them complete; the
# tap holds the logits of the tokens delivered; nothing compiles or
# lowers after warm-up.
"""The one-step-deep pipeline between scheduler and engine."""
import json
import os

import jax
import numpy as np
import pytest

from flashy_tpu.serve import ContinuousBatchingScheduler
from tests.data.record_lockstep_served_tokens import (KINDS, engine_of,
                                                      prompts, served)

PAGED = tuple(kind for kind in KINDS if kind != "dense")


@pytest.fixture(scope="module")
def engines():
    """One warm greedy engine a kind, shared: every test leaves it with
    no live slot (`_drive` asserts it)."""
    cache = {}

    def get(kind):
        if kind not in cache:
            cache[kind] = engine_of(kind)
        return cache[kind]

    return get


@pytest.fixture(scope="module")
def streams(engines):
    """The greedy stream of each of `prompts()` a kind, from an engine
    driven BY HAND in lock-step, one request at a time: slices and decode
    steps read back one by one, no scheduler."""
    cache = {}

    def get(kind):
        if kind not in cache:
            engine = engines(kind)
            cache[kind] = [_by_hand(engine, prompt, 8)
                           for prompt, _ in prompts()]
        return cache[kind]

    return get


def _by_hand(engine, prompt, budget):
    slot = engine.acquire_slot()
    start, first = engine.admit(slot, prompt, budget), None
    while first is None:
        start, first = engine.prefill_chunk(slot, prompt, start)
    tokens = [first]
    while len(tokens) < budget:
        tokens.append(int(engine.decode()[slot]))
    engine.retire(slot)
    return tokens


def _drive(scheduler, handles, max_steps=200):
    """Step until idle. After every step: the pool's invariants; with a
    tap, the tapped rows are the logits of the tokens that step
    delivered (as benchmarks/runners/serve_family.py pairs them)."""
    engine = scheduler.engine
    for _ in range(max_steps):
        if scheduler.idle:
            break
        had = [(len(h.generated), h.state) for h in handles]
        scheduler.step()
        if engine.pool is not None:
            engine.pool.check()
        if not engine.keep_logits:
            continue
        for handle, (before, state) in zip(handles, had):
            new = handle.generated[before:]
            if new and state != "running":  # a (resumed) prompt's token
                logits = np.asarray(engine.tapped["prefill_chunk"])[0]
                assert int(logits.argmax()) == new.pop(0)
            for token in new:  # at most one decode token a step
                row = np.asarray(engine.tapped["decode"])[handle.slot]
                assert int(row.argmax()) == token
    assert scheduler.idle and scheduler._in_flight is None
    assert engine.live_count == 0
    assert all(h.in_flight == 0 for h in handles)


@pytest.mark.parametrize("kind", KINDS)
def test_sampled_tokens_are_the_lockstep_parents(kind):
    # temperature 0.8 from one key: the tokens AND the order the keys
    # are drawn in, against what the parent of PR 34 served
    recorded = os.path.join(os.path.dirname(__file__), "data",
                            "lockstep_served_tokens.json")
    with open(recorded) as f:
        want = json.load(f)[kind]
    assert served(kind) == want
    assert [len(tokens) for tokens in want] == [b for _, b in prompts()]


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_streams_pool_tap_and_no_build_after_warmup(kind, engines,
                                                           streams):
    engine, want = engines(kind), streams(kind)
    warm = engine.compile_cache.stats()
    lowered = []

    def listener(event, seconds, **kwargs):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(kwargs.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        scheduler = ContinuousBatchingScheduler(engine)
        handles = [scheduler.submit(prompt, budget)
                   for prompt, budget in prompts()]
        _drive(scheduler, handles)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    for handle, stream, (_, budget) in zip(handles, want, prompts()):
        assert handle.generated == stream[:budget]
        assert handle.finish_reason == "length"
    stats = engine.compile_cache.stats()
    assert stats["misses"] == warm["misses"] and not stats["recompiles"]
    # the eager row writes of retire lowered in warm-up too
    assert not lowered
    metrics = scheduler.metrics
    # budgets end by count: no row is ever decoded for a finished request
    assert metrics.late_rows == 0 and metrics.rows_decoded == sum(
        budget - 1 for _, budget in prompts())
    assert 0 < metrics.steps_in_flight < scheduler.steps
    assert metrics.summary()["late_row_share"] == 0.0


def _eos_case(engine, length, budget, lo, hi):
    """(prompt, greedy stream, cut): a prompt of `length` tokens whose
    by-hand stream of `budget` has, at an index `cut` in [lo, hi), a
    token that occurs nowhere before it — an EOS that fires exactly
    there (greedy toy streams cycle, so seeds are tried in order)."""
    for seed in range(64):
        prompt = np.random.default_rng(seed).integers(
            1, 64, length).astype(np.int32)
        stream = _by_hand(engine, prompt, budget)
        for cut in range(lo, hi):
            if stream[cut] not in stream[:cut]:
                return prompt, stream, cut
    raise AssertionError("no seed gives a stream with a fresh token there")


@pytest.mark.parametrize("kind", KINDS)
def test_an_eos_is_seen_one_step_late_and_crosses_to_nobody(kind, engines,
                                                            streams):
    # two slots, three requests: A ends by EOS in mid-stream (one row
    # decoded late, dropped), C waits for A's slot and is admitted the
    # step after the late retire, B decodes across both
    engine, want = engines(kind), streams(kind)
    (b, _), (c, _) = prompts()[3], prompts()[5]
    a, stream, cut = _eos_case(engine, 9, 8, 1, 6)
    scheduler = ContinuousBatchingScheduler(engine)
    first = scheduler.submit(a, 8, eos_token=stream[cut])
    second = scheduler.submit(b, 8)
    third = scheduler.submit(c, 6)
    _drive(scheduler, [first, second, third])
    assert first.generated == stream[:cut + 1]
    assert first.finish_reason == "eos"
    assert second.generated == want[3] and third.generated == want[5][:6]
    assert third.slot == first.slot  # the slot was handed on
    assert scheduler.metrics.late_rows == 1
    assert scheduler.metrics.summary()["late_rows"] == 1
    assert 0 < scheduler.metrics.summary()["late_row_share"] < 0.1


@pytest.mark.parametrize("kind", KINDS)
def test_a_first_token_that_is_eos_and_a_budget_of_one(kind, engines,
                                                       streams):
    engine, want = engines(kind), streams(kind)
    prompt = prompts()[0][0]
    scheduler = ContinuousBatchingScheduler(engine)
    handle = scheduler.submit(prompt, 8, eos_token=want[0][0])
    _drive(scheduler, [handle])
    assert handle.generated == want[0][:1] and handle.finish_reason == "eos"
    # the row went live with its slice: it decoded in that step and in
    # the next before the first token was read
    assert scheduler.metrics.late_rows == 2
    # a budget of one is known by count: the slot is parked when the
    # final slice is dispatched, no decode row is launched for it
    scheduler = ContinuousBatchingScheduler(engine)
    handle = scheduler.submit(prompt, 1)
    steps = 0
    while not handle.in_flight:
        scheduler.step()
        steps += 1
    assert engine.live_count == 0 and not handle.done
    assert not scheduler.idle and handle.generated == []
    _drive(scheduler, [handle])
    assert handle.generated == want[0][:1]
    assert handle.finish_reason == "length"
    assert scheduler.metrics.rows_decoded == 0


@pytest.mark.parametrize("kind", KINDS)
def test_step_then_flush_is_lockstep(kind, engines, streams):
    engine, want = engines(kind), streams(kind)
    scheduler = ContinuousBatchingScheduler(engine)
    handle = scheduler.submit(prompts()[4][0], 5)
    while not handle.done:
        launched = scheduler.step()
        assert launched == 0  # nothing was in flight to deliver
        scheduler.flush()
        assert scheduler._in_flight is None and handle.in_flight == 0
        # every token the device has made is on the request
        assert len(handle.generated) == (
            0 if handle.state == "prefilling" else
            engine.slot_length(handle.slot) - handle.prompt.size + 1
            if not handle.done else 5)
    assert handle.generated == want[4][:5] and scheduler.idle
    assert scheduler.flush() == 0


@pytest.mark.parametrize("kind", KINDS)
def test_preempt_reads_the_step_in_flight_first(kind, engines, streams):
    engine, want = engines(kind), streams(kind)
    scheduler = ContinuousBatchingScheduler(engine)
    handle = scheduler.submit(prompts()[3][0], 8)
    other = scheduler.submit(prompts()[0][0], 8)
    while len(handle.generated) < 3:
        scheduler.step()
    assert handle.in_flight == 1 and not scheduler.idle
    victim = scheduler.preempt(handle.slot)
    assert victim is handle and handle.in_flight == other.in_flight == 0
    assert scheduler._in_flight is None
    had = len(handle.generated)
    assert handle.generated == want[3][:had] and had >= 4
    _drive(scheduler, [handle, other])
    assert handle.preemptions == 1
    assert handle.generated == want[3] and other.generated == want[0]


@pytest.mark.parametrize("kind", KINDS)
def test_drain_for_reroute_reads_the_step_in_flight_first(kind, engines,
                                                          streams):
    engine, want = engines(kind), streams(kind)
    scheduler = ContinuousBatchingScheduler(engine)
    picked = (1, 3, 5)  # a budget of one among them, and one left queued
    handles = [scheduler.submit(prompts()[i][0], min(prompts()[i][1], 6))
               for i in picked]
    while not handles[0].in_flight:  # the budget of one: its final slice
        scheduler.step()
    assert not scheduler.idle and not handles[0].done
    drained = scheduler.drain_for_reroute()
    assert scheduler.idle and all(h.in_flight == 0 for h in handles)
    # the budget of one was complete on the device: done, not drained
    assert handles[0].done and handles[0] not in drained
    assert {h.uid for h in drained} == {h.uid for h in handles[1:]}
    for handle, i in zip(handles, picked):
        assert handle.generated == want[i][:len(handle.generated)]
    # the engine was presumed dead, so nothing of it was retired; here
    # it is swept by hand and takes them over as the survivor would
    for slot in list(engine.allocator.live):
        engine.retire(slot)
    survivor = ContinuousBatchingScheduler(engine)
    for handle in drained:
        survivor.enqueue(handle)
    _drive(survivor, handles)
    for handle, i in zip(handles, picked):
        assert handle.generated == want[i][:handle.max_new_tokens]


def test_a_hand_off_with_a_step_in_flight_carries_that_steps_token():
    # two engines over one pool, driven by hand: the decode step whose
    # handle nobody has read is the one the packet's last token is from
    from flashy_tpu.serve.fleet import DisaggregatedPair
    from flashy_tpu.serve.fleet.handoff import hand_off
    from tests.data.record_lockstep_served_tokens import toy
    model, params, _ = toy("int8")
    pair = DisaggregatedPair(model, params, prefill_slots=1, decode_slots=2,
                             block_size=4, kv_dtype="int8", max_seq_len=64)
    pair.warmup()
    prompt = prompts()[3][0]
    want = _by_hand(pair.decode, prompt, 8)
    src, dst = pair.prefill, pair.decode
    slot = src.acquire_slot()
    start, handle = src.admit(slot, prompt, 8), None
    while handle is None:
        start, handle = src.dispatch_prefill_chunk(slot, prompt, start)
    step = src.dispatch_decode()  # two steps in flight, neither read
    new_slot, packet = hand_off(src, dst, slot)
    tokens = [int(src.collect(handle)[0]), int(src.collect(step)[slot])]
    assert packet.last_token == tokens[-1]
    assert packet.position == prompt.size + 1
    while len(tokens) < 8:
        tokens.append(int(dst.decode()[new_slot]))
    assert tokens == want
    dst.retire(new_slot)
    pair.pool.check()


@pytest.mark.parametrize("kind", PAGED)
def test_a_late_row_writes_inside_its_reservation_or_the_sentinel(
        kind, engines):
    # the last block of a budget at the edge of max_seq_len: the request
    # reserves every block of its slot; an EOS on its last-but-one token
    # is seen late with the budget's last row already launched (known by
    # count), so no row is ever launched beyond the reservation, and the
    # retired slot's table row is all sentinel before anyone else writes
    engine, budget = engines(kind), 6
    prompt, stream, cut = _eos_case(engine, engine.max_seq_len - budget,
                                    budget, budget - 2, budget - 1)
    scheduler = ContinuousBatchingScheduler(engine)
    handle = scheduler.submit(prompt, budget, eos_token=stream[cut])
    while not scheduler.idle:
        scheduler.step()
        engine.pool.check()
        # while the slot is held its next write lies inside the slot
        if handle.slot is not None and engine._active_host[handle.slot]:
            assert engine._positions_host[handle.slot] < engine.max_seq_len
    assert handle.generated == stream[:cut + 1]
    # the late row was the budget's last, at the slot's last-but-one
    # position: launched by count, its slot parked behind it at once
    assert prompt.size + cut == engine.max_seq_len - 2
    assert scheduler.metrics.rows_decoded == budget - 1
    assert scheduler.metrics.late_rows == 1
    assert not engine._table_host.any()  # every row back at the sentinel
    assert np.asarray(engine._positions).tolist() == [engine.max_seq_len] * 2
    assert not np.asarray(engine._active).any()


# ----------------------------------------------------------------------
# the record of a slow step (ServeMetrics.on_step_end)
# ----------------------------------------------------------------------
STATE = {"in_flight": 1, "queued": 0, "prefilling": 0, "running": 2}


def _fed(metrics, walls, children=None):
    """Feed `ServeMetrics.on_step_end` one synthetic step a wall time."""
    for step, wall in enumerate(walls):
        phases = {"serve/step": wall, **(children or {})}
        metrics.on_step_end(step, phases, cpu_seconds=wall / 4,
                            gc_seconds=0.0, gc_collections=0, **STATE)


def test_the_slow_step_rule_is_five_medians_and_fifty_ms(caplog):
    from flashy_tpu.serve import ServeMetrics
    metrics = ServeMetrics()
    fast = [0.004] * 130
    with caplog.at_level("WARNING", logger="flashy_tpu.serve.metrics"):
        # five medians but under 50 ms; over 50 ms but under five
        # medians of a slow engine; no history to judge by: none is slow
        _fed(metrics, fast + [0.045])
        _fed(ServeMetrics(), [0.030] * 130 + [0.140])
        _fed(ServeMetrics(), [0.004] * 5 + [2.0])
        assert not caplog.records
    summary = metrics.summary()
    assert summary["slow_steps"] == 0 and summary["slowest_step_ms"] == 0.0
    assert not metrics.slow_step_records
    with caplog.at_level("WARNING", logger="flashy_tpu.serve.metrics"):
        _fed(metrics, [0.0801], children={"serve/decode/readback": 0.07,
                                          "serve/admission": 0.004})
    (record,) = metrics.slow_step_records
    assert record["wall_ms"] == pytest.approx(80.1)
    assert record["median_ms"] == pytest.approx(4.0)
    assert record["cpu_ms"] == pytest.approx(80.1 / 4)
    # largest first; under its own name what no child covers
    assert list(record["phases"]) == ["serve/decode/readback", "serve/step",
                                      "serve/admission"]
    assert record["phases"]["serve/step"] == pytest.approx(6.1)
    assert {key: record[key] for key in STATE} == STATE
    (line,) = [r.getMessage() for r in caplog.records]
    assert line.startswith("serve: step 0 took 80.1 ms (median 4.0, cpu 20.0)"
                           ": serve/decode/readback 70.0, serve/step 6.1, "
                           "serve/admission 4.0; gc 0 in 0.0 ms; in_flight 1")
    assert metrics.summary()["slow_steps"] == 1
    assert metrics.summary()["slowest_step_ms"] == pytest.approx(80.1)
    # serve.json's two keys reach `flashy_tpu.info` and the stage line
    from flashy_tpu.info import format_serve_status
    from flashy_tpu.logging import serve_formatter
    assert "slow_steps=1  slowest_step_ms=80.1" in format_serve_status(
        metrics.summary())
    assert "slow_steps" not in format_serve_status(ServeMetrics().summary())
    shown = serve_formatter()(metrics.summary())
    assert (shown["slow_steps"], shown["slowest_step_ms"]) == ("1", "80.1ms")


def test_forty_slow_steps_keep_sixteen_records():
    from flashy_tpu.serve import ServeMetrics
    from flashy_tpu.serve.metrics import SLOW_STEP_RECORDS
    metrics = ServeMetrics()
    # three fast steps between slow ones: the median stays a fast step's
    _fed(metrics, [0.002] * 130 + [0.002, 0.002, 0.002, 0.1] * 40)
    assert metrics.summary()["slow_steps"] == 40
    assert SLOW_STEP_RECORDS == 16 == len(metrics.slow_step_records)
    assert [r["step"] for r in metrics.slow_step_records] == list(
        range(130 + 4 * 24 + 3, 130 + 160, 4))  # the oldest went


def _busy(scheduler, steps):
    """`steps` scheduler steps with both slots decoding in nearly all."""
    prompt = prompts()[0][0]
    target = scheduler.steps + steps
    while scheduler.steps < target:
        while scheduler.queue_depth < 2:
            scheduler.submit(prompt, 24)
        scheduler.step()


def _finish(scheduler):
    scheduler.run()
    assert scheduler.engine.live_count == 0


@pytest.fixture
def injector():
    from flashy_tpu.resilience import chaos
    yield chaos.install()
    chaos.uninstall(verify=False)


def _the_record(metrics, caplog, step):
    """The one record of scheduler step `step`. Every record has its
    WARNING line and its count (another step of this toy engine may be
    stalled by a loaded test machine: it is then slow too, and says so)."""
    records = list(metrics.slow_step_records)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "flashy_tpu.serve.metrics"]
    assert len(lines) == len(records) == metrics.summary()["slow_steps"]
    assert all(r["wall_ms"] > 50 for r in records)
    (record,) = [r for r in records if r["step"] == step]
    (line,) = [line for line in lines
               if line.startswith(f"serve: step {step} took ")]
    return record, line


def test_a_delay_in_no_child_is_the_steps_own_time(engines, injector, caplog,
                                                   tmp_path):
    import gc
    import time
    from flashy_tpu.observability import Tracer
    from flashy_tpu.serve import ServeMetrics
    journal = tmp_path / "telemetry.jsonl"
    metrics = ServeMetrics(tracer=Tracer(jsonl_path=journal))
    scheduler = ContinuousBatchingScheduler(engines("int8"), metrics=metrics)
    with caplog.at_level("WARNING", logger="flashy_tpu.serve.metrics"):
        _busy(scheduler, 130)
        # the fault point of `serve/step` lies in none of its children
        injector.delay_at("serve.step", injector.counts["serve.step"] + 1,
                          0.08)
        _busy(scheduler, 1)
        delayed = scheduler.steps - 1
        # a second one, with a collection forced inside it
        injector.act_at("serve.step", injector.counts["serve.step"] + 3,
                        lambda: (gc.collect(), time.sleep(0.08)))
        _busy(scheduler, 3)
        collected = scheduler.steps - 1
        _finish(scheduler)
    assert injector.hits("serve.step") == 2
    record, line = _the_record(metrics, caplog, delayed)
    assert record["wall_ms"] >= 80 and record["cpu_ms"] < record["wall_ms"] / 2
    assert next(iter(record["phases"])) == "serve/step"
    assert record["phases"]["serve/step"] >= 80
    assert sum(record["phases"].values()) == pytest.approx(record["wall_ms"])
    assert record["median_ms"] < 16 and record["running"] == 2
    assert record["in_flight"] == 1 and record["gc_collections"] == 0
    assert "serve/step 8" in line and "; gc 0 in 0.0 ms; in_flight 1" in line
    record, _ = _the_record(metrics, caplog, collected)
    assert record["gc_collections"] >= 1
    # the sleep is not the collector's, and a collection is CPU time
    assert 0 < record["gc_ms"] < record["wall_ms"] - 75
    assert record["cpu_ms"] > record["gc_ms"] / 2
    # the journal has each record whole, the WARNING's numbers included
    lines = [json.loads(line) for line in journal.read_text().splitlines()]
    slow = [line for line in lines if line["type"] == "serve_slow_step"]
    assert [line["step"] for line in slow] == [
        r["step"] for r in metrics.slow_step_records]
    assert slow[-1]["phases"] == record["phases"]
    assert metrics.summary()["slowest_step_ms"] >= 80


def test_a_delay_in_the_readback_is_found_there(engines, caplog, monkeypatch):
    import time
    engine = engines("int8")
    scheduler = ContinuousBatchingScheduler(engine)

    class Late:
        """A step's tokens that take 80 ms to come back."""

        def __init__(self, read):
            self.read = read

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.08)
            return np.asarray(self.read)

    dispatch = engine.dispatch_decode

    def late_once(step=None):
        monkeypatch.setattr(engine, "dispatch_decode", dispatch)
        handle = dispatch(step=step)
        return handle._replace(read=Late(handle.read))

    with caplog.at_level("WARNING", logger="flashy_tpu.serve.metrics"):
        _busy(scheduler, 130)
        monkeypatch.setattr(engine, "dispatch_decode", late_once)
        _busy(scheduler, 2)  # dispatched in one step, read in the next
        _finish(scheduler)
    record, line = _the_record(scheduler.metrics, caplog, 131)
    assert next(iter(record["phases"])) == "serve/decode/readback"
    assert record["phases"]["serve/decode/readback"] >= 80
    assert record["phases"]["serve/step"] < 40
    assert record["cpu_ms"] < record["wall_ms"] / 2
    assert "): serve/decode/readback 8" in line


def test_fast_steps_leave_no_record_and_cost_no_collection_hook(engines):
    import gc
    from flashy_tpu.serve.metrics import _on_gc
    scheduler = ContinuousBatchingScheduler(engines("int8"))
    handle = scheduler.submit(prompts()[2][0], 6)
    _drive(scheduler, [handle])
    summary = scheduler.metrics.summary()
    # under sixteen steps nothing is judged, whatever a step took
    assert scheduler.steps < 16 and summary["slow_steps"] == 0
    assert not scheduler.metrics.slow_step_records
    assert len(scheduler.metrics._step_walls) == scheduler.steps
    # ONE hook for the process, however many schedulers have stepped
    assert gc.callbacks.count(_on_gc) == 1


def test_warmup_ends_with_a_full_collection(engines):
    # what the slow-step record found in every run of the chat cell: the
    # garbage of tracing and compiling, collected a few hundred steps
    # into traffic; warm-up collects it, so the next full collection is
    # a quarter of the long-lived heap away
    import gc
    seen = []

    def hook(phase, info):
        seen.append((phase, info["generation"]))

    gc.callbacks.append(hook)
    try:
        engines("int8").warmup()
    finally:
        gc.callbacks.remove(hook)
    assert ("stop", 2) in seen[-4:]  # its last act, bar a young one after
