"""The readers of the pipelined step on a hand-made window with known
numbers: three whole scheduler steps and one that straddles the window's
end, one decode run in flight behind each (times in us; the window is
0..4000)."""
import pytest

from benchmarks.readers import pipeline_spans, program_spans, xspace

US = 1e-3  # a microsecond in ms
NS = 1e3   # a microsecond in ns, the unit of an Event


def _event(name, start, end, **stats):
    return xspace.Event((name, start * NS, end * NS, stats))


def _step(at, number, more=()):
    """One pipelined step of 1,000 us from `at`: admission 100 (20 of it
    a table upload), a slice 200, gauges 50, decode 150 (dispatch 100),
    launch_out 30, then the read-back of the step before it 300 with its
    empty /moe child behind, first token 20, retire 40; 110 in no child."""
    return [
        _event("serve/step", at, at + 1000, step=number),
        _event("serve/admission", at + 10, at + 110),
        _event("serve/table_upload", at + 50, at + 70),
        _event("serve/prefill_chunk", at + 120, at + 320, step=number),
        _event("serve/gauges", at + 330, at + 380),
        _event("serve/decode", at + 390, at + 540, step=number, running=2),
        _event("serve/decode/dispatch", at + 420, at + 520, step=number),
        _event("serve/launch_out", at + 545, at + 575, rows=1),
        _event("serve/decode/readback", at + 580, at + 880, step=number - 1),
        _event("serve/decode/moe", at + 880, at + 880),
        _event("serve/first_token", at + 900, at + 920, slot=1),
        _event("serve/retire", at + 930, at + 970),
    ] + [_event(name, at + a, at + b) for name, a, b in more]


def _run(spans, runs):
    """A run record over `spans` and the decode runs `(start, end)`."""
    host = [_event("bench/traced_window", 0, 4000)] + spans
    ops = [_event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", a, b)
           for a, b in runs]
    modules = [_event("jit_decode_paged(7)", a, b) for a, b in runs]
    trace = program_spans.reduce({
        "/host:CPU": {"main": host},
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}})
    return {"trace": {}, "program_trace": trace}


# the run dispatched in step k begins behind the one before it and ends
# 0.2 ms (200 us) before step k + 1's read-back ends; while that
# read-back waits, the run step k + 1 dispatched has already BEGUN
RUNS = [(-500, 680), (690, 1680), (1690, 2680), (2690, 3780)]


@pytest.fixture(scope="module")
def run():
    spans = (_step(0, 7) + _step(1000, 8) + _step(2000, 9)
             + _step(3100, 10))  # the last ends at 4,100: straddles
    return _run(spans, RUNS)


@pytest.mark.parametrize("bucket,us", [
    ("admit", 80 + 20 + 50), ("dispatch", 200 + 50 + 100),
    ("retire", 40 + 30 + 20), ("step", 110)])
def test_each_bucket_gets_its_self_time(run, bucket, us):
    assert pipeline_spans.host_ms(run, bucket) == pytest.approx(us * US)


def test_busy_and_slack_add_up_to_the_mean_step(run):
    steps = pipeline_spans.whole_steps(run["program_trace"])
    assert [step["wall"] for step in steps] == [1000 * NS] * 3  # not step 10
    busy = pipeline_spans.host_busy_ms(run)
    slack = pipeline_spans.host_slack_ms(run)
    assert slack == pytest.approx(300 * US, abs=1e-9)
    assert busy + slack == pytest.approx(1000 * US, abs=1e-9)
    assert sum(pipeline_spans.host_ms(run, bucket) for bucket in (
        "admit", "dispatch", "retire", "step")) == pytest.approx(
            busy, abs=1e-9)
    # the straddling step's spans began in the window and are in no step
    assert any(s.stats.get("step") == 10
               for s in run["program_trace"]["spans"])


def test_a_child_of_unknown_name_lands_in_the_steps_own_bucket():
    # 26 us of the step's own 110 under a name no bucket knows, 10 of
    # them in a known child, which keeps its own bucket: 84 + 16
    spans = _step(0, 3, more=[("serve/new_phase", 972, 998),
                              ("serve/gauges", 980, 990)])
    run = _run(spans, RUNS[:1])
    assert pipeline_spans.host_ms(run, "step") == pytest.approx(100 * US)
    assert pipeline_spans.host_ms(run, "admit") == pytest.approx(160 * US)
    assert (pipeline_spans.host_busy_ms(run)
            + pipeline_spans.host_slack_ms(run)) == pytest.approx(
                1000 * US, abs=1e-9)


def test_a_readback_is_paired_with_the_run_that_ended_before_it(run):
    trace = run["program_trace"]
    found = pipeline_spans.readback_lags(trace)
    # the read-back at 580-880 has only the run that began before the
    # window; the three after it read a run each, 200 us after its end
    assert found["unpaired"] == [pytest.approx((1680 - 880) * US)]
    assert found["lags"] == pytest.approx([200 * US] * 3)
    assert pipeline_spans.readback_lag_ms(run) == pytest.approx(200 * US)
    # the check it supersedes takes the run that BEGAN last, the one in
    # flight, and reads a negative lag
    check = program_spans.clock_check(trace)
    assert check["negative"] == 3 and check["median_ms"] < 0


def test_a_readback_whose_run_was_read_already_stays_unpaired():
    # two read-backs and one run between them: the second has none
    spans = _step(0, 1) + [_event("serve/decode/readback", 1100, 1200)]
    found = pipeline_spans.readback_lags(
        _run(spans, [(100, 700)])["program_trace"])
    assert found["lags"] == pytest.approx([180 * US])
    assert found["unpaired"] == [None]


READERS = [
    (pipeline_spans.host_busy_ms, {}), (pipeline_spans.host_slack_ms, {}),
    (pipeline_spans.host_ms, {"bucket": "admit"}),
    (pipeline_spans.host_ms, {"bucket": "dispatch"}),
    (pipeline_spans.host_ms, {"bucket": "retire"}),
    (pipeline_spans.host_ms, {"bucket": "step"}),
    (pipeline_spans.readback_lag_ms, {})]


@pytest.mark.parametrize("reader,args", READERS)
def test_a_run_without_spans_or_without_a_trace_reads_nothing(reader, args):
    bare = _run([], RUNS)  # the device ran; the program wrote no span
    assert reader(bare, **args) is None
    assert reader({"trace": None}, **args) is None


def test_the_seven_metrics_name_these_readers(manifest, root):
    import json
    import os
    mine = [m for m in manifest["per_layer"]
            if m["name"].startswith(("host_", "readback_lag"))]
    assert [m["name"] for m in mine] == [
        "host_busy_ms", "host_slack_ms", "host_ms.admit", "host_ms.dispatch",
        "host_ms.retire", "host_ms.step", "readback_lag_ms"]
    # appended as one run (not "the last seven": the next PR appends too)
    first = manifest["per_layer"].index(mine[0])
    assert manifest["per_layer"][first:first + 7] == mine and first >= 55
    serving = [w["name"] for w in manifest["workloads"]
               if w["name"] != "olmo1b-train-2k"]
    for metric, (reader, args) in zip(mine, READERS):
        assert metric["source"] == "program_span" and metric["unit"] == "ms"
        assert metric["workloads"] == serving
        with open(os.path.join(root, "benchmarks", "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == f"pipeline_spans:{reader.__name__}"
        assert spec.get("args", {}) == args


def test_report_prints_the_split_and_the_lag(run):
    lines = []
    pipeline_spans.report(run["program_trace"], say=lines.append)
    text = "\n".join(lines)
    assert "3 whole serve/step" in text
    assert "mean wall 1.0000 ms = host busy 0.7000 + slack" in text
    assert "retire 0.0900, step 0.1100 (serve/step)" in text
    assert "spans 3, unpaired 1 (" in text and "), median 0.2000" in text
    assert "negative 0" in text
    assert all(line.startswith("[bench] ") for line in lines)
