"""The program-span, scope and collective readers on hand-built traces
with known numbers (data/*_program_trace.textproto, written by
data/make_program_traces.py)."""
import os

import pytest

from benchmarks.harness import flops
from benchmarks.readers import collectives, program_spans, xspace

US = 1e-3  # a microsecond in ms


def _run(name, **more):
    import jax
    path = os.path.join(os.path.dirname(__file__), "data", name)
    with open(path) as f:
        data = jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            f.read())
    trace = program_spans.reduce(xspace.parse(data))
    return {"trace": {}, "program_trace": trace, **more}


@pytest.fixture(scope="module")
def serve():
    config = {"hidden_size": 16, "num_hidden_layers": 2, "vocab_size": 32,
              "intermediate_size": 64}
    return _run("serve_program_trace.textproto", config=config,
                peak=flops.peaks("TPU v5 lite"), memory_peak_bytes=4e9)


@pytest.fixture(scope="module")
def train():
    return _run("train_program_trace.textproto", peak=None)


def test_xspace_reads_event_and_metadata_stats(serve):
    spans = {s.name: s for s in serve["program_trace"]["spans"]}
    assert spans["serve/prefill_chunk"].stats == {
        "uid": 9, "slot": 1, "size": 16, "offset": 0, "length": 30,
        "final": 0}
    assert spans["serve/table_upload"].stats == {"bytes": 256}
    ops = serve["program_trace"]["devices"][0]["ops"]
    assert program_spans.scope_path(ops[1][2]) == (
        "jit", "decode_paged", "jit", "main", "qkv", "dot_general")
    assert program_spans.scope_path(ops[-1][2]) == ("cache",)  # an argument
    assert not any(s.name.startswith("bench/")
                   for s in serve["program_trace"]["spans"])


@pytest.mark.parametrize("bucket,per_step_us", [
    ("admit", 50), ("dispatch", 25), ("readback", 5), ("retire", 0),
    ("outside_step", 100)])
def test_idle_gaps_go_to_the_innermost_program_span(serve, bucket,
                                                    per_step_us):
    assert program_spans.serve_idle_ms(serve, bucket) == pytest.approx(
        per_step_us * US)


def test_idle_buckets_sum_to_the_idle_time(serve):
    idle = program_spans.idle_by_span(serve["program_trace"])
    assert sum(idle["gaps"].values()) == pytest.approx(360e3)  # ns
    assert idle["in_modules"] == pytest.approx(10e3)
    assert idle["gaps"].get("serve/step", 0.0) == 0.0
    assert idle["gaps"]["serve/decode"] == pytest.approx(50e3)
    # the same idle time cut at the span boundaries: 0-60 is step, then
    # admission, step, prefill_chunk, table_upload, prefill_chunk ...
    assert sum(idle["overlap"].values()) == pytest.approx(360e3)
    assert idle["overlap"]["serve/table_upload"] == pytest.approx(17e3)
    assert idle["overlap"]["serve/admission"] == pytest.approx(
        (15 + 2) * 1e3)  # 5-20 and 612-614
    assert idle["overlap"]["(outside serve/step)"] == pytest.approx(
        (1 + 90 + 10) * 1e3)  # 0-1, 520-610 and 990-1000
    total = sum(program_spans.serve_idle_ms(serve, bucket) for bucket in (
        "admit", "dispatch", "readback", "retire", "outside_step"))
    assert total == pytest.approx(180 * US)


def test_decoding_slots_and_clock_check(serve):
    assert program_spans.decoding_slots(serve) == pytest.approx(2.5)
    check = program_spans.clock_check(serve["program_trace"])
    assert check["spans"] == 2 and check["negative"] == 0
    assert check["median_ms"] == pytest.approx(7.5 * US)
    assert check["min_ms"] == pytest.approx(5 * US)


def test_decode_time_by_scope_counts_only_the_decode_executable(serve):
    assert program_spans.decode_scope_ms(
        serve, "kv_write") == pytest.approx(45 * US)
    assert program_spans.decode_scope_ms(serve, "qkv") == pytest.approx(
        60 * US)  # the slice's qkv (100 us) belongs to chunk_paged
    assert program_spans.decode_scope_ms(
        serve, "qkv", module="chunk_paged") == pytest.approx(100 * US)
    assert program_spans.decode_scope_ms(serve, "rotary") is None


def test_weight_stream_roofline_counts_the_configs_parameters(serve):
    nbytes = flops.lm_param_count(serve["config"]) * 4
    took = (60 + 25) * 1e-6  # qkv + mlp per run, seconds
    assert program_spans.weight_stream_roofline_pct(
        serve, bytes_per_param=4) == pytest.approx(
            100 * nbytes / 819e9 / took)
    assert program_spans.hbm_peak_pct(serve) == pytest.approx(25.0)


@pytest.mark.parametrize("group,us", [
    ("attn", 200), ("mlp", 190), ("head_loss", 140), ("optimizer", 140)])
def test_train_step_by_scope(train, group, us):
    assert program_spans.train_scope_ms(train, group) == pytest.approx(
        us * US)


def test_exposed_and_hidden_collectives(train):
    exposed, total = collectives.exposed_ms_per_run(
        train["program_trace"], "train_step")
    # device 0: 10 + 60 + 60 exposed, the reduce-scatter hidden under a
    # fusion; device 1's all-gather wait is 30 us shorter
    assert exposed == pytest.approx(115 * US)
    assert total == pytest.approx((210 + 180) / 2 * US)
    assert collectives.exposed_collective_ms(train) == pytest.approx(115 * US)
    rest = program_spans.scope_ms_per_run(
        train["program_trace"], "train_step", program_spans.TRAIN_GROUPS)
    assert rest["(rest)"] == pytest.approx(195 * US)


# a name to recognise, not bytes to count
ASYNC_HALF = "%all-gather-start.3 = (f32[2]) all-gather(%p)"  # flashy: noqa[FT005]


@pytest.mark.parametrize("hlo,expected", [
    (ASYNC_HALF, True),
    ("%all-reduce-scatter-fusion.1 = f32[2] fusion(f32[8] %g), kind=kCustom",
     True),
    ("%fusion.9 = f32[8] fusion(f32[2] %p), kind=kCustom, "
     "calls=%all-gather.clone.1", True),
    ("%fusion.3 = f32[8] fusion(f32[8] %p), kind=kLoop, "
     "calls=%fused_computation.3", False),
    ("%copy.4 = f32[8] copy(f32[8] %p)", False)])
def test_collectives_are_found_by_opcode(hlo, expected):
    assert collectives.is_collective(hlo) is expected


def test_a_program_without_spans_or_scopes_reads_nothing(train):
    """The parent of the PR that added them: every reader returns None."""
    for bucket in program_spans.IDLE_BUCKETS:
        assert program_spans.serve_idle_ms(train, bucket) is None
    assert program_spans.decoding_slots(train) is None
    assert program_spans.decode_scope_ms(train, "kv_write") is None
    assert program_spans.weight_stream_roofline_pct(train, 4) is None
    bare = {"trace": None}
    assert program_spans.train_scope_ms(bare, "attn") is None
    assert collectives.exposed_collective_ms(bare) is None
    assert program_spans.hbm_peak_pct({"peak": None}) is None


def test_report_prints_the_span_table(serve):
    lines = []
    program_spans.report(serve, say=lines.append)
    text = "\n".join(lines)
    assert "serve/decode/readback: count 2" in text
    assert "clock check" in text and "decode_paged device ms per run" in text
    assert all(line.startswith("[bench] ") for line in lines)
