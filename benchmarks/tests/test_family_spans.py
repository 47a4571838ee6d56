"""The latent-read and expert-stream readers on a hand-built trace with
known numbers, and the new cell's runner end to end at a toy size."""
import json
import os

import pytest

from benchmarks.harness import flops, flops_dots
from benchmarks.readers import family_spans, program_spans, xspace
from benchmarks.tests.test_run import KEYS, _run

MS = 1_000_000  # ns


def _event(name, start_ms, end_ms, **stats):
    return xspace.Event((name, int(start_ms * MS), int(end_ms * MS), stats))


@pytest.fixture(scope="module")
def run(root):
    with open(os.path.join(root, "benchmarks", "configs",
                           "dots-vlm1-ep16-6l.json")) as f:
        config = json.load(f)
    scope = lambda path: {"tf_op": f"jit(decode_paged)/{path}:"}
    ops = [_event("%fusion.1 = bf16[8]", 1.0, 2.0, **scope("attn/dot_general")),
           _event("%fusion.2 = bf16[8]", 2.0, 2.2, **scope("mla_q/dot_general")),
           _event("%scatter.3 = bf16[8]", 2.2, 2.3, **scope("kv_write/scatter")),
           _event("%gmm.4 = f32[8]", 2.3, 4.3, **scope("mlp/experts/gmm")),
           _event("%fusion.5 = f32[8]", 4.3, 4.4, **scope("mlp/router/dot")),
           _event("%fusion.6 = bf16[8]", 4.4, 4.5,
                  **scope("mlp/shared_expert/dot_general")),
           _event("%fusion.7 = f32[8]", 4.5, 4.9, **scope("head/dot_general"))]
    ops += [_event("%fusion.8 = f32[8]", 6.0, 6.5,
                   **{"tf_op": "jit(chunk_paged)/attn/dot_general:"}),
            _event("%fusion.9 = f32[8]", 6.5, 6.7,
                   **{"tf_op": "jit(chunk_paged)/mlp/experts/gmm:"})]
    rows = 1000  # latent rows one layer's read attended
    planes = {
        "/host:CPU": {"main": [
            _event("bench/traced_window", 0.0, 10.0),
            _event("serve/step", 0.5, 6.0),
            _event("serve/decode", 0.6, 5.5, running=2, live=2,
                   kv_bytes=rows * 1280 * config["num_hidden_layers"]),
            _event("serve/decode/moe", 5.40, 5.41, moe_assignments=12,
                   moe_experts_hit=6)]},
        "/device:TPU:0": {
            "XLA Modules": [_event("jit_decode_paged(1)", 1.0, 5.0),
                            _event("jit_chunk_paged(2)", 6.0, 6.8)],
            "XLA Ops": ops}}
    return {"trace": {"modules": {"jit_decode_paged": [4e-3]}},
            "program_trace": program_spans.reduce(planes), "config": config,
            "peak": flops.peaks("TPU v5 lite")}


def test_device_ms_by_part(run):
    assert family_spans.decode_device_ms(run, "mla") == pytest.approx(1.3)
    assert family_spans.decode_device_ms(run, "experts") == pytest.approx(2.2)


def test_slice_device_ms_reads_the_prefill_slices_executable(run):
    assert family_spans.slice_device_ms(run, "attn") == pytest.approx(0.5)
    assert family_spans.slice_device_ms(run, "experts") == pytest.approx(0.2)
    assert family_spans.slice_device_ms({"trace": None}, "attn") is None


def test_roofline_shares(run):
    # 1000 rows: 1.28 MB / 819 GB/s = 1.563 us beats 278.5 MFLOP /
    # 197 TFLOP/s = 1.414 us; six layers against 1 ms under attn
    assert family_spans.mla_read_roofline_pct(run) == pytest.approx(
        100 * 6 * 1.28e6 / 819e9 / 1e-3)
    # six experts of 88,080,384 B against 2 ms under experts
    assert family_spans.expert_stream_roofline_pct(run) == pytest.approx(
        100 * 6 * 88_080_384 / 819e9 / 2e-3)
    assert family_spans.moe_tokens_per_expert(run) == pytest.approx(2.0)
    least = flops_dots.decode_step_roofline_seconds(
        run["config"], run["peak"], slots=2, attended_tokens=1000,
        assignments=12, experts_hit=6)
    # 3.73 GB of weights every step reads whole (attention, the dense
    # MLP, routers, shared experts, head), six experts, 6 x 1.28 MB
    assert least == pytest.approx(
        (2 * 1864.2e6 + 6 * 88_080_384 + 6 * 1.28e6) / 819e9, rel=1e-3)
    assert family_spans.decode_step_mfu_pct(run) == pytest.approx(
        100 * least / 4e-3)


def test_a_program_without_the_scopes_gives_nothing(run):
    bare = dict(run, program_trace=dict(run["program_trace"], spans=[],
                                        scope_ms={}))
    for reader in (family_spans.mla_read_roofline_pct,
                   family_spans.expert_stream_roofline_pct,
                   family_spans.moe_tokens_per_expert,
                   family_spans.decode_step_mfu_pct):
        assert reader(bare) is None
    assert family_spans.decode_device_ms({"trace": None}, "mla") is None


def test_rehearsal_runs_the_family_runner_end_to_end(root, manifest):
    done = _run(root, "--workload", "dotsvlm1-doc-closed", "--seed",
                str(2 ** 31 + 17), "--seconds", "2", "--trace", "0",
                "--rehearse", os.path.join("benchmarks", "tests",
                                           "toy_dots.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("[rehearsal] ")
    line = json.loads(last[len("[rehearsal] "):])
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    assert "check logit_rms_sigma" in done.stdout


@pytest.mark.parametrize("control, refused_by", [
    ("dots-plain-rotary", ("margin_sigma", "logit_rms_sigma")),
    ("dots-no-shared-expert", ("margin_sigma", "logit_rms_sigma")),
    ("dots-bfloat16", ())])
def test_controls_go_through_the_cells_own_comparison(root, tmp_path, control,
                                                      refused_by):
    """benchmarks/controls/*.json laid over the toy sizes: a planted
    fault comes out as not correct by both limits. The bfloat16 control
    takes the program's place in both readings; at toy widths and the
    toy's loose limit it passes, which limit refuses it at the published
    widths is a reading of the chip (PERF.md section 6)."""
    from benchmarks.run import merge
    with open(os.path.join(root, "benchmarks", "tests", "toy_dots.json")) as f:
        toy = json.load(f)
    with open(os.path.join(root, "benchmarks", "controls",
                           f"{control}.json")) as f:
        both = merge(toy, json.load(f))
    path = tmp_path / "control.json"
    path.write_text(json.dumps(both))
    done = _run(root, "--workload", "dotsvlm1-doc-closed", "--seed", "23",
                "--seconds", "1", "--trace", "0", "--rehearse", str(path))
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    line = json.loads(last[len("[rehearsal] "):])
    assert line["correct"] is (not refused_by)
    assert ("CONTROL" if control == "dots-bfloat16" else "PLANTED FAULT") \
        in done.stdout
    failed = [l for l in done.stdout.splitlines() if "CHECK FAILED" in l]
    assert len(failed) == len(refused_by)
