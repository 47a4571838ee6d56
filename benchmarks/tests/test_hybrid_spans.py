"""The readers of the two K/V reads and the `.gqa` twins on a hand-built
trace with known numbers, the new configuration's arithmetic and
manifest entry, and the new cell's runner end to end at a toy size."""
import json
import os

import pytest

from benchmarks.harness import flops, flops_mimo
from benchmarks.readers import hybrid_spans, program_spans, xspace
from benchmarks.tests.test_run import KEYS, _run

MS = 1_000_000  # ns
CELL = "mimo25-doc16k-closed"


def _event(name, start_ms, end_ms, **stats):
    return xspace.Event((name, int(start_ms * MS), int(end_ms * MS), stats))


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "benchmarks", "configs",
                           "mimo-v2.5-ep16-7l.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config):
    scope = lambda path: {"tf_op": f"jit(decode_paged)/{path}:"}
    ops = [_event("%fusion.1 = bf16[8]", 1.0, 2.0,
                  **scope("attn/global/dot_general")),
           _event("%fusion.2 = bf16[8]", 2.0, 2.25,
                  **scope("attn/window/dot_general")),
           _event("%fusion.3 = bf16[8]", 2.25, 2.3, **scope("attn/mul")),
           _event("%scatter.4 = bf16[8]", 2.3, 2.4,
                  **scope("kv_write/scatter")),
           _event("%gmm.5 = f32[8]", 2.4, 4.4, **scope("mlp/experts/gmm")),
           _event("%fusion.6 = f32[8]", 4.4, 4.5, **scope("mlp/router/dot")),
           _event("%fusion.7 = f32[8]", 4.5, 4.9,
                  **scope("head/dot_general"))]
    # two slots at contexts 9,000 and 11,000: a full layer attends
    # 20,000 rows, a window layer 2 x 128
    full, window = 20000 * 5120, 256 * 25600
    planes = {
        "/host:CPU": {"main": [
            _event("bench/traced_window", 0.0, 10.0),
            _event("serve/step", 0.5, 6.0),
            _event("serve/decode", 0.6, 5.5, running=2, live=2,
                   kv_bytes=full + window, kv_bytes_window=window),
            _event("serve/decode/moe", 5.40, 5.41, moe_assignments=18,
                   moe_experts_hit=12)]},
        "/device:TPU:0": {
            "XLA Modules": [_event("jit_decode_paged(1)", 1.0, 5.0)],
            "XLA Ops": ops}}
    return {"trace": {"modules": {"jit_decode_paged": [4e-3]}},
            "program_trace": program_spans.reduce(planes), "config": config,
            "peak": flops.peaks("TPU v5 lite")}


def test_arithmetic(config):
    parts = flops_mimo.parts(config)
    assert round(parts["attention"] / 1e6, 2) == 89.13
    assert round(parts["window_attention"] / 1e6, 2) == 94.37
    assert round(parts["dense_mlp"] / 1e6, 2) == 201.33
    assert round(parts["routed_expert"] / 1e6, 2) == 25.17
    assert round(flops_mimo.param_count(config) / 1e6, 1) == 3429.9
    assert flops_mimo.layer_counts(config) == {
        "full": 2, "window": 5, "dense": 1, "experts": 6}
    # K and V as stored: 4 x (192 + 128) x 2 B a full layer, 8 x 320 x 2
    # a window layer
    assert flops_mimo.kv_bytes_per_token(config) == {
        "full": 2 * 2560, "window": 5 * 5120}
    cost = flops_mimo.kv_read_cost(config, 1000.0, 100.0)
    assert cost == (64 * 2 * 320 * (2 * 1000 + 5 * 100),
                    1000 * 5120 + 100 * 25600)
    assert flops_mimo.expert_bytes(config) == 50_331_648
    at = flops_mimo.slice_attention_flops(config, 4096, 512)
    # 512 queries at 4,096: mean 4,352.5 keys in a full layer, 128 in a
    # window layer
    assert at["full"] == 2 * 64 * 2 * 320 * 512 * 4352.5
    assert at["window"] == 5 * 64 * 2 * 320 * 512 * 128


def test_device_ms_by_read(run):
    assert hybrid_spans.decode_device_ms(run, "global") == pytest.approx(1.0)
    assert hybrid_spans.decode_device_ms(run, "window") == pytest.approx(0.25)


def test_roofline_shares(run, config):
    # bytes bind: (20,000 x 5,120 + 256 x 25,600) B / 819 GB/s = 133.0 us
    # beats 1.69 GFLOP / 197 TFLOP/s = 8.6 us; against 1.3 ms under attn
    nbytes = 20000 * 5120 + 256 * 25600
    assert hybrid_spans.kv_read_roofline_pct(run) == pytest.approx(
        100 * nbytes / 819e9 / 1.3e-3)
    assert hybrid_spans.kv_window_share_pct(run) == pytest.approx(
        100 * 256 * 25600 / nbytes)
    # twelve experts of 50,331,648 B against 2 ms under experts
    assert hybrid_spans.expert_stream_roofline_pct(run) == pytest.approx(
        100 * 12 * 50_331_648 / 819e9 / 2e-3)
    assert hybrid_spans.moe_tokens_per_expert(run) == pytest.approx(1.5)
    least = flops_mimo.decode_step_roofline_seconds(
        config, run["peak"], slots=2, full_rows=20000, window_rows=256,
        assignments=18, experts_hit=12)
    # 934.8M parameters every step reads whole (attention, the dense
    # MLP, routers, head), twelve experts, the K/V bytes
    assert least == pytest.approx(
        (2 * 934.8e6 + 12 * 50_331_648 + nbytes) / 819e9, rel=1e-3)
    assert hybrid_spans.decode_step_mfu_pct(run) == pytest.approx(
        100 * least / 4e-3)


def test_a_program_without_the_scopes_gives_nothing(run):
    bare = dict(run, program_trace=dict(run["program_trace"], spans=[],
                                        scope_ms={}))
    for reader in (hybrid_spans.kv_read_roofline_pct,
                   hybrid_spans.kv_window_share_pct,
                   hybrid_spans.expert_stream_roofline_pct,
                   hybrid_spans.moe_tokens_per_expert,
                   hybrid_spans.decode_step_mfu_pct):
        assert reader(bare) is None
    assert hybrid_spans.decode_device_ms({"trace": None}, "window") is None
    # another family's decode spans carry kv_bytes and no window part
    other = [s for s in run["program_trace"]["spans"]]
    assert hybrid_spans._decode_means(dict(run, program_trace=dict(
        run["program_trace"], spans=[
            s for s in other if s.name != "serve/decode"]))) is None


def test_the_configuration_keeps_its_sources_numbers_and_lists_its_cuts(
        manifest, config):
    """test_manifest_families.py compares NUMBERS: a cut list in
    `reduced` is outside what it can see (PERF.md section 7 asks the
    next benchmark issue for it). Here the whole row, lists included."""
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mimo-v2.5-ep16-7l")
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == entry["source"])
    changed = [key for key, value in row["config"].items()
               if config[key] != value]
    assert changed == ["hybrid_layer_pattern", "moe_layer_freq",
                       "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(changed) == sorted(entry["reduced"])
    assert config["published"] == {key: row["config"][key]
                                   for key in entry["reduced"]}
    # one whole period as published closes the cut: five window layers
    # and the full layer after them (layers 6-11 of the 48)
    assert config["hybrid_layer_pattern"][1:] == row["config"][
        "hybrid_layer_pattern"][6:12]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == entry["name"]
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["decode_device_ms.attn_window",
                    "decode_device_ms.attn_global", "kv_read_roofline",
                    "kv_window_share_pct", "expert_stream_roofline.gqa",
                    "moe_tokens_per_expert.gqa", "decode_step_mfu_pct.gqa"]


def test_rehearsal_runs_the_new_cell_to_its_last_line(root):
    done = _run(root, "--workload", CELL, "--seed", str(2 ** 31 + 17),
                "--seconds", "2", "--trace", "0", "--rehearse",
                os.path.join("benchmarks", "tests", "toy_mimo.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("[rehearsal] ")
    line = json.loads(last[len("[rehearsal] "):])
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tok_s", "itl_p95_ms"}
    assert "check logit_rms_sigma" in done.stdout
    assert "kernel=gather" in done.stdout


@pytest.mark.parametrize("control", [
    "mimo-no-sink", "mimo-no-window", "mimo-one-rotary-base",
    "mimo-bfloat16"])
def test_controls_go_through_the_cells_own_comparison(root, tmp_path,
                                                      control):
    """benchmarks/controls/mimo-*.json laid over the toy sizes: a
    planted fault in the window layers' softmax or mask comes out as
    not correct. The bfloat16 control takes the program's place in both
    readings, and one rotary base for both kinds barely differs from
    two over the toy's contexts of 48-80 tokens: at toy widths their
    verdicts mean nothing either way; which limit refuses them at the
    published widths is a reading of the chip (PERF.md section 6)."""
    from benchmarks.run import merge
    with open(os.path.join(root, "benchmarks", "tests", "toy_mimo.json")) as f:
        toy = json.load(f)
    with open(os.path.join(root, "benchmarks", "controls",
                           f"{control}.json")) as f:
        both = merge(toy, json.load(f))
    path = tmp_path / "control.json"
    path.write_text(json.dumps(both))
    done = _run(root, "--workload", CELL, "--seed", "23", "--seconds", "1",
                "--trace", "0", "--rehearse", str(path))
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    line = json.loads(last[len("[rehearsal] "):])
    if control in ("mimo-bfloat16", "mimo-one-rotary-base"):
        assert "check logit_rms" in done.stdout and (
            "CONTROL" in done.stdout or "PLANTED FAULT" in done.stdout)
        return
    assert line["correct"] is False and "PLANTED FAULT" in done.stdout
    assert [l for l in done.stdout.splitlines() if "CHECK FAILED" in l]
