"""The reader of the recurrent layers' and the latent experts' scopes on
a hand-built trace with known numbers, the new configuration's
arithmetic and manifest entries, and the new cell's runner end to end at
a toy size, its controls included."""
import json
import os

import pytest

from benchmarks.harness import flops, flops_nemotron
from benchmarks.readers import program_spans, ssm_spans, xspace
from benchmarks.tests.test_run import KEYS, _run

MS = 1_000_000  # ns
CELL = "nemotron3s-reason-closed"
CONFIG = "nemotron3-super-ep4-11l"
ROW = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)  # a slot's state, bytes


def _event(name, start_ms, end_ms, **stats):
    return xspace.Event((name, int(start_ms * MS), int(end_ms * MS), stats))


@pytest.fixture(scope="module")
def config(root):
    with open(os.path.join(root, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(config):
    decode = lambda path: {"tf_op": f"jit(decode_paged)/{path}:"}
    chunk = lambda path: {"tf_op": f"jit(chunk_paged)/{path}:"}
    ops = [
        # a decode run, 1.0 - 9.0 ms
        _event("%fusion.1 = bf16[8]", 1.0, 1.5, **decode("ssm/in_proj/dot")),
        _event("%fusion.2 = bf16[8]", 1.5, 1.6, **decode("ssm/conv/mul")),
        _event("%ssd_state_update.3 = f32[8]", 1.6, 4.6,
               **decode("ssm/scan/ssd_state_update/pallas_call")),
        _event("%fusion.4 = bf16[8]", 4.6, 4.7, **decode("ssm/gate_norm/mul")),
        _event("%fusion.5 = bf16[8]", 4.7, 5.0, **decode("ssm/out_proj/dot")),
        _event("%fusion.6 = bf16[8]", 5.0, 5.2,
               **decode("mlp/latent_down/dot_general")),
        _event("%gmm.7 = f32[8]", 5.2, 7.2, **decode("mlp/experts/gmm")),
        _event("%fusion.8 = bf16[8]", 7.2, 7.3,
               **decode("mlp/latent_up/dot_general")),
        _event("%grouped_decode_fused.9 = bf16[8]", 7.3, 7.8,
               **decode("attn/global/pallas_call")),
        _event("%fusion.10 = bf16[8]", 7.8, 8.0, **decode("out_proj/dot")),
        # a slice, 10.0 - 14.0 ms: two layers' scan kernels of 0.5 ms
        _event("%ssd_scan_fused.11 = f32[8]", 10.0, 10.5,
               **chunk("ssm/scan/ssd_scan_fused/pallas_call")),
        _event("%fusion.12 = bf16[8]", 10.5, 11.5, **chunk("ssm/in_proj/dot")),
        _event("%ssd_scan_fused.13 = f32[8]", 11.5, 12.0,
               **chunk("ssm/scan/ssd_scan_fused/pallas_call")),
        _event("%gmm.14 = f32[8]", 12.0, 14.0, **chunk("mlp/experts/gmm"))]
    planes = {
        "/host:CPU": {"main": [
            _event("bench/traced_window", 0.0, 20.0),
            _event("serve/step", 0.5, 15.0),
            _event("serve/prefill_chunk", 9.5, 14.5, size=512, offset=0),
            _event("serve/decode", 0.6, 9.2, running=100, live=128,
                   kv_bytes=100 * 2000 * 1024,
                   ssm_state_bytes=2 * 100 * ROW),
            _event("serve/decode/moe", 9.10, 9.11, moe_assignments=2750,
                   moe_experts_hit=500)]},
        "/device:TPU:0": {
            "XLA Modules": [_event("jit_decode_paged(1)", 1.0, 9.0),
                            _event("jit_chunk_paged(2)", 10.0, 14.0)],
            "XLA Ops": ops}}
    return {"trace": {"modules": {"jit_decode_paged": [8e-3]}},
            "program_trace": program_spans.reduce(planes), "config": config,
            "peak": flops.peaks("TPU v5 lite")}


def test_arithmetic(config):
    parts = flops_nemotron.parts(config)
    assert round(parts["mamba"] / 1e6, 2) == 109.64
    assert round(parts["attention"] / 1e6, 2) == 35.65
    assert round(parts["expert_shell"] / 1e6, 2) == 54.53
    assert parts["routed_expert"] == 2 * 1024 * 2688
    # 4,648.2M with the layers' norm scales, which `parts` leaves out
    assert round(flops_nemotron.param_count(config) / 1e6, 1) == 4648.1
    assert flops_nemotron.layer_counts(config) == {
        "mamba": 5, "experts": 5, "attention": 1}
    assert flops_nemotron.state_row_bytes(config) == ROW == 21_278_720
    assert flops_nemotron.kv_row_bytes(config) == 1024
    assert flops_nemotron.expert_bytes(config) == 11_010_048
    assert flops_nemotron.kv_read_cost(config, 1000.0) == (
        32 * 4 * 128 * 1000.0, 1024 * 1000.0)
    # the update: five operations a float32 state element, read + written
    assert flops_nemotron.state_update_cost(config, 8.0e6) == (5.0e6, 8.0e6)
    # one layer's scan of 512 tokens at the published block 128
    ops, nbytes = flops_nemotron.chunked_scan_cost(config, 512)
    assert ops == 128 * 4 * (2 * 128 * 128 * (128 + 64) + 4 * 128 * 128 * 64)
    assert nbytes == (512 * 128 * (2 * 64 * 4 + 4) + 512 * 2 * 8 * 128 * 2
                      + 2 * 128 * 64 * 128 * 4)


def test_device_ms_by_scope(run):
    ms = lambda module, *scopes, **kw: ssm_spans.device_ms(
        run, module, list(scopes), **kw)
    assert ms("decode_paged", "ssm") == pytest.approx(4.0)
    assert ms("decode_paged", "scan", under=["ssm"]) == pytest.approx(3.0)
    assert ms("decode_paged", "latent_down", "latent_up") == pytest.approx(0.3)
    assert ms("decode_paged", "attn") == pytest.approx(0.5)
    assert ms("chunk_paged", "ssm") == pytest.approx(2.0)
    assert ms("decode_paged", "rotary") is None


def test_roofline_shares(run, config):
    # 200 x 21.28 MB read and written against 3 ms under ssm/scan
    state = 2 * 100 * ROW
    assert ssm_spans.state_roofline_pct(run) == pytest.approx(
        100 * state / 819e9 / 3e-3)
    # one layer's scan: FLOPs bind (8.05 GFLOP / 197 TFLOP/s = 40.9 us
    # against 35.7 MB / 819 GB/s = 43.6 us: bytes, just), 0.5 ms a kernel
    cost = flops_nemotron.chunked_scan_cost(config, 512)
    assert ssm_spans.scan_kernel_roofline_pct(run) == pytest.approx(
        100 * max(cost[0] / 197e12, cost[1] / 819e9) / 0.5e-3)
    # 200,000 rows of 1,024 B against 0.5 ms under attn
    assert ssm_spans.kv_read_roofline_pct(run) == pytest.approx(
        100 * 200000 * 1024 / 819e9 / 0.5e-3)
    # 500 experts of 11.0 MB against 2 ms under experts
    assert ssm_spans.expert_stream_roofline_pct(run) == pytest.approx(
        100 * 500 * 11_010_048 / 819e9 / 2e-3)
    assert ssm_spans.moe_tokens_per_expert(run) == pytest.approx(5.5)
    least = flops_nemotron.decode_step_roofline_seconds(
        config, run["peak"], slots=100, kv_rows=200000, state_bytes=state,
        assignments=2750, experts_hit=500)
    # 990.7M parameters read whole, 500 experts, the state, the K/V
    assert least == pytest.approx(
        (2 * 990.7e6 + 500 * 11_010_048 + state + 200000 * 1024) / 819e9,
        rel=1e-3)
    assert ssm_spans.decode_step_mfu_pct(run) == pytest.approx(
        100 * least / 8e-3)


def test_a_program_or_a_family_without_them_gives_nothing(run, config):
    bare = dict(run, program_trace=dict(run["program_trace"], spans=[]))
    other = dict(run, config=dict(config, harness={"flops": "flops_mimo"}))
    none = dict(run, config={"hidden_size": 2048})
    for reader in (ssm_spans.state_roofline_pct,
                   ssm_spans.scan_kernel_roofline_pct,
                   ssm_spans.kv_read_roofline_pct,
                   ssm_spans.expert_stream_roofline_pct,
                   ssm_spans.moe_tokens_per_expert,
                   ssm_spans.decode_step_mfu_pct):
        assert reader(bare) is None
        if reader is not ssm_spans.moe_tokens_per_expert:
            assert reader(none) is None
    # a family whose arithmetic lacks the recurrent functions
    assert ssm_spans.state_roofline_pct(other) is None
    assert ssm_spans.scan_kernel_roofline_pct(other) is None
    assert ssm_spans.device_ms({"trace": None}, "decode_paged",
                               ["ssm"]) is None


def test_the_configuration_keeps_its_sources_numbers_and_lists_its_cuts(
        manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert entry is manifest["configs"][-1]  # added at the end
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == entry["source"])
        changed = [key for key, value in row["config"].items()
                   if config[key] != value]
        assert changed == ["hybrid_override_pattern", "n_routed_experts",
                           "num_hidden_layers", "vocab_size"]
        assert sorted(changed) == sorted(entry["reduced"])
        assert config["published"] == {key: row["config"][key]
                                       for key in entry["reduced"]}
        # one whole period as published: layers 27-37
        assert config["hybrid_override_pattern"] == row["config"][
            "hybrid_override_pattern"][27:38] == "MEMEMEMEM*E"
    # the floors of a cut: a whole period, 8 experts, an eighth of the ids
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert list(config["assumed"])[0] == "no_rotary"
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell is manifest["workloads"][-1]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["decode_device_ms.ssm", "ssm_state_roofline",
                    "prefill_chunk_device_ms.ssm", "ssd_scan_roofline",
                    "decode_device_ms.latent_proj",
                    "expert_stream_roofline.ssm", "moe_tokens_per_expert.ssm",
                    "decode_step_mfu_pct.ssm", "kv_read_roofline.ssm",
                    "decode_device_ms.attn.ssm"]
    assert [m["name"] for m in manifest["per_layer"][-len(mine):]] == mine
    for name in ("serve_tok_s", "itl_p95_ms"):
        metric = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL


def test_the_program_makes_the_files_model(config):
    import jax.numpy as jnp
    from benchmarks.harness import model_nemotron
    cfg = model_nemotron.transformer_config(config, attention="dense",
                                            dtype=jnp.bfloat16)
    assert cfg.layer_pattern == "MEMEMEMEM*E" and not cfg.rope
    assert (cfg.dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssd_state_dim,
            cfg.ssm_groups, cfg.ssm_conv) == (4096, 128, 64, 128, 8, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.qk_head_dim) == (32, 2, 128)
    assert (cfg.n_routed, cfg.held_experts, cfg.expert_top_k,
            cfg.expert_hidden, cfg.expert_latent, cfg.shared_hidden,
            cfg.expert_scale) == (512, (0, 128), 22, 2688, 1024, 5376, 5.0)
    with pytest.raises(ValueError, match="cannot express"):
        model_nemotron.transformer_config(dict(config,
                                               mlp_hidden_act="silu"))


def test_rehearsal_runs_the_new_cell_to_its_last_line(root):
    done = _run(root, "--workload", CELL, "--seed", str(2 ** 31 + 17),
                "--seconds", "2", "--trace", "0", "--rehearse",
                os.path.join("benchmarks", "tests", "toy_nemotron.json"))
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
    "nemotron-no-conv", "nemotron-no-dt-input", "nemotron-state-reset",
    "nemotron-gated-silu", "nemotron-bfloat16"])
def test_controls_go_through_the_cells_own_comparison(root, tmp_path,
                                                      control):
    """benchmarks/controls/nemotron-*.json laid over the toy sizes: a
    planted fault in the conv, the time step's input or the experts'
    activation comes out as not correct. The state reset falls at
    position 512, past the toy's contexts, and the bfloat16 control
    takes the program's place: at toy widths their verdicts mean
    nothing either way; which limit refuses them at the published
    widths is a reading of the chip (PERF.md section 6)."""
    from benchmarks.run import merge
    with open(os.path.join(root, "benchmarks", "tests",
                           "toy_nemotron.json")) as f:
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
    if control in ("nemotron-bfloat16", "nemotron-state-reset"):
        assert "check logit_rms" in done.stdout and (
            "CONTROL" in done.stdout or "PLANTED FAULT" in done.stdout)
        return
    assert line["correct"] is False and "PLANTED FAULT" in done.stdout
    assert [l for l in done.stdout.splitlines() if "CHECK FAILED" in l]
