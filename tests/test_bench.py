# bench.py is one process that benchmarks on a TPU or not at all: no
# chip means a non-zero exit and no result line, and an error recorded
# by any leg or sub-leg fails the run. Both properties are provable
# without a chip.
"""Process-model + output-contract tests for bench.py."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_main_exits_nonzero_without_a_tpu(monkeypatch, tmp_path, capsys):
    """On a CPU-only machine main() must refuse: non-zero exit, no JSON
    result on stdout, no leg run."""
    import bench

    # with the variable set the helper sets no cache dir in code, so the
    # rest of the test session is left alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "partial.json"))
    monkeypatch.setattr(bench, "run_legs", lambda *a, **k: pytest.fail(
        "a leg ran off-TPU"))
    with pytest.raises(SystemExit) as raised:
        bench.main()
    assert raised.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
    assert not (tmp_path / "partial.json").exists()


def test_leg_and_subleg_errors_fail_the_run(monkeypatch, tmp_path):
    """A leg that raises is recorded and the next leg still runs; the
    exit code is non-zero for a raised leg AND for an error a sub-leg
    swallowed into its record."""
    import bench

    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "partial.json"))
    monkeypatch.setattr(bench, "_STATE_DIR", str(tmp_path))

    def boom(record):
        raise RuntimeError("mosaic refused the kernel")

    legs = {"smoke": boom,
            "cifar": lambda record: {"images_per_sec_per_chip": 1.0}}
    record = bench.run_legs(legs, {}, "tpu")
    assert "mosaic refused" in record["smoke"]["error"]
    assert record["cifar"]["images_per_sec_per_chip"] == 1.0  # ran after
    assert record["cifar"]["leg_platform"] == "tpu"
    assert bench.recorded_errors(record) == ["smoke.error"]
    assert bench.exit_code(record) == 1
    with open(tmp_path / "partial.json") as f:
        assert json.load(f)["smoke"]["error"]

    healthy = {"cifar": {"images_per_sec_per_chip": 1.0},
               "decode": {"tokens_per_sec_per_chip": 2.0}}
    assert bench.exit_code(healthy) == 0
    # the decode leg's sub-legs record `<name>_error` and carry on
    healthy["decode"]["fused_error"] = "kernel did not lower"
    assert bench.recorded_errors(healthy) == ["decode.fused_error"]
    assert bench.exit_code(healthy) == 1
    # nested one level deeper (lm.tp.error), and a None *_error is clean
    nested = {"cifar": {"images_per_sec_per_chip": 1.0},
              "roofline": {"lm_cost_error": None},
              "lm": {"tp": {"error": "x"}}}
    assert bench.recorded_errors(nested) == ["lm.tp.error"]
    # no headline is a failure even with no error recorded
    assert bench.exit_code({"decode": {"tokens_per_sec_per_chip": 2.0}}) == 1


def test_peak_for_reads_the_one_table():
    """bench._peak_for and observability.device_peaks are one table:
    same answers, and an unknown accelerator is an error in both."""
    import bench
    from flashy_tpu.observability import device_peaks

    for kind in ("TPU v5 lite", "TPU v5e", "TPU v4", "TPU v5p"):
        assert bench._peak_for(kind) == device_peaks(kind)[0]
    assert bench._peak_for("TPU v5 lite") == 197e12
    assert device_peaks("TPU v5 lite") == (197e12, 819e9)
    assert device_peaks("cpu") == (None, None)
    assert device_peaks() == (None, None)  # this session's CPU backend
    for fn in (bench._peak_for, device_peaks):
        with pytest.raises(ValueError, match="DEVICE_SPECS"):
            fn("TPU v9 imaginary")


def test_per_chip_divisor_is_the_devices_used():
    """A mesh-less computation runs on one device of the eight here;
    its per-chip rate divides by one, not by the host's device count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bench

    assert len(jax.devices()) == 8
    single = {"w": jnp.ones((8, 4)), "n": 3}
    assert bench._devices_used(single) == 1
    mesh = Mesh(jax.devices(), ("d",))
    spread = jax.device_put(jnp.ones((8, 4)), NamedSharding(mesh, P("d")))
    assert bench._devices_used({"w": spread}) == 8


def test_compact_line_fits_driver_tail_worst_case():
    """Even with every leg at maximal field width, the stdout line must
    fit MAX_LINE_CHARS."""
    import bench

    fat_leg = {
        "tokens_per_sec_per_chip": 123456.8, "mfu": 0.2984,
        "mfu_vs_measured": 0.9876, "achieved_tflops_per_chip": 158.63,
        "batch_size": 512, "variant": "flash_noremat_chunked_b32",
        "images_per_sec_per_chip": 132109.4, "flash_speedup": 12.83,
        "lm_step_ms": 1234.56, "cifar_step_ms": 987.65,
        "measured_bf16_tflops": 197.33, "ceiling_bf16_tflops": 197.33,
        "speedup": 11.83, "flash_tuned_ms": 123.45, "dense_ms": 456.78,
        "overhead_pct": 123.4, "steps_per_sec": 1234.56,
        "gib_per_sec": 123.45, "bus_bandwidth_gb_s": 1234.56,
        "bubble_frac_1f1b_int2": 0.157895, "stash_flat_in_m": True,
        "recompiles": 0, "packed_step_ratio": 0.5717,
        "packed_tick_eff": 0.8984, "packed_bitwise": True,
        # the decode sub-leg scalars (spec/paged/fused/ssd) and the
        # recovery scalars (wal_replay_ms & co) are deliberately NOT
        # in this maximal leg: they only ever appear in their one
        # entry (never once per leg), and the runtime shed guard
        # keeps any real overflow inside MAX_LINE_CHARS by trimming
        # detail. The widest decode-only keys still ride along as
        # representatives so each subleg's longest key IS priced once:
        "fused_vs_gather": 12.345,
        "ssd_max_concurrent_slots_at_fixed_hbm": 12345678,
        # the lm tensor-parallel subleg scalars at maximal width, plus
        # the pipeline leg's 3D-composition flag — every key
        # _COMPACT_KEYS whitelists must be priced into the budget
        "tp_step_ms_t1": 12345.67, "tp_step_ms_t2": 12345.67,
        "tp_step_ms_t4": 12345.67, "tp_opt_bytes_ratio": 0.1259,
        "tp_flash_bwd_parity": 0.000123, "flash_bwd_vs_unfused": 12.345,
        "tensor_compose_ok": False,
        "leg_platform": "tpu",
    }
    record = {name: dict(fat_leg) for name in bench.LEG_ORDER}
    compact = {
        "platform": "tpu", "device_kind": "TPU v5 lite chip",
        "n_devices": 8, "peak_bf16_tflops": 197.0,
        "legs": bench._compact_legs(record),
        "detail_path": "BENCH_DETAIL.json",
    }
    payload = {"metric": "cifar10_resnet18_train_images_per_sec_per_chip",
               "value": 132109.4, "unit": "images/sec/chip",
               "vs_baseline": 44.036, "extra": compact}
    line = json.dumps(payload, separators=(",", ":"))
    assert len(line) <= bench.MAX_LINE_CHARS, len(line)
    # errored legs keep a truncated message, skipped legs carry nothing
    record["ring"] = {"error": "x" * 500}
    record["all_reduce"] = {"skipped": "single device"}
    compact_legs = bench._compact_legs(record)
    assert len(compact_legs["ring"]["error"]) == 60
    assert "all_reduce" not in compact_legs


def test_honest_ceiling_never_exceeds_one():
    """mfu_vs_measured must divide by a true capture-wide ceiling: when
    the LM leg sustains more than the MXU microbench read, the ceiling
    is lifted to the LM rate."""
    import bench

    record = {
        "mxu": {"measured_bf16_tflops": 45.33},
        "lm": {"achieved_tflops_per_chip": 58.63, "mfu_vs_measured": 1.29},
    }
    bench._apply_honest_ceiling(record)
    assert record["mxu"]["ceiling_bf16_tflops"] == 58.63
    # the lm leg itself set the ceiling: flag the source, and publish
    # no ratio for the self-referential leg (a 1.0 would masquerade as
    # an independent measurement)
    assert record["mxu"]["ceiling_source"] == "lm"
    assert record["lm"]["mfu_vs_measured"] is None

    # ...while an MXU-sourced ceiling keeps an honest sub-1.0 ratio
    mxu_record = {
        "mxu": {"measured_bf16_tflops": 80.0},
        "lm": {"achieved_tflops_per_chip": 58.63, "mfu_vs_measured": 0.7},
    }
    bench._apply_honest_ceiling(mxu_record)
    assert mxu_record["mxu"]["ceiling_source"] == "mxu"
    assert mxu_record["lm"]["mfu_vs_measured"] == round(58.63 / 80.0, 4)

    # mxu leg errored: the lm rate alone is not a ceiling
    no_mxu = {
        "mxu": {"error": "leg failed"},
        "lm": {"achieved_tflops_per_chip": 58.63, "mfu_vs_measured": 0.9},
    }
    bench._apply_honest_ceiling(no_mxu)
    assert no_mxu["lm"]["mfu_vs_measured"] is None
