"""run.py end to end at a toy size on the CPU, one run per runner, and
its refusal to measure without a TPU."""
import json
import os
import subprocess
import sys

import pytest

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell, trace", [
    ("olmo1b-train-2k", 0), ("olmo1b-chat-closed", 0),
    ("olmo1b-chat-closed", 1)])
def test_rehearsal_runs_each_runner_end_to_end(root, manifest, cell, trace):
    if cell not in [w["name"] for w in manifest["workloads"]]:
        pytest.skip(f"{cell} is not in BENCHMARK.json")
    done = _run(root, "--workload", cell, "--seed", str(2 ** 31 + 17),
                "--seconds", "2", "--trace", str(trace), "--rehearse",
                os.path.join("benchmarks", "tests", "toy.json"))
    assert done.returncode == 0, done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    # a rehearsal's last line is prefixed: nothing can take it for a result
    assert last.startswith("[rehearsal] ")
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)
    line = json.loads(last[len("[rehearsal] "):])
    assert set(line) == KEYS  # a CPU trace has no device plane: no breakdown
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    kind = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in manifest[kind]
              if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= wanted
    if not trace:
        assert set(line["metrics"]) == wanted
    else:  # trace readers find no device plane on the CPU and stay out
        assert "tick_ms" in line["metrics"]
        assert "device_idle_pct.serve" not in line["metrics"]
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result(root):
    done = _run(root, "--workload", "olmo1b-train-2k", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode == 3
    for line in done.stdout.splitlines():
        assert not line.startswith("{")
