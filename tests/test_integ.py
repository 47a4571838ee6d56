# End-to-end integration test driving the real CLI in subprocesses —
# the role of reference tests/test_integ.py:12-29: train 2 epochs, check
# history; rerun and check resume extends history with the first entries
# bit-identical; then a genuine 2-worker distributed run on localhost.
import json
import os
import subprocess as sp
import sys

import pytest


def _run(tmpdir, *args, workers=None):
    env = dict(os.environ)
    env["_FLASHY_TMDIR"] = str(tmpdir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    cmd = [sys.executable, "-m", "tests.dummy.train", *args]
    if workers:
        cmd.append(f"--workers={workers}")
    sp.run(cmd, check=True, env=env, timeout=600)


def _history(tmpdir):
    xps = os.path.join(str(tmpdir), "xps")
    (sig,) = os.listdir(xps)
    with open(os.path.join(xps, sig, "history.json")) as f:
        return json.load(f)


@pytest.mark.slow
def test_integ(tmp_path):
    _run(tmp_path, "--clear", "stop_at=2")
    history = _history(tmp_path)
    assert len(history) == 2
    assert set(history[0].keys()) == {"train", "valid"}
    old_history = list(history)

    # resume: same config (stop_at excluded from the signature) -> same
    # XP; continues to epoch 4 with the first two entries untouched.
    _run(tmp_path)
    history = _history(tmp_path)
    assert len(history) == 4
    assert history[:2] == old_history

    # training made progress
    assert history[-1]["valid"]["mse"] < history[0]["valid"]["mse"]


@pytest.mark.slow
def test_integ_distributed(tmp_path):
    _run(tmp_path, "--clear", "stop_at=2", workers=2)
    history = _history(tmp_path)
    assert len(history) == 2
    # both ranks logged to their own file
    xps = os.path.join(str(tmp_path), "xps")
    (sig,) = os.listdir(xps)
    files = os.listdir(os.path.join(xps, sig))
    assert "solver.log.0" in files and "solver.log.1" in files
