# chip_smoke.py is the driver's on-chip check. What can be proven
# without a chip: it refuses to pass without one, it needs the repo
# around it, and its parent process stays off JAX (a parent that
# touched JAX would hold the chip its children need).
"""Contract tests for chip_smoke.py that need no accelerator."""
import ast
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _result_lines(stdout: str):
    return [line for line in stdout.splitlines() if line.startswith("{")]


def test_chip_smoke_fails_without_a_chip_and_outside_the_repo(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--out", str(tmp_path / "out")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stdout
    assert not _result_lines(proc.stdout)  # no result without a chip

    # alone in a directory: nothing of the repo to import
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SCRIPT, lone)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=lone,
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)


def test_chip_smoke_parent_imports_only_the_standard_library():
    with open(SCRIPT) as f:
        tree = ast.parse(f.read())
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level.update(alias.name.split(".")[0] for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            top_level.add((node.module or "").split(".")[0])
    assert top_level <= set(sys.stdlib_module_names), top_level
    parent = next(node for node in tree.body
                  if isinstance(node, ast.FunctionDef)
                  and node.name == "parent")
    assert not [node for node in ast.walk(parent)
                if isinstance(node, (ast.Import, ast.ImportFrom))]
