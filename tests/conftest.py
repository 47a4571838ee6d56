# Test harness: run everything on a virtual 8-device CPU mesh so device
# level parallelism (sharding, collectives, ring attention) is exercised
# without TPU hardware — the strategy SURVEY.md §4 prescribes (the
# reference's analogue was gloo-on-localhost, tests/test_distrib.py:22).
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# entry points under test point JAX's persistent compile cache into the
# checkout (utils.configure_compile_cache); tests compile from scratch
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from flashy_tpu.xp import temporary_xp  # noqa: E402


@pytest.fixture()
def xp():
    """A throwaway active XP in a temp dir."""
    with temporary_xp({"dummy": 1}) as active:
        yield active


@pytest.fixture()
def mesh8():
    """2x2x2x1 mesh (data x fsdp x tensor x seq) over the 8 CPU devices."""
    from flashy_tpu.parallel import make_mesh
    return make_mesh({"data": 2, "fsdp": 2, "tensor": 2, "seq": 1})


def spawn_workers(script_path, num_workers, timeout=600, extra_env=None):
    """Launch `num_workers` copies of a worker script that rendezvous via
    jax.distributed on localhost; returns [(exit_code, stderr), ...].

    Shared by the multi-process test suites. Worker stderr goes to temp
    files (no pipes, so a chatty worker can never block on a full pipe
    while a sibling is being drained); on timeout every worker is
    killed and whatever stderr was captured is still returned.
    """
    import socket
    import subprocess as sp
    import sys
    import tempfile
    import time

    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]

    procs = []
    err_files = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update({
            "FLASHY_TPU_COORDINATOR": f"localhost:{port}",
            "FLASHY_TPU_NUM_PROCESSES": str(num_workers),
            "FLASHY_TPU_PROCESS_ID": str(rank),
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
                + env.get("PYTHONPATH", "").split(os.pathsep)),
        })
        if extra_env:
            env.update(extra_env)
        err_file = tempfile.NamedTemporaryFile("w+", suffix=f".worker{rank}.err",
                                               delete=False)
        err_files.append(err_file)
        procs.append(sp.Popen([sys.executable, str(script_path)], env=env,
                              stderr=err_file, text=True))

    deadline = time.time() + timeout
    try:
        for p in procs:
            remaining = max(deadline - time.time(), 1.0)
            try:
                p.wait(timeout=remaining)
            except sp.TimeoutExpired:
                break
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    results = []
    for p, err_file in zip(procs, err_files):
        err_file.flush()
        err_file.seek(0)
        results.append((p.returncode, err_file.read()))
        err_file.close()
        os.unlink(err_file.name)
    return results
