# Unit tests for flashy_tpu.utils — real coverage for what the reference
# left as an empty stub (tests/test_formatter.py etc. were license-only).
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.utils import averager, freeze, to_numpy, tree_bytes, write_and_rename


def test_averager_plain_mean():
    update = averager()
    out = update({"loss": 4.0})
    assert out == {"loss": 4.0}
    out = update({"loss": 2.0})
    assert out == {"loss": 3.0}
    out = update({"loss": 0.0, "acc": 1.0})
    assert out["loss"] == pytest.approx(2.0)
    assert out["acc"] == pytest.approx(1.0)


def test_averager_weighted():
    update = averager()
    update({"loss": 1.0}, weight=1)
    out = update({"loss": 4.0}, weight=3)
    assert out["loss"] == pytest.approx((1 + 12) / 4)


def test_averager_ema():
    update = averager(beta=0.5)
    update({"x": 1.0})
    out = update({"x": 3.0})
    # num = 1*0.5 + 3 = 3.5 ; den = 0.5 + 1 = 1.5
    assert out["x"] == pytest.approx(3.5 / 1.5)


def test_averager_jax_scalars():
    update = averager()
    out = update({"loss": jnp.asarray(2.0)})
    assert isinstance(out["loss"], float)
    assert out["loss"] == 2.0


def test_write_and_rename(tmp_path):
    target = tmp_path / "file.bin"
    with write_and_rename(target) as f:
        f.write(b"hello")
        assert not target.exists()  # nothing at final path until close
    assert target.read_bytes() == b"hello"
    assert not (tmp_path / "file.bin.tmp").exists()


def test_write_and_rename_pid(tmp_path):
    target = tmp_path / "file.txt"
    with write_and_rename(target, "w", pid=True) as f:
        f.write("x")
        assert str(os.getpid()) in f.name
    assert target.read_text() == "x"


def test_freeze_blocks_gradient():
    def loss(w):
        return jnp.sum(freeze(w) * w)

    w = jnp.ones(3)
    grad = jax.grad(loss)(w)
    # d/dw [stop_grad(w) * w] = stop_grad(w) = 1
    np.testing.assert_allclose(grad, np.ones(3))


def test_to_numpy_and_tree_bytes():
    tree = {"a": jnp.zeros((2, 3), jnp.float32), "b": [np.ones(4, np.float64), "str"]}
    host = to_numpy(tree)
    assert isinstance(host["a"], np.ndarray)
    assert host["b"][1] == "str"
    assert tree_bytes(tree) == 2 * 3 * 4 + 4 * 8


def test_prng_key_helpers():
    from flashy_tpu.utils import data_key, model_key
    a = model_key(0)
    b = model_key(0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # data_key folds the rank in, so it differs from the raw seed key
    d = data_key(0)
    assert d.shape == a.shape
    assert not np.array_equal(np.asarray(d), np.asarray(a))


def test_compile_cache_helper_honours_env_and_ignores_cwd(monkeypatch,
                                                          tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache
    directory in code (JAX reads the variable itself); otherwise the
    cache lands at one fixed, git-ignored path inside the checkout,
    whatever the working directory."""
    from pathlib import Path

    import jax

    import flashy_tpu
    from flashy_tpu.utils import (COMPILE_CACHE_DIRNAME,
                                  configure_compile_cache)

    checkout = Path(flashy_tpu.__file__).resolve().parent.parent
    saved = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "placed_from_outside"))
        assert configure_compile_cache() == str(
            tmp_path / "placed_from_outside")
        assert jax.config.jax_compilation_cache_dir is None  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        seen = []
        for cwd in (tmp_path, checkout / "tests"):
            monkeypatch.chdir(cwd)
            seen.append(configure_compile_cache())
            assert jax.config.jax_compilation_cache_dir == seen[-1]
        assert seen[0] == seen[1] == str(checkout / COMPILE_CACHE_DIRNAME)
        ignored = (checkout / ".gitignore").read_text().split()
        assert COMPILE_CACHE_DIRNAME + "/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
