# Subprocess smoke tests for every shipped example — the user-facing
# entry points themselves, driven exactly as a user would (CLI module
# execution, config overrides), on tiny budgets.
import json
import os
import subprocess as sp
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(tmpdir, module, *overrides, timeout=420):
    env = dict(os.environ)
    env["_FLASHY_TMDIR"] = str(tmpdir)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + env.get("PYTHONPATH", "").split(os.pathsep))
    sp.run([sys.executable, "-m", module, "--clear", *overrides],
           check=True, env=env, timeout=timeout, cwd=REPO)


def _history(tmpdir):
    xps = os.path.join(str(tmpdir), "xps")
    (sig,) = os.listdir(xps)
    with open(os.path.join(xps, sig, "history.json")) as f:
        return json.load(f)


@pytest.mark.slow
def test_basic_example(tmp_path):
    _run_example(tmp_path, "examples.basic.train", "epochs=3")
    history = _history(tmp_path)
    assert len(history) == 3
    assert history[-1]["train"]["loss"] < history[0]["train"]["loss"]


@pytest.mark.slow
def test_cifar_example(tmp_path):
    _run_example(tmp_path, "examples.cifar.train", "epochs=1",
                 "max_batches=2", "batch_size=16")
    history = _history(tmp_path)
    assert set(history[0].keys()) == {"train", "valid"}
    assert "images_per_sec" in history[0]["train"]


@pytest.mark.slow
def test_cifar_example_vit(tmp_path):
    # the second model family through the SAME example/solver: the
    # BN-free state path (batch_stats == {}) must train and eval
    _run_example(tmp_path, "examples.cifar.train", "model=vit_tiny",
                 "epochs=1", "max_batches=2", "batch_size=16")
    history = _history(tmp_path)
    assert set(history[0].keys()) == {"train", "valid"}
    assert np.isfinite(history[0]["valid"]["loss"])


@pytest.mark.slow
def test_lm_example(tmp_path):
    # batch must divide the data axis (8 virtual devices under the
    # test env's XLA_FLAGS, which the subprocess inherits)
    _run_example(tmp_path, "examples.lm.solver", "epochs=1",
                 "steps_per_epoch=2", "batch_size=8", "seq_len=32",
                 "model.dim=32", "model.num_layers=1", "model.num_heads=2",
                 "model.vocab_size=64", "model.attention=dense",
                 "generate_every=1")
    history = _history(tmp_path)
    assert "ppl" in history[0]["train"]
    assert "ppl" in history[0]["valid"]
    assert "generate" in history[0]


@pytest.mark.slow
def test_lm_example_chunked_loss(tmp_path):
    # loss=chunked (ops.losses chunked CE head) through the example's
    # own training path; same train/valid surface as the dense loss.
    _run_example(tmp_path, "examples.lm.solver", "epochs=1",
                 "steps_per_epoch=2", "batch_size=8", "seq_len=32",
                 "model.dim=32", "model.num_layers=1", "model.num_heads=2",
                 "model.vocab_size=64", "model.attention=dense",
                 "loss=chunked", "loss_chunk=16")
    history = _history(tmp_path)
    assert "ppl" in history[0]["train"]
    assert history[0]["train"]["loss"] > 0


@pytest.mark.slow
def test_lm_example_pipelined(tmp_path):
    # the flagship trains THROUGH the example's own pipe>1 code path
    # (scan-stacked blocks + GPipe schedule), and the loss is sane.
    _run_example(tmp_path, "examples.lm.solver", "epochs=1",
                 "steps_per_epoch=2", "batch_size=8", "seq_len=32",
                 "model.dim=32", "model.num_layers=2", "model.num_heads=2",
                 "model.vocab_size=64", "model.attention=dense",
                 "mesh.pipe=2", "mesh.data=4")
    history = _history(tmp_path)
    assert "loss" in history[0]["train"]
    assert history[0]["train"]["loss"] > 0


@pytest.mark.slow
def test_lm_solver_pipelined_loss_parity(tmp_path):
    # The example's own train step with mesh.pipe=2 computes the same
    # loss as the unpipelined (pipe=1) solver on identical params+batch.
    import jax
    from examples.lm.solver import LMSolver
    from flashy_tpu.xp import Config, temporary_xp

    def make_cfg(mesh):
        return Config({
            "model": {"vocab_size": 64, "dim": 32, "num_layers": 2,
                      "num_heads": 2, "mlp_ratio": 2, "attention": "dense",
                      "scan_layers": True},
            "mesh": mesh,
            "seq_len": 32, "batch_size": 8, "accumulate": 1,
            "steps_per_epoch": 2, "epochs": 1, "generate_every": 0,
            "lr": 1e-3, "warmup_steps": 1, "weight_decay": 0.0,
        })

    losses = {}
    for name, mesh in (("plain", {"data": 8, "pipe": 1}),
                       ("piped", {"data": 4, "pipe": 2})):
        with temporary_xp():
            solver = LMSolver(make_cfg(mesh))
            _, metrics = solver._train_step(solver.state, solver.batch_at(0))
            losses[name] = float(jax.device_get(metrics["loss"]))
    assert abs(losses["plain"] - losses["piped"]) < 1e-3, losses


def test_cifar_ingestion_override(tmp_path, monkeypatch):
    import pickle
    import numpy as np
    import pytest
    from examples.cifar.data import load_cifar10

    # explicit root that doesn't resolve must raise, not silently fall
    # back to synthetic (that would fake the accuracy-to-baseline run)
    with pytest.raises(FileNotFoundError):
        load_cifar10(str(tmp_path / "missing"))
    monkeypatch.setenv("FLASHY_TPU_CIFAR", str(tmp_path / "missing"))
    with pytest.raises(FileNotFoundError):
        load_cifar10()
    monkeypatch.delenv("FLASHY_TPU_CIFAR")

    # a directory in the on-disk format torchvision unpacks
    # (cifar-10-batches-py pickles with b"data" [N, 3072] and b"labels")
    root = tmp_path / "cifar-10-batches-py"
    root.mkdir()
    rng = np.random.default_rng(0)
    for name, n in [(f"data_batch_{i}", 4) for i in range(1, 6)] + [
            ("test_batch", 6)]:
        entry = {b"data": rng.integers(0, 256, (n, 3072), dtype=np.uint8),
                 b"labels": rng.integers(0, 10, n).tolist()}
        with open(root / name, "wb") as f:
            pickle.dump(entry, f)

    x_train, y_train, x_test, y_test, is_real = load_cifar10(str(root))
    assert is_real
    assert x_train.shape == (20, 32, 32, 3) and y_train.shape == (20,)
    assert x_test.shape == (6, 32, 32, 3)
    assert x_train.dtype == np.float32 and 0.0 <= x_train.min() <= x_train.max() <= 1.0

    # env var route finds the same directory
    monkeypatch.setenv("FLASHY_TPU_CIFAR", str(root))
    assert load_cifar10()[4] is True


def test_lm_eval_stream_disjoint_from_train():
    """The held-out stream must be an independently-seeded subset, not a
    step offset: at IDENTICAL step indices train and eval batches differ,
    both streams are deterministic, and both share the same Markov
    transition structure (same seed -> same mixing table)."""
    from examples.lm.solver import synthetic_token_stream

    stream = synthetic_token_stream(vocab_size=128)
    for step in (0, 1, 12345):
        train = stream(4, 64, step, subset=0)
        evalb = stream(4, 64, step, subset=1)
        assert not np.array_equal(train, evalb), step
        np.testing.assert_array_equal(train, stream(4, 64, step, subset=0))
        np.testing.assert_array_equal(evalb, stream(4, 64, step, subset=1))


def test_lm_solver_ema_shadow_tracks_params():
    """ema_decay > 0 threads an f32 shadow through the sharded jitted
    train step; valid() evaluates the shadow. The shadow must (a) exist
    in the checkpointed state, (b) move toward the live params, (c) stay
    f32 while params are whatever the model config says."""
    import jax
    import jax.numpy as jnp
    from examples.lm.solver import LMSolver
    from flashy_tpu.xp import Config, temporary_xp

    cfg = Config({
        "model": {"vocab_size": 64, "dim": 32, "num_layers": 1,
                  "num_heads": 2, "mlp_ratio": 2, "attention": "dense"},
        "mesh": {"data": 8}, "seq_len": 16, "batch_size": 8,
        "accumulate": 1, "steps_per_epoch": 2, "epochs": 1,
        "generate_every": 0, "lr": 1e-2, "warmup_steps": 1,
        "weight_decay": 0.0, "ema_decay": 0.9,
    })
    with temporary_xp():
        solver = LMSolver(cfg)
        assert "ema" in solver.state
        before_leaf = jax.tree_util.tree_leaves(solver.state["ema"])[0]
        assert before_leaf.dtype == jnp.float32
        # the train step donates its input state: snapshot to host first
        before = np.asarray(jax.device_get(before_leaf), np.float64)
        state, _ = solver._train_step(solver.state, solver.batch_at(0))
        # shadow moved toward the updated params
        p = jax.tree_util.tree_leaves(state["params"])[0]
        e = jax.tree_util.tree_leaves(state["ema"])[0]
        assert e.dtype == jnp.float32
        # warmup decay at step 0 is 1/10: shadow is 90% of the way to p
        np.testing.assert_allclose(
            np.asarray(e, np.float64),
            before * 0.1 + np.asarray(p, np.float64) * 0.9,
            rtol=2e-3, atol=2e-6)


def test_lm_solver_ema_reconcile_after_restore():
    """restore() replaces the state wholesale; the solver must align the
    restored contents with THIS run's ema_decay (a pre-EMA checkpoint
    resumed with EMA on gets a fresh shadow; a shadow resumed with EMA
    off is dropped)."""
    import jax
    import jax.numpy as jnp
    from examples.lm.solver import LMSolver
    from flashy_tpu.xp import Config, temporary_xp

    def make(decay):
        return Config({
            "model": {"vocab_size": 64, "dim": 32, "num_layers": 1,
                      "num_heads": 2, "mlp_ratio": 2, "attention": "dense"},
            "mesh": {"data": 8}, "seq_len": 16, "batch_size": 8,
            "accumulate": 1, "steps_per_epoch": 1, "epochs": 1,
            "generate_every": 0, "lr": 1e-2, "warmup_steps": 1,
            "weight_decay": 0.0, "ema_decay": decay,
        })

    with temporary_xp():
        solver = LMSolver(make(0.9))
        # simulate restoring a pre-EMA checkpoint
        del solver.state["ema"]
        solver._reconcile_ema()
        assert "ema" in solver.state
        leaf = jax.tree_util.tree_leaves(solver.state["ema"])[0]
        assert leaf.dtype == jnp.float32

    with temporary_xp():
        solver = LMSolver(make(0.0))
        # simulate restoring a checkpoint that carried a shadow
        solver.state["ema"] = solver.state["params"]
        solver._reconcile_ema()
        assert "ema" not in solver.state


@pytest.mark.slow
def test_mlm_example(tmp_path):
    # the bidirectional encoder workload end-to-end (causal=False
    # through the shared blocks, masked-CE objective, solver surface)
    _run_example(tmp_path, "examples.mlm.solver", "epochs=1",
                 "steps_per_epoch=2", "valid_steps=1", "batch_size=8",
                 "seq_len=32", "model.dim=32", "model.num_layers=1",
                 "model.num_heads=2", "model.vocab_size=64",
                 "model.attention=dense", "warmup_steps=1")
    history = _history(tmp_path)
    assert "ppl" in history[0]["train"]
    assert np.isfinite(history[0]["valid"]["loss"])


def test_mlm_masking_recipe_invariants():
    """batch_at implements the 80/10/10 BERT recipe: ~mask_prob of
    positions selected; of those ~80% become [MASK], ~10% random, ~10%
    unchanged; labels always hold the ORIGINAL token; the [MASK] id
    never occurs naturally in the labels."""
    import jax
    from examples.mlm.solver import MLMSolver
    from flashy_tpu.xp import Config, temporary_xp

    cfg = Config({
        "model": {"vocab_size": 64, "dim": 32, "num_layers": 1,
                  "num_heads": 2, "mlp_ratio": 2, "attention": "dense"},
        "mesh": {"data": 8}, "seq_len": 128, "batch_size": 16,
        "mask_prob": 0.15, "mask_token": 0,
        "epochs": 1, "steps_per_epoch": 1, "valid_steps": 0,
        "lr": 1e-3, "warmup_steps": 1, "weight_decay": 0.0,
    })
    with temporary_xp():
        solver = MLMSolver(cfg)
        batch = {k: np.asarray(jax.device_get(v))
                 for k, v in solver.batch_at(0).items()}

    sel = batch["selected"]
    frac = sel.mean()
    assert 0.10 < frac < 0.20, frac
    # the reserved id never appears among the labels or random swaps
    assert (batch["labels"] != 0).all()
    # unselected inputs are untouched
    np.testing.assert_array_equal(batch["inputs"][~sel],
                                  batch["labels"][~sel])
    masked = (batch["inputs"] == 0) & sel
    changed = (batch["inputs"] != batch["labels"]) & sel & ~masked
    kept = (batch["inputs"] == batch["labels"]) & sel
    n = sel.sum()
    assert 0.7 < masked.sum() / n < 0.9          # ~80% [MASK]
    assert kept.sum() / n > 0.05                 # ~10% kept (+ random
    assert changed.sum() / n < 0.2               #  collisions land here)
    # train and eval masks/streams differ at the same step (batch_at
    # is stateless — same solver serves both subsets), and a NON-ZERO
    # mask_token is reserved just the same (the id never occurs in
    # labels; 80% of selected inputs carry it)
    with temporary_xp():
        solver = MLMSolver(cfg)
        ev = {k: np.asarray(jax.device_get(v))
              for k, v in solver.batch_at(0, eval_set=True).items()}
        solver.cfg["mask_token"] = 5
        b5 = {k: np.asarray(jax.device_get(v))
              for k, v in solver.batch_at(0).items()}
    assert not np.array_equal(ev["labels"], batch["labels"])
    assert (b5["labels"] != 5).all()
    sel5 = b5["selected"]
    n5 = sel5.sum()
    assert 0.7 < ((b5["inputs"] == 5) & sel5).sum() / n5 < 0.9


@pytest.mark.slow
def test_translate_example(tmp_path):
    # the encoder-decoder family end-to-end through the solver surface:
    # teacher-forced training + cached-greedy-decode accuracy metrics
    _run_example(tmp_path, "examples.translate.solver", "epochs=1",
                 "steps_per_epoch=2", "valid_steps=1",
                 "model.vocab_size=32", "model.dim=32",
                 "model.enc_layers=1", "model.dec_layers=1",
                 "model.num_heads=2", "model.attention=dense",
                 "src_len=8", "batch_size=8", "warmup_steps=1")
    history = _history(tmp_path)
    assert "seq_acc" in history[0]["valid"]
    assert np.isfinite(history[0]["valid"]["loss"])


def test_translate_pairs_subsets_disjoint():
    from examples.translate.solver import synthetic_pairs

    pairs = synthetic_pairs(64, task="reverse")
    s0, t0 = pairs(4, 8, 0, subset=0)
    s1, t1 = pairs(4, 8, 0, subset=1)
    assert not np.array_equal(s0, s1)
    np.testing.assert_array_equal(t0, s0[:, ::-1])
    # deterministic per (step, subset)
    s0b, _ = pairs(4, 8, 0, subset=0)
    np.testing.assert_array_equal(s0, s0b)
    with pytest.raises(ValueError, match="task"):
        synthetic_pairs(64, task="sort")
