# Tests for checkpoint IO: pickle path, atomicity, torch interop
# round-trip (the BASELINE.json north-star requirement), and optax state
# survival.
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flashy_tpu.checkpoint import (from_torch_state_dict, load_state, save_state,
                                   to_torch_state_dict)


def test_save_load_roundtrip(tmp_path):
    state = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "history": [{"train": {"loss": 1.0}}],
        "epoch": 3,
    }
    path = tmp_path / "ckpt.fsy"
    save_state(state, path)
    loaded = load_state(path)
    np.testing.assert_allclose(loaded["params"]["w"], np.arange(6).reshape(2, 3))
    assert isinstance(loaded["params"]["w"], np.ndarray)  # host arrays
    assert loaded["history"] == state["history"]
    assert loaded["epoch"] == 3


def test_no_partial_file_on_crash(tmp_path):
    path = tmp_path / "ckpt.fsy"
    save_state({"a": 1}, path)

    class Boom:
        def __reduce__(self):
            raise RuntimeError("not picklable")

    with pytest.raises(RuntimeError):
        save_state({"bad": Boom()}, path)
    # original checkpoint intact
    assert load_state(path) == {"a": 1}


def test_optax_state_roundtrip(tmp_path):
    params = {"w": jnp.ones(3)}
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    grads = {"w": jnp.full(3, 0.1)}
    _, opt_state = opt.update(grads, opt_state, params)

    save_state({"opt": opt_state}, tmp_path / "o.fsy")
    restored = load_state(tmp_path / "o.fsy")["opt"]
    orig_leaves = [np.asarray(x) for x in
                   __import__("jax").tree_util.tree_leaves(opt_state)]
    new_leaves = [np.asarray(x) for x in
                  __import__("jax").tree_util.tree_leaves(restored)]
    assert len(orig_leaves) == len(new_leaves)
    for a, b in zip(orig_leaves, new_leaves):
        np.testing.assert_allclose(a, b)


def test_torch_interop_roundtrip():
    torch = pytest.importorskip("torch")
    tree = {"layer": {"kernel": jnp.ones((2, 2)), "bias": jnp.zeros(2)}, "step": 5}
    flat = to_torch_state_dict(tree)
    assert isinstance(flat["layer.kernel"], torch.Tensor)
    assert flat["step"] == 5
    back = from_torch_state_dict(flat)
    np.testing.assert_allclose(back["layer"]["kernel"], np.ones((2, 2)))
    np.testing.assert_allclose(back["layer"]["bias"], np.zeros(2))


def test_from_torch_accepts_torch_module_state():
    torch = pytest.importorskip("torch")
    module = torch.nn.Linear(4, 2)
    tree = from_torch_state_dict(module.state_dict())
    assert tree["weight"].shape == (2, 4)
    assert tree["bias"].shape == (2,)


def test_orbax_sharded_roundtrip(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax
    from flashy_tpu.checkpoint import restore_sharded, save_sharded
    from flashy_tpu.parallel import make_mesh, shard_params

    mesh = make_mesh({"fsdp": 4, "data": 2})
    params = {"w": jnp.arange(1024 * 8, dtype=jnp.float32).reshape(1024, 8),
              "b": jnp.ones(8)}
    sharded = shard_params(params, mesh, min_size=16)
    save_sharded(sharded, tmp_path / "ckpt")
    restored = restore_sharded(tmp_path / "ckpt")
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(params["w"]))
    np.testing.assert_allclose(np.asarray(restored["b"]),
                               np.asarray(params["b"]))


def test_import_flashy_checkpoint(tmp_path):
    torch = pytest.importorskip("torch")
    from flashy_tpu.checkpoint import import_flashy_checkpoint

    # fabricate a reference-style checkpoint: torch.save of the solver
    # state dict shape (model/optim state dicts + history + cfg/sig)
    model = torch.nn.Linear(4, 2)
    state = {
        "model": model.state_dict(),
        "history": [{"train": {"loss": 1.0}}],
        "xp.cfg": {"lr": 0.1},
        "xp.sig": "abcd1234",
        "best_loss": torch.tensor(0.5),
    }
    torch.save(state, tmp_path / "checkpoint.th")

    imported = import_flashy_checkpoint(tmp_path / "checkpoint.th")
    assert imported["history"] == [{"train": {"loss": 1.0}}]
    assert imported["xp.sig"] == "abcd1234"
    assert imported["model"]["weight"].shape == (2, 4)
    assert isinstance(imported["model"]["weight"], np.ndarray)
    assert float(imported["best_loss"]) == 0.5


def test_import_flashy_checkpoint_nested_optimizer():
    torch = pytest.importorskip("torch")
    import tempfile
    from flashy_tpu.checkpoint import import_flashy_checkpoint

    model = torch.nn.Linear(4, 2)
    optim = torch.optim.Adam(model.parameters())
    model(torch.zeros(1, 4)).sum().backward()
    optim.step()
    with tempfile.TemporaryDirectory() as tmp:
        path = tmp + "/checkpoint.th"
        torch.save({"optim": optim.state_dict()}, path)
        imported = import_flashy_checkpoint(path)
    exp_avg = imported["optim"]["state"][0]["exp_avg"]
    assert isinstance(exp_avg, np.ndarray)  # deep conversion reached it


def test_import_flashy_checkpoint_unflattens_dotted_keys():
    torch = pytest.importorskip("torch")
    import tempfile
    from flashy_tpu.checkpoint import import_flashy_checkpoint

    model = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.Linear(8, 2))
    with tempfile.TemporaryDirectory() as tmp:
        path = tmp + "/checkpoint.th"
        torch.save({"model": model.state_dict()}, path)
        imported = import_flashy_checkpoint(path)
    # '0.weight' -> nested {'0': {'weight': ...}}
    assert imported["model"]["0"]["weight"].shape == (8, 4)
    assert imported["model"]["1"]["bias"].shape == (2,)


def test_place_like_restores_shardings():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flashy_tpu.checkpoint import place_like
    from flashy_tpu.parallel import make_mesh

    mesh = make_mesh({"fsdp": 4, "data": 2})
    sh = NamedSharding(mesh, P("fsdp", None))
    live = {"params": {"w": jax.device_put(jnp.ones((8, 4)), sh)},
            "step": 3, "note": "x"}
    restored = {"params": {"w": np.full((8, 4), 2.0, np.float32)},
                "step": 7, "note": "y"}
    placed = place_like(live, restored)
    assert isinstance(placed["params"]["w"], jax.Array)
    assert placed["params"]["w"].sharding == sh
    np.testing.assert_allclose(np.asarray(placed["params"]["w"]), 2.0)
    assert placed["step"] == 7 and placed["note"] == "y"


def test_place_like_tolerates_mismatch():
    import jax
    from flashy_tpu.checkpoint import place_like
    # shape mismatch -> restored value kept as-is; missing template -> kept
    live = {"w": jnp.ones((4,)), "extra": None}
    restored = {"w": np.ones((8,), np.float32), "new": 5}
    out = place_like(live, restored)
    assert isinstance(out["w"], np.ndarray) and out["w"].shape == (8,)
    assert out["new"] == 5


def test_place_like_keeps_uncommitted_leaves_uncommitted():
    # Regression: `jit(optax.init)` scalars (Adam's `count`) come back
    # UNCOMMITTED on the default device — they follow the other
    # arguments of the next jitted call. place_like used to device_put
    # them, committing the restored scalar to one device; the next
    # multi-device train step then rejected the state ("Received
    # incompatible devices": count on [0] vs params on the mesh) —
    # resume was broken for every multi-device LM example run.
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flashy_tpu.checkpoint import place_like
    from flashy_tpu.parallel import make_mesh

    mesh = make_mesh({"data": -1})
    params = {"w": jax.device_put(jnp.ones((8, 4)),
                                  NamedSharding(mesh, P()))}
    opt = optax.adam(1e-3)
    live = jax.jit(opt.init)(params)
    host = jax.tree_util.tree_map(np.asarray, live)
    placed = place_like(live, host)

    def committed(leaf):
        return getattr(leaf, "_committed", None)

    count_live, count_placed = live[0].count, placed[0].count
    assert committed(count_placed) == committed(count_live)
    # and the mixed state is accepted by a multi-device jitted step
    out = jax.jit(lambda p, s: (p["w"].sum(), s[0].count + 1))(
        params, placed)
    assert int(out[1]) == 1


def test_place_like_optax_namedtuple():
    import jax
    from flashy_tpu.checkpoint import place_like

    params = {"w": jnp.ones(3)}
    opt = optax.adam(1e-3)
    live = opt.init(params)
    host = jax.tree_util.tree_map(lambda x: np.asarray(x), live)
    placed = place_like(live, host)
    assert type(placed) is type(live)
    leaves = jax.tree_util.tree_leaves(placed)
    import jax as _jax
    assert all(isinstance(x, _jax.Array) or np.isscalar(x) for x in leaves)


def test_sharded_state_roundtrip_with_placements(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flashy_tpu.checkpoint import (load_state_sharded, save_state_sharded,
                                       sharded_checkpoint_exists)
    from flashy_tpu.parallel import make_mesh

    mesh = make_mesh({"fsdp": 4, "data": 2})
    sh = NamedSharding(mesh, P("fsdp", None))
    state = {
        "state": {"params": {"w": jax.device_put(
            jnp.arange(32.0).reshape(8, 4), sh)},
            "step": jnp.int32(5)},
        "history": [{"train": {"loss": 1.5}}],
        "xp.cfg": {"lr": 0.1},
    }
    directory = tmp_path / "ckpt.sharded"
    assert not sharded_checkpoint_exists(directory)
    save_state_sharded(state, directory)
    assert sharded_checkpoint_exists(directory)

    placements = {"state": state["state"]}
    restored = load_state_sharded(directory, placements)
    w = restored["state"]["params"]["w"]
    assert isinstance(w, jax.Array) and w.sharding == sh
    np.testing.assert_allclose(np.asarray(w), np.arange(32.0).reshape(8, 4))
    assert int(restored["state"]["step"]) == 5
    assert restored["history"] == [{"train": {"loss": 1.5}}]
    assert restored["xp.cfg"] == {"lr": 0.1}


def test_sharded_save_fits_a_file_size_limit(tmp_path, monkeypatch):
    # a state several times larger than the process may write into one
    # file (`ulimit -f`) must still save: Orbax's default of one data
    # file of up to 2 GB fails there with EFBIG
    pytest.importorskip("orbax.checkpoint")
    import os
    import resource
    from flashy_tpu import checkpoint

    monkeypatch.setattr(checkpoint, "DATA_FILE_BYTES", 256 << 10)
    rng = np.random.default_rng(0)
    state = {"w": jnp.asarray(rng.normal(size=(1024, 1024)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)}
    directory = tmp_path / "ckpt.sharded"
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 20, hard))
    try:
        checkpoint.save_state_sharded(state, directory)
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    largest = max(os.path.getsize(os.path.join(folder, name))
                  for folder, _, names in os.walk(directory)
                  for name in names)
    assert largest <= 2 * checkpoint.DATA_FILE_BYTES
    restored = checkpoint.load_state_sharded(directory, state)
    for key, value in state.items():
        np.testing.assert_array_equal(np.asarray(restored[key]),
                                      np.asarray(value))


def test_sharded_ab_slots_survive_next_save(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from flashy_tpu.checkpoint import (_read_slot_pointer, load_state_sharded,
                                       save_state_sharded)

    directory = tmp_path / "ckpt.sharded"
    save_state_sharded({"v": jnp.float32(1.0)}, directory)
    first_slot = _read_slot_pointer(directory)
    save_state_sharded({"v": jnp.float32(2.0)}, directory)
    second_slot = _read_slot_pointer(directory)
    assert first_slot != second_slot  # alternating slots
    assert float(np.asarray(load_state_sharded(directory)["v"])) == 2.0


def test_async_sharded_checkpointer_defers_commit(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from flashy_tpu.checkpoint import (AsyncShardedCheckpointer,
                                       load_state_sharded,
                                       sharded_checkpoint_exists)

    ckpt = AsyncShardedCheckpointer()
    directory = tmp_path / "ckpt.sharded"
    ckpt.save({"v": jnp.float32(1.0)}, directory)
    # not active until finalized: a crash here must keep the old state
    assert not sharded_checkpoint_exists(directory)
    ckpt.wait()
    assert sharded_checkpoint_exists(directory)
    assert float(np.asarray(load_state_sharded(directory)["v"])) == 1.0

    # second save: finalizes the first implicitly, commits on wait
    ckpt.save({"v": jnp.float32(2.0)}, directory)
    ckpt.wait()
    assert float(np.asarray(load_state_sharded(directory)["v"])) == 2.0
    ckpt.close()


# ---------------------------------------------------------------------------
# Elastic resume: topology metadata + restore-time resharding
# ---------------------------------------------------------------------------
def _layout_state(layout, mesh):
    """A {'params', 'opt_state'} state placed per `layout` on `mesh`."""
    import jax
    import optax
    from flashy_tpu.parallel.data_parallel import fsdp_sharding
    from flashy_tpu.parallel.zero import zero_sharding

    params = {"w1": jnp.arange(64.0 * 8).reshape(64, 8),
              "w2": jnp.arange(64.0).reshape(8, 8) * 0.5}
    opt_state = optax.adam(1e-3).init(params)
    state = {"params": params, "opt_state": opt_state}
    if layout == "replicated":
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state)
    elif layout == "zero1":
        spec = zero_sharding(state, mesh, min_size=64)
    else:  # fsdp
        spec = {"params": fsdp_sharding(params, mesh, min_size=64),
                "opt_state": zero_sharding(opt_state, mesh, axis="fsdp",
                                           min_size=64)}
    return jax.device_put(state, spec)


def _leaf_arrays(tree):
    import jax
    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("layout", ["replicated", "zero1", "fsdp"])
def test_elastic_roundtrip_world_sizes(tmp_path, layout):
    """save@8 -> restore@{4,2,1} -> save@4 -> restore@8, topology-free
    (no placements: the target mesh + the slot's saved specs drive the
    whole reshard). Values must be exact and sharded layouts must stay
    GENUINELY sharded on every smaller mesh — never silently gathered
    to full replication."""
    pytest.importorskip("orbax.checkpoint")
    import jax
    from flashy_tpu.checkpoint import (load_state_sharded, load_topology,
                                       save_state_sharded)
    from flashy_tpu.parallel.mesh import make_mesh
    from flashy_tpu.parallel.zero import describe_state_sharding, \
        per_device_bytes

    # fsdp shards parameters over the 'fsdp' mesh axis; the other two
    # layouts live on the 'data' axis — the target meshes must carry
    # the same named axis for the logical spec to re-apply
    axis = "fsdp" if layout == "fsdp" else "data"
    mesh8 = make_mesh({axis: 8})
    state = _layout_state(layout, mesh8)
    want = _leaf_arrays(state)
    directory = tmp_path / "ck.sharded"
    save_state_sharded(state, directory)
    topology = load_topology(directory)
    assert topology["device_count"] == 8
    assert 8 in topology["mesh"]["shape"]

    expected_mode = {"replicated": "replicated", "zero1": "zero1",
                     "fsdp": "fsdp"}[layout]
    for m in (4, 2, 1):
        mesh_m = make_mesh({axis: m}, devices=jax.devices()[:m])
        restored = load_state_sharded(directory, mesh=mesh_m)
        got = _leaf_arrays(restored)
        assert all(np.array_equal(a, b) for a, b in zip(want, got))
        described = describe_state_sharding(restored)
        # the logical layout survives every mesh size (on 1 chip the
        # named axis has size 1 — degenerate but still declared)
        assert described["mode"] == expected_mode
        if m > 1:
            if layout != "replicated":
                # no silent full-replication fallback: per-chip bytes of
                # the sharded leaves stay ~1/m
                import jax as _jax
                sharded = [leaf for leaf in
                           _jax.tree_util.tree_leaves(restored)
                           if leaf.size >= 64
                           and not leaf.sharding.is_fully_replicated]
                assert sharded, "nothing stayed sharded after reshard"
                full = sum(leaf.size * leaf.dtype.itemsize
                           for leaf in sharded)
                assert per_device_bytes(sharded) / full <= 1.0 / m + 0.01

    # shrink-save, then grow back: save@4 -> restore@8
    mesh4 = make_mesh({axis: 4}, devices=jax.devices()[:4])
    shrunk = load_state_sharded(directory, mesh=mesh4)
    save_state_sharded(shrunk, directory)
    assert load_topology(directory)["device_count"] == 4
    grown = load_state_sharded(directory, mesh=mesh8)
    got = _leaf_arrays(grown)
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    if layout != "replicated":
        assert describe_state_sharding(grown)["mode"] == expected_mode


def test_reshard_fault_site_fires_only_on_topology_mismatch(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    import jax
    from flashy_tpu.checkpoint import load_state_sharded, save_state_sharded
    from flashy_tpu.parallel.mesh import make_mesh
    from flashy_tpu.resilience import chaos

    mesh8 = make_mesh({"data": 8})
    state = {"v": _layout_state("zero1", mesh8)}
    directory = tmp_path / "ck.sharded"
    save_state_sharded(state, directory)

    injector = chaos.install()
    try:
        # same topology: plain load, the reshard site must NOT tick
        load_state_sharded(directory, mesh=mesh8)
        assert injector.counts.get("ckpt.reshard", 0) == 0
        # smaller mesh: the site ticks, and a transient injected fault
        # is absorbed by the retry around the shard read
        injector.fail_at("ckpt.reshard", call=1)
        mesh4 = make_mesh({"data": 4}, devices=jax.devices()[:4])
        restored = load_state_sharded(directory, mesh=mesh4)
        assert injector.hits("ckpt.reshard", kind="fail") == 1
        assert injector.counts["ckpt.reshard"] == 2  # failed + retried
        assert all(np.array_equal(a, b) for a, b in zip(
            _leaf_arrays(state), _leaf_arrays(restored)))
    finally:
        chaos.uninstall()


def test_reshard_error_names_saved_and_target_mesh(tmp_path):
    """A failed restore onto a different topology must name BOTH the
    saved and the target mesh in the CheckpointError — not leak a raw
    Orbax error with neither topology in the message."""
    pytest.importorskip("orbax.checkpoint")
    import shutil
    import jax
    from flashy_tpu.checkpoint import (_read_slot_pointer,
                                       load_state_sharded,
                                       save_state_sharded)
    from flashy_tpu.parallel.mesh import make_mesh
    from flashy_tpu.resilience.integrity import CheckpointError

    mesh8 = make_mesh({"data": 8})
    state = {"v": _layout_state("zero1", mesh8)}
    directory = tmp_path / "ck.sharded"
    save_state_sharded(state, directory)
    slot = _read_slot_pointer(directory)
    shutil.rmtree(directory / slot / "arrays")
    # the manifest now fails verification (missing payload files); make
    # the error come from the ARRAY restore, not slot selection
    from flashy_tpu.resilience.integrity import write_manifest
    write_manifest(directory / slot)

    mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
    with pytest.raises(CheckpointError) as err:
        load_state_sharded(directory, mesh=mesh2)
    message = str(err.value)
    assert "8 device(s)" in message      # saved topology
    assert "2 device(s)" in message      # restore target
    assert "mesh(data=8)" in message


def test_reshard_undivisible_dim_falls_back_replicated(tmp_path):
    """A dim no longer divisible by the target axis restores replicated
    for that leaf (with a WARN) instead of failing the whole restore."""
    pytest.importorskip("orbax.checkpoint")
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from flashy_tpu.checkpoint import load_state_sharded, save_state_sharded
    from flashy_tpu.parallel.mesh import make_mesh

    mesh8 = make_mesh({"data": 8})
    # dim 8 shards on 8 chips but NOT on the 3-chip target
    state = {"opt_w": jax.device_put(jnp.arange(8.0 * 4).reshape(8, 4),
                                     NamedSharding(mesh8, P("data")))}
    directory = tmp_path / "ck.sharded"
    save_state_sharded(state, directory)
    mesh3 = make_mesh({"data": 3}, devices=jax.devices()[:3])
    restored = load_state_sharded(directory, mesh=mesh3)
    leaf = restored["opt_w"]
    assert leaf.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(leaf),
                                  np.arange(32.0).reshape(8, 4))


def test_reshard_detects_same_count_mesh_change(tmp_path):
    """Fleet churn is not only a device-count change: re-axing the same
    8 chips (data=8 -> data=4 x fsdp=2) must also count as a reshard —
    loud WARN + the ckpt.reshard fault site — per the documented
    'mesh shape / device count' contract."""
    pytest.importorskip("orbax.checkpoint")
    import jax
    from flashy_tpu.checkpoint import load_state_sharded, save_state_sharded
    from flashy_tpu.parallel.mesh import make_mesh
    from flashy_tpu.resilience import chaos

    mesh_flat = make_mesh({"data": 8})
    state = {"v": _layout_state("zero1", mesh_flat)}
    directory = tmp_path / "ck.sharded"
    save_state_sharded(state, directory)

    injector = chaos.install()
    try:
        mesh_folded = make_mesh({"data": 4, "fsdp": 2})
        restored = load_state_sharded(directory, mesh=mesh_folded)
        assert injector.counts.get("ckpt.reshard", 0) == 1
        assert all(np.array_equal(a, b) for a, b in zip(
            _leaf_arrays(state), _leaf_arrays(restored)))
    finally:
        chaos.uninstall()


def test_mesh_kwarg_without_topology_warns(tmp_path, caplog):
    """mesh= against a pre-elastic checkpoint (no topology record) must
    say it cannot place anything, not silently return host arrays."""
    pytest.importorskip("orbax.checkpoint")
    import logging as _logging
    from flashy_tpu.checkpoint import (TOPOLOGY_NAME, _read_slot_pointer,
                                       load_state_sharded,
                                       save_state_sharded)
    from flashy_tpu.parallel.mesh import make_mesh
    from flashy_tpu.resilience.integrity import write_manifest

    directory = tmp_path / "ck.sharded"
    save_state_sharded({"v": jnp.arange(8.0)}, directory)
    slot = _read_slot_pointer(directory)
    (directory / slot / TOPOLOGY_NAME).unlink()   # simulate pre-elastic
    write_manifest(directory / slot)
    with caplog.at_level(_logging.WARNING):
        restored = load_state_sharded(
            directory, mesh=make_mesh({"data": 4},
                                      devices=__import__("jax").devices()[:4]))
    assert "no topology record" in caplog.text
    np.testing.assert_array_equal(np.asarray(restored["v"]), np.arange(8.0))
