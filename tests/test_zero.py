# ZeRO-1/2 sharded weight update (parallel/zero.py) on the virtual
# 8-device CPU mesh: the per-chip optimizer-HBM claim is asserted from
# sharding inspection (per_device_bytes), the numerics against the
# replicated path (the same DDP-equivalence oracle test_parallel uses),
# the zero-recompile claim through the RecompileWatchdog that wrap's
# executable cache now reports into, and the checkpoint story through a
# solver round trip + `--verify-checkpoint` audit.
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from flashy_tpu.observability import RecompileWatchdog
from flashy_tpu.parallel import (describe_state_sharding, make_mesh,
                                 per_device_bytes, shard_batch, wrap,
                                 with_grad_accumulation, zero_sharding,
                                 zero_update)


@pytest.fixture()
def mesh_data():
    return make_mesh({"data": -1})  # all 8 devices on the data axis


def _state(w=None, optim=None, n=64, m=32):
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(
        w if w is not None else rng.normal(size=(n, m)).astype(np.float32))}
    optim = optim or optax.adamw(1e-2)
    return {"params": params, "opt_state": optim.init(params)}, optim


def _batch(n=64, m=32, b=16, seed=1):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(b, n)).astype(np.float32),
            "y": rng.normal(size=(b, m)).astype(np.float32)}


def _loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _make_step(optim):
    def step(state, batch):
        loss, grads = jax.value_and_grad(_loss_fn)(state["params"], batch)
        updates, opt_state = optim.update(grads, state["opt_state"],
                                          state["params"])
        return ({"params": optax.apply_updates(state["params"], updates),
                 "opt_state": opt_state}, {"loss": loss})

    return step


def test_zero_sharding_shards_opt_state_only(mesh_data):
    state, _ = _state()
    shardings = zero_sharding(state, mesh_data, min_size=1)
    # compute params replicated...
    for leaf in jax.tree_util.tree_leaves(shardings["params"]):
        assert leaf.spec == P()
    # ...optimizer moments sharded over the data axis
    mu = None
    for leaf in jax.tree_util.tree_leaves(shardings["opt_state"]):
        if leaf.spec != P():
            assert "data" in str(leaf.spec)
            mu = leaf
    assert mu is not None, "no opt-state leaf was sharded"
    # min_size: tiny leaves stay replicated
    coarse = zero_sharding(state, mesh_data, min_size=10 ** 9)
    for leaf in jax.tree_util.tree_leaves(coarse["opt_state"]):
        assert leaf.spec == P()


def test_zero_sharding_explicit_keys_and_bare_tree(mesh_data):
    state, optim = _state()
    state["master_params"] = state["params"]
    shardings = zero_sharding(state, mesh_data, min_size=1)
    # ZeRO-2 style: master params shard too (key marker 'master')
    assert any(leaf.spec != P() for leaf in
               jax.tree_util.tree_leaves(shardings["master_params"]))
    # explicit shard_keys override the marker heuristic
    only_params = zero_sharding(state, mesh_data, min_size=1,
                                shard_keys=("params",))
    assert all(leaf.spec == P() for leaf in
               jax.tree_util.tree_leaves(only_params["opt_state"]))
    assert any(leaf.spec != P() for leaf in
               jax.tree_util.tree_leaves(only_params["params"]))
    # a bare (non-mapping) tree is treated wholly as optimizer state
    bare = zero_sharding(state["opt_state"], mesh_data, min_size=1)
    assert any(leaf.spec != P()
               for leaf in jax.tree_util.tree_leaves(bare))


def test_zero1_matches_replicated_and_shrinks_opt_state(mesh_data):
    # The acceptance oracle: over a 3-step run, ZeRO-1 must stay
    # numerically equivalent to the replicated path, shrink per-chip
    # optimizer bytes ~1/N, and report ZERO post-warm-up recompiles
    # through the watchdog.
    n_dev = mesh_data.shape["data"]
    optim = optax.adamw(1e-2)
    step = _make_step(optim)
    watchdog = RecompileWatchdog(warmup=1)
    batch = shard_batch(_batch(), mesh_data, batch_axes=("data",))

    from jax.sharding import NamedSharding

    # start each run ON its steady-state placement: a host-placed state
    # would legitimately retrace once when the committed sharded outputs
    # come back as step-2 inputs
    state_r, _ = _state(optim=optim)
    state_r = jax.device_put(state_r, jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh_data, P()), state_r))
    wrapped_r = wrap(step, mesh=mesh_data, batch_axes=("data",),
                     watchdog=watchdog)
    state_z, _ = _state(optim=optim)
    zero_spec = zero_sharding(state_z, mesh_data, min_size=1)
    state_z = jax.device_put(state_z, zero_spec)
    wrapped_z = wrap(step, mesh=mesh_data, batch_axes=("data",),
                     state_sharding=zero_spec,
                     watchdog=watchdog)
    for _ in range(3):
        state_r, aux_r = wrapped_r(state_r, batch)
        state_z, aux_z = wrapped_z(state_z, batch)

    np.testing.assert_allclose(np.asarray(state_z["params"]["w"]),
                               np.asarray(state_r["params"]["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_z["loss"]), float(aux_r["loss"]),
                               rtol=1e-5)

    # per-chip optimizer bytes: moments shard 1/N; adam's scalar count
    # (and nothing else here) stays replicated
    bytes_r = per_device_bytes(state_r["opt_state"])
    bytes_z = per_device_bytes(state_z["opt_state"])
    assert bytes_z <= bytes_r / n_dev + 64, (bytes_z, bytes_r)
    # fresh params still replicated (full size on every chip)
    assert per_device_bytes(state_z["params"]) == \
        per_device_bytes(state_r["params"])

    # sharding inspection, not just byte math
    mu = state_z["opt_state"][0].mu["w"]
    assert mu.sharding.shard_shape(mu.shape)[0] == mu.shape[0] // n_dev

    # one compile per wrapped step, nothing past warm-up
    assert watchdog.summary() == {}
    assert wrapped_r.compile_stats() == {"calls": 3, "compiles": 1,
                                         "recompiles": 0}
    assert wrapped_z.compile_stats()["recompiles"] == 0


def test_zero_update_explicit_path_with_grad_accumulation(mesh_data):
    # The explicit split step (reduce-scatter -> shard-local update ->
    # all-gather), with microbatch accumulation composed IN FRONT so the
    # collectives run once per step on the accumulated gradient.
    optim = optax.adamw(1e-2)
    grad_fn = with_grad_accumulation(jax.value_and_grad(_loss_fn), 4)
    step = zero_update(grad_fn, optim, mesh=mesh_data, min_size=1)
    state, _ = _state(optim=optim)
    shardings = zero_sharding(state, mesh_data, min_size=1)
    wrapped = wrap(step, mesh=mesh_data, batch_axes=("data",),
                   state_sharding=shardings, donate_state=False)
    batch_host = _batch()
    batch = shard_batch(batch_host, mesh_data, batch_axes=("data",))
    for _ in range(2):
        state, aux = wrapped(state, batch)

    # replicated single-device reference (no accumulation: the wrapper
    # is exact for a mean loss)
    ref, _ = _state(optim=optim)
    ref_step = jax.jit(_make_step(optim))
    host = {k: jnp.asarray(v) for k, v in batch_host.items()}
    for _ in range(2):
        ref, ref_aux = ref_step(ref, host)
    np.testing.assert_allclose(np.asarray(state["params"]["w"]),
                               np.asarray(ref["params"]["w"]),
                               rtol=1e-5, atol=1e-6)
    # moments really live sharded
    mu = state["opt_state"][0].mu["w"]
    assert mu.sharding.spec != P()


def test_zero_update_compiles_expected_collectives(mesh_data):
    # HLO evidence: the explicit path must communicate — gradients
    # reduced (all-reduce or reduce-scatter; the CPU lowering may pick
    # either) and the fresh params re-gathered (all-gather).
    from jax.sharding import NamedSharding
    from flashy_tpu.parallel import collective_stats

    optim = optax.sgd(1e-2)
    step = zero_update(jax.value_and_grad(_loss_fn), optim,
                       mesh=mesh_data, min_size=1)
    state, _ = _state(optim=optim)
    shardings = zero_sharding(state, mesh_data, min_size=1)
    batch = shard_batch(_batch(), mesh_data, batch_axes=("data",))
    batch_sharding = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh_data, P(("data",))), batch)
    compiled = jax.jit(step, in_shardings=(shardings, batch_sharding),
                       out_shardings=(shardings, None)) \
        .lower(state, batch).compile()
    stats = collective_stats(compiled)
    reduced = (stats["all-reduce"]["bytes"]
               + stats["reduce-scatter"]["bytes"])
    assert reduced > 0, stats
    assert stats["all-gather"]["count"] > 0, stats
    # the all-gather moves (at least) the sharded update's bytes back
    # to every replica
    assert stats["all-gather"]["bytes"] >= 64 * 32 * 4 * 7 // 8, stats


def test_wrap_cache_reports_recompiles_and_is_bounded(mesh_data):
    watchdog = RecompileWatchdog(warmup=1)

    def step(state, batch):
        return state + batch.sum(), {}

    wrapped = wrap(step, mesh=mesh_data, batch_axes=("data",),
                   donate_state=False, watchdog=watchdog, max_cache=2)
    batch = shard_batch(jnp.ones((16, 2)), mesh_data, batch_axes=("data",))
    wrapped(jnp.zeros(()), batch)
    wrapped(jnp.zeros(()), batch)  # cache hit: no new compile
    assert wrapped.compile_stats() == {"calls": 2, "compiles": 1,
                                       "recompiles": 0}
    assert watchdog.summary() == {}

    # a changed BATCH shape hits the same state key but retraces the
    # inner jit — the classic silent-recompile source; the growth-based
    # accounting must catch it, not just state-key misses
    small = shard_batch(jnp.ones((8, 2)), mesh_data, batch_axes=("data",))
    wrapped(jnp.zeros(()), small)
    assert wrapped.compile_stats()["recompiles"] == 1
    assert watchdog.summary() == {wrapped.watchdog_name: 1}

    # a new state shape is a cache miss past warm-up -> tallied too
    wrapped(jnp.zeros((2,)), batch)
    assert wrapped.compile_stats()["recompiles"] == 2

    # the cache is bounded: a third shape evicts the LRU scalar entry;
    # coming BACK to the evicted shape rebuilds the map entry but jit's
    # shared tracing cache spares the XLA compile — nothing new tallied
    wrapped(jnp.zeros((3,)), batch)
    wrapped(jnp.zeros(()), batch)
    stats = wrapped.compile_stats()
    assert stats["compiles"] == 4
    assert stats["recompiles"] == 3
    assert stats["calls"] == 6


def test_wrap_watchdog_carryover_across_telemetry_toggle(mesh_data, tmp_path):
    # Enabling telemetry mid-run must MOVE the wrap's compile tally onto
    # the telemetry watchdog — a fresh entry would restart the warm-up
    # budget and swallow the next (real) recompile.
    from flashy_tpu import observability

    def step(state, batch):
        return state + batch.sum(), {}

    wrapped = wrap(step, mesh=mesh_data, batch_axes=("data",),
                   donate_state=False)
    batch = shard_batch(jnp.ones((16, 2)), mesh_data, batch_axes=("data",))
    wrapped(jnp.zeros(()), batch)  # warm-up compile in the fallback
    telemetry = observability.enable_telemetry(folder=tmp_path)
    try:
        small = shard_batch(jnp.ones((8, 2)), mesh_data,
                            batch_axes=("data",))
        wrapped(jnp.zeros(()), small)  # recompile AFTER the toggle
        assert telemetry.watchdog.summary() == {wrapped.watchdog_name: 1}
        assert wrapped.compile_stats() == {"calls": 2, "compiles": 2,
                                           "recompiles": 1}
    finally:
        observability.disable_telemetry()


def test_grad_accumulation_keeps_complex_gradients():
    # complex grads must accumulate in a complex dtype — a float32
    # accumulator would silently drop every imaginary part.
    def value_and_grad(params, batch):
        grads = jnp.mean(batch, axis=0)
        return jnp.zeros(()), {"g": grads}

    batch = (jnp.arange(8, dtype=jnp.float32)[:, None]
             * (1 + 1j)).astype(jnp.complex64) * jnp.ones((8, 4))
    params = {"g": jnp.zeros((4,), jnp.complex64)}
    loss, grads = jax.jit(with_grad_accumulation(value_and_grad, 4))(
        params, batch)
    assert grads["g"].dtype == jnp.complex64
    ref = np.asarray(jnp.mean(batch, axis=0))
    np.testing.assert_allclose(np.asarray(grads["g"]), ref, rtol=1e-6)
    assert np.abs(np.asarray(grads["g"]).imag).max() > 0


def test_per_device_bytes_and_describe(mesh_data):
    state, _ = _state()
    sharded = jax.device_put(state, zero_sharding(state, mesh_data,
                                                  min_size=1))
    desc = describe_state_sharding(sharded)
    assert desc["mode"] == "zero1"
    assert desc["summary"] == "zero1(data=8)"
    assert desc["update_axes"] == ["data"] and desc["param_axes"] == []
    # replicated state classifies as replicated
    assert describe_state_sharding(state)["mode"] == "replicated"
    # fsdp: params themselves sharded
    from flashy_tpu.parallel import fsdp_sharding
    mesh_f = make_mesh({"fsdp": -1})
    fs = jax.device_put(state, fsdp_sharding(state, mesh_f, min_size=1))
    assert describe_state_sharding(fs)["mode"] == "fsdp"
    # the discriminating key may sit BELOW the top level (a solver
    # registering one combined {'params', 'opt_state'} attribute):
    # still zero1, not fsdp — the params leg is replicated
    nested = {"state": sharded, "history": []}
    assert describe_state_sharding(nested)["mode"] == "zero1"
    # host leaves (numpy) count full size; sharded leaves count 1/N
    w = sharded["opt_state"][0].mu["w"]
    assert per_device_bytes({"mu": w}) == w.size * w.dtype.itemsize // 8
    host = np.zeros((4, 4), np.float32)
    assert per_device_bytes({"h": host}) == host.nbytes


def test_solver_zero_checkpoint_roundtrip_and_info(tmp_path, capsys):
    pytest.importorskip("orbax.checkpoint")
    from flashy_tpu import info
    from flashy_tpu.solver import BaseSolver
    from flashy_tpu.xp import temporary_xp

    mesh = make_mesh({"data": -1})
    n_dev = mesh.shape["data"]

    class ZSolver(BaseSolver):
        def __init__(self):
            super().__init__()
            self.params = {"w": jnp.asarray(
                np.arange(256.0, dtype=np.float32).reshape(32, 8))}
            self.optim = optax.adamw(1e-2)
            self.opt_state = self.optim.init(self.params)
            self.register_stateful("params", "opt_state")
            self.set_state_sharding(
                "opt_state", zero_sharding(self.opt_state, mesh, min_size=1))

        def train_stage(self):
            grads = {"w": jnp.ones((32, 8))}
            updates, self.opt_state = self.optim.update(
                grads, self.opt_state, self.params)
            self.params = optax.apply_updates(self.params, updates)
            return {"loss": 1.0}

    with temporary_xp() as xp:
        solver = ZSolver()
        solver.run_stage("train", solver.train_stage)
        solver.commit()
        # declared non-replicated shardings force the Orbax path even
        # for a tiny, fully-addressable state: never gathered to 1 host
        assert solver._resolve_checkpoint_mode(solver.state_dict()) \
            == "sharded"
        assert solver.sharded_checkpoint_path.exists()
        mu_before = np.asarray(solver.opt_state[0].mu["w"])
        w_before = np.asarray(solver.params["w"])

        xp.link.load()
        solver2 = ZSolver()
        assert solver2.restore() is True
        mu = solver2.opt_state[0].mu["w"]
        # restored DIRECTLY onto the declared ZeRO sharding
        assert mu.sharding.spec == P("data", None)
        assert mu.sharding.shard_shape(mu.shape)[0] == \
            mu.shape[0] // n_dev
        np.testing.assert_allclose(np.asarray(mu), mu_before)
        np.testing.assert_allclose(np.asarray(solver2.params["w"]), w_before)
        assert solver2.epoch == 2

        # the layout is recorded for info...
        meta = json.loads(
            (solver.folder / "checkpoint_meta.json").read_text())
        assert meta["mode"] == "sharded"
        assert meta["state_sharding"]["summary"] == f"zero1(data={n_dev})"

        # ...and `python -m flashy_tpu.info` surfaces it
        root = solver.folder.parent.parent
        assert info.main([str(root)]) == 0
        out = capsys.readouterr().out
        assert f"state-sharding=zero1(data={n_dev})" in out

        # the integrity audit passes over the ZeRO-sharded checkpoint
        assert info.verify_checkpoints(root) == 0


@pytest.mark.slow
def test_run_zero_bench_record():
    # `run_zero_bench` end-to-end on the virtual mesh: ratio ~1/N,
    # numerics tight, zero recompiles (what `make zero-demo` asserts).
    from flashy_tpu.parallel.zero import run_zero_bench

    result = run_zero_bench(steps=3, seq=32)
    n = result["n_devices"]
    assert result["recompiles"] == 0
    assert result["max_param_delta"] < 1e-4
    assert result["opt_bytes_ratio_zero1"] < 1.5 / n + 0.25
    for mode in ("replicated", "zero1", "fsdp"):
        assert result["step_ms"][mode] > 0
        assert result["opt_state_bytes_per_chip"][mode] > 0
    assert result["sharding"]["zero1"] == f"zero1(data={n})"
