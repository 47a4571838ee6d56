# Fused ring attention (parallel/ring_fused): the single-kernel
# forward with in-kernel RDMA K/V rotation, exercised on the virtual
# CPU mesh through the pallas TPU interpret machinery (which simulates
# the inter-device copies and semaphores). Oracle: dense attention over
# the gathered sequence — the same exactness bar as the scan ring
# (test_parallel.py).
#
# NOTE: meshes here use at most 4 of the 8 virtual devices. In
# interpret mode every simulated device's semaphore waits occupy a
# slot of XLA's host thread pool; a ring spanning every host device
# starves the pool and deadlocks (documented in ring_self_attention).
# Real-TPU Mosaic execution has no such shared pool.
"""Tests for the fused (single-kernel RDMA) ring attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashy_tpu.parallel import make_mesh, ring_self_attention


def _dense_attention(q, k, v, causal):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_fused_ring_matches_dense(causal):
    mesh = make_mesh({"seq": 4, "data": 1}, devices=jax.devices()[:4])
    rng = np.random.default_rng(7)
    shape = (1, 512, 2, 64)  # t_local = 128: the kernel's minimum tile
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))

    out = ring_self_attention(q, k, v, mesh=mesh, causal=causal,
                              batch_axes=("data",), impl="fused")
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_fused_ring_two_device_bf16():
    # bf16 operands through the fused kernel; f32 softmax state keeps
    # the error at bf16 resolution.
    mesh = make_mesh({"seq": 2, "data": 2}, devices=jax.devices()[:4])
    rng = np.random.default_rng(8)
    shape = (2, 256, 2, 64)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))

    out = ring_self_attention(q, k, v, mesh=mesh, causal=True,
                              batch_axes=("data",), impl="fused")
    ref = _dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


@pytest.mark.slow
def test_fused_ring_grad_matches_dense():
    # The custom VJP routes the backward through the scan-ring rotation
    # pass; end-to-end gradients must match the dense reference.
    mesh = make_mesh({"seq": 2, "data": 1}, devices=jax.devices()[:2])
    rng = np.random.default_rng(9)
    shape = (1, 256, 1, 64)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))

    def loss(q, k, v):
        out = ring_self_attention(q, k, v, mesh=mesh, causal=True,
                                  batch_axes=("data",), impl="fused")
        return jnp.sum(out ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)


def test_all_device_interpret_mesh_falls_back_to_scan(caplog):
    # r4 regression class: an interpret-mode fused ring over EVERY host
    # device starves XLA's thread pool and hangs forever. The shard_map
    # entry point must transparently re-route to the scan ring...
    import logging
    mesh = make_mesh({"seq": 2, "data": 4}, devices=jax.devices())
    rng = np.random.default_rng(11)
    shape = (4, 256, 1, 64)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    with caplog.at_level(logging.WARNING, "flashy_tpu.parallel.ring"):
        out = ring_self_attention(q, k, v, mesh=mesh, causal=True,
                                  batch_axes=("data",), impl="fused")
    assert any("falling back" in r.message for r in caplog.records)
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_all_device_interpret_mesh_direct_call_raises():
    # ...and the direct fused entry point refuses loudly instead of
    # silently deadlocking.
    import functools
    from jax.sharding import PartitionSpec as P
    from flashy_tpu.parallel.ring_fused import fused_ring_attention

    mesh = make_mesh({"seq": 2, "data": 4}, devices=jax.devices())
    rng = np.random.default_rng(12)
    shape = (4, 256, 1, 64)
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    spec = P("data", "seq", None, None)
    mesh_axes = tuple((name, mesh.shape[name]) for name in mesh.axis_names)
    fn = functools.partial(fused_ring_attention, axis_name="seq",
                           causal=True, mesh_axes=mesh_axes)
    with pytest.raises(Exception, match="deadlock"):
        jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)(q, k, v)
