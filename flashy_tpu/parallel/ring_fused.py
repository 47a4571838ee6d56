# Fused ring attention: the whole sequence-parallel attention forward
# runs as ONE pallas kernel per device — K/V blocks travel the ring via
# in-kernel inter-chip RDMA (`pltpu.make_async_remote_copy`) while the
# MXU computes flash attention over the blocks that have already
# arrived. This removes the XLA-level scan/ppermute alternation of
# `parallel.ring` (reference has no analogue — SURVEY §5 long-context:
# absent there): the transfer of block s+1 is in flight during the
# compute of block s by construction, inside the kernel, not at the
# mercy of the XLA scheduler.
#
# Construction (a fused ring *gather*):
#   * Each device owns K/V block `my` ([BH, T_loc, D]) and an HBM slot
#     buffer [n, BH, T_loc, D]. Slot s holds the block visiting at ring
#     step s (owner (my - s) mod n).
#   * The (bh=0, q_tile=0) grid sweep drives the communication chain:
#     copy the local block into slot 0, then for each arriving slot s
#     forward it to the right neighbour's slot s+1. Every block makes
#     n-1 hops total — the ring schedule, each hop overlapped with the
#     flash compute of earlier slots.
#   * Slots are write-once (slot s is only ever written by the arrival
#     of block my-s), so there is no buffer-reuse hazard and no ack
#     protocol — the double-buffer WAR race of a 2-slot rotation design
#     cannot occur.
#   * A REGULAR per-slot semaphore fans arrival out to the other
#     (bh, q_tile) grid iterations: the comm driver signals it
#     `BH * n_q` times once the slot's data is in HBM; every consumer
#     waits one count before reading.
#   * Online softmax state (running max / normalizer / accumulator)
#     lives in VMEM scratch and persists across the innermost `step`
#     grid dimension — exactly the k-block recurrence of
#     `ops.attention._flash_kernel`, with ring steps as the k loop.
#
# Causality is a *traced* predicate (step <= my_index via
# `jax.lax.axis_index`), so one compiled kernel serves every device of
# the SPMD program; the diagonal block (step 0) applies the in-block
# triangular mask.
#
# HBM cost is O(T_global) per device (the gather buffer) — the fused
# kernel trades the XLA ring's O(T_local) footprint for single-kernel
# overlap, which is the right trade until T_global stops fitting HBM;
# `parallel.ring` remains the unbounded-length path. Memory for
# attention STATE stays O(T_local) (never a TxT score tile).
#
# The backward reuses `parallel.ring`'s rotation pass (pallas block
# kernels + overlapped ppermute) through a custom VJP: the fused
# forward emits the same (out, lse) contract the ring backward
# consumes.
"""Single-kernel ring attention: RDMA K/V rotation fused with flash."""
import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import attention as _attn
from . import ring as _ring

NEG_INF = -1e30
LANES = 128
# Reserved collective id for the fused-ring kernel's cross-device
# barrier semaphore. Any OTHER concurrently-live pallas collective in
# the same program must use a different id (Mosaic keys the shared
# barrier semaphore off this value).
FUSED_RING_COLLECTIVE_ID = 7
# Admission budget for the kernel's resident VMEM tiles. TPU cores have
# ~16 MiB of VMEM; leave headroom for Mosaic's own spills and the
# pipeline's double buffering of the Q/out blocks.
VMEM_BUDGET = 12 * 1024 * 1024


def _fused_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  kg_ref, vg_ref,
                  k_tile, v_tile, m_scr, l_scr, acc_scr,
                  copy_sem, send_sem, recv_sem, ready_sem,
                  *, axis_name: str, mesh_axes: tp.Tuple[tp.Tuple[str, int],
                                                         ...],
                  causal: bool, block_q: int,
                  n_steps: int, bh: int, n_q: int, t_loc: int):
    """One (bh, q_tile, step) grid iteration of the fused ring forward."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)
    my = jax.lax.axis_index(axis_name)
    n_consumers = bh * n_q

    # RDMA device ids are FLAT logical indices over the whole mesh, not
    # per-axis coordinates: compute this device's flat id from every
    # bound mesh axis, then offset only the ring-axis coordinate. With a
    # per-axis index here, two rings on a multi-axis mesh (e.g. data=2,
    # seq=2) would cross-target each other's devices and deadlock.
    flat = jnp.int32(0)
    stride = 1
    seq_stride = 1
    for name, size in reversed(mesh_axes):
        flat = flat + jax.lax.axis_index(name) * stride
        if name == axis_name:
            seq_stride = stride
        stride *= size

    def _ring_peer(offset: int):
        peer = jax.lax.rem(my + offset, n_steps)
        return flat + (peer - my) * seq_stride

    # ---- communication driver: the (0, 0, s) sweep moves the ring ----
    @pl.when(jnp.logical_and(b == 0, qi == 0))
    def _drive_comm():
        right = _ring_peer(1)

        @pl.when(s == 0)
        def _first():
            if n_steps > 1:
                # Neighbour barrier: nobody RDMAs into a device that has
                # not entered the kernel (and allocated its slots) yet.
                left = _ring_peer(n_steps - 1)
                barrier = pltpu.get_barrier_semaphore()
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_signal(
                    barrier, inc=1, device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                pltpu.semaphore_wait(barrier, 2)
            # Own block -> slot 0 (HBM -> HBM local copy).
            ck = pltpu.make_async_copy(k_ref, kg_ref.at[0], copy_sem.at[0])
            cv = pltpu.make_async_copy(v_ref, vg_ref.at[0], copy_sem.at[1])
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()
            pltpu.semaphore_signal(ready_sem.at[0], inc=n_consumers)

        @pl.when(s > 0)
        def _arrivals():
            # Block for step s arrives from the left into slot s.
            pltpu.make_async_copy(
                kg_ref.at[s], kg_ref.at[s], recv_sem.at[s]).wait()
            pltpu.make_async_copy(
                vg_ref.at[s], vg_ref.at[s], recv_sem.at[s]).wait()
            pltpu.semaphore_signal(ready_sem.at[s], inc=n_consumers)

        # Forward slot s onward (slot s -> right neighbour's slot s+1);
        # write-once slots make this hazard-free.
        @pl.when(s + 1 < n_steps)
        def _forward():
            rk = pltpu.make_async_remote_copy(
                src_ref=kg_ref.at[s], dst_ref=kg_ref.at[s + 1],
                send_sem=send_sem.at[2 * s], recv_sem=recv_sem.at[s + 1],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rv = pltpu.make_async_remote_copy(
                src_ref=vg_ref.at[s], dst_ref=vg_ref.at[s + 1],
                send_sem=send_sem.at[2 * s + 1], recv_sem=recv_sem.at[s + 1],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rk.start()
            rv.start()

    # ---- every iteration: wait slot readiness (unconditional, keeps
    # the ready_sem counts balanced), fetch + accumulate only when the
    # block is causally visible ----
    pltpu.semaphore_wait(ready_sem.at[s], 1)

    @pl.when(s == 0)
    def _init_state():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # ---- flash accumulate (online softmax across ring steps) ----
    def _accumulate():
        # The HBM->VMEM tile fetch lives inside the visibility guard:
        # causally-skipped steps must not burn fetch bandwidth.
        fk = pltpu.make_async_copy(kg_ref.at[s, b], k_tile, copy_sem.at[2])
        fv = pltpu.make_async_copy(vg_ref.at[s, b], v_tile, copy_sem.at[3])
        fk.start()
        fv.start()
        fk.wait()
        fv.wait()
        scale = 1.0 / np.sqrt(q_ref.shape[-1])
        scores = jax.lax.dot_general(
            q_ref[0], k_tile[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            # Diagonal block (step 0): in-block triangular mask. Earlier
            # blocks (s <= my, s > 0) are fully visible. The traced
            # where() is cheap relative to the matmuls.
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, t_loc), 0)
            k_pos = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, t_loc), 1)
            scores = jnp.where(
                jnp.logical_or(s > 0, q_pos >= k_pos), scores, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.where(m_new > NEG_INF * 0.5,
                          jnp.exp(scores - m_new), 0.0)
        l_new = l_scr[:, :1] * alpha + probs.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            probs.astype(v_tile.dtype), v_tile[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Blocks from the future (step > my ring position) contribute
        # nothing; skip their MXU work. Traced predicate: one compiled
        # kernel serves every device of the SPMD program.
        pl.when(s <= my)(_accumulate)
    else:
        _accumulate()

    @pl.when(s == n_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
        # Drain the send semaphores before the last iteration retires
        # (every RDMA issued by this device must be complete).
        @pl.when(jnp.logical_and(b == bh - 1, qi == n_q - 1))
        def _drain():
            # Even semaphores carry K transfers, odd ones V — the dummy
            # descriptor must match each transfer's byte count (K and V
            # slot dtypes may differ).
            for i in range(max(0, 2 * (n_steps - 1))):
                ref = kg_ref if i % 2 == 0 else vg_ref
                pltpu.make_async_copy(
                    ref.at[0], ref.at[0], send_sem.at[i]).wait()


def _fused_forward(q, k, v, axis_name: str, mesh_axes, causal: bool,
                   interpret: bool):
    """Returns (out [B,T_loc,H,D], lse [B,H,T_loc]) — local blocks."""
    batch, t_loc, heads, dim = q.shape
    n_steps = jax.lax.psum(1, axis_name)
    bh = batch * heads
    qf, kf, vf = (_attn._fold(x) for x in (q, k, v))

    block_q, _ = _vmem_plan(t_loc, dim, q.dtype.itemsize, k.dtype.itemsize,
                            v.dtype.itemsize)
    n_q = t_loc // block_q

    kernel = functools.partial(
        _fused_kernel, axis_name=axis_name, mesh_axes=mesh_axes,
        causal=causal,
        block_q=block_q, n_steps=n_steps, bh=bh, n_q=n_q, t_loc=t_loc)
    vma = jax.typeof(q).vma
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, dim), lambda b, qi, s: (b, qi, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # local K (RDMA source)
            pl.BlockSpec(memory_space=pl.ANY),   # local V
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dim), lambda b, qi, s: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, s: (b, qi, 0)),
            # The ring-gather slot buffers live in HBM as (discarded)
            # outputs: pallas scratch cannot be ANY-space under the
            # interpret machinery, and an output expresses the same
            # whole-kernel-lifetime HBM allocation.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_loc, dim), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((bh, t_loc, LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n_steps, bh, t_loc, dim), k.dtype,
                                 vma=vma),
            jax.ShapeDtypeStruct((n_steps, bh, t_loc, dim), v.dtype,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((t_loc, dim), k.dtype),              # K tile
            pltpu.VMEM((t_loc, dim), v.dtype),              # V tile
            pltpu.VMEM((block_q, LANES), jnp.float32),      # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),      # normalizer
            pltpu.VMEM((block_q, dim), jnp.float32),        # accumulator
            pltpu.SemaphoreType.DMA((4,)),                  # copy sems
            pltpu.SemaphoreType.DMA((max(1, 2 * (n_steps - 1)),)),  # send
            pltpu.SemaphoreType.DMA((max(1, n_steps),)),    # recv
            pltpu.SemaphoreType.REGULAR((max(1, n_steps),)),  # ready
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=FUSED_RING_COLLECTIVE_ID),
        # 'eager' DMA execution: the senders here intentionally defer
        # their send-semaphore waits to the end of the kernel, which the
        # default 'on_wait' interpret scheduling would deadlock on (the
        # transfer would never run for the blocked receiver).
        interpret=(pltpu.InterpretParams(dma_execution_mode="eager")
                   if interpret else False),
        name="ring_flash_fwd",
    )(qf, kf, vf)[:2]
    lse_rows = lse[:, :, 0].reshape(batch, heads, t_loc)
    return _attn._unfold(out, batch, heads), lse_rows


def _vmem_plan(t_loc: int, dim: int, q_itemsize: int = 4,
               k_itemsize: int = 4, v_itemsize: int = 4
               ) -> tp.Tuple[int, int]:
    """Pick block_q and account the kernel's resident VMEM.

    Sums every tile live at once inside one grid iteration — K tile, V
    tile, f32 score tile [block_q, t_loc], running max + normalizer,
    f32 accumulator, and the pipelined Q / out / lse blocks — and
    shrinks block_q until the total fits `VMEM_BUDGET`. Returns
    (block_q, total_bytes_at_that_block_q)."""
    def total(bq: int) -> int:
        k_tile = t_loc * dim * k_itemsize
        v_tile = t_loc * dim * v_itemsize
        score = bq * t_loc * 4            # f32 scores + probs
        state = 2 * bq * LANES * 4        # running max + normalizer
        acc = bq * dim * 4                # f32 accumulator
        q_blk = bq * dim * q_itemsize
        o_blk = bq * dim * q_itemsize + bq * LANES * 4   # out + lse
        return k_tile + v_tile + score + state + acc + q_blk + o_blk

    block_q = _attn._dividing_block(t_loc) or t_loc
    while block_q > 128 and total(block_q) > VMEM_BUDGET:
        block_q //= 2
    return block_q, total(block_q)


def _supported(t_loc: int, dim: int, q_itemsize: int = 4,
               k_itemsize: int = 4, v_itemsize: int = 4) -> bool:
    """Shapes the fused kernel handles: 128-aligned T_loc whose full
    resident tile set (K+V tiles, score tile, softmax state,
    accumulator, Q/out blocks) fits the VMEM budget at the smallest
    block_q."""
    if t_loc % 128:
        return False
    _, total = _vmem_plan(t_loc, dim, q_itemsize, k_itemsize, v_itemsize)
    return total <= VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str = "seq",
                         causal: bool = False,
                         mesh_axes: tp.Optional[tp.Tuple[tp.Tuple[str, int],
                                                         ...]] = None
                         ) -> jax.Array:
    """Single-kernel ring attention over blocks sharded on `axis_name`.

    Same contract as `ring.ring_attention` (call inside shard_map with
    local [B, T_loc, H, D] blocks; exact global attention comes back),
    but the forward is one pallas kernel per device with in-kernel RDMA
    rotation. The backward runs `ring`'s overlapped rotation pass.
    """
    out, _ = _fused_fwd_impl(q, k, v, axis_name, causal, mesh_axes)
    return out


def _fused_fwd_impl(q, k, v, axis_name, causal, mesh_axes):
    t_loc, dim = q.shape[1], q.shape[3]
    if not _supported(t_loc, dim, q.dtype.itemsize, k.dtype.itemsize,
                      v.dtype.itemsize):
        raise ValueError(
            f"fused ring attention needs a 128-aligned local "
            f"sequence block whose resident tiles (K+V+scores+state) fit "
            f"the {VMEM_BUDGET >> 20} MiB VMEM budget; got "
            f"t_local={t_loc}, head_dim={dim}. "
            f"Use impl='scan' for these shapes.")
    if mesh_axes is None:
        # Single-axis ring: the flat logical id IS the ring index.
        mesh_axes = ((axis_name, int(jax.lax.psum(1, axis_name))),)
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        # The kernel is Mosaic-TPU; on GPU it would fail deep inside the
        # lowering with an opaque error. Refuse up front.
        raise NotImplementedError(
            f"fused ring attention lowers via Mosaic (TPU) or the pallas "
            f"interpret machinery (CPU); backend {backend!r} is not "
            f"supported. Use impl='scan'.")
    interpret = backend == "cpu"
    if not interpret and dim % LANES:
        # Mosaic lays the HBM ring-gather buffers out in 128-lane tiles
        # and refuses to slice them at a narrower head_dim ("Slice shape
        # along dimension 3 must be aligned to tiling (128), but is
        # 64"). Say so here instead of deep inside the compiler.
        raise ValueError(
            f"fused ring attention compiles on TPU only at head_dim a "
            f"multiple of {LANES} (the HBM slot buffers are sliced in "
            f"128-lane tiles); got head_dim={dim}. Use impl='scan'.")
    if interpret:
        # In interpret mode every simulated device's RDMA semaphore
        # waits occupy a slot of XLA's host intra-op thread pool. A mesh
        # spanning every host device starves the pool and the kernel
        # hangs forever (no Mosaic analogue — real TPUs have dedicated
        # DMA engines). Refuse instead of deadlocking; callers going
        # through `ring_self_attention` are transparently re-routed to
        # impl='scan' before reaching this point.
        mesh_size = math.prod(size for _, size in mesh_axes)
        # size-1 meshes have no cross-device RDMA to starve on
        if mesh_size > 1 and mesh_size >= len(jax.devices()):
            raise RuntimeError(
                f"fused ring attention in interpret mode (CPU backend) "
                f"over a {mesh_size}-device mesh covering every host "
                f"device ({len(jax.devices())} visible) would deadlock: "
                f"the simulated RDMA semaphore waits starve XLA's host "
                f"thread pool. Leave at least one host device outside "
                f"the mesh, or use impl='scan'.")
    return _fused_forward(q, k, v, axis_name, mesh_axes, causal, interpret)


def _fused_fwd(q, k, v, axis_name, causal, mesh_axes):
    out, lse = _fused_fwd_impl(q, k, v, axis_name, causal, mesh_axes)
    return out, (q, k, v, out, lse)


def _fused_bwd(axis_name, causal, mesh_axes, residuals, do):
    q, k, v, out, lse = residuals
    return _ring._ring_backward_pass(q, k, v, out, lse, do, axis_name, causal)


fused_ring_attention.defvjp(_fused_fwd, _fused_bwd)
