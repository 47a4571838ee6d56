# Ring attention: exact attention over sequences sharded across the
# mesh's 'seq' axis. Long-context support the reference does not have
# (SURVEY §5: absent there), built TPU-first: each device holds one
# sequence block of Q/K/V; K/V blocks rotate around the ring via
# `lax.ppermute` over ICI while each device accumulates its Q block's
# attention; the full TxT score matrix never materializes and memory
# stays O(T_local).
#
# The per-block compute is the pallas flash kernel (ops/attention) when
# the shapes allow: each visiting block produces a normalized output
# plus its logsumexp, and blocks merge with the standard
# logaddexp-weighted combination — so the MXU-tiled online softmax runs
# inside every ring step while the next K/V block is in flight on ICI.
# Gradients are a custom VJP that rotates K/V again, reusing the pallas
# backward kernels per block with the forward's GLOBAL logsumexp; dK/dV
# accumulators travel around the ring with their blocks and arrive home
# after the final hop. Both directions fall back to a pure-XLA block
# computation off TPU-friendly shapes.
#
# Communication pattern follows the ring-attention construction of Liu &
# Abbeel (blockwise parallel transformers); one K/V block is always in
# flight, overlapping the ppermute with the block computation.
"""Sequence-parallel exact attention via K/V ring rotation."""
import functools
import typing as tp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import attention as _attn

NEG_INF = -1e30


def _use_pallas(t_q: int, t_k: int, block: int = 128) -> bool:
    """Pallas path needs 128-aligned block dims (and is TPU-targeted)."""
    return (t_q % block == 0 and t_k % block == 0
            and jax.default_backend() not in ("gpu", "cuda", "rocm"))


def _block_sizes(t_q: int, t_k: int) -> tp.Tuple[int, int]:
    """Largest kernel tile that DIVIDES each length (the kernels' grid
    floor-divides, so a non-dividing tile would silently drop rows —
    t_local=384 with a 256 tile covers only rows 0-255).

    Candidates are every multiple of the 128-lane width up to 512 (the
    VMEM comfort zone for the [block_q, block_k] f32 score tile —
    `ops.attention._dividing_block`, the one candidate list shared with
    `flash_attention`'s auto-pick), so any 128-aligned t_local gets a
    pallas tile — e.g. 384 runs at 384 instead of falling back to plain
    XLA as the {512,256,128} set did; the worst 128-aligned case (640,
    1664, ...) still runs at 128."""

    def pick(t: int) -> int:
        # 0 = not 128-aligned: t < 128 only reachable in interpret mode
        return _attn._dividing_block(t) or t

    return pick(t_q), pick(t_k)


def _block_forward(q, k, v, *, causal_diag: bool):
    """One ring block: returns (out [B,T,H,D] f32 normalized, lse [B,H,T]).

    `causal_diag=True` applies the self-block causal mask (offset 0);
    False means the block is fully visible.
    """
    batch, t_q, heads, head_dim = q.shape
    t_k = k.shape[1]
    if _use_pallas(t_q, t_k):
        block_q, block_k = _block_sizes(t_q, t_k)
        out, lse = _attn._flash_forward(
            q, k, v, causal=causal_diag, block_q=block_q, block_k=block_k,
            interpret=jax.default_backend() == "cpu")
        lse_rows = lse[:, :, 0].reshape(batch, heads, t_q)
        return out.astype(jnp.float32), lse_rows
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal_diag:
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = scores.max(axis=-1)                          # [B, H, Tq]
    probs = _attn._guarded_probs(scores, m[..., None])
    denom = jnp.maximum(probs.sum(axis=-1), 1e-30)
    # P in the operand dtype + f32 accumulation (the scheme the pallas
    # kernels use); the block output stays f32 for the logaddexp merge.
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     (probs / denom[..., None]).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, m + jnp.log(denom)


def _block_backward(q, k, v, out_global, do, lse_rows, delta_rows, *,
                    causal_diag: bool):
    """Per-block gradients from the GLOBAL logsumexp: (dq, dk, dv).

    probs = exp(scores - lse_global) are the exact global attention
    weights for this block, so each block's contribution is independent
    and sums to the full gradient — the decomposition the pallas
    backward kernels implement.
    """
    batch, t_q, heads, head_dim = q.shape
    t_k = k.shape[1]
    if _use_pallas(t_q, t_k):
        block_q, block_k = _block_sizes(t_q, t_k)
        # kernels read lse/delta broadcast over the 128-lane dim, [BH, T]
        lse = jnp.broadcast_to(
            lse_rows.reshape(batch * heads, t_q)[:, :, None],
            (batch * heads, t_q, _attn.LANES))
        delta = jnp.broadcast_to(
            delta_rows.reshape(batch * heads, t_q)[:, :, None],
            (batch * heads, t_q, _attn.LANES))
        return _attn._flash_backward(
            q, k, v, out_global, lse, do, causal=causal_diag,
            block_q=block_q, block_k=block_k,
            interpret=jax.default_backend() == "cpu", delta=delta)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal_diag:
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    # Empty-row guard (mirrors the pallas backward kernels): rows whose
    # forward lse hit the clamp floor have no visible key and must get
    # zero probs/gradients.
    probs = _attn._guarded_probs(scores, lse_rows[..., None])  # [B,H,Tq,Tk]
    # P/dS in the operand dtype + f32 accumulation, as in the kernels.
    dv = jnp.einsum("bhqk,bqhd->bkhd", probs.astype(do.dtype), do,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v,
                    preferred_element_type=jnp.float32)
    ds = probs * (dp - delta_rows[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds.astype(q.dtype), q,
                    preferred_element_type=jnp.float32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _merge(out_acc, lse_acc, out_blk, lse_blk):
    """logaddexp merge of two normalized partial attentions."""
    new_lse = jnp.logaddexp(lse_acc, lse_blk)        # [B, H, T]
    w_acc = jnp.exp(lse_acc - new_lse).transpose(0, 2, 1)[..., None]
    w_blk = jnp.exp(lse_blk - new_lse).transpose(0, 2, 1)[..., None]
    return out_acc * w_acc + out_blk * w_blk, new_lse


def _mark_varying(tree, like):
    """Make every leaf device-varying on the axes `like` varies over —
    scan carries need stable varying types, and block outputs computed
    purely from replicated inputs would otherwise come back invariant."""
    target = jax.typeof(like).vma
    if not target:
        return tree

    def mark(x):
        missing = tuple(target - jax.typeof(x).vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree_util.tree_map(mark, tree)


def _ring_forward_pass(q, k, v, axis_name: str, causal: bool):
    """Returns (out [B,T,H,D] in q.dtype, lse [B,H,T])."""
    n_blocks = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    # Step 0: the device's own (diagonal) block. The first rotation is
    # issued BEFORE the block compute: the two are dataflow-independent
    # (both only read the resident k/v), so XLA's latency-hiding
    # scheduler can run the ppermute on ICI while the MXU works — the
    # one-block-always-in-flight schedule of the ring construction.
    if n_blocks > 1:
        k_blk = jax.lax.ppermute(k, axis_name, perm)
        v_blk = jax.lax.ppermute(v, axis_name, perm)
    out, lse = _block_forward(q, k, v, causal_diag=causal)
    out, lse = _mark_varying((out, lse), q)

    if n_blocks > 1:
        def step(carry, step_index):
            # carry holds the block that already ARRIVED for this step
            # (owner (my_index - s) mod n); the rotation for the NEXT
            # step is issued here, independent of this step's compute,
            # so the hop overlaps the block computation below. The last
            # iteration's rotation is one wasted hop (it returns each
            # block to its owner) — the price of the static schedule.
            out_acc, lse_acc, k_blk, v_blk = carry
            k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
            if causal:
                # owner < my_index  <=>  my_index >= s: fully visible;
                # otherwise the block is entirely in the future — skip
                # compute AND merge (cond, so the skipped branch costs
                # nothing on-device).
                def visible(args):
                    out_acc, lse_acc, k_blk, v_blk = args
                    out_b, lse_b = _block_forward(q, k_blk, v_blk,
                                                  causal_diag=False)
                    out_acc, lse_acc = _merge(out_acc, lse_acc, out_b, lse_b)
                    return out_acc, lse_acc

                out_acc, lse_acc = jax.lax.cond(
                    my_index >= step_index, visible,
                    lambda args: (args[0], args[1]),
                    (out_acc, lse_acc, k_blk, v_blk))
            else:
                out_b, lse_b = _block_forward(q, k_blk, v_blk,
                                              causal_diag=False)
                out_acc, lse_acc = _merge(out_acc, lse_acc, out_b, lse_b)
            return (out_acc, lse_acc, k_nxt, v_nxt), None

        (out, lse, _, _), _ = jax.lax.scan(
            step, (out, lse, k_blk, v_blk), jnp.arange(1, n_blocks))
    return out.astype(q.dtype), lse


def _ring_backward_pass(q, k, v, out, lse, do, axis_name: str, causal: bool):
    n_blocks = jax.lax.psum(1, axis_name)
    my_index = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    # D = rowsum(dO * O) over the GLOBAL output: identical for every
    # block this device processes.
    delta_rows = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                         axis=-1).transpose(0, 2, 1)   # [B, H, Tq]

    # First K/V rotation issued before the own-block compute (both read
    # only the resident k/v), so the hop overlaps the MXU work — same
    # schedule as the forward.
    if n_blocks > 1:
        k_blk, v_blk = jax.lax.ppermute((k, v), axis_name, perm)
    dq, dk, dv = _block_backward(q, k, v, out, do, lse, delta_rows,
                                 causal_diag=causal)
    # Accumulate across ring steps in f32 (matching the forward merge);
    # summing per-block bf16 grads would compound rounding once per hop.
    dq, dk, dv = (g.astype(jnp.float32) for g in (dq, dk, dv))
    dq, dk, dv = _mark_varying((dq, dk, dv), q)

    if n_blocks > 1:
        def step(carry, step_index):
            # carry holds the block that already arrived for this step
            # plus the dK/dV accumulators the device filled LAST step
            # (they travel with their block, one rotation behind it).
            # Both rotations below are independent of this step's block
            # compute — dk_in/dv_in are only consumed at the final add —
            # so the ICI hops overlap the MXU work.
            dq_acc, k_blk, v_blk, dk_prev, dv_prev = carry
            k_nxt, v_nxt = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
            dk_in, dv_in = jax.lax.ppermute((dk_prev, dv_prev), axis_name,
                                            perm)

            def visible(args):
                dq_acc, dk_acc, dv_acc = args
                dq_b, dk_b, dv_b = _block_backward(
                    q, k_blk, v_blk, out, do, lse, delta_rows,
                    causal_diag=False)
                return (dq_acc + dq_b.astype(jnp.float32),
                        dk_acc + dk_b.astype(jnp.float32),
                        dv_acc + dv_b.astype(jnp.float32))

            if causal:
                dq_acc, dk_acc, dv_acc = jax.lax.cond(
                    my_index >= step_index, visible, lambda args: args,
                    (dq_acc, dk_in, dv_in))
            else:
                dq_acc, dk_acc, dv_acc = visible((dq_acc, dk_in, dv_in))
            return (dq_acc, k_nxt, v_nxt, dk_acc, dv_acc), None

        (dq, _, _, dk, dv), _ = jax.lax.scan(
            step, (dq, k_blk, v_blk, dk, dv), jnp.arange(1, n_blocks))
        # The in-scan rotations moved each accumulator n-1 hops; one more
        # returns it to the device that owns its K/V block.
        dk, dv = jax.lax.ppermute((dk, dv), axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "seq", causal: bool = False) -> jax.Array:
    """Attention over a sequence sharded on `axis_name`.

    Must be called inside a `shard_map` (or pmap) context where
    `axis_name` is bound. Arguments are the *local* blocks:

        q, k, v: [batch, t_local, heads, head_dim]

    Returns the local output block [batch, t_local, heads, head_dim] of
    exact (optionally causal) softmax attention over the *global*
    sequence. Positions are global: block b covers
    [b * t_local, (b+1) * t_local).
    """
    out, _ = _ring_forward_pass(q, k, v, axis_name, causal)
    return out


def _ring_fwd(q, k, v, axis_name, causal):
    out, lse = _ring_forward_pass(q, k, v, axis_name, causal)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, residuals, do):
    q, k, v, out, lse = residuals
    return _ring_backward_pass(q, k, v, out, lse, do, axis_name, causal)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        mesh: tp.Optional[Mesh] = None, axis: str = "seq",
                        causal: bool = False,
                        batch_axes: tp.Sequence[str] = ("data", "fsdp"),
                        check_vma: bool = False,
                        impl: str = "scan") -> jax.Array:
    """shard_map entry point: global [B, T, H, D] arrays, T sharded on `axis`.

    Shards the batch over `batch_axes` and the sequence over `axis`, runs
    `ring_attention` per device. Use inside a jitted step whose arrays
    already live on the mesh (the specs below just tell shard_map how to
    slice them).

    `impl` selects the per-device construction:
      * 'scan' (default) — lax.scan of pallas flash block kernels with
        overlapped `ppermute` K/V rotation (`ring_attention`).
      * 'fused' — the single-kernel forward of `ring_fused`: in-kernel
        RDMA rotation overlapped with the flash compute. Requires
        128-aligned local sequence blocks; NOTE: in interpret mode
        (CPU testing) the mesh must leave at least one host device
        outside the ring, or the simulated RDMA semaphore waits can
        starve XLA's intra-op thread pool.
    """
    from .mesh import default_mesh
    mesh = mesh or default_mesh()
    # Shard the batch over the largest prefix of batch_axes it divides
    # (a probe forward with a tiny batch — e.g. model.init — would
    # otherwise be rejected by shard_map). Falling short of the full
    # product means redundant compute, so make it loud.
    use_batch_axes = _attn.dividing_axes(q.shape[0], mesh, batch_axes)
    full_ways = 1
    for name in batch_axes:
        full_ways *= mesh.shape[name]
    if len(use_batch_axes) != len(tuple(batch_axes)) and q.shape[0] > 1:
        import logging
        logging.getLogger(__name__).warning(
            "ring_self_attention: batch %d not divisible by mesh axes %s "
            "(%d ways); sharding over %s only — redundant compute on the "
            "remaining axes.", q.shape[0], tuple(batch_axes), full_ways,
            use_batch_axes)
    spec = P(use_batch_axes or None, axis, None, None)
    if (impl == "fused" and jax.default_backend() == "cpu"
            and mesh.devices.size > 1
            and mesh.devices.size >= len(jax.devices())):
        # Interpret-mode deadlock guard: on the CPU backend the fused
        # kernel's simulated RDMA semaphore waits each occupy a slot of
        # XLA's host thread pool, so a mesh covering every host device
        # starves the pool and hangs forever. Fall back to the scan ring
        # (identical contract and numerics) instead of deadlocking; the
        # fused path still raises if called directly (ring_fused).
        import logging
        logging.getLogger(__name__).warning(
            "ring_self_attention: impl='fused' on the CPU backend with a "
            "%d-device mesh covering all %d host devices would deadlock "
            "in interpret mode; falling back to impl='scan'.",
            mesh.devices.size, len(jax.devices()))
        impl = "scan"
    if impl == "fused":
        from .ring_fused import fused_ring_attention
        mesh_axes = tuple((name, mesh.shape[name])
                          for name in mesh.axis_names)
        fn = functools.partial(fused_ring_attention, axis_name=axis,
                               causal=causal, mesh_axes=mesh_axes)
    elif impl == "scan":
        fn = functools.partial(ring_attention, axis_name=axis, causal=causal)
    else:
        raise ValueError(f"impl must be 'scan' or 'fused', got {impl!r}")
    # check_vma defaults to False: pallas interpret mode (the CPU test
    # path) cannot yet propagate varying-axis types through its block
    # slicing — the workaround the upstream error message prescribes.
    # The vma checker is a tracer-level lint; numerics are unaffected.
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=check_vma)(q, k, v)
