# Distributed utilities over jax.distributed + XLA collectives.
#
# Role parity with reference flashy/distrib.py:21-276, re-designed for the
# JAX multi-controller model. Two distinct levels exist on TPU:
#
#  * PROCESS level (this module): one python process per TPU host. `rank`
#    / `world_size` are process indices, exactly like the reference's
#    torch.distributed ranks. Host-side helpers (metric averaging,
#    object broadcast, barriers) ride `jax.experimental.multihost_utils`,
#    which lowers to XLA collectives over ICI/DCN — the gloo/NCCL split
#    of the reference collapses to platform selection.
#
#  * DEVICE level (flashy_tpu.parallel): within a jitted step function,
#    data-parallelism is expressed by sharding the batch over a mesh axis
#    and letting XLA insert `psum`s for the gradients. `wrap()` — the
#    DistributedDataParallel replacement (reference flashy/distrib.py:65) —
#    lives there and is re-exported here.
#
# Everything in this module no-ops (or reduces to identity) when
# `world_size() == 1`, so the same solver code runs single-process —
# the property the reference's helpers all share.
"""Communication and DDP-alternative helpers for TPU training."""
import functools
from functools import wraps
import logging
import os
import typing as tp

import jax
import numpy as np

logger = logging.getLogger(__name__)

_initialized = False


def _env(*names: str, default: tp.Optional[str] = None) -> tp.Optional[str]:
    for name in names:
        if name in os.environ:
            return os.environ[name]
    return default


def init(backend: tp.Optional[str] = None) -> None:
    """Initialize multi-process JAX if the environment asks for it.

    Autodetects, in order: flashy_tpu launcher env
    (`FLASHY_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`, set by
    `--workers=N`), torch-style env (`MASTER_ADDR/MASTER_PORT/WORLD_SIZE/
    RANK`, for drop-in familiarity), then TPU pod metadata (plain
    `jax.distributed.initialize()` autodetection on Cloud TPU VMs).
    Single process → no-op, like reference `init` via dora.distrib.

    `backend` is accepted for API compatibility and ignored: on TPU the
    transport is always XLA over ICI/DCN.
    """
    global _initialized
    if _initialized or jax.distributed.is_initialized():
        # Already set up (by us or by the user calling jax.distributed
        # directly). Don't touch the backend: forcing device init here
        # would serialize every process on backend bring-up.
        _initialized = True
        return

    coordinator = _env("FLASHY_TPU_COORDINATOR")
    num = _env("FLASHY_TPU_NUM_PROCESSES")
    pid = _env("FLASHY_TPU_PROCESS_ID")
    if coordinator is None and _env("MASTER_ADDR") and _env("WORLD_SIZE"):
        coordinator = f"{_env('MASTER_ADDR')}:{_env('MASTER_PORT', default='29500')}"
        num = _env("WORLD_SIZE")
        pid = _env("RANK")

    if coordinator is not None and int(num or 1) > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num),  # type: ignore[arg-type]
            process_id=int(pid or 0))
        _initialized = True
        logger.info("jax.distributed initialized: process %d/%d, %d global devices",
                    jax.process_index(), jax.process_count(), jax.device_count())
    elif len((_env("TPU_WORKER_HOSTNAMES") or "").split(",")) > 1:
        # Multi-host TPU pod: full autodetection from the TPU metadata.
        jax.distributed.initialize()
        _initialized = True
    # else: single process, nothing to do.


def _launcher_rank_world() -> tp.Optional[tp.Tuple[int, int]]:
    """(rank, world_size) from the launcher environment, or None.

    Only trusts an env var set when the *complete* set `init()` would act
    on is present: a stale `RANK=3` left over from an unrelated torchrun
    (without MASTER_ADDR/WORLD_SIZE) must not make `is_rank_zero()` False
    on a plain single-process run — that would silently disable history
    and checkpoint writes.
    """
    if _env("FLASHY_TPU_COORDINATOR") and _env("FLASHY_TPU_NUM_PROCESSES"):
        return int(_env("FLASHY_TPU_PROCESS_ID") or 0), int(_env("FLASHY_TPU_NUM_PROCESSES"))
    if _env("MASTER_ADDR") and _env("WORLD_SIZE"):
        return int(_env("RANK") or 0), int(_env("WORLD_SIZE"))
    return None


def rank() -> int:
    """Process index, available even before `init()`.

    Reads the launcher environment first and only queries the JAX backend
    once distributed init actually happened — asking `jax.process_index()`
    cold would force backend initialization just to name a log file
    (the reference had the same concern: rank pre-init via
    dora.distrib.get_distrib_spec, flashy/logging.py:66-68).
    """
    from_env = _launcher_rank_world()
    if from_env is not None:
        return from_env[0]
    if _initialized or jax.distributed.is_initialized():
        return jax.process_index()
    return 0


def world_size() -> int:
    from_env = _launcher_rank_world()
    if from_env is not None:
        return from_env[1]
    if _initialized or jax.distributed.is_initialized():
        return jax.process_count()
    return 1


def is_rank_zero() -> bool:
    return rank() == 0


def is_distributed() -> bool:
    return world_size() > 1


def _require_backend() -> None:
    """Fail loud when a collective runs before `init()`.

    The launcher env can say world_size > 1 (so `is_distributed()` is
    True) while `jax.distributed` was never initialized — the user's
    entry point forgot `distrib.init()`. multihost_utils collectives
    then see a 1-process world and return garbage (broadcast_object
    used to die with an opaque pickle EOFError three frames later)."""
    if not (_initialized or jax.distributed.is_initialized()):
        raise RuntimeError(
            f"This run is distributed (world_size={world_size()} from the "
            "launcher environment) but flashy_tpu.distrib.init() was never "
            "called. Call distrib.init() at the start of your entry point, "
            "before any collective (see examples/cifar/train.py).")


def rank_zero_only(fn: tp.Callable) -> tp.Callable:
    """Decorator: run only on process 0 (logging, checkpoint IO, media).

    Only ever wrap *host-side IO* with this — never anything containing a
    collective, or non-zero ranks will hang waiting for rank 0
    (the deadlock class reference flashy/distrib.py:78-89 guards against).
    """

    @wraps(fn)
    def wrapped(*args: tp.Any, **kwargs: tp.Any) -> tp.Optional[tp.Any]:
        if is_rank_zero():
            return fn(*args, **kwargs)
        return None

    return wrapped


def _check_tree_sizes(tree: tp.Any) -> None:
    """Anti-deadlock guard: verify all processes bring the same pytree.

    All-gathers the (cheap) leaf count + total element count before any
    tensor collective so a structure mismatch raises a RuntimeError
    instead of hanging the pod — the `_check_number_of_params` role
    (reference flashy/distrib.py:78-89).
    """
    if not is_distributed():
        return
    _require_backend()
    from jax.experimental import multihost_utils
    leaves = jax.tree_util.tree_leaves(tree)
    signature = np.array([len(leaves), sum(int(np.size(leaf)) for leaf in leaves)],
                         dtype=np.int64)
    gathered = multihost_utils.process_allgather(signature)
    if not (gathered == signature[None, :]).all():
        raise RuntimeError(
            f"Mismatch in synced pytree across processes: ours has "
            f"{signature[0]} leaves / {signature[1]} elements, gathered {gathered.tolist()}.")


def _is_float_or_complex(leaf: tp.Any) -> bool:
    # Read the dtype attribute when present (jax.Array / np.ndarray) —
    # np.asarray on a device array would round-trip it to the host just
    # to look at its dtype.
    dtype = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
    return np.issubdtype(dtype, np.floating) or np.issubdtype(dtype, np.complexfloating)


def all_reduce(value: tp.Any, op: str = "sum") -> tp.Any:
    """Reduce an array over all processes; identity when single-process.

    Unlike the torch version (in-place on a tensor), this returns the
    reduced value — JAX arrays are immutable.
    """
    if not is_distributed():
        return value
    _require_backend()
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(np.asarray(value))
    if op == "sum":
        return gathered.sum(axis=0)
    if op == "max":
        return gathered.max(axis=0)
    if op == "min":
        return gathered.min(axis=0)
    if op == "mean":
        return gathered.mean(axis=0)
    raise ValueError(f"Unsupported reduce op: {op}")


def average_metrics(metrics: tp.Dict[str, float], count: float = 1.0) -> tp.Dict[str, float]:
    """Average a dict of metrics across processes, weighted by `count`.

    The stacked-vector weight trick of reference flashy/distrib.py:50-62:
    one collective moves `[v * count for v in values] + [count]`, and the
    weighted mean is the ratio.
    """
    if not is_distributed():
        return metrics
    keys = list(metrics.keys())
    vector = np.array([float(metrics[k]) for k in keys] + [1.0], dtype=np.float64) * count
    total = all_reduce(vector, "sum")
    return dict(zip(keys, (total[:-1] / total[-1]).tolist()))


# Above this many bytes, average_tensors switches from a process
# allgather (every host receives world_size full copies) to an in-graph
# reduction (O(N) on the wire): syncing a large model across an 8-host
# pod should not move 8x the model per step.
REDUCE_MIN_BYTES = 1 << 20


def _one_device_per_process_mesh():
    from jax.sharding import Mesh
    first: tp.Dict[int, tp.Any] = {}
    for device in jax.devices():
        first.setdefault(device.process_index, device)
    devices = [first[i] for i in sorted(first)]
    return Mesh(np.array(devices), ("proc",))


@functools.lru_cache(maxsize=None)
def _mean_over_processes_fn(mesh):
    """Jitted mean over the process dim, cached per mesh — a fresh
    jit(lambda) per call would recompile a model-sized reduction on
    every sync step (jit caches on function identity)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.jit(lambda a: a.mean(axis=0),
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def _reduce_mean_across_processes(floats: tp.List[np.ndarray]) -> tp.List[np.ndarray]:
    """Average per-process host arrays with an XLA reduction.

    Leaves are grouped by dtype and concatenated into one vector per
    dtype; each process contributes its vector as one shard of a
    [world, N] global array over a one-device-per-process mesh, and a
    jitted mean over the process dim lowers to a reduce — bytes on the
    wire O(N) per process versus the allgather's O(world * N).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _one_device_per_process_mesh()
    local_device = {d.process_index: d for d in mesh.devices.flat}[
        jax.process_index()]
    world = world_size()

    by_dtype: tp.Dict[np.dtype, tp.List[int]] = {}
    for index, leaf in enumerate(floats):
        by_dtype.setdefault(leaf.dtype, []).append(index)

    out: tp.List[tp.Optional[np.ndarray]] = [None] * len(floats)
    for dtype, indices in by_dtype.items():
        flat = np.concatenate([floats[i].reshape(-1) for i in indices])
        sharding = NamedSharding(mesh, P("proc", None))
        local = jax.device_put(flat[None], local_device)
        global_arr = jax.make_array_from_single_device_arrays(
            (world, flat.size), sharding, [local])
        mean = _mean_over_processes_fn(mesh)(global_arr)
        reduced = np.asarray(mean.addressable_data(0))
        offset = 0
        for i in indices:
            size = floats[i].size
            out[i] = reduced[offset:offset + size].reshape(floats[i].shape)
            offset += size
    return tp.cast(tp.List[np.ndarray], out)


def average_tensors(tree: tp.Any, *, method: str = "auto") -> tp.Any:
    """Mean of every float leaf across processes; returns the new pytree.

    Non-float leaves (step counters, int buffers) pass through untouched,
    mirroring the `_is_complex_or_float` filter of reference
    flashy/distrib.py:92-111. This is the *host-side parity path*; inside
    a jitted step prefer mesh sharding (`flashy_tpu.parallel`), where XLA
    fuses and overlaps the reduction.

    `method`: 'allgather' (every process receives all copies — lowest
    latency for small metric trees), 'reduce' (in-graph reduction, O(N)
    bytes on the wire — the right choice for model-sized trees), or
    'auto' (reduce above REDUCE_MIN_BYTES).
    """
    if not is_distributed():
        return tree
    floats, treedef = _partition_floats(tree)
    _check_tree_sizes(floats)
    total = sum(leaf.nbytes for leaf in floats)
    _note_host_sync(total)
    if method == "auto":
        method = "reduce" if total >= REDUCE_MIN_BYTES else "allgather"
    if method == "reduce":
        averaged: tp.Any = _reduce_mean_across_processes(floats)
    elif method == "allgather":
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(floats)
        averaged = jax.tree_util.tree_map(lambda x: x.mean(axis=0), gathered)
    else:
        raise ValueError(f"unknown method {method!r}")
    return _combine_floats(tree, treedef, averaged)


_host_sync_big_calls = 0


def _note_host_sync(total_bytes: int) -> None:
    """One-time performance warning for the slow-by-construction path.

    `average_tensors` stages every leaf device→host→device; the reduce
    method fixes wire bytes but not the host staging. A couple of large
    calls are normal (init broadcast, checkpoint averaging) — but a
    model-sized tree moving through here repeatedly is the reference's
    sync_model-per-step workflow, which on TPU regresses badly versus
    the in-graph route (`distrib.wrap` / `parallel.wrap`, where XLA
    keeps the gradient reduction on ICI, fused with the step). Warn
    once, on the third large call.
    """
    global _host_sync_big_calls
    if total_bytes < REDUCE_MIN_BYTES:
        return
    _host_sync_big_calls += 1
    if _host_sync_big_calls == 3:
        logger.warning(
            "average_tensors has now moved a >%d-byte tree through host "
            "memory %d times; if this is a per-step gradient/model sync, "
            "switch to the in-graph data-parallel path (distrib.wrap) — "
            "host staging serializes transfers the mesh path overlaps.",
            REDUCE_MIN_BYTES, _host_sync_big_calls)


def broadcast_tensors(tree: tp.Any, src: int = 0) -> tp.Any:
    """Broadcast float leaves from process `src` to all; returns new tree.

    Used to make sure all workers start from the same init
    (reference flashy/distrib.py:114-133).
    """
    if not is_distributed():
        return tree
    _require_backend()
    from jax.experimental import multihost_utils
    floats, treedef = _partition_floats(tree)
    _check_tree_sizes(floats)
    received = multihost_utils.broadcast_one_to_all(floats, is_source=rank() == src)
    return _combine_floats(tree, treedef, received)


def _partition_floats(tree: tp.Any):
    """Split out float leaves as host numpy arrays, remember positions."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    floats = [np.asarray(jax.device_get(leaf)) if _is_float_or_complex(leaf) else None
              for leaf in leaves]
    return [f for f in floats if f is not None], (treedef, [f is not None for f in floats], leaves)


def _combine_floats(tree: tp.Any, info, new_floats) -> tp.Any:
    treedef, mask, leaves = info
    new_floats = list(new_floats)
    out = []
    for leaf, is_float in zip(leaves, mask):
        out.append(new_floats.pop(0) if is_float else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_model(params: tp.Any, src: int = 0) -> tp.Any:
    """Broadcast a model's parameter pytree (params + mutable collections)."""
    return broadcast_tensors(params, src)


def sync_gradients(grads: tp.Any) -> tp.Any:
    """Average a gradient pytree across processes — the manual DDP
    alternative (reference flashy/distrib.py:136-150). Returns the new
    tree; apply it to your optimizer as usual.

    On TPU the preferred spelling is in-graph: shard the batch over the
    mesh's `data` axis with `flashy_tpu.parallel.wrap` and XLA emits the
    gradient psum itself, fused and overlapped with the backward.
    """
    return average_tensors(grads)


def sync_model(params: tp.Any, batch_stats: tp.Any = None, *,
               average_buffers: bool = True) -> tp.Any:
    """Average gradients-equivalent on a full model state.

    Given `(params, batch_stats)` pytrees (flax convention for mutable
    buffers like BatchNorm statistics), averages both — or broadcasts the
    buffers from process 0 when `average_buffers=False` (DDP behavior),
    mirroring reference flashy/distrib.py:193-210.
    """
    params = average_tensors(params)
    if batch_stats is None:
        return params
    if average_buffers:
        batch_stats = average_tensors(batch_stats)
    else:
        batch_stats = broadcast_tensors(batch_stats)
    return params, batch_stats


def eager_sync_gradients(grads: tp.Any) -> tp.Any:
    """API-compatible alias of `sync_gradients`.

    The reference's eager variant (flashy/distrib.py:153-190) starts
    all-reduces from backward hooks to overlap communication with the
    backward pass. Under XLA the latency-hiding scheduler performs that
    overlap automatically for in-graph reductions, so the eager/non-eager
    distinction is a no-op here by design.
    """
    return sync_gradients(grads)


def eager_sync_model(params: tp.Any, batch_stats: tp.Any = None, *,
                     average_buffers: bool = True) -> tp.Any:
    """API-compatible alias of `sync_model`; see `eager_sync_gradients`."""
    return sync_model(params, batch_stats, average_buffers=average_buffers)


def broadcast_object(obj: tp.Any = None, src: int = 0) -> tp.Any:
    """Share any picklable object from process `src` with everyone.

    The two-phase size-then-buffer dance of reference
    flashy/distrib.py:246-269 is unnecessary here:
    `broadcast_one_to_all` moves a padded byte tensor in one collective.
    """
    if not is_distributed():
        return obj
    _require_backend()
    import pickle
    from jax.experimental import multihost_utils
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8) if rank() == src \
        else np.zeros(0, dtype=np.uint8)
    size = int(multihost_utils.broadcast_one_to_all(
        np.array(len(payload), dtype=np.int64), is_source=rank() == src))
    if rank() != src:
        payload = np.zeros(size, dtype=np.uint8)
    data = multihost_utils.broadcast_one_to_all(payload, is_source=rank() == src)
    return pickle.loads(np.asarray(data).tobytes())


def barrier(name: str = "flashy_tpu_barrier") -> None:
    """Block until every process reaches this point."""
    if is_distributed():
        _require_backend()
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def loader(dataset, *args, shuffle: bool = False, klass=None, **kwargs):
    """Build a dataloader that shards correctly under distribution.

    Training (`shuffle=True`) uses an epoch-seeded shuffling sampler that
    pads to equal per-process length (DistributedSampler role); eval uses
    a strided shard with no sample replication — the exact split
    semantics of reference flashy/distrib.py:227-243. If the eval step
    runs in-graph collectives, pass `pad_to_even=True` to get equal
    per-process step counts with `(batch, valid_mask)` pairs instead
    (see `flashy_tpu.data.DataLoader` / `flashy_tpu.data.masked_mean`);
    plain strided shards may differ in length by one and deadlock the
    pod. See `flashy_tpu.data.DataLoader` for prefetch options.
    """
    from .data import DataLoader
    klass = klass or DataLoader
    return klass(dataset, *args, shuffle=shuffle,
                 num_shards=world_size(), shard_index=rank(), **kwargs)


def wrap(step_fn=None, **kwargs):
    """Data-parallel wrapper for a step function — the DDP role.

    See `flashy_tpu.parallel.wrap`: returns the step jitted with the batch
    sharded over the mesh's data axis and parameters replicated (or FSDP
    sharded); XLA inserts the gradient reductions.
    """
    from .parallel import wrap as _wrap
    return _wrap(step_fn, **kwargs)
