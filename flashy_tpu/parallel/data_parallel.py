# The DDP replacement. Reference `flashy.distrib.wrap` returned a
# DistributedDataParallel module (flashy/distrib.py:65-75); here `wrap`
# returns the user's *step function* jitted with the batch sharded over
# the mesh's batch axes and the train state replicated (or FSDP-sharded).
# XLA's SPMD partitioner then inserts the gradient psum (or
# reduce-scatter, under FSDP) and the latency-hiding scheduler overlaps
# it with the backward — the role of DDP's bucketed NCCL all-reduce and
# of `eager_sync_gradients` (flashy/distrib.py:153-190), done by the
# compiler instead of by hooks.
"""Data-parallel / FSDP step wrapping and batch sharding helpers."""
import collections
import itertools
import logging
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability.watchdog import RecompileWatchdog, describe_abstract
from .mesh import default_mesh

logger = logging.getLogger(__name__)

BATCH_AXES = ("data", "fsdp")

# Compile accounting for `wrap` when telemetry is off: misses still land
# in a watchdog so `wrapped.compile_stats()` always answers (mirrors the
# private-watchdog fallback of serve.CompileCache).
_fallback_watchdog = RecompileWatchdog(warmup=1)
_wrap_ids = itertools.count()


def replicate(tree: tp.Any, mesh: tp.Optional[Mesh] = None) -> tp.Any:
    """Place every leaf fully replicated over the mesh."""
    mesh = mesh or default_mesh()
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def batch_spec(batch_axes: tp.Sequence[str] = BATCH_AXES) -> P:
    """PartitionSpec sharding the leading (batch) dim over the batch axes."""
    return P(tuple(batch_axes))


def shard_batch(batch: tp.Any, mesh: tp.Optional[Mesh] = None,
                batch_axes: tp.Sequence[str] = BATCH_AXES) -> tp.Any:
    """Shard a host batch (pytree of arrays, leading dim = batch) over the
    mesh's batch axes.

    Single-process: a plain device_put with the sharded layout.
    Multi-process: each process contributes its local shard and the
    result is the *global* array (per-process loaders feed disjoint data,
    see flashy_tpu.data), so jitted steps see the full global batch.
    """
    mesh = mesh or default_mesh()
    spec = batch_spec(batch_axes)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(batch, mesh, spec)
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


def axis_leaf_sharding(mesh: Mesh, axis: str, min_size: int,
                       base: tp.Optional[tp.Callable[[tp.Any], P]] = None
                       ) -> tp.Callable[[tp.Any], NamedSharding]:
    """Leaf rule shared by `fsdp_sharding` (axis='fsdp') and
    `zero.zero_sharding` (axis='data'): shard the largest dimension
    divisible by the axis size; leaves below `min_size` elements stay
    replicated (sharding tiny arrays costs more in collective latency
    than it saves in HBM).

    `base` composes a second parallelism dimension through the same
    seam: a callable returning the PartitionSpec a leaf ALREADY
    carries (the megatron column/row splits of `tensor.py`'s
    `transformer_shardings`). The rule then shards the largest
    divisible dim NOT claimed by the base spec and merges the two — a
    qkv kernel tensor-split on its heads dim gets its zero1 'data'
    shard on the model dim, so per-chip update state scales
    ~1/(data*tensor) under the composed mesh."""
    axis_size = mesh.shape[axis]

    def leaf_sharding(x) -> NamedSharding:
        shape = np.shape(x)
        if base is None:
            spec: tp.List[tp.Any] = [None] * len(shape)
        else:
            spec = list(base(x))
            spec += [None] * (len(shape) - len(spec))
        used = {name for part in spec if part is not None
                for name in (part if isinstance(part, tuple) else (part,))}
        if axis_size > 1 and np.size(x) >= min_size and axis not in used:
            # Prefer sharding the largest divisible dim.
            order = sorted(range(len(shape)), key=lambda i: -shape[i])
            for dim in order:
                if spec[dim] is None and shape[dim] % axis_size == 0:
                    spec[dim] = axis
                    break
            else:
                # Every divisible dim is claimed by the base spec (a 2D
                # megatron matrix carries tensor AND fsdp): ride along
                # an already-sharded dim — the HSDP spelling ('fsdp',
                # 'data') — wherever the composed shard still divides.
                # Without this, exactly the biggest MLP/embedding
                # moments would stay at 1/tensor instead of
                # 1/(tensor*data), which FT101's live-bytes gate flags.
                for dim in order:
                    part = spec[dim]
                    if part is None:
                        continue
                    parts = part if isinstance(part, tuple) else (part,)
                    span = axis_size * int(
                        np.prod([mesh.shape[p] for p in parts]))
                    if shape[dim] % span == 0:
                        spec[dim] = (*parts, axis)
                        break
        if base is None and not any(part is not None for part in spec):
            # exact historical spelling: a replicated leaf is P(), not
            # an all-None spec of matching rank
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*spec))

    return leaf_sharding


def fsdp_sharding(tree: tp.Any, mesh: tp.Optional[Mesh] = None,
                  axis: str = "fsdp", min_size: int = 2 ** 16) -> tp.Any:
    """Per-leaf NamedShardings that split each large parameter over `axis`.

    The largest dimension divisible by the axis size is sharded; small
    leaves stay replicated. With params sharded this way and the
    batch sharded on ('data','fsdp'), XLA emits the ZeRO-3 pattern:
    all-gather params into each matmul, reduce-scatter the grads.
    For the ZeRO-1 middle ground (shard only the *update*, keep compute
    params replicated) see `flashy_tpu.parallel.zero`.
    """
    mesh = mesh or default_mesh()
    return jax.tree_util.tree_map(axis_leaf_sharding(mesh, axis, min_size),
                                  tree)


def shard_params(params: tp.Any, mesh: tp.Optional[Mesh] = None,
                 axis: str = "fsdp", min_size: int = 2 ** 16) -> tp.Any:
    """Apply `fsdp_sharding` placements to a concrete parameter pytree."""
    shardings = fsdp_sharding(params, mesh, axis, min_size)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def with_grad_accumulation(value_and_grad_fn: tp.Callable,
                           num_microbatches: int, *,
                           fold_rng: tp.Union[bool, str] = True) -> tp.Callable:
    """Split the batch into microbatches and accumulate gradients.

    Wraps `value_and_grad_fn(params, batch, *rest) -> (loss, grads)`
    (a mean-reduced loss) into a function with identical signature and
    results, but peak activation memory divided by `num_microbatches`:
    the microbatches run sequentially under `lax.scan` with a running
    gradient sum. Composes with `wrap` — accumulate first, then shard::

        grad_fn = with_grad_accumulation(jax.value_and_grad(loss_fn), 8)

    The batch's leading dim must divide by `num_microbatches`. With
    `fold_rng=True` (default), any PRNG key found among `rest` has the
    microbatch index folded in, so dropout (etc.) draws fresh randomness
    per microbatch instead of repeating the same pattern
    `num_microbatches` times. Typed keys (`jax.random.key`) are detected
    exactly; legacy raw keys are detected heuristically as uint32 arrays
    of shape (2,) — a warning is logged once when that heuristic fires,
    because a NON-key uint32 pair passed through `rest` would be
    rewritten too. Set `fold_rng="typed"` to fold only exactly-detected
    typed keys, or `fold_rng=False` to disable folding.
    """
    if fold_rng not in (True, False, "typed"):
        raise ValueError(
            f"fold_rng must be True, False or 'typed', got {fold_rng!r}")
    if num_microbatches <= 1:
        return value_and_grad_fn
    warned = []  # one warning per wrapped fn, fires at trace time

    def fold_rng_keys(tree, index):
        if not fold_rng:
            return tree

        def fold(leaf):
            dtype = getattr(leaf, "dtype", None)
            if dtype is None:
                return leaf
            if jnp.issubdtype(dtype, jax.dtypes.prng_key):
                return jax.random.fold_in(leaf, index)
            if (fold_rng != "typed"
                    and dtype == jnp.uint32
                    and getattr(leaf, "shape", None) == (2,)):
                if not warned:
                    warned.append(True)
                    logger.warning(
                        "with_grad_accumulation: folding a raw (2,)-uint32 "
                        "array as a legacy PRNG key; if this is not a key, "
                        "pass fold_rng='typed' (and use jax.random.key) or "
                        "fold_rng=False.")
                return jax.random.fold_in(leaf, index)
            return leaf

        return jax.tree_util.tree_map(fold, tree)

    def wrapped(params, batch, *rest):
        def split(x):
            return x.reshape(num_microbatches, x.shape[0] // num_microbatches,
                             *x.shape[1:])

        micro = jax.tree_util.tree_map(split, batch)

        # The running sums live in float32 (f64 for f64 grads) no matter
        # what dtype the grads come back in: a bf16 running sum loses the
        # low mantissa bits of every addend once the partial sum grows —
        # past ~8 microbatches the accumulated gradient visibly drifts
        # from the full-batch one. Output dtypes (from eval_shape, no
        # FLOPs) are restored after the scan, so the wrapper's contract
        # — identical signature and results — still holds.
        loss_struct, grad_struct = jax.eval_shape(
            value_and_grad_fn, params,
            jax.tree_util.tree_map(lambda x: x[0], micro),
            *fold_rng_keys(rest, 0))

        def body(carry, inputs):
            index, microbatch = inputs
            loss_acc, grad_acc = carry
            loss, grads = value_and_grad_fn(params, microbatch,
                                            *fold_rng_keys(rest, index))
            grad_acc = jax.tree_util.tree_map(
                lambda acc, g: acc + g.astype(acc.dtype), grad_acc, grads)
            return (loss_acc + loss.astype(loss_acc.dtype), grad_acc), None

        zeros = jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, _accum_dtype(g.dtype)), grad_struct)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros(loss_struct.shape,
                             _accum_dtype(loss_struct.dtype)), zeros),
            (jnp.arange(num_microbatches), micro))
        scale = 1.0 / num_microbatches
        return ((loss * scale).astype(loss_struct.dtype),
                jax.tree_util.tree_map(
                    lambda g, s: (g * scale).astype(s.dtype),
                    grads, grad_struct))

    return wrapped


def _accum_dtype(dtype):
    """Accumulator dtype for a gradient/loss dtype: f64/complex stay as
    they are (already full-width; casting complex to f32 would silently
    drop the imaginary part), every other float (incl. bf16/f16) is
    summed in f32."""
    dtype = np.dtype(dtype)
    if dtype == np.float64 or np.issubdtype(dtype, np.complexfloating):
        return dtype
    return np.float32


def wrap(step_fn: tp.Optional[tp.Callable] = None, *,
         mesh: tp.Optional[Mesh] = None,
         batch_axes: tp.Sequence[str] = BATCH_AXES,
         fsdp: bool = False,
         state_sharding: tp.Any = None,
         donate_state: bool = True,
         static_argnums: tp.Union[int, tp.Sequence[int]] = (),
         watchdog: tp.Optional[RecompileWatchdog] = None,
         max_cache: int = 8) -> tp.Callable:
    """Make a step function data-parallel over the mesh — the DDP role.

    The step must have signature `step(state, batch, *rest) -> (state, aux)`
    (or any output pytree; the first output leg is given the same sharding
    as the input state). `state` is replicated (or FSDP-sharded with
    `fsdp=True` / an explicit `state_sharding` pytree); `batch` is sharded
    on its leading dim over `batch_axes`. Because the loss averages over
    the *global* batch, `jax.grad` inside the step yields gradients that
    XLA automatically psums across the batch axes — no explicit
    `sync_gradients` call, no hooks, no buckets.

    Usable as decorator (`@wrap`) or call (`wrap(step, mesh=mesh)`).
    Feed batches through `shard_batch` (or `flashy_tpu.data` loaders,
    which do it for you).

    The per-state-shape executable cache is bounded (`max_cache`, LRU)
    and every underlying XLA compile — a state-shape cache miss AND any
    inner-jit retrace from changed batch/rest shapes — is reported
    through the PR 1 `RecompileWatchdog` (`watchdog` argument > the
    enabled telemetry's watchdog > a module fallback), so a step
    recompiling past warm-up WARNs with the offending argument shapes
    instead of silently growing a cache; `wrapped.compile_stats()`
    exposes the tally.
    """
    if step_fn is None:
        return lambda fn: wrap(fn, mesh=mesh, batch_axes=batch_axes, fsdp=fsdp,
                               state_sharding=state_sharding,
                               donate_state=donate_state,
                               static_argnums=static_argnums,
                               watchdog=watchdog, max_cache=max_cache)

    mesh = mesh or default_mesh()
    data_sharding = NamedSharding(mesh, batch_spec(batch_axes))
    replicated = NamedSharding(mesh, P())

    def resolve_state_sharding(state):
        if state_sharding is not None:
            return state_sharding
        if fsdp:
            return fsdp_sharding(state, mesh)
        return jax.tree_util.tree_map(lambda _: replicated, state)

    compiled_cache: tp.Dict[tp.Any, tp.Callable] = collections.OrderedDict()
    # Unique per wrap instance so two wraps of same-named step functions
    # never share (and cross-pollute) a watchdog entry.
    watch_name = (f"wrap:{getattr(step_fn, '__name__', 'step')}"
                  f"#{next(_wrap_ids)}")

    last_watchdog: tp.List[tp.Optional[RecompileWatchdog]] = [None]

    def resolve_watchdog() -> RecompileWatchdog:
        if watchdog is not None:
            return watchdog
        from .. import observability
        telemetry = observability.get_telemetry()
        wd = telemetry.watchdog if telemetry is not None \
            else _fallback_watchdog
        previous = last_watchdog[0]
        if previous is not None and previous is not wd:
            # telemetry toggled mid-run: MOVE this wrap's tally to the
            # new watchdog, or the fresh entry would restart the warm-up
            # budget and swallow exactly the post-warm-up recompile the
            # watchdog exists to report.
            carried = previous.counts.pop(watch_name, None)
            if carried is not None:
                entry = wd._entry(watch_name)
                for field, count in carried.items():
                    entry[field] = entry.get(field, 0) + count
        last_watchdog[0] = wd
        return wd

    def wrapped(state, batch, *rest):
        # Key on structure AND leaf shapes/dtypes: resolved shardings
        # depend on leaf shapes (fsdp picks the dim to split), so a state
        # with the same structure but different shapes must not reuse them.
        key = (jax.tree_util.tree_structure(state),
               tuple((tuple(np.shape(leaf)), str(getattr(leaf, "dtype", type(leaf))))
                     for leaf in jax.tree_util.tree_leaves(state)))
        wd = resolve_watchdog()
        wd.note_call(watch_name)
        missed = key not in compiled_cache
        if not missed:
            compiled_cache.move_to_end(key)
        else:
            if len(compiled_cache) >= max_cache:
                evicted, _ = compiled_cache.popitem(last=False)
                logger.warning(
                    "wrap cache for %r exceeded max_cache=%d; evicting the "
                    "least-recently-used executable (a recompile awaits its "
                    "state shape).", watch_name, max_cache)
            sharding = resolve_state_sharding(state)
            # `None` legs leave the sharding to the partitioner (prefix
            # pytrees are allowed in jit shardings).
            in_shardings = (sharding, data_sharding) + tuple(None for _ in rest)
            # Shape the out_shardings to the step's actual output
            # structure: the first leg of a tuple output is the new state
            # (same sharding as the input state); anything else is left
            # to the partitioner. A bare (non-tuple) output is treated as
            # the state itself.
            out_struct = jax.eval_shape(step_fn, state, batch, *rest)
            if isinstance(out_struct, tuple) and len(out_struct) >= 1:
                out_shardings = (sharding,) + (None,) * (len(out_struct) - 1)
            else:
                out_shardings = sharding
            compiled_cache[key] = jax.jit(
                step_fn,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
                donate_argnums=(0,) if donate_state else (),
                static_argnums=static_argnums)
        fn = compiled_cache[key]
        # Count ACTUAL XLA compiles via the inner jit's cache growth
        # (the same hook RecompileWatchdog.watch polls): a state-shape
        # miss above compiles on this first call, but so does a changed
        # batch/rest shape against a cached entry — the most common
        # silent-recompile source, invisible to the key check alone.
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is None:
            # no growth hook on this jax: fall back to miss counting
            if missed:
                wd.note_compile(watch_name, describe_abstract(
                    (state, batch) + tuple(rest), {}))
            return fn(state, batch, *rest)
        before = cache_size()
        out = fn(state, batch, *rest)
        for _ in range(cache_size() - before):
            wd.note_compile(watch_name, describe_abstract(
                (state, batch) + tuple(rest), {}))
        return out

    def compile_stats() -> tp.Dict[str, int]:
        """{calls, compiles, recompiles} of this wrapped step, as tallied
        by whichever watchdog its cache misses were reported through."""
        totals = {"calls": 0, "compiles": 0, "recompiles": 0}
        candidates = [watchdog] if watchdog is not None else None
        if candidates is None:
            from .. import observability
            telemetry = observability.get_telemetry()
            candidates = [_fallback_watchdog] + (
                [telemetry.watchdog] if telemetry is not None else [])
        for wd in candidates:
            entry = wd.counts.get(watch_name)
            if entry:
                for field in totals:
                    totals[field] += entry[field]
        return totals

    wrapped.mesh = mesh  # type: ignore[attr-defined]
    wrapped.watchdog_name = watch_name  # type: ignore[attr-defined]
    wrapped.compile_stats = compile_stats  # type: ignore[attr-defined]
    return wrapped
