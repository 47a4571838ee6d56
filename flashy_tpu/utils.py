# Core utilities for flashy_tpu.
#
# Behavior parity with reference flashy/utils.py:19-69 (averager,
# write_and_rename, readonly), re-designed for JAX: metric values may be
# jax scalars (device arrays) and are converted on the host; `readonly`
# is provided for API compatibility but the idiomatic JAX spelling is
# `jax.lax.stop_gradient`, which `freeze` applies over a pytree.
"""Various utilities: metric averaging, atomic file writes, pytree helpers."""
from collections import defaultdict
from contextlib import contextmanager
from pathlib import Path
import os
import typing as tp

import jax
import numpy as np

AnyPath = tp.Union[Path, str]


def _scalar(value: tp.Any) -> float:
    """Convert a metric value (python number, numpy or jax scalar) to float.

    Device→host transfer happens here, once per metric, outside of jit.
    """
    if isinstance(value, (jax.Array, np.ndarray)):
        return float(np.asarray(value))
    return float(value)


def percentile(samples: tp.Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy semantics, stdlib-only).

    The one percentile used everywhere numbers are summarized (StepTimer
    step splits, the serving TTFT/ITL/occupancy surface) so a p95 means
    the same thing across subsystems. q is in [0, 100]; empty input
    returns 0.0 so summaries of an idle run stay well-formed.
    """
    if not samples:
        return 0.0
    ordered = sorted(samples)
    if len(ordered) == 1:
        return float(ordered[0])
    rank = (q / 100.0) * (len(ordered) - 1)
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    frac = rank - low
    return float(ordered[low] * (1.0 - frac) + ordered[high] * frac)


def averager(beta: float = 1.0) -> tp.Callable[..., tp.Dict[str, float]]:
    """Exponential Moving Average callback over dicts of metrics.

    Returns a function ``update(metrics, weight=1)`` that folds the given
    metrics into the running average and returns the averaged dict. With
    ``beta=1`` this is a plain (weighted) mean — the common case for
    per-epoch metric averaging. Mirrors reference flashy/utils.py:19-37.

    Values can be python floats, numpy scalars or jax scalars; jax values
    are pulled to the host (so call this outside of jit, typically on the
    output of a jitted step function).
    """
    num: tp.Dict[str, float] = defaultdict(float)
    den: tp.Dict[str, float] = defaultdict(float)

    def _update(metrics: tp.Dict[str, tp.Any], weight: float = 1.0) -> tp.Dict[str, float]:
        for key, value in metrics.items():
            num[key] = num[key] * beta + weight * _scalar(value)
            den[key] = den[key] * beta + weight
        return {key: value / den[key] for key, value in num.items()}

    return _update


@contextmanager
def write_and_rename(path: AnyPath, mode: str = "wb", suffix: str = ".tmp", pid: bool = False):
    """Write to a temporary file, then atomically rename over `path`.

    Renaming is atomic on POSIX filesystems, so a process killed mid-write
    (e.g. TPU pod preemption) can never leave a truncated checkpoint at the
    final path. Mirrors reference flashy/utils.py:40-54.
    """
    tmp_path = str(path) + suffix
    if pid:
        tmp_path += f".{os.getpid()}"
    with open(tmp_path, mode) as f:
        yield f
    os.rename(tmp_path, path)


def freeze(tree: tp.Any) -> tp.Any:
    """Return a copy of the pytree with gradients blocked on every leaf.

    The JAX equivalent of temporarily flipping ``requires_grad`` off
    (reference flashy/utils.py:57-69): apply the adversary with
    ``freeze(params)`` and its parameters receive no gradient from the
    enclosing `jax.grad`.
    """
    return jax.tree_util.tree_map(jax.lax.stop_gradient, tree)


# `readonly` is the reference's name for the same concept; in JAX there is
# no mutable requires_grad flag, so we expose it as a trivial alias used as
# `model.apply(readonly(params), x)`.
readonly = freeze


# The persistent compilation cache lives inside the checkout, next to
# the package, unless the environment places it elsewhere. The
# directory is part of the cache key, so it must not move between runs.
COMPILE_CACHE_DIRNAME = ".jax_compile_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Called by the entry points (`@flashy_tpu.main`, `python -m
    flashy_tpu.serve`, `chip_smoke.py`, `benchmarks/run.py`) before the
    first compile. With `JAX_COMPILATION_CACHE_DIR` set, JAX reads it itself
    and nothing is set in code; otherwise the cache goes to
    `<checkout>/.jax_compile_cache`, derived from this package's
    location so every working directory and every process of one
    checkout share it. Returns the directory in use.

    Either way MLIR locations stop carrying the Python call stack: a
    Pallas kernel's locations are serialized INTO the program (the
    Mosaic body rides the custom call as bytes the cache key hashes), so
    with the callers' frames the same train step compiled from two
    callers gets two cache keys and never hits. One frame stays, the
    op's own: with tracebacks switched off altogether jax 0.9.0 nests
    the name stack under the primitive's name and XLA keeps only the
    outer one, so every `jax.named_scope` and Flax module path would be
    missing from the HLO `op_name` (and from a device trace) — measured
    on the v5e: `dot_general` instead of `jit(decode_paged)/qkv/dot_general`.
    """
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(Path(__file__).resolve().parent.parent / COMPILE_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_key(seed: int = 0) -> "jax.Array":
    """PRNG key identical on every process: use for parameter init so
    all workers start from the same model (pairs with, or replaces, an
    explicit `distrib.broadcast_model`)."""
    return jax.random.PRNGKey(seed)


def data_key(seed: int = 0) -> "jax.Array":
    """PRNG key distinct per process: use for data augmentation /
    sampling so workers do not duplicate randomness."""
    from .distrib import rank  # env-first; never forces backend init
    return jax.random.fold_in(jax.random.PRNGKey(seed), rank())


def to_numpy(tree: tp.Any) -> tp.Any:
    """Convert every array leaf of a pytree to a host numpy array.

    Used when assembling checkpoints: device arrays are gathered to host
    memory so serialization never holds HBM references. Globally-sharded
    arrays (multi-host, not fully addressable locally) are all-gathered —
    a COLLECTIVE: every process must call this together, even if only
    rank zero writes the result to disk.
    """

    def _leaf(x):
        if isinstance(x, jax.Array):
            if not x.is_fully_addressable:
                from jax.experimental import multihost_utils
                return np.asarray(multihost_utils.process_allgather(x, tiled=True))
            return np.asarray(jax.device_get(x))
        return x

    return jax.tree_util.tree_map(_leaf, tree)


def tree_bytes(tree: tp.Any) -> int:
    """Total size in bytes of all array leaves of a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(x.size * x.dtype.itemsize for x in leaves
               if isinstance(x, (jax.Array, np.ndarray)))
