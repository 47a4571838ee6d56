# Kernel block-size autotuning. The pallas kernels take tile-size
# knobs whose optimum depends on the chip generation and shape — the
# flash-attention kernels their (block_q, block_k) score tiles, the
# fused paged-decode kernel its head_block (heads per grid step:
# deeper VMEM scratch vs more pipeline steps over the block table);
# this module measures the candidates on the live backend once per
# shape and caches the winner process-wide. The winner decides which
# kernel gets compiled, so nothing outside the checkout is consulted by
# default: the on-disk cache exists only where `FLASHY_TPU_TUNE_CACHE`
# names a file. Every cache key leads with the
# KERNEL NAME: two kernels tuned at coincidentally equal geometry
# (same batch/heads/head_dim spelling) must never replay each other's
# winner — a flash (block_q, block_k) pair is meaningless to the
# paged kernel and vice versa. `python -m flashy_tpu.ops.tuning
# --show` prints the persisted winners (and `--clear` drops them) so
# a stale-looking pick is debuggable instead of a mystery.
"""Autotune pallas kernel block sizes on the attached accelerator."""
import functools
import json
import logging
import os
import time
import typing as tp
import uuid

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

# Candidate (block_q, block_k) tiles, all multiples of the 128-lane
# vector width; the sweep keeps only those dividing the sequence length.
CANDIDATES: tp.Tuple[tp.Tuple[int, int], ...] = (
    (128, 128), (128, 256), (128, 512), (256, 128), (256, 256),
    (256, 512), (256, 1024), (512, 256), (512, 512),
)

_cache: tp.Dict[tp.Tuple, tp.Any] = {}


def _cache_path() -> tp.Optional[str]:
    """The on-disk winners file: `FLASHY_TPU_TUNE_CACHE` when set, else
    None (winners then live for the process only)."""
    return os.environ.get("FLASHY_TPU_TUNE_CACHE") or None


def _load_disk_cache() -> tp.Dict[str, tp.List[int]]:
    path = _cache_path()
    if path is None:
        return {}
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return {}


def _runtime_fingerprint() -> tp.Tuple[str, str]:
    """(jax, jaxlib) version pair baked into every cache key.

    Block-size winners are measurements of a SPECIFIC compiled kernel:
    a jax/jaxlib upgrade can change the pallas lowering (or the
    candidate's viability entirely), so a persisted winner must never
    be replayed across runtimes — stale winners silently pessimize, or
    worse, pick a tile the new lowering cannot fit in VMEM.
    """
    try:
        import jaxlib
        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = "unknown"
    return (f"jax-{jax.__version__}", f"jaxlib-{jaxlib_version}")


def _make_key(kernel: str, *parts: tp.Any) -> tp.Tuple:
    """(kernel name, runtime fingerprint, device_kind, *shape parts).

    The kernel name LEADS so the flash and paged-decode tunings live in
    disjoint key spaces — the PR-8 shadowing lesson applied to the
    cache: same-looking geometry under two kernels must never collide.
    """
    return (kernel,) + _runtime_fingerprint() + (
        jax.devices()[0].device_kind,) + parts


def _flash_key(batch: int, seq_len: int, heads: int, head_dim: int,
               causal: bool, dtype: tp.Any,
               include_backward: bool) -> tp.Tuple:
    return _make_key("flash", batch, seq_len, heads, head_dim, causal,
                     str(jnp.dtype(dtype)), include_backward)


def lookup_tuned_blocks(batch: int, seq_len: int, heads: int, head_dim: int, *,
                        causal: bool = True, dtype: tp.Any = jnp.bfloat16,
                        include_backward: bool = True
                        ) -> tp.Optional[tp.Tuple[int, int]]:
    """Cache-only lookup of tuned (block_q, block_k) — NEVER sweeps.

    `flash_attention` calls this at trace time when no explicit block
    sizes were requested, so a winner recorded by `tune_flash_blocks`
    (this process, or the `FLASHY_TPU_TUNE_CACHE` file) applies to
    every later model at the same shape with zero per-run cost.
    Returns None on a cache miss (caller keeps its defaults).
    """
    try:
        key = _flash_key(batch, seq_len, heads, head_dim, causal, dtype,
                         include_backward)
    except Exception:  # devices not initialized / no backend
        return None
    return _coerce_pair(_lookup(key))


def _lookup(key: tp.Tuple) -> tp.Optional[tp.Any]:
    """Memory-then-disk cache lookup shared by every kernel's tuner."""
    if key in _cache:
        return _cache[key]
    disk_key = "/".join(str(part) for part in key)
    disk = _load_disk_cache()
    if disk_key in disk:
        _cache[key] = disk[disk_key]
        return disk[disk_key]
    return None


def _coerce_pair(hit: tp.Any) -> tp.Optional[tp.Tuple[int, int]]:
    """Disk value -> (block_q, block_k), or None on a corrupt entry.

    The cache file is hand-editable (the CLI points users at it) and
    may live on shared storage: a torn/garbage value must read as a
    MISS (caller keeps its defaults or re-sweeps), never raise at
    trace time."""
    if isinstance(hit, (str, bytes)):
        # a digit string is indexable — "128"[0] would coerce to the
        # bogus winner (1, 2) instead of reading as corruption
        return None
    try:
        pair = (int(hit[0]), int(hit[1]))
    except (TypeError, ValueError, IndexError, KeyError):
        return None
    return pair if all(p > 0 for p in pair) else None


def _coerce_int(hit: tp.Any) -> tp.Optional[int]:
    """Disk value -> a positive int winner, or None on corruption.

    Strings are corruption even when they parse: the tuner writes
    ints, so a string is always a hand-edit — same contract as
    `_coerce_pair`, where an indexable digit string would silently
    mangle into a bogus winner."""
    if isinstance(hit, (str, bytes)):
        return None
    try:
        value = int(hit)
    except (TypeError, ValueError):
        return None
    return value if value > 0 else None


def _store_disk_cache(key: str, best: tp.Any) -> None:
    path = _cache_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        disk = _load_disk_cache()
        # tuples json-round-trip as lists; scalar winners (the paged
        # kernel's head_block) store as-is
        disk[key] = list(best) if isinstance(best, (tuple, list)) else best
        # write-and-rename (as checkpoint.py): concurrent tuners (all
        # hosts of a pod, cache on shared storage) must never interleave
        # partial writes — a torn file would silently drop the cache.
        # uuid, not pid: containerized pod hosts often share pids.
        tmp = f"{path}.tmp.{uuid.uuid4().hex}"
        try:
            with open(tmp, "w") as f:
                json.dump(disk, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    except Exception as exc:  # cache is best-effort
        logger.debug("could not persist tune cache: %s", exc)


def _time_call(fn: tp.Callable[[], tp.Any], reps: int = 5) -> float:
    # one wait per measurement; the dispatches in between pipeline
    out = fn()
    jax.block_until_ready(out)
    begin = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - begin) / reps


def tune_flash_blocks(batch: int, seq_len: int, heads: int, head_dim: int, *,
                      causal: bool = True, dtype: tp.Any = jnp.bfloat16,
                      include_backward: bool = True,
                      candidates: tp.Sequence[tp.Tuple[int, int]] = CANDIDATES,
                      reps: int = 5,
                      interpret: tp.Optional[bool] = None) -> tp.Tuple[int, int]:
    """Measure flash-attention block-size candidates; return the winner.

    Benchmarks the jitted fwd (+bwd) at every viable candidate on the
    attached backend and caches per (device_kind, shape, causal, dtype)
    in memory (and in the `FLASHY_TPU_TUNE_CACHE` file when set). On
    CPU the kernel runs in interpret mode — timing there is meaningless,
    so the default (256, 256) is returned without sweeping.
    """
    from .attention import flash_attention

    key = _flash_key(batch, seq_len, heads, head_dim, causal, dtype,
                     include_backward)
    hit = _coerce_pair(_lookup(key))
    if hit is not None:
        return hit
    disk_key = "/".join(str(part) for part in key)

    viable = [(bq, bk) for bq, bk in candidates
              if seq_len % bq == 0 and seq_len % bk == 0]
    if (jax.default_backend() == "cpu" and not interpret) or not viable:
        # interpret-mode timings are meaningless; keep the default.
        return (256, 256)

    shape = (batch, seq_len, heads, head_dim)
    q = jnp.ones(shape, dtype)
    k = jnp.ones(shape, dtype)
    v = jnp.ones(shape, dtype)

    def build(bq: int, bk: int) -> tp.Callable[[], tp.Any]:
        if include_backward:
            grad = jax.jit(jax.grad(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    interpret=interpret)
                .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
            return lambda: grad(q, k, v)
        fwd = jax.jit(functools.partial(flash_attention, causal=causal,
                                        block_q=bq, block_k=bk,
                                        interpret=interpret))
        return lambda: fwd(q, k, v)

    timings: tp.Dict[tp.Tuple[int, int], float] = {}
    for bq, bk in viable:
        try:
            timings[(bq, bk)] = _time_call(build(bq, bk), reps)
        except Exception as exc:  # tile too large for VMEM, etc.
            logger.debug("flash tune: (%d, %d) failed: %s", bq, bk, exc)
    if not timings:
        return (256, 256)
    best = min(timings, key=timings.get)  # type: ignore[arg-type]
    logger.info("flash tune %s: best blocks %s (%.3f ms); swept %d candidates",
                key, best, timings[best] * 1e3, len(timings))
    _cache[key] = best
    _store_disk_cache(disk_key, best)
    return best


# ----------------------------------------------------------------------
# flash backward (training) tiles: the backward kernel runs 2-3
# matmuls per block pair against the forward's two, so its VMEM sweet
# spot can differ from the forward winner — tuned under its own
# "flash_bwd" key and consulted by the custom-vjp backward at trace
# time (cache-only, the lookup_tuned_blocks convention).
# ----------------------------------------------------------------------
def _flash_bwd_key(batch: int, seq_len: int, heads: int, head_dim: int,
                   causal: bool, dtype: tp.Any) -> tp.Tuple:
    return _make_key("flash_bwd", batch, seq_len, heads, head_dim, causal,
                     str(jnp.dtype(dtype)))


def lookup_tuned_bwd_blocks(batch: int, seq_len: int, heads: int,
                            head_dim: int, *, causal: bool = True,
                            dtype: tp.Any = jnp.bfloat16
                            ) -> tp.Optional[tp.Tuple[int, int]]:
    """Cache-only lookup of tuned backward (block_q, block_k) — NEVER
    sweeps. None on a miss (the backward then reuses the forward's
    tiles). Keyed "flash_bwd", disjoint from the forward's "flash" key
    space: the winners answer different questions."""
    try:
        key = _flash_bwd_key(batch, seq_len, heads, head_dim, causal, dtype)
    except Exception:  # devices not initialized / no backend
        return None
    return _coerce_pair(_lookup(key))


def tune_flash_bwd_blocks(batch: int, seq_len: int, heads: int,
                          head_dim: int, *, causal: bool = True,
                          dtype: tp.Any = jnp.bfloat16,
                          candidates: tp.Sequence[tp.Tuple[int, int]]
                          = CANDIDATES,
                          reps: int = 5,
                          interpret: tp.Optional[bool] = None
                          ) -> tp.Tuple[int, int]:
    """Measure BACKWARD-pass tile candidates; return + persist the winner.

    The timed program is the gradient alone (vjp of a precomputed
    forward — what the training step's backward actually pays), with
    the fused one-pass backward kernel at each candidate tile. On CPU
    without explicit `interpret=True` the default (256, 256) is
    returned unswept — interpret-mode timings are meaningless, the
    `tune_flash_blocks` convention.
    """
    from .attention import flash_attention

    key = _flash_bwd_key(batch, seq_len, heads, head_dim, causal, dtype)
    hit = _coerce_pair(_lookup(key))
    if hit is not None:
        return hit
    disk_key = "/".join(str(part) for part in key)

    viable = [(bq, bk) for bq, bk in candidates
              if seq_len % bq == 0 and seq_len % bk == 0]
    if (jax.default_backend() == "cpu" and not interpret) or not viable:
        return (256, 256)

    shape = (batch, seq_len, heads, head_dim)
    q = jnp.ones(shape, dtype)
    k = jnp.ones(shape, dtype)
    v = jnp.ones(shape, dtype)

    def build(bq: int, bk: int) -> tp.Callable[[], tp.Any]:
        def loss(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=interpret) \
                .astype(jnp.float32).sum()

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return lambda: grad(q, k, v)

    timings: tp.Dict[tp.Tuple[int, int], float] = {}
    for bq, bk in viable:
        try:
            timings[(bq, bk)] = _time_call(build(bq, bk), reps)
        except Exception as exc:  # tile too large for VMEM, etc.
            logger.debug("flash bwd tune: (%d, %d) failed: %s", bq, bk, exc)
    if not timings:
        return (256, 256)
    best = min(timings, key=timings.get)  # type: ignore[arg-type]
    logger.info("flash bwd tune %s: best blocks %s (%.3f ms); swept %d "
                "candidates", key, best, timings[best] * 1e3, len(timings))
    _cache[key] = best
    _store_disk_cache(disk_key, best)
    return best


# ----------------------------------------------------------------------
# remat-policy search: which transformer.py remat_policy a stage should
# run is a measurement, not a guess — 'dots' keeps most of the no-remat
# speed at a fraction of the activation HBM, but the winner depends on
# whether the stage is compute- or HBM-bound on THIS chip at THIS shape.
# ----------------------------------------------------------------------
REMAT_POLICIES: tp.Tuple[str, ...] = ("full", "dots", "dots_no_batch")


def _remat_key(stage: str, *parts: tp.Any) -> tp.Tuple:
    return _make_key("remat_policy", stage, *parts)


def _coerce_choice(hit: tp.Any,
                   choices: tp.Sequence[str]) -> tp.Optional[str]:
    """Disk value -> a known policy name, or None on corruption. The
    winner IS a string here, so (unlike `_coerce_int`) strings are
    valid — but only ones naming a policy this runtime knows."""
    return hit if isinstance(hit, str) and hit in choices else None


def lookup_remat_policy(stage: str, *parts: tp.Any) -> tp.Optional[str]:
    """Cache-only lookup of a recorded remat-policy winner — NEVER
    sweeps. `stage` names the timed program (e.g. 'lm_block'); `parts`
    carry its geometry (dim, layers, seq, batch...). None on a miss."""
    try:
        key = _remat_key(stage, *parts)
    except Exception:  # devices not initialized / no backend
        return None
    return _coerce_choice(_lookup(key), REMAT_POLICIES)


def search_remat_policy(build_step: tp.Callable[[str],
                                                tp.Callable[[], tp.Any]],
                        stage: str, *parts: tp.Any,
                        policies: tp.Sequence[str] = REMAT_POLICIES,
                        reps: int = 3,
                        allow_cpu: bool = False) -> str:
    """Time `build_step(policy)()` per candidate policy; record the
    winner under the "remat_policy" key for `lookup_remat_policy`.

    `build_step` returns the timeable thunk for one policy — typically
    a jitted grad step of a TransformerLM built with
    `dataclasses.replace(cfg, remat=True, remat_policy=policy)`. On
    CPU the sweep is skipped (timings there do not predict the TPU
    winner) and 'dots' — the policy that keeps matmul outputs — is
    returned unrecorded, unless `allow_cpu=True` (mechanism tests).
    """
    unknown = [p for p in policies if p not in REMAT_POLICIES]
    if unknown:
        raise ValueError(f"unknown remat policies {unknown}; "
                         f"pick from {list(REMAT_POLICIES)}")
    key = _remat_key(stage, *parts)
    hit = _coerce_choice(_lookup(key), REMAT_POLICIES)
    if hit is not None:
        return hit
    if jax.default_backend() == "cpu" and not allow_cpu:
        return "dots"
    timings: tp.Dict[str, float] = {}
    for policy in policies:
        try:
            timings[policy] = _time_call(build_step(policy), reps)
        except Exception as exc:  # policy OOMs / fails to lower
            logger.debug("remat search %s: %r failed: %s", stage, policy, exc)
    if not timings:
        return "dots"
    best = min(timings, key=timings.get)  # type: ignore[arg-type]
    logger.info("remat search %s%s: best %r (%.3f ms); swept %d policies",
                stage, parts, best, timings[best] * 1e3, len(timings))
    _cache[key] = best
    _store_disk_cache("/".join(str(part) for part in key), best)
    return best


# ----------------------------------------------------------------------
# fused paged-decode kernel (ops/paged_decode.py): head_block tuning
# ----------------------------------------------------------------------
def _paged_key(batch: int, queries: int, heads: int, head_dim: int,
               block_size: int, entries: int, quantized: bool,
               dtype: tp.Any) -> tp.Tuple:
    return _make_key("paged_decode", batch, queries, heads, head_dim,
                     block_size, entries, quantized, str(jnp.dtype(dtype)))


def lookup_tuned_paged_blocks(batch: int, queries: int, heads: int,
                              head_dim: int, *, block_size: int,
                              entries: int, quantized: bool,
                              dtype: tp.Any) -> tp.Optional[int]:
    """Cache-only lookup of the tuned paged-decode `head_block` —
    NEVER sweeps (`fused_paged_attention` consults it at trace time,
    the `lookup_tuned_blocks` convention). None on a miss."""
    try:
        key = _paged_key(batch, queries, heads, head_dim, block_size,
                         entries, quantized, dtype)
    except Exception:  # devices not initialized / no backend
        return None
    return _coerce_int(_lookup(key))


def tune_paged_blocks(batch: int, queries: int, heads: int,
                      head_dim: int, *, block_size: int, entries: int,
                      quantized: bool = True, dtype: tp.Any = jnp.bfloat16,
                      candidates: tp.Optional[tp.Sequence[int]] = None,
                      reps: int = 5,
                      interpret: tp.Optional[bool] = None) -> int:
    """Measure fused paged-decode `head_block` candidates per
    `device_kind`; return (and persist) the winner.

    Candidates default to the divisors of `heads`; the timed program
    is the fused kernel over a synthetic pool at exactly the serving
    geometry (batch=S slots, queries=1 decode or k+1 verify). On CPU
    without explicit `interpret=True` the default head_block is
    returned unswept — interpret-mode timings are meaningless, the
    `tune_flash_blocks` convention.
    """
    from .paged_decode import _default_head_block, fused_paged_attention

    key = _paged_key(batch, queries, heads, head_dim, block_size,
                     entries, quantized, dtype)
    hit = _coerce_int(_lookup(key))
    if hit is not None:
        return hit
    disk_key = "/".join(str(part) for part in key)

    if candidates is None:
        candidates = [hb for hb in range(1, heads + 1) if heads % hb == 0]
    viable = [hb for hb in candidates if heads % hb == 0]
    # sweep only where the fused kernel actually RUNS: on cpu/gpu
    # without explicit interpret, fused_paged_attention resolves to
    # interpret mode (meaningless timings) or the gather fallback
    # (head_block ignored — every candidate would time the same program
    # and persist a noise winner, possibly onto shared storage other
    # hosts replay); an explicit interpret=True still sweeps (mechanism
    # tests).
    backend = jax.default_backend()
    if not viable or (not interpret
                      and backend in ("cpu", "gpu", "cuda", "rocm")):
        return _default_head_block(heads, quantized)

    from .paged_attention import pool_spec
    spec = pool_spec(entries + 1, block_size, heads, head_dim, dtype,
                     "int8" if quantized else "model")
    rng = np.random.default_rng(0)
    entry = {name: jnp.asarray(rng.standard_normal(shape), dt)
             if jnp.dtype(dt) != jnp.int8
             else jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
             for name, (shape, dt) in spec.items()}
    q = jnp.asarray(rng.standard_normal(
        (batch, queries, heads, head_dim)), dtype)
    # every slot's table full: the steady-state (worst-case) read
    table = jnp.tile(jnp.arange(1, entries + 1, dtype=jnp.int32)[None],
                     (batch, 1))
    positions = (jnp.full((batch, 1), entries * block_size - queries,
                          jnp.int32)
                 + jnp.arange(queries, dtype=jnp.int32)[None])

    def build(hb: int) -> tp.Callable[[], tp.Any]:
        fwd = jax.jit(functools.partial(
            fused_paged_attention, head_dim=head_dim, dtype=dtype,
            head_block=hb, interpret=interpret))
        return lambda: fwd(q, entry, table, positions)

    timings: tp.Dict[int, float] = {}
    for hb in viable:
        try:
            timings[hb] = _time_call(build(hb), reps)
        except Exception as exc:  # tile too large for VMEM, etc.
            logger.debug("paged tune: head_block %d failed: %s", hb, exc)
    if not timings:
        return _default_head_block(heads, quantized)
    best = min(timings, key=timings.get)  # type: ignore[arg-type]
    logger.info("paged tune %s: best head_block %d (%.3f ms); swept %d "
                "candidates", key, best, timings[best] * 1e3,
                len(timings))
    _cache[key] = best
    _store_disk_cache(disk_key, best)
    return best


# ----------------------------------------------------------------------
# fused SSD chunked-scan kernel (ops/ssd_scan.py): chunk-size tuning
# ----------------------------------------------------------------------
def _ssd_key(batch: int, seq: int, heads: int, head_dim: int,
             dstate: int, dtype: tp.Any) -> tp.Tuple:
    return _make_key("ssd_scan", batch, seq, heads, head_dim, dstate,
                     str(jnp.dtype(dtype)))


def lookup_tuned_ssd_chunk(batch: int, seq: int, heads: int,
                           head_dim: int, dstate: int, *,
                           dtype: tp.Any) -> tp.Optional[int]:
    """Cache-only lookup of the tuned SSD chunk size — NEVER sweeps
    (`ssd_chunked_scan` consults it at trace time, the
    `lookup_tuned_blocks` convention). None on a miss."""
    try:
        key = _ssd_key(batch, seq, heads, head_dim, dstate, dtype)
    except Exception:  # devices not initialized / no backend
        return None
    return _coerce_int(_lookup(key))


def tune_ssd_chunk(batch: int, seq: int, heads: int, head_dim: int,
                   dstate: int, *, dtype: tp.Any = jnp.bfloat16,
                   candidates: tp.Optional[tp.Sequence[int]] = None,
                   reps: int = 5,
                   interpret: tp.Optional[bool] = None) -> int:
    """Measure fused SSD chunked-scan chunk-size candidates per
    `device_kind`; return (and persist) the winner.

    The chunk size trades intra-chunk matmul shape ([C, C] decay mask,
    [C, N]/[C, Dh] operands — bigger C feeds the MXU better) against
    grid length and VMEM residency, so the winner is a device-kind
    property. Candidates default to `ssd_scan.CHUNK_CANDIDATES`
    filtered to divisors of `seq`. On CPU without explicit
    `interpret=True` the default chunk is returned unswept —
    interpret-mode timings are meaningless, the `tune_flash_blocks`
    convention.
    """
    from .ssd_scan import CHUNK_CANDIDATES, default_chunk, ssd_chunked_scan

    key = _ssd_key(batch, seq, heads, head_dim, dstate, dtype)
    hit = _coerce_int(_lookup(key))
    if hit is not None:
        return hit
    disk_key = "/".join(str(part) for part in key)

    if candidates is None:
        candidates = CHUNK_CANDIDATES
    viable = [c for c in candidates if c <= seq and seq % c == 0]
    # sweep only where the fused kernel actually RUNS (the
    # tune_paged_blocks rationale: interpret-mode or fallback timings
    # would persist a noise winner onto shared storage).
    backend = jax.default_backend()
    if not viable or (not interpret
                      and backend in ("cpu", "gpu", "cuda", "rocm")):
        return default_chunk(seq)

    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.standard_normal((batch, seq, heads, dstate)), dtype)
    b = jnp.asarray(rng.standard_normal((batch, seq, heads, dstate)), dtype)
    v = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)), dtype)
    log_a = -jnp.abs(jnp.asarray(
        rng.standard_normal((batch, seq, heads)), jnp.float32))

    def build(chunk: int) -> tp.Callable[[], tp.Any]:
        fwd = jax.jit(functools.partial(
            ssd_chunked_scan, chunk=chunk, kernel="fused",
            interpret=interpret))
        return lambda: fwd(c, b, v, log_a)

    timings: tp.Dict[int, float] = {}
    for chunk in viable:
        try:
            timings[chunk] = _time_call(build(chunk), reps)
        except Exception as exc:  # tile too large for VMEM, etc.
            logger.debug("ssd tune: chunk %d failed: %s", chunk, exc)
    if not timings:
        return default_chunk(seq)
    best = min(timings, key=timings.get)  # type: ignore[arg-type]
    logger.info("ssd tune %s: best chunk %d (%.3f ms); swept %d "
                "candidates", key, best, timings[best] * 1e3,
                len(timings))
    _cache[key] = best
    _store_disk_cache(disk_key, best)
    return best


# ----------------------------------------------------------------------
# inspection CLI: `python -m flashy_tpu.ops.tuning --show / --clear`
# ----------------------------------------------------------------------
def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    """Print or drop the persisted tuning winners.

    A stale winner silently pessimizes (or picks a tile the current
    lowering cannot fit); when a kernel feels slow, `--show` answers
    "what winner is this runtime replaying, for which kernel, from
    which jax/jaxlib/device fingerprint" and `--clear` forces the next
    run to re-sweep.
    """
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m flashy_tpu.ops.tuning",
        description="Inspect/clear the persisted kernel tuning cache.")
    parser.add_argument("--show", action="store_true",
                        help="print every persisted winner, one per line")
    parser.add_argument("--clear", action="store_true",
                        help="delete the on-disk cache file")
    args = parser.parse_args(argv)
    if not (args.show or args.clear):
        parser.error("pick --show and/or --clear")
    path = _cache_path()
    if path is None:
        print("FLASHY_TPU_TUNE_CACHE is not set: no tuning winners are "
              "persisted or replayed")
        return 0
    if args.show:
        disk = _load_disk_cache()
        print(f"{path}: {len(disk)} entr{'y' if len(disk) == 1 else 'ies'}")
        for key in sorted(disk):
            kernel = key.split("/", 1)[0]
            print(f"  [{kernel}] {key} -> {disk[key]}")
    if args.clear:
        _cache.clear()
        try:
            os.unlink(path)
            print(f"cleared {path}")
        except FileNotFoundError:
            print(f"nothing to clear at {path}")
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via CLI tests
    raise SystemExit(main())
