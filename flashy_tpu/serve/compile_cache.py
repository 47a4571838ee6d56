# Bucketed compile cache. The serving hot loop must never pay an XLA
# trace mid-flight: a fresh compile stalls EVERY live request for
# seconds (the exact failure the PR 1 RecompileWatchdog exposes on the
# training side). The cache pins one compiled executable per *shape
# bucket* — ("decode", S) for the slot-batched step, ("prefill", B) per
# power-of-two prompt bucket — wraps each in the watchdog so any
# post-warm-up recompile is counted and WARNed, and pre-warms the whole
# set at startup so steady-state traffic runs compile-free.
"""CompileCache: one watched, pre-warmed executable per shape bucket."""
import logging
import typing as tp

from ..observability import RecompileWatchdog, Tracer

logger = logging.getLogger(__name__)

Key = tp.Tuple[tp.Any, ...]


def bucket_length(n: int, *, minimum: int = 4,
                  maximum: tp.Optional[int] = None) -> int:
    """Round `n` up to the next power of two (>= `minimum`).

    Bucketing prompt lengths collapses the unbounded space of request
    shapes onto a handful of compiled prefill executables; the waste is
    bounded (at most 2x padded tokens) and the pad positions are never
    attended (causal mask) nor kept (overwritten by decode writes).
    `maximum` (the engine's max_seq_len) caps the bucket; `n` beyond it
    raises — the request cannot fit the cache.
    """
    if n < 1:
        raise ValueError(f"cannot bucket a length < 1, got {n}")
    bucket = minimum
    while bucket < n:
        bucket *= 2
    if maximum is not None:
        if n > maximum:
            raise ValueError(f"length {n} exceeds the bucket cap {maximum}")
        bucket = min(bucket, maximum)
    return bucket


class CompileCache:
    """Keyed registry of jitted functions with hit/miss + recompile stats.

    `get(key, build)` returns the function registered under `key`,
    building (and `RecompileWatchdog.watch`-wrapping) it on first use.
    Hits and misses are tallied and journaled through the tracer, so a
    serving run can assert "zero compiles after warm-up" the same way
    the training side asserts on the watchdog: `recompiles()` sums the
    post-warm-up recompile count across every cached function.

    Args:
        watchdog: the RecompileWatchdog recompiles are reported through;
            a private one is created when telemetry is off so the
            accounting always works.
        tracer: optional Tracer — each miss (a real XLA build) lands in
            the journal as a `compile_cache` record and an instant event.
    """

    def __init__(self, watchdog: tp.Optional[RecompileWatchdog] = None,
                 tracer: tp.Optional[Tracer] = None,
                 record_signatures: bool = True):
        self.watchdog = watchdog or RecompileWatchdog(warmup=1)
        self.tracer = tracer
        self.hits = 0
        self.misses = 0
        self._fns: tp.Dict[Key, tp.Callable] = {}
        # Per-executable distinct abstract call signatures (shape/dtype/
        # weak-type tuples -> call count): the registry the FT103
        # trace auditor consumes — a pre-flight "would these calls
        # retrace" record. Costs one tree_flatten per call, so only
        # the first `signature_sample` calls per executable pay it:
        # warm-up + the audit sweep live there, and anything leaking a
        # shape later is still caught by the runtime watchdog.
        self.record_signatures = record_signatures
        self.signature_sample = 64
        self.signatures: tp.Dict[str, tp.Dict[tp.Tuple, int]] = {}

    def __contains__(self, key: Key) -> bool:
        return key in self._fns

    def __len__(self) -> int:
        return len(self._fns)

    @staticmethod
    def _name(key: Key) -> str:
        return "/".join(str(part) for part in key)

    def get(self, key: Key, build: tp.Callable[[], tp.Callable]) -> tp.Callable:
        """The function under `key`; built via `build()` on first use.

        `build` must return a `jax.jit`-wrapped callable (the watchdog
        wrap enforces it). Each distinct key is built exactly once per
        cache lifetime — a steady stream of same-bucket requests is all
        hits.
        """
        fn = self._fns.get(key)
        if fn is not None:
            self.hits += 1
            return fn
        self.misses += 1
        name = self._name(key)
        fn = self.watchdog.watch(build(), name=name)
        if self.record_signatures:
            fn = self._with_signature_log(fn, name)
        self._fns[key] = fn
        logger.debug("compile cache miss: built %s", name)
        if self.tracer is not None:
            self.tracer.instant(f"compile_cache/miss/{name}",
                                category="serve")
            self.tracer.record({"type": "compile_cache", "event": "miss",
                                "key": name})
        return fn

    def warm(self, key: Key, build: tp.Callable[[], tp.Callable],
             *args: tp.Any, **kwargs: tp.Any) -> tp.Any:
        """Register `key` and execute it once on the given arguments.

        Calling (rather than AOT-lowering) warms the *jit cache itself*,
        so later calls with matching shapes are pure lookups and the
        watchdog's warm-up budget is consumed here, at startup, instead
        of on the first live request.
        """
        fn = self.get(key, build)
        if self.tracer is not None:
            with self.tracer.span(f"compile_cache/warm/{self._name(key)}",
                                  category="serve"):
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)

    def _with_signature_log(self, fn: tp.Callable, name: str) -> tp.Callable:
        import functools

        from ..analysis.trace.recompile_risk import call_signature
        log = self.signatures.setdefault(name, {})

        @functools.wraps(fn)
        def recorded(*args: tp.Any, **kwargs: tp.Any) -> tp.Any:
            if sum(log.values()) < self.signature_sample:
                sig = call_signature(args, kwargs)
                log[sig] = log.get(sig, 0) + 1
            return fn(*args, **kwargs)

        recorded.watchdog_name = getattr(  # type: ignore[attr-defined]
            fn, "watchdog_name", name)
        return recorded

    def executables(self) -> tp.Dict[str, tp.Callable]:
        """{name: watched function} — the audit registry: every compiled
        executable this cache manages, keyed by its watchdog name, with
        its recorded call signatures in `signatures[name]`."""
        return {self._name(key): fn for key, fn in self._fns.items()}

    def recompiles(self) -> int:
        """Total post-warm-up recompiles across all cached functions.

        The serving acceptance signal: after `warm()`ing every bucket,
        this stays 0 for the whole run — any growth means a shape leaked
        past the bucketing (and the watchdog already WARNed with the
        offending shapes).
        """
        return sum(self.watchdog.counts.get(self._name(key),
                                            {}).get("recompiles", 0)
                   for key in self._fns)

    def stats(self) -> tp.Dict[str, int]:
        """{hits, misses, entries, recompiles} snapshot."""
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._fns), "recompiles": self.recompiles()}
