# Per-executable roofline attribution. An aggregate MFU ("the step did
# 120 TFLOP/s") cannot say WHICH executable to optimize nor whether it
# is even compute-bound; the roofline model (arithmetic intensity vs
# the machine balance point) answers both per executable. XLA already
# knows every compiled program's FLOPs and HBM traffic — its
# `cost_analysis()` — so the profiler's job is bookkeeping: collect
# (flops, bytes) per executable at compile/registration time, collect
# measured wall time per call at run time, and divide. The analytic
# numbers the bench derives by hand (6*P flops/token, the paged-decode
# `decode_read_bytes_per_token`) become cross-checks against the
# compiler's own accounting instead of the only estimate.
#
# cost_analysis caveats (documented in docs/design.md): on the CPU
# backend the numbers come from XLA's generic HLO cost model — FLOPs
# are reliable for matmul-dominated programs, "bytes accessed" counts
# buffer traffic (not a real HBM), and fusion can legitimately shrink
# both vs a hand count. MFU on CPU is therefore reported against an
# explicitly passed peak only; without one the profiler still reports
# realized FLOP/s, GB/s and the intensity-based verdict.
"""RooflineProfiler: XLA cost_analysis + wall time -> MFU/GBps verdicts."""
import logging
import time
import typing as tp

from ..utils import percentile

logger = logging.getLogger(__name__)

# (device_kind substring, peak bf16 FLOP/s, peak HBM bytes/s) — the one
# peaks table (bench.py reads it too). Nominal per-chip numbers from the
# Google Cloud TPU documentation (cloud.google.com/tpu/docs, system
# architecture pages), matched case-insensitively against
# `jax.Device.device_kind`.
DEVICE_SPECS: tp.Tuple[tp.Tuple[str, float, float], ...] = (
    ("v6e", 918e12, 1640e9), ("trillium", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9), ("v5 lite", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)


def device_peaks(device_kind: tp.Optional[str] = None
                 ) -> tp.Tuple[tp.Optional[float], tp.Optional[float]]:
    """(peak FLOP/s, peak HBM bytes/s) for a device kind.

    `device_kind=None` reads the default jax device. The CPU has no
    accelerator peak and yields `(None, None)` (the profiler still
    reports realized rates there); an ACCELERATOR kind missing from
    `DEVICE_SPECS` raises — a utilization printed as `None` hides that
    nothing anchors it.
    """
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    if kind == "cpu":
        return None, None
    for needle, flops, bandwidth in DEVICE_SPECS:
        if needle in kind:
            return flops, bandwidth
    raise ValueError(
        f"no peak FLOP/s / HBM bandwidth entry for accelerator "
        f"{device_kind!r}: add it to observability.roofline.DEVICE_SPECS "
        f"with its source")


def _cost_analysis_dict(compiled: tp.Any) -> tp.Dict[str, float]:
    """Normalize `Compiled.cost_analysis()` across jax versions (it has
    returned both a dict and a one-element list of dicts)."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    return dict(analysis or {})


class ExecutableProfile:
    """Cost + timing record for one compiled executable."""

    def __init__(self, name: str, source: str = "cost_analysis"):
        self.name = name
        self.source = source            # 'cost_analysis' | 'analytic'
        self.flops: tp.Optional[float] = None
        self.bytes_accessed: tp.Optional[float] = None
        self.cost_error: tp.Optional[str] = None
        self.calls = 0
        self.wall: tp.List[float] = []  # per-call wall seconds (sampled)
        self.total_wall = 0.0
        self._lower: tp.Optional[tp.Callable[[], tp.Any]] = None

    @property
    def intensity(self) -> tp.Optional[float]:
        """Arithmetic intensity, FLOPs per byte of HBM traffic."""
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def resolve_costs(self) -> None:
        """Evaluate a deferred lowering (see `register_jit`) if pending."""
        if self._lower is None or self.flops is not None \
                or self.cost_error is not None:
            return
        lower, self._lower = self._lower, None
        try:
            analysis = _cost_analysis_dict(lower())
        except Exception as exc:  # noqa: BLE001 — cost is best-effort
            self.cost_error = str(exc)[:200]
            logger.debug("roofline: cost_analysis failed for %s: %s",
                         self.name, exc)
            return
        if "flops" in analysis:
            self.flops = float(analysis["flops"])
        if "bytes accessed" in analysis:
            self.bytes_accessed = float(analysis["bytes accessed"])


class RooflineProfiler:
    """Registry of executables with costs, timings and roofline verdicts.

    Registration paths (all idempotent per name):

    * `register_compiled(name, compiled)` — an AOT-compiled
      `jax.stages.Compiled`; costs read immediately (bench path).
    * `register_jit(name, fn, args, kwargs)` — a `jax.jit` callable
      plus the concrete call arguments; the arguments are abstracted to
      shape structs immediately (no buffers held alive — donation
      safe), and the lower+compile for `cost_analysis` is DEFERRED to
      the first `report()`, off the hot path (`wrap()` path).
    * `register_costs(name, flops, bytes_accessed)` — hand-derived
      numbers (`source='analytic'`), e.g. `decode_read_bytes_per_token`.

    Timing arrives via `observe(name, seconds)` (explicitly measured
    wall time — the only honest kind; the profiler never times async
    dispatch itself). `report()` divides: realized FLOP/s and HBM GB/s
    per executable, MFU / bandwidth fraction when peaks are known, and
    the compute-vs-bandwidth verdict from arithmetic intensity against
    the machine balance point.

    A disabled profiler (`enabled=False`, the Telemetry default) makes
    every method a cheap no-op, so call sites register unconditionally.
    """

    MAX_WALL_SAMPLES = 4096  # per executable; total stays bounded

    def __init__(self, peak_flops: tp.Optional[float] = None,
                 peak_bytes_per_sec: tp.Optional[float] = None,
                 tracer: tp.Optional[tp.Any] = None,
                 enabled: bool = True):
        self.tracer = tracer
        self.enabled = enabled
        self._explicit_peaks = (peak_flops is not None
                                or peak_bytes_per_sec is not None)
        self.peak_flops = peak_flops
        self.peak_bytes_per_sec = peak_bytes_per_sec
        self._peaks_probed = self._explicit_peaks
        self.profiles: tp.Dict[str, ExecutableProfile] = {}

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def _profile(self, name: str, source: str) -> ExecutableProfile:
        profile = self.profiles.get(name)
        if profile is None:
            profile = self.profiles[name] = ExecutableProfile(name, source)
        return profile

    def register_compiled(self, name: str, compiled: tp.Any) -> None:
        """Register an AOT `jax.stages.Compiled`; costs read now."""
        if not self.enabled or name in self.profiles:
            return
        profile = self._profile(name, "cost_analysis")
        try:
            analysis = _cost_analysis_dict(compiled)
        except Exception as exc:  # noqa: BLE001 — cost is best-effort
            profile.cost_error = str(exc)[:200]
            return
        if "flops" in analysis:
            profile.flops = float(analysis["flops"])
        if "bytes accessed" in analysis:
            profile.bytes_accessed = float(analysis["bytes accessed"])

    def register_jit(self, name: str, fn: tp.Any,
                     args: tp.Sequence[tp.Any],
                     kwargs: tp.Optional[tp.Dict[str, tp.Any]] = None,
                     static_argnums: tp.Sequence[int] = ()) -> None:
        """Register a jitted callable via its concrete call arguments.

        Array leaves are abstracted to `jax.ShapeDtypeStruct`
        IMMEDIATELY (donated buffers are not kept alive); python
        scalars and static positions pass through untouched so the
        deferred `fn.lower(...)` sees the same signature the live call
        did. The lower+compile that feeds `cost_analysis` runs at the
        first `report()` — one extra XLA compile per executable, paid
        off the hot path and only when a report is actually requested.
        """
        if not self.enabled or name in self.profiles:
            return
        import jax

        # validate eagerly: a bad signature would otherwise surface only
        # at the first report(), as a confusing deferred lower() error
        # (and an array passed as `args` would silently enumerate its
        # leading axis into a bogus per-row signature)
        if not isinstance(args, (tuple, list)):
            raise TypeError(
                f"register_jit args must be a tuple/list of call "
                f"arguments, got {type(args).__name__}: wrap a single "
                f"argument as (arg,)")
        if kwargs is not None and not isinstance(kwargs, dict):
            raise TypeError(
                f"register_jit kwargs must be a dict or None, got "
                f"{type(kwargs).__name__}")
        static = set(int(i) for i in (
            (static_argnums,) if isinstance(static_argnums, int)
            else static_argnums))

        def abstract(leaf: tp.Any) -> tp.Any:
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype)
            return leaf

        spec_args = tuple(
            arg if i in static else jax.tree_util.tree_map(abstract, arg)
            for i, arg in enumerate(args))
        spec_kwargs = {k: jax.tree_util.tree_map(abstract, v)
                       for k, v in (kwargs or {}).items()}
        profile = self._profile(name, "cost_analysis")
        profile._lower = lambda: fn.lower(*spec_args,
                                          **spec_kwargs).compile()

    def register_costs(self, name: str, flops: tp.Optional[float] = None,
                       bytes_accessed: tp.Optional[float] = None,
                       source: str = "analytic") -> None:
        """Register hand-derived costs (or override missing fields)."""
        if not self.enabled:
            return
        profile = self._profile(name, source)
        if flops is not None:
            profile.flops = float(flops)
        if bytes_accessed is not None:
            profile.bytes_accessed = float(bytes_accessed)

    # ------------------------------------------------------------------
    # timing
    # ------------------------------------------------------------------
    def observe(self, name: str, seconds: float, calls: int = 1) -> None:
        """Record `seconds` of measured wall time over `calls` calls."""
        if not self.enabled:
            return
        profile = self._profile(name, "cost_analysis")
        profile.calls += calls
        profile.total_wall += seconds
        if len(profile.wall) < self.MAX_WALL_SAMPLES and calls == 1:
            profile.wall.append(seconds)

    def note_call(self, name: str) -> None:
        """Count a call without timing it (wrap()'s async hot path —
        the stage's wall time arrives separately via `stage_summary`)."""
        if not self.enabled:
            return
        self._profile(name, "cost_analysis").calls += 1

    def timed(self, name: str, fn: tp.Callable) -> tp.Callable:
        """Wrap `fn` so each call is timed to completion (blocking on
        its outputs) and fed to `observe`. Meant for serving
        executables whose outputs are materialized immediately anyway
        (the engine converts to numpy right after) — the block moves
        the sync, it does not add one."""
        if not self.enabled:
            return fn
        import functools

        @functools.wraps(fn)
        def wrapped(*args: tp.Any, **kwargs: tp.Any) -> tp.Any:
            import jax
            start = time.perf_counter()
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            self.observe(name, time.perf_counter() - start)
            return out

        return wrapped

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def _ensure_peaks(self) -> None:
        if self._peaks_probed:
            return
        self._peaks_probed = True
        flops, bandwidth = device_peaks()
        self.peak_flops = self.peak_flops or flops
        self.peak_bytes_per_sec = self.peak_bytes_per_sec or bandwidth

    @property
    def balance(self) -> tp.Optional[float]:
        """The machine balance point (FLOPs/byte): intensity above it is
        compute-bound, below it bandwidth-bound."""
        self._ensure_peaks()
        if not self.peak_flops or not self.peak_bytes_per_sec:
            return None
        return self.peak_flops / self.peak_bytes_per_sec

    def _verdict(self, profile: ExecutableProfile) -> str:
        intensity = profile.intensity
        balance = self.balance
        if intensity is None:
            return "unknown"
        if balance is None:
            # no machine model: still classify by the common-sense cut
            # that decode-style streaming (< 10 flops/byte) is
            # bandwidth-bound on every accelerator ever built
            return "bandwidth-bound" if intensity < 10.0 else "unknown"
        return "compute-bound" if intensity >= balance else "bandwidth-bound"

    def summarize(self, name: str) -> tp.Optional[tp.Dict[str, tp.Any]]:
        """The roofline record for one executable, or None if unknown."""
        profile = self.profiles.get(name)
        if profile is None:
            return None
        profile.resolve_costs()
        entry: tp.Dict[str, tp.Any] = {
            "name": name, "source": profile.source,
            "flops_per_call": profile.flops,
            "bytes_per_call": profile.bytes_accessed,
            "intensity": profile.intensity,
            "calls": profile.calls,
            "verdict": self._verdict(profile),
        }
        if profile.cost_error:
            entry["cost_error"] = profile.cost_error
        if profile.calls and profile.total_wall > 0:
            per_call = profile.total_wall / profile.calls
            entry["wall_ms_per_call"] = per_call * 1e3
            if profile.wall:
                entry["wall_ms_p50"] = percentile(profile.wall, 50) * 1e3
            if profile.flops is not None:
                realized = profile.flops / per_call
                entry["realized_flops_per_sec"] = realized
                if self.peak_flops:
                    entry["mfu"] = realized / self.peak_flops
            if profile.bytes_accessed is not None:
                gbps = profile.bytes_accessed / per_call / 1e9
                entry["realized_hbm_gb_per_sec"] = gbps
                if self.peak_bytes_per_sec:
                    entry["hbm_frac"] = (gbps * 1e9
                                         / self.peak_bytes_per_sec)
        return entry

    def report(self) -> tp.Dict[str, tp.Any]:
        """Full roofline report: machine model + every executable."""
        self._ensure_peaks()
        executables = {}
        for name in sorted(self.profiles):
            entry = self.summarize(name)
            if entry is not None:
                executables[name] = entry
        return {"peak_flops": self.peak_flops,
                "peak_hbm_gb_per_sec": (self.peak_bytes_per_sec / 1e9
                                        if self.peak_bytes_per_sec else None),
                "balance_flops_per_byte": self.balance,
                "executables": executables}

    def stage_summary(self, device_seconds: float,
                      since: tp.Optional[tp.Dict[str, int]] = None
                      ) -> tp.Dict[str, float]:
        """Stage-level realized MFU/GBps from externally measured time.

        `device_seconds` is the stage's summed device time (StepTimer);
        the FLOPs/bytes are summed over every registered executable's
        calls (minus the `since` snapshot from `mark()`, so back-to-back
        stages don't double count). Flat numeric keys, ready to merge
        into a stage metrics dict."""
        if not self.enabled or device_seconds <= 0:
            return {}
        total_flops = 0.0
        total_bytes = 0.0
        priced_calls = 0
        for name, profile in self.profiles.items():
            calls = profile.calls - (since or {}).get(name, 0)
            if calls <= 0:
                continue
            profile.resolve_costs()
            if profile.flops is not None:
                total_flops += profile.flops * calls
                priced_calls += calls
            if profile.bytes_accessed is not None:
                total_bytes += profile.bytes_accessed * calls
        if not priced_calls:
            return {}
        out: tp.Dict[str, float] = {}
        if total_flops:
            realized = total_flops / device_seconds
            out["roofline_tflops_per_sec"] = realized / 1e12
            if self.peak_flops:
                out["roofline_mfu"] = realized / self.peak_flops
        if total_bytes:
            out["roofline_hbm_gb_per_sec"] = (total_bytes / device_seconds
                                              / 1e9)
        return out

    def mark(self) -> tp.Dict[str, int]:
        """Per-executable call-count snapshot (for `stage_summary`)."""
        return {name: p.calls for name, p in self.profiles.items()}

    def record(self, tracer: tp.Optional[tp.Any] = None) -> tp.Dict[str, tp.Any]:
        """Journal the report (`{"type": "roofline"}` record)."""
        report = self.report()
        tracer = tracer or self.tracer
        if tracer is not None:
            tracer.record({"type": "roofline", **report})
        return report
