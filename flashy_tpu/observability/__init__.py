# Runtime telemetry for flashy_tpu — the profiler subsystem the
# reference never shipped (SURVEY §5). Six pieces, one switch:
#
#  * Tracer            host-side spans -> Perfetto trace + telemetry.jsonl
#  * span              one host interval -> the profiler's clock + the Tracer
#  * StepTimer         data-wait / host / device split per training step
#  * RecompileWatchdog WARN when a jitted fn recompiles after warm-up
#  * Heartbeat         per-rank liveness files + cross-host straggler report
#  * SLOEngine         declarative latency budgets + burn-rate alerting
#
# `enable_telemetry()` (or `solver.enable_telemetry()`) turns everything
# on; the solver's stage loop, LogProgressBar and DataLoader then feed
# it automatically. Complements `solver.enable_profiling` (the XLA
# device-op timeline): profiling answers "what is the device doing",
# telemetry answers "why is the step slower than the device time".
#
# The serving layer (flashy_tpu.serve) reports through the same pipe:
# its CompileCache wraps every bucketed executable in the
# RecompileWatchdog, and its metrics surface emits "serve" category
# spans (serve/prefill, serve/decode), counter tracks
# (serve/queue_depth, serve/slot_occupancy) and serve_summary journal
# records via the Tracer.
#
# This module must stay importable with no accelerator present and must
# not initialize a JAX backend at import time (tests enforce it): jax
# is only imported inside functions that genuinely touch devices.
"""Runtime telemetry: tracing, step timing, recompile and straggler watch."""

from .tracer import JsonlJournal, Tracer, span  # noqa
from .steptimer import StepTimer  # noqa
from .watchdog import RecompileWatchdog  # noqa
from .heartbeat import (  # noqa
    Heartbeat, device_memory_stats, read_heartbeats, straggler_report,
    format_straggler_report,
)
from .slo import (  # noqa
    COUNTER_SLO_BURN, DEFAULT_SLO_BUDGETS, SLOBudget, SLOEngine,
    engine_budget_sets, format_slo_report,
)
from .telemetry import (  # noqa
    Telemetry, enable_telemetry, disable_telemetry, get_telemetry,
    TELEMETRY_NAME, TRACE_NAME, HEARTBEAT_DIR_NAME,
)

__all__ = [
    "Tracer", "JsonlJournal", "span", "StepTimer", "RecompileWatchdog",
    "Heartbeat", "Telemetry",
    "enable_telemetry", "disable_telemetry", "get_telemetry",
    "device_memory_stats", "read_heartbeats", "straggler_report",
    "format_straggler_report",
    "SLOBudget", "SLOEngine", "DEFAULT_SLO_BUDGETS", "format_slo_report",
    "engine_budget_sets", "COUNTER_SLO_BURN",
    "TELEMETRY_NAME", "TRACE_NAME", "HEARTBEAT_DIR_NAME",
]
