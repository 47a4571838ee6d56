default: linter tests

linter:
	@if python -m flake8 --version >/dev/null 2>&1; then \
		python -m flake8 --max-line-length=120 flashy_tpu tests examples __graft_entry__.py; \
	else \
		echo "flake8 not installed; running syntax check only"; \
		python -m compileall -q flashy_tpu tests examples __graft_entry__.py; \
	fi

# Fast lane (default): everything but the `slow` marker — interpret-mode
# kernel grids, multi-process spawns, whole-example subprocesses, big
# SPMD compiles. Target: a few minutes. `tests-all` is the full matrix.
tests:
	python -m pytest tests -x -q -m "not slow"

# Project-aware static lint (flashy_tpu.analysis): trace-leak,
# shape-policy, fault-site-registry, stateful-attr, collective
# accounting and telemetry-naming invariants (FT001-FT006). Exit 1 on
# any NEW violation vs the committed .analysis-baseline.json. The
# analyzer itself is additionally type-checked with mypy when
# available (CI installs it via the dev extras).
analyze:
	python -m flashy_tpu.analysis
	@if python -m mypy --version >/dev/null 2>&1; then \
		python -m mypy --config-file mypy.ini flashy_tpu/analysis; \
	else \
		echo "mypy not installed; skipping analyzer type check"; \
	fi

# Trace-level program audit (flashy_tpu.analysis.trace): build the
# zero/pipeline/serve/elastic demo programs on 8 virtual CPU devices
# and run the FT101-FT104 auditors — compiled sharding layouts +
# collective mix (FT101, incl. the elastic leg: a zero1 checkpoint
# restored onto a half-size mesh must stay genuinely sharded, not fall
# back to silent full replication), pipeline tick tables model-checked
# against the traced ppermute ring (FT102), jit-signature retrace risk
# (FT103), and FLOP-priced idle-lane accounting (FT104). Exit 1 on any
# NEW finding vs the committed .analysis-trace-baseline.json.
analyze-trace:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.analysis --trace

# Numerics-flow audit (flashy_tpu.analysis.numerics): trace the
# registered hot programs (grad-accumulation + zero1 step, 1F1B
# pipeline, paged int8 attention, speculative verify, datapipe seed
# derivations) on 8 virtual CPU devices and run the FT201-FT204
# auditors — accumulation dtype (narrow scan-carry/reduction
# accumulators, complex-dropping casts), cast discipline (precision
# round trips, downcasts into optimizer state), int8 quant-scale
# placement (the scores/probs folding identity), and RNG discipline
# (key single-use, pure (seed, k) host derivations). Exit 1 on any
# NEW finding vs the committed .analysis-numerics-baseline.json.
analyze-numerics:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.analysis --numerics

# All three halves in one run — merged exit code, one summary table
# (the individual targets above remain for scoped runs).
analyze-all:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.analysis --all

tests-all:
	python -m pytest tests -x -q

coverage:
	coverage run -m pytest tests -q && coverage report -m --include='flashy_tpu/*'

# Continuous-batching serving smoke demo on CPU, all three legs: 32
# staggered requests through an 8-slot engine (token-exact against
# per-request generate(), zero post-warm-up recompiles), the
# speculative leg (n-gram draft + chunked prefill, still token-exact,
# acceptance over the floor), and the chunked-prefill stall-bound leg
# (exit 1 on any violation). A couple of minutes; also run by the
# tests workflow.
serve-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve --requests 32 --slots 8 \
		--legs batching,speculative,chunked

# Speculative decoding + chunked prefill gate on CPU: a repetitive
# mixed-length workload through a chunked-prefill engine with the
# n-gram draft must stay token-exact vs generate(), clear the
# acceptance-rate floor, and trigger zero post-warm-up compiles across
# admission/chunked prefill/verify/retirement; then a long prompt
# admitted mid-decode must cost live slots at most one chunk of
# prefill per tick (exit 1 on any violation). Seconds; also run by the
# tests workflow.
serve-spec-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve --legs speculative,chunked

# Paged-KV-cache gate on CPU: an int8 block pool sized to the dense
# cache budget of 4 slots must serve 16 concurrent slots (>= 2x is the
# floor) over a staggered workload sharing a long system prompt —
# token-exact vs per-request generate(), prefix-hit-rate over its
# floor, at least one copy-on-write fork, the pool conservation
# invariant held (never over-committed), and zero post-warm-up
# compiles across admission/prefix-hit/COW/decode/speculative
# verify/retirement (exit 1 on any violation). Every pool read runs
# the FUSED Pallas paged-decode kernel in interpret mode (the demo's
# default; --kernel gather re-runs the XLA reference). A minute or
# so; also run by the tests workflow.
serve-paged-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve --legs paged

# Observability gate on CPU: the batching workload served untraced,
# then with per-request tracing at sampling=1.0 + the SLO burn-rate
# engine — every finished request phase-attributable from
# requests.jsonl and the Perfetto async spans, no burn-rate alert on
# the healthy run while serve.json carries the slo report block, zero
# post-warm-up compiles in both passes, and full-rate tracing within
# 2x (+2ms) of the untraced ITL p50 (exit 1 on any violation).
# Seconds; also run by the tests workflow.
serve-slo-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve --legs slo

# State-space-mixer gate on CPU: a pure-SSD stack served through
# cache_layout='ssd', where each slot's decode state is one fixed
# [H, Dh, Dstate] tensor — dual-form (chunked vs recurrent) parity
# asserted at the ops layer, streaming sessions token-exact vs
# per-request generate() PAST the engine's attention-layout
# max_seq_len ceiling, zero post-warm-up compiles, and
# state_bytes_per_slot constant across max_seq_len in {1k, 8k, 64k}
# while paged-int8 grows linearly (so the same HBM budget holds
# strictly more SSD slots at 64k context). Exit 1 on any violation.
# Seconds; also run by the tests workflow.
ssd-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve --legs ssd

# Serving-fleet gate on CPU, all four legs: disaggregated prefill->
# decode handoff over one shared block pool (block-list transfer,
# token-exact vs per-request generate(), zero post-warm-up compiles on
# both engines), sticky prefix routing >= round-robin prefix hit rate
# on a shared-system-prompt workload with replayable deterministic
# decisions, priority preemption (victims evicted, re-queued and
# finished token-exactly, per-tenant rollups in serve.json, pool
# conservation throughout), and the engine-death drill (strict
# fleet.engine_step injection mid-decode, every in-flight request
# re-routed and re-served token-exactly, death recorded in
# fleet.json). Exit 1 on any violation. A minute or so; also run by
# the tests workflow.
fleet-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.serve.fleet

# Fault-tolerance chaos drill on CPU: train with an injected transient
# IO fault (must be absorbed by retry), a simulated mid-stage SIGTERM
# (must stop at a boundary with the requeue exit code) and a corrupted
# active checkpoint slot (must fall back to the sibling A/B slot), then
# resume and demand history/metrics identical to an uninterrupted run
# (exit 1 on any violation). Seconds; also run by the tests workflow.
chaos-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.resilience --epochs 5

# Registry-driven chaos campaign on 8 virtual CPU devices: every FT003
# fault site swept under at least one seeded fault schedule (transient
# raise / fatal kill / latency stall / on-disk corruption, as each
# site's scenario declares), driven through the real train / datapipe /
# serve / fleet / pipeline / elastic workloads with their invariant
# oracles (token-exactness vs generate(), pool conservation,
# checkpoint restorability, strict all-armed-faults-fired, WAL restart
# dedup). Exit 1 on any oracle failure (the failing schedule is
# ddmin-shrunk to campaign_repro.json — replay it with
# `python -m flashy_tpu.resilience --campaign --replay <artifact>`)
# or on incomplete registry coverage. A few minutes; also run by the
# tests workflow.
chaos-campaign:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.resilience --campaign --seed 0

# ZeRO-1 sharded-weight-update demo on 8 virtual CPU devices: replicated
# vs zero1 vs fsdp step time + per-chip optimizer HBM, exit 1 on any
# numeric drift from the replicated path or any post-warm-up recompile.
zero-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.parallel.zero --steps 3

# Pipeline-schedule gate on 8 virtual CPU devices: GPipe vs 1F1B vs
# interleaved vs packed-1F1B gradient steps on dense + MoE LMs over a
# pipe=4 mesh. Exit 1 unless 1F1B gradients match the GPipe oracle
# (MoE aux included), packed gradients are BIT-identical to unpacked
# 1F1B with realized step_ms strictly below it at equal (S, M, v),
# the 1F1B activation stash stays flat when the microbatch count
# doubles (while GPipe's residency grows), the interleaved bubble is
# strictly below GPipe's at equal M, the pipeline/bubble telemetry
# track was recorded, and zero post-warm-up recompiles were reported.
# A couple of minutes; also run by the tests workflow.
# (-W silences runpy's benign double-import warning: the package
# __init__ must eagerly export the `pipeline` function, which puts the
# submodule in sys.modules before runpy executes it.)
pipeline-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -W "ignore::RuntimeWarning:runpy" -m flashy_tpu.parallel.pipeline --steps 3

# Tensor-parallel (megatron) demo on 8 virtual CPU devices: train-step
# time, achieved TFLOP/s and per-chip optimizer HBM at tensor widths
# {1,2,4} with the zero1 update shard composed on top. Exit 1 unless
# TP gradients match the replicated single-chip oracle, per-chip
# optimizer bytes land at ~1/(data*tensor), the fused flash backward
# is BIT-identical to the split two-kernel oracle (interpret mode),
# and zero post-warm-up recompiles were reported. A couple of minutes;
# also run by the tests workflow.
tp-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.parallel.tensor --steps 3

# Elastic world-size drill on 8 virtual CPU devices: train at world 8,
# take a simulated SIGTERM mid-epoch, resume at world 4 (a lost slice)
# and grow back to 8 — with transient faults injected into the
# checkpoint reshard (ckpt.reshard) and the datapipe cursor re-split
# (datapipe.resplit), both of which must fire and be absorbed (strict
# injector). Exit 1 unless params are allclose across every
# save->restore transition, the consumed-token stream (canonical global
# order) is bit-identical to an uninterrupted run, restored optimizer
# state is genuinely sharded on the new mesh, and zero post-warm-up
# recompiles happen in any phase. Seconds; also run by the tests
# workflow.
elastic-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m flashy_tpu.resilience --elastic

# Streaming-datapipe drill on CPU: pack a synthetic jsonl+npy corpus
# mixture into fixed [B, L] segment-masked batches, train a tiny LM,
# kill it with a simulated SIGTERM mid-stream, resume from the
# committed input cursor, and demand the consumed token stream be
# IDENTICAL to an uninterrupted run with zero post-warm-up recompiles
# (exit 1 on any violation). Seconds; also run by the tests workflow.
datapipe-demo:
	JAX_PLATFORMS=cpu python -m flashy_tpu.datapipe

docs:
	python tools/gendocs.py -o docs/api -p flashy_tpu \
		-c 'flashy_tpu.observability*' -c 'flashy_tpu.serve*' \
		-c 'flashy_tpu.serve.fleet*' \
		-c 'flashy_tpu.resilience*' -c 'flashy_tpu.parallel*' \
		-c 'flashy_tpu.datapipe*' -c 'flashy_tpu.analysis*' \
		-c 'flashy_tpu.ops*'

native:
	python tools/build_native.py

dist:
	python -m build --sdist

.PHONY: default linter tests tests-all analyze analyze-trace analyze-numerics analyze-all coverage serve-demo serve-spec-demo serve-paged-demo serve-slo-demo ssd-demo fleet-demo chaos-demo chaos-campaign elastic-demo zero-demo pipeline-demo tp-demo datapipe-demo docs native dist
