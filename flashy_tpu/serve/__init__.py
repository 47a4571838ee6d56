# Continuous-batching inference serving — the request-level layer on
# top of models/decoding.py. The training side of this repo already
# compiles one step function and reuses it for a whole run; serving
# gets the same compiler-first discipline: a fixed-capacity KV cache
# partitioned into S per-request slots, ONE compiled [S, 1] decode step
# that runs whatever mix of slots is live (liveness is an input mask,
# never a shape), prompt prefill bucketed to powers of two — or, in
# chunked mode, advanced in fixed [1, chunk] slices interleaved with
# decode ticks so a long prompt never monopolizes a step — and a FIFO
# continuous-batching scheduler that retires requests on EOS/length and
# refills freed slots while decode keeps streaming. Speculative
# decoding rides the same static-shape discipline: a draft provider
# proposes k tokens per slot, ONE [S, k+1] verify step scores them all
# against the target model, and the longest accepted prefix plus a
# bonus token is emitted — token-exact under greedy verification,
# distribution-exact under rejection sampling, with rollback free by
# construction (stale K/V rows past the accepted position are beyond
# every causal horizon until overwritten). Pieces:
#
#  * DecodeEngine / SlotAllocator   slot cache + compiled steps: decode,
#                                   [S, k+1] verify, bucketed or chunked
#                                   prefill (engine)
#  * DraftProvider / NGramDraft /   k-token proposals: prompt-lookup
#    ModelDraft                     (host-side, dependency-free) or a
#                                   small TransformerLM mirror (draft)
#  * BlockPool / PrefixIndex        paged KV cache (engine
#                                   cache_layout='paged'): block-pool
#                                   reservations, refcounted prefix
#                                   sharing + copy-on-write forks,
#                                   int8 K/V — more slots per HBM byte
#                                   (paged; device half in
#                                   ops/paged_attention.py)
#  * ContinuousBatchingScheduler    queue, admission (slot + block-pool
#                                   headroom), chunked-prefill
#                                   interleave, retirement
#  * CompileCache / bucket_length   per-bucket executables, hit/miss +
#                                   recompile accounting via the PR 1
#                                   RecompileWatchdog
#  * ServeMetrics                   TTFT / ITL / queue / occupancy /
#                                   acceptance-rate p50-p95 -> Tracer +
#                                   ResultLogger + serve.json
#                                   (flashy_tpu.info)
#
# `python -m flashy_tpu.serve` runs CPU smoke legs: staggered requests
# through a slot engine (plain, speculative, and chunked-prefill),
# outputs verified token-exact against per-request generate(), zero
# post-warm-up recompiles.
"""Continuous-batching serving: slot KV cache + speculative decoding."""

from .compile_cache import CompileCache, bucket_length  # noqa
from .draft import DraftProvider, ModelDraft, NGramDraft  # noqa
from .engine import (  # noqa
    DecodeEngine, SlotAllocator, SPAN_ADMIT, SPAN_DECODE, SPAN_PREFILL,
    SPAN_PREFILL_CHUNK, SPAN_VERIFY,
)
from .metrics import (  # noqa
    ServeMetrics, percentile, COUNTER_QUEUE, COUNTER_OCCUPANCY,
    COUNTER_ACCEPTANCE, COUNTER_POOL, COUNTER_PREFIX,
)
from .paged import (  # noqa
    BlockPool, PoolExhausted, PrefixIndex, POOL_FAULT_SITE,
)
from .scheduler import ContinuousBatchingScheduler, QueueFull, Request  # noqa

__all__ = [
    "DecodeEngine", "SlotAllocator", "ContinuousBatchingScheduler",
    "Request", "QueueFull", "CompileCache", "bucket_length", "ServeMetrics",
    "DraftProvider", "NGramDraft", "ModelDraft",
    "BlockPool", "PoolExhausted", "PrefixIndex", "POOL_FAULT_SITE",
    "percentile", "SPAN_DECODE", "SPAN_PREFILL", "SPAN_PREFILL_CHUNK",
    "SPAN_VERIFY", "COUNTER_QUEUE", "COUNTER_OCCUPANCY",
    "COUNTER_ACCEPTANCE", "COUNTER_POOL", "COUNTER_PREFIX",
]
