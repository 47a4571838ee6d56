# The serving smoke demo — `python -m flashy_tpu.serve`, mirroring
# `python -m flashy_tpu.info`'s role as a no-setup CLI. Runs the full
# stack on CPU with a tiny randomly-initialized TransformerLM in three
# legs, each an acceptance gate runnable anywhere in seconds:
#
#  * batching    staggered mixed-length requests through a slot engine,
#                token-exact vs per-request generate(), zero
#                post-warm-up recompiles.
#  * speculative the same contract under speculative decoding + chunked
#                prefill: greedy output must stay token-exact on
#                concurrent mixed-length requests WHATEVER the draft
#                proposed, the n-gram draft's acceptance rate must
#                clear a floor on the repetitive corpus, and admission,
#                chunked prefill, verify, and retirement together must
#                trigger zero post-warm-up compiles.
#  * chunked     a long prompt admitted mid-decode must not stall live
#                slots: every scheduler tick advances at most one chunk
#                of prefill AND the live request emits on every tick.
#  * paged       the block-pool KV cache: staggered requests sharing a
#                long common system prompt through a paged + int8
#                engine whose pool fits the DENSE cache budget of half
#                (or fewer) the slots — >= 2x concurrent slots per HBM
#                byte, token-exact vs generate(), prefix-hit-rate over
#                a floor, the pool conservation invariant held, and
#                zero post-warm-up compiles across admission,
#                prefix-hit, COW fork, decode, speculative verify and
#                retirement. By default every pool read runs the FUSED
#                Pallas paged-decode kernel (ops/paged_decode.py) in
#                interpret mode — the same gates, proven on the kernel
#                the TPU serves with (--kernel gather re-runs the XLA
#                reference path).
#  * ssd         the state-space mixer: a pure-SSD stack served with
#                cache_layout='ssd', whose per-slot decode state is ONE
#                fixed [H, Dh, Dstate] tensor instead of a
#                max_seq_len-long K/V slab. Gates: the chunked
#                (training) and recurrent (serving) forms agree on the
#                same inputs, streaming sessions run token-exact PAST
#                the engine's attention-layout max_seq_len ceiling vs
#                per-request generate(), zero post-warm-up compiles,
#                and state_bytes_per_slot stays CONSTANT across
#                max_seq_len in {1k, 8k, 64k} while a paged-int8
#                attention cache grows linearly — so at a fixed HBM
#                budget the SSD engine fits strictly more concurrent
#                slots than paged-int8 at 64k context.
#  * slo         the observability contract: the batching workload
#                served twice (tracing off, then RequestTracer at
#                sampling=1.0 + SLOEngine); every finished request must
#                be phase-attributable from requests.jsonl and the
#                Perfetto async spans, the healthy run must raise no
#                burn-rate alert while serve.json carries the slo
#                report, and full-rate tracing must stay within a
#                bounded ITL overhead of the untraced run.
"""`python -m flashy_tpu.serve`: CPU continuous-batching smoke demo."""
import argparse
import logging
import sys
import typing as tp

logger = logging.getLogger("flashy_tpu.serve.demo")

LEGS = ("batching", "speculative", "chunked", "paged", "ssd", "slo")


def _build_model(vocab: int, seed: int):
    import jax
    import jax.numpy as jnp
    from ..models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=vocab, dim=32, num_layers=2,
                            num_heads=4, attention="dense", max_seq_len=64,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))
    return model, params


def _request_mix(n: int, vocab: int, seed: int):
    """Deterministic mixed workload: (prompt, max_new_tokens) pairs with
    prompt lengths spanning several buckets."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lengths = [3, 4, 5, 7, 9, 12, 14, 17, 20, 24]
    news = [4, 6, 8, 10, 12]
    return [(rng.integers(0, vocab, rng.choice(lengths)).astype(np.int32),
             int(rng.choice(news))) for _ in range(n)]


def run_demo(requests: int = 32, slots: int = 8, verify: bool = True,
             seed: int = 0, max_queue: int = 64,
             stagger: int = 3, log: tp.Optional[logging.Logger] = None) -> int:
    """Serve `requests` staggered requests through a `slots`-slot engine.

    Returns 0 on success; 1 when verification or the compile-free
    steady-state check fails. `stagger` requests are submitted per
    scheduler step (continuous batching visibly refills freed slots
    mid-run instead of admitting one frozen batch).
    """
    import numpy as np
    from ..models.decoding import generate
    from .engine import DecodeEngine
    from .scheduler import ContinuousBatchingScheduler

    log = log or logger
    vocab = 64
    model, params = _build_model(vocab, seed)
    workload = _request_mix(requests, vocab, seed + 1)

    engine = DecodeEngine(model, params, slots=slots)
    log.info("warming %d-slot engine (buckets for prompt lengths %s)...",
             slots, sorted({len(p) for p, _ in workload}))
    engine.warmup(prompt_lengths=[len(p) for p, _ in workload])
    warm_stats = dict(engine.compile_cache.stats())

    scheduler = ContinuousBatchingScheduler(engine, max_queue=max_queue)
    handles = []
    pending = list(workload)
    steps = 0
    deferred = 0
    while pending or not scheduler.idle:
        # honor the scheduler's backpressure: a real client would map
        # QueueFull to retry-after; the demo defers to the next step
        # instead of submitting into a full queue.
        room = scheduler.max_queue - scheduler.queue_depth
        wanted = min(stagger, len(pending))
        deferred += max(0, wanted - room)
        for _ in range(min(wanted, room)):
            prompt, max_new = pending.pop(0)
            handles.append(scheduler.submit(prompt, max_new))
        scheduler.step()
        steps += 1
    if deferred:
        log.info("backpressure: %d submission attempts deferred to a "
                 "later step (queue at its %d-deep cap)", deferred,
                 scheduler.max_queue)

    stats = engine.compile_cache.stats()
    post_warm_builds = stats["misses"] - warm_stats["misses"]
    summary = scheduler.metrics.summary()
    log.info("served %d requests in %d steps: %s", len(handles), steps,
             ", ".join(f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in sorted(summary.items())))
    log.info("compile cache: %d executables, %d hits, %d misses "
             "(%d post-warm-up), %d recompiles", stats["entries"],
             stats["hits"], stats["misses"], post_warm_builds,
             stats["recompiles"])

    failures = 0
    if not all(h.done for h in handles):
        log.error("%d requests never finished",
                  sum(not h.done for h in handles))
        failures += 1
    if stats["recompiles"] != 0 or post_warm_builds != 0:
        log.error("steady state was not compile-free: %d recompiles, "
                  "%d post-warm-up builds", stats["recompiles"],
                  post_warm_builds)
        failures += 1
    if verify:
        mismatches = 0
        for handle in handles:
            want = np.asarray(generate(model, params, handle.prompt[None],
                                       max_new_tokens=handle.max_new_tokens))[0]
            if not np.array_equal(handle.output, want):
                mismatches += 1
                log.error("request %d diverged from generate():\n"
                          "  served   %s\n  generate %s", handle.uid,
                          handle.output.tolist(), want.tolist())
        if mismatches:
            failures += 1
        else:
            log.info("verified: all %d outputs token-exact against "
                     "per-request generate()", len(handles))
    return 1 if failures else 0


def _repetitive_mix(n: int, vocab: int, seed: int):
    """Mixed-length REPETITIVE workload for the speculative leg: each
    prompt tiles a short random pattern, the regime prompt-lookup
    drafting exists for (templated text, code, retrieval-stuffed
    prompts). Token-exactness holds for ANY workload — repetition only
    buys a meaningful acceptance rate to assert a floor on."""
    import numpy as np
    rng = np.random.default_rng(seed)
    # generations long enough that the steady-state (where lookup
    # shines) dominates the per-request transient
    lengths = [4, 6, 9, 12, 15]
    news = [16, 20, 24]
    out = []
    for _ in range(n):
        period = int(rng.integers(2, 5))
        pattern = rng.integers(0, vocab, period).astype(np.int32)
        length = int(rng.choice(lengths))
        prompt = np.tile(pattern, length // period + 1)[:length]
        out.append((prompt, int(rng.choice(news))))
    return out


def run_spec_demo(requests: int = 16, slots: int = 4, k: int = 4,
                  chunk: int = 8, draft_kind: str = "ngram",
                  accept_floor: float = 0.2, seed: int = 0,
                  log: tp.Optional[logging.Logger] = None) -> int:
    """Speculative decoding + chunked prefill acceptance gate.

    Serves a repetitive mixed-length workload through a chunked-prefill
    engine with a draft provider; exits 1 unless every output is
    token-exact vs per-request `generate()`, the acceptance rate clears
    `accept_floor`, and admission + chunked prefill + verify +
    retirement together cause zero post-warm-up compiles.
    """
    import numpy as np
    from ..models.decoding import generate
    from .draft import ModelDraft, NGramDraft
    from .engine import DecodeEngine
    from .scheduler import ContinuousBatchingScheduler

    log = log or logger
    vocab = 64
    model, params = _build_model(vocab, seed)
    workload = _repetitive_mix(requests, vocab, seed + 1)

    engine = DecodeEngine(model, params, slots=slots, spec_k=k, chunk=chunk)
    if draft_kind == "ngram":
        draft: tp.Any = NGramDraft(slots=slots, k=k, ngram=3)
    elif draft_kind == "model":
        # a half-size draft LM sharing the vocabulary (random init —
        # its acceptance is poor, which is exactly the point: output
        # must stay exact even under a bad draft; use --accept-floor 0)
        import jax
        import jax.numpy as jnp
        from ..models import TransformerConfig, TransformerLM
        dcfg = TransformerConfig(vocab_size=vocab, dim=16, num_layers=1,
                                 num_heads=2, attention="dense",
                                 max_seq_len=64, dtype=jnp.float32)
        dmodel = TransformerLM(dcfg)
        dparams = dmodel.init(jax.random.PRNGKey(seed + 13),
                              jnp.ones((1, 8), jnp.int32))
        draft = ModelDraft(dmodel, dparams, slots=slots, k=k)
        draft.warmup(prompt_lengths=[len(p) for p, _ in workload])
    else:
        raise ValueError(f"unknown draft kind {draft_kind!r}")

    log.info("speculative leg: warming %d-slot engine (k=%d, chunk=%d, "
             "%s draft)...", slots, k, chunk, draft_kind)
    engine.warmup()
    warm_misses = engine.compile_cache.stats()["misses"]

    scheduler = ContinuousBatchingScheduler(engine, draft=draft)
    handles = []
    pending = list(workload)
    steps = 0
    while pending or not scheduler.idle:
        room = scheduler.max_queue - scheduler.queue_depth
        for _ in range(min(2, len(pending), room)):
            prompt, max_new = pending.pop(0)
            handles.append(scheduler.submit(prompt, max_new))
        scheduler.step()
        steps += 1

    stats = engine.compile_cache.stats()
    post_warm_builds = stats["misses"] - warm_misses
    summary = scheduler.metrics.summary()
    log.info("speculative leg: %d requests in %d steps, acceptance "
             "%.0f%% (%d drafted -> %d emitted), accepted/step "
             "p50=%.1f p95=%.1f, itl p95 %.2fms",
             len(handles), steps, summary["acceptance_rate"] * 100,
             summary["spec_drafted"], summary["spec_emitted"],
             summary["accepted_per_step_p50"],
             summary["accepted_per_step_p95"], summary["itl_ms_p95"])

    failures = 0
    if not all(h.done for h in handles):
        log.error("%d requests never finished",
                  sum(not h.done for h in handles))
        failures += 1
    if stats["recompiles"] != 0 or post_warm_builds != 0:
        log.error("speculative steady state was not compile-free: %d "
                  "recompiles, %d post-warm-up builds (admission + "
                  "chunked prefill + verify + retirement must all hit "
                  "warmed shapes)", stats["recompiles"], post_warm_builds)
        failures += 1
    mismatches = 0
    for handle in handles:
        want = np.asarray(generate(model, params, handle.prompt[None],
                                   max_new_tokens=handle.max_new_tokens))[0]
        if not np.array_equal(handle.output, want):
            mismatches += 1
            log.error("request %d diverged from generate() under "
                      "speculation:\n  served   %s\n  generate %s",
                      handle.uid, handle.output.tolist(), want.tolist())
    if mismatches:
        failures += 1
    else:
        log.info("verified: all %d speculative outputs token-exact "
                 "against per-request generate()", len(handles))
    if summary["acceptance_rate"] < accept_floor:
        log.error("acceptance rate %.2f below the %.2f floor — the "
                  "draft is not earning its verify step on this corpus",
                  summary["acceptance_rate"], accept_floor)
        failures += 1
    return 1 if failures else 0


def run_chunked_demo(chunk: int = 8, seed: int = 0,
                     log: tp.Optional[logging.Logger] = None) -> int:
    """Chunked-prefill stall-bound gate: a long prompt admitted while
    another slot is mid-decode must cost live slots at most one chunk
    of prefill per tick — asserted structurally (prompt tokens advanced
    per step <= chunk AND the live request emits on every tick of the
    admission window) — and stay token-exact; exit 1 otherwise."""
    import time

    import numpy as np
    from ..models.decoding import generate
    from .engine import DecodeEngine
    from .scheduler import ContinuousBatchingScheduler

    log = log or logger
    vocab = 64
    model, params = _build_model(vocab, seed)
    rng = np.random.default_rng(seed + 2)

    engine = DecodeEngine(model, params, slots=2, chunk=chunk)
    log.info("chunked leg: warming 2-slot engine (chunk=%d)...", chunk)
    engine.warmup()
    warm_misses = engine.compile_cache.stats()["misses"]
    scheduler = ContinuousBatchingScheduler(engine)

    short = scheduler.submit(rng.integers(0, vocab, 4).astype(np.int32),
                             max_new_tokens=24)
    for _ in range(3):  # the short request is actively decoding...
        scheduler.step()
    long_prompt = rng.integers(0, vocab, 5 * chunk).astype(np.int32)
    long = scheduler.submit(long_prompt, max_new_tokens=4)

    # ...when the long prompt lands: every tick of its prefill window
    # must advance <= chunk prompt tokens AND still emit for the short
    # request (the stall bound: one chunk's compute, not one prompt's).
    failures = 0
    ticks = 0
    stalls = []
    while long.state in ("queued", "prefilling"):
        before = len(short.generated)
        tick_start = time.perf_counter()
        scheduler.step()
        stalls.append(time.perf_counter() - tick_start)
        ticks += 1
        if scheduler.prefill_tokens_last_step > chunk:
            log.error("tick advanced %d prompt tokens > chunk %d",
                      scheduler.prefill_tokens_last_step, chunk)
            failures += 1
        if short.done:
            break
        if len(short.generated) <= before:
            log.error("live request stalled on tick %d of the long "
                      "prompt's prefill window", ticks)
            failures += 1
    scheduler.run()

    stats = engine.compile_cache.stats()
    post_warm_builds = stats["misses"] - warm_misses
    expected_ticks = -(-long_prompt.size // chunk)  # ceil
    log.info("chunked leg: %d-token prompt prefilled over %d ticks "
             "(expected >= %d), live slot kept emitting, max tick "
             "%.2fms, max prefill tokens/step %d (chunk %d)",
             long_prompt.size, ticks, expected_ticks,
             max(stalls) * 1e3 if stalls else 0.0,
             scheduler.max_prefill_tokens_per_step, chunk)
    if ticks < expected_ticks:
        log.error("prefill finished in %d ticks < %d — chunks were not "
                  "interleaved one per step", ticks, expected_ticks)
        failures += 1
    if scheduler.max_prefill_tokens_per_step > chunk:
        log.error("max prefill tokens per step %d exceeds chunk %d",
                  scheduler.max_prefill_tokens_per_step, chunk)
        failures += 1
    if stats["recompiles"] != 0 or post_warm_builds != 0:
        log.error("chunked steady state was not compile-free: %d "
                  "recompiles, %d post-warm-up builds",
                  stats["recompiles"], post_warm_builds)
        failures += 1
    for handle, name in ((short, "short"), (long, "long")):
        want = np.asarray(generate(model, params, handle.prompt[None],
                                   max_new_tokens=handle.max_new_tokens))[0]
        if not np.array_equal(handle.output, want):
            log.error("%s request diverged from generate():\n"
                      "  served   %s\n  generate %s", name,
                      handle.output.tolist(), want.tolist())
            failures += 1
    if not failures:
        log.info("verified: chunked admission mid-decode stayed "
                 "token-exact with the stall bound held")
    return 1 if failures else 0


def run_paged_demo(requests: int = 32, dense_slots: int = 4,
                   paged_slots: int = 16, block_size: int = 8, k: int = 4,
                   prefix_floor: float = 0.25, stagger: int = 4,
                   seed: int = 0, kernel: str = "fused",
                   log: tp.Optional[logging.Logger] = None) -> int:
    """Paged KV cache acceptance gate: more slots per HBM byte, exactly.

    Sizes an int8 block pool to the DENSE cache budget of
    `dense_slots` slots, then serves `requests` staggered requests
    sharing a long common system prompt through `paged_slots` (>= 2x)
    concurrent slots — phase A under plain decode, phase B under
    speculative verify on the same engine, so admission, prefix-hit,
    COW fork, decode, verify and retirement all run against one warmed
    executable set. Exits 1 unless every output is token-exact vs
    per-request `generate()`, the prefix-hit-rate clears
    `prefix_floor`, at least `2 * dense_slots` slots were live at
    once inside the dense budget, the pool conservation invariant
    holds (never over-committed), and zero executables were built
    post-warm-up.

    `kernel='fused'` (the default — what `make serve-paged-demo`
    gates) routes every pool read through the Pallas paged-decode
    kernel, interpret mode on CPU: the same token-exactness +
    zero-post-warm-up-build bar, now proven on the fused read path
    across admission, prefix-hit, COW, decode, verify and retirement.
    `kernel='gather'` re-runs the leg on the XLA reference path.

    The workload is screened to requests whose greedy argmax survives
    int8 K/V noise: a RANDOM-INIT model's logits carry near-ties far
    below the <= 0.8% quantization error, a regime trained models'
    margins dominate — the screen runs per-request (no sharing), so
    the cohort gate still proves what it claims: paging + prefix
    sharing + COW + int8 change nothing the screen didn't already
    accept about each request in isolation.
    """
    import jax
    import numpy as np
    from ..models.decoding import generate
    from ..ops.paged_attention import block_bytes
    from .draft import NGramDraft
    from .engine import DecodeEngine
    from .scheduler import ContinuousBatchingScheduler

    log = log or logger
    vocab = 64
    model, params = _build_model(vocab, seed)
    cfg = model.config
    rng = np.random.default_rng(seed + 3)
    # a system prompt whose length is NOT a multiple of block_size, so
    # every repeat exercises the copy-on-write fork of the partially
    # shared block, not just full-block refcount bumps
    system = rng.integers(0, vocab, 2 * block_size + block_size // 2 + 1
                          ).astype(np.int32)

    dense = DecodeEngine(model, params, slots=dense_slots,
                         cache_scope="densebudget")
    budget = dense.cache_bytes()
    per_block = block_bytes(cfg, block_size, "int8")
    num_blocks = budget // per_block
    engine = DecodeEngine(model, params, slots=paged_slots,
                          cache_layout="paged", block_size=block_size,
                          num_blocks=num_blocks, kv_dtype="int8",
                          kernel=kernel, spec_k=k)
    paged_bytes = engine.cache_bytes()
    log.info("paged leg (%s kernel%s): dense budget = %d slots x %d "
             "tokens = %.0f KiB; same budget paged+int8 = %d blocks x "
             "%d tokens -> %d slots (%.1fx), %.0f KiB",
             engine.kernel,
             ", interpret mode" if engine.kernel == "fused"
             and jax.default_backend() == "cpu" else "",
             dense_slots, dense.max_seq_len, budget / 1024,
             num_blocks - 1, block_size, paged_slots,
             paged_slots / dense_slots, paged_bytes / 1024)

    # --- workload: shared system prompt + per-request tail, screened
    # for int8-argmax-safe requests (per-request, sharing disabled;
    # SAME kernel as the serving engine, so the screen accepts exactly
    # what the gated path will compute)
    screen = DecodeEngine(model, params, slots=1, cache_layout="paged",
                          block_size=block_size, kv_dtype="int8",
                          kernel=kernel, prefix_cache=False,
                          cache_scope="screen")
    screen.warmup()
    screen_sched = ContinuousBatchingScheduler(screen)
    workload = []
    tried = 0
    while len(workload) < requests and tried < requests * 4:
        tried += 1
        tail = rng.integers(0, vocab, int(rng.integers(3, block_size))
                            ).astype(np.int32)
        prompt = np.concatenate([system, tail])
        max_new = int(rng.integers(6, 13))
        handle = screen_sched.submit(prompt, max_new)
        screen_sched.run()
        want = np.asarray(generate(model, params, prompt[None],
                                   max_new_tokens=max_new))[0]
        if np.array_equal(handle.output, want):
            workload.append((prompt, max_new, want))
    if len(workload) < requests:
        log.error("screen kept only %d/%d requests — int8 argmax noise "
                  "dominates this init; pick another seed", len(workload),
                  requests)
        return 1
    log.info("screened workload: kept %d int8-argmax-safe requests out "
             "of %d candidates", len(workload), tried)

    log.info("warming %d-slot paged engine (block_size=%d, int8 K/V, "
             "spec_k=%d)...", paged_slots, block_size, k)
    engine.warmup()
    warm_misses = engine.compile_cache.stats()["misses"]

    # --- phase A: plain decode; phase B: speculative verify — one
    # engine, one executable set, one prefix cache across both
    peak_live = 0
    handles: tp.List[tp.Any] = []

    def serve_phase(batch, draft):
        nonlocal peak_live
        scheduler = ContinuousBatchingScheduler(engine, draft=draft)
        pending = list(batch)
        while pending or not scheduler.idle:
            room = scheduler.max_queue - scheduler.queue_depth
            for _ in range(min(stagger, len(pending), room)):
                prompt, max_new, _ = pending.pop(0)
                handles.append(scheduler.submit(prompt, max_new))
            scheduler.step()
            peak_live = max(peak_live, engine.live_count)
        return scheduler

    half = len(workload) // 2
    sched_a = serve_phase(workload[:half], draft=None)
    sched_b = serve_phase(workload[half:],
                          draft=NGramDraft(slots=paged_slots, k=k, ngram=3))

    stats = engine.compile_cache.stats()
    post_warm_builds = stats["misses"] - warm_misses
    pool = engine.pool_stats()
    summary_a = sched_a.metrics.summary()
    summary_b = sched_b.metrics.summary()
    log.info("paged leg: %d requests (%d plain + %d speculative), "
             "prefix hit rate %.0f%%, %d COW forks, %d evictions, peak "
             "%d/%d blocks, peak %d live slots, pool occupancy p95 "
             "%.0f%%/%.0f%%", len(handles), half, len(workload) - half,
             pool["prefix_hit_rate"] * 100, pool["cow_forks"],
             pool["evictions"], pool["peak_in_use"], pool["capacity"],
             peak_live, summary_a.get("pool_occupancy_p95", 0.0) * 100,
             summary_b.get("pool_occupancy_p95", 0.0) * 100)
    log.info("compile cache: %d executables, %d post-warm-up builds, "
             "%d recompiles", stats["entries"], post_warm_builds,
             stats["recompiles"])

    failures = 0
    if not all(h.done for h in handles):
        log.error("%d requests never finished",
                  sum(not h.done for h in handles))
        failures += 1
    mismatches = 0
    for handle, (_, _, want) in zip(handles, workload):
        if not np.array_equal(handle.output, want):
            mismatches += 1
            log.error("request %d diverged from generate() on the paged "
                      "int8 layout:\n  served   %s\n  generate %s",
                      handle.uid, handle.output.tolist(), want.tolist())
    if mismatches:
        failures += 1
    else:
        log.info("verified: all %d outputs token-exact against "
                 "per-request generate() (paged + prefix sharing + COW "
                 "+ int8 K/V)", len(handles))
    if stats["recompiles"] != 0 or post_warm_builds != 0:
        log.error("paged steady state was not compile-free: %d "
                  "recompiles, %d post-warm-up builds (admission, "
                  "prefix-hit, COW fork, decode, verify and retirement "
                  "must all hit warmed shapes)", stats["recompiles"],
                  post_warm_builds)
        failures += 1
    if pool["prefix_hit_rate"] < prefix_floor:
        log.error("prefix hit rate %.2f below the %.2f floor — the "
                  "shared system prompt was re-prefilled",
                  pool["prefix_hit_rate"], prefix_floor)
        failures += 1
    if pool["cow_forks"] < 1:
        log.error("no COW fork happened — the partially-shared block "
                  "path was never exercised")
        failures += 1
    if peak_live < 2 * dense_slots:
        log.error("peak concurrency %d never reached 2x the dense "
                  "budget's %d slots", peak_live, dense_slots)
        failures += 1
    if paged_bytes > budget:
        log.error("paged pool (%d bytes) exceeds the dense budget "
                  "(%d bytes)", paged_bytes, budget)
        failures += 1
    try:
        engine._pool.check()
    except AssertionError as exc:
        log.error("pool conservation violated: %s", exc)
        failures += 1
    if not failures:
        log.info("verified: %dx concurrent slots inside the dense HBM "
                 "budget, pool never over-committed",
                 peak_live // dense_slots)
    return 1 if failures else 0


def run_ssd_demo(requests: int = 6, slots: int = 4, chunk: int = 8,
                 ceiling: int = 64, seed: int = 0,
                 log: tp.Optional[logging.Logger] = None) -> int:
    """SSD mixer acceptance gate: constant-memory long-context decode.

    Builds a pure-SSD TransformerLM (every mixer a state-space layer,
    `ssd_chunk` pinned to the engine's prefill chunk so engine chunking
    is bit-identical to generate()'s whole-prompt call) and serves
    streaming sessions through a `cache_layout='ssd'` engine whose
    max_seq_len is a deliberately SMALL attention-layout ceiling.
    Exits 1 unless:

      * the chunked (training) and recurrent (serving) forms agree on
        identical inputs — the state-space duality the subsystem is
        named for, asserted directly at the ops layer;
      * every session streams token-exact vs per-request generate()
        to final positions PAST the ceiling (the O(1) state makes
        max_seq_len a prefill-chunking parameter, not a wall);
      * admission, chunked prefill, decode and retirement trigger zero
        post-warm-up compiles;
      * `state_bytes_per_slot` is CONSTANT across max_seq_len in
        {1k, 8k, 64k} while the paged-int8 attention layout grows
        linearly, and at the 64k paged pool's HBM budget the SSD
        layout fits strictly more concurrent slots — and the same
        number is what `ServeMetrics.static_info` publishes to
        serve.json.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models import TransformerConfig, TransformerLM
    from ..models.decoding import generate
    from ..ops.ssd_scan import ssd_chunked_scan, ssd_recurrent_scan
    from .engine import DecodeEngine, state_bytes_per_slot
    from .scheduler import ContinuousBatchingScheduler

    log = log or logger
    vocab = 64
    cfg = TransformerConfig(vocab_size=vocab, dim=32, num_layers=2,
                            num_heads=4, attention="dense",
                            max_seq_len=4096, dtype=jnp.float32,
                            mixer="ssd", ssd_state_dim=8, ssd_chunk=chunk)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))
    rng = np.random.default_rng(seed + 5)
    failures = 0

    # --- gate 1: state-space duality, asserted at the ops layer. One
    # random sequence, model-scale shapes: the chunked form (intra-chunk
    # dense matmuls + inter-chunk f32 carry) and the recurrent form
    # (one [H, Dh, Dstate] state advanced per token) must agree.
    b_, t_, h_, dh_, n_ = 2, 3 * chunk + 5, cfg.num_heads, cfg.head_dim, 8
    key = jax.random.PRNGKey(seed + 7)
    kc, kb, kv, ka = jax.random.split(key, 4)
    c = jax.random.normal(kc, (b_, t_, h_, n_), jnp.float32)
    bq = jax.random.normal(kb, (b_, t_, h_, n_), jnp.float32)
    v = jax.random.normal(kv, (b_, t_, h_, dh_), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ka, (b_, t_, h_), jnp.float32))
    y_chunk, s_chunk = ssd_chunked_scan(c, bq, v, la, chunk=chunk)
    y_rec, s_rec = ssd_recurrent_scan(c, bq, v, la,
                                      jnp.zeros((b_, h_, dh_, n_),
                                                jnp.float32))
    err_y = float(jnp.max(jnp.abs(y_chunk - y_rec)))
    err_s = float(jnp.max(jnp.abs(s_chunk - s_rec)))
    log.info("ssd leg: dual-form parity on [%d, %d] tokens: max |dy| "
             "%.2e, max |dstate| %.2e", b_, t_, err_y, err_s)
    if err_y > 1e-4 or err_s > 1e-4:
        log.error("chunked and recurrent SSD forms diverged — the "
                  "duality the serving path depends on does not hold")
        failures += 1

    # --- gate 2+3: streaming sessions past the ceiling, token-exact,
    # compile-free. The engine's max_seq_len is the ceiling an
    # attention layout would enforce; pure-SSD engines are unbounded.
    engine = DecodeEngine(model, params, slots=slots, chunk=chunk,
                          max_seq_len=ceiling, cache_layout="ssd")
    assert engine.unbounded, "pure-SSD engine must report unbounded"
    log.info("ssd leg: warming %d-slot ssd engine (chunk=%d, ceiling "
             "%d tokens, %d state bytes/slot)...", slots, chunk,
             ceiling, engine.state_bytes_per_slot())
    engine.warmup()
    warm_misses = engine.compile_cache.stats()["misses"]

    scheduler = ContinuousBatchingScheduler(engine)
    published = scheduler.metrics.static_info.get("state_bytes_per_slot")
    if published != engine.state_bytes_per_slot():
        log.error("static_info publishes state_bytes_per_slot=%s, "
                  "engine says %d", published,
                  engine.state_bytes_per_slot())
        failures += 1

    # every session's final position clears the ceiling: long
    # generations on mixed prompts, staggered admission
    workload = []
    for i in range(requests):
        plen = int(rng.integers(5, 3 * chunk))
        max_new = ceiling - plen + int(rng.integers(8, 33))
        workload.append((rng.integers(0, vocab, plen).astype(np.int32),
                         max_new))
    handles = []
    pending = list(workload)
    while pending or not scheduler.idle:
        room = scheduler.max_queue - scheduler.queue_depth
        for _ in range(min(2, len(pending), room)):
            prompt, max_new = pending.pop(0)
            handles.append(scheduler.submit(prompt, max_new))
        scheduler.step()

    stats = engine.compile_cache.stats()
    post_warm_builds = stats["misses"] - warm_misses
    finals = [len(p) + n for p, n in workload]
    log.info("ssd leg: %d sessions streamed to final positions %s "
             "(ceiling %d); compile cache: %d executables, %d "
             "post-warm-up builds, %d recompiles", len(handles),
             sorted(finals), ceiling, stats["entries"],
             post_warm_builds, stats["recompiles"])
    if not all(h.done for h in handles):
        log.error("%d sessions never finished",
                  sum(not h.done for h in handles))
        failures += 1
    if min(finals) <= ceiling:
        log.error("a session ended at position %d <= the %d ceiling — "
                  "the leg did not prove streaming past it",
                  min(finals), ceiling)
        failures += 1
    if stats["recompiles"] != 0 or post_warm_builds != 0:
        log.error("ssd steady state was not compile-free: %d "
                  "recompiles, %d post-warm-up builds",
                  stats["recompiles"], post_warm_builds)
        failures += 1
    mismatches = 0
    for handle in handles:
        want = np.asarray(generate(model, params, handle.prompt[None],
                                   max_new_tokens=handle.max_new_tokens))[0]
        if not np.array_equal(handle.output, want):
            mismatches += 1
            log.error("session %d diverged from generate() past the "
                      "ceiling:\n  served   %s\n  generate %s",
                      handle.uid, handle.output.tolist(), want.tolist())
    if mismatches:
        failures += 1
    else:
        log.info("verified: all %d streaming sessions token-exact "
                 "against per-request generate() past the %d-token "
                 "ceiling", len(handles), ceiling)

    # --- gate 4: O(1) state. Host arithmetic over the SAME accounting
    # `static_info` publishes: SSD state bytes must not move with
    # max_seq_len while paged-int8 attention grows linearly, and the
    # 64k paged budget must buy MORE ssd slots than paged slots.
    attn_cfg = TransformerConfig(vocab_size=vocab, dim=32, num_layers=2,
                                 num_heads=4, attention="dense",
                                 max_seq_len=65536, dtype=jnp.float32)
    lens = (1024, 8192, 65536)
    ssd_bytes = [state_bytes_per_slot(cfg, n, "ssd") for n in lens]
    paged_bytes = [state_bytes_per_slot(attn_cfg, n, "paged",
                                        kv_dtype="int8", block_size=16)
                   for n in lens]
    log.info("ssd leg: state bytes/slot across max_seq_len %s: ssd %s "
             "(constant), paged-int8 %s (linear)", lens, ssd_bytes,
             paged_bytes)
    if len(set(ssd_bytes)) != 1:
        log.error("ssd state bytes/slot moved with max_seq_len: %s — "
                  "the O(1) contract is broken", ssd_bytes)
        failures += 1
    if not (paged_bytes[0] < paged_bytes[1] < paged_bytes[2]):
        log.error("paged-int8 bytes/slot %s are not growing with "
                  "max_seq_len — the comparison baseline is wrong",
                  paged_bytes)
        failures += 1
    budget = 16 * paged_bytes[-1]  # 16 paged slots' worth of HBM at 64k
    ssd_slots = budget // ssd_bytes[-1]
    log.info("ssd leg: a %d-slot paged-int8 budget at 64k context "
             "(%.1f MiB) holds %d ssd slots (%.0fx)", 16,
             budget / 2**20, ssd_slots, ssd_slots / 16)
    if ssd_slots <= 16:
        log.error("ssd fits only %d slots in the 16-slot paged budget "
                  "— no capacity win", ssd_slots)
        failures += 1
    if not failures:
        log.info("verified: dual-form parity, token-exact streaming "
                 "past the ceiling, compile-free steady state, O(1) "
                 "state bytes per slot")
    return 1 if failures else 0


def run_slo_demo(requests: int = 24, slots: int = 8, stagger: int = 3,
                 overhead_factor: float = 2.0, seed: int = 0,
                 log: tp.Optional[logging.Logger] = None) -> int:
    """SLO + request-tracing acceptance gate.

    Serves the batching workload twice — tracing OFF (baseline), then
    tracing ON at sampling=1.0 with an SLOEngine attached — and exits 1
    unless: the healthy run raises NO burn-rate alert and its
    `serve.json` carries the `slo` report block; EVERY finished request
    is attributable from `requests.jsonl` to named phases (queue wait /
    prefill / decode) and from the Perfetto trace's async spans; both
    runs stay compile-free post-warm-up; and full-rate tracing costs at
    most `overhead_factor` x the untraced ITL p50 (+2ms CPU-noise
    floor) — observability that slows the service down is a regression,
    not a feature.
    """
    import json
    import tempfile
    from pathlib import Path

    from ..observability import SLOEngine, Tracer, format_slo_report
    from ..xp import REQUESTS_NAME, SERVE_STATUS_NAME, TRACE_NAME
    from .engine import DecodeEngine
    from .metrics import ServeMetrics
    from .scheduler import ContinuousBatchingScheduler
    from .tracing import (RequestTracer, SPAN_DECODE, SPAN_PREFILL,
                          SPAN_QUEUED, SPAN_REQUEST)

    log = log or logger
    vocab = 64
    model, params = _build_model(vocab, seed)
    workload = _request_mix(requests, vocab, seed + 1)

    def serve_pass(tracer, tracing, slo):
        engine = DecodeEngine(model, params, slots=slots, tracer=tracer,
                              cache_scope="traced" if tracer else "plain")
        engine.warmup(prompt_lengths=[len(p) for p, _ in workload])
        warm_misses = engine.compile_cache.stats()["misses"]
        metrics = ServeMetrics(tracer=tracer, slo=slo)
        scheduler = ContinuousBatchingScheduler(engine, metrics=metrics,
                                                tracing=tracing)
        handles = []
        pending = list(workload)
        while pending or not scheduler.idle:
            room = scheduler.max_queue - scheduler.queue_depth
            for _ in range(min(stagger, len(pending), room)):
                prompt, max_new = pending.pop(0)
                handles.append(scheduler.submit(prompt, max_new))
            scheduler.step()
        stats = engine.compile_cache.stats()
        return (handles, scheduler,
                stats["recompiles"], stats["misses"] - warm_misses)

    failures = 0
    log.info("slo leg: baseline pass (tracing off)...")
    base_handles, base_sched, base_rec, base_builds = serve_pass(
        None, None, None)
    base_itl = base_sched.metrics.summary()["itl_ms_p50"]

    log.info("slo leg: traced pass (sampling=1.0, SLO engine attached)...")
    with tempfile.TemporaryDirectory() as tmp:
        folder = Path(tmp)
        tracer = Tracer(trace_path=folder / TRACE_NAME)
        tracing = RequestTracer(tracer=tracer,
                                journal_path=folder / REQUESTS_NAME,
                                sample_rate=1.0)
        slo = SLOEngine(tracer=tracer)
        handles, sched, recompiles, builds = serve_pass(tracer, tracing, slo)
        traced_itl = sched.metrics.summary()["itl_ms_p50"]
        sched.metrics.write_status(folder)
        tracing.close()
        tracer.close()

        if not all(h.done for h in base_handles + handles):
            log.error("requests never finished")
            failures += 1
        if base_rec or base_builds or recompiles or builds:
            log.error("steady state was not compile-free (baseline "
                      "%d/%d, traced %d/%d recompiles/builds) — tracing "
                      "must not perturb shapes", base_rec, base_builds,
                      recompiles, builds)
            failures += 1

        # --- SLO gate: report present, silent on the healthy run
        with open(folder / SERVE_STATUS_NAME) as f:
            status = json.load(f)
        report = status.get("slo")
        if not report or not report.get("budgets"):
            log.error("serve.json carries no slo report block")
            failures += 1
        elif report["alerting"]:
            log.error("burn-rate alert fired on a healthy run:\n%s",
                      format_slo_report(report))
            failures += 1
        else:
            log.info("slo report (healthy, no alert):\n%s",
                     format_slo_report(report))

        # --- attribution gate: every finished uid has a journal line
        # with its named phases, and async spans in the trace
        finished: tp.Dict[int, tp.Dict[str, tp.Any]] = {}
        with open(folder / REQUESTS_NAME) as f:
            for line in f:
                event = json.loads(line)
                if event.get("event") == "finished":
                    finished[event["uid"]] = event
        for handle in handles:
            event = finished.get(handle.uid)
            if event is None:
                log.error("request %d finished but has no requests.jsonl "
                          "summary", handle.uid)
                failures += 1
            elif not {"queue_wait_s", "latency_s"} <= set(event):
                log.error("request %d summary lacks phase attribution: %s",
                          handle.uid, event)
                failures += 1
        spans = {}
        with open(folder / TRACE_NAME) as f:
            for event in json.load(f)["traceEvents"]:
                if event.get("ph") in ("b", "e"):
                    key = (event["name"], event["id"], event["ph"])
                    spans[key] = spans.get(key, 0) + 1
        for handle in handles:
            uid = f"0x{handle.uid:x}"
            for name in (SPAN_REQUEST, SPAN_QUEUED, SPAN_PREFILL,
                         SPAN_DECODE):
                opened = spans.get((name, uid, "b"), 0)
                closed = spans.get((name, uid, "e"), 0)
                if name == SPAN_REQUEST and (opened != 1 or closed != 1):
                    log.error("request %d: %s opened %d / closed %d times",
                              handle.uid, name, opened, closed)
                    failures += 1
                elif opened != closed:
                    log.error("request %d: unbalanced %s spans (%d open, "
                              "%d close)", handle.uid, name, opened, closed)
                    failures += 1

    # --- overhead gate: full-rate tracing must stay cheap
    bound = base_itl * overhead_factor + 2.0
    log.info("slo leg: itl p50 %.3fms untraced vs %.3fms traced at "
             "sampling=1.0 (bound %.3fms)", base_itl, traced_itl, bound)
    if traced_itl > bound:
        log.error("tracing overhead blew the bound: %.3fms > %.3fms",
                  traced_itl, bound)
        failures += 1
    if not failures:
        log.info("verified: SLO report healthy, every request phase-"
                 "attributable from requests.jsonl + Perfetto, tracing "
                 "overhead bounded")
    return 1 if failures else 0


def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m flashy_tpu.serve",
        description="Continuous-batching serving smoke demo (CPU).")
    parser.add_argument("-n", "--requests", type=int, default=32)
    parser.add_argument("-s", "--slots", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--stagger", type=int, default=3,
                        help="requests submitted per scheduler step")
    parser.add_argument("--max-queue", type=int, default=64,
                        help="admission queue depth (submissions past it "
                             "are deferred — the backpressure path)")
    parser.add_argument("--no-verify", dest="verify", action="store_false",
                        help="skip the per-request generate() comparison")
    parser.add_argument("--legs", default="all",
                        help="comma list of legs to run: "
                             f"{','.join(LEGS)} (or 'all')")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="tokens drafted per speculative step")
    parser.add_argument("--chunk", type=int, default=8,
                        help="prefill chunk size (speculative + chunked "
                             "legs)")
    parser.add_argument("--draft", default="ngram",
                        choices=("ngram", "model"),
                        help="draft provider for the speculative leg")
    parser.add_argument("--accept-floor", type=float, default=0.2,
                        help="minimum acceptance rate the speculative "
                             "leg must clear (use 0 with --draft model: "
                             "a random-init draft proposes noise)")
    parser.add_argument("--prefix-floor", type=float, default=0.25,
                        help="minimum prefix-cache hit rate the paged "
                             "leg must clear on its shared-system-"
                             "prompt workload")
    parser.add_argument("--kernel", default="fused",
                        choices=("gather", "fused"),
                        help="paged pool read path for the paged leg: "
                             "the fused Pallas kernel (interpret mode "
                             "on CPU; the default and the CI gate) or "
                             "the XLA gather reference")
    args = parser.parse_args(argv)

    legs = LEGS if args.legs == "all" else tuple(args.legs.split(","))
    unknown = set(legs) - set(LEGS)
    if unknown:
        parser.error(f"unknown legs: {sorted(unknown)} (choose from {LEGS})")

    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="[%(levelname)s] %(message)s")
    from ..utils import configure_compile_cache
    configure_compile_cache()
    rc = 0
    if "batching" in legs:
        rc |= run_demo(requests=args.requests, slots=args.slots,
                       verify=args.verify, seed=args.seed,
                       stagger=args.stagger, max_queue=args.max_queue)
    if "speculative" in legs:
        rc |= run_spec_demo(requests=max(4, args.requests // 2),
                            slots=max(2, args.slots // 2), k=args.spec_k,
                            chunk=args.chunk, draft_kind=args.draft,
                            accept_floor=args.accept_floor, seed=args.seed)
    if "chunked" in legs:
        rc |= run_chunked_demo(chunk=args.chunk, seed=args.seed)
    if "paged" in legs:
        rc |= run_paged_demo(requests=args.requests,
                             k=args.spec_k, seed=args.seed,
                             prefix_floor=args.prefix_floor,
                             kernel=args.kernel)
    if "ssd" in legs:
        rc |= run_ssd_demo(chunk=args.chunk, seed=args.seed)
    if "slo" in legs:
        rc |= run_slo_demo(requests=max(8, args.requests // 2),
                           slots=args.slots, stagger=args.stagger,
                           seed=args.seed)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
