# Runner `serve_engine`: the serving user's path. TransformerLM ->
# DecodeEngine (the cell's `engine` parameters) -> warmup() ->
# ContinuousBatchingScheduler, driven by closed-loop clients: each sends
# its next request the moment its last one completes, so the offered
# load follows the engine's speed and the numbers stay meaningful at
# today's speed and after a tenfold gain.
#
# Clocks: one host clock (time.perf_counter, the scheduler's own). A
# request's tokens are stamped when the `scheduler.step()` that produced
# them returns — what a streaming client would see — and the gaps come
# from those stamps, not from `ServeMetrics.itl`, whose clock starts
# after admission and so leaves the prefill slice out of the gap.
"""Closed-loop serving driver over DecodeEngine + the scheduler."""
import dataclasses
import time
import typing as tp

from ..harness import model as model_lib, reference
from ..harness.trace import timed


class Tick(tp.NamedTuple):
    """One scheduler step as the driver saw it."""
    begin: float
    end: float
    emitted: int
    context: int  # cached tokens the step's decode call attended


@dataclasses.dataclass(eq=False)
class Sent:
    """One request as its client saw it."""
    index: int
    prompt_tokens: int
    budget: int
    sent_at: float
    handle: tp.Any
    times: list = dataclasses.field(default_factory=list)  # per token


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.models import TransformerLM
    from flashy_tpu.serve import (ContinuousBatchingScheduler, DecodeEngine,
                                  QueueFull)

    cfg, traffic, checks = ctx.config, ctx.traffic, ctx.cell["checks"]
    vocab, clients = cfg["vocab_size"], traffic["clients"]
    model = TransformerLM(model_lib.transformer_config(
        cfg, attention="dense", dtype=jnp.bfloat16))
    with timed(ctx.setup, "weights_s"):
        params = {"params": model_lib.seeded_params(model, ctx.seed)}
        jax.block_until_ready(params)
    with timed(ctx.setup, "engine_s"):
        engine = DecodeEngine(model, params, cache_scope=ctx.cell_name,
                              **ctx.cell["engine"])
        engine.warmup()
    warm = engine.compile_cache.stats()
    scheduler = ContinuousBatchingScheduler(
        engine, max_queue=2 * clients, **ctx.cell.get("scheduler", {}))
    ctx.say(f"engine: kernel={engine.kernel}, {engine.slots} slots, "
            f"{engine.num_blocks} blocks of {engine.block_size}, pool "
            f"{engine.cache_bytes() / 1e9:.2f} GB, chunk {engine.chunk}; "
            f"{warm['entries']} executables warm")

    request_of = ctx.generator.generate(traffic, ctx.seed, vocab)
    sent, inflight, rejected, ticks = [], {}, [], []

    def send() -> None:
        prompt, budget = request_of(len(sent) + len(rejected))
        now = time.perf_counter()
        with ctx.tracing.span("submit"):
            try:
                handle = scheduler.submit(prompt, budget)
            except (QueueFull, ValueError) as exc:  # full, or a refused length
                rejected.append((now, repr(exc)))
                return
        record = Sent(len(sent), int(prompt.size), budget, now, handle)
        sent.append(record)
        inflight[handle.uid] = record

    def tick() -> None:
        begin = time.perf_counter()
        with ctx.tracing.span("scheduler.step"):
            emitted = scheduler.step()
        end = time.perf_counter()
        context = 0
        with ctx.tracing.span("clients"):
            for uid, record in list(inflight.items()):
                have, seen = len(record.handle.generated), len(record.times)
                if seen:
                    context += record.prompt_tokens + seen
                if have > seen:
                    record.times.extend([end] * (have - seen))
                if record.handle.done:
                    del inflight[uid]
                    send()
        ticks.append(Tick(begin, end, emitted, context))

    # ramp (set-up): every client's first request has its first token
    with timed(ctx.setup, "ramp_s"):
        for _ in range(clients):
            send()
        first_generation = sent[:clients]
        while not all(r.times for r in first_generation):
            tick()
            if len(ticks) > checks["ramp_ticks_max"]:
                raise RuntimeError("the ramp did not reach steady state in "
                                   f"{len(ticks)} scheduler steps")
    ramp_ticks, ramp_steps = len(ticks), len(scheduler.metrics.occupancy)

    mark = ctx.compile_log.mark()
    begin = ctx.start_window()
    deadline = begin + ctx.seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        ctx.tracing.poll(deadline - now)
        tick()
    end = ticks[-1].end
    ctx.tracing.end_window()
    occupancy = scheduler.metrics.occupancy[ramp_steps:]
    window_ticks = ticks[ramp_ticks:]
    lowered = ctx.compile_log.lowerings_since(mark)
    in_window = [r for r in sent if begin <= r.sent_at < end]
    # drain, bounded: the loop stays closed until every request sent in
    # the window has its first token (its completion would cost up to
    # the longest budget in steps, in every run of every later check)
    for _ in range(checks["drain_ticks_max"]):
        if all(r.times for r in in_window):
            break
        tick()
    trace = ctx.tracing.stop()
    memory_peak = model_lib.memory_peak_bytes()
    stats = engine.compile_cache.stats()
    builds = stats["misses"] - warm["misses"] + stats["recompiles"]

    stamps = [t for r in sent for t in r.times if begin < t <= end]
    gaps = [later - earlier for r in sent
            for earlier, later in zip(r.times, r.times[1:])
            if begin < later <= end]
    ttft = [r.times[0] - r.sent_at for r in in_window if r.times]
    done = [r for r in sent if r.handle.done]
    elapsed = end - begin
    end_to_end = {"serve_tok_s": len(stamps) / elapsed,
                  "itl_p95_ms": 1e3 * percentile(gaps, 95),
                  "ttft_p95_ms": 1e3 * percentile(ttft, 95)}
    ctx.say(f"window: {elapsed:.3f}s, {len(window_ticks)} scheduler steps, "
            f"{len(stamps)} tokens, {len(in_window)} requests sent, "
            f"{sum(1 for r in done if begin < r.times[-1] <= end)} completed; "
            f"gaps: {len(gaps)} (p50 {1e3 * percentile(gaps, 50):.2f} ms, "
            f"p95 {end_to_end['itl_p95_ms']:.2f} ms); time to first token "
            f"over {len(ttft)}: p50 {1e3 * percentile(ttft, 50):.1f} ms, "
            f"p95 {end_to_end['ttft_p95_ms']:.1f} ms; ramp {ramp_ticks} "
            f"steps; builds after warm-up {builds}; lowerings in the "
            f"window: {lowered or 'none'}")
    tick_s = [t.end - t.begin for t in window_ticks]
    longest = sorted(range(len(tick_s)), key=lambda i: -tick_s[i])[:3]
    ctx.say(f"longest scheduler steps (index of {len(tick_s)}, ms): "
            + ", ".join(f"{i}: {1e3 * tick_s[i]:.1f}" for i in longest))

    # correctness, outside the window
    failures, failed = [], len([r for r in rejected if begin <= r[0] < end])
    for _, why in rejected:
        failures.append(f"a request was refused: {why}")
    for record in in_window:
        if not record.times or record.handle.finish_reason == "expired":
            failed += 1
            failures.append(f"request {record.index} has no first token")
    for record in done:
        tokens = record.handle.generated
        if (len(tokens) != record.budget
                or not all(0 <= int(t) < vocab for t in tokens)):
            failed += 1
            failures.append(f"request {record.index}: {len(tokens)} tokens "
                            f"for a budget of {record.budget}, or a token "
                            f"outside the vocabulary")
    try:
        engine._pool.check()
    except AssertionError as exc:
        failures.append(f"block pool invariant broken: {exc}")
    if builds:
        failures.append(f"{builds} executable(s) built after warm-up")
    if lowered:
        failures.append(f"lowered inside the window: {lowered}")

    # the reference's full forward pass over prompt + output: every
    # served token must lie within a margin of its largest logit
    length = cfg["max_position_embeddings"]
    width = max(r.budget for r in sent)
    gaps_fn = jax.jit(lambda p, t, pos, tok: reference.served_token_gaps(
        p, t, pos, tok, cfg))
    picked = sorted(done, key=lambda r: not begin <= r.sent_at < end)[
        :checks["reference_requests"]]  # sent in the window first
    worst = 0.0
    for record in picked:
        output = np.asarray(record.handle.output, np.int32)
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :output.size] = output
        positions = np.zeros(width, np.int32)
        served = np.zeros(width, np.int32)
        positions[:record.budget] = record.prompt_tokens - 1 + np.arange(
            record.budget)
        served[:record.budget] = output[record.prompt_tokens:]
        gap, spread = gaps_fn(params["params"], tokens, positions, served)
        ratio = np.asarray(gap / spread)[:record.budget]
        worst = max(worst, float(ratio.max()))
        if float(ratio.max()) > checks["margin_sigma"]:
            failures.append(
                f"request {record.index}: served token {int(ratio.argmax())} "
                f"lies {float(ratio.max()):.3f} of the logits' spread under "
                f"the reference's largest logit")
    ctx.say(f"reference: {len(picked)} completed requests, every served "
            f"token within {worst:.4f} of the logits' spread of the "
            f"reference's largest logit (margin {checks['margin_sigma']})")
    if len(picked) < checks["reference_requests"]:
        failures.append(f"only {len(picked)} requests completed for the "
                        f"reference to check")
    for failure in failures[:20]:
        ctx.say(f"CHECK FAILED: {failure}")

    admitted = [r for r in in_window if r.handle.admitted_at is not None]
    return {"correct": not failures,
            "attempted": len(in_window) + len(rejected), "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": memory_peak,
            "trace": trace,
            "host": {
                "tick_s": tick_s, "ticks": window_ticks,
                "trace_started_at":
                    ctx.tracing.started_at,
                "occupancy": occupancy,
                "queue_wait_s": [r.handle.admitted_at - r.sent_at
                                 for r in admitted],
                "prefill_s_per_ktok": [
                    (r.handle.first_token_at - r.handle.admitted_at)
                    * 1e3 / r.prompt_tokens for r in admitted
                    if r.handle.first_token_at is not None],
                "pool_peak": engine.pool_stats()["peak_in_use"]
                / engine.pool_stats()["capacity"],
                "window_builds": builds, "window_lowerings": lowered,
                "slots": engine.slots, "kv_dtype": engine.kv_dtype}}
