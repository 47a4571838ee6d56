# Runner `serve_family`: `serve_engine`'s closed-loop drive for a model
# family that brings its own mapping and reference. The configuration
# file names them under `harness`: `model` (a module of harness/ with
# `transformer_config(config, **overrides)`, `seeded_params` and
# `memory_peak_bytes`) and `reference` (one with `logits_at(params,
# tokens, positions, config, precision)`). The loop, the clocks, the
# counts and the budget / pool / compile checks are `serve_engine`'s,
# copied because that file may not be edited here (PERF.md section 7
# asks the next benchmark issue to fold the two). What differs:
#  * the reference's sequence length is the ENGINE's `max_seq_len`, not
#    the model's `max_position_embeddings` (163,840 for dots.vlm1);
#  * the scheduler and the engine are dropped before the reference runs,
#    so that its float32 blocks have the pool's room;
#  * agreement with the reference is read twice, each as a quantile:
#    the tokens served in the window (how far under the reference's
#    largest logit), and the logits of the engine's OWN executables,
#    tapped (`DecodeEngine(keep_logits=True)`, in the cell's `engine`)
#    while the closed loop runs on after the window with every slot
#    live: the timed decode step and the timed prefill slice, at the
#    timed sizes. The cell file's `checks` say why.
# Controls, run with `--rehearse benchmarks/controls/<file>.json` so that
# their line can never be taken for a result; each has to come out as
# not correct:
#  * `checks.control: "bfloat16"`: the reference computed in bfloat16
#    throughout stands in for the program in both readings;
#  * `checks.reference_config`: keys laid over the configuration the
#    reference sees (a term dropped, a table changed), so that program
#    and reference differ by a planted fault.
"""Closed-loop serving driver for a family named by the config file."""
import functools
import gc
import importlib
import time

from ..harness.trace import timed
from .serve_engine import Sent, Tick, percentile

# an rms error of a position's logits over this share of their spread is
# a router's near-tie that fell the other way, not rounding (reported)
JUMP = 0.05


def _tap_logits(engine, inflight, tick, *, ticks: int, requests: int):
    """`ticks` more steps of the closed loop, every slot live, keeping
    the logits the engine's executables sampled from. Followed: the
    `requests` decoding requests with the most tokens still to come,
    from where they stand, and the first `requests` requests whose
    first token arrives in these steps, from that token on. Returns
    [(record, offset, float32 logits [n, V])]: row j is what the
    record's token offset + j was taken from (token 0 from the prefill
    slice's tap, every other from the decode step's row of its slot)."""
    import numpy as np
    decoding = sorted(
        (r for r in inflight.values() if r.handle.generated),
        key=lambda r: len(r.handle.generated) - r.budget)[:requests]
    followed = {r.index: (r, len(r.handle.generated), []) for r in decoding}
    for _ in range(ticks):
        before = [(r, len(r.handle.generated)) for r in inflight.values()]
        tick()
        grown = [(r, had, len(r.handle.generated)) for r, had in before
                 if len(r.handle.generated) > had]
        first = [r for r, had, _ in grown if had == 0]
        # (two first tokens in one step: the slice's tap holds the later)
        if len(first) == 1 and len(followed) < len(decoding) + requests:
            followed[first[0].index] = (first[0], 0, [])
        decode = None
        for record, had, have in grown:
            if record.index not in followed:
                continue
            rows = followed[record.index][2]
            if had == 0:
                rows.append(np.asarray(engine.tapped["prefill_chunk"])[0])
                had = 1
            if have > had:  # one token a decode step
                if decode is None:
                    decode = np.asarray(engine.tapped["decode"])
                rows.append(decode[record.handle.slot])
    return [(record, offset, np.stack(rows))
            for record, offset, rows in followed.values() if rows]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.models import TransformerLM
    from flashy_tpu.serve import (ContinuousBatchingScheduler, DecodeEngine,
                                  QueueFull)

    cfg, traffic, checks = ctx.config, ctx.traffic, ctx.cell["checks"]
    vocab, clients = cfg["vocab_size"], traffic["clients"]
    model_lib, reference = (
        importlib.import_module(f"benchmarks.harness.{cfg['harness'][key]}")
        for key in ("model", "reference"))
    # an earlier program lacks the family's config keys: it fails here,
    # in seconds, before any weight is made
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
    elapsed = end - begin
    end_to_end = {"serve_tok_s": len(stamps) / elapsed,
                  "itl_p95_ms": 1e3 * percentile(gaps, 95),
                  "ttft_p95_ms": 1e3 * percentile(ttft, 95)}
    completed = sum(1 for r in sent
                    if r.handle.done and begin < r.times[-1] <= end)
    ctx.say(f"window: {elapsed:.3f}s, {len(window_ticks)} scheduler steps, "
            f"{len(stamps)} tokens, {len(in_window)} requests sent, "
            f"{completed} completed; "
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

    # correctness, outside the window. First the loop runs on, every slot
    # live, and the engine's own logits are kept for the comparison below
    tapped = _tap_logits(engine, inflight, tick, ticks=checks["logit_ticks"],
                         requests=checks["reference_requests"])
    done = [r for r in sent if r.handle.done]
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
    for record, offset, rows in tapped:
        served = record.handle.generated[offset:offset + len(rows)]
        if not np.array_equal(rows.argmax(axis=-1), served):
            failures.append(f"request {record.index}: the tapped logits are "
                            f"not those its tokens were taken from")
    try:
        engine._pool.check()
    except AssertionError as exc:
        failures.append(f"block pool invariant broken: {exc}")
    if builds:
        failures.append(f"{builds} executable(s) built after warm-up")
    if lowered:
        failures.append(f"lowered inside the window: {lowered}")

    host = {"slots": engine.slots, "kv_dtype": engine.kv_dtype,
            "pool_peak": engine.pool_stats()["peak_in_use"]
            / engine.pool_stats()["capacity"]}
    # Against the reference's full forward pass over prompt + output,
    # two readings, each a quantile because a routed model's errors have
    # a heavy tail (a near-tie of the router that falls the other way in
    # bfloat16 moves that token's logits by a large step, in any
    # precision below the reference's own):
    #  * margin: served tokens of `reference_requests` requests that
    #    completed, those sent in the window first: how far under the
    #    reference's largest logit each lies;
    #  * logit_rms: the engine's tapped logits of the requests it
    #    followed after the window, against the reference's at the same
    #    positions (root mean square over the vocabulary).
    # Both over the logits' spread at the position. Under the control
    # "bfloat16" the reference computed in bfloat16 throughout stands in
    # for the program: its logits, and the tokens it would serve.
    length = engine.max_seq_len
    del scheduler, engine  # the pool's room goes to the reference
    gc.collect()
    control = checks.get("control")
    width = max(max(r.budget for r in sent), checks["logit_ticks"] + 1)
    reference_cfg = dict(cfg, **checks.get("reference_config", {}))
    logits_at = {precision: jax.jit(functools.partial(
        lambda p, t, pos, precision: reference.logits_at(
            p, t, pos, reference_cfg, precision), precision=precision))
        for precision in ("float32", "bfloat16")}

    def against_reference(record, count, offset=0):
        """Reference logits [count, V] at the positions the record's
        output tokens offset .. offset + count were taken from, and the
        stand-in's (the control's) or None."""
        output = np.asarray(record.handle.output, np.int32)[:length]
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :output.size] = output
        positions = np.zeros(width, np.int32)
        positions[:count] = (record.prompt_tokens - 1 + offset
                             + np.arange(count))
        args = params["params"], tokens, positions
        ref = np.asarray(logits_at["float32"](*args))[:count]
        low = (np.asarray(logits_at[control](*args))[:count]
               if control else None)
        return ref, low

    picked = sorted(done, key=lambda r: not begin <= r.sent_at < end)[
        :checks["reference_requests"]]  # sent in the window first
    quantiles = lambda v: ", ".join(
        f"p{q} {np.percentile(v, q):.4f}"
        for q in (50, 75, 90, 95, 99, 100))
    under, rms = [], []
    for record in picked:
        ref, low = against_reference(record, record.budget)
        chosen = (np.asarray(record.handle.generated) if low is None
                  else low.argmax(axis=-1))
        under.append((ref.max(axis=-1) - ref[np.arange(len(ref)), chosen])
                     / ref.std(axis=-1))
    for record, offset, rows in tapped:
        ref, low = against_reference(record, len(rows), offset)
        mine = rows if low is None else low
        rms.append(np.sqrt(np.mean((mine - ref) ** 2, axis=-1))
                   / ref.std(axis=-1))
    slices = sum(1 for _, offset, _ in tapped if offset == 0)
    who = (f"CONTROL, the reference in {control} throughout in the "
           f"program's place: " if control else "")
    if checks.get("reference_config"):
        who += (f"PLANTED FAULT, the reference sees "
                f"{checks['reference_config']}: ")
    for what, values, limit in (
            (f"the distance of the served tokens of {len(picked)} completed "
             f"requests under the reference's largest logit", under,
             "margin"),
            (f"the rms error of the engine's own logits over {len(tapped)} "
             f"requests followed with every slot live ({slices} from their "
             f"prefill slice's first token on)", rms, "logit_rms")):
        if not values:
            continue
        values = np.concatenate(values)
        q, most = checks[f"{limit}_quantile"], checks[f"{limit}_sigma"]
        reading = float(np.percentile(values, q))
        ctx.say(f"{who}{what}, {values.size} positions, of the logits' "
                f"spread: {quantiles(values)}; over {JUMP}: "
                f"{100.0 * float(np.mean(values > JUMP)):.2f}%")
        ctx.say(f"check {limit}_sigma: p{q} is {reading:.4f} "
                f"(limit {most})")
        if not reading <= most:
            failures.append(f"p{q} of {what} is {reading:.4f} of the "
                            f"logits' spread, over the limit {most}")
    if len(tapped) < checks["reference_requests"] or not slices:
        failures.append(f"{len(tapped)} requests followed, {slices} from "
                        f"their first token, in {checks['logit_ticks']} "
                        f"steps after the window")
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
                "window_builds": builds, "window_lowerings": lowered,
                **host}}
