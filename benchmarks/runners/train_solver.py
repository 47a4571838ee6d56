# Runner `train_solver`: the training user's path. Builds the solver the
# way `examples.lm.solver.main` does (`main.get_xp(argv)` ->
# `LMSolver(cfg)`), replaces its seed-0 weights by weights from --seed,
# and drives `solver._train_step(state, batch)` with batches drawn on
# the host every step, as `LMSolver.train` does — but reading no metric
# back inside the loop: a step is dispatched while the previous one
# runs, and the host waits only for the one before (RUN_AHEAD), so the
# device is never more than one step ahead of what has been counted.
"""Closed-loop training driver over examples.lm.solver.LMSolver."""
import dataclasses
import gc
import math
import os
import time

from ..harness import flops, model as model_lib, reference
from ..harness.trace import timed

RUN_AHEAD = 1  # steps in flight behind the one being dispatched


def _argv(ctx) -> list:
    cfg, traffic = ctx.config, ctx.traffic
    return [f"model.dim={cfg['hidden_size']}",
            f"model.num_layers={cfg['num_hidden_layers']}",
            f"model.num_heads={cfg['num_attention_heads']}",
            f"model.mlp_ratio={cfg['intermediate_size'] // cfg['hidden_size']}",
            f"model.vocab_size={cfg['vocab_size']}",
            f"seq_len={traffic['seq_len']}",
            f"batch_size={traffic['batch_size']}",
            f"dora.dir={os.path.join(ctx.out_dir, 'xp')}",
            ] + list(ctx.cell["trainer"]["overrides"])


def run(ctx) -> dict:
    import jax
    import numpy as np
    from examples.lm import solver as lm
    from flashy_tpu.models import TransformerLM
    from flashy_tpu.parallel import shard_batch

    cfg, traffic, checks = ctx.config, ctx.traffic, ctx.cell["checks"]
    model_lib.transformer_config(cfg)  # refuse what the block cannot express
    argv = _argv(ctx)
    ctx.say(f"trainer argv: {' '.join(argv)}")
    xp = lm.main.get_xp(argv)
    with xp.enter():
        with timed(ctx.setup, "solver_s"):
            solver = lm.LMSolver(xp.cfg)
        # weights from --seed, in one jitted call, laid out as the
        # solver laid out its own; its seed-0 copy is dropped first
        with timed(ctx.setup, "weights_s"):
            state, solver.state = dict(solver.state), None
            shardings = jax.tree_util.tree_map(lambda x: x.sharding,
                                               state.pop("params"))
            twin = TransformerLM(dataclasses.replace(
                solver.model.config, attention="dense", remat=False))
            state["params"] = {"params": model_lib.seeded_params(
                twin, ctx.seed, shardings["params"])}
            jax.block_until_ready(state)
        draw = ctx.generator.generate(traffic, ctx.seed, cfg["vocab_size"])

        def place(tokens):
            return shard_batch(jax.numpy.asarray(tokens), solver.mesh,
                               batch_axes=("data", "fsdp"))

        # warm-up: two steps (the second proves the first call's layouts
        # are the steady state's: PR 21 saw a second compile there)
        first_batch = draw(0)
        with timed(ctx.setup, "warmup_s"):
            state, metrics = solver._train_step(state, place(first_batch))
            first_loss = float(metrics["loss"])
            state, metrics = solver._train_step(state, place(draw(1)))
            jax.block_until_ready(state)

        mark = ctx.compile_log.mark()
        begin = ctx.start_window()
        deadline = begin + ctx.seconds
        losses, data_wait, step = [], [], 2
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            ctx.tracing.poll(deadline - now)
            with ctx.tracing.span("batch", step=step):
                tokens = place(draw(step))
            data_wait.append(time.perf_counter() - now)
            with ctx.tracing.span("train_step", step=step):
                state, metrics = solver._train_step(state, tokens)
            losses.append(metrics["loss"])
            if len(losses) > RUN_AHEAD:
                with ctx.tracing.span("block_until_ready"):
                    losses[-1 - RUN_AHEAD].block_until_ready()
            step += 1
        with ctx.tracing.span("block_until_ready"):
            jax.block_until_ready(state)
        elapsed = time.perf_counter() - begin
        ctx.tracing.end_window()
        trace = ctx.tracing.stop()
        lowered = ctx.compile_log.lowerings_since(mark)
        memory_peak = model_lib.memory_peak_bytes()

        losses = [float(x) for x in losses]
        steps = len(losses)
        tokens_per_step = traffic["batch_size"] * traffic["seq_len"]
        tok_s = steps * tokens_per_step / elapsed / ctx.chips
        per_token = flops.train_flops_per_token(cfg, traffic["seq_len"])
        ctx.say(f"window: {steps} steps of {tokens_per_step} tokens in "
                f"{elapsed:.3f}s; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
                f"{per_token / 1e9:.3f} GFLOP/token; lowerings in the "
                f"window: {lowered or 'none'}")

        # correctness, outside the window: the loss falls, and at the
        # initial weights (made again from the seed) the train step's
        # own loss on batch 0 agrees with the plain reference
        failures = []
        if not all(math.isfinite(x) for x in losses):
            failures.append("a step's loss is not finite")
        if not losses[-1] < losses[0]:
            failures.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
        if lowered:
            failures.append(f"lowered inside the window: {lowered}")
        del state, metrics
        gc.collect()
        initial = model_lib.seeded_params(twin, ctx.seed, shardings["params"])
        loss_fn = jax.jit(lambda p, t: reference.next_token_loss(p, t, cfg))
        rows = [float(loss_fn(initial, first_batch[i:i + 1]))
                for i in range(first_batch.shape[0])]
        want = float(np.mean(rows))
        relative = abs(first_loss - want) / abs(want)
        ctx.say(f"loss at the initial weights: train step {first_loss:.6f}, "
                f"reference {want:.6f}, relative difference {relative:.2e} "
                f"(tolerance {checks['loss_rel_tol']:.0e})")
        if not relative <= checks["loss_rel_tol"]:
            failures.append(f"train-step loss {first_loss} vs reference "
                            f"{want}: relative {relative:.2e}")
        # and the program's logits on one sequence, position by position
        program = jax.jit(lambda p, t: solver.model.apply({"params": p}, t))
        rows = min(checks.get("logit_rows", 1), first_batch.shape[0])
        got = program(initial, first_batch[:rows]).astype(jax.numpy.float32)
        ref = jax.jit(lambda p, t: reference.logits(p, t, cfg))(
            initial, first_batch[:rows])
        spread = jax.numpy.std(ref)
        error = float(jax.numpy.max(jax.numpy.abs(got - ref)) / spread)
        rms = float(jax.numpy.sqrt(jax.numpy.mean((got - ref) ** 2)) / spread)
        ctx.say(f"logits vs reference on {rows} sequence(s): largest "
                f"difference {error:.4f} of the logits' spread "
                f"(tolerance {checks['logit_tol_sigma']}), root mean square "
                f"{rms:.4f}")
        if not error <= checks["logit_tol_sigma"]:
            failures.append(f"logits differ from the reference by "
                            f"{error:.4f} of their spread")
    for failure in failures:
        ctx.say(f"CHECK FAILED: {failure}")
    return {"correct": not failures, "attempted": steps, "failed": 0,
            "end_to_end": {"train_tok_s": tok_s},
            "memory_peak_bytes": memory_peak, "trace": trace,
            "host": {"data_wait_s": data_wait, "window_lowerings": lowered,
                     "train_tok_s": tok_s, "flops_per_token": per_token,
                     "batch_heads": traffic["batch_size"]
                     * cfg["num_attention_heads"],
                     "seq_len": traffic["seq_len"],
                     "head_dim": cfg["hidden_size"]
                     // cfg["num_attention_heads"]}}
