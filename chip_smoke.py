# The quickest proof that the system still starts on the chip. Drives the
# main path once, at the full width of the flagship LM (dim 1024, 12
# layers, 16 heads of 64, vocab 32768, seq 1024, bf16 — ~235M parameters,
# random weights from a seed), through the entry points a user calls:
#
#   kernels  every Pallas kernel a default TPU run can reach, compiled by
#            Mosaic (no interpret mode) at the flagship's shapes and
#            compared with its XLA reference;
#   trainer  `examples.lm.solver.main([...])`: two epochs of a few steps
#            (two commits, both A/B checkpoint slots), then the same call
#            with one more epoch, which must restore and continue;
#   server   TransformerLM -> DecodeEngine(paged, int8, kernel='auto') ->
#            warmup() -> ContinuousBatchingScheduler over staggered
#            shared-prefix requests, one pass with NGramDraft speculation;
#            plus a float32 copy at kv_dtype='model' whose greedy outputs
#            must equal per-request generate().
#
# A chip belongs to one process, so this parent imports neither jax nor
# flashy_tpu: it runs the work in a child, then a second child that
# compiles the same train step again so a compile cache that never hits
# is visible (cold vs warm seconds). It exits non-zero — printing no
# result line — when JAX finds no TPU or the package is not importable,
# and when any phase or check failed. The last stdout line of a passing
# run is one JSON object: {"ok": true, "device": {...}}.
#
# `--rehearse` runs the same code at a toy size on whatever backend JAX
# finds (Pallas in interpret mode on the CPU). It exists to debug this
# script without a chip; it says so in its output and proves nothing
# about the device.
"""chip_smoke: train, commit, resume and serve the flagship LM on one TPU."""
import argparse
import json
import os
import resource
import shutil
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(HERE, "chip_smoke_out")
# the driver allows 1200 s, compilation included
BUDGET_S = 1150.0
WARM_RESERVE_S = 150.0

FLAGSHIP = dict(dim=1024, layers=12, heads=16, vocab=32768, seq=1024,
                batch=16, serve_len=256, slots=8, block_size=16, spec_k=4)
TOY = dict(dim=64, layers=2, heads=4, vocab=256, seq=128, batch=4,
           serve_len=64, slots=4, block_size=8, spec_k=3)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ----------------------------------------------------------------------
# parent: stdlib only, never touches the chip
# ----------------------------------------------------------------------
def _run_child(phase: str, args: argparse.Namespace, timeout: float) -> int:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
           "--out", args.out]
    if args.rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, cwd=HERE)
    try:
        return proc.wait(timeout=max(timeout, 1.0))
    except subprocess.TimeoutExpired:
        say(f"child '{phase}' exceeded its {timeout:.0f}s budget; killing it")
        return 124
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def parent(args: argparse.Namespace) -> int:
    begin = time.monotonic()
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    if args.rehearse:
        say("REHEARSAL: toy sizes on whatever backend JAX finds — this "
            "proves nothing about the chip")
    results = {}
    for phase, reserve in (("run", WARM_RESERVE_S), ("warm", 0.0)):
        left = BUDGET_S - (time.monotonic() - begin) - reserve
        code = _run_child(phase, args, left)
        record = os.path.join(args.out, f"{phase}.json")
        if os.path.exists(record):
            with open(record) as f:
                results[phase] = json.load(f)
        if code != 0:
            say(f"child '{phase}' failed with exit code {code}")
            # whoever reads only the end of stderr still learns why
            for name, info in results.get(phase, {}).get("phases",
                                                         {}).items():
                for failure in info["failed"]:
                    print(f"[chip_smoke] FAILED [{name}]: {failure}",
                          file=sys.stderr, flush=True)
            return code if 0 < code < 256 else 1

    run, warm = results["run"], results["warm"]
    cold_s = run["phases"]["trainer"]["train_step_compile_seconds"]
    warm_s = warm["train_step_compile_seconds"]
    say(f"compile cache at {run['compile_cache_dir']}: train step cold "
        f"{cold_s:.1f}s, warm (second process) {warm_s:.1f}s, "
        f"{warm['persistent_cache_hits']} persistent-cache hit(s)")
    failures = []
    if warm["compile_cache_dir"] != run["compile_cache_dir"]:
        failures.append("the two processes used different cache directories")
    if run["phases"]["trainer"]["train_step_cache_hit"]:
        # the machine came with a populated cache: nothing was cold
        say("the first process already found the train step in the cache")
        hit = warm["persistent_cache_hits"] >= 1
    else:
        hit = warm["persistent_cache_hits"] >= 1 and warm_s < 0.5 * cold_s
    file_limit = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    if not hit and any(
            os.path.getsize(os.path.join(run["compile_cache_dir"], name))
            == file_limit for name in os.listdir(run["compile_cache_dir"])):
        # the machine's doing, not the program's: say so, do not fail on it
        say(f"COMPILE CACHE NOT VERIFIED: this machine's file size limit "
            f"({file_limit} bytes) cut a cache entry short, so the train "
            f"step could not be stored")
    elif not hit:
        failures.append(f"the compile cache did not hit: warm {warm_s:.1f}s "
                        f"vs cold {cold_s:.1f}s")
    for name, phase in run["phases"].items():
        say(f"phase {name}: {'ok' if phase['ok'] else 'FAILED'} in "
            f"{phase['seconds']:.1f}s ({phase['compile_seconds']:.1f}s "
            f"compiling)")
    say(f"total wall time {time.monotonic() - begin:.1f}s")
    for failure in failures:
        say(f"FAILED: {failure}")
    summary = {"ok": not failures, "device": run["device"]}
    if args.rehearse:
        summary["rehearsal"] = True
    print(json.dumps(summary), flush=True)
    return 0 if not failures else 1


# ----------------------------------------------------------------------
# child plumbing
# ----------------------------------------------------------------------
class Checks:
    """Collects a phase's failed expectations so one run reports them all."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed = []

    def expect(self, condition: bool, what: str) -> None:
        if not condition:
            self.failed.append(what)
            say(f"  CHECK FAILED [{self.phase}]: {what}")


class CompileLog:
    """jax.monitoring listener: backend-compile seconds, lowerings per
    function name and persistent-cache hits/misses."""

    def __init__(self):
        import jax
        self.compile_seconds = 0.0
        self.lowerings = {}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_seconds += seconds
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            name = str(kwargs.get("fun_name", "?"))
            self.lowerings[name] = self.lowerings.get(name, 0) + 1

    def _event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def _versions() -> dict:
    from importlib import metadata
    out = {}
    for package in ("jax", "jaxlib", "libtpu", "flax", "optax",
                    "orbax-checkpoint"):
        try:
            out[package] = metadata.version(package)
        except metadata.PackageNotFoundError:
            out[package] = None
    return out


def _start_child(args: argparse.Namespace):
    """Common child start: compile cache, backend, device identity.
    Returns (device dict, cache dir, size dict) or exits 3 off-TPU."""
    sys.path.insert(0, HERE)
    from flashy_tpu.utils import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {device}; versions: {_versions()}; compile cache: "
        f"{cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f", JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    # the trainer phase writes two ~2.6 GiB checkpoint slots under --out
    file_limit = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    say(f"disk: {shutil.disk_usage(args.out).free / 2**30:.1f} GiB free at "
        f"{args.out}, file size limit "
        f"{'none' if file_limit == resource.RLIM_INFINITY else file_limit}")
    if device["platform"] != "tpu" and not args.rehearse:
        say("JAX found no TPU: nothing to prove here (use --rehearse to "
            "debug this script at a toy size)")
        raise SystemExit(3)
    return device, cache_dir, (TOY if args.rehearse else FLAGSHIP)


def _trainer_argv(size: dict, out: str) -> list:
    return [f"model.dim={size['dim']}", f"model.num_layers={size['layers']}",
            f"model.num_heads={size['heads']}",
            f"model.vocab_size={size['vocab']}", "model.attention=flash",
            # flash + full remat at b=16 needs ~6 GB of the 16 GB chip;
            # without remat the same step needs ~14.4 GB
            "model.remat=true", f"seq_len={size['seq']}",
            f"batch_size={size['batch']}", "steps_per_epoch=3",
            "valid_steps=2", "warmup_steps=2",
            f"dora.dir={os.path.join(out, 'xp')}"]


def _compile_train_step(argv: list):
    """Build the solver exactly as the entry point will and compile its
    train step ahead of time. Returns (compiled, seconds, solver, xp)."""
    from examples.lm import solver as lm
    xp = lm.main.get_xp(argv)
    with xp.enter():
        solver = lm.LMSolver(xp.cfg)
        lowered = solver._train_step.lower(solver.state, solver.batch_at(0))
        begin = time.perf_counter()
        compiled = lowered.compile()
        seconds = time.perf_counter() - begin
    return compiled, seconds, solver, xp


def _maxerr(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _largest_file(root) -> int:
    return max((os.path.getsize(os.path.join(folder, name))
                for folder, _, names in os.walk(root) for name in names),
               default=0)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# ----------------------------------------------------------------------
# phase: block_until_ready vs host readback
# ----------------------------------------------------------------------
def phase_sync(ctx: dict, checks: Checks) -> dict:
    """`jax.block_until_ready` must really wait: after it returns from a
    long dependent chain, a host readback of the result has nothing left
    to wait for."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, reps = (4096, 400) if not ctx["rehearse"] else (512, 100)
    a = (jax.random.normal(jax.random.PRNGKey(0), (n, n))
         / n ** 0.5).astype(jnp.bfloat16)
    # a tiny result, so the readback below is a wait and nothing else
    chain = jax.jit(lambda a, x: jax.lax.fori_loop(
        0, reps, lambda i, y: (a @ y).astype(jnp.bfloat16), x)[:8, :128])
    np.asarray(chain(a, a))  # compile
    begin = time.perf_counter()
    out = chain(a, a)
    dispatched = time.perf_counter() - begin
    jax.block_until_ready(out)
    waited = time.perf_counter() - begin
    np.asarray(out)
    readback = time.perf_counter() - begin - waited
    say(f"  sync: dispatch returned after {dispatched * 1e3:.1f} ms, "
        f"block_until_ready after {waited * 1e3:.1f} ms, the readback "
        f"after it took {readback * 1e3:.1f} ms")
    checks.expect(readback < 0.1 * waited + 0.005,
                  f"block_until_ready returned {readback * 1e3:.1f} ms "
                  f"before the chain really finished")
    return {"dispatch_ms": round(dispatched * 1e3, 2),
            "block_until_ready_ms": round(waited * 1e3, 2),
            "readback_after_ms": round(readback * 1e3, 2),
            "chain": f"{reps} x {n}^3 bf16 matmul"}


# ----------------------------------------------------------------------
# phase: every kernel compiles (Mosaic) and matches its XLA reference
# ----------------------------------------------------------------------
def phase_kernels(ctx: dict, checks: Checks) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashy_tpu.ops import attention as attn
    from flashy_tpu.ops import ssd_scan
    from flashy_tpu.ops.paged_attention import paged_attention, pool_spec
    from flashy_tpu.ops.paged_decode import (fused_paged_attention,
                                             fused_speculative_verify)
    from flashy_tpu.parallel.moe_ep import _grouped_mlp

    size, real = ctx["size"], not ctx["rehearse"]
    verdicts = {}
    rng = np.random.default_rng(0)
    f32, bf16 = jnp.float32, jnp.bfloat16

    def run(name, fn, *operands):
        """Compile `fn` (no interpret mode on the chip), run it, and
        require Mosaic custom calls in the program."""
        begin = time.perf_counter()
        compiled = jax.jit(fn).lower(*operands).compile()
        seconds = time.perf_counter() - begin
        calls = _mosaic_calls(compiled)
        if real:
            checks.expect(calls > 0, f"{name}: no Mosaic custom call in "
                                     f"the compiled program")
        verdicts[name] = {"mosaic_calls": calls,
                          "compile_seconds": round(seconds, 1)}
        return compiled(*operands)

    def verdict(name, error, bound, **more):
        ok = bool(np.isfinite(error) and error < bound)
        verdicts[name].update(rel_err=round(error, 5), bound=bound, ok=ok,
                              **more)
        checks.expect(ok, f"{name}: error {error:.3g} vs its XLA reference "
                          f"exceeds {bound}")
        say(f"  kernel {name}: {'ok' if ok else 'FAILED'} "
            f"{json.dumps(verdicts[name])}")

    def scaled(got, want):
        return _maxerr(got, want) / (float(np.max(np.abs(
            np.asarray(want, np.float32)))) or 1.0)

    # --- flash attention: forward, split backward, fused backward
    b, t, h = size["batch"], size["seq"], size["heads"]
    d = size["dim"] // h
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), bf16)
               for _ in range(3))

    def sq_loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(f32) ** 2).sum()

    dense = functools.partial(attn.dot_product_attention, causal=True)
    ref_out = jax.jit(dense)(q, k, v)
    ref_grads = jax.jit(jax.grad(sq_loss(dense), argnums=(0, 1, 2)))(q, k, v)
    grads = {}
    out = run("flash_fwd", functools.partial(attn.flash_attention,
                                             causal=True), q, k, v)
    verdict("flash_fwd", scaled(out, ref_out), 3e-2)
    for name, fused in (("flash_bwd_split", False), ("flash_bwd_fused", None)):
        flash = functools.partial(attn.flash_attention, causal=True,
                                  fused_backward=fused)
        grads[name] = run(name, jax.grad(sq_loss(flash), argnums=(0, 1, 2)),
                          q, k, v)
        verdict(name, max(scaled(g, r) for g, r
                          in zip(grads[name], ref_grads)), 3e-2)
    verdicts["flash_bwd_fused"]["bitwise_equal_to_split"] = all(
        np.array_equal(np.asarray(a, np.float32), np.asarray(c, np.float32))
        for a, c in zip(grads["flash_bwd_fused"], grads["flash_bwd_split"]))
    del q, k, v, grads, ref_grads, ref_out, out

    # --- fused paged decode (T=1) and speculative verify (T=k+1)
    slots, bs = size["slots"], size["block_size"]
    entries = size["serve_len"] // bs
    blocks = slots * entries + 1
    table = jnp.asarray(rng.permutation(np.arange(1, blocks))
                        .reshape(slots, entries).astype(np.int32))
    base = rng.integers(bs, size["serve_len"] - size["spec_k"] - 1, slots)
    for kv in ("model", "int8"):
        entry = {}
        for name, (shape, dtype) in pool_spec(blocks, bs, h, d, bf16,
                                              kv).items():
            if jnp.dtype(dtype) == jnp.int8:
                entry[name] = jnp.asarray(rng.integers(-127, 128, shape),
                                          jnp.int8)
            elif name.endswith("_scale"):
                entry[name] = jnp.asarray(rng.uniform(0.004, 0.02, shape),
                                          dtype)
            else:
                entry[name] = jnp.asarray(rng.normal(size=shape), dtype)
        for queries, fn in ((1, fused_paged_attention),
                            (size["spec_k"] + 1, fused_speculative_verify)):
            name = f"{fn.__name__}[{kv},T={queries}]"
            pq = jnp.asarray(rng.normal(size=(slots, queries, h, d)), bf16)
            positions = jnp.asarray(
                base[:, None] + np.arange(queries)[None], jnp.int32)
            got = run(name, functools.partial(fn, head_dim=d, dtype=bf16),
                      pq, entry, table, positions)
            want = jax.jit(functools.partial(
                paged_attention, head_dim=d, dtype=bf16))(
                    pq, entry, table, positions)
            verdict(name, scaled(got, want), 3e-2)

    # --- fused SSD chunked scan vs the XLA chunked form
    n = 16
    sb = max(b // 2, 1)
    c_in, b_in = (jnp.asarray(rng.normal(size=(sb, t, h, n)) * 0.3, bf16)
                  for _ in range(2))
    v_in = jnp.asarray(rng.normal(size=(sb, t, h, d)), bf16)
    log_a = -jnp.abs(jnp.asarray(rng.normal(size=(sb, t, h)) * 0.1, f32))
    got_y, got_state = run(
        "ssd_chunked_scan[fused]",
        functools.partial(ssd_scan.ssd_chunked_scan, kernel="fused"),
        c_in, b_in, v_in, log_a)
    want_y, want_state = jax.jit(functools.partial(
        ssd_scan.ssd_chunked_scan, kernel="gather"))(c_in, b_in, v_in, log_a)
    verdict("ssd_chunked_scan[fused]",
            max(scaled(got_y, want_y), scaled(got_state, want_state)), 3e-2)
    try:
        jax.jit(jax.grad(lambda v_: ssd_scan.ssd_chunked_scan(
            c_in, b_in, v_, log_a, kernel="fused")[0].astype(f32).sum())
                ).lower(v_in)
        differentiates = True
    except Exception as exc:  # noqa: BLE001 — recorded, not a gate (R8)
        differentiates = f"{type(exc).__name__}: {str(exc)[:160]}"
    verdicts["ssd_chunked_scan[fused]"]["differentiates"] = differentiates
    say(f"  fused SSD kernel under jax.grad: {differentiates}")

    # --- megablox grouped matmul (the dropless expert MLP)
    experts, dim, hidden = 8, size["dim"], 4 * size["dim"]
    sizes = np.array([96, 160, 0, 384, 128, 32, 64, 160], np.int32)
    xs = jnp.asarray(rng.normal(size=(int(sizes.sum()), dim)), bf16)
    w_up = jnp.asarray(rng.normal(size=(experts, dim, hidden)) * 0.03, f32)
    w_down = jnp.asarray(rng.normal(size=(experts, hidden, dim)) * 0.03, f32)
    got = run("megablox_gmm", lambda x, up, down, sz: _grouped_mlp(
        x, up, down, sz, bf16), xs, w_up, w_down, jnp.asarray(sizes))
    rows, offset = [], 0
    for e, count in enumerate(sizes):
        part = np.asarray(xs, np.float32)[offset:offset + count]
        hid = np.asarray(jax.nn.gelu(part @ np.asarray(w_up[e])))
        rows.append(hid @ np.asarray(w_down[e]))
        offset += count
    verdict("megablox_gmm", scaled(got, np.concatenate(rows)), 3e-2)
    return {"kernels": verdicts}


# ----------------------------------------------------------------------
# phase: the trainer, through its entry point
# ----------------------------------------------------------------------
def phase_trainer(ctx: dict, checks: Checks) -> dict:
    import gc

    import jax
    import numpy as np

    from examples.lm import solver as lm
    from flashy_tpu import checkpoint
    from flashy_tpu.parallel import (describe_state_sharding, memory_stats,
                                     per_device_bytes)
    from flashy_tpu.utils import tree_bytes

    log: CompileLog = ctx["compile_log"]
    argv = _trainer_argv(ctx["size"], ctx["out"])
    info = {"argv": argv}

    # the program first: what the entry point is about to run
    hits_before = log.cache_hits
    compiled, seconds, solver, xp = _compile_train_step(argv + ["epochs=2"])
    info["train_step_compile_seconds"] = round(seconds, 2)
    info["train_step_cache_hit"] = log.cache_hits > hits_before
    info["train_step_mosaic_calls"] = _mosaic_calls(compiled)
    info["train_step_memory"] = memory_stats(compiled)
    if not ctx["rehearse"]:
        checks.expect(info["train_step_mosaic_calls"] > 0,
                      "no Mosaic custom call in the compiled train step: "
                      "the flash kernels are not in the program")
    state = solver.state
    info["state_sharding"] = describe_state_sharding(state)["summary"]
    info["state_bytes"] = tree_bytes(state)
    info["state_bytes_per_device"] = per_device_bytes(state)
    info["params_per_device_over_total"] = round(
        per_device_bytes(state["params"]) / tree_bytes(state["params"]), 4)
    info["opt_per_device_over_total"] = round(
        per_device_bytes(state["opt_state"])
        / tree_bytes(state["opt_state"]), 4)
    info["n_params"] = int(sum(
        x.size for x in jax.tree_util.tree_leaves(state["params"])))
    say(f"  train step: compiled in {seconds:.1f}s, "
        f"{info['train_step_mosaic_calls']} Mosaic calls, memory "
        f"{info['train_step_memory']}; state {info['state_sharding']}, "
        f"{info['state_bytes'] / 2**30:.2f} GiB total, "
        f"{info['state_bytes_per_device'] / 2**30:.2f} GiB per device")
    del compiled, solver, state
    gc.collect()

    def device_memory():
        return [{key: stats.get(key) for key in
                 ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                for stats in (dev.memory_stats() or {}
                              for dev in jax.devices())]

    def history():
        with open(xp.folder / "history.json") as f:
            return json.load(f)

    before = dict(log.lowerings)
    lm.main(argv + ["epochs=2"])
    checks.expect(len(history()) == 2, f"history.json has "
                  f"{len(history())} entries after two epochs, not 2")
    info["device_memory_after_training"] = device_memory()
    slots = sorted(str(p.relative_to(xp.folder))
                   for p in xp.folder.glob("**/state.pkl"))
    info["checkpoint_slots"] = slots
    if info["state_bytes"] >= lm.LMSolver.sharded_checkpoint_min_bytes:
        checks.expect(len(slots) == 2, f"two commits should leave both "
                      f"A/B checkpoint slots written, found {slots}")
    else:  # a toy state takes the single-file path
        checks.expect((xp.folder / lm.LMSolver.checkpoint_name).exists(),
                      "no checkpoint file after two commits")

    lm.main(argv + ["epochs=3"])
    entries = history()
    checks.expect(len(entries) == 3, f"history.json has {len(entries)} "
                  f"entries after the resumed epoch, not 3")
    with open(xp.folder / "solver.log.0") as f:
        solver_log = f.read()
    checks.expect("Restored: True; starting at epoch 3" in solver_log,
                  "the third epoch did not log 'Restored: True; starting "
                  "at epoch 3'")
    checks.expect("Restored: False; starting at epoch 1" in solver_log,
                  "the first call did not start fresh")
    losses = [entry[stage]["loss"] for entry in entries
              for stage in ("train", "valid")]
    info["losses"] = [round(float(x), 4) for x in losses]
    info["train_tokens_per_sec_observed"] = [
        round(entry["train"]["tokens_per_sec"]) for entry in entries]
    checks.expect(bool(np.all(np.isfinite(losses))),
                  f"non-finite loss in {losses}")
    # one lowering of the train step per entry-point call: nothing
    # compiled again after a jitted function's first step
    lowered = {name: count - before.get(name, 0)
               for name, count in log.lowerings.items()
               if count - before.get(name, 0) and "step" in name}
    info["step_lowerings_in_two_calls"] = lowered
    checks.expect(lowered.get("jit(train_step)") == 2,
                  f"expected exactly one train_step lowering per call, "
                  f"got {lowered}")
    # a machine that caps file size must still take the checkpoint
    info["largest_checkpoint_file_bytes"] = _largest_file(xp.folder)
    say(f"  trained, committed twice, resumed: losses {info['losses']}, "
        f"slots {slots}, largest file "
        f"{info['largest_checkpoint_file_bytes'] / 2**20:.1f} MiB, "
        f"lowerings {lowered}")
    if len(slots) == 2:
        checks.expect(info["largest_checkpoint_file_bytes"]
                      <= 2 * checkpoint.DATA_FILE_BYTES,
                      f"a checkpoint file of "
                      f"{info['largest_checkpoint_file_bytes']} bytes exceeds "
                      f"twice checkpoint.DATA_FILE_BYTES")
    # the checkpoints did their job; leave only the small records behind
    for payload in xp.folder.glob("checkpoint*"):
        if payload.is_dir():
            shutil.rmtree(payload, ignore_errors=True)
        else:
            payload.unlink()
    return info


# ----------------------------------------------------------------------
# phase: the server, through the public serving API
# ----------------------------------------------------------------------
def _serve_workload(size: dict, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    bs = size["block_size"]
    # a shared prefix that is NOT block-aligned: every later admission
    # takes the copy-on-write fork of the partially shared block
    system = rng.integers(0, size["vocab"], 2 * bs + bs // 2 + 1)
    workload = []
    for i in range(8):
        # three (prompt length, budget) shapes: generate() compiles once
        # per shape
        kind = i % 3
        tail = rng.integers(0, size["vocab"], 3 + 2 * kind)
        workload.append((np.concatenate([system, tail]).astype(np.int32),
                         12 + 2 * kind))
    return workload


def _serve(engine, workload, draft, stagger: int = 3):
    from flashy_tpu.serve import ContinuousBatchingScheduler
    scheduler = ContinuousBatchingScheduler(engine, draft=draft,
                                            max_queue=len(workload))
    pending, handles = list(workload), []
    while pending or not scheduler.idle:
        for _ in range(min(stagger, len(pending))):
            prompt, max_new = pending.pop(0)
            handles.append(scheduler.submit(prompt, max_new))
        scheduler.step()
    return handles, scheduler.metrics.summary()


def phase_server(ctx: dict, checks: Checks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.models.decoding import generate
    from flashy_tpu.serve import DecodeEngine, NGramDraft

    size, info = ctx["size"], {}
    workload = _serve_workload(size, seed=11)

    def build(dtype, seed):
        cfg = TransformerConfig(
            vocab_size=size["vocab"], dim=size["dim"],
            num_layers=size["layers"], num_heads=size["heads"],
            attention="dense", max_seq_len=size["serve_len"], dtype=dtype)
        model = TransformerLM(cfg)
        params = {"params": model.init(
            jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]}
        return model, params

    def serve_both_ways(engine, tag):
        """Plain decode, then NGramDraft speculation, on one warmed
        engine; returns the handles of both passes."""
        begin = time.perf_counter()
        engine.warmup()
        info[f"{tag}_warmup_seconds"] = round(time.perf_counter() - begin, 1)
        warm_misses = engine.compile_cache.stats()["misses"]
        plain, _ = _serve(engine, workload, draft=None)
        spec, summary = _serve(engine, workload, draft=NGramDraft(
            slots=engine.slots, k=engine.spec_k, ngram=3))
        stats = engine.compile_cache.stats()
        info[f"{tag}_compile_cache"] = stats
        info[f"{tag}_pool"] = {key: engine.pool_stats()[key] for key in
                               ("prefix_hit_rate", "cow_forks",
                                "peak_in_use", "capacity")}
        info[f"{tag}_acceptance_rate"] = round(
            summary.get("acceptance_rate", 0.0), 3)
        checks.expect(stats["misses"] == warm_misses
                      and stats["recompiles"] == 0,
                      f"{tag}: {stats['misses'] - warm_misses} executable(s) "
                      f"built and {stats['recompiles']} recompiled after "
                      f"warm-up")
        for handle, (_, max_new) in zip(plain + spec, workload + workload):
            checks.expect(handle.done and len(handle.generated) == max_new,
                          f"{tag}: request {handle.uid} ended "
                          f"{handle.state!r} with {len(handle.generated)} of "
                          f"{max_new} tokens")
        try:
            engine._pool.check()
        except AssertionError as exc:
            checks.expect(False, f"{tag}: block pool invariant broken: {exc}")
        checks.expect(info[f"{tag}_pool"]["cow_forks"] >= 1,
                      f"{tag}: the non-aligned shared prefix never forked")
        return plain, spec

    # --- the engine's default path at the model's own dtype: int8 pool,
    # kernel='auto'
    model, params = build(jnp.bfloat16, seed=0)
    engine = DecodeEngine(model, params, slots=size["slots"],
                          max_seq_len=size["serve_len"],
                          cache_layout="paged", kv_dtype="int8",
                          block_size=size["block_size"],
                          spec_k=size["spec_k"], cache_scope="smoke_int8")
    info["kernel"] = engine.kernel
    if not ctx["rehearse"]:
        checks.expect(engine.kernel == "fused",
                      f"kernel='auto' resolved to {engine.kernel!r} on the "
                      f"TPU, not the fused kernel")
    begin = time.perf_counter()
    plain, spec = serve_both_ways(engine, "int8")
    tokens = sum(len(h.generated) for h in plain + spec)
    info["int8_tokens_served"] = tokens
    info["int8_serve_seconds"] = round(time.perf_counter() - begin, 1)
    vocab_ok = all(0 <= int(tok) < size["vocab"]
                   for h in plain + spec for tok in h.generated)
    checks.expect(vocab_ok, "int8: a served token is outside the vocabulary")
    say(f"  int8 paged engine (kernel={engine.kernel}): {tokens} tokens "
        f"over {2 * len(workload)} requests, pool "
        f"{info['int8_pool']}, acceptance {info['int8_acceptance_rate']}")
    del engine, model, params, plain, spec

    # --- PR 8's exactness oracle on the chip: a float32 copy at
    # kv_dtype='model' must serve exactly generate()'s greedy tokens.
    # Full-precision matmuls for this pass: at the TPU's default (bf16
    # passes) the engine's and generate()'s differently-shaped programs
    # round differently and a random-init model's near-tie argmax flips.
    with jax.default_matmul_precision("highest"):
        model, params = build(jnp.float32, seed=1)
        engine = DecodeEngine(model, params, slots=size["slots"],
                              max_seq_len=size["serve_len"],
                              cache_layout="paged", kv_dtype="model",
                              block_size=size["block_size"],
                              spec_k=size["spec_k"], cache_scope="smoke_f32")
        plain, spec = serve_both_ways(engine, "f32")
        reference = jax.jit(
            lambda p, prompt, max_new: generate(model, p, prompt,
                                                max_new_tokens=max_new),
            static_argnums=2)
        mismatches = 0
        for index, (prompt, max_new) in enumerate(workload):
            want = np.asarray(reference(params, prompt[None], max_new))[0]
            for name, handle in (("plain", plain[index]),
                                 ("speculative", spec[index])):
                if not np.array_equal(handle.output, want):
                    mismatches += 1
                    say(f"  f32 request {index} ({name}) diverged from "
                        f"generate():\n    served   "
                        f"{handle.output[len(prompt):].tolist()}\n    "
                        f"generate {want[len(prompt):].tolist()}")
    info["f32_mismatches"] = mismatches
    checks.expect(mismatches == 0, f"{mismatches} of {2 * len(workload)} "
                  f"float32 outputs differ from per-request generate()")
    say(f"  f32 paged engine (kernel={engine.kernel}): "
        f"{2 * len(workload) - mismatches}/{2 * len(workload)} outputs "
        f"token-exact against generate()")
    return info


# ----------------------------------------------------------------------
# children
# ----------------------------------------------------------------------
def child_run(args: argparse.Namespace) -> int:
    device, cache_dir, size = _start_child(args)
    ctx = {"size": size, "out": args.out, "rehearse": args.rehearse,
           "compile_log": CompileLog()}
    record = {"device": device, "versions": _versions(),
              "compile_cache_dir": cache_dir, "rehearsal": args.rehearse,
              "size": size, "phases": {}}
    log = ctx["compile_log"]
    for name, phase in (("sync", phase_sync), ("kernels", phase_kernels),
                        ("trainer", phase_trainer),
                        ("server", phase_server)):
        say(f"phase {name} ...")
        checks = Checks(name)
        begin, compiled_before = time.perf_counter(), log.compile_seconds
        info = {}
        try:
            info = phase(ctx, checks)
        except Exception as exc:  # noqa: BLE001 — recorded, fails the run
            traceback.print_exc(file=sys.stdout)
            text = str(exc)  # the cause is often at the end of a long one
            if len(text) > 800:
                text = f"{text[:400]} ... {text[-400:]}"
            checks.failed.append(f"raised {type(exc).__name__}: {text}")
        record["phases"][name] = {
            "ok": not checks.failed, "failed": checks.failed,
            "seconds": round(time.perf_counter() - begin, 1),
            "compile_seconds": round(log.compile_seconds - compiled_before,
                                     1), **info}
        say(f"phase {name}: {'ok' if not checks.failed else 'FAILED'} in "
            f"{record['phases'][name]['seconds']}s")
    record["persistent_cache"] = {
        "hits": log.cache_hits, "misses": log.cache_misses,
        "largest_entry_bytes": _largest_file(cache_dir)}
    with open(os.path.join(args.out, "run.json"), "w") as f:
        json.dump(record, f, indent=1, default=str)
    return 0 if all(p["ok"] for p in record["phases"].values()) else 1


def child_warm(args: argparse.Namespace) -> int:
    """Second process: compile the same train step again. With a working
    compile cache this is a lookup."""
    _, cache_dir, size = _start_child(args)
    log = CompileLog()
    argv = _trainer_argv(size, args.out)
    _, seconds, _, _ = _compile_train_step(argv + ["epochs=2"])
    say(f"warm process: train step compiled in {seconds:.1f}s "
        f"({log.cache_hits} persistent-cache hits, {log.cache_misses} "
        f"misses)")
    with open(os.path.join(args.out, "warm.json"), "w") as f:
        json.dump({"compile_cache_dir": cache_dir,
                   "train_step_compile_seconds": round(seconds, 2),
                   "persistent_cache_hits": log.cache_hits,
                   "persistent_cache_misses": log.cache_misses}, f)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes on any backend: debugs this script, "
                             "proves nothing about the chip")
    parser.add_argument("--out", default=DEFAULT_OUT,
                        help="directory for run records and the trainer's "
                             "XP folder (emptied first)")
    parser.add_argument("--child", choices=("run", "warm"),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    args.out = os.path.abspath(args.out)
    if args.child == "run":
        return child_run(args)
    if args.child == "warm":
        return child_warm(args)
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
