# Benchmark harness. Prints ONE JSON line on stdout:
#   {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}
# The line is kept under MAX_LINE_CHARS (the driver records only a
# ~2,000-char stdout tail): extra carries whitelisted per-leg scalars,
# and the full record goes to BENCH_DETAIL.json next to this file.
# All diagnostics go to stderr.
#
# One process: it initializes the backend, refuses to run anywhere but
# on a TPU (a CPU timing under a device-metric name is worse than no
# number), runs the legs in order, and exits non-zero when the headline
# is missing or any leg or sub-leg recorded an error.
#
# Headline (BASELINE.json metric): CIFAR-10 ResNet-18 training
# throughput in images/sec/chip. extra carries the flagship Transformer
# LM numbers: tokens/sec/chip and MFU (analytic model FLOPs vs the
# chip's peak bf16 FLOPs), plus backend/platform diagnostics.
#
# The reference publishes no numbers (BASELINE.md), so vs_baseline for
# the headline is reported against REFERENCE_IMAGES_PER_SEC below — the
# widely reproduced single-GPU (V100-class) torch throughput ballpark
# for CIFAR ResNet-18 training (~3000 img/s at its throughput-optimal
# batch size); the self-grounded number is extra.lm.mfu.
"""flashy_tpu benchmark: CIFAR img/s/chip + Transformer-LM tokens/s + MFU."""
import json
import os
import sys
import time
import traceback
import typing as tp

REFERENCE_IMAGES_PER_SEC = 3000.0  # single-GPU torch reference ballpark

# Results land here as each leg completes, so a bench killed mid-run
# (driver timeout) still leaves its numbers. The state dir is
# overridable so concurrent runs don't race on the same files.
_STATE_DIR = os.environ.get("FLASHY_TPU_BENCH_STATE_DIR",
                            os.path.dirname(os.path.abspath(__file__)))
PARTIAL_PATH = os.path.join(_STATE_DIR, "BENCH_PARTIAL.json")
DETAIL_PATH = os.path.join(_STATE_DIR, "BENCH_DETAIL.json")

# Budget for the single stdout JSON line: the driver records only a
# ~2,000-char tail of stdout, so the line must stay comfortably inside
# it (worst case measured by
# test_compact_line_fits_driver_tail_worst_case).
MAX_LINE_CHARS = 1900


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _peak_for(device_kind: str):
    """Nominal peak bf16 FLOP/s for a device kind — read from the one
    peaks table (`observability.roofline.DEVICE_SPECS`); an accelerator
    missing from it raises."""
    from flashy_tpu.observability import device_peaks
    return device_peaks(device_kind)[0]


def _devices_used(tree) -> int:
    """How many devices hold the arrays of `tree` — the divisor for a
    per-chip rate. A computation without a mesh runs on ONE device
    however many the host has."""
    import jax
    devices = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            devices.update(leaf.sharding.device_set)
    return max(len(devices), 1)


def bench_smoke(jax, on_tpu: bool):
    """Fast first leg (<60s incl. compiles): prove the pallas kernels
    lower under Mosaic on the live backend and capture one
    flash-vs-dense fwd+bwd timing, one tiny LM train step, and one tiny
    CIFAR train step."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flashy_tpu.models import TransformerConfig, TransformerLM, resnet18
    from flashy_tpu.ops import attention as attn_mod

    out = {}
    rng = np.random.default_rng(0)
    b, t, h, d = (4, 1024, 8, 64) if on_tpu else (1, 256, 2, 32)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
               for _ in range(3))

    def fwd_bwd(fn):
        return jax.jit(jax.grad(lambda q, k, v: fn(q, k, v, causal=True)
                                .astype(jnp.float32).sum(), argnums=(0, 1, 2)))

    def time_once(grad_fn, reps: int = 5):
        jax.block_until_ready(grad_fn(q, k, v))  # compile + 1st run
        begin = time.perf_counter()
        for _ in range(reps):
            grads = grad_fn(q, k, v)
        jax.block_until_ready(grads)
        return (time.perf_counter() - begin) / reps

    dense_t = time_once(fwd_bwd(attn_mod.dot_product_attention))
    out["dense_ms"] = round(dense_t * 1e3, 3)
    if on_tpu:
        flash_t = time_once(fwd_bwd(attn_mod.flash_attention))
        out["flash_ms"] = round(flash_t * 1e3, 3)
        out["flash_speedup"] = round(dense_t / flash_t, 2)
        out["mosaic_ok"] = True  # a real (non-interpret) lowering ran
    out["attn_shape"] = [b, t, h, d]

    # one tiny LM train step (matmul/softmax/optimizer path end-to-end)
    cfg = TransformerConfig(vocab_size=512, dim=128, num_layers=2,
                            num_heads=4, attention="flash" if on_tpu else "dense")
    model = TransformerLM(cfg)
    tokens = jnp.asarray(rng.integers(0, 512, (2, 256)), jnp.int32)
    params = {"params": model.init(jax.random.PRNGKey(0), tokens)["params"]}
    optim = optax.adamw(1e-4)

    def lm_step(params, opt_state, tokens):
        def loss_fn(variables):
            logits = model.apply(variables, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optim.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(lm_step)
    p2, o2, loss = step(params, optim.init(params), tokens)
    jax.block_until_ready(loss)
    begin = time.perf_counter()
    for _ in range(5):
        p2, o2, loss = step(p2, o2, tokens)
    jax.block_until_ready(loss)
    out["lm_step_ms"] = round((time.perf_counter() - begin) / 5 * 1e3, 2)
    assert np.isfinite(float(loss))

    # one tiny CIFAR train step (conv/batchnorm path)
    rmodel = resnet18(num_classes=10)
    variables = rmodel.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                            train=False)
    images = jnp.asarray(rng.normal(size=(32, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, 32), jnp.int32)

    def cifar_step(params, batch_stats):
        def loss_fn(p):
            logits, mutated = rmodel.apply(
                {"params": p, "batch_stats": batch_stats},
                images, train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(), mutated
        (loss, mutated), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, grads

    cstep = jax.jit(cifar_step)
    loss, grads = cstep(variables["params"], variables["batch_stats"])
    jax.block_until_ready(loss)
    begin = time.perf_counter()
    for _ in range(5):
        loss, grads = cstep(variables["params"], variables["batch_stats"])
    jax.block_until_ready(loss)
    out["cifar_step_ms"] = round((time.perf_counter() - begin) / 5 * 1e3, 2)
    log(f"smoke: dense {out['dense_ms']}ms"
        + (f", flash {out['flash_ms']}ms" if "flash_ms" in out else "")
        + f", lm step {out['lm_step_ms']}ms, cifar step {out['cifar_step_ms']}ms")
    return out


def bench_mxu(jax, peak_flops, on_tpu=True):
    """Measured best-case bf16 matmul rate of the attached chip.

    The nominal peak is a datasheet number; this measured ceiling is
    what LM MFU is also read against (`lm.mfu_vs_measured`)."""
    import jax.numpy as jnp

    # Best across several trials and sizes: the "ceiling" must be the
    # best the chip ever delivers, so take the max.
    key = jax.random.PRNGKey(0)
    best = (0.0, 0, 0.0)   # (tflops, n, per_matmul)
    for n in (4096, 8192) if on_tpu else (1024,):
        a = (jax.random.normal(key, (n, n))
             * (1.0 / n ** 0.5)).astype(jnp.bfloat16)
        reps = 30 if n <= 4096 else 8

        def chain(x, a=a, reps=reps):
            # dependent chain inside ONE dispatch: no per-op launch gap
            return jax.lax.fori_loop(0, reps, lambda i, y: a @ y, x)

        f = jax.jit(chain)
        jax.block_until_ready(f(a))
        for _ in range(3):
            begin = time.perf_counter()
            out = f(a)
            jax.block_until_ready(out)
            per_matmul = (time.perf_counter() - begin) / reps
            tflops = 2 * n ** 3 / per_matmul / 1e12
            if tflops > best[0]:
                best = (tflops, n, per_matmul)
    tflops, n, per_matmul = best
    log(f"mxu: {tflops:.1f} TFLOP/s measured bf16 matmul peak "
        f"({per_matmul * 1e3:.2f} ms per {n}^3, best of trials)")
    return {"measured_bf16_tflops": round(tflops, 2),
            "matmul_n": n,
            "pct_of_nominal_peak": (round(tflops * 1e12 / peak_flops * 100, 1)
                                    if peak_flops else None)}


def bench_host_sync(jax, on_tpu: bool):
    """Per-call cost of staging a model-sized tree device→host→device —
    the built-in overhead of the host-mediated `average_tensors` /
    `sync_model` parity path that the in-graph `wrap()` route avoids
    entirely (distrib.py warns after repeated large calls; this leg
    gives the docs a number to quote)."""
    import jax.numpy as jnp
    import numpy as np

    n = 25_000_000 if on_tpu else 2_000_000  # ~100 / 8 MB f32
    tree = {f"w{i}": jnp.ones((n // 8,), jnp.float32) for i in range(8)}
    jax.block_until_ready(tree)
    reps = 5
    begin = time.perf_counter()
    for _ in range(reps):
        host = {k: np.asarray(jax.device_get(v)) for k, v in tree.items()}
        back = {k: jnp.asarray(v) for k, v in host.items()}
        jax.block_until_ready(back)
    elapsed = (time.perf_counter() - begin) / reps
    mib = n * 4 / 2**20
    log(f"host-sync staging: {elapsed * 1e3:.1f} ms per {mib:.0f} MiB round "
        f"trip ({mib / 1024 / elapsed:.2f} GiB/s)")
    return {"stage_ms_per_roundtrip": round(elapsed * 1e3, 1),
            "tree_mib": round(mib, 1),
            "gib_per_sec": round(mib / 1024 / elapsed, 2)}


def bench_cifar(jax, on_tpu: bool):
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flashy_tpu.models import resnet18
    from flashy_tpu.parallel import make_mesh, wrap
    from flashy_tpu.data import prefetch_to_device

    batch_size = 512 if on_tpu else 64
    warmup, measure = (5, 30) if on_tpu else (2, 5)

    devices = jax.devices()
    mesh = make_mesh({"data": len(devices)})
    model = resnet18(num_classes=10)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False)
    optim = optax.sgd(0.1, momentum=0.9, nesterov=True)
    state = {
        "params": variables["params"],
        "batch_stats": variables["batch_stats"],
        "opt_state": optim.init(variables["params"]),
    }

    def step(state, batch):
        def loss_fn(params):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": state["batch_stats"]},
                batch["image"], train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["label"]).mean()
            return loss, mutated["batch_stats"]

        (loss, batch_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        updates, opt_state = optim.update(grads, state["opt_state"])
        return ({"params": optax.apply_updates(state["params"], updates),
                 "batch_stats": batch_stats, "opt_state": opt_state},
                {"loss": loss})

    train_step = wrap(step, mesh=mesh, batch_axes=("data",))

    rng = np.random.default_rng(0)
    host_batches = [{
        "image": rng.normal(size=(batch_size, 32, 32, 3)).astype(np.float32),
        "label": rng.integers(0, 10, batch_size).astype(np.int32),
    } for _ in range(4)]

    # Stage the cycling batches in HBM ONCE (one prefetch pass), then
    # iterate over device-resident arrays: the leg times the training
    # step, not the host→device link (extra.host_sync measures that).
    device_batches = list(prefetch_to_device(
        iter(host_batches), size=2, mesh=mesh, batch_axes=("data",)))

    def batch_stream(n_steps):
        return (device_batches[i % len(device_batches)]
                for i in range(n_steps))

    for batch in batch_stream(warmup):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics["loss"])

    begin = time.perf_counter()
    for batch in batch_stream(measure):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics["loss"])
    elapsed = time.perf_counter() - begin

    per_chip = measure * batch_size / elapsed / len(devices)
    log(f"cifar: {per_chip:.1f} img/s/chip (batch {batch_size}, {measure} steps)")
    return {"images_per_sec_per_chip": round(per_chip, 1),
            "batch_size": batch_size}


def _measure_lm_config(jax, overrides, batch, seq, dims, warmup, measure,
                       peak_flops, measured_flops):
    """One LM training-throughput measurement at a given config."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flashy_tpu.models import TransformerConfig, TransformerLM

    dim, layers, heads, vocab = dims
    overrides = dict(overrides)
    loss_mode = overrides.pop("loss", "dense")
    cfg = TransformerConfig(vocab_size=vocab, dim=dim, num_layers=layers,
                            num_heads=heads, **overrides)
    model = TransformerLM(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"]}
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))

    optim = optax.adamw(1e-4)
    state = {"params": params, "opt_state": optim.init(params)}

    def train_step(state, tokens):
        def loss_fn(variables):
            from flashy_tpu.ops import lm_next_token_loss
            return lm_next_token_loss(model, variables, tokens,
                                      mode=loss_mode)

        loss, grads = jax.value_and_grad(loss_fn)(state["params"])
        updates, opt_state = optim.update(grads, state["opt_state"],
                                          state["params"])
        return ({"params": optax.apply_updates(state["params"], updates),
                 "opt_state": opt_state}, loss)

    step = jax.jit(train_step, donate_argnums=(0,))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)

    # compile explicitly so the variant's per-device memory footprint
    # lands in the record (the OOM boundary between remat/no-remat/
    # chunked-CE configs is part of the perf story)
    from flashy_tpu.parallel import memory_stats
    step = step.lower(state, tokens).compile()
    mem = memory_stats(step)

    for _ in range(warmup):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)

    begin = time.perf_counter()
    for _ in range(measure):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - begin

    n_chips = len(jax.devices())
    tokens_per_sec = measure * batch * seq / elapsed
    tokens_per_sec_per_chip = tokens_per_sec / n_chips
    # Analytic train FLOPs/token: 6*P for the matmuls (fwd+bwd), plus
    # causal attention 6*L*T*dim (12*L*T*dim for full attention, halved
    # for the causal mask).
    flops_per_token = 6.0 * n_params + 6.0 * layers * seq * dim
    achieved = flops_per_token * tokens_per_sec / n_chips
    mfu = round(achieved / peak_flops, 4) if peak_flops else None
    # vs the chip's MEASURED matmul rate (bench_mxu). main() re-derives this against the capture-wide honest ceiling
    # (max of every sustained rate in the run) before publishing.
    mfu_measured = (round(achieved / measured_flops, 4)
                    if measured_flops else None)
    # The human line mirrors the JSON keys: `mfu` is vs the NOMINAL
    # peak and legitimately absent on backends without one (CPU
    # fallback), in which case the measured-peak figure IS the headline
    # — "MFU=None (vs measured peak: 0.31)" read like a broken record
    # when the JSON right next to it carried a real number.
    if mfu is not None:
        mfu_text = f"MFU={mfu} (vs measured peak: {mfu_measured})"
    elif mfu_measured is not None:
        mfu_text = (f"MFU={mfu_measured} vs measured peak "
                    f"(no nominal peak for this backend)")
    else:
        mfu_text = "MFU=n/a (no peak reference)"
    log(f"lm[{overrides.get('attention')},remat={overrides.get('remat')},"
        f"b={batch}]: {tokens_per_sec_per_chip:.0f} tok/s/chip, "
        f"{achieved / 1e12:.1f} TFLOP/s/chip, {mfu_text} "
        f"({n_params / 1e6:.0f}M params, seq {seq}, batch {batch})")
    result = {"tokens_per_sec_per_chip": round(tokens_per_sec_per_chip, 1),
              "mfu": mfu, "mfu_vs_measured": mfu_measured,
              "achieved_tflops_per_chip": round(achieved / 1e12, 2),
              "n_params": n_params, "seq_len": seq, "batch_size": batch}
    if mem.get("peak"):
        result["hbm_peak_gib"] = round(mem["peak"] / 2**30, 3)
    return result


# flash+remat at b=16: the configuration that never OOMs the 16G v5e
# (dense/no-remat at this size needs 16.7G HBM).
_LM_DEFAULT = (dict(attention="flash", remat=True), 16)


def bench_lm(jax, on_tpu: bool, peak_flops, measured_flops=None):
    if on_tpu:
        dims, seq = (1024, 12, 16, 32768), 1024
        warmup, measure = 3, 10
        overrides, batch = dict(_LM_DEFAULT[0]), _LM_DEFAULT[1]
        variant = "default"
    else:
        dims, seq = (128, 2, 4, 512), 128
        warmup, measure = 1, 3
        overrides, batch = dict(attention="dense", remat=False), 4
        variant = "cpu-tiny"

    result = _measure_lm_config(jax, overrides, batch, seq, dims,
                                warmup, measure, peak_flops, measured_flops)
    result["variant"] = variant

    # --- tensor-parallel sub-leg: the same training math at tensor
    # widths {1,2,4} (parallel/tensor.py), inline over the attached
    # devices. The stepwise record is the MFU trajectory the
    # training-path push is judged by: tensor width x remat policy x
    # tuned backward tiles.
    try:
        from flashy_tpu.parallel.tensor import run_tp_bench
        tp_result = run_tp_bench(steps=3)
        result["tp"] = tp_result
        for width, ms in tp_result.get("step_ms", {}).items():
            result[f"tp_step_ms_t{width}"] = ms
        for width, tflops in tp_result.get("tflops_per_chip", {}).items():
            result[f"tp_tflops_t{width}"] = tflops
        if "opt_bytes_ratio" in tp_result:
            result["tp_opt_bytes_ratio"] = tp_result["opt_bytes_ratio"]
        if "flash_bwd_parity" in tp_result:
            result["tp_flash_bwd_parity"] = tp_result["flash_bwd_parity"]

        from flashy_tpu.ops import (lookup_remat_policy,
                                    lookup_tuned_bwd_blocks)
        dim, layers, heads, vocab = dims
        tuned = lookup_tuned_bwd_blocks(
            batch, seq, heads, dim // heads, causal=True)
        peak = peak_flops or measured_flops
        result["mfu_trajectory"] = {
            "tensor_widths": tp_result.get("widths"),
            "tflops_per_chip": tp_result.get("tflops_per_chip"),
            "mfu": ({w: round(t * 1e12 / peak, 4) for w, t
                     in tp_result.get("tflops_per_chip", {}).items()}
                    if peak else None),
            "remat_policy": lookup_remat_policy("lm"),
            "tuned_bwd_blocks": list(tuned) if tuned else None,
        }

        if on_tpu:
            # fused one-pass flash backward vs the split two-kernel
            # path, real kernels (interpreter timings would measure
            # the interpreter)
            import jax.numpy as jnp
            import numpy as np
            from flashy_tpu.ops import attention as attn_mod
            b, t, h, d = 4, 2048, 16, 64
            rng = np.random.default_rng(0)
            q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)),
                                   jnp.bfloat16) for _ in range(3))

            def timed_bwd(fused):
                grad = jax.jit(jax.grad(
                    lambda q, k, v: attn_mod.flash_attention(
                        q, k, v, causal=True, fused_backward=fused)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
                jax.block_until_ready(grad(q, k, v))
                begin = time.perf_counter()
                for _ in range(10):
                    out = grad(q, k, v)
                jax.block_until_ready(out)
                return (time.perf_counter() - begin) / 10

            fused_s, unfused_s = timed_bwd(True), timed_bwd(False)
            result["flash_bwd_fused_ms"] = round(fused_s * 1e3, 2)
            result["flash_bwd_unfused_ms"] = round(unfused_s * 1e3, 2)
            result["flash_bwd_vs_unfused"] = round(unfused_s / fused_s, 3)
            if fused_s > unfused_s:
                # the TPU gate, the decode leg's fused_violation rule: a
                # fused kernel slower than the pair it replaces is a
                # regression, not a data point
                result["fused_bwd_violation"] = (
                    f"fused bwd {fused_s * 1e3:.1f}ms > split "
                    f"{unfused_s * 1e3:.1f}ms at [{b},{t},{h},{d}]")
        log(f"lm tp: step_ms={tp_result.get('step_ms')}, opt bytes "
            f"ratio {tp_result.get('opt_bytes_ratio')}, flash bwd "
            f"parity {tp_result.get('flash_bwd_parity')}"
            + (f", fused bwd {result['flash_bwd_vs_unfused']}x vs split"
               if "flash_bwd_vs_unfused" in result else ""))
    except Exception as exc:  # noqa: BLE001 — keep the headline, record the failure
        traceback.print_exc(file=sys.stderr)
        result["tp"] = {"error": str(exc)[:200]}
    return result


def bench_flash_attention(jax, on_tpu: bool):
    """Pallas flash attention vs XLA dense attention, fwd+bwd step time."""
    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.ops import attention as attn_mod

    if on_tpu:
        b, h, t, d = 4, 16, 2048, 64
        reps = 10
    else:
        b, h, t, d = 1, 2, 256, 32
        reps = 2
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
               for _ in range(3))

    def timed(fn):
        grad = jax.jit(jax.grad(lambda q, k, v: fn(q, k, v, causal=True)
                                .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        jax.block_until_ready(grad(q, k, v))
        begin = time.perf_counter()
        for _ in range(reps):
            out = grad(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - begin) / reps

    try:
        dense_t = timed(attn_mod.dot_product_attention)
        # Only label a 'flash' timing when the pallas kernel actually
        # runs: on GPU backends flash_attention falls back to the dense
        # path and the comparison would be meaningless.
        flash_t = tuned_t = blocks = None
        if jax.default_backend() == "tpu":
            flash_t = timed(attn_mod.flash_attention)  # default 256/256
            from flashy_tpu.ops import tune_flash_blocks
            blocks = tune_flash_blocks(b, t, h, d, causal=True)
            bq, bk = blocks
            tuned_t = timed(lambda q, k, v, causal: attn_mod.flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk))
    except Exception as exc:  # noqa: BLE001
        log(f"flash-attention bench skipped: {exc}")
        return {"error": str(exc)[:200]}
    result = {"dense_ms": round(dense_t * 1e3, 2),
              "shape": [b, t, h, d]}
    if flash_t is not None:
        result["flash_ms"] = round(flash_t * 1e3, 2)
        result["speedup"] = round(dense_t / flash_t, 2)
    if tuned_t is not None:
        result["flash_tuned_ms"] = round(tuned_t * 1e3, 2)
        result["tuned_blocks"] = list(blocks)
    log(f"attention fwd+bwd: dense {result['dense_ms']}ms"
        + (f", flash {result['flash_ms']}ms" if flash_t else ""))
    return result


def bench_decode(jax, on_tpu: bool):
    """KV-cache autoregressive generation throughput (tokens/s/chip) on
    the flagship LM layout — the serving-side counterpart of the lm
    training leg."""
    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.models.decoding import generate

    if on_tpu:
        dim, layers, heads, vocab = 1024, 12, 16, 32768
        batch, prompt_len, new_tokens = 8, 128, 128
    else:
        dim, layers, heads, vocab = 128, 2, 4, 512
        batch, prompt_len, new_tokens = 2, 16, 16
    # max_seq_len only caps cache allocation (generate() sizes its cache
    # at prompt+new); headroom beyond the baseline shapes lets the
    # speculative serving sub-leg run generations long enough for the
    # draft's steady state to show. CPU fallback measures in f32: bf16
    # is emulated (slow) there and its near-tie argmax is shape-
    # sensitive, which would make the greedy stream inconsistent
    # between the [S, 1] decode and [S, k+1] verify executables.
    cfg = TransformerConfig(vocab_size=vocab, dim=dim, num_layers=layers,
                            num_heads=heads, attention="dense",
                            max_seq_len=max(prompt_len + new_tokens, 64),
                            dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, prompt_len)),
                         jnp.int32)

    run = jax.jit(lambda params, prompt: generate(
        model, params, prompt, max_new_tokens=new_tokens))
    jax.block_until_ready(run(params, prompt))  # compile
    reps = 3
    begin = time.perf_counter()
    for _ in range(reps):
        out = run(params, prompt)
    jax.block_until_ready(out)
    elapsed = (time.perf_counter() - begin) / reps
    tok_s = batch * new_tokens / elapsed / _devices_used(out)
    log(f"decode: {tok_s:.0f} tok/s/chip (batch {batch}, "
        f"{new_tokens} new tokens, {elapsed * 1e3:.0f}ms per call)")
    result = {"tokens_per_sec_per_chip": round(tok_s, 1),
              "batch_size": batch, "new_tokens": new_tokens,
              "ms_per_generate": round(elapsed * 1e3, 1)}

    # --- speculative serving: spec-off vs spec-on tok/s through the
    # slot engine on a repetitive corpus (prompt-lookup's home turf —
    # templated text / code; the draft costs no device work, the
    # [S, k+1] verify step amortizes the per-step launch + full
    # cache-read cost over accepted+1 tokens).
    try:
        from flashy_tpu.serve import (ContinuousBatchingScheduler,
                                      DecodeEngine, NGramDraft)

        slots = batch
        spec_k = 4
        serve_prompt = 8
        serve_new = min(new_tokens * 3, cfg.max_seq_len - serve_prompt * 2)
        # Repetitive corpus = prompts whose greedy continuation stays
        # repetitive (<= 3 distinct tokens over the tail) — the
        # prompt-lookup regime (templated text, copy/extraction tasks)
        # this technique is deployed for. Screened against the model
        # itself; random-init models vary, so cap the attempts.
        corpus_rng = np.random.default_rng(7)
        screen = jax.jit(lambda params, p: generate(
            model, params, p, max_new_tokens=serve_new))
        workload = []
        tried = 0
        while len(workload) < slots * 4 and tried < slots * 32:
            tried += 1
            period = int(corpus_rng.integers(1, 4))
            pattern = corpus_rng.integers(0, vocab, period)
            prompt = np.tile(pattern, serve_prompt // period + 1)\
                [:serve_prompt].astype(np.int32)
            tail = np.asarray(screen(params, prompt[None])
                              )[0][serve_prompt:][-serve_new // 2:]
            if len(set(tail.tolist())) <= 3:
                workload.append((prompt, serve_new))
        if not workload:  # pathological init: measure unscreened
            workload = [(np.tile(corpus_rng.integers(0, vocab, 2), 4)
                         .astype(np.int32), serve_new)
                        for _ in range(slots * 4)]

        def serve_run(spec: bool):
            engine = DecodeEngine(
                model, params, slots=slots,
                max_seq_len=cfg.max_seq_len,
                spec_k=spec_k if spec else None)
            engine.warmup(prompt_lengths=[len(p) for p, _ in workload])
            draft = (NGramDraft(slots=slots, k=spec_k, ngram=3)
                     if spec else None)
            scheduler = ContinuousBatchingScheduler(engine, draft=draft,
                                                    max_queue=len(workload))
            handles = [scheduler.submit(p, m) for p, m in workload]
            begin = time.perf_counter()
            scheduler.run()
            wall = time.perf_counter() - begin
            tokens = sum(len(h.generated) for h in handles)
            assert engine.compile_cache.stats()["recompiles"] == 0
            return tokens / wall / _devices_used(params), \
                scheduler.metrics.summary()

        off_tok_s, off_summary = serve_run(spec=False)
        on_tok_s, on_summary = serve_run(spec=True)
        result.update({
            "engine_tokens_per_sec_per_chip": round(off_tok_s, 1),
            "spec_tokens_per_sec_per_chip": round(on_tok_s, 1),
            "spec_speedup": round(on_tok_s / off_tok_s, 2),
            "spec_k": spec_k,
            "acceptance_rate": round(on_summary["acceptance_rate"], 3),
            "itl_ms_p50": round(on_summary["itl_ms_p50"], 3),
            "itl_ms_p95": round(on_summary["itl_ms_p95"], 3),
            "itl_ms_p95_spec_off": round(off_summary["itl_ms_p95"], 3),
        })
        log(f"decode spec: {off_tok_s:.0f} -> {on_tok_s:.0f} tok/s/chip "
            f"({on_tok_s / off_tok_s:.2f}x, acceptance "
            f"{on_summary['acceptance_rate'] * 100:.0f}%, itl p95 "
            f"{on_summary['itl_ms_p95']:.2f}ms)")
    except Exception as exc:  # noqa: BLE001  (serve leg is additive)
        log(f"decode speculative sub-leg skipped: {exc}")
        result["spec_error"] = str(exc)[:200]

    # --- paged KV cache: paged+int8 vs dense through the slot engine
    # at EQUAL batch (the tok/s parity check), plus the capacity story:
    # bytes reserved per slot and how many concurrent requests of this
    # workload fit the dense layout's HBM budget under each layout.
    # Workload: a shared system prompt + per-request tails — the
    # prefix-cache regime (system prompts, few-shot headers) paging
    # exists for.
    try:
        from flashy_tpu.ops.paged_attention import block_bytes
        from flashy_tpu.serve import (ContinuousBatchingScheduler,
                                      DecodeEngine)

        slots = batch
        block_size = 16 if on_tpu else 8
        sys_len = 2 * block_size + block_size // 2  # partial block: COW
        # decode long enough that the timed steady-state window (all
        # slots live, pure decode) dominates timer noise
        paged_new = cfg.max_seq_len - sys_len - block_size
        corpus_rng = np.random.default_rng(11)
        system = corpus_rng.integers(0, vocab, sys_len).astype(np.int32)
        paged_workload = []
        for _ in range(slots * 4):
            tail = corpus_rng.integers(
                0, vocab, int(corpus_rng.integers(2, block_size))
            ).astype(np.int32)
            paged_workload.append((np.concatenate([system, tail]),
                                   paged_new))

        def paged_serve_run(layout: str, kernel: str = "auto"):
            # the parity claim is DECODE throughput at equal batch, so
            # the timed window starts once every slot is live (prefill
            # differs by construction: one bucketed call dense vs
            # `prompt/chunk` chunk calls paged — a TTFT trade, not a
            # steady-state cost) and ends at the synchronized
            # retirement; a second, untimed wave then measures the
            # capacity/prefix story under slot turnover.
            engine = DecodeEngine(
                model, params, slots=slots, max_seq_len=cfg.max_seq_len,
                cache_layout=layout, block_size=block_size,
                kv_dtype="int8" if layout == "paged" else "model",
                kernel=kernel if layout == "paged" else "gather",
                cache_scope=f"bench_{layout}_{kernel}")
            engine.warmup(
                prompt_lengths=[len(p) for p, _ in paged_workload])
            scheduler = ContinuousBatchingScheduler(
                engine, max_queue=len(paged_workload))
            best = 0.0
            for wave in range(3):  # best-of-3 synchronized waves
                handles = [scheduler.submit(p, m)
                           for p, m in paged_workload[:slots]]
                while any(h.state in ("queued", "prefilling")
                          for h in handles):
                    scheduler.step()
                decoded = sum(len(h.generated) for h in handles)
                begin = time.perf_counter()
                scheduler.run()
                wall = time.perf_counter() - begin
                tokens = sum(len(h.generated) for h in handles) - decoded
                best = max(best, tokens / wall)
            for p, m in paged_workload[slots:]:  # capacity wave, untimed
                scheduler.submit(p, m)
            scheduler.run()
            assert engine.compile_cache.stats()["recompiles"] == 0
            return (best / _devices_used(params), engine,
                    scheduler.metrics.summary())

        dense_tok_s, dense_eng, _ = paged_serve_run("dense")
        paged_tok_s, paged_eng, paged_summary = paged_serve_run(
            "paged", kernel="gather")
        per_block = block_bytes(cfg, block_size, "int8")
        pool = paged_eng.pool_stats()
        budget = dense_eng.cache_bytes()
        dense_per_slot = budget / slots
        # average private (non-shared) blocks one request of this
        # workload costs — the marginal HBM price of one more slot
        # (3 parity waves of `slots` + the capacity wave all allocated)
        admissions = 3 * slots + len(paged_workload) - slots
        fresh_per_req = pool["allocated_total"] / admissions
        paged_per_slot = fresh_per_req * per_block
        result.update({
            "paged_tokens_per_sec_per_chip": round(paged_tok_s, 1),
            "paged_vs_dense": round(paged_tok_s / dense_tok_s, 3),
            "kv_bytes_per_slot_dense": int(dense_per_slot),
            "kv_bytes_per_slot": int(paged_per_slot),
            "max_concurrent_slots_at_fixed_hbm": int(
                budget // max(paged_per_slot, 1)),
            "max_concurrent_slots_at_fixed_hbm_dense": slots,
            "prefix_hit_rate": round(
                paged_summary.get("prefix_hit_rate", 0.0), 3),
            "paged_block_size": block_size,
            "paged_cow_forks": int(pool["cow_forks"]),
        })
        log(f"decode paged: {dense_tok_s:.0f} (dense) -> "
            f"{paged_tok_s:.0f} (paged int8) tok/s/chip "
            f"({paged_tok_s / dense_tok_s:.2f}x at equal batch), "
            f"{dense_per_slot / 1024:.0f} -> {paged_per_slot / 1024:.0f} "
            f"KiB/slot, {result['max_concurrent_slots_at_fixed_hbm']} "
            f"slots at the dense {slots}-slot budget, prefix hit "
            f"{result['prefix_hit_rate'] * 100:.0f}%")

        # --- fused Pallas paged decode: same workload, same engine
        # geometry, pool reads through ops/paged_decode.py. On TPU the
        # gate is fused >= gather at equal batch (the whole point of
        # the kernel: close paged toward >= dense tok/s); the CPU
        # fallback runs the kernel in interpret mode, where timings
        # measure the interpreter, so the subleg records token PARITY
        # and is non-gating. The analytic decode-side HBM bytes/token
        # rides along: tok/s x bytes/token is the bandwidth the decode
        # actually demands — the number that says "bandwidth-bound"
        # instead of asserting it.
        try:
            from flashy_tpu.ops.paged_decode import (
                decode_read_bytes_per_token)

            fused_tok_s, _, _ = paged_serve_run("paged", kernel="fused")
            # steady-state decode context of this workload: the full
            # per-request budget (prompt + generated), mid-generation
            mean_context = int(np.mean(
                [len(p) + m // 2 for p, m in paged_workload[:slots]]))
            kv_bytes_tok = decode_read_bytes_per_token(
                cfg, mean_context, "int8")
            result.update({
                "fused_tokens_per_sec_per_chip": round(fused_tok_s, 1),
                "fused_vs_gather": round(fused_tok_s / paged_tok_s, 3),
                "fused_interpret": not on_tpu,
                "kv_read_bytes_per_token": int(kv_bytes_tok),
                "kv_read_bytes_per_token_model": int(
                    decode_read_bytes_per_token(cfg, mean_context,
                                                "model")),
                "fused_hbm_gb_per_sec": round(
                    fused_tok_s * kv_bytes_tok / 1e9, 3),
            })
            if on_tpu and fused_tok_s < paged_tok_s:
                # the TPU gate: a fused kernel slower than the gather
                # it replaces is a regression, not a data point
                result["fused_violation"] = (
                    f"fused {fused_tok_s:.0f} < gather "
                    f"{paged_tok_s:.0f} tok/s/chip at equal batch")
            log(f"decode fused: {paged_tok_s:.0f} (gather) -> "
                f"{fused_tok_s:.0f} (fused) tok/s/chip "
                f"({fused_tok_s / paged_tok_s:.2f}x"
                f"{', interpret mode — non-gating' if not on_tpu else ''}"
                f"), {kv_bytes_tok / 1024:.1f} KiB/token decode-side "
                f"KV read at context {mean_context}")
        except Exception as exc:  # noqa: BLE001  (subleg is additive)
            log(f"decode fused sub-leg skipped: {exc}")
            result["fused_error"] = str(exc)[:200]
    except Exception as exc:  # noqa: BLE001  (serve leg is additive)
        log(f"decode paged sub-leg skipped: {exc}")
        result["paged_error"] = str(exc)[:200]

    # --- SSD mixer: the constant-memory decode state. Same flagship
    # geometry with every mixer a state-space layer, served through
    # cache_layout='ssd' — tok/s at equal batch rides along, but the
    # story this subleg records is capacity: state bytes per slot is
    # INDEPENDENT of max_seq_len (one [H, Dh, Dstate] f32 tensor per
    # layer), so at the dense layout's HBM budget the slot count beats
    # the paged-int8 baseline and keeps growing with context length
    # while paged's shrinks.
    try:
        from flashy_tpu.serve import (ContinuousBatchingScheduler,
                                      DecodeEngine)
        from flashy_tpu.serve.engine import state_bytes_per_slot

        slots = batch
        block_size = 16 if on_tpu else 8
        ssd_cfg = TransformerConfig(
            vocab_size=vocab, dim=dim, num_layers=layers,
            num_heads=heads, attention="dense",
            max_seq_len=cfg.max_seq_len, dtype=cfg.dtype,
            mixer="ssd", ssd_state_dim=16)
        ssd_model = TransformerLM(ssd_cfg)
        ssd_params = {"params": ssd_model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]}
        corpus_rng = np.random.default_rng(13)
        ssd_new = cfg.max_seq_len - 16
        ssd_workload = [
            (corpus_rng.integers(0, vocab, 8).astype(np.int32), ssd_new)
            for _ in range(slots)]

        engine = DecodeEngine(ssd_model, ssd_params, slots=slots,
                              max_seq_len=cfg.max_seq_len,
                              cache_layout="ssd", cache_scope="bench_ssd")
        engine.warmup(prompt_lengths=[len(p) for p, _ in ssd_workload])
        scheduler = ContinuousBatchingScheduler(
            engine, max_queue=len(ssd_workload))
        best = 0.0
        for _ in range(3):  # best-of-3 synchronized decode waves
            handles = [scheduler.submit(p, m) for p, m in ssd_workload]
            while any(h.state in ("queued", "prefilling")
                      for h in handles):
                scheduler.step()
            decoded = sum(len(h.generated) for h in handles)
            begin = time.perf_counter()
            scheduler.run()
            wall = time.perf_counter() - begin
            tokens = sum(len(h.generated) for h in handles) - decoded
            best = max(best, tokens / wall)
        assert engine.compile_cache.stats()["recompiles"] == 0
        ssd_tok_s = best / _devices_used(ssd_params)

        # capacity at the dense layout's HBM budget for `slots` slots
        # of this geometry, against the paged-int8 row above: paged
        # reserves max_seq_len/block_size blocks per slot (grows with
        # context), ssd reserves one fixed state per layer
        ssd_per_slot = engine.state_bytes_per_slot()
        budget = slots * state_bytes_per_slot(cfg, cfg.max_seq_len,
                                              "dense")
        paged_per_slot = state_bytes_per_slot(
            cfg, cfg.max_seq_len, "paged", kv_dtype="int8",
            block_size=block_size)
        result.update({
            "ssd_tokens_per_sec_per_chip": round(ssd_tok_s, 1),
            "ssd_state_bytes_per_slot": int(ssd_per_slot),
            "ssd_max_concurrent_slots_at_fixed_hbm": int(
                budget // ssd_per_slot),
            "ssd_paged_slots_at_same_budget": int(
                budget // paged_per_slot),
            "ssd_state_dim": int(ssd_cfg.ssd_state_dim),
        })
        log(f"decode ssd: {ssd_tok_s:.0f} tok/s/chip at equal batch, "
            f"{ssd_per_slot / 1024:.1f} KiB/slot decode state "
            f"(context-independent) -> "
            f"{result['ssd_max_concurrent_slots_at_fixed_hbm']} slots "
            f"at the dense {slots}-slot budget vs "
            f"{result['ssd_paged_slots_at_same_budget']} paged-int8 "
            f"at context {cfg.max_seq_len}")
    except Exception as exc:  # noqa: BLE001  (subleg is additive)
        log(f"decode ssd sub-leg skipped: {exc}")
        result["ssd_error"] = str(exc)[:200]
    return result


def bench_fleet(jax, on_tpu: bool):
    """Serving-fleet scaling: aggregate tok/s/chip through the router-
    fronted deployment at 1 vs 2 vs 4 engines, plus shed rate and TTFT
    p95 under an over-admission burst (every request submitted up
    front against a finite tenant quota — the door sheds the
    overflow, the survivors' TTFT shows the queueing cost)."""
    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.serve.fleet import (QuotaManager, ServingFleet,
                                        TenantQuota)

    if on_tpu:
        dim, layers, heads, vocab = 512, 4, 8, 4096
        slots, new_tokens = 8, 32
    else:
        dim, layers, heads, vocab = 128, 2, 4, 512
        slots, new_tokens = 4, 12
    cfg = TransformerConfig(vocab_size=vocab, dim=dim, num_layers=layers,
                            num_heads=heads, attention="dense",
                            max_seq_len=64,
                            dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    system = rng.integers(0, vocab, 16).astype(np.int32)

    quota_cap = 2 * 4 * slots  # vs the 4-engine fleet's slot count
    # one shared-system-prompt burst, identical for every fleet size
    # (fair scaling comparison), sized to over-admit (3x the quota
    # cap) so the shed path is always exercised
    prompts = []
    for i in range(3 * quota_cap):
        tail = rng.integers(0, vocab, 3 + i % 6).astype(np.int32)
        prompts.append(np.concatenate([system, tail])
                       if i % 2 == 0 else tail)
    # every engine of the fleet lives on the params' device: the fleet
    # has no device argument yet (ROADMAP R7)
    chips = _devices_used(params)
    result = {}
    per_engines = {}
    for engines in (1, 2, 4):
        fleet = ServingFleet.build(
            model, params, engines=engines, slots=slots, block_size=16,
            max_queue=4 * quota_cap, kernel="fused" if on_tpu else "gather",
            quotas=QuotaManager(default=TenantQuota(
                max_inflight=quota_cap)))
        fleet.warmup(prompt_lengths=[len(p) for p in prompts])
        from flashy_tpu.serve import QueueFull
        handles, sheds = [], 0
        begin = time.perf_counter()
        for prompt in prompts:
            try:
                handles.append(fleet.submit(prompt, new_tokens))
            except QueueFull:
                sheds += 1
        fleet.run()
        elapsed = time.perf_counter() - begin
        tokens = sum(len(h.generated) for h in handles)
        tok_s = tokens / elapsed / chips
        ttft = np.concatenate([m.scheduler.metrics.ttft or [0.0]
                               for m in fleet.members.values()])
        entry = {"tokens_per_sec_per_chip": round(tok_s, 1),
                 "shed_rate": round(sheds / len(prompts), 3),
                 "ttft_ms_p95": round(
                     float(np.percentile(ttft, 95)) * 1e3, 1)}
        per_engines[engines] = entry
        log(f"fleet x{engines}: {tok_s:.0f} tok/s/chip aggregate, "
            f"shed {entry['shed_rate'] * 100:.0f}% of "
            f"{len(prompts)} burst submits, ttft p95 "
            f"{entry['ttft_ms_p95']:.0f}ms")
    result["engines"] = per_engines
    # compact headline: the 4-engine aggregate + scaling vs 1 engine
    one = per_engines[1]["tokens_per_sec_per_chip"]
    result.update({
        "tokens_per_sec_per_chip": per_engines[4]["tokens_per_sec_per_chip"],
        "scaling_2e": round(
            per_engines[2]["tokens_per_sec_per_chip"] / one, 2),
        "scaling_4e": round(
            per_engines[4]["tokens_per_sec_per_chip"] / one, 2),
        "shed_rate": per_engines[4]["shed_rate"],
        "ttft_ms_p95": per_engines[4]["ttft_ms_p95"],
    })
    return result


def bench_recovery(jax, on_tpu: bool):
    """Crash-recovery cost for the durable request WAL: journaling
    overhead on the serving hot path (same burst with and without a
    WAL attached), then a mid-decode crash at 1/2/4 engines — WAL
    replay latency, drain time for the re-admitted requests, and the
    fraction of final tokens that had to be re-derived from the
    journal (the at-least-once re-serve cost)."""
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.serve.fleet import (QuotaManager, RequestWAL,
                                        ServingFleet, TenantQuota)

    if on_tpu:
        dim, layers, heads, vocab = 512, 4, 8, 4096
        slots, new_tokens, requests, kill_steps = 8, 32, 64, 8
    else:
        dim, layers, heads, vocab = 128, 2, 4, 512
        slots, new_tokens, requests, kill_steps = 4, 12, 16, 4
    cfg = TransformerConfig(vocab_size=vocab, dim=dim, num_layers=layers,
                            num_heads=heads, attention="dense",
                            max_seq_len=64,
                            dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    prompts = [rng.integers(0, vocab, 4 + i % 6).astype(np.int32)
               for i in range(requests)]
    # recovered requests prefill prompt+replayed-tokens, so every
    # integer length up to len(p)+new_tokens must have a warm bucket
    lengths = sorted({n for p in prompts
                      for n in range(len(p), len(p) + new_tokens + 1)})
    workdir = tempfile.mkdtemp(prefix="bench_recovery_")

    def build(engines, wal_path=None):
        return ServingFleet.build(
            model, params, engines=engines, slots=slots, block_size=16,
            max_queue=4 * requests,
            kernel="fused" if on_tpu else "gather",
            quotas=QuotaManager(default=TenantQuota(
                max_inflight=2 * requests)),
            wal=RequestWAL(wal_path) if wal_path else None)

    def serve_all(fleet):
        fleet.warmup(prompt_lengths=[len(p) for p in prompts])
        begin = time.perf_counter()
        handles = [fleet.submit(p, new_tokens) for p in prompts]
        fleet.run()
        return time.perf_counter() - begin, handles

    result = {}
    # journaling overhead: identical warmed burst, WAL off vs WAL on
    # (the admit fsync + per-step progress marks are the difference).
    # One discarded burst first: process-level caches warm for BOTH
    # timed runs, or the plain one eats the bias
    serve_all(build(1))
    plain_s, _ = serve_all(build(1))
    fleet = build(1, os.path.join(workdir, "overhead.wal"))
    walled_s, _ = serve_all(fleet)
    fleet.wal.close()
    overhead = (walled_s - plain_s) / plain_s * 100
    result["wal_append_overhead_pct"] = round(overhead, 1)
    log(f"recovery: WAL journaling overhead {overhead:+.1f}% "
        f"({walled_s * 1e3:.0f}ms vs {plain_s * 1e3:.0f}ms burst)")

    per_engines = {}
    for engines in (1, 2, 4):
        wal_path = os.path.join(workdir, f"crash_{engines}e.wal")
        fleet = build(engines, wal_path)
        fleet.warmup(prompt_lengths=lengths)
        for prompt in prompts:
            fleet.submit(prompt, new_tokens)
        for _ in range(kill_steps):
            fleet.step()  # mid-decode "crash": journal survives, state dies
        fleet.wal.close()
        del fleet

        fleet = build(engines, wal_path)
        fleet.warmup(prompt_lengths=lengths)
        begin = time.perf_counter()
        rec = fleet.recover_from_wal()
        replay_s = time.perf_counter() - begin
        replayed = sum(len(r.generated) for r in rec["recovered"].values())
        replayed += sum(len(e.generated) for e in rec["completed"].values())
        begin = time.perf_counter()
        fleet.run()
        drain_s = time.perf_counter() - begin
        fleet.wal.close()
        total = sum(len(r.generated) for r in rec["recovered"].values())
        total += sum(len(e.generated) for e in rec["completed"].values())
        entry = {"wal_replay_ms": round(replay_s * 1e3, 1),
                 "recovery_drain_ms": round(drain_s * 1e3, 1),
                 "reserved_token_frac": round(replayed / max(total, 1), 3),
                 "wal_bytes": os.path.getsize(wal_path),
                 "recovered": len(rec["recovered"]),
                 "completed_from_log": len(rec["completed"])}
        per_engines[engines] = entry
        log(f"recovery x{engines}: replay {entry['wal_replay_ms']:.0f}ms "
            f"({entry['wal_bytes']}B journal), drain "
            f"{entry['recovery_drain_ms']:.0f}ms, "
            f"{entry['recovered']} re-admitted + "
            f"{entry['completed_from_log']} answered from the log, "
            f"{entry['reserved_token_frac'] * 100:.0f}% of tokens "
            f"re-derived")
    shutil.rmtree(workdir, ignore_errors=True)
    result["engines"] = per_engines
    result.update({
        "wal_replay_ms": per_engines[4]["wal_replay_ms"],
        "recovery_drain_ms": per_engines[4]["recovery_drain_ms"],
        "reserved_token_frac": per_engines[4]["reserved_token_frac"],
    })
    return result


def bench_roofline(jax, on_tpu: bool):
    """Per-executable roofline from XLA `cost_analysis` over measured
    wall time (observability.RooflineProfiler): realized MFU for the LM
    train step, realized HBM GB/s + compute-vs-bandwidth verdict for
    the fused paged-decode serving step — each cross-checked against
    the analytic cost model the bench already publishes.

    Tolerances (the cross-check is a unit-level sanity bound, not a
    precision claim):
      * train step: cost_analysis FLOPs vs the analytic
        `6*P + 6*L*T*D` per token must agree within a factor of 2 —
        the analytic side ignores non-matmul work (norms, softmax, the
        AdamW update) while XLA counts every HLO op.
      * decode step: the analytic per-step stream is every parameter
        byte (each weight is read once per step — THE decode cost at
        small batch) plus the live slots' KV bytes
        (`decode_read_bytes_per_token`). cost_analysis counts WHOLE
        buffers (the full pool, sized for max_seq, not the live
        prefix; inputs and outputs both), so it must be >= the
        analytic stream and within 8x of it.
    """
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.observability import RooflineProfiler
    from flashy_tpu.ops import lm_next_token_loss

    profiler = RooflineProfiler()  # peaks probed from the live device
    result = {}

    # --- LM train step: AOT compile -> cost_analysis now, timed calls
    if on_tpu:
        dim, layers, heads, vocab = 1024, 12, 16, 32768
        batch, seq = 16, 1024
        warmup, measure = 3, 10
    else:
        dim, layers, heads, vocab = 128, 2, 4, 512
        batch, seq = 2, 64
        warmup, measure = 2, 5
    cfg = TransformerConfig(vocab_size=vocab, dim=dim, num_layers=layers,
                            num_heads=heads, attention="dense",
                            max_seq_len=seq,
                            dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = TransformerLM(cfg)
    params = {"params": model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]}
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    optim = optax.adamw(1e-4)
    state = {"params": params, "opt_state": optim.init(params)}

    def train_step(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lm_next_token_loss(model, p, tokens))(state["params"])
        updates, opt_state = optim.update(grads, state["opt_state"],
                                          state["params"])
        return ({"params": optax.apply_updates(state["params"], updates),
                 "opt_state": opt_state}, loss)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    compiled = jax.jit(train_step).lower(state, tokens).compile()
    profiler.register_compiled("lm/train_step", compiled)
    step = profiler.timed("lm/train_step", compiled)
    for _ in range(warmup):
        state, loss = step(state, tokens)
    jax.block_until_ready(loss)
    # drop the warm-up calls from the record: the roofline should price
    # the steady state, not the first-call dispatch transient
    profiler.profiles["lm/train_step"].calls = 0
    profiler.profiles["lm/train_step"].total_wall = 0.0
    profiler.profiles["lm/train_step"].wall.clear()
    for _ in range(measure):
        state, loss = step(state, tokens)

    analytic_flops = (6.0 * n_params + 6.0 * layers * seq * dim) \
        * batch * seq
    entry = profiler.summarize("lm/train_step") or {}
    ratio = (entry.get("flops_per_call") / analytic_flops
             if entry.get("flops_per_call") else None)
    result.update({
        "lm_tflops_per_sec": round(
            entry["realized_flops_per_sec"] / 1e12, 3)
        if entry.get("realized_flops_per_sec") else None,
        "lm_mfu": round(entry["mfu"], 4) if entry.get("mfu") else None,
        "lm_verdict": entry.get("verdict"),
        "lm_flops_ratio_vs_analytic": round(ratio, 3) if ratio else None,
        "lm_cost_error": entry.get("cost_error"),
    })
    log(f"roofline lm/train_step: "
        f"{(entry.get('realized_flops_per_sec') or 0) / 1e12:.3f} "
        f"TFLOP/s, mfu={entry.get('mfu')}, verdict={entry.get('verdict')}"
        f", cost/analytic FLOPs ratio={ratio}")
    if ratio is not None and not (0.5 <= ratio <= 2.0):
        result["lm_flops_violation"] = (
            f"cost_analysis/analytic FLOPs ratio {ratio:.3f} outside "
            f"[0.5, 2.0]")

    # --- fused paged-decode serving step: profiler attached to the
    # engine's compile cache BEFORE warmup, costs deferred to report
    try:
        from flashy_tpu.ops.paged_decode import decode_read_bytes_per_token
        from flashy_tpu.serve import (ContinuousBatchingScheduler,
                                      DecodeEngine)

        sdim, slayers, sheads, svocab = 128, 2, 4, 512
        slots, prompt_len, new_tokens = 4, 8, 16
        scfg = TransformerConfig(vocab_size=svocab, dim=sdim,
                                 num_layers=slayers, num_heads=sheads,
                                 attention="dense", max_seq_len=64,
                                 dtype=jnp.bfloat16 if on_tpu
                                 else jnp.float32)
        smodel = TransformerLM(scfg)
        sparams = {"params": smodel.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]}
        engine = DecodeEngine(smodel, sparams, slots=slots,
                              cache_layout="paged", kv_dtype="int8")
        engine.attach_roofline(profiler)
        workload = [(rng.integers(0, svocab, prompt_len).astype(np.int32),
                     new_tokens) for _ in range(slots * 2)]
        engine.warmup(prompt_lengths=[prompt_len])
        scheduler = ContinuousBatchingScheduler(engine,
                                                max_queue=len(workload))
        for prompt, max_new in workload:
            scheduler.submit(prompt, max_new)
        scheduler.run()
        decode_names = [n for n in profiler.profiles
                        if "decode" in n and "prefill" not in n]
        decode_name = decode_names[0] if decode_names else None
        entry = (profiler.summarize(decode_name) or {}) \
            if decode_name else {}
        # analytic per-step stream: every parameter byte once, plus
        # every live slot's whole-context K/V bytes (mid-generation
        # context on this workload)
        param_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(sparams))
        analytic_bytes = param_bytes + slots * decode_read_bytes_per_token(
            scfg, prompt_len + new_tokens // 2, "int8")
        bytes_ratio = (entry.get("bytes_per_call") / analytic_bytes
                       if entry.get("bytes_per_call") else None)
        result.update({
            "decode_executable": decode_name,
            "decode_hbm_gb_per_sec": round(
                entry["realized_hbm_gb_per_sec"], 3)
            if entry.get("realized_hbm_gb_per_sec") else None,
            "decode_verdict": entry.get("verdict"),
            "decode_intensity": round(entry["intensity"], 3)
            if entry.get("intensity") is not None else None,
            "decode_bytes_ratio_vs_analytic": round(bytes_ratio, 2)
            if bytes_ratio else None,
            "decode_cost_error": entry.get("cost_error"),
        })
        log(f"roofline {decode_name}: "
            f"{entry.get('realized_hbm_gb_per_sec')} GB/s, "
            f"intensity={entry.get('intensity')}, "
            f"verdict={entry.get('verdict')}, "
            f"cost/analytic bytes ratio={bytes_ratio}")
        if bytes_ratio is not None and not (1.0 <= bytes_ratio <= 8.0):
            result["decode_bytes_violation"] = (
                f"cost_analysis/analytic bytes ratio {bytes_ratio:.2f} "
                f"outside [1, 8]")
    except Exception as exc:  # noqa: BLE001  (serve sub-leg is additive)
        log(f"roofline decode sub-leg skipped: {exc}")
        result["decode_error"] = str(exc)[:200]

    # the full machine model + per-executable table goes to
    # BENCH_DETAIL.json; the compact line keeps the headline scalars
    report = profiler.report()
    result["peak_flops"] = report["peak_flops"]
    result["peak_hbm_gb_per_sec"] = report["peak_hbm_gb_per_sec"]
    result["executables"] = report["executables"]
    return result


def bench_zero(jax, on_tpu: bool):
    """ZeRO-1 sharded weight update vs replicated vs FSDP on the LM:
    step time + per-chip optimizer-state HBM bytes per layout, plus the
    watchdog's post-warm-up recompile count for the 3-step run (must be
    0 — see flashy_tpu/parallel/zero.py). Runs inline over the attached
    devices.
    """
    from flashy_tpu.parallel.zero import run_zero_bench
    result = run_zero_bench(steps=3)
    # compact-payload scalars (the nested dicts stay in BENCH_DETAIL)
    for mode in ("replicated", "zero1", "fsdp"):
        if mode in result.get("step_ms", {}):
            result[f"step_ms_{mode}"] = result["step_ms"][mode]
        if mode in result.get("opt_state_bytes_per_chip", {}):
            result[f"opt_state_bytes_per_chip_{mode}"] = \
                result["opt_state_bytes_per_chip"][mode]
    log(f"zero: opt bytes/chip zero1/replicated="
        f"{result.get('opt_bytes_ratio_zero1')} over "
        f"{result.get('n_devices')} devices; step_ms={result.get('step_ms')}; "
        f"recompiles={result.get('recompiles')}")
    return result


def bench_pipeline(jax, on_tpu: bool):
    """Pipeline schedules on the flagship LM over a 'pipe' mesh: GPipe
    vs 1F1B vs interleaved vs packed-1F1B gradient steps — bubble_frac
    (counted idle ticks; idle lanes for packed), peak_stash_bytes (the
    O(S) 1F1B ring vs GPipe's O(M) residency), step_ms, grad drift vs
    the GPipe oracle, tick_efficiency (realized step_ms over the
    schedule-theoretic tick bound, per-tick cost calibrated on the
    unpacked 1f1b leg — the counted-vs-realized gap tracker), packed
    bitwise-parity + step ratio vs unpacked, and the watchdog's
    post-warm-up recompile count (must be 0 — see
    flashy_tpu/parallel/pipeline.py). Runs inline over the attached
    devices.
    """
    if len(jax.devices()) < 2:
        return {"skipped": "single device; a 'pipe' axis needs >= 2 chips"}
    from flashy_tpu.parallel.pipeline import run_pipeline_bench
    result = run_pipeline_bench(steps=3)
    # compact-payload scalars (the nested dicts stay in BENCH_DETAIL)
    for name, stats in result.get("dense", {}).get("schedules", {}).items():
        key = name.replace("-", "_")
        for field in ("bubble_frac", "peak_stash_bytes", "step_ms",
                      "grad_drift", "num_ticks", "tick_efficiency",
                      "step_ms_vs_unpacked", "grads_bitwise_vs_unpacked",
                      "dead_compute_frac"):
            if field in stats:
                result[f"{field}_{key}"] = stats[field]
    # short aliases for the stdout line's whitelist — the driver-tail
    # budget cannot afford the flattened long names
    packed = result.get("dense", {}).get("schedules", {}).get(
        "packed_1f1b", {})
    if "step_ms_vs_unpacked" in packed:
        result["packed_step_ratio"] = packed["step_ms_vs_unpacked"]
    if "tick_efficiency" in packed:
        result["packed_tick_eff"] = packed["tick_efficiency"]
    if "grads_bitwise_vs_unpacked" in packed:
        result["packed_bitwise"] = packed["grads_bitwise_vs_unpacked"]
    # FT104's scalar (flashy_tpu.analysis.trace.dead_compute): the
    # FLOP-priced masked-idle-lane fraction packing exists to narrow
    if "dead_compute_frac" in packed:
        result["packed_dead_compute"] = packed["dead_compute_frac"]
    # the tensor x pipe 3D-composition probe (parallel.tensor): both
    # parallelisms in one jit, numbers identical to pipe-only
    compose = result.get("tensor_compose")
    if isinstance(compose, dict):
        result["tensor_compose_ok"] = compose.get("ok")
    log(f"pipeline: bubble gpipe={result.get('bubble_frac_gpipe')} "
        f"1f1b-int2={result.get('bubble_frac_1f1b_int2')}; packed step "
        f"{result.get('step_ms_packed_1f1b')}ms vs 1f1b "
        f"{result.get('step_ms_1f1b')}ms (ratio "
        f"{result.get('step_ms_vs_unpacked_packed_1f1b')}, bitwise "
        f"{result.get('grads_bitwise_vs_unpacked_packed_1f1b')}); "
        f"tick_eff packed={result.get('tick_efficiency_packed_1f1b')}; "
        f"stash bytes 1f1b={result.get('stash_bytes_at_m')} (flat in M: "
        f"{result.get('stash_flat_in_m')}) vs gpipe "
        f"{result.get('gpipe_stash_bytes_at_m')}; "
        f"recompiles={result.get('recompiles')}")
    return result


def bench_datapipe(jax, on_tpu: bool):
    """Packing throughput of the streaming data pipeline (host-side:
    jsonl+npy shard read -> weighted mixture -> fixed [B, L] sequence
    packing -> background prefetch). Reports host tokens/s and packing
    efficiency (non-padding fraction) — the number to compare against
    the LM leg's device tokens/s: the pipeline must outrun the step
    function or data_wait eats the MXU."""
    del jax  # host-only leg
    from flashy_tpu.datapipe.__main__ import run_packing_bench
    result = run_packing_bench(batches=200 if on_tpu else 100,
                               batch_size=8, seq_len=512)
    log(f"datapipe: {result.get('tokens_per_sec')} packed tokens/s host-side "
        f"({result.get('batch_shape')} batches, efficiency "
        f"{result.get('packing_efficiency')})")
    return result


def bench_ring(jax, on_tpu: bool):
    """Ring attention (shard_map + pallas per-block kernel) vs the plain
    flash kernel at the same global shape. With one attached chip the
    seq axis has a single shard, so the delta IS the ring machinery
    overhead (shard_map partitioning + the degenerate rotation) — the
    composition cost of one ring hop; multi-chip scaling then adds the
    ppermute wire time that overlaps with block compute."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from flashy_tpu.ops import attention as attn_mod
    from flashy_tpu.parallel.ring import ring_self_attention

    if not on_tpu:
        return {"skipped": "composition overhead only meaningful on TPU"}
    b, t, h, d = 2, 2048, 8, 64
    reps = 10
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "fsdp", "seq"))

    def timed(fn):
        grad = jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        jax.block_until_ready(grad(q, k, v))
        begin = time.perf_counter()
        for _ in range(reps):
            out = grad(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - begin) / reps

    flash_t = timed(lambda q, k, v: attn_mod.flash_attention(
        q, k, v, causal=True))
    ring_t = timed(lambda q, k, v: ring_self_attention(
        q, k, v, mesh=mesh, causal=True))
    log(f"ring: {ring_t * 1e3:.2f}ms vs flash {flash_t * 1e3:.2f}ms "
        f"(1-shard composition overhead {(ring_t / flash_t - 1) * 100:.0f}%)")
    return {"ring_ms": round(ring_t * 1e3, 2),
            "flash_ms": round(flash_t * 1e3, 2),
            "overhead_pct": round((ring_t / flash_t - 1) * 100, 1),
            "shape": [b, t, h, d]}


def bench_gan(jax, on_tpu: bool):
    """The adversarial two-optimizer stage (BASELINE configs[3]): one
    generator step + one discriminator step per iteration, MLP G/D."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flashy_tpu.adversarial import AdversarialLoss

    dim, hidden, batch = (256, 1024, 1024) if on_tpu else (32, 64, 64)
    warmup, measure = (3, 10) if on_tpu else (1, 3)

    rngs = jax.random.split(jax.random.PRNGKey(0), 4)

    def mlp_init(key, sizes):
        params = []
        for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
            k = jax.random.fold_in(key, i)
            params.append({"w": jax.random.normal(k, (a, b)) * (1.0 / np.sqrt(a)),
                           "b": jnp.zeros(b)})
        return params

    def mlp_apply(params, x):
        for i, layer in enumerate(params):
            x = x @ layer["w"] + layer["b"]
            if i < len(params) - 1:
                x = jax.nn.leaky_relu(x, 0.2)
        return x

    g_params = mlp_init(rngs[0], [dim, hidden, dim])
    d_params = mlp_init(rngs[1], [dim, hidden, 1])
    g_optim = optax.adam(1e-4)
    g_opt_state = g_optim.init(g_params)
    adv = AdversarialLoss(mlp_apply, d_params, optax.adam(1e-4))

    real = jax.random.normal(rngs[2], (batch, dim))
    noise = jax.random.normal(rngs[3], (batch, dim))

    def g_step(g_params, g_opt_state, d_params, noise):
        def loss_fn(gp):
            fake = mlp_apply(gp, noise)
            return adv.gen_loss(d_params, fake)

        loss, grads = jax.value_and_grad(loss_fn)(g_params)
        updates, g_opt_state = g_optim.update(grads, g_opt_state)
        return optax.apply_updates(g_params, updates), g_opt_state, loss

    g_step = jax.jit(g_step)

    def iteration():
        fake = mlp_apply(g_params, noise)
        adv.train_adv(fake, real)
        return g_step(g_params, g_opt_state, adv.params, noise)

    for _ in range(warmup):
        g_params, g_opt_state, loss = iteration()
    jax.block_until_ready(loss)
    begin = time.perf_counter()
    for _ in range(measure):
        g_params, g_opt_state, loss = iteration()
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - begin

    steps_per_sec = measure / elapsed
    log(f"gan: {steps_per_sec:.1f} G+D steps/sec (dim {dim}, batch {batch})")
    return {"steps_per_sec": round(steps_per_sec, 2),
            "batch_size": batch, "dim": dim}


def bench_all_reduce(jax):
    """psum bus bandwidth over the attached devices (multi-chip only)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "single device; ICI bandwidth needs >= 2 chips"}
    n = len(devices)
    size = 64 * 1024 * 1024 // 4  # 64 MiB of f32 per device
    mesh = Mesh(np.array(devices), ("d",))
    # Materialize directly sharded: building the full array on one chip
    # first would spike O(n_devices * 64MiB) HBM on device 0.
    x = jax.jit(lambda: jnp.ones((n, size), jnp.float32),
                out_shardings=NamedSharding(mesh, P("d", None)))()
    reduce = jax.jit(lambda a: a.sum(axis=0),
                     out_shardings=NamedSharding(mesh, P()))
    jax.block_until_ready(reduce(x))
    reps = 10
    begin = time.perf_counter()
    for _ in range(reps):
        out = reduce(x)
    jax.block_until_ready(out)
    elapsed = (time.perf_counter() - begin) / reps
    # ring all-reduce moves 2*(n-1)/n of the data per device
    bus_bytes = 2 * (n - 1) / n * size * 4
    gbps = bus_bytes / elapsed / 1e9
    log(f"all_reduce: {gbps:.1f} GB/s bus bandwidth over {n} devices")
    return {"bus_bandwidth_gb_s": round(gbps, 2), "n_devices": n,
            "payload_mib": 64}


def _apply_honest_ceiling(record: dict) -> None:
    """Make mfu_vs_measured honest.

    A single short MXU window can read BELOW what the LM leg itself
    sustains, and a 'ceiling' the chip demonstrably exceeds is not a
    ceiling. Redefine it per capture: ceiling := max(mxu rate, lm
    rate), stored as mxu.ceiling_bf16_tflops with its source, and
    re-derive lm.mfu_vs_measured from it — no ratio at all when the lm
    leg itself sets the ceiling (a self-referential 1.0 is not a
    measurement) or when no independent MXU rate exists."""
    lm, mxu = record.get("lm"), record.get("mxu")
    if not (isinstance(lm, dict) and lm.get("achieved_tflops_per_chip")):
        return
    if not (isinstance(mxu, dict) and mxu.get("measured_bf16_tflops")):
        lm["mfu_vs_measured"] = None
        return
    lm_rate = float(lm["achieved_tflops_per_chip"])
    mxu_rate = float(mxu["measured_bf16_tflops"])
    mxu["ceiling_bf16_tflops"] = round(max(lm_rate, mxu_rate), 2)
    mxu["ceiling_source"] = "mxu" if lm_rate <= mxu_rate else "lm"
    lm["mfu_vs_measured"] = (round(lm_rate / mxu_rate, 4)
                             if lm_rate <= mxu_rate else None)


# Per-leg scalar whitelist for the one-line stdout payload. Everything
# else (shapes, params counts, per-trial detail) goes to
# BENCH_DETAIL.json: a line past the driver's 2,000-char tail parses as
# null.
_COMPACT_KEYS = {
    "smoke": ("flash_speedup", "lm_step_ms"),
    "mxu": ("measured_bf16_tflops", "ceiling_bf16_tflops"),
    "cifar": ("images_per_sec_per_chip", "batch_size"),
    "lm": ("tokens_per_sec_per_chip", "mfu", "mfu_vs_measured",
           "achieved_tflops_per_chip", "variant", "tp_step_ms_t1",
           "tp_step_ms_t2", "tp_step_ms_t4", "tp_opt_bytes_ratio",
           "tp_flash_bwd_parity", "flash_bwd_vs_unfused"),
    "attention": ("speedup", "flash_tuned_ms"),
    "zero": ("opt_bytes_ratio_zero1", "step_ms_zero1", "step_ms_replicated",
             "recompiles"),
    "pipeline": ("bubble_frac_1f1b_int2", "stash_flat_in_m", "recompiles",
                 "packed_step_ratio", "packed_tick_eff", "packed_bitwise",
                 "packed_dead_compute", "tensor_compose_ok"),
    "ring": ("overhead_pct",),
    "datapipe": ("tokens_per_sec", "packing_efficiency"),
    "gan": ("steps_per_sec",),
    "decode": ("tokens_per_sec_per_chip", "spec_tokens_per_sec_per_chip",
               "spec_speedup", "acceptance_rate", "itl_ms_p95",
               "paged_tokens_per_sec_per_chip", "paged_vs_dense",
               "kv_bytes_per_slot", "max_concurrent_slots_at_fixed_hbm",
               "prefix_hit_rate", "fused_tokens_per_sec_per_chip",
               "fused_vs_gather", "kv_read_bytes_per_token",
               "ssd_tokens_per_sec_per_chip", "ssd_state_bytes_per_slot",
               "ssd_max_concurrent_slots_at_fixed_hbm"),
    "fleet": ("tokens_per_sec_per_chip", "scaling_2e", "scaling_4e",
              "shed_rate", "ttft_ms_p95"),
    "recovery": ("wal_replay_ms", "recovery_drain_ms",
                 "reserved_token_frac", "wal_append_overhead_pct"),
    "host_sync": ("gib_per_sec",),
    "all_reduce": ("bus_bandwidth_gb_s",),
    "roofline": ("lm_mfu", "lm_tflops_per_sec",
                 "lm_flops_ratio_vs_analytic", "decode_hbm_gb_per_sec",
                 "decode_verdict", "decode_bytes_ratio_vs_analytic"),
}


def _compact_legs(record: dict) -> dict:
    """Whitelisted scalars per leg; errors truncated; skipped legs
    carry nothing."""
    out = {}
    for name in LEG_ORDER:
        leg = record.get(name)
        if not isinstance(leg, dict) or "skipped" in leg:
            continue
        if "error" in leg:
            out[name] = {"error": str(leg["error"])[:60]}
        else:
            out[name] = {k: leg[k] for k in _COMPACT_KEYS.get(name, ())
                         if leg.get(k) is not None}
    return out


def _atomic_json_write(path: str, obj: dict) -> None:
    """json.dump to a sibling tmp file, then atomic rename."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _persist_partial(extra: dict) -> None:
    """Refresh BENCH_PARTIAL.json after every leg (atomic rename)."""
    try:
        os.makedirs(_STATE_DIR, exist_ok=True)
        _atomic_json_write(PARTIAL_PATH, extra)
    except OSError as exc:  # never let persistence kill the bench
        log(f"could not persist partial results: {exc}")


# Leg execution order: smoke first (kernel evidence within the first
# minute); mxu early so lm can report MFU against the measured matmul
# ceiling. FLASHY_TPU_BENCH_LEGS (comma list) restricts the run to a
# subset — handy for re-measuring one leg.
_LEGS_FILTER = os.environ.get("FLASHY_TPU_BENCH_LEGS")
LEG_ORDER = tuple(
    name for name in ("smoke", "mxu", "cifar", "lm", "attention", "zero",
                      "pipeline", "ring", "gan", "decode", "fleet",
                      "recovery", "roofline", "datapipe", "host_sync",
                      "all_reduce")
    if _LEGS_FILTER is None or name in _LEGS_FILTER.split(","))


def recorded_errors(record: tp.Any, path: str = "") -> tp.List[str]:
    """Every error a leg or sub-leg recorded, as dotted paths: a value
    under a key named `error` or ending in `_error`, at any depth."""
    found = []
    if isinstance(record, dict):
        for key, value in record.items():
            where = f"{path}.{key}" if path else str(key)
            if (key == "error" or str(key).endswith("_error")) and value:
                found.append(where)
            else:
                found.extend(recorded_errors(value, where))
    return found


def run_legs(legs: tp.Dict[str, tp.Callable[[dict], tp.Any]],
             extra: dict, platform: str) -> dict:
    """Run `legs` (name -> fn(extra_so_far)) in order, in this process.
    A leg that raises is recorded as `{"error": ...}` and the run goes
    on — the exit code, not a lost run, reports it. Results are
    persisted after each leg."""
    for name, leg in legs.items():
        log(f"leg {name} ...")
        begin = time.perf_counter()
        try:
            result = leg(extra)
        except Exception as exc:  # noqa: BLE001 — recorded, fails the run
            traceback.print_exc(file=sys.stderr)
            result = {"error": str(exc)[:300]}
        if isinstance(result, dict):
            result["leg_platform"] = platform
            result["leg_seconds"] = round(time.perf_counter() - begin, 1)
        extra[name] = result
        _persist_partial(extra)
    return extra


def exit_code(extra: dict) -> int:
    """0 only when the headline exists and no leg or sub-leg recorded an
    error."""
    errors = recorded_errors(extra)
    for where in errors:
        log(f"recorded error: {where}")
    headline = (extra.get("cifar") or {}).get("images_per_sec_per_chip")
    return 0 if headline is not None and not errors else 1


def main() -> None:
    from flashy_tpu.utils import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    if platform != "tpu":
        log(f"backend is {platform!r} ({device_kind}), not a TPU: refusing "
            f"to benchmark — a number from this platform must not be "
            f"printed under a device-metric name")
        sys.exit(2)
    peak = _peak_for(device_kind)
    log(f"backend up: {len(devices)} x {device_kind}; compile cache at "
        f"{cache_dir}")

    extra = {"platform": platform, "device_kind": device_kind,
             "n_devices": len(devices), "peak_bf16_tflops": peak / 1e12}
    _persist_partial(extra)

    def measured_flops(record):
        tf = (record.get("mxu") or {}).get("measured_bf16_tflops")
        return tf * 1e12 if tf else None

    legs = {
        "smoke": lambda r: bench_smoke(jax, True),
        "mxu": lambda r: bench_mxu(jax, peak),
        "cifar": lambda r: bench_cifar(jax, True),
        "lm": lambda r: bench_lm(jax, True, peak, measured_flops(r)),
        "attention": lambda r: bench_flash_attention(jax, True),
        "zero": lambda r: bench_zero(jax, True),
        "pipeline": lambda r: bench_pipeline(jax, True),
        "ring": lambda r: bench_ring(jax, True),
        "decode": lambda r: bench_decode(jax, True),
        "fleet": lambda r: bench_fleet(jax, True),
        "recovery": lambda r: bench_recovery(jax, True),
        "roofline": lambda r: bench_roofline(jax, True),
        "gan": lambda r: bench_gan(jax, True),
        "datapipe": lambda r: bench_datapipe(jax, True),
        "host_sync": lambda r: bench_host_sync(jax, True),
        "all_reduce": lambda r: bench_all_reduce(jax),
    }
    extra = run_legs({name: legs[name] for name in LEG_ORDER}, extra,
                     platform)
    _apply_honest_ceiling(extra)

    # Full record (every field, sub-legs) goes to a file; the stdout
    # line carries headline + per-leg scalars only.
    try:
        _atomic_json_write(DETAIL_PATH, extra)
    except OSError as exc:
        log(f"could not write {DETAIL_PATH}: {exc}")

    headline = (extra.get("cifar") or {}).get("images_per_sec_per_chip")
    compact = {k: extra[k] for k in
               ("platform", "device_kind", "n_devices", "peak_bf16_tflops")}
    compact["legs"] = _compact_legs(extra)
    compact["detail_path"] = "BENCH_DETAIL.json"
    payload = {
        "metric": "cifar10_resnet18_train_images_per_sec_per_chip",
        "value": headline,
        "unit": "images/sec/chip",
        "vs_baseline": (round(headline / REFERENCE_IMAGES_PER_SEC, 3)
                        if headline else None),
        "extra": compact,
    }
    line = json.dumps(payload, separators=(",", ":"))
    if len(line) > MAX_LINE_CHARS:  # hard guard: shed detail, keep headline
        compact.pop("legs", None)
        line = json.dumps(payload, separators=(",", ":"))
    print(line, flush=True)
    sys.exit(exit_code(extra))


if __name__ == "__main__":
    main()
