# The sweep behind `ops/attention.py:TILES` (PERF.md section 6, PR 36):
# milliseconds a forward call (`flash_fwd`) and a fused backward call
# (`flash_bwd_fused`) take at one shape for every pair of tiles, with
# jax's own Pallas TPU kernels timed beside them in the same process.
# Run it through the chip tool; no cell imports it.
#
#   chiprun -- python3 tools/flash_sweep.py --library \
#       --out chiprun_out/flash_sweep.json
#
# `--module PATH` times another copy of `ops/attention.py` (a parent
# commit's, or one with a part of the schedule taken out) under the same
# harness, and each row carries a digest of the outputs' bits: two
# copies that compute the same values at the same tiles show it. The
# kernels are called on folded operands ([B*H, T, 1, D]: the fold is
# then a reshape), so a time is the custom call's own; a copy whose
# backward folds dQ partials outside the kernel has that fold in its
# backward time.
"""Sweep the flash kernels' tiles on the chip; time jax's kernels beside."""
import argparse
import importlib.util
import itertools
import json
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def load(path):
    spec = importlib.util.spec_from_file_location(
        "swept_" + pathlib.Path(path).stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ms_a_call(fn, args, calls, rounds=3):
    """Median over `rounds` of the wall time of `calls` queued calls."""
    jax.block_until_ready(fn(*args))  # compile, warm
    times = []
    for _ in range(rounds):
        begin = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append(1e3 * (time.perf_counter() - begin) / calls)
    return statistics.median(times)


def worst(got, want):
    """Largest error against the reference, over the heads it holds."""
    return max(float(jnp.max(jnp.abs(a[:len(b)].astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(got, want))


@jax.jit
def _checksum(x):
    bits = jax.lax.bitcast_convert_type(
        x, jnp.uint16 if x.dtype.itemsize == 2 else jnp.uint32
    ).astype(jnp.uint32).reshape(-1)
    return jnp.sum(bits * (jnp.arange(bits.size, dtype=jnp.uint32) % 8191 + 1))


def digest(arrays):
    """A checksum of the arrays' bits: two rows with the same tiles whose
    digests are equal computed the same values bit for bit."""
    return [int(_checksum(x)) for x in arrays]


def sweep(module, operands, reference, tiles, causal, calls, interpret, emit):
    q, k, v, do = operands
    # one forward's residuals feed every backward
    out, lse = jax.jit(lambda q, k, v: module._flash_forward(
        q, k, v, causal=causal, block_q=256, block_k=256,
        interpret=interpret))(q, k, v)
    delta = jnp.broadcast_to(jnp.sum(
        do[:, :, 0].astype(jnp.float32) * out[:, :, 0].astype(jnp.float32),
        axis=-1)[:, :, None], lse.shape)
    for block_q, block_k in tiles:
        row = {"block_q": block_q, "block_k": block_k}
        forward = jax.jit(lambda q, k, v: module._flash_forward(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret)[0])
        backward = jax.jit(lambda q, k, v, out, lse, do, delta: (
            module._flash_backward_fused(
                q, k, v, out, lse, do, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, delta=delta)))
        for name, fn, args, want in (
                ("fwd", forward, (q, k, v), reference[:1]),
                ("bwd", backward, (q, k, v, out, lse, do, delta),
                 reference[1:])):
            try:
                begin = time.perf_counter()
                got = jax.block_until_ready(fn(*args))
                row[name + "_compile_s"] = time.perf_counter() - begin
                got = got if isinstance(got, tuple) else (got,)
                row[name + "_max_err"] = worst(got, want)
                row[name + "_digest"] = digest(got)
                row[name + "_ms"] = ms_a_call(fn, args, calls)
            except Exception as error:  # Mosaic's refusals are results too
                row[name + "_error"] = str(error).splitlines()[0][:200]
        emit(row)


def library(operands, reference, heads, causal, calls, emit):
    """jax's two Pallas TPU attention kernels at the same shape: the
    forward alone, and backward = (forward + backward) - forward."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    q, k, v, do = (x[:, :, 0] for x in operands)     # [BH, T, D]
    shape = (-1, heads) + q.shape[1:]
    q, k, v, do = (x.reshape(shape) for x in (q, k, v, do))  # [B, H, T, D]
    scale = 1.0 / np.sqrt(q.shape[-1])
    want = tuple(x[:, :, 0].reshape(shape) for x in reference)

    def timed(name, attend, blocks):
        row = {"kernel": name, "blocks": blocks}
        try:
            forward = jax.jit(attend)
            both = jax.jit(lambda q, k, v: jax.vjp(attend, q, k, v)[1](do))
            row["fwd_max_err"] = worst((forward(q, k, v),), want[:1])
            row["bwd_max_err"] = worst(both(q, k, v), want[1:])
            row["fwd_ms"] = ms_a_call(forward, (q, k, v), calls)
            row["bwd_ms"] = ms_a_call(both, (q, k, v), calls) - row["fwd_ms"]
        except Exception as error:
            row["error"] = str(error).splitlines()[0][:200]
        emit(row)

    for size in (128, 256, 512, 1024):
        sizes = fa.BlockSizes(
            block_q=size, block_k_major=size, block_k=size, block_b=1,
            block_q_major_dkv=size, block_k_major_dkv=size, block_k_dkv=size,
            block_q_dkv=size, block_k_major_dq=size, block_k_dq=size,
            block_q_dq=size)
        timed("pallas.ops.tpu.flash_attention", lambda q, k, v: (
            fa.flash_attention(q, k, v, causal=causal, sm_scale=scale,
                               block_sizes=sizes)), size)
    t_q = q.shape[2]
    mask = sm.MultiHeadMask([
        sm.CausalMask((t_q, t_q)) if causal else sm.FullMask((t_q, t_q))
        for _ in range(heads)])
    for size, compute, fused in ((512, 512, False), (512, 512, True),
                                 (1024, 512, True), (1024, 1024, True),
                                 (2048, 512, True)):
        sizes = sk.BlockSizes(
            block_q=min(size, 1024), block_kv=size, block_kv_compute=compute,
            block_q_dkv=min(size, 1024), block_kv_dkv=size,
            block_kv_dkv_compute=compute,
            block_q_dq=None if fused else size,
            block_kv_dq=None if fused else size,
            use_fused_bwd_kernel=fused)
        kernel = sk.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                    q_seq_shards=1)
        timed("splash_attention", lambda q, k, v: jax.vmap(kernel)(
            (q * scale).astype(q.dtype), k, v),
            [size, compute, "fused_bwd" if fused else "split_bwd"])


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--shape", default="8,2048,16,128",
                        help="batch,time,heads,head_dim (bfloat16)")
    parser.add_argument("--tiles", default="256,512,1024,2048",
                        help="block_q and block_k candidates")
    parser.add_argument("--pairs", default="",
                        help="block_q:block_k pairs instead of the square")
    parser.add_argument("--module", action="append", default=[],
                        help="a copy of ops/attention.py (repeatable)")
    parser.add_argument("--library", action="store_true",
                        help="time jax's flash and splash kernels too")
    parser.add_argument("--causal", type=int, default=1)
    parser.add_argument("--rehearse", action="store_true",
                        help="interpret mode off the chip: proves the "
                             "script at a toy --shape, times nothing real")
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--seed", type=int, default=36)
    parser.add_argument("--out", default="chiprun_out/flash_sweep.json")
    args = parser.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit("flash_sweep.py times kernels: it needs the chip")
    batch, t, heads, dim = (int(x) for x in args.shape.split(","))
    causal = bool(args.causal)
    sizes = [int(x) for x in args.tiles.split(",")]
    tiles = ([tuple(int(x) for x in pair.split(":"))
              for pair in args.pairs.split(",")] if args.pairs
             else list(itertools.product(sizes, sizes)))
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    operands = tuple(jax.random.normal(key, (batch * heads, t, 1, dim),
                                       jnp.bfloat16) for key in keys)
    from flashy_tpu.ops.attention import dot_product_attention
    # XLA's attention over the first `heads` folded heads (its T x T
    # scores are 2 GB for all 128) is what every kernel is held against
    q, k, v, do = (x[:heads] for x in operands)
    out, vjp = jax.vjp(jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal)), q, k, v)
    reference = (out,) + vjp(do)

    results = {"device": device.device_kind, "rehearsal": args.rehearse,
               "shape": args.shape,
               "causal": causal, "calls": args.calls, "rows": []}
    path = ROOT / args.out
    path.parent.mkdir(parents=True, exist_ok=True)

    def emit(row):
        results["rows"].append(row)
        print(json.dumps(row), flush=True)
        path.write_text(json.dumps(results, indent=1))

    modules = args.module or [str(ROOT / "flashy_tpu/ops/attention.py")]
    for module_path in modules:
        sweep(load(module_path), operands, reference, tiles, causal,
              args.calls, device.platform != "tpu",
              lambda row: emit({"module": module_path, **row}))
    if args.library:
        library(operands, reference, heads, causal, args.calls, emit)


if __name__ == "__main__":
    main()
