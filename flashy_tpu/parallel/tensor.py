# Megatron-style tensor parallelism as a first-class mesh axis — the
# 2D/3D composition under ONE `wrap()`. Where `fsdp_sharding` (ZeRO-3)
# shards parameters and pays an all-gather inside every matmul, and
# `zero.zero_sharding` (ZeRO-1/2) shards only the update, the 'tensor'
# axis shards the MODEL MATH: the QKV and MLP up-projections are
# column-split (each chip computes a head/hidden slice, no collective),
# the attention-out and MLP down-projections are row-split (each chip
# holds partial sums), and the reduction is folded into the layer
# boundary as a sharding constraint (`models.transformer._tp_boundary`)
# so XLA's SPMD partitioner lowers it as exactly the megatron
# all-reduce pair — one after attention, one after the MLP. Everything
# is declarative: `tensor_state_sharding` composes the megatron
# parameter specs (`transformer_shardings`) with a ZeRO-1 update shard
# over the data axis through the same `axis_leaf_sharding` seam the
# rest of the package uses, so tensor × data × zero1 and
# tensor × pipeline compose in one jit with no hand-written
# collectives.
"""Tensor-parallel (megatron column/row) sharding over the 'tensor' axis."""
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .data_parallel import axis_leaf_sharding
from .mesh import default_mesh
from .zero import _is_update_key, describe_state_sharding, per_device_bytes


def _divisor_hint(value: int) -> tp.List[int]:
    divisors = [d for d in range(1, value + 1) if value % d == 0]
    return divisors[:min(len(divisors), 6)]


def validate_tensor_args(num_heads: int, mlp_hidden: int, tensor: int, *,
                         num_devices: tp.Optional[int] = None) -> None:
    """Validate a (heads, hidden, tensor-width) combination with
    actionable messages — the `validate_pipeline_args` convention.

    Column-parallel layers split the head axis (attention) and the MLP
    hidden axis over the tensor axis, so both must divide; a width that
    does not divide the device count cannot be materialized as a mesh
    axis at all.
    """
    if tensor < 1:
        raise ValueError(f"tensor width must be >= 1, got {tensor}")
    if num_heads % tensor:
        raise ValueError(
            f"tensor width {tensor} does not divide num_heads="
            f"{num_heads}: column-parallel attention gives each chip "
            f"num_heads/tensor whole heads. Pick tensor from the "
            f"divisors of num_heads (e.g. {_divisor_hint(num_heads)}) "
            f"or pad the head count.")
    if mlp_hidden % tensor:
        raise ValueError(
            f"tensor width {tensor} does not divide the MLP hidden size "
            f"{mlp_hidden}: the column-split up-projection gives each "
            f"chip hidden/tensor columns. Pick tensor from the divisors "
            f"of the hidden size (e.g. {_divisor_hint(mlp_hidden)}) or "
            f"round the hidden size up.")
    if num_devices is not None and num_devices % tensor:
        raise ValueError(
            f"tensor width {tensor} does not divide the device count "
            f"{num_devices}; the mesh factors devices as "
            f"tensor x data, so pick tensor from the divisors of the "
            f"device count (e.g. {_divisor_hint(num_devices)}).")


def tensor_state_sharding(state: tp.Any, mesh: tp.Optional[Mesh] = None, *,
                          zero_axis: str = "data",
                          min_size: int = 2 ** 12) -> tp.Any:
    """NamedShardings for a whole `{'params', 'opt_state'}` train state
    under megatron tensor parallelism, composed with a ZeRO-1 update
    shard.

    Parameter leaves get the `transformer_shardings` column/row specs
    verbatim. Update-state leaves (top-level key matching
    `zero.UPDATE_KEY_MARKERS` — the Adam moments, fp32 masters) START
    from the same megatron spec — the moments mirror the param layout,
    so their tensor split comes for free — and are then additionally
    sharded over `zero_axis` on the largest still-free divisible dim
    via `axis_leaf_sharding(..., base=...)`. On a (data=D, tensor=T)
    mesh the optimizer state therefore lands at ~1/(D*T) of its
    replicated footprint per chip, which is what the FT101 sweep's
    tensor leg audits. `transformer_shardings` matches on path
    substrings, so the optimizer mirrors (`.../qkv/kernel` inside
    mu/nu) pick up the same specs as the params they shadow; scalar
    leaves (Adam's step count) stay replicated.

    Directly consumable as `wrap(step, state_sharding=
    tensor_state_sharding(state, mesh), batch_axes=('data',))`.
    """
    from ..models.transformer import transformer_shardings

    mesh = mesh or default_mesh()
    specs = transformer_shardings(state)

    def for_leaf(path: tp.Tuple, leaf: tp.Any, spec: P) -> NamedSharding:
        is_update = any(
            _is_update_key(str(getattr(entry, "key",
                                       getattr(entry, "name", entry))))
            for entry in path)
        if not is_update:
            return NamedSharding(mesh, spec)
        rule = axis_leaf_sharding(mesh, zero_axis, min_size,
                                  base=lambda _: spec)
        return rule(leaf)

    return jax.tree_util.tree_map_with_path(
        for_leaf, state, specs,
        is_leaf=lambda x: isinstance(x, P))


def flash_bwd_parity(*, interpret: tp.Optional[bool] = None,
                     dtype: tp.Any = jnp.float32) -> float:
    """Max |fused - split| over dq/dk/dv of one small flash-attention
    grad — the bit-level oracle gate (0.0 means bit-identical).

    The fused one-pass backward kernel replays the split dq/dkv pair's
    accumulation order op for op, so the two paths must agree BITWISE,
    not merely within tolerance; any nonzero delta is a kernel bug. On
    CPU this runs the kernels under pallas interpret mode (the same
    oracle FT203 audits); on TPU it compares the real kernels.
    """
    from ..ops.attention import flash_attention

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(7)
    shape = (2, 128, 2, 64)  # [batch, time, heads, head_dim]
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for _ in range(3))

    def loss(fused):
        def inner(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=64,
                                  block_k=64, interpret=interpret,
                                  fused_backward=fused)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    fused_grads = loss(True)
    split_grads = loss(False)
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(fused_grads, split_grads))


# ---------------------------------------------------------------------------
# Measurement harness: `python -m flashy_tpu.parallel.tensor` (`make
# tp-demo`) runs this — step time, achieved TFLOP/s and
# per-chip optimizer HBM at tensor widths {1, 2, 4} on one small LM,
# gradients checked against a replicated single-chip oracle, with every
# compile reported through one RecompileWatchdog.
# ---------------------------------------------------------------------------

def run_tp_bench(steps: int = 3, *, dim: int = 128, num_layers: int = 2,
                 num_heads: int = 4, vocab_size: int = 512,
                 batch: tp.Optional[int] = None, seq: int = 64,
                 widths: tp.Optional[tp.Sequence[int]] = None,
                 min_size: int = 2 ** 10) -> tp.Dict[str, tp.Any]:
    """Measure tensor-parallel training at several tensor widths.

    Returns a record with ``step_ms`` / ``tflops_per_chip`` /
    ``opt_state_bytes_per_chip`` / ``sharding`` / ``loss_trajectory``
    dicts keyed by tensor width (as str — JSON-stable),
    ``grads_max_delta`` (worst leaf-wise |TP grad - replicated oracle
    grad| across widths), ``opt_bytes_ratio`` (widest width's per-chip
    optimizer bytes over the replicated footprint — ~1/(data*tensor)),
    ``flash_bwd_parity`` (fused-vs-split backward kernel delta, 0.0 =
    bit-identical) and ``recompiles`` (watchdog total past warm-up).
    The model runs f32 + dense attention so the oracle comparison is a
    numerics statement, not a tolerance negotiation.
    """
    import time

    import optax

    from ..models import TransformerConfig, TransformerLM
    from ..observability import RecompileWatchdog
    from ..resilience import chaos
    from .data_parallel import shard_batch, wrap
    from .mesh import make_mesh

    n_devices = len(jax.devices())
    if batch is None:
        batch = max(8, 2 * n_devices)
    if batch % n_devices:
        batch += n_devices - batch % n_devices
    mlp_hidden = dim * 4
    if widths is None:
        widths = [w for w in (1, 2, 4)
                  if n_devices % w == 0 and num_heads % w == 0
                  and mlp_hidden % w == 0]
    for width in widths:
        validate_tensor_args(num_heads, mlp_hidden, width,
                             num_devices=n_devices)

    cfg = TransformerConfig(vocab_size=vocab_size, dim=dim,
                            num_layers=num_layers, num_heads=num_heads,
                            attention="dense", dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens_host = rng.integers(0, vocab_size, (batch, seq)).astype(np.int32)
    oracle_model = TransformerLM(cfg)
    init = jax.tree_util.tree_map(np.asarray, {"params": oracle_model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]})
    optim = optax.adamw(1e-3)
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(init))
    # the standard 6ND training-FLOPs estimate (fwd matmuls + 2x bwd)
    step_flops = 6.0 * n_params * batch * seq

    def make_state():
        params = jax.tree_util.tree_map(jnp.asarray, init)
        return {"params": params, "opt_state": optim.init(params)}

    def make_step(model):
        def step(state, tokens):
            def loss_fn(variables):
                logits = model.apply(variables, tokens)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], tokens[:, 1:]).mean()

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, opt_state = optim.update(grads, state["opt_state"],
                                              state["params"])
            return ({"params": optax.apply_updates(state["params"], updates),
                     "opt_state": opt_state}, {"loss": loss})
        return step

    # replicated single-chip oracle: same params, same batch, default
    # placement — the reference every TP width's first-step gradients
    # must reproduce
    def oracle_grads_fn(params, tokens):
        def loss_fn(variables):
            logits = oracle_model.apply(variables, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()
        return jax.grad(loss_fn)(params)

    oracle_grads = jax.tree_util.tree_map(
        np.asarray,
        jax.jit(oracle_grads_fn)(make_state()["params"],
                                 jnp.asarray(tokens_host)))
    replicated_opt_bytes = per_device_bytes(make_state()["opt_state"])

    watchdog = RecompileWatchdog(warmup=1)
    result: tp.Dict[str, tp.Any] = {
        "n_devices": n_devices, "batch": batch, "seq": seq,
        "n_params": n_params, "widths": [int(w) for w in widths],
        "step_ms": {}, "tflops_per_chip": {},
        "opt_state_bytes_per_chip": {}, "sharding": {},
        "loss_trajectory": {}, "grads_max_delta": {},
    }
    for width in widths:
        key = str(int(width))
        mesh = make_mesh({"tensor": width, "data": n_devices // width})
        model = TransformerLM(cfg, mesh=mesh)
        state = make_state()
        spec = tensor_state_sharding(state, mesh, min_size=min_size)
        # device_put onto the shardings wrap resolves, so step 1 already
        # runs at the steady-state placement (the run_zero_bench rule:
        # otherwise the second call legitimately retraces and "zero
        # recompiles" cannot hold)
        state = jax.device_put(state, spec)
        tokens = shard_batch(jnp.asarray(tokens_host), mesh,
                             batch_axes=("data",))

        tp_grads = jax.jit(
            jax.grad(lambda p, t: optax.
                     softmax_cross_entropy_with_integer_labels(
                         model.apply(p, t)[:, :-1],
                         t[:, 1:]).mean()),
            in_shardings=(spec["params"],
                          tokens.sharding))(state["params"], tokens)
        deltas = jax.tree_util.tree_map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - b))),
            tp_grads, oracle_grads)
        result["grads_max_delta"][key] = max(
            jax.tree_util.tree_leaves(deltas))

        wrapped = wrap(make_step(model), mesh=mesh, batch_axes=("data",),
                       state_sharding=spec, watchdog=watchdog)
        losses: tp.List[float] = []
        state, aux = wrapped(state, tokens)  # compile + step 1
        jax.block_until_ready(aux["loss"])
        losses.append(float(aux["loss"]))
        begin = time.perf_counter()
        for index in range(steps):
            chaos.fault_point("tensor.step", width=int(width), step=index)
            state, aux = wrapped(state, tokens)
            losses.append(float(aux["loss"]))
        jax.block_until_ready(aux["loss"])
        step_ms = (time.perf_counter() - begin) / steps * 1e3
        result["step_ms"][key] = round(step_ms, 2)
        result["tflops_per_chip"][key] = round(
            step_flops / (step_ms / 1e3) / n_devices / 1e12, 4)
        result["opt_state_bytes_per_chip"][key] = per_device_bytes(
            state["opt_state"])
        result["sharding"][key] = describe_state_sharding(state)["summary"]
        result["loss_trajectory"][key] = losses

    widest = str(int(max(widths)))
    result["opt_bytes_ratio"] = round(
        result["opt_state_bytes_per_chip"][widest] / replicated_opt_bytes, 4)
    result["grads_max_delta_overall"] = max(
        result["grads_max_delta"].values())
    result["flash_bwd_parity"] = flash_bwd_parity()
    result["recompiles"] = sum(watchdog.summary().values())
    return result


def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    """`python -m flashy_tpu.parallel.tensor [--steps N]`: run the
    tensor-width sweep and print one JSON line; exit 1 when TP grads
    drift from the replicated oracle, the optimizer shard did not
    happen, the fused flash backward loses bit parity with the split
    oracle, or any post-warm-up recompile was reported."""
    import argparse
    import json
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m flashy_tpu.parallel.tensor",
        description="Megatron tensor-parallel training bench at widths "
                    "{1,2,4}.")
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--seq", type=int, default=64)
    args = parser.parse_args(argv)

    result = run_tp_bench(steps=args.steps, seq=args.seq)
    print(json.dumps(result), flush=True)
    problems = []
    if result["recompiles"]:
        problems.append(f"{result['recompiles']} post-warm-up recompiles")
    if result["grads_max_delta_overall"] > 1e-4:
        problems.append(
            f"TP grads drifted from the replicated oracle by "
            f"{result['grads_max_delta_overall']:.2e}")
    n = result["n_devices"]
    if n >= 2 and result["opt_bytes_ratio"] > (1.5 / n + 0.25):
        problems.append(
            f"opt-state per chip is {result['opt_bytes_ratio']}x the "
            f"replicated footprint on a {n}-device mesh — the "
            f"tensor x zero1 shard did not happen")
    if result["flash_bwd_parity"] != 0.0:
        problems.append(
            f"fused flash backward lost bit parity with the split "
            f"oracle: max delta {result['flash_bwd_parity']:.2e}")
    for problem in problems:
        print(f"tensor bench FAILED: {problem}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
