# Weights-only int8 quantization for serving. The reference has no
# serving/quantization path (it is a training harness); flashy_tpu
# ships one because autoregressive decoding is memory-bandwidth-bound:
# at batch sizes below the MXU's arithmetic-intensity knee, each
# decode step streams every weight byte from HBM once, so halving the
# bytes (bf16 -> int8) is worth up to 2x decode throughput on TPU.
#
# Scheme: symmetric per-output-channel absmax. Each matmul kernel leaf
# W is replaced by {"q": int8, "scale": f32} where scale is the absmax
# over the CONTRACTION dims, kept per output channel — the finest
# granularity that still lets the scale apply to the matmul OUTPUT
# (out = einsum(x, q.astype(bf16)) * scale), which keeps the int8->
# bf16 convert a pure elementwise op XLA fuses into the dot's operand
# read instead of materializing a dequantized copy in HBM.
#
# Quantized trees stay plain pytrees (dicts of arrays): orbax/
# checkpoint.save handle them unchanged, and `generate`
# (models/decoding.py) consumes them transparently. Router kernels and
# norm scales stay f32 — they are tiny and accuracy-critical.
"""Weights-only int8 quantization of TransformerLM params for decode."""
import typing as tp

import jax
import jax.numpy as jnp

# Symmetric int8 quantization range: values land in [-QMAX, QMAX].
# Shared by the weight and KV paths — and by every consumer of the
# scale-folding identity (ops/paged_attention.py's gather read and
# ops/paged_decode.py's fused kernel both reconstruct x ~= q * scale
# with scale = absmax / QMAX).
QMAX = 127.0


def is_quantized(leaf: tp.Any) -> bool:
    """True for a {"q", "scale"} quantized-tensor dict."""
    return (isinstance(leaf, dict) and set(leaf) == {"q", "scale"}
            and getattr(leaf.get("q"), "dtype", None) == jnp.int8)


def kernel_operand(w, dtype):
    """Matmul operand + output scale for a (possibly int8) kernel leaf.

    Quantized leaves ({"q", "scale"}) contribute the raw int8 payload
    converted to the compute dtype — a pure elementwise convert XLA
    fuses into the dot's operand read — and the per-output-channel
    scale to apply to the einsum RESULT. Dense leaves scale by None.
    """
    if is_quantized(w):
        return w["q"].astype(dtype), w["scale"]
    return w.astype(dtype), None


def postscale(out: jax.Array, scale) -> jax.Array:
    """Apply a kernel's output scale (broadcast over leading dims)."""
    if scale is None:
        return out
    return out * scale.astype(out.dtype)


def _quantize(w: jax.Array, contract_axes: tp.Sequence[int]) -> tp.Dict:
    """Symmetric absmax int8 over `contract_axes` (scale per out-channel)."""
    w = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=tuple(contract_axes), keepdims=True)
    scale = _safe_scale(absmax)
    q = jnp.clip(jnp.round(w / scale), -QMAX, QMAX).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def dequantize(leaf: tp.Any, dtype=jnp.float32) -> jax.Array:
    """{"q","scale"} -> dense array (testing / fallback)."""
    if not is_quantized(leaf):
        return leaf
    return (leaf["q"].astype(jnp.float32) * leaf["scale"]).astype(dtype)


# Kernel name -> contraction axes of its decode einsum
# (models/decoding.py): the scale must be constant over exactly these.
_CONTRACT_AXES = {
    "embed": (1,),          # head einsum "btd,vd->btv" contracts d
    ("attn", "qkv"): (0,),        # "btd,dchk->btchk"
    ("attn", "out"): (0, 1),      # "bqhd,hdD->bqD"
    ("mlp", "up"): (0,),          # "btd,df->btf"
    ("mlp", "down"): (0,),        # "btf,fd->btd"
    ("moe", "w_up"): (1,),        # [E, D, F]: contracts D per expert
    ("moe", "w_down"): (1,),      # [E, F, D]: contracts F per expert
}


def quantize_lm_params(params: tp.Any, *,
                       keep_embed_dense: bool = False) -> tp.Any:
    """Quantize a TransformerLM parameter tree's matmul kernels to int8.

    Accepts the full variables dict ({"params": ...}) or the inner
    tree; returns the same structure with each large kernel replaced by
    {"q": int8, "scale": f32}. Norms, biases, and MoE routers stay
    full precision. The result decodes through `models.decoding.generate`
    unchanged; use `dequantize_lm_params` to recover dense weights.

    `keep_embed_dense=True` leaves the tied embedding/LM-head table in
    full precision: the head logits feed the softmax directly, so its
    quantization error lands on the output distribution with no
    downstream matmul to wash it out — the standard escape hatch when
    int8 perplexity regresses.
    """
    wrapped = isinstance(params, dict) and set(params) == {"params"}
    tree = params["params"] if wrapped else params

    def walk(node, path):
        if not isinstance(node, dict) or is_quantized(node):
            return node
        # Scan-stacked layouts carry a leading [num_layers] dim on every
        # block leaf; shift the contraction axes past it so scales stay
        # per (layer, out-channel) and slice correctly under lax.scan.
        shift = 1 if "blocks" in path else 0

        def axes(key):
            return tuple(a + shift for a in _CONTRACT_AXES[key])

        out = {}
        for name, child in node.items():
            p = path + (name,)
            if name == "embed" and not isinstance(child, dict):
                out[name] = (child if keep_embed_dense
                             else _quantize(child, _CONTRACT_AXES["embed"]))
            elif name == "kernel" and len(path) >= 2 \
                    and (path[-2], path[-1]) in _CONTRACT_AXES:
                out[name] = _quantize(child, axes((path[-2], path[-1])))
            elif name in ("w_up", "w_down") and path \
                    and path[-1] == "moe" and not isinstance(child, dict):
                out[name] = _quantize(child, axes(("moe", name)))
            else:
                out[name] = walk(child, p)
        return out

    result = walk(tree, ())
    return {"params": result} if wrapped else result


# ----------------------------------------------------------------------
# KV-cache quantization (the serving paged cache, serve/paged.py)
# ----------------------------------------------------------------------
# The same symmetric absmax scheme extended from weights to cache
# WRITES: decode streams every cached K/V byte per step, so halving
# (bf16) or quartering-ish (f32) the cache bytes buys read bandwidth
# exactly like int8 weights buy weight bandwidth. Granularity is per
# cache ROW and head — absmax over head_dim — the K/V analogue of
# per-output-channel: the scale multiplies the dequantized row as one
# broadcast, so int8->compute-dtype stays a pure elementwise op XLA
# fuses into the attention gather instead of materializing a
# dequantized pool copy in HBM. Two readers consume this layout under
# one identity (FT203): the XLA gather path (ops/paged_attention.py)
# and the fused Pallas kernel (ops/paged_decode.py) both fold K scales
# into the scores pre-softmax and V scales into the probs post-softmax.

def _safe_scale(absmax: jax.Array) -> jax.Array:
    """absmax -> quant scale with a clamped denominator.

    An all-zero row (the paged pool's sentinel block, a zero-init
    cache, a dead head) has absmax 0: dividing by `absmax / 127` raw
    is inf/NaN, and an epsilon clamp alone still hands downstream math
    a ~8e-15 scale whose reciprocal (or bf16 square) overflows. Zero
    rows carry no information, so they get a unit scale: q == 0 and
    the dequantized row is EXACTLY zero, no matter what dtype touches
    the scale later.
    """
    return jnp.where(absmax > 0, jnp.maximum(absmax, 1e-12), QMAX) / QMAX


def quantize_kv(x: jax.Array) -> tp.Tuple[jax.Array, jax.Array]:
    """Quantize K or V rows `[..., head_dim]` to int8 + per-row scale.

    Symmetric absmax over the trailing head_dim (one scale per cache
    row per head, stored beside the pool by the paged cache); exact
    inverse up to rounding: `dequantize_kv(*quantize_kv(x))` ~= x with
    relative error <= 1/254 per element. All-zero rows quantize to
    (q=0, scale=1) — see `_safe_scale`.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = _safe_scale(absmax)
    q = jnp.clip(jnp.round(xf / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.float32)


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """Inverse of `quantize_kv`: int8 rows `[..., head_dim]` + per-row
    `[...]` scales -> dense rows in `dtype`."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def dequantize_lm_params(params: tp.Any, dtype=jnp.float32) -> tp.Any:
    """Inverse of `quantize_lm_params` (up to rounding error)."""
    def walk(node):
        if is_quantized(node):
            return dequantize(node, dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)
