# Encoder-decoder transformer — completes the family triad (decoder-
# only `TransformerLM`, encoder-only `ViT`/MLM, and now seq2seq). Built
# from the SAME shared pieces so every TPU-first property carries over:
#
#  * encoder = the shared `transformer.Block` with `causal=False`
#    (bidirectional flash/dense attention, SwiGLU MLP, RMSNorm);
#  * decoder blocks add one cross-attention sublayer between the causal
#    self-attention and the MLP — fused KV projection over the encoder
#    memory (one [D, 2D] matmul), no positional encoding on the
#    cross path (alignment is learned; rotary stays on self-attention
#    where relative offsets are meaningful);
#  * setup()-based module: `encode` and `decode` are standalone apply
#    methods, so generation computes the encoder memory ONCE and scans
#    only the decoder (`greedy_translate`);
#  * the sharding rules extend `transformer_shardings` by name
#    (megatron column/row splits over 'tensor', FSDP over 'fsdp'), so
#    a seq2seq step shards with the same one-liner as the LM.
"""Seq2Seq encoder-decoder transformer on the shared blocks."""
import dataclasses
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from .transformer import (Attention, Block, MLPBlock, TransformerConfig,
                          rmsnorm)


@dataclasses.dataclass(frozen=True)
class Seq2SeqConfig:
    vocab_size: int = 32000
    dim: int = 512
    enc_layers: int = 6
    dec_layers: int = 6
    num_heads: int = 8
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dropout: float = 0.0
    dtype: tp.Any = jnp.bfloat16
    attention: str = "dense"     # self-attention impl: 'dense' | 'flash'

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    def _block_config(self, causal: bool) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=1, dim=self.dim, num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio, max_seq_len=self.max_seq_len,
            dropout=self.dropout, dtype=self.dtype,
            attention=self.attention, causal=causal)


class CrossAttention(nn.Module):
    """Decoder queries attend over the encoder memory (no mask)."""

    config: Seq2SeqConfig

    @nn.compact
    def __call__(self, x: jax.Array, memory: jax.Array,
                 train: bool = False) -> jax.Array:
        cfg = self.config
        q = nn.DenseGeneral((cfg.num_heads, cfg.head_dim), axis=-1,
                            use_bias=False, dtype=cfg.dtype, name="q")(x)
        kv = nn.DenseGeneral((2, cfg.num_heads, cfg.head_dim), axis=-1,
                             use_bias=False, dtype=cfg.dtype,
                             name="kv")(memory)
        k, v = kv[:, :, 0], kv[:, :, 1]
        out = dot_product_attention(q, k, v, causal=False)
        out = nn.DenseGeneral(cfg.dim, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, name="out")(out)
        if cfg.dropout > 0.0:
            out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out


class DecoderBlock(nn.Module):
    """Causal self-attention + cross-attention + MLP, pre-RMSNorm."""

    config: Seq2SeqConfig
    mesh: tp.Any = None

    @nn.compact
    def __call__(self, x: jax.Array, memory: jax.Array,
                 positions: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        bcfg = cfg._block_config(causal=True)
        x = x + Attention(bcfg, mesh=self.mesh, name="attn")(
            nn.RMSNorm(dtype=cfg.dtype, name="norm1")(x), positions, train)
        x = x + CrossAttention(cfg, name="xattn")(
            nn.RMSNorm(dtype=cfg.dtype, name="norm2")(x), memory, train)
        x = x + MLPBlock(bcfg, name="mlp")(
            nn.RMSNorm(dtype=cfg.dtype, name="norm3")(x), train)
        return x


class Seq2SeqTransformer(nn.Module):
    """(src [B, S], tgt [B, T]) int32 -> logits [B, T, vocab].

    Teacher-forced training forward: the decoder sees `tgt` shifted by
    the caller (standard convention: feed BOS + tgt[:-1], predict tgt).
    The embedding table is shared between source, target, and the tied
    output head. `encode` / `decode` are standalone apply methods
    (`model.apply(params, src, method=Seq2SeqTransformer.encode)`), so
    serving computes the memory once.
    """

    config: Seq2SeqConfig
    mesh: tp.Any = None

    def setup(self):
        cfg = self.config
        self.embed = self.param(
            "embed", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.dim), jnp.float32)
        enc_cfg = cfg._block_config(causal=False)
        self.enc_blocks = [Block(enc_cfg, mesh=self.mesh)
                           for _ in range(cfg.enc_layers)]
        self.enc_norm = nn.RMSNorm(dtype=cfg.dtype)
        self.dec_blocks = [DecoderBlock(cfg, mesh=self.mesh)
                           for _ in range(cfg.dec_layers)]
        self.dec_norm = nn.RMSNorm(dtype=cfg.dtype)

    def _positions(self, tokens: jax.Array) -> jax.Array:
        if tokens.shape[1] > self.config.max_seq_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds "
                f"max_seq_len={self.config.max_seq_len}")
        return jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)

    def encode(self, src: jax.Array, train: bool = False) -> jax.Array:
        positions = self._positions(src)
        x = jnp.take(self.embed, src, axis=0).astype(self.config.dtype)
        for block in self.enc_blocks:
            x = block(x, positions, train)
        return self.enc_norm(x)

    def decode(self, tgt: jax.Array, memory: jax.Array,
               train: bool = False) -> jax.Array:
        positions = self._positions(tgt)
        y = jnp.take(self.embed, tgt, axis=0).astype(self.config.dtype)
        for block in self.dec_blocks:
            y = block(y, memory, positions, train)
        y = self.dec_norm(y)
        # tied head in f32 (same recipe as TransformerLM)
        return jnp.einsum("btd,vd->btv", y.astype(jnp.float32),
                          self.embed.astype(jnp.float32))

    def __call__(self, src: jax.Array, tgt: jax.Array,
                 train: bool = False) -> jax.Array:
        return self.decode(tgt, self.encode(src, train), train)


def seq2seq_shardings(params: tp.Any) -> tp.Any:
    """PartitionSpec tree for a Seq2SeqTransformer parameter pytree.

    Same megatron/FSDP rules as `transformer_shardings` (the shared
    block names match), extended with the cross-attention projections:
    q [D, H, Dh] column-split, fused kv [D, 2, H, Dh] column-split,
    out [H, Dh, D] row-split.
    """
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf) -> P:
        joined = "/".join(str(getattr(p, "key", p)) for p in path)
        if "embed" in joined:
            return P("tensor", "fsdp")
        if "xattn/q" in joined:
            base: tp.Tuple = ("fsdp", "tensor", None)
        elif "xattn/kv" in joined:
            base = ("fsdp", None, "tensor", None)
        elif "xattn/out" in joined:
            base = ("tensor", None, "fsdp")
        elif "qkv" in joined:
            base = ("fsdp", None, "tensor", None)
        elif "attn/out" in joined:
            base = ("tensor", None, "fsdp")
        elif "mlp/up" in joined:
            base = ("fsdp", "tensor")
        elif "mlp/down" in joined:
            base = ("tensor", "fsdp")
        else:
            base = ()
        return P(*base[:getattr(leaf, "ndim", 0)])

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _precompute_cross_kv(cfg: Seq2SeqConfig, p: tp.Dict,
                         memory: jax.Array) -> tp.List:
    """Cross K/V per decoder block, computed ONCE per generation.

    The cross-attention keys/values depend only on the encoder memory —
    loop-invariant across decode steps — so caching them turns the
    per-step cross sublayer into one [B,1,H,Dh] @ [B,S,H,Dh] attention
    with no projection matmuls."""
    out = []
    for i in range(cfg.dec_layers):
        kernel = p[f"dec_blocks_{i}"]["xattn"]["kv"]["kernel"]
        kv = jnp.einsum("bsd,dchk->bschk", memory.astype(cfg.dtype),
                        kernel.astype(cfg.dtype))
        out.append((kv[:, :, 0], kv[:, :, 1]))
    return out


def init_decode_cache(cfg: Seq2SeqConfig, batch: int,
                      max_len: int) -> tp.Dict:
    """Self-attention K/V cache for the decoder blocks."""
    shape = (batch, max_len, cfg.num_heads, cfg.head_dim)
    return {f"dec_blocks_{i}": {"k": jnp.zeros(shape, cfg.dtype),
                                "v": jnp.zeros(shape, cfg.dtype)}
            for i in range(cfg.dec_layers)}


def _dec_step(cfg: Seq2SeqConfig, p: tp.Dict, tokens: jax.Array,
              positions: jax.Array, cache: tp.Dict,
              cache_index: jax.Array, cross_kv: tp.List):
    """Decoder forward of `tokens` [B, S] against the caches.

    Mirrors `Seq2SeqTransformer.decode` exactly (same kernels, same
    f32 softmax/logit recipe) but attends to the cached self-attention
    prefix and the precomputed cross K/V. The self-attention and MLP
    bodies are decoding.py's shared cached-layer helpers (one
    implementation of the cache-update + prefix-mask recipe, quantized
    kernels included); only the cross sublayer is seq2seq-specific.
    Returns (logits, new_cache).
    """
    from .decoding import _cached_self_attention
    from .moe import gated_mlp

    x = jnp.take(p["embed"], tokens, axis=0).astype(cfg.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    new_cache = {}
    block_cfg = cfg._block_config(causal=True)  # states the rotary keys
    for i in range(cfg.dec_layers):
        name = f"dec_blocks_{i}"
        bp = p[name]
        x, k_cache, v_cache = _cached_self_attention(
            block_cfg, bp, x, positions, cache[name]["k"], cache[name]["v"],
            cache_index)
        new_cache[name] = {"k": k_cache, "v": v_cache}

        # -- cross-attention against the precomputed memory K/V --
        normed = rmsnorm(x, bp["norm2"]["scale"], cfg.dtype)
        qx = jnp.einsum("btd,dhk->bthk", normed,
                        bp["xattn"]["q"]["kernel"].astype(cfg.dtype))
        kx, vx = cross_kv[i]
        xs = jnp.einsum("bqhd,bkhd->bhqk", qx, kx,
                        preferred_element_type=jnp.float32) * scale
        xp = jax.nn.softmax(xs, axis=-1)
        xa = jnp.einsum("bhqk,bkhd->bqhd", xp.astype(cfg.dtype), vx)
        x = x + jnp.einsum("bqhd,hdD->bqD", xa,
                           bp["xattn"]["out"]["kernel"].astype(cfg.dtype))

        x = x + gated_mlp(bp["mlp"],
                           rmsnorm(x, bp["norm3"]["scale"], cfg.dtype),
                           cfg.dtype)

    x = rmsnorm(x, p["dec_norm"]["scale"], cfg.dtype)
    logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                        p["embed"].astype(jnp.float32))
    return logits, new_cache


def cached_translate(model: Seq2SeqTransformer, params: tp.Any,
                     src: jax.Array, *, max_new_tokens: int,
                     bos_id: int = 1) -> jax.Array:
    """Greedy decode with KV caches: O(T) per step instead of O(T^2).

    The encoder runs once; the cross K/V are precomputed per block;
    each step runs the decoder on ONE token against the cached
    self-attention prefix. Same argmax chain as `greedy_translate`
    (the oracle tests assert token-exact agreement).
    """
    cfg = model.config
    if max_new_tokens + 1 > cfg.max_seq_len:
        raise ValueError(
            f"max_new_tokens + 1 = {max_new_tokens + 1} exceeds "
            f"max_seq_len={cfg.max_seq_len}")
    batch = src.shape[0]
    memory = model.apply(params, src, method=Seq2SeqTransformer.encode)
    p = params["params"]
    cross_kv = _precompute_cross_kv(cfg, p, memory)
    cache = init_decode_cache(cfg, batch, max_new_tokens + 1)

    bos = jnp.full((batch, 1), bos_id, jnp.int32)

    def step(carry, t):
        token, cache = carry
        positions = jnp.broadcast_to(t, (batch, 1)).astype(jnp.int32)
        logits, cache = _dec_step(cfg, p, token, positions, cache, t,
                                  cross_kv)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return (nxt, cache), nxt[:, 0]

    (_, _), tokens = jax.lax.scan(step, (bos, cache),
                                  jnp.arange(max_new_tokens))
    return tokens.T


def greedy_translate(model: Seq2SeqTransformer, params: tp.Any,
                     src: jax.Array, *, max_new_tokens: int,
                     bos_id: int = 1) -> jax.Array:
    """Greedy decode: returns [B, max_new_tokens] generated tokens.

    The encoder memory is computed ONCE; the scan re-runs only the
    decoder on a padded static-shape target buffer (causal masking
    makes the padding inert for already-decoded positions). Exact but
    O(T^2) in the decoder; long-generation serving belongs to the
    KV-cache LM decoder.
    """
    batch = src.shape[0]
    memory = model.apply(params, src, method=Seq2SeqTransformer.encode)
    buf = jnp.full((batch, max_new_tokens + 1), bos_id, jnp.int32)

    def step(buf, t):
        logits = model.apply(params, buf, memory,
                             method=Seq2SeqTransformer.decode)
        nxt = jnp.argmax(logits[:, t], axis=-1).astype(jnp.int32)
        buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, t + 1, axis=1)
        return buf, nxt

    _, tokens = jax.lax.scan(step, buf, jnp.arange(max_new_tokens))
    return tokens.T
