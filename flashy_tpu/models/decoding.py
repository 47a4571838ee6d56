# Autoregressive decoding for TransformerLM: KV-cached generation under
# lax.scan — the "generate" stage counterpart of the training path
# (AudioCraft-style solvers interleave train/valid/generate stages; the
# reference framework is model-agnostic but its downstream users need
# this). TPU-first: static shapes throughout (cache laid out at
# max_len), one fused scan instead of a python token loop, greedy or
# temperature/top-k sampling.
#
# All TransformerLM layouts decode here: per-layer parameter trees
# (block_i), scan-stacked models (stacked [L, ...] params — the cache is
# stacked too and the layer loop is a lax.scan), MoE blocks (routed
# dropless at decode time through `moe.expert_layer`: every token sees
# its top-k experts; capacity buffers are a *training* batching artifact
# with no meaning for autoregressive decoding) and latent-attention
# blocks (`attn_kind='mla'`: the cache holds the latent, models/mla.py).
"""KV-cache decoding: generate(model, params, prompt, ...) -> tokens."""
import numbers
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ssd_scan import ssd_chunked_scan, ssd_recurrent_scan
from . import gqa, mamba2, mla
from .moe import expert_layer, gated_mlp
from .transformer import (TransformerConfig, _rotary, expert_layers,
                          mixer_pattern, pattern_kinds, rmsnorm as _rmsnorm)
from .quantize import (is_quantized, kernel_operand as _kernel,
                       postscale as _postscale)
from .ssd import ssd_log_decay


def _norm(cfg, x: jax.Array, scale: jax.Array) -> jax.Array:
    """RMSNorm at the config's epsilon (a config of another family that
    shares these bodies, models/seq2seq.py, states none: 1e-6)."""
    return _rmsnorm(x, scale, cfg.dtype, getattr(cfg, "norm_eps", 1e-6))


def _split_heads(qkv: jax.Array) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def init_cache(cfg: TransformerConfig, batch: int, max_len: int) -> tp.Dict:
    """Allocate the static-shape decode cache.

    Attention layers get {'k','v'} slabs [B, max_len, H, Dh] — or,
    under latent attention, {'c','kr'} slabs [B, max_len, 1, width]:
    the normed latent and the rotated shared key, one row a token for
    all heads (the singleton keeps the slot dim where the K/V slabs
    have it); SSD layers get one {'ssd'} f32 state [B, H, Dh, Dstate] —
    NO max_len dim, the O(1)-in-context-length decode state. Per-layer
    models get
    one entry per block; scan-stacked models (uniform mixer pattern by
    construction) get single stacked [L, ...] arrays, the layer dim
    scanned together with the stacked parameters. Both layouts keep the
    slot (batch) dim at position -4 on every leaf, which is what the
    serving engine's slot take/merge slicing relies on. Under a
    `layer_pattern` a layer's entry is its one mixer's: a Mamba-2
    layer's {'state','conv'} (`mamba2.state_spec`, no max_len either),
    an attention layer's grouped slabs, an expert layer's nothing.
    """
    shape = (batch, max_len, cfg.num_heads, cfg.head_dim)
    sshape = (batch, cfg.num_heads, cfg.head_dim, cfg.ssd_state_dim)
    pattern = mixer_pattern(cfg)
    expert_layers(cfg)  # refuses what the new kinds cannot combine with
    if cfg.attn_kind == "gqa" or cfg.layer_pattern:
        # every attention layer a slab of its own kind's heads, a window
        # layer's whole too: the dense layout masks a window, it bounds
        # nothing; under a `layer_pattern` the other layers hold their
        # own mixer's entry
        kinds = gqa.layer_kinds(cfg) if cfg.attn_kind == "gqa" else ()
        pattern = pattern_kinds(cfg) or "*" * cfg.num_layers

        def entry(layer):
            if pattern[layer] == "E":
                return {}
            if pattern[layer] == "M":
                return {name: jnp.zeros(*leaf) for name, leaf in
                        mamba2.state_spec(cfg, batch).items()}
            return {"k": jnp.zeros((batch, max_len, kinds[layer].kv_heads,
                                    gqa.key_dim(cfg)), cfg.dtype),
                    "v": jnp.zeros((batch, max_len, kinds[layer].kv_heads,
                                    gqa.value_dim(cfg)), cfg.dtype)}

        return {f"block_{i}": entry(i) for i in range(cfg.num_layers)}
    if cfg.attn_kind == "mla":
        attn = {"c": (batch, max_len, 1, cfg.kv_lora_rank),
                "kr": (batch, max_len, 1, cfg.qk_rope_head_dim)}
    else:
        attn = {"k": shape, "v": shape}
    if cfg.scan_layers:
        if pattern[0] == "ssd":
            return {"ssd": jnp.zeros((cfg.num_layers,) + sshape,
                                     jnp.float32)}
        stacked = (cfg.num_layers,) + shape
        return {"k": jnp.zeros(stacked, cfg.dtype),
                "v": jnp.zeros(stacked, cfg.dtype)}
    return {
        f"block_{i}": (
            {"ssd": jnp.zeros(sshape, jnp.float32)}
            if pattern[i] == "ssd" else
            {name: jnp.zeros(dims, cfg.dtype) for name, dims in attn.items()})
        for i in range(cfg.num_layers)
    }


# The decode step's named scopes — ONE set for the dense step below and
# the paged step (serve/paged.py), so a device trace attributes time the
# same way under both layouts: embed, norm, qkv, rotary, kv_write, attn,
# out_proj, mlp, head (+ `sample` in the engine); a latent block has
# mla_q, mla_kv, kv_write, attn, mla_out; an expert layer nests router,
# experts, shared_expert under mlp. The scope is the HLO `op_name` path
# of every op traced inside it.
def _qkv_heads(cfg, bp: tp.Dict, x: jax.Array, positions: jax.Array
               ) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-norm, fused QKV projection and rotary: (q, k, v), each
    [B, S, H, Dh] (quantized kernels supported)."""
    with jax.named_scope("norm"):
        normed = _norm(cfg, x, bp["norm1"]["scale"])
    with jax.named_scope("qkv"):
        qkv_w, qkv_s = _kernel(bp["attn"]["qkv"]["kernel"], cfg.dtype)
        qkv = _postscale(jnp.einsum("btd,dchk->btchk", normed, qkv_w), qkv_s)
        q, k, v = _split_heads(qkv)
    with jax.named_scope("rotary"):
        return _rotary(q, positions, cfg), _rotary(k, positions, cfg), v


def _attn_residual(cfg, bp: tp.Dict, x: jax.Array, attn: jax.Array
                   ) -> jax.Array:
    """x + output projection of the attended heads [B, S, H, Dh]."""
    with jax.named_scope("out_proj"):
        out_w, out_s = _kernel(bp["attn"]["out"]["kernel"], cfg.dtype)
        return x + _postscale(jnp.einsum("bqhd,hdD->bqD", attn, out_w),
                              out_s)


def _mlp_residual(cfg, bp: tp.Dict, x: jax.Array,
                  stats: tp.Optional[tp.List] = None) -> jax.Array:
    """x + the block's pre-normed MLP (gated, or the expert layer, whose
    (assignments, experts hit) pair is appended to `stats` if given)."""
    with jax.named_scope("norm"):
        normed = _norm(cfg, x, bp["norm2"]["scale"])
    with jax.named_scope("mlp"):
        if "moe" in bp:
            out, counts = expert_layer(cfg, bp["moe"], normed)
            if stats is not None:
                stats.append(counts)
            return x + out
        return x + gated_mlp(bp["mlp"], normed, cfg.dtype)


def _cache_write(cache: jax.Array, new: jax.Array,
                 cache_index: jax.Array) -> jax.Array:
    """Write `new` [B, S, H, Dh] into `cache` at `cache_index`.

    A scalar index writes the same offset for every row (the batched
    `generate()` path: one shared decode position). A [B] vector writes
    each row at its own offset — the serving path, where every slot of
    the shared cache sits at a different sequence length. Out-of-range
    rows (a retired slot parked at max_len) are dropped, not clamped:
    a clamp would silently overwrite the last real position.
    """
    if jnp.ndim(cache_index) == 0:
        return jax.lax.dynamic_update_slice(
            cache, new, (0, cache_index, 0, 0))
    batch, seq = new.shape[:2]
    rows = jnp.arange(batch)[:, None]
    cols = cache_index[:, None] + jnp.arange(seq)[None, :]
    return cache.at[rows, cols].set(new, mode="drop")


def _cached_self_attention(cfg, bp: tp.Dict, x: jax.Array,
                           positions: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, cache_index: jax.Array):
    """Pre-norm causal self-attention against the K/V cache.

    Returns (x + attn_out, k_cache, v_cache). `cfg` only needs
    `.dtype`/`.head_dim`, so the seq2seq decoder shares this body (and
    its quantized-kernel support) — ONE implementation of the cache
    update + causal-prefix mask recipe. `cache_index` is a scalar (all
    rows at the same length) or a [B] vector (per-slot lengths, the
    serving engine); the causal mask is per-row either way because it
    derives from `positions`, so rows at different lengths attend only
    their own live prefix."""
    q, k, v = _qkv_heads(cfg, bp, x, positions)
    with jax.named_scope("kv_write"):
        k_cache = _cache_write(k_cache, k.astype(cfg.dtype), cache_index)
        v_cache = _cache_write(v_cache, v.astype(cfg.dtype), cache_index)

    # Attend over the cache prefix [0, cache_index + seq).
    with jax.named_scope("attn"):
        max_len = k_cache.shape[1]
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                            preferred_element_type=jnp.float32) * scale
        key_pos = jnp.arange(max_len)[None, :]
        query_pos = positions[:, :, None]  # [B, S, 1] global positions
        mask = key_pos[None] <= query_pos  # causal over the cache
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype),
                          v_cache)
    return _attn_residual(cfg, bp, x, attn), k_cache, v_cache


def _ssd_mixer_forward(cfg, bp: tp.Dict, x: jax.Array, state: jax.Array,
                       token_mask: tp.Optional[jax.Array],
                       state_mask: tp.Optional[jax.Array]):
    """Pre-norm SSD mixer against the resident [B, H, Dh, N] f32 state.

    Returns (x + mixer_out, new_state). The dual-form dispatch is by
    shape: a single-token call (a decode tick) advances the recurrence
    — bit-identical whether it happens in `generate()`'s token loop or
    the serving engine's decode step — while a multi-token call (a
    prefill slice) runs the chunked form, whose fixed-chunk tiling
    makes any chunk-aligned partitioning of the stream bit-identical
    to one whole-stream call (ops.ssd_scan). `token_mask` [B, S] masks
    right-padded prefill tokens out of the state; `state_mask` [B]
    False freezes a row's state entirely (the engine's inactive slots:
    a mid-chunked-prefill slot must not have its accumulated state
    advanced by decode ticks it is not part of).
    """
    normed = _norm(cfg, x, bp["norm1"]["scale"])
    nstate = cfg.ssd_state_dim
    cbv_w, cbv_s = _kernel(bp["ssd"]["cbv"]["kernel"], cfg.dtype)
    cbv = _postscale(jnp.einsum("btd,dhp->bthp", normed, cbv_w), cbv_s)
    c = cbv[..., :nstate]
    b = cbv[..., nstate:2 * nstate]
    v = cbv[..., 2 * nstate:2 * nstate + cfg.head_dim]
    log_a = ssd_log_decay(cbv[..., -1], bp["ssd"]["dt_bias"])
    if x.shape[1] == 1:
        y, new_state = ssd_recurrent_scan(c, b, v, log_a, state)
    else:
        y, new_state = ssd_chunked_scan(
            c, b, v, log_a, state=state,
            chunk=cfg.ssd_chunk if cfg.ssd_chunk > 0 else None,
            token_mask=token_mask, kernel=cfg.ssd_kernel)
    if state_mask is not None:
        new_state = jnp.where(state_mask[:, None, None, None], new_state,
                              state)
    out_w, out_s = _kernel(bp["ssd"]["out"]["kernel"], cfg.dtype)
    out = _postscale(jnp.einsum("bthd,hdD->btD", y, out_w), out_s)
    return x + out, new_state


def _ssd_layer_forward(cfg: TransformerConfig, bp: tp.Dict, x: jax.Array,
                       state: jax.Array,
                       token_mask: tp.Optional[jax.Array] = None,
                       state_mask: tp.Optional[jax.Array] = None):
    """One SSD block against the resident state: returns (x, state)."""
    x, state = _ssd_mixer_forward(cfg, bp, x, state, token_mask,
                                  state_mask)
    return _mlp_residual(cfg, bp, x), state


def latent_projections(cfg, bp: tp.Dict, x: jax.Array, positions: jax.Array):
    """A latent block's pre-norm and projections, shared by the dense
    and the paged step: (q_lat, q_rope) — the queries with W_kvb's key
    half absorbed — and (c_kv, k_rope), the token's cache row."""
    with jax.named_scope("norm"):
        normed = _norm(cfg, x, bp["norm1"]["scale"])
    with jax.named_scope("mla_q"):
        q_nope, q_rope = mla.queries(cfg, bp["attn"], normed, positions)
        q_lat = mla.absorb_queries(cfg, bp["attn"], q_nope)
    with jax.named_scope("mla_kv"):
        c_kv, k_rope = mla.latents(cfg, bp["attn"], normed, positions)
    return (q_lat, q_rope), (c_kv, k_rope)


def latent_residual(cfg, bp: tp.Dict, x: jax.Array, o_lat: jax.Array
                    ) -> jax.Array:
    """x + W_o of the attended latents expanded through W_kvb's value
    half."""
    with jax.named_scope("mla_out"):
        return x + mla.output(cfg, bp["attn"],
                              mla.expand_values(cfg, bp["attn"], o_lat))


def _cached_latent_attention(cfg, bp: tp.Dict, x: jax.Array,
                             positions: jax.Array, entry: tp.Dict,
                             cache_index: jax.Array):
    """Pre-norm latent attention (cached form, models/mla.py) against
    the dense {'c','kr'} slabs: returns (x + attn_out, entry)."""
    (q_lat, q_rope), (c_kv, k_rope) = latent_projections(cfg, bp, x,
                                                         positions)
    with jax.named_scope("kv_write"):
        entry = {"c": _cache_write(entry["c"], c_kv[:, :, None], cache_index),
                 "kr": _cache_write(entry["kr"], k_rope[:, :, None],
                                    cache_index)}
    with jax.named_scope("attn"):
        o_lat = mla.cached_attention(cfg, q_lat, q_rope, entry["c"][:, :, 0],
                                     entry["kr"][:, :, 0], positions)
    return latent_residual(cfg, bp, x, o_lat), entry


def grouped_projections(cfg, kind: gqa.LayerKind, bp: tp.Dict,
                        x: jax.Array, positions: jax.Array):
    """A grouped-attention block's pre-norm and projections, shared by
    the dense and the paged step: (q, k, v) of models/gqa.py."""
    with jax.named_scope("norm"):
        normed = _norm(cfg, x, bp["norm1"]["scale"])
    return gqa.project(cfg, kind, bp["attn"], normed, positions)


def grouped_residual(cfg, bp: tp.Dict, x: jax.Array, heads_out: jax.Array
                     ) -> jax.Array:
    """x + W_o of the attended heads [B, S, H, Dv]."""
    with jax.named_scope("out_proj"):
        return x + gqa.output(cfg, bp["attn"], heads_out)


def _cached_grouped_attention(cfg, kind: gqa.LayerKind, bp: tp.Dict,
                              x: jax.Array, positions: jax.Array,
                              entry: tp.Dict, cache_index: jax.Array):
    """Pre-norm grouped attention of one layer kind against the dense
    {'k','v'} slabs, row s of which holds position s: returns
    (x + attn_out, entry)."""
    q, k, v = grouped_projections(cfg, kind, bp, x, positions)
    with jax.named_scope("kv_write"):
        entry = {"k": _cache_write(entry["k"], k.astype(cfg.dtype),
                                   cache_index),
                 "v": _cache_write(entry["v"], v.astype(cfg.dtype),
                                   cache_index)}
    with jax.named_scope("attn"), jax.named_scope(kind.scope):
        rows = jnp.broadcast_to(jnp.arange(entry["k"].shape[1]),
                                entry["k"].shape[:2])
        side_by_side = lambda slab: slab.reshape(slab.shape[:2] + (-1,))
        heads_out = gqa.attend(cfg, kind, bp["attn"], q,
                               side_by_side(entry["k"]),
                               side_by_side(entry["v"]), rows, positions)
    return grouped_residual(cfg, bp, x, heads_out), entry


def mamba_residual(cfg, bp: tp.Dict, x: jax.Array, state: jax.Array,
                   tail: jax.Array, *, rows: tp.Optional[jax.Array] = None,
                   used: tp.Optional[jax.Array] = None):
    """x + a Mamba-2 layer's mixer on its pre-norm, shared by the dense
    and the paged step: (x, state, tail) of `mamba2.mixer`."""
    with jax.named_scope("norm"):
        normed = _norm(cfg, x, bp["norm1"]["scale"])
    out, state, tail = mamba2.mixer(cfg, bp["ssm"], normed, state, tail,
                                    rows=rows, used=used)
    return x + out, state, tail


def _layer_forward(cfg: TransformerConfig, bp: tp.Dict, x: jax.Array,
                   positions: jax.Array, entry: tp.Dict,
                   cache_index: jax.Array,
                   stats: tp.Optional[tp.List] = None, layer: int = 0,
                   token_mask: tp.Optional[jax.Array] = None):
    """One block against its cache entry ({'k','v'} slabs, or the
    latent {'c','kr'}): returns (x, entry). `layer` picks the layer's
    kind where the config has one a layer (`attn_kind='gqa'`, and a
    `layer_pattern`, whose layer is its one mixer: the experts, the
    attention alone, or the Mamba-2 mixer against its {'state','conv'}
    entry, `token_mask` keeping a slice's pads out of both)."""
    if cfg.layer_pattern:
        kind = pattern_kinds(cfg)[layer]
        if kind == "E":
            return _mlp_residual(cfg, bp, x, stats), entry
        if kind == "*":
            return _cached_grouped_attention(
                cfg, gqa.layer_kinds(cfg)[layer], bp, x, positions, entry,
                cache_index)
        used = None if token_mask is None else jnp.sum(token_mask, axis=1)
        x, state, tail = mamba_residual(cfg, bp, x, entry["state"],
                                        entry["conv"], used=used)
        return x, {"state": state, "conv": tail}
    if cfg.attn_kind == "gqa":
        x, entry = _cached_grouped_attention(
            cfg, gqa.layer_kinds(cfg)[layer], bp, x, positions, entry,
            cache_index)
    elif cfg.attn_kind == "mla":
        x, entry = _cached_latent_attention(cfg, bp, x, positions, entry,
                                            cache_index)
    else:
        x, k_cache, v_cache = _cached_self_attention(
            cfg, bp, x, positions, entry["k"], entry["v"], cache_index)
        entry = {"k": k_cache, "v": v_cache}
    return _mlp_residual(cfg, bp, x, stats), entry


@jax.named_scope("embed")
def _embed_tokens(p: tp.Dict, tokens: jax.Array, dtype) -> jax.Array:
    """Token ids [B, S] -> embeddings [B, S, D] (int8 tables supported).

    Shared by the dense `_apply_step` and the paged serving step
    (serve/paged.py) — one copy of the quantized-row-gather rule.
    """
    if is_quantized(p["embed"]):
        # Row gather stays int8 (tiny); dequantize only the gathered rows.
        return (jnp.take(p["embed"]["q"], tokens, axis=0).astype(dtype)
                * jnp.take(p["embed"]["scale"], tokens, axis=0).astype(dtype))
    return jnp.take(p["embed"], tokens, axis=0).astype(dtype)


def _head_logits(p: tp.Dict, x: jax.Array, cfg: TransformerConfig
                 ) -> jax.Array:
    """Final norm + LM head (the tied embedding, or the `head` table of
    an untied model): [B, S, D] -> f32 logits [B, S, V].

    Head operands in the compute dtype + f32 accumulation — must match
    TransformerLM.__call__'s head exactly (the decode-vs-uncached-
    forward equality tests compare these logits). The quantized head's
    per-vocab-row scale applies to the f32 logits. Shared by the dense
    and paged apply steps.
    """
    with jax.named_scope("norm"):
        x = _norm(cfg, x, p["norm_f"]["scale"])
    with jax.named_scope("head"):
        table = p["head"] if "head" in p else p["embed"]
        if is_quantized(table):
            logits = jnp.einsum("btd,vd->btv", x,
                                table["q"].astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
            return logits * table["scale"][:, 0]
        return jnp.einsum("btd,vd->btv", x, table.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _apply_step(model, params, cfg: TransformerConfig, tokens: jax.Array,
                positions: jax.Array, cache: tp.Dict, cache_index: jax.Array,
                *, token_mask: tp.Optional[jax.Array] = None,
                state_mask: tp.Optional[jax.Array] = None,
                stats: tp.Optional[tp.List] = None):
    """Forward `tokens` [B, S] at `positions`, reading+writing the cache.

    Re-implements the block stack against cached K/V (the training
    module computes full-sequence attention; decoding attends to the
    cache prefix). SSD layers — recognized by their {'ssd'} cache entry
    — advance their resident state instead (see `_ssd_mixer_forward`;
    `token_mask` / `state_mask` apply only to them: attention layers
    already ignore padded/parked rows through the positions-derived
    mask and out-of-range-dropped cache writes). Weights are read from
    the same parameter tree; the scan-stacked layout runs the layer
    loop as a lax.scan over the stacked params + stacked cache. Each
    expert layer of an unstacked model appends its (assignments, experts
    hit) counts to `stats` when a list is given.
    """
    p = params["params"]
    x = _embed_tokens(p, tokens, cfg.dtype)
    if cfg.scan_layers:
        stacked = p["blocks"]["block"]  # every leaf has leading [L]
        if "ssd" in cache:
            def ssd_body(x, layer_in):
                bp, s = layer_in
                x, s = _ssd_layer_forward(cfg, bp, x, s, token_mask,
                                          state_mask)
                return x, s

            x, states = jax.lax.scan(ssd_body, x, (stacked, cache["ssd"]))
            new_cache: tp.Dict = {"ssd": states}
        else:
            def body(x, layer_in):
                bp, k_c, v_c = layer_in
                x, entry = _layer_forward(cfg, bp, x, positions,
                                          {"k": k_c, "v": v_c}, cache_index)
                return x, (entry["k"], entry["v"])

            x, (k_cache, v_cache) = jax.lax.scan(
                body, x, (stacked, cache["k"], cache["v"]))
            new_cache = {"k": k_cache, "v": v_cache}
    else:
        new_cache = {}
        for layer in range(cfg.num_layers):
            name = f"block_{layer}"
            if "ssd" in cache[name]:
                x, state = _ssd_layer_forward(
                    cfg, p[name], x, cache[name]["ssd"], token_mask,
                    state_mask)
                new_cache[name] = {"ssd": state}
            else:
                x, new_cache[name] = _layer_forward(
                    cfg, p[name], x, positions, cache[name], cache_index,
                    stats, layer, token_mask)

    return _head_logits(p, x, cfg), new_cache


def speculative_acceptance(draft_tokens: jax.Array, logits: jax.Array, *,
                           temperature: float = 0.0,
                           draft_probs: tp.Optional[jax.Array] = None,
                           rng: tp.Optional[jax.Array] = None,
                           pad_token: int = 0
                           ) -> tp.Tuple[jax.Array, jax.Array]:
    """Longest-prefix acceptance of drafted tokens against target logits.

    The verify forward scores a slot's last emitted token plus its k
    drafted tokens in ONE `[B, k+1]` call; `logits[:, i]` is then the
    target model's distribution for draft token i (and `logits[:, k]`
    the "bonus" position after all k drafts). This function turns those
    logits into the emitted tokens of a speculative step:

    * Greedy (`temperature == 0`): draft token i is accepted iff it
      equals `argmax(logits[:, i])` and every earlier draft was
      accepted. The emitted tokens are exactly the target's greedy
      tokens — accepted drafts ARE the argmax, and the first
      disagreement (or the bonus position) contributes the argmax
      token itself — so a speculative greedy decode is token-for-token
      identical to `generate()`, whatever the draft proposed.
    * Sampling (`temperature > 0`): classic rejection sampling. Draft
      token x_i (proposal probability q_i(x_i), one-hot when
      `draft_probs` is None — a deterministic draft like n-gram lookup
      or a greedy draft model) is accepted with probability
      `min(1, p_i(x_i) / q_i(x_i))`; the first rejection resamples from
      the residual distribution `norm(max(0, p_i - q_i))`, and full
      acceptance samples the bonus position from `p_k`. The emitted
      tokens are an exact sample from the target distribution — the
      rejection-sampling identity — so speculation changes throughput,
      never the output law.

    Everything is fixed-shape (`accepted` is data, never a shape), so
    one compiled executable serves every acceptance outcome.

    Args:
        draft_tokens: [B, k] int drafted tokens.
        logits: [B, k+1, V] target logits from the verify forward.
        temperature: must match the sampling temperature of the serving
            engine (0 = greedy).
        draft_probs: optional [B, k, V] proposal distribution; None
            means a deterministic proposal (one-hot at `draft_tokens`).
        rng: PRNG key, required when `temperature > 0`.
        pad_token: fills the out-token tail beyond the emitted span.

    Returns:
        (out_tokens, accepted): out_tokens [B, k+1] holds the emitted
        tokens at indices 0..accepted (inclusive — index `accepted` is
        the bonus/resampled token) and `pad_token` beyond; accepted [B]
        counts the drafts kept (0..k).
    """
    batch, k = draft_tokens.shape
    draft_tokens = draft_tokens.astype(jnp.int32)
    idx = jnp.arange(k + 1, dtype=jnp.int32)[None]

    if temperature <= 0.0:
        target = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
        match = draft_tokens == target[:, :k]
        accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=-1),
                           axis=-1)
        out = jnp.where(idx <= accepted[:, None], target, jnp.int32(pad_token))
        return out, accepted

    if rng is None:
        raise ValueError("speculative_acceptance(temperature>0) resamples "
                         "rejected positions and needs an explicit `rng`.")
    probs = jax.nn.softmax(logits[:, :k] / temperature, axis=-1)  # [B, k, V]
    p_x = jnp.take_along_axis(probs, draft_tokens[..., None],
                              axis=-1)[..., 0]                    # [B, k]
    if draft_probs is None:
        vocab = logits.shape[-1]
        q_full = jax.nn.one_hot(draft_tokens, vocab, dtype=probs.dtype)
        q_x = jnp.ones_like(p_x)
    else:
        q_full = draft_probs.astype(probs.dtype)
        q_x = jnp.take_along_axis(q_full, draft_tokens[..., None],
                                  axis=-1)[..., 0]
    key_u, key_s = jax.random.split(rng)
    u = jax.random.uniform(key_u, draft_tokens.shape, dtype=probs.dtype)
    # u < min(1, p/q)  <=>  u * q < p  (no division, q == 0 safe)
    accept = u * q_x < p_x
    accepted = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1),
                       axis=-1)                                   # [B]

    # Final-token distribution: the residual at the first rejected index
    # (a rejection implies q(x) > p(x) there, so the residual has mass),
    # or the plain bonus distribution after full acceptance.
    rows = jnp.arange(batch)
    at = jnp.clip(accepted, 0, k - 1)
    residual = jnp.maximum(probs[rows, at] - q_full[rows, at], 0.0)
    residual = residual / jnp.maximum(
        jnp.sum(residual, axis=-1, keepdims=True), 1e-20)
    bonus = jax.nn.softmax(logits[:, k] / temperature, axis=-1)
    dist = jnp.where((accepted < k)[:, None], residual, bonus)
    final = jax.random.categorical(
        key_s, jnp.where(dist > 0, jnp.log(dist), -jnp.inf),
        axis=-1).astype(jnp.int32)

    padded_draft = jnp.concatenate(
        [draft_tokens, jnp.full((batch, 1), pad_token, jnp.int32)], axis=1)
    out = jnp.where(idx < accepted[:, None], padded_draft,
                    jnp.where(idx == accepted[:, None], final[:, None],
                              jnp.int32(pad_token)))
    return out, accepted


def nucleus_filter(logits: jax.Array, top_p: float) -> jax.Array:
    """Top-p (nucleus) logit filter, sort-once formulation.

    A token stays eligible iff the cumulative probability of STRICTLY
    more likely tokens is < `top_p` — so the argmax always survives,
    even when its own probability exceeds `top_p`. Tokens exactly TIED
    with the cutoff logit all stay eligible (dropping an arbitrary
    subset of equally-likely tokens would bias the distribution).
    Ineligible logits are masked to -1e30. Jit-safe (one sort + cumsum,
    no dynamic shapes); `logits` is [..., vocab]. A concrete `top_p`
    must be in (0, 1]: `top_p <= 0` would make EVERY position
    ineligible (near-uniform sampling over -1e30 logits) and is
    rejected loudly instead.
    """
    # concrete values only: python scalars AND numpy scalars (np.float32
    # is not a python float); traced values can't be range-checked.
    if (isinstance(top_p, (numbers.Real, np.number))
            and not 0.0 < float(top_p) <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    eligible = cum_before < top_p
    # The argmax survives unconditionally — also for traced top_p values
    # the concreteness check above cannot see.
    eligible = eligible.at[..., 0].set(True)
    # cutoff = the smallest sorted logit still eligible per row
    cutoff = jnp.min(jnp.where(eligible, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, -1e30, logits)


def generate(model, params, prompt: jax.Array, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: tp.Optional[int] = None,
             top_p: tp.Optional[float] = None,
             eos_token: tp.Optional[int] = None,
             rng: tp.Optional[jax.Array] = None) -> jax.Array:
    """Autoregressive generation with a KV cache.

    Args:
        model: a TransformerLM (its config drives shapes). All layer
            layouts are supported: per-layer params, scan-stacked, and
            MoE blocks (decoded dropless — see `moe.expert_layer`).
        params: the model's variables ({'params': ...}).
        prompt: [B, P] int32 prompt tokens.
        max_new_tokens: tokens to append.
        temperature: 0 -> greedy; >0 -> sampling.
        top_k: restrict sampling to the k most likely tokens.
        top_p: nucleus sampling — restrict to the smallest set of
            tokens whose cumulative probability reaches `top_p` (the
            most likely token always stays eligible). Composes with
            top_k (applied first).
        eos_token: when set, a row that emits this token is *done*:
            every subsequent token of that row is pinned to `eos_token`
            inside the scan (mask-based, shapes stay static — the scan
            still runs `max_new_tokens` steps). The serving engine
            (`flashy_tpu.serve`) reuses the same emitted-EOS convention
            for slot retirement.
        rng: PRNG key — required when temperature > 0 (sampling without
            an explicit key would silently reuse PRNGKey(0) across
            calls); greedy decoding needs no key.

    Returns [B, P + max_new_tokens] tokens. Jit-compatible: shapes are
    static in P and max_new_tokens.
    """
    cfg: TransformerConfig = model.config
    if not getattr(cfg, "causal", True):
        # the KV-cache mask below is causal by construction; decoding a
        # bidirectional encoder would silently diverge from model.apply
        raise ValueError(
            "generate() implements causal KV-cache decoding; a "
            "config.causal=False (bidirectional/encoder) model has no "
            "autoregressive decode.")
    if rng is None:
        # float() concretizes python/numpy scalars AND concrete 0-d jax
        # arrays; only a traced temperature escapes the check (and the
        # `temperature <= 0` python branch in sample() rejects traced
        # values loudly anyway).
        try:
            concrete_temp = float(temperature)
        except (TypeError, jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            concrete_temp = None
        if concrete_temp is not None and concrete_temp > 0.0:
            raise ValueError(
                "generate(temperature>0) samples and needs an explicit "
                "`rng` key; pass rng=jax.random.PRNGKey(...) (greedy "
                "temperature=0 decoding needs no key).")
    batch, prompt_len = prompt.shape
    total = prompt_len + max_new_tokens
    if total > cfg.max_seq_len and "attention" in mixer_pattern(cfg):
        # pure-SSD stacks have no length-dependent state — nothing
        # caps T (the streaming-session story); any attention layer
        # reinstates the ceiling.
        raise ValueError(f"prompt + new tokens {total} > max_seq_len {cfg.max_seq_len}")
    cache = init_cache(cfg, batch, total)

    # Prefill: run the whole prompt through once.
    positions = jnp.broadcast_to(jnp.arange(prompt_len, dtype=jnp.int32)[None],
                                 (batch, prompt_len))
    logits, cache = _apply_step(model, params, cfg, prompt, positions, cache,
                                jnp.int32(0))
    last_logits = logits[:, -1]

    if rng is None:
        # greedy path (validated above): the key is split, never consulted
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None:
            kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None:
            logits = nucleus_filter(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def step(carry, t):
        last_logits, cache, key, done = carry
        key, sub = jax.random.split(key)
        token = sample(last_logits, sub)
        if eos_token is not None:
            # rows already done keep emitting EOS; the row that samples
            # EOS right now emits it (it IS the terminator) and is done
            # from the next step on.
            token = jnp.where(done, jnp.int32(eos_token), token)
            done = done | (token == eos_token)
        position = jnp.broadcast_to(prompt_len + t, (batch, 1)).astype(jnp.int32)
        logits, cache = _apply_step(model, params, cfg, token[:, None],
                                    position, cache, prompt_len + t)
        return (logits[:, -1], cache, key, done), token

    done0 = jnp.zeros((batch,), bool)
    (_, _, _, _), tokens = jax.lax.scan(
        step, (last_logits, cache, rng, done0), jnp.arange(max_new_tokens))
    return jnp.concatenate([prompt, tokens.T.astype(prompt.dtype)], axis=1)
