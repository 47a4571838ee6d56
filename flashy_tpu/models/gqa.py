# Grouped-query attention whose kind is a property of the LAYER
# (`attn_kind='gqa'`): every layer is a full-attention layer or a window
# layer (`window_layers`), each kind with its own number of KV heads and
# its own rotary base; keys and queries are `qk_head_dim` wide and the
# values `v_head_dim`; rotary turns the first `rotary_dim` dimensions of
# a head (pairs (i, i + rotary_dim / 2)) and the rest pass; the values
# are scaled by `value_scale` before they are cached; a window layer's
# query sees itself and the `window - 1` positions before it, and, with
# `window_sink`, a learned float32 scalar a head joins its softmax's
# denominator and takes no value.
#
# One definition of each piece, as functions over raw parameters, as in
# models/mla.py: the Flax module below (full-sequence forward), the
# dense decode step (models/decoding.py) and the paged step
# (serve/paged.py) call the same projection and the same `attend`, which
# is told the position every key row holds — so the whole sequence, a
# dense slab, a block table's logical view and a window layer's ring
# are one masked attend over different views.
"""Grouped attention by layer kind: projections, the masked attend."""
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .mla import SCORE_BLOCK_BYTES, rotate
from .moe import Leaf


class LayerKind(tp.NamedTuple):
    """What one layer's attention is, from the config alone."""
    window: int     # 0: full attention; else keys t - window < j <= t
    kv_heads: int
    theta: float    # rotary base
    sink: bool      # a learned scalar a head in the softmax denominator

    @property
    def scope(self) -> str:
        """The layer's named scope under `attn`."""
        return "window" if self.window else "global"


def key_dim(cfg) -> int:
    return cfg.qk_head_dim or cfg.dim // cfg.num_heads


def value_dim(cfg) -> int:
    return cfg.v_head_dim or key_dim(cfg)


def layer_kinds(cfg) -> tp.Tuple[LayerKind, ...]:
    """Per layer its LayerKind; refuses what the kinds cannot be
    combined with (the kernels of training take no window, sink or
    grouped heads)."""
    if cfg.attn_kind != "gqa":
        raise ValueError(f"layer kinds are attn_kind='gqa''s, got "
                         f"{cfg.attn_kind!r}")
    if cfg.attention != "dense":
        raise ValueError(
            f"attention={cfg.attention!r} has no window, sink or grouped "
            f"KV heads: a config with attn_kind='gqa' runs "
            f"attention='dense'")
    pattern = tuple(cfg.window_layers) or (0,) * cfg.num_layers
    full_heads = cfg.num_kv_heads or cfg.num_heads
    window_heads = cfg.window_kv_heads or full_heads
    rotary = cfg.rotary_dim or key_dim(cfg)
    if (len(pattern) != cfg.num_layers or set(pattern) - {0, 1}
            or (1 in pattern and cfg.window < 1)
            or cfg.num_heads % full_heads or cfg.num_heads % window_heads
            or rotary % 2 or rotary > key_dim(cfg)):
        raise ValueError(
            f"attn_kind='gqa' needs window_layers (0 | 1) for each of "
            f"{cfg.num_layers} layers, a window >= 1 where one is 1, KV "
            f"heads that divide {cfg.num_heads} and an even rotary_dim "
            f"within the head; got {pattern}, window {cfg.window}, "
            f"{full_heads} | {window_heads} KV heads, rotary {rotary}")
    return tuple(
        LayerKind(cfg.window, window_heads,
                  cfg.window_rope_theta or cfg.rope_theta, cfg.window_sink)
        if windowed else LayerKind(0, full_heads, cfg.rope_theta, False)
        for windowed in pattern)


def has_window(cfg) -> bool:
    """Whether some layer of `cfg` keeps only a window of its keys."""
    return (getattr(cfg, "attn_kind", "mha") == "gqa"
            and any(kind.window for kind in layer_kinds(cfg)))


def _rotary(cfg, kind: LayerKind, x: jax.Array, positions: jax.Array
            ) -> jax.Array:
    """The first `rotary_dim` dimensions of every head of x [B,T,H,D]
    rotated at the layer kind's base; the rest pass. `rotary_dim` 0 is
    the whole head; a config with `rope=False` rotates nothing (its
    attention has no position embedding)."""
    if not cfg.rope:
        return x
    width = cfg.rotary_dim or x.shape[-1]
    index = np.arange(width // 2, dtype=np.float64)
    inv_freq = (kind.theta ** (-2.0 * index / width)).astype(np.float32)
    turned = rotate(x[..., :width], positions, inv_freq, False)
    if width == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def project(cfg, kind: LayerKind, ap: tp.Dict, normed: jax.Array,
            positions: jax.Array
            ) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    """[q | k | v] = normed W_in (one leaf, `in_proj` [D, H Dk + Hkv Dk
    + Hkv Dv]); q and k rotated, v scaled: what the cache stores is what
    is read. Returns q [B,T,H,Dk], k [B,T,Hkv,Dk], v [B,T,Hkv,Dv]."""
    dk, dv = key_dim(cfg), value_dim(cfg)
    heads, kv_heads = cfg.num_heads, kind.kv_heads
    with jax.named_scope("qkv"):
        qkv = jnp.einsum("btd,dw->btw", normed,
                         ap["in_proj"]["kernel"].astype(cfg.dtype))
        q, k, v = jnp.split(
            qkv, [heads * dk, (heads + kv_heads) * dk], axis=-1)
        lead = qkv.shape[:2]
        q = q.reshape(lead + (heads, dk))
        k = k.reshape(lead + (kv_heads, dk))
        v = v.reshape(lead + (kv_heads, dv))
        if cfg.value_scale != 1.0:
            v = v * jnp.asarray(cfg.value_scale, v.dtype)
    with jax.named_scope("rotary"):
        return (_rotary(cfg, kind, q, positions),
                _rotary(cfg, kind, k, positions), v)


# A step of at most this many query rows a slot (decode, verify) reads
# the keys and values as stored, all KV heads of a row side by side on
# the lanes, against queries laid out block-diagonally over them: the
# Hkv-fold surplus of its products is nothing beside the bytes, and the
# view needs no relayout (a `[.., Hkv, 192]` view of 768 lanes is one:
# 3.9 ms a layer a decode step at 32 slots of 17,408 rows, PERF.md
# section 6, PR 31). A prefill slice has the rows to fill the MXU and
# splits the heads.
FLAT_QUERY_ROWS = 8


def _attend_tile(cfg, kind: LayerKind, sink, q, k_view, v_view,
                 key_positions, positions):
    batch, length, heads, dk = q.shape
    kv_heads, dv = kind.kv_heads, v_view.shape[-1] // kind.kv_heads
    group = heads // kv_heads
    flat = length <= FLAT_QUERY_ROWS and kv_heads > 1
    if flat:
        # head h's query on the lanes of KV head h // group, zero beside
        own = (jnp.arange(heads)[:, None] // group
               == jnp.arange(kv_heads)[None, :])              # [H, Hkv]
        wide = jnp.where(own[None, None, :, :, None], q[:, :, :, None, :],
                         jnp.zeros((), q.dtype))
        scores = jnp.einsum(
            "bthf,bsf->bhts", wide.reshape(batch, length, heads, -1), k_view,
            preferred_element_type=jnp.float32).reshape(
                batch, kv_heads, group, length, -1)
    else:
        scores = jnp.einsum(
            "btkgd,bskd->bkgts",
            q.reshape(batch, length, kv_heads, group, dk),
            k_view.reshape(k_view.shape[:2] + (kv_heads, dk)),
            preferred_element_type=jnp.float32)
    scores = scores * dk ** -0.5
    seen = ((key_positions[:, None, :] <= positions[:, :, None])
            & (key_positions[:, None, :] >= 0))              # [B, T, S]
    if kind.window:
        seen &= (positions[:, :, None] - key_positions[:, None, :]
                 < kind.window)
    scores = jnp.where(seen[:, None, None], scores, -1e30)
    if kind.sink:
        b = sink.astype(jnp.float32).reshape(1, kv_heads, group, 1, 1)
        top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), b)
        weights = jnp.exp(scores - top)
        probs = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                           + jnp.exp(b - top))
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    probs = probs.astype(cfg.dtype)
    if flat:
        # every KV head's values for every query head; its own are kept
        every = jnp.einsum(
            "bhts,bsf->bthf", probs.reshape(batch, heads, length, -1),
            v_view).reshape(batch, length, heads, kv_heads, dv)
        return jnp.sum(jnp.where(own[None, None, :, :, None], every,
                                 jnp.zeros((), every.dtype)), axis=3)
    out = jnp.einsum("bkgts,bskv->btkgv", probs,
                     v_view.reshape(v_view.shape[:2] + (kv_heads, dv)))
    return out.reshape(batch, length, heads, dv)


def attend(cfg, kind: LayerKind, ap: tp.Dict, q: jax.Array,
           k_view: jax.Array, v_view: jax.Array, key_positions: jax.Array,
           positions: jax.Array) -> jax.Array:
    """Queries q [B,T,H,Dk] at `positions` [B,T] against each row's key
    rows k_view [B,S,Hkv * Dk], v_view [B,S,Hkv * Dv] (a row's heads
    side by side, as a grouped pool stores them), row s of which holds
    position `key_positions` [B,S] (negative: nothing yet). Query head h
    reads KV head h // (H / Hkv); a key is seen iff its position is in
    [0, t], and in a window layer within `window` of t; the softmax is
    float32, with the layer's sink in its denominator. Returns the
    heads' outputs [B,T,H,Dv]. Query slices whose float32 score block
    would pass SCORE_BLOCK_BYTES attend in tiles of the T axis (a
    512-token slice against 17,408 keys is 2.3 GB untiled)."""
    batch, length, heads = q.shape[:3]
    sink = ap["sink"] if kind.sink else None
    per_query = batch * heads * k_view.shape[1] * 4
    tile = max(1, min(length, SCORE_BLOCK_BYTES // per_query))
    while length % tile:
        tile -= 1
    if tile == length:
        return _attend_tile(cfg, kind, sink, q, k_view, v_view,
                            key_positions, positions)

    def tiles(x):  # [B, T, ...] -> [T/tile, B, tile, ...]
        return jnp.moveaxis(
            x.reshape((batch, length // tile, tile) + x.shape[2:]), 1, 0)

    out = jax.lax.map(
        lambda qs: _attend_tile(cfg, kind, sink, qs[0], k_view, v_view,
                                key_positions, qs[1]),
        (tiles(q), tiles(positions)))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(q.shape[:3] + out.shape[-1:])


def output(cfg, ap: tp.Dict, heads_out: jax.Array) -> jax.Array:
    """The heads' outputs [B,T,H,Dv] through W_o [H, Dv, D]."""
    return jnp.einsum("bthv,hvd->btd", heads_out,
                      ap["out"]["kernel"].astype(cfg.dtype))


class GroupedAttention(nn.Module):
    """Full-sequence attention of layer `layer` of an `attn_kind='gqa'`
    config (dense masked scores): the training / init forward. Declares
    the parameters the functions above read."""

    config: tp.Any
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 train: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if segment_ids is not None:
            raise ValueError("attn_kind='gqa' has no packed-batch path")
        if not cfg.causal:
            raise ValueError("attn_kind='gqa' is causal attention")
        kind = layer_kinds(cfg)[self.layer]
        heads, pd = cfg.num_heads, cfg.param_dtype
        dk, dv = key_dim(cfg), value_dim(cfg)
        heads_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=(0, 1), out_axis=2)
        ap = {
            "in_proj": {"kernel": Leaf(name="in_proj")(
                "kernel", nn.initializers.lecun_normal(),
                (cfg.dim, (heads + kind.kv_heads) * dk + kind.kv_heads * dv),
                pd)},
            "out": {"kernel": Leaf(name="out")(
                "kernel", heads_in, (heads, dv, cfg.dim), pd)},
        }
        if kind.sink:
            ap["sink"] = self.param("sink", nn.initializers.zeros, (heads,),
                                    jnp.float32)
        q, k, v = project(cfg, kind, ap, x, positions)
        side_by_side = lambda rows: rows.reshape(rows.shape[:2] + (-1,))
        with jax.named_scope("attn"), jax.named_scope(kind.scope):
            heads_out = attend(cfg, kind, ap, q, side_by_side(k),
                               side_by_side(v), positions, positions)
        with jax.named_scope("out_proj"):
            return output(cfg, ap, heads_out)
