# Latent attention (MLA) and the rotary frequencies a published config
# can state (base, yarn). One definition of each piece, as functions
# over raw parameters: the Flax module below (full-sequence forward,
# plain form) and the decode / paged steps (cached form) call the same
# projections, so the two forms cannot drift apart.
#
# Plain form (what the equations say): per head, K = [k_nope | k_rope]
# with k_nope, v = c_kv W_kvb and ONE rotated k_rope shared by all
# heads; softmax((q_nope.k_nope + q_rope.k_rope) * scale), causal.
# Cached form (what serving runs): the cache holds the normed latent
# c_kv and the rotated k_rope, never per-head K/V. W_kvb's key half is
# absorbed into the query, q_lat = q_nope W_kvb[k]^T, so the scores are
# q_lat.c_kv + q_rope.k_rope against one shared key of width
# kv_lora_rank + qk_rope_head_dim whose first kv_lora_rank columns are
# also the value; o = (softmax . c_kv) W_kvb[v]. Same arithmetic up to
# the order of two matrix products.
"""Latent attention: projections, plain and cached attend, rotary tables."""
import math
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .moe import Leaf

# no [B, H, T, L] float32 score block larger than this is materialized
# by the cached form: longer query slices attend in tiles
SCORE_BLOCK_BYTES = 256 * 2 ** 20


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The `dim // 2` rotary frequencies under yarn: per dimension a
    blend of the original frequency theta^(-2i/dim) and the interpolated
    one (divided by `factor`). Dimensions that turn more than
    `beta_fast` times over `original_len` positions keep the original,
    those that turn fewer than `beta_slow` times are interpolated, and a
    linear ramp joins the two. float64 on the host, returned float32."""
    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    index = np.arange(dim // 2, dtype=np.float64)
    original = theta ** (-2.0 * index / dim)
    ramp = np.clip((index - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 where the original frequency stays
    return (original / factor * (1.0 - keep) + original * keep).astype(
        np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """yarn's attention temperature term 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def plain_rotary(cfg) -> bool:
    """True when the config states the table `transformer._rotary` has
    always computed itself (base 10000, halves paired, no yarn): such a
    config traces the program it always traced."""
    return (cfg.rope_theta == 10000.0 and cfg.yarn_factor <= 1.0
            and not cfg.rope_interleaved)


def rope_inv_freq(cfg, dim: int) -> np.ndarray:
    """The config's `dim // 2` rotary frequencies for a rotated width."""
    if cfg.yarn_factor > 1.0:
        return yarn_inv_freq(dim, cfg.rope_theta, cfg.yarn_factor,
                             cfg.yarn_original_len, cfg.yarn_beta_fast,
                             cfg.yarn_beta_slow)
    index = np.arange(dim // 2, dtype=np.float64)
    return (cfg.rope_theta ** (-2.0 * index / dim)).astype(np.float32)


def rope_cos_sin_scale(cfg) -> float:
    """What yarn multiplies cos and sin by: mscale over mscale_all_dim
    (1 when the config sets both alike, as the published ones do)."""
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def rotate(x: jax.Array, positions: jax.Array, inv_freq,
           interleaved: bool, scale: float = 1.0) -> jax.Array:
    """Rotary embedding of x [B, T, ..., D] at `positions` [B, T] with
    explicit frequencies. `interleaved` pairs dimensions (2i, 2i+1), the
    published layout of the latent models; otherwise (i, i + D/2)."""
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3)
                            + angles.shape[-1:])
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-0.5 times yarn's mscale_all_dim term squared."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def latent_width(cfg) -> int:
    """Values one cached token holds in one layer."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def _norm(x, scale, dtype):
    from .transformer import rmsnorm
    return rmsnorm(x, scale, dtype)


def _rope(cfg, x, positions):
    return rotate(x, positions, rope_inv_freq(cfg, cfg.qk_rope_head_dim),
                  cfg.rope_interleaved, rope_cos_sin_scale(cfg))


def queries(cfg, ap: tp.Dict, normed: jax.Array, positions: jax.Array
            ) -> tp.Tuple[jax.Array, jax.Array]:
    """c_q = norm(x W_qa); [q_nope | q_rope] = c_q W_qb per head, q_rope
    rotated. Returns (q_nope [B,T,H,nope], q_rope [B,T,H,rope])."""
    dt = cfg.dtype
    c_q = jnp.einsum("btd,dr->btr", normed, ap["q_a"]["kernel"].astype(dt))
    c_q = _norm(c_q, ap["q_norm"]["scale"], dt)
    q = jnp.einsum("btr,rhk->bthk", c_q, ap["q_b"]["kernel"].astype(dt))
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, _rope(cfg, q_rope, positions)


def latents(cfg, ap: tp.Dict, normed: jax.Array, positions: jax.Array
            ) -> tp.Tuple[jax.Array, jax.Array]:
    """[c_kv | k_rope] = x W_kva; c_kv normed, k_rope rotated (one
    vector for all heads): what the cache stores of a token.
    Returns (c_kv [B,T,rank], k_rope [B,T,rope])."""
    dt = cfg.dtype
    kv = jnp.einsum("btd,dr->btr", normed, ap["kv_a"]["kernel"].astype(dt))
    c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    return (_norm(c_kv, ap["kv_norm"]["scale"], dt),
            _rope(cfg, k_rope, positions))


def _kv_b(cfg, ap):
    """W_kvb [rank, H, nope + v] split into its key and value halves."""
    w = ap["kv_b"]["kernel"].astype(cfg.dtype)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def plain_attention(cfg, ap: tp.Dict, q_nope, q_rope, c_kv, k_rope,
                    mask: jax.Array) -> jax.Array:
    """The plain form over a whole sequence: per-head K and V expanded
    from the latents, dense scores under `mask` [B, 1|H, T, S] (True =
    attend). Returns the heads' outputs [B, T, H, v]."""
    w_k, w_v = _kv_b(cfg, ap)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, w_k)
    value = jnp.einsum("bsr,rhv->bshv", c_kv, w_v)
    scores = (jnp.einsum("bthk,bshk->bhts", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope,
                           preferred_element_type=jnp.float32))
    scores = jnp.where(mask, scores * softmax_scale(cfg), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhts,bshv->bthv", probs, value)


def absorb_queries(cfg, ap: tp.Dict, q_nope: jax.Array) -> jax.Array:
    """q_lat = q_nope W_kvb[k]^T: [B,T,H,nope] -> [B,T,H,rank]."""
    w_k, _ = _kv_b(cfg, ap)
    return jnp.einsum("bthk,rhk->bthr", q_nope, w_k)


def _attend_tile(cfg, q_lat, q_rope, c_view, kr_view, positions):
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat, c_view,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthk,bsk->bhts", q_rope, kr_view,
                           preferred_element_type=jnp.float32))
    key_pos = jnp.arange(c_view.shape[1])
    mask = key_pos[None, None, :] <= positions[:, :, None]   # [B, T, S]
    scores = jnp.where(mask[:, None], scores * softmax_scale(cfg), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhts,bsr->bthr", probs, c_view)


def cached_attention(cfg, q_lat: jax.Array, q_rope: jax.Array,
                     c_view: jax.Array, kr_view: jax.Array,
                     positions: jax.Array) -> jax.Array:
    """The cached form's attend: queries [B,T,H,*] against each row's
    logical latents c_view [B,S,rank], kr_view [B,S,rope] (this step's
    rows already written), causal by `positions` [B,T] (key position <=
    query position; later rows are stale or sentinel and never seen).
    Returns o_lat [B,T,H,rank]. Query slices whose float32 score block
    would pass SCORE_BLOCK_BYTES attend in tiles of the T axis."""
    batch, length, heads = q_lat.shape[:3]
    per_query = batch * heads * c_view.shape[1] * 4
    tile = max(1, min(length, SCORE_BLOCK_BYTES // per_query))
    while length % tile:
        tile -= 1
    if tile == length:
        return _attend_tile(cfg, q_lat, q_rope, c_view, kr_view, positions)

    def tiles(x):  # [B, T, ...] -> [T/tile, B, tile, ...]
        return jnp.moveaxis(
            x.reshape((batch, length // tile, tile) + x.shape[2:]), 1, 0)

    out = jax.lax.map(
        lambda qs: _attend_tile(cfg, qs[0], qs[1], c_view, kr_view, qs[2]),
        (tiles(q_lat), tiles(q_rope), tiles(positions)))
    return jnp.moveaxis(out, 0, 1).reshape(q_lat.shape)


def expand_values(cfg, ap: tp.Dict, o_lat: jax.Array) -> jax.Array:
    """o = o_lat W_kvb[v]: [B,T,H,rank] -> the heads' outputs [B,T,H,v]."""
    _, w_v = _kv_b(cfg, ap)
    return jnp.einsum("bthr,rhv->bthv", o_lat, w_v)


def output(cfg, ap: tp.Dict, heads_out: jax.Array) -> jax.Array:
    """The heads' outputs [B,T,H,v] through W_o."""
    return jnp.einsum("bthv,hvd->btd", heads_out,
                      ap["out"]["kernel"].astype(cfg.dtype))


class LatentAttention(nn.Module):
    """Full-sequence latent attention (plain form, dense causal scores):
    the training / init forward of an `attn_kind='mla'` block. Declares
    the parameters the functions above read."""

    config: tp.Any

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 train: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if segment_ids is not None:
            raise ValueError("attn_kind='mla' has no packed-batch path")
        heads, pd = cfg.num_heads, cfg.param_dtype
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        kernel = nn.initializers.lecun_normal()
        heads_in = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=(0, 1), out_axis=2)
        ones = nn.initializers.ones
        shapes = {
            "q_a": ("kernel", kernel, (cfg.dim, cfg.q_lora_rank), pd),
            "q_norm": ("scale", ones, (cfg.q_lora_rank,), jnp.float32),
            "q_b": ("kernel", kernel, (cfg.q_lora_rank, heads, qk), pd),
            "kv_a": ("kernel", kernel,
                     (cfg.dim, latent_width(cfg)), pd),
            "kv_norm": ("scale", ones, (cfg.kv_lora_rank,), jnp.float32),
            "kv_b": ("kernel", kernel,
                     (cfg.kv_lora_rank, heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim), pd),
            "out": ("kernel", heads_in, (heads, cfg.v_head_dim, cfg.dim), pd),
        }
        ap = {name: {leaf: Leaf(name=name)(leaf, init, shape, dt)}
              for name, (leaf, init, shape, dt) in shapes.items()}
        with jax.named_scope("mla_q"):
            q_nope, q_rope = queries(cfg, ap, x, positions)
        with jax.named_scope("mla_kv"):
            c_kv, k_rope = latents(cfg, ap, x, positions)
        length = x.shape[1]
        mask = jnp.tril(jnp.ones((length, length), bool))[None, None]
        if not cfg.causal:
            mask = jnp.ones_like(mask)
        with jax.named_scope("attn"):
            heads_out = plain_attention(cfg, ap, q_nope, q_rope, c_kv,
                                        k_rope, mask)
        with jax.named_scope("mla_out"):
            return output(cfg, ap, heads_out)
