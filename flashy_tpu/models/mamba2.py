# The Mamba-2 mixer (a layer of kind 'M' of `layer_pattern`), as
# published: one input projection gives a gate z, the convolved stream
# [x | B | C] and a time step a head; a causal depthwise convolution over
# the last `ssm_conv` tokens and a silu; B and C shared by the heads of
# a group; the time step scales what a token writes; a decay a head; a
# skip D; the gate before a grouped RMSNorm; one output projection.
#
#   [z | xBC | dt] = u W_in          (D -> HP + (HP + 2GN) + H)
#   xBC <- silu(conv(xBC) + bias)    (depthwise, causal, `ssm_conv` taps)
#   x [H, P], B [G, N], C [G, N] = split(xBC)
#   dt <- softplus(dt + dt_bias);  A = -exp(A_log)          (per head)
#   h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t   h [H, P, N] float32
#   y_t = h_t C_t + D x_t            (head h reads group h // (H / G))
#   y <- RMSNorm_grouped(y * silu(z)) * scale    (groups of HP / G)
#   out = y W_out                    (HP -> D)
#
# On ops/ssd_scan.py's recurrence this is v = dt x, b = B, c = C,
# log a = dt A: the one scan of the repo, told its groups. What a
# sequence carries between calls is the state h and the convolution's
# tail, the last `ssm_conv - 1` rows of xBC before the activation: a
# prefill slice takes both in and hands both out (the chunked form), a
# decode step advances one token a row.
#
# One definition, as functions over raw parameters (as models/mla.py
# and models/gqa.py): the Flax module below (the whole sequence from a
# zero state), the dense decode step (models/decoding.py) and the paged
# step (serve/paged.py), where state and tail are tables with one entry
# a slot beside the block pool (`ops.paged_attention.layer_pool_specs`).
"""Mamba-2 mixer: projections, causal conv, the scan, gated norm."""
import math
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssd_scan import (ssd_chunked_scan, ssd_recurrent_scan,
                            ssd_state_update)
from .moe import Leaf

# The published initialisation of the time step (Mamba-2's defaults,
# which the `nemotron_h` config repeats as time_step_min / _max /
# _floor): dt log-uniform in [DT_MIN, DT_MAX], floored, stored through
# the inverse softplus; A uniform in [1, 16], stored as its log.
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_RANGE = (1.0, 16.0)


def inner_dim(cfg) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_dim(cfg) -> int:
    """Channels of the convolved stream [x | B | C]."""
    return inner_dim(cfg) + 2 * cfg.ssm_groups * cfg.ssd_state_dim


def state_spec(cfg, rows: int
               ) -> tp.Dict[str, tp.Tuple[tp.Tuple[int, ...], tp.Any]]:
    """Leaf name -> (shape, dtype) of what `rows` sequences carry in one
    Mamba layer: `state` [rows, H, P, N] float32 and `conv`
    [rows, ssm_conv - 1, HP + 2GN] in the compute dtype (the rows of
    xBC as projected, before the activation)."""
    return {"state": ((rows, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssd_state_dim), jnp.float32),
            "conv": ((rows, cfg.ssm_conv - 1, conv_dim(cfg)), cfg.dtype)}


def check(cfg) -> None:
    if (cfg.ssm_heads < 1 or cfg.ssm_head_dim < 1 or cfg.ssd_state_dim < 1
            or cfg.ssm_conv < 2 or cfg.ssm_groups < 1
            or cfg.ssm_heads % cfg.ssm_groups
            or inner_dim(cfg) % cfg.ssm_groups):
        raise ValueError(
            f"a Mamba-2 layer needs ssm_heads, ssm_head_dim, ssd_state_dim "
            f">= 1, ssm_conv >= 2 and ssm_groups dividing the heads; got "
            f"{cfg.ssm_heads} x {cfg.ssm_head_dim} x {cfg.ssd_state_dim}, "
            f"conv {cfg.ssm_conv}, {cfg.ssm_groups} groups")


def _conv(cfg, sp: tp.Dict, xbc: jax.Array, tail: jax.Array,
          used: tp.Optional[jax.Array]
          ) -> tp.Tuple[jax.Array, jax.Array]:
    """silu(conv(xBC) + bias) over [tail | xBC] [B, K - 1 + T, C], and
    the tail the sequence carries on: the K - 1 rows that end at its
    last real token (`used` [B] real tokens of the T, None = all; with
    fewer than K - 1 of them, rows of the old tail stay)."""
    taps = cfg.ssm_conv
    length = xbc.shape[1]
    padded = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    kernel = sp["conv"]["kernel"].astype(jnp.float32)          # [K, C]
    out = sp["conv"]["bias"].astype(jnp.float32)
    for k in range(taps):
        out = out + kernel[k] * padded[:, k:k + length].astype(jnp.float32)
    if used is None:
        new_tail = padded[:, length:]
    else:
        at = used[:, None] + jnp.arange(taps - 1)[None, :]      # [B, K-1]
        new_tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return jax.nn.silu(out).astype(cfg.dtype), new_tail.astype(tail.dtype)


def mixer(cfg, sp: tp.Dict, u: jax.Array, state: jax.Array,
          tail: jax.Array, *, rows: tp.Optional[jax.Array] = None,
          used: tp.Optional[jax.Array] = None
          ) -> tp.Tuple[jax.Array, jax.Array, jax.Array]:
    """The mixer on pre-normed u [B, T, D]: (out [B, T, D], state, tail).

    `rows` None: `state` [B, H, P, N] and `tail` [B, K - 1, C] are the
    batch's own, taken in and handed out; T > 1 runs the chunked form
    (`cfg.ssd_chunk`, `cfg.ssd_kernel`), T == 1 the recurrence. `used`
    [B] counts a right-padded slice's real tokens: the pads neither
    decay nor feed the state and stay out of the tail.
    `rows` [B] int32 (T == 1, the serving decode run): `state` and
    `tail` are TABLES `[R, ...]` and row i advances entry `rows[i]` in
    place (`ops.ssd_scan.ssd_state_update`); rows that share an entry (a
    sentinel) leave it with one of their writes."""
    batch, length, _ = u.shape
    heads, dim, nstate = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssd_state_dim
    groups, inner = cfg.ssm_groups, inner_dim(cfg)
    if rows is not None and length != 1:
        raise ValueError("a table of states advances one token a row")
    with jax.named_scope("ssm"):
        with jax.named_scope("in_proj"):
            zxd = jnp.einsum("btd,dw->btw", u,
                             sp["in_proj"]["kernel"].astype(cfg.dtype))
            z, xbc, dt = jnp.split(zxd, [inner, inner + conv_dim(cfg)],
                                   axis=-1)
        with jax.named_scope("conv"):
            if rows is None:
                xbc, tail = _conv(cfg, sp, xbc, tail, used)
            else:
                xbc, fresh = _conv(cfg, sp, xbc, tail[rows], None)
                tail = tail.at[rows].set(fresh)
        with jax.named_scope("scan"):
            x, b, c = jnp.split(xbc, [inner, inner + groups * nstate],
                                axis=-1)
            x = x.reshape(batch, length, heads, dim)
            b = b.reshape(batch, length, groups, nstate)
            c = c.reshape(batch, length, groups, nstate)
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + sp["dt_bias"].astype(jnp.float32))
            log_a = -jnp.exp(sp["A_log"].astype(jnp.float32)) * dt
            v = dt[..., None] * x.astype(jnp.float32)       # [B, T, H, P]
            if rows is not None:
                y, state = ssd_state_update(
                    state, rows, jnp.exp(log_a[:, 0]), v[:, 0], b[:, 0],
                    c[:, 0], kernel=cfg.ssd_kernel)
                y = y[:, None]
            elif length == 1:
                y, state = ssd_recurrent_scan(c, b, v, log_a, state)
            else:
                mask = (None if used is None else
                        jnp.arange(length)[None, :] < used[:, None])
                y, state = ssd_chunked_scan(
                    c, b, v, log_a, state=state, token_mask=mask,
                    chunk=cfg.ssd_chunk if cfg.ssd_chunk > 0 else None,
                    kernel=cfg.ssd_kernel)
            y = y + sp["D"].astype(jnp.float32)[:, None] * x.astype(
                jnp.float32)
        with jax.named_scope("gate_norm"):
            y = y.reshape(batch, length, inner) * jax.nn.silu(
                z.astype(jnp.float32))
            y = y.reshape(batch, length, groups, inner // groups)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                  + cfg.norm_eps)
            y = (y.reshape(batch, length, inner)
                 * sp["norm"]["scale"].astype(jnp.float32)).astype(cfg.dtype)
        with jax.named_scope("out_proj"):
            out = jnp.einsum("btw,wd->btd", y,
                             sp["out_proj"]["kernel"].astype(cfg.dtype))
    return out, state, tail


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE)
                   ).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The whole sequence from a zero state (the training / init
    forward). Declares the parameters `mixer` reads."""

    config: tp.Any

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 train: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        check(cfg)
        if segment_ids is not None:
            raise ValueError("a Mamba-2 layer has no packed-batch path")
        inner, width, pd = inner_dim(cfg), conv_dim(cfg), cfg.param_dtype
        dense = nn.initializers.lecun_normal()
        bound = cfg.ssm_conv ** -0.5  # a depthwise conv's fan-in is its taps

        def taps(key, shape, dtype):
            return jax.random.uniform(key, shape, jnp.float32, -bound,
                                      bound).astype(dtype)

        conv = Leaf(name="conv")
        sp = {
            "in_proj": {"kernel": Leaf(name="in_proj")(
                "kernel", dense, (cfg.dim, inner + width + cfg.ssm_heads),
                pd)},
            "conv": {"kernel": conv("kernel", taps, (cfg.ssm_conv, width), pd),
                     "bias": conv("bias", taps, (width,), pd)},
            "dt_bias": self.param("dt_bias", _dt_bias_init,
                                  (cfg.ssm_heads,), jnp.float32),
            "A_log": self.param("A_log", _a_log_init, (cfg.ssm_heads,),
                                jnp.float32),
            "D": self.param("D", nn.initializers.ones, (cfg.ssm_heads,),
                            jnp.float32),
            "norm": {"scale": Leaf(name="norm")(
                "scale", nn.initializers.ones, (inner,), jnp.float32)},
            "out_proj": {"kernel": Leaf(name="out_proj")(
                "kernel", dense, (inner, cfg.dim), pd)},
        }
        spec = state_spec(cfg, x.shape[0])
        out, _, _ = mixer(cfg, sp, x,
                          *(jnp.zeros(*spec[leaf])
                            for leaf in ("state", "conv")))
        return out
