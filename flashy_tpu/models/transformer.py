# Transformer language model — the flagship workload (the AudioCraft
# style Transformer LM solver of BASELINE.json configs[4]). Built
# TPU-first:
#
#  * bf16 activations, f32 params and softmax accumulation;
#  * fused QKV projection (one [D, 3D] matmul keeps the MXU busy);
#  * rotary position embeddings (no learned positional table, no
#    max-length retracing);
#  * attention dispatch: pallas flash attention on a single device, or
#    ring attention over the mesh's 'seq' axis for sequence parallelism;
#  * sharding rules (`transformer_shardings`) that map the parameter
#    tree onto the (data, fsdp, tensor, seq) mesh: megatron-style
#    column/row splits over 'tensor', parameter sharding over 'fsdp'.
#    With those specs on a jitted step, XLA's SPMD partitioner inserts
#    exactly the all-reduce / all-gather / reduce-scatter pattern of a
#    hand-written megatron layer.
"""TransformerLM: decoder-only LM with TP/FSDP/SP sharding support."""
import dataclasses
import typing as tp

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import (dot_product_attention, flash_attention,
                             sharded_flash_attention)
from .moe import ExpertMLP, MoEMLP


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 512
    num_layers: int = 8
    num_heads: int = 8
    mlp_ratio: int = 4
    max_seq_len: int = 2048      # hard cap, checked at call time
    dropout: float = 0.0         # applied after attn-out and mlp-down when
                                 # train=True (pass rngs={'dropout': key})
    dtype: tp.Any = jnp.bfloat16
    attention: str = "flash"     # 'flash' | 'dense' | 'ring' | 'ring_fused'
    causal: bool = True          # False = bidirectional (encoder/ViT)
    remat: bool = False          # jax.checkpoint each block (HBM for FLOPs)
    remat_policy: str = "full"   # what remat SAVES per block:
                                 #   'full'  - nothing (recompute all);
                                 #   'dots'  - matmul outputs saveable
                                 #     (recompute only elementwise/norms
                                 #     - most of the no-remat speed at a
                                 #     fraction of the activation HBM);
                                 #   'dots_no_batch' - contractions with
                                 #     no batch dims (params-side only)
    moe_experts: int = 0         # >0 replaces the MLP with a routed MoE
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"  # einsum | sorted | dropless |
                                  # dropless_ep (see MoEMLP)
    scan_layers: bool = False    # nn.scan-stack the blocks: params get a
                                 # leading [num_layers] dim (O(1) compile
                                 # time in depth; enables 'pipe' sharding
                                 # and pipelined_apply)
    mixer: str = "attention"     # per-layer sequence mixer: 'attention',
                                 # 'ssd', or a comma-separated pattern
                                 # cycled over the layers (e.g.
                                 # 'ssd,ssd,attention' — a hybrid stack;
                                 # see mixer_pattern)
    ssd_state_dim: int = 16      # Dstate of SSD layers ([H, Dh, Dstate]
                                 # decode state per sequence)
    ssd_chunk: int = 0           # chunked-form chunk size; 0 = tuned /
                                 # largest divisor (ops.ssd_scan)
    ssd_kernel: str = "auto"     # 'auto' | 'gather' | 'fused' — the
                                 # ops.ssd_scan chunked-kernel seam
    layer_pattern: str = ""      # one residual mixer a layer, a kind a
                                 # character (x <- x + mixer(norm(x)),
                                 # nothing else in the layer): 'M' a
                                 # Mamba-2 mixer (models/mamba2.py, the
                                 # ssm_ keys below), 'E' the `n_routed`
                                 # expert layer with no attention before
                                 # it, '*' attention (`attn_kind='gqa'`)
                                 # with no MLP after it. '' = every layer
                                 # a mixer and an MLP, as above
    ssm_heads: int = 0           # 'M': heads H, each `ssm_head_dim` P
    ssm_head_dim: int = 0        # wide, a state [H, P, ssd_state_dim]
                                 # float32 a sequence (`ssd_chunk` /
                                 # `ssd_kernel`: the chunked form's)
    ssm_groups: int = 1          # 'M': groups sharing one B and one C
    ssm_conv: int = 4            # 'M': taps of the causal depthwise conv
    # What a published config states beyond the block above. None of it
    # is tunable, and the defaults are the model this file always built
    # (full multi-head attention, base-10000 rotary over the whole head,
    # one MLP kind in every block, tied head, float32 leaves).
    attn_kind: str = "mha"       # 'mha' | 'mla': latent attention
                                 # (models/mla.py), whose cache entry is
                                 # the latent, not per-head K and V
                                 # | 'gqa': grouped KV heads, a layer
                                 # kind PER LAYER (full or window), key
                                 # and value widths of their own
                                 # (models/gqa.py; the keys below)
    q_lora_rank: int = 0         # mla: width of the query latent
    kv_lora_rank: int = 0        # mla: width of the cached latent
    qk_nope_head_dim: int = 0    # mla: per head, unrotated / rotated
    qk_rope_head_dim: int = 0    #      key-query widths,
    v_head_dim: int = 0          #      and the value width (gqa: 0 =
                                 #      the key width)
    qk_head_dim: int = 0         # gqa: key/query width a head
    rotary_dim: int = 0          # gqa: leading dimensions of a head that
                                 # rotate (0 = the whole head)
    rope: bool = True            # gqa: False = no position embedding in
                                 # the attention at all (position comes
                                 # through other layers)
    num_kv_heads: int = 0        # gqa: KV heads of a full layer
    window_layers: tp.Tuple[int, ...] = ()  # gqa: per layer 1 = window
                                 # attention, 0 = full; () = all full
    window: int = 0              # gqa: a window layer's query sees itself
                                 # and the window - 1 positions before it
    window_kv_heads: int = 0     # gqa: KV heads of a window layer
    window_rope_theta: float = 0.0  # gqa: its rotary base (0 = rope_theta)
    window_sink: bool = False    # gqa: a learned scalar a head joins the
                                 # window layers' softmax denominator
    value_scale: float = 1.0     # gqa: the values are multiplied by it
    norm_eps: float = 1e-6       # every RMSNorm's epsilon
    rope_theta: float = 10000.0
    rope_interleaved: bool = False  # rotary pairs (2i, 2i+1), not
                                    # (i, i + D/2)
    yarn_factor: float = 1.0     # > 1: yarn-scaled frequencies
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    dense_layers: int = 0        # with n_routed: leading layers that
                                 # keep the dense MLP
    dense_hidden: int = 0        # dense MLP width; 0 = dim * mlp_ratio
    n_routed: int = 0            # > 0: expert layers with the sigmoid
                                 # group-limited router (moe.ExpertMLP)
                                 # after the leading dense ones
    held_experts: tp.Tuple[int, int] = (0, 0)  # (first, count) of the
                                 # routed experts THIS chip holds; the
                                 # router keeps n_routed outputs.
                                 # count 0 = all of them
    expert_top_k: int = 0        # experts per token
    expert_groups: int = 1       # router groups, and how many of them
    expert_topk_groups: int = 1  # a token may choose from
    expert_scale: float = 1.0    # routed_scaling_factor
    n_shared: int = 0            # shared experts (one MLP of that width)
    expert_hidden: int = 0       # width of one expert
    expert_act: str = "silu"     # 'silu': gated, silu(gate) * value;
                                 # 'relu2': NOT gated, relu(up)^2 (the
                                 # shared expert alike)
    expert_latent: int = 0       # > 0: the routed experts live in a
                                 # latent this wide: one down-projection
                                 # shared by them before the dispatch,
                                 # one up-projection after the weighted
                                 # sum; the router and the shared expert
                                 # see the full hidden state
    shared_hidden: int = 0       # the shared expert's width (0 =
                                 # expert_hidden * n_shared)
    tie_head: bool = True        # False: an output table `head` [V, D]
    param_dtype: tp.Any = jnp.float32  # dtype of the matrix leaves
                                 # (norm scales and the router's bias
                                 # stay float32)

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    @property
    def mlp_hidden(self) -> int:
        return self.dense_hidden or self.dim * self.mlp_ratio


def mixer_pattern(cfg: "TransformerConfig") -> tp.Tuple[str, ...]:
    """Resolve cfg.mixer into one mixer name per layer.

    A single name applies to every layer; a comma-separated pattern is
    cycled over the depth ('ssd,attention' alternates, starting with
    ssd). Names must be 'attention' or 'ssd'.
    """
    names = tuple(part.strip() for part in cfg.mixer.split(","))
    bad = [n for n in names if n not in ("attention", "ssd")]
    if bad:
        raise ValueError(
            f"config.mixer entries must be 'attention' or 'ssd', got "
            f"{bad[0]!r} in {cfg.mixer!r}")
    return tuple(names[i % len(names)] for i in range(cfg.num_layers))


def pattern_kinds(cfg: "TransformerConfig") -> tp.Tuple[str, ...]:
    """`cfg.layer_pattern` as one kind a layer ('M' | 'E' | '*'), or ()
    for a config whose layers are all a mixer and an MLP. Refuses what
    a layer of one mixer cannot be combined with."""
    pattern = tuple(cfg.layer_pattern)
    if not pattern:
        return ()
    if len(pattern) != cfg.num_layers or set(pattern) - set("ME*"):
        raise ValueError(
            f"config.layer_pattern names each of the {cfg.num_layers} "
            f"layers 'M' (Mamba-2), 'E' (experts) or '*' (attention); got "
            f"{cfg.layer_pattern!r}")
    if cfg.scan_layers or cfg.mixer != "attention" or cfg.moe_experts > 0:
        raise ValueError(
            "a layer_pattern's layers differ in kind and parameters: "
            "scan_layers stacks one body, `mixer` cycles blocks of a "
            "mixer and an MLP, and moe_experts is such a block's MLP "
            "(scan_layers=False, mixer='attention', moe_experts=0)")
    if cfg.attention != "dense":
        raise ValueError(
            f"attention={cfg.attention!r} is a kernel of blocks that are "
            f"all attention: a config with a layer_pattern runs "
            f"attention='dense'")
    if "*" in pattern and cfg.attn_kind != "gqa":
        raise ValueError("a layer_pattern's '*' layers are grouped "
                         "attention: attn_kind='gqa'")
    if "E" in pattern and cfg.n_routed <= 0:
        raise ValueError("a layer_pattern's 'E' layers are the n_routed "
                         "expert layer: state n_routed and its keys")
    if "M" in pattern:
        from . import mamba2
        mamba2.check(cfg)
    return pattern


def expert_layers(cfg: "TransformerConfig") -> tp.Tuple[bool, ...]:
    """Per layer: True where the block's MLP is the `n_routed` expert
    layer (every layer after the `dense_layers` leading ones; the 'E'
    layers of a `layer_pattern`). Checks what the new kinds cannot be
    combined with."""
    if cfg.attn_kind not in ("mha", "mla", "gqa"):
        raise ValueError(f"config.attn_kind must be 'mha', 'mla' or 'gqa', "
                         f"got {cfg.attn_kind!r}")
    if cfg.n_routed > 0 and cfg.moe_experts > 0:
        raise ValueError("config.n_routed (sigmoid group-limited experts) "
                         "and config.moe_experts (softmax MoEMLP) are two "
                         "expert layers: state one")
    if cfg.scan_layers and (cfg.attn_kind != "mha" or cfg.n_routed > 0
                            or not cfg.tie_head):
        raise ValueError("scan_layers stacks one block body: latent "
                         "attention, grouped attention by layer kind, "
                         "n_routed expert layers and an untied head are not "
                         "stacked (scan_layers=False)")
    pattern = pattern_kinds(cfg)
    if pattern:
        return tuple(kind == "E" for kind in pattern)
    return tuple(cfg.n_routed > 0 and layer >= cfg.dense_layers
                 for layer in range(cfg.num_layers))


def rmsnorm(x: jax.Array, scale: jax.Array, dtype: tp.Any,
            eps: float = 1e-6) -> jax.Array:
    """Functional RMSNorm matching nn.RMSNorm's math (f32 accumulation,
    eps 1e-6 unless the config states another: `norm_eps`); used by the
    decode/pipelined paths that read raw params."""
    h = jnp.asarray(x, jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
    return (h * scale.astype(jnp.float32)).astype(dtype)


def _rotary(x: jax.Array, positions: jax.Array,
            cfg: tp.Optional[TransformerConfig] = None) -> jax.Array:
    """Apply rotary embeddings to [B, T, H, D] at the given positions.
    With a `cfg` that states another base, yarn or the interleaved
    pairing, its frequencies (models/mla.py) instead of the table below."""
    if cfg is not None:
        from .mla import (plain_rotary, rope_cos_sin_scale, rope_inv_freq,
                          rotate)
        if not plain_rotary(cfg):
            return rotate(x, positions, rope_inv_freq(cfg, x.shape[-1]),
                          cfg.rope_interleaved, rope_cos_sin_scale(cfg))
    half = x.shape[-1] // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([
        x1 * cos - x2 * sin,
        x2 * cos + x1 * sin,
    ], axis=-1).astype(x.dtype)


def _tp_boundary(x: jax.Array, mesh: tp.Any, *tail: tp.Any) -> jax.Array:
    """Pin an activation's layout at a megatron layer boundary.

    `tail` is the PartitionSpec beyond the [batch, time] dims:
    'tensor' on the heads/hidden dim inside a block (column-parallel
    outputs stay split, no collective), nothing at the block boundary
    — where pinning the tensor-unsharded layout makes XLA lower the
    row-parallel matmul's partial sums as THE all-reduce over
    'tensor', one after attention and one after the MLP, exactly the
    hand-written megatron pair. No-op without a mesh, at tensor width
    1, or outside a trace (an eager `model.init` must not commit
    device placements before the step's jit decides them).
    """
    if mesh is None or not isinstance(x, jax.core.Tracer):
        return x
    try:
        if dict(mesh.shape).get("tensor", 1) <= 1:
            return x
    except Exception:  # mesh-like without named shape: nothing to pin
        return x
    from jax.sharding import NamedSharding
    spec = P(("data", "fsdp"), None, *tail)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class Attention(nn.Module):
    config: TransformerConfig
    mesh: tp.Any = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 train: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        qkv = nn.DenseGeneral((3, cfg.num_heads, cfg.head_dim), axis=-1,
                              use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name="qkv")(x)
        # column-parallel output: heads stay split over 'tensor' so the
        # whole attention body is head-local — no collective here
        qkv = _tp_boundary(qkv, self.mesh, None, "tensor", None)
        q, k, v = (qkv[:, :, i] for i in range(3))  # [B, T, H, Dh]
        q = _rotary(q, positions, cfg)
        k = _rotary(k, positions, cfg)

        if segment_ids is not None:
            # Packed batches (datapipe.SequencePacker): tokens may only
            # attend within their own segment, so documents packed into
            # one row never see each other. Routed through the dense
            # masked path — the pallas flash / ring kernels take no mask
            # (packed training rows are max_len-sized, so the O(T^2)
            # score block is the moderate, static-shape case). Ring
            # attention exists to SHARD the sequence axis; silently
            # materializing full unsharded [B,H,T,T] scores on exactly
            # those long-context configs would be an OOM far from its
            # cause — refuse loudly instead.
            if cfg.attention in ("ring", "ring_fused"):
                raise ValueError(
                    f"segment_ids is not supported with attention="
                    f"{cfg.attention!r}: segment-aware masking uses the "
                    "dense O(T^2) path, which cannot shard the sequence "
                    "axis; use attention='dense' (or 'flash', which falls "
                    "back to dense under a mask) for packed batches.")
            segment_mask = (segment_ids[:, :, None]
                            == segment_ids[:, None, :])[:, None]  # [B,1,T,T]
            out = dot_product_attention(q, k, v, causal=cfg.causal,
                                        mask=segment_mask)
        elif cfg.attention in ("ring", "ring_fused"):
            from ..parallel import ring_self_attention
            out = ring_self_attention(
                q, k, v, mesh=self.mesh, causal=cfg.causal,
                impl="fused" if cfg.attention == "ring_fused" else "scan")
        elif cfg.attention == "flash":
            if (self.mesh is not None and self.mesh.size > 1
                    and isinstance(q, jax.core.Tracer)):
                # a Mosaic kernel inside a multi-device jit must run
                # per device (an eager init stays on one device)
                out = sharded_flash_attention(q, k, v, self.mesh,
                                              causal=cfg.causal)
            else:
                out = flash_attention(q, k, v, causal=cfg.causal)
        else:
            out = dot_product_attention(q, k, v, causal=cfg.causal)

        out = nn.DenseGeneral(cfg.dim, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              name="out")(out)
        # row-parallel output: the contraction over 'tensor'-sharded
        # heads left partial sums — this boundary IS the all-reduce
        out = _tp_boundary(out, self.mesh)
        if cfg.dropout > 0.0:
            out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out


class MLPBlock(nn.Module):
    config: TransformerConfig
    mesh: tp.Any = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        hidden = cfg.mlp_hidden
        # Gated (SwiGLU-style) MLP: one fused up-projection, split in two.
        up = nn.Dense(2 * hidden, use_bias=False, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="up")(x)
        gate, value = jnp.split(up, 2, axis=-1)
        # Constrain the gated product, not `up`: gate/value are each F
        # wide and tensor-shard cleanly, whereas pinning the fused 2F
        # output would put the split boundary mid-shard (an all-to-all).
        h = _tp_boundary(nn.silu(gate) * value, self.mesh, "tensor")
        out = nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="down")(h)
        out = _tp_boundary(out, self.mesh)  # row-parallel: the MLP all-reduce
        if cfg.dropout > 0.0:
            out = nn.Dropout(cfg.dropout, deterministic=not train)(out)
        return out


class Block(nn.Module):
    config: TransformerConfig
    mesh: tp.Any = None
    mixer: str = "attention"  # this layer's entry from mixer_pattern
    experts: bool = False     # this layer's entry from expert_layers
    layer: int = 0            # its index (gqa: picks the layer's kind)

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 train: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        if cfg.layer_pattern:
            # one mixer on one norm, which keeps the name it has in a
            # block of two (`norm1` before a sequence mixer, `norm2`
            # before the experts): the decode steps read either tree
            # with the same functions
            kind = pattern_kinds(cfg)[self.layer]
            norm = lambda name: nn.RMSNorm(
                epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)(x)
            if kind == "E":
                return x + ExpertMLP(cfg, name="moe")(norm("norm2"))
            if kind == "M":
                from .mamba2 import Mamba2Mixer
                one: nn.Module = Mamba2Mixer(cfg, name="ssm")
            else:
                from .gqa import GroupedAttention
                one = GroupedAttention(cfg, layer=self.layer, name="attn")
            return x + one(norm("norm1"), positions, train, segment_ids)
        if self.mixer == "ssd":
            from .ssd import SSDMixer
            mix: nn.Module = SSDMixer(cfg, mesh=self.mesh, name="ssd")
        elif cfg.attn_kind == "mla":
            from .mla import LatentAttention
            mix = LatentAttention(cfg, name="attn")
        elif cfg.attn_kind == "gqa":
            from .gqa import GroupedAttention
            mix = GroupedAttention(cfg, layer=self.layer, name="attn")
        else:
            mix = Attention(cfg, mesh=self.mesh, name="attn")
        x = x + mix(
            nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                       name="norm1")(x), positions, train, segment_ids)
        normed = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                            name="norm2")(x)
        if self.experts:
            x = x + ExpertMLP(cfg, name="moe")(normed)
        elif cfg.moe_experts > 0:
            x = x + MoEMLP(dim=cfg.dim, hidden=cfg.dim * cfg.mlp_ratio,
                           num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                           capacity_factor=cfg.moe_capacity_factor,
                           dispatch=cfg.moe_dispatch, mesh=self.mesh,
                           dtype=cfg.dtype, name="moe")(normed)
        else:
            x = x + MLPBlock(cfg, mesh=self.mesh, name="mlp")(normed, train)
        return x


def _remat(cfg: TransformerConfig):
    """nn.remat wrapper for Block honouring cfg.remat_policy."""
    policies = {
        "full": None,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    if cfg.remat_policy not in policies:
        raise ValueError(f"remat_policy must be one of {sorted(policies)}, "
                         f"got {cfg.remat_policy!r}")
    policy = policies[cfg.remat_policy]
    kwargs = {"policy": policy} if policy is not None else {}
    return nn.remat(Block, static_argnums=(3,), **kwargs)


class _CarryBlock(nn.Module):
    """Block wrapper with scan-compatible (carry, out) signature.

    `train` is a (static) module attribute so nn.scan only sees array
    arguments.
    """

    config: TransformerConfig
    mesh: tp.Any = None
    train: bool = False
    mixer: str = "attention"

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        block = _remat(self.config) if self.config.remat else Block
        y = block(self.config, mesh=self.mesh, mixer=self.mixer,
                  name="block")(x, positions, self.train, segment_ids)
        return y, None


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, T] int32 -> logits [B, T, vocab]."""

    config: TransformerConfig
    mesh: tp.Any = None

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: tp.Optional[jax.Array] = None,
                 train: bool = False,
                 return_hidden: bool = False,
                 segment_ids: tp.Optional[jax.Array] = None) -> tp.Any:
        """Apply the LM. With `segment_ids` ([B, T] int, 0 = padding,
        1-based per packed document — the `datapipe.SequencePacker`
        layout), attention is segment-aware: packed documents never
        attend across their boundaries; pass the packer's per-segment
        `positions` alongside so rotary phases restart per document."""
        cfg = self.config
        pattern = mixer_pattern(cfg)
        if tokens.shape[1] > cfg.max_seq_len and "attention" in pattern:
            # A pure-SSD stack has no positional table and no [T, T]
            # score block — nothing in it caps T, so only stacks with
            # attention layers enforce the ceiling.
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds "
                f"config.max_seq_len={cfg.max_seq_len}")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :], tokens.shape)
        # Embedding table kept in f32 (it doubles as the tied output
        # head); activations drop to the compute dtype right after lookup.
        embedding = self.param(
            "embed", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.dim),
            cfg.param_dtype)
        x = jnp.take(embedding, tokens, axis=0).astype(cfg.dtype)
        experts = expert_layers(cfg)
        if cfg.scan_layers:
            # One compiled block body, scanned over a stacked [L, ...]
            # parameter dim — the idiomatic deep-model layout on TPU.
            # One body means one parameter shape, so the mixer pattern
            # must be uniform (a hybrid stack's attention and SSD
            # layers have different parameter trees and cannot stack).
            if len(set(pattern)) > 1:
                raise ValueError(
                    "scan_layers requires a uniform mixer pattern (one "
                    f"scanned body = one parameter shape); got {pattern}. "
                    "Use scan_layers=False for hybrid attention/SSD "
                    "stacks.")
            scan_block = nn.scan(
                _CarryBlock, variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,
                length=cfg.num_layers)
            x, _ = scan_block(cfg, mesh=self.mesh, train=train,
                              mixer=pattern[0],
                              name="blocks")(x, positions, segment_ids)
        else:
            block = _remat(cfg) if cfg.remat else Block
            for layer in range(cfg.num_layers):
                x = block(cfg, mesh=self.mesh, mixer=pattern[layer],
                          experts=experts[layer], layer=layer,
                          name=f"block_{layer}")(
                    x, positions, train, segment_ids)
        x = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                       name="norm_f")(x)
        if not cfg.tie_head:
            # an output table of its own, laid out like the embedding
            embedding = self.param(
                "head", nn.initializers.normal(0.02),
                (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        if return_hidden:
            # Skip the head: the caller contracts against the tied
            # embedding itself (e.g. ops.losses.chunked_softmax_
            # cross_entropy, which never materializes [B, T, V]).
            return x, embedding
        # Tied output head: operands in the compute dtype (the model's
        # single largest matmul — f32 operands would run it at a
        # fraction of the bf16 MXU rate), accumulated in f32 for a
        # stable cross-entropy.
        logits = jnp.einsum("btd,vd->btv", x,
                            embedding.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        return logits


def transformer_shardings(params: tp.Any) -> tp.Any:
    """PartitionSpec tree for a TransformerLM parameter pytree.

    Megatron-style tensor parallelism over the 'tensor' axis with FSDP
    sharding over 'fsdp':

      embed [V, D]            -> (tensor, fsdp)   vocab-parallel embedding
      attn qkv [D, 3, H, Dh]  -> (fsdp, None, tensor, None)  column split
      attn out [H, Dh, D]     -> (tensor, None, fsdp)        row split
      ssd cbv [D, H, P]       -> (fsdp, tensor, None)        column split
      ssd out [H, Dh, D]      -> (tensor, None, fsdp)        row split
      ssd dt_bias [H]         -> (tensor,)                   head-local
      mlp up [D, 2F]          -> (fsdp, tensor)              column split
      mlp down [F, D]         -> (tensor, fsdp)              row split
      moe w_up [E, D, F]      -> (expert, fsdp, tensor)      expert parallel
      moe w_down [E, F, D]    -> (expert, tensor, fsdp)
      moe router [D, E]       -> replicated
      norms [D]               -> replicated

    Contractions over a 'tensor'-sharded dimension leave partial sums;
    XLA inserts the psum over 'tensor' exactly where megatron puts its
    all-reduce. Apply with jax.tree.map + NamedSharding(mesh, spec).
    """

    def spec_for(path: tp.Tuple[str, ...], leaf) -> P:
        joined = "/".join(str(getattr(p, "key", p)) for p in path)
        if "embed" in joined:
            return P("tensor", "fsdp")
        if "moe/w_up" in joined:
            base: tp.Tuple = ("expert", "fsdp", "tensor")
        elif "moe/w_down" in joined:
            base = ("expert", "tensor", "fsdp")
        elif "router" in joined:
            base = ()
        elif "qkv" in joined:
            base = ("fsdp", None, "tensor", None)
        elif "attn/out" in joined:
            base = ("tensor", None, "fsdp")
        elif "ssd/cbv" in joined:
            base = ("fsdp", "tensor", None)
        elif "ssd/out" in joined:
            base = ("tensor", None, "fsdp")
        elif "ssd/dt_bias" in joined:
            base = ("tensor",)
        elif "mlp/up" in joined:
            base = ("fsdp", "tensor")
        elif "mlp/down" in joined:
            base = ("tensor", "fsdp")
        else:
            base = ()
        if "blocks/" in joined:
            # scan-stacked layout: leading [num_layers] dim shards over
            # 'pipe' (a no-op when the pipe axis has size 1).
            return P("pipe", *base)
        return P(*base)

    return jax.tree_util.tree_map_with_path(spec_for, params)
