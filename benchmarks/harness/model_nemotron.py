# From a nemotron_h-shaped configuration file (the published
# config.json's keys, plus `held_experts` and the published expert count
# for the chip's share) to the program's TransformerConfig, and its
# weights from the seed. The device helpers are harness/model.py's.
"""Build the program's model from a Mamba-2 / latent-expert / attention
hybrid's configuration file."""
import jax
import jax.numpy as jnp

from .model import device_record, memory_peak_bytes  # noqa: F401

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# What the seed draws beside the program's own initialisers (normal(0.02)
# embedding, lecun-normal projections, Mamba-2's published A / dt / D,
# normal(0.01) correction bias): the configuration file's `assumed`
# gives the reason of each.
DRAWING = {
    "embed_std": 2.0,          # the embedding table, times 2.0 / 0.02
    "router_bias_std": 0.001,  # the correction bias, times 0.001 / 0.01
    "bc_scale": 2.0,           # the B and C columns of every Mamba in_proj
    "centred": ("moe", "shared", "down"),  # minus its mean over the
                                           # hidden units (below)
    # every projection into the residual times 1 / sqrt(layers as run):
    # rescale_prenorm_residual
    "residual_leaves": (("ssm", "out_proj"), ("attn", "out"),
                        ("moe", "latent_up"), ("moe", "shared", "down")),
}


def transformer_config(config: dict, **overrides):
    """The program's TransformerConfig for the config file's keys.
    Refuses what the program cannot express instead of running a
    different model under the published name. A program without the
    layer-pattern keys (an earlier commit) fails here with a TypeError,
    before any weight is made."""
    from flashy_tpu.models import TransformerConfig
    pattern, layers = config["hybrid_override_pattern"], config[
        "num_hidden_layers"]
    first, count = config["held_experts"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    problems = [what for what, bad in (
        ("a layer pattern of another length, or of other kinds than M, E "
         "and *", len(pattern) != layers or set(pattern) - set("ME*")),
        ("a Mamba width other than expand x hidden_size",
         inner != config["expand"] * config["hidden_size"]),
        ("a Mamba activation other than silu",
         config["mamba_hidden_act"] != "silu"),
        ("an expert activation other than relu2",
         config["mlp_hidden_act"] != "relu2"),
        ("biases other than the conv's",
         bool(config["attention_bias"] or config["mlp_bias"]
              or config["use_bias"] or config["mamba_proj_bias"])
         or not config["use_conv_bias"]),
        ("a time-step init other than Mamba-2's defaults",
         (config["time_step_min"], config["time_step_max"],
          config["time_step_floor"]) != (0.001, 0.1, 0.0001)),
        ("two norm epsilons",
         config["norm_eps"] != config["layer_norm_epsilon"]),
        ("a window", config.get("sliding_window") is not None),
        ("a tied output head", bool(config["tie_word_embeddings"])),
        ("a float32 residual stream", bool(config["residual_in_fp32"])),
        ("unnormalised top-k gates", not config["norm_topk_prob"]),
        ("router groups", (config["n_group"], config["topk_group"])
         != (1, 1)),
        ("more than one shared expert, or none",
         config["n_shared_experts"] != 1),
        ("a held range unlike n_routed_experts",
         count != config["n_routed_experts"]
         or first + count > config["n_routed_experts_published"]),
    ) if bad]
    if problems:
        raise ValueError(f"TransformerLM cannot express: {problems}")
    return TransformerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=layers, num_heads=config["num_attention_heads"],
        max_seq_len=config["max_position_embeddings"],
        layer_pattern=pattern,
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssd_state_dim=config["ssm_state_size"],
        ssm_groups=config["n_groups"], ssm_conv=config["conv_kernel"],
        attn_kind="gqa", qk_head_dim=config["head_dim"],
        num_kv_heads=config["num_key_value_heads"],
        rope=False,  # the file's `assumed.no_rotary`
        norm_eps=float(config["norm_eps"]),
        n_routed=config["n_routed_experts_published"],
        held_experts=(first, count),
        expert_top_k=config["num_experts_per_tok"],
        expert_scale=float(config["routed_scaling_factor"]),
        n_shared=config["n_shared_experts"],
        expert_hidden=config["moe_intermediate_size"],
        expert_act="relu2", expert_latent=config["moe_latent_size"],
        shared_hidden=config["moe_shared_expert_intermediate_size"],
        tie_head=False, param_dtype=DTYPES[config["torch_dtype"]],
        **overrides)


def _redraw(cfg, params: dict) -> dict:
    """`params` as the program's init made them, with DRAWING laid over."""
    params = dict(params)
    embed = params["embed"]
    params["embed"] = (embed * (DRAWING["embed_std"] / 0.02)).astype(
        embed.dtype)
    shrink = cfg.num_layers ** -0.5
    for layer in range(cfg.num_layers):
        block = jax.tree_util.tree_map(lambda leaf: leaf,
                                       params[f"block_{layer}"])
        for path in DRAWING["residual_leaves"]:
            at = block
            for name in path:
                at = at.get(name) if isinstance(at, dict) else None
                if at is None:
                    break
            if at is not None:
                at["kernel"] = (at["kernel"] * shrink).astype(
                    at["kernel"].dtype)
        if "moe" in block:
            # relu(.)^2 is not zero-mean: centred over the hidden units,
            # the shared expert adds nothing that every token shares
            down = block["moe"]["shared"]["down"]
            down["kernel"] = (down["kernel"] - jnp.mean(
                down["kernel"].astype(jnp.float32), axis=0, keepdims=True)
                              ).astype(down["kernel"].dtype)
        if "ssm" in block:
            inner = cfg.ssm_heads * cfg.ssm_head_dim  # [z | x | B C | dt]
            states = 2 * cfg.ssm_groups * cfg.ssd_state_dim
            block["ssm"]["in_proj"]["kernel"] = block["ssm"]["in_proj"][
                "kernel"].at[:, 2 * inner:2 * inner + states].multiply(
                    DRAWING["bc_scale"])
        if "moe" in block:
            block["moe"]["router_bias"] = block["moe"]["router_bias"] * (
                DRAWING["router_bias_std"] / 0.01)
        params[f"block_{layer}"] = block
    return params


def seeded_params(model, seed: int):
    """The model's parameter tree from `seed`, made on the device by one
    jitted call: the program's init, then DRAWING (no float32 copy of a
    bfloat16 leaf is ever held)."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))

    def make(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return _redraw(model.config, params)

    return jax.jit(make)(key)
