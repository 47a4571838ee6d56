# From a MiMo-V2-shaped configuration file (the published config.json's
# keys, plus `held_experts` and the published expert count for the
# chip's share) to the program's TransformerConfig, and its weights from
# the seed. The device helpers are harness/model.py's.
"""Build the program's model from a window/full grouped-attention,
routed-expert configuration file."""
import jax
import jax.numpy as jnp

from .model import device_record, memory_peak_bytes  # noqa: F401

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# What the seed draws beside the program's own initialisers (normal(0.02)
# embedding, zero sinks, lecun-normal projections, normal(0.01)
# correction bias): the configuration file's `assumed` gives the reason
# of each, PERF.md section 6 (PR 31) the readings.
DRAWING = {
    "embed_std": 1.0,          # the embedding table, times 1.0 / 0.02
    "query_scale": 3.0,        # the query columns of every in_proj
    "attn_out_scale": 0.267,   # every attention's output projection,
                               # 1 / sqrt(2 x 7 layers)
    "sink_mean": 7.0,          # a window layer's sinks: normal(mean, std)
    "sink_std": 1.0,
    "router_bias_std": 0.001,  # the correction bias, times 0.001 / 0.01
}


def transformer_config(config: dict, **overrides):
    """The program's TransformerConfig for the config file's keys.
    Refuses what the program cannot express instead of running a
    different model under the published name. A program without the
    grouped-attention keys (an earlier commit) fails here with a
    TypeError, before any weight is made."""
    from flashy_tpu.models import TransformerConfig
    heads, layers = config["num_attention_heads"], config["num_hidden_layers"]
    scaling = config.get("rope_scaling") or {}
    first, count = config["held_experts"]
    experts = list(config["moe_layer_freq"])
    dense = experts.index(1) if 1 in experts else layers
    problems = [what for what, bad in (
        ("window layers with another head count or head widths than the "
         "full layers' query side",
         config["swa_num_attention_heads"] != heads
         or config["swa_head_dim"] != config["head_dim"]
         or config["swa_v_head_dim"] != config["v_head_dim"]),
        ("a layer pattern or an expert pattern of another length",
         len(config["hybrid_layer_pattern"]) != layers
         or len(experts) != layers),
        ("dense layers after the first expert layer",
         experts != [0] * dense + [1] * (layers - dense)),
        ("a sink in the full-attention layers",
         bool(config.get("add_full_attention_sink_bias"))),
        ("a window unlike sliding_window_size",
         config["sliding_window"] != config.get("sliding_window_size",
                                                config["sliding_window"])),
        ("an activation other than silu", config.get("hidden_act") != "silu"),
        ("biases", bool(config.get("attention_bias"))),
        ("a tied output head", bool(config.get("tie_word_embeddings"))),
        ("a rope scaling", scaling.get("type", "default") != "default"),
        ("a scoring function other than sigmoid",
         config.get("scoring_func") != "sigmoid"),
        ("a top-k method other than noaux_tc",
         config.get("topk_method") != "noaux_tc"),
        ("unnormalised top-k gates", not config.get("norm_topk_prob")),
        ("a held range unlike n_routed_experts",
         count != config["n_routed_experts"]
         or first + count > config["n_routed_experts_published"]),
    ) if bad]
    if problems:
        raise ValueError(f"TransformerLM cannot express: {problems}")
    return TransformerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=layers, num_heads=heads,
        max_seq_len=config["max_position_embeddings"],
        attn_kind="gqa", qk_head_dim=config["head_dim"],
        v_head_dim=config["v_head_dim"],
        rotary_dim=int(config["partial_rotary_factor"] * config["head_dim"]),
        num_kv_heads=config["num_key_value_heads"],
        window_layers=tuple(config["hybrid_layer_pattern"]),
        window=config["sliding_window"],
        window_kv_heads=config["swa_num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        window_rope_theta=float(config["swa_rope_theta"]),
        window_sink=bool(config["add_swa_attention_sink_bias"]),
        value_scale=float(config["attention_value_scale"]),
        norm_eps=float(config["layernorm_epsilon"]),
        dense_layers=dense, dense_hidden=config["intermediate_size"],
        n_routed=config["n_routed_experts_published"],
        held_experts=(first, count),
        expert_top_k=config["num_experts_per_tok"],
        expert_groups=config["n_group"],
        expert_topk_groups=config["topk_group"],
        expert_scale=float(config["routed_scaling_factor"] or 1.0),
        n_shared=config["n_shared_experts"] or 0,
        expert_hidden=config["moe_intermediate_size"],
        tie_head=False, param_dtype=DTYPES[config["torch_dtype"]],
        **overrides)


def _redraw(cfg, params: dict, key) -> dict:
    """`params` as the program's init made them, with DRAWING laid over."""
    params = dict(params)
    embed = params["embed"]
    params["embed"] = (embed * (DRAWING["embed_std"] / 0.02)).astype(
        embed.dtype)
    query_columns = cfg.num_heads * cfg.qk_head_dim
    for layer in range(cfg.num_layers):
        block = dict(params[f"block_{layer}"])
        attn = dict(block["attn"])
        attn["in_proj"] = {"kernel": attn["in_proj"]["kernel"].at[
            :, :query_columns].multiply(DRAWING["query_scale"])}
        attn["out"] = {"kernel": attn["out"]["kernel"]
                       * DRAWING["attn_out_scale"]}
        if "sink" in attn:
            attn["sink"] = DRAWING["sink_mean"] + DRAWING["sink_std"] * (
                jax.random.normal(jax.random.fold_in(key, layer),
                                  attn["sink"].shape, attn["sink"].dtype))
        block["attn"] = attn
        if "moe" in block:
            block["moe"] = dict(block["moe"], router_bias=block["moe"][
                "router_bias"] * (DRAWING["router_bias_std"] / 0.01))
        params[f"block_{layer}"] = block
    return params


def seeded_params(model, seed: int):
    """The model's parameter tree from `seed`, made on the device by one
    jitted call: the program's init, then DRAWING (no float32 copy of a
    bfloat16 leaf is ever held)."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))

    def make(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return _redraw(model.config, params, jax.random.fold_in(key, 1))

    return jax.jit(make)(key)
