# From a dots.vlm1 / DeepSeek-V3-shaped configuration file (the
# published config.json's keys, plus `held_experts` and the published
# expert count for the chip's share) to the program's TransformerConfig.
# `seeded_params` and the device helpers are harness/model.py's.
"""Build the program's model from a latent-attention, routed-expert
configuration file."""
import jax.numpy as jnp

from .model import device_record, memory_peak_bytes, seeded_params  # noqa: F401

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def transformer_config(config: dict, **overrides):
    """The program's TransformerConfig for the config file's keys.
    Refuses what the program cannot express instead of running a
    different model under the published name. A program without the
    latent / expert keys (an earlier commit) fails here with a
    TypeError, before any weight is made."""
    from flashy_tpu.models import TransformerConfig
    heads = config["num_attention_heads"]
    scaling = config.get("rope_scaling") or {}
    first, count = config["held_experts"]
    problems = [what for what, bad in (
        ("grouped KV heads", config.get("num_key_value_heads", heads) != heads),
        ("an activation other than silu", config.get("hidden_act") != "silu"),
        ("biases", bool(config.get("attention_bias"))),
        ("a tied output head", bool(config.get("tie_word_embeddings"))),
        ("a rope scaling other than yarn",
         scaling.get("type", "yarn") != "yarn"),
        ("a scoring function other than sigmoid",
         config.get("scoring_func") != "sigmoid"),
        ("a top-k method other than noaux_tc",
         config.get("topk_method") != "noaux_tc"),
        ("unnormalised top-k gates", not config.get("norm_topk_prob")),
        ("expert layers that skip layers", config.get("moe_layer_freq") != 1),
        ("a held range unlike n_routed_experts",
         count != config["n_routed_experts"]
         or first + count > config["n_routed_experts_published"]),
    ) if bad]
    if problems:
        raise ValueError(f"TransformerLM cannot express: {problems}")
    return TransformerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_hidden_layers"], num_heads=heads,
        max_seq_len=config["max_position_embeddings"],
        attn_kind="mla", q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]), rope_interleaved=True,
        yarn_factor=float(scaling.get("factor", 1.0)),
        yarn_original_len=scaling.get("original_max_position_embeddings", 0),
        yarn_beta_fast=float(scaling.get("beta_fast", 32)),
        yarn_beta_slow=float(scaling.get("beta_slow", 1)),
        yarn_mscale=float(scaling.get("mscale", 1.0)),
        yarn_mscale_all_dim=float(scaling.get("mscale_all_dim", 0.0)),
        dense_layers=config["first_k_dense_replace"],
        dense_hidden=config["intermediate_size"],
        n_routed=config["n_routed_experts_published"],
        held_experts=(first, count),
        expert_top_k=config["num_experts_per_tok"],
        expert_groups=config["n_group"],
        expert_topk_groups=config["topk_group"],
        expert_scale=float(config["routed_scaling_factor"]),
        n_shared=config["n_shared_experts"],
        expert_hidden=config["moe_intermediate_size"],
        tie_head=False, param_dtype=DTYPES[config["torch_dtype"]],
        **overrides)
