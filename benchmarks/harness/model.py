# From a configuration file (the published config.json's keys) to the
# program's TransformerLM, and its weights from the seed in one jitted
# call on the device.
"""Build the program's model from a benchmark configuration file."""
import jax
import jax.numpy as jnp


def transformer_config(config: dict, **overrides):
    """The program's TransformerConfig for a config file's `model` keys.
    Refuses what `TransformerLM` cannot express instead of running a
    different model under the published name."""
    from flashy_tpu.models import TransformerConfig
    heads = config["num_attention_heads"]
    ratio, rest = divmod(config["intermediate_size"], config["hidden_size"])
    problems = [what for what, bad in (
        ("grouped KV heads", config.get("num_key_value_heads", heads) != heads),
        ("a non-integer MLP ratio", rest != 0),
        ("rope_theta other than 10000", config.get("rope_theta", 1e4) != 1e4),
        ("an untied output head", not config.get("tie_word_embeddings")),
        ("an activation other than silu", config.get("hidden_act") != "silu"),
        ("biases", bool(config.get("attention_bias"))),
    ) if bad]
    if problems:
        raise ValueError(f"TransformerLM cannot express: {problems}")
    return TransformerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        num_layers=config["num_hidden_layers"], num_heads=heads,
        mlp_ratio=ratio, max_seq_len=config["max_position_embeddings"],
        **overrides)


def seeded_params(model, seed: int, shardings=None):
    """The model's parameter tree from `seed`, made on the device by one
    jitted init (f32 leaves, as the program trains and serves them)."""
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    init = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], out_shardings=shardings)
    return init(key)


def device_record() -> dict:
    """platform / kind / count as JAX reports them."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where unreported)."""
    return max((int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for dev in jax.devices()), default=0)
