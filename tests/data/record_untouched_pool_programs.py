# Recorder of tests/data/untouched_pool_programs.json: the sha256 of the
# lowered text of every executable of toy engines whose pools have no
# scale leaves — a latent pool (gather and fused reads) and K/V pools
# in the model's dtype (the toy's grid walk, gather, and eight 128-wide
# heads on the grouped walk). PR 30 changed how an int8 pool stores its
# scales; these programs had to stay the programs they were, byte for
# byte. tests/test_paged.py imports `programs` from here and runs it on
# the working tree, so both sides are one definition:
#
#   git archive <parent> | tar -x -C /tmp/parent
#   JAX_PLATFORMS=cpu python tests/data/record_untouched_pool_programs.py \
#       /tmp/parent tests/data/untouched_pool_programs.json
#
# The file in the repo was first taken from commit 61bbbe0 (PR 29),
# before the first edit of PR 30. Record it anew only when a PR means to
# change what a pool without scales compiles to. PR 34 did, on its own
# finished tree (the parent cannot lower the new argument list): the
# decode step returns the advanced positions beside the tokens, and a
# prefill slice takes `(tokens, positions, active)` and `final` and
# returns the three with row `slot` put live, so that the next step can
# be dispatched before this one is read. `verify` and `copy_block` came
# out byte-equal to PR 29's, as they must: nothing of theirs moved.
"""Record the lowered programs of engines whose pools hold no scales."""
import hashlib
import json
import sys


def _engines():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import model_dots
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.serve import DecodeEngine
    from tests.test_latent_experts import TOY

    config = dict(TOY, kv_lora_rank=128, held_experts=[4, 8],
                  n_routed_experts=8)
    cfg = model_dots.transformer_config(config, attention="dense",
                                        dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = {"params": model_dots.seeded_params(model, 3)}
    for kernel in ("gather", "fused"):
        yield f"latent/{kernel}", DecodeEngine(
            model, params, slots=3, max_seq_len=64, cache_layout="paged",
            block_size=8, chunk=16, kernel=kernel, spec_k=2,
            cache_scope=f"untouched_latent_{kernel}")

    def kv_model(**sizes):
        cfg = TransformerConfig(vocab_size=32, attention="dense",
                                dtype=jnp.float32, **sizes)
        model = TransformerLM(cfg)
        return model, model.init(jax.random.PRNGKey(0),
                                 jnp.ones((1, 4), jnp.int32))

    toy = kv_model(dim=16, num_layers=2, num_heads=2, max_seq_len=32)
    wide = kv_model(dim=1024, num_layers=1, num_heads=8, max_seq_len=512)
    for name, (model, params), block_size, kernel in (
            ("kv-toy/gather", toy, 4, "gather"),
            ("kv-toy/fused", toy, 4, "fused"),
            ("kv-wide/fused", wide, 16, "fused")):
        yield name, DecodeEngine(
            model, params, slots=2, cache_layout="paged", chunk=16,
            block_size=block_size, kv_dtype="model", kernel=kernel,
            spec_k=2, cache_scope=f"untouched_{name}")


def programs() -> dict:
    """Name -> sha256 of the lowered text, for whatever `flashy_tpu` is
    importable: decode, both prefill slices, verify and the COW copy of
    each engine, lowered at the engine's own (warm-up) arguments."""
    import jax.numpy as jnp
    out = {}
    for name, engine in _engines():
        table = engine._table()
        slot_args = (engine._tokens, engine._positions, engine._active,
                     engine._next_key())
        drafts = jnp.full((engine.slots, engine.spec_k), engine.pad_token,
                          jnp.int32)
        lowered = {
            "decode": engine._build_decode().lower(
                engine._params, engine._cache, table, *slot_args),
            "verify": engine._build_verify(engine.spec_k).lower(
                engine._params, engine._cache, table, slot_args[0], drafts,
                *slot_args[1:]),
            "copy_block": engine._build_copy().lower(
                engine._cache, jnp.int32(0), jnp.int32(0)),
        }
        for size in sorted({engine.chunk, engine.tail_bucket}):
            lowered[f"prefill_chunk/{size}"] = \
                engine._build_prefill_chunk(size).lower(
                    engine._params, engine._cache, table,
                    jnp.full((1, size), engine.pad_token, jnp.int32),
                    jnp.int32(0), jnp.int32(1), jnp.int32(0),
                    engine._next_key(), slot_args[:3], jnp.bool_(False))
        for program, low in lowered.items():
            out[f"{name}/{program}"] = hashlib.sha256(
                low.as_text().encode()).hexdigest()
    return out


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    with open(sys.argv[2], "w") as f:
        json.dump(programs(), f, indent=1, sort_keys=True)
        f.write("\n")
