# Recorder of tests/data/olmo_toy_parent_logits.npz: a toy OLMo-shaped
# model's paged chunk / decode / verify logits (bf16 compute, pools in
# the model's dtype and in int8) and the sha256 of the lowered chunk and
# decode programs. Run on a checkout of the PARENT commit it writes the
# file; tests/test_latent_experts.py imports `steps` from here and runs
# it on the working tree, so both sides are one definition:
#
#   git archive <parent> | tar -x -C /tmp/parent
#   JAX_PLATFORMS=cpu python tests/data/record_olmo_toy_parent_logits.py \
#       /tmp/parent tests/data/olmo_toy_parent_logits.npz
#
# The file in the repo was taken from commit 73d3e70 (PR 26). Record it
# anew only when a PR means to change what a default config computes.
"""Record a default-config model's paged-step logits from a checkout."""
import hashlib
import sys

import numpy as np


def steps() -> dict:
    """The logits and program hashes of whatever `flashy_tpu` is
    importable."""
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM
    from flashy_tpu.ops.paged_attention import init_pool
    from flashy_tpu.serve.paged import paged_apply_step
    out = {}
    for kv_dtype in ("model", "int8"):
        cfg = TransformerConfig(vocab_size=96, dim=64, num_layers=2,
                                num_heads=4, mlp_ratio=4, max_seq_len=64,
                                attention="dense", dtype=jnp.bfloat16)
        model = TransformerLM(cfg)
        params = {"params": jax.jit(lambda k: model.init(
            k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(7))}
        pool = init_pool(cfg, 13, 8, kv_dtype)
        table = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0], [5, 6, 7, 8, 0, 0, 0, 0],
                             [9, 10, 11, 12, 0, 0, 0, 0]], jnp.int32)
        step = jax.jit(lambda p, c, t, tok, pos: paged_apply_step(
            model, p, cfg, tok, pos, c, t, kernel="gather"))

        def digest(*args):
            text = step.lower(*args).as_text()
            return np.frombuffer(hashlib.sha256(text.encode()).digest(),
                                 np.uint8)

        rng = np.random.default_rng(5)
        toks = jnp.asarray(rng.integers(0, 96, (1, 16)), jnp.int32)
        for i, start in enumerate((0, 8)):
            pos = (start + jnp.arange(8, dtype=jnp.int32))[None]
            logits, pool = step(params, pool, table[:1],
                                toks[:, start:start + 8], pos)
            out[f"{kv_dtype}/chunk{i}"] = np.asarray(logits)
        if kv_dtype == "model":
            out["hash/chunk"] = digest(params, pool, table[:1], toks[:, :8],
                                       pos)
        tok = jnp.asarray(rng.integers(0, 96, (3, 1)), jnp.int32)
        pos = jnp.asarray([[16], [0], [3]], jnp.int32)
        logits, pool = step(params, pool, table, tok, pos)
        out[f"{kv_dtype}/decode"] = np.asarray(logits)
        if kv_dtype == "model":
            out["hash/decode"] = digest(params, pool, table, tok, pos)
        tok = jnp.asarray(rng.integers(0, 96, (3, 5)), jnp.int32)
        pos = (jnp.asarray([[17], [1], [4]], jnp.int32)
               + jnp.arange(5, dtype=jnp.int32)[None])
        logits, pool = step(params, pool, table, tok, pos)
        out[f"{kv_dtype}/verify"] = np.asarray(logits)
    return out


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    np.savez(sys.argv[2], **steps())
