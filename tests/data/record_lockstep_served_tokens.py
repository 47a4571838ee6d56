# Recorder of tests/data/lockstep_served_tokens.json: what the scheduler
# served, token for token, on toy engines of every pool the benchmark's
# cells run (K/V int8 paged, latent, grouped with window rings,
# recurrent state) plus dense, SAMPLING at temperature 0.8 from one key
# a engine — so the file pins the tokens AND the order the sampling keys
# are drawn in, slice by slice and step by step, with slots reused and
# budgets from 1 up. Run on a checkout of the commit it writes the file;
# tests/test_pipelined_step.py imports `served` from here and runs it on
# the working tree, so both sides are one definition:
#
#   git archive <parent> | tar -x -C /tmp/parent
#   JAX_PLATFORMS=cpu python tests/data/record_lockstep_served_tokens.py \
#       /tmp/parent tests/data/lockstep_served_tokens.json
#
# The file in the repo was taken from commit 3d99890 (PR 33), whose
# scheduler read every step back before it planned the next: PR 34 keeps
# one step in flight and must serve the same tokens. Every request here
# ends by its budget, the ending whose slot turnover the pipeline does
# not delay (an EOS is seen one step late, which moves later admissions
# by a step and with them the keys: the tests hold those runs to
# `generate()`'s greedy stream instead). Record it anew only when a PR
# means to change what is sampled.
"""Record what toy engines of every pool kind serve under sampling."""
import json
import sys

KINDS = ("dense", "int8", "latent", "window", "recurrent")
# (prompt length, budget): slots are reused, slices are uneven, a budget
# of 1 ends on its first token
REQUESTS = ((5, 4), (11, 1), (3, 6), (17, 3), (9, 2), (6, 5), (20, 7))


def toy(kind: str):
    """(model, params, engine keywords) of one pool kind."""
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM
    paged = {"cache_layout": "paged", "block_size": 4, "chunk": 8}
    if kind in ("dense", "int8"):
        cfg = TransformerConfig(vocab_size=64, dim=16, num_layers=2,
                                num_heads=2, max_seq_len=64,
                                attention="dense", dtype=jnp.float32)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.ones((1, 4), jnp.int32))
        return model, params, ({"chunk": 8} if kind == "dense"
                               else dict(paged, kv_dtype="int8"))
    if kind == "latent":
        from tests.test_latent_experts import _toy
    elif kind == "window":
        from tests.test_hybrid_attention import _toy
    else:
        from tests.test_recurrent_hybrid import _toy
        paged["block_size"] = 8
    _, _, model, params = _toy()
    return model, {"params": params}, dict(paged, keep_logits=True)


def engine_of(kind: str, **kwargs):
    """A warm two-slot engine of one pool kind."""
    from flashy_tpu.serve import DecodeEngine
    model, params, keywords = toy(kind)
    engine = DecodeEngine(model, params, **{
        "slots": 2, "max_seq_len": 64, "cache_scope": f"pipelined_{kind}",
        **keywords, **kwargs})
    engine.warmup()
    return engine


def prompts():
    import numpy as np
    rng = np.random.default_rng(34)
    return [(rng.integers(1, 64, length).astype(np.int32), budget)
            for length, budget in REQUESTS]


def served(kind: str) -> list:
    """The generated tokens of every request, in submission order, for
    whatever `flashy_tpu` is importable."""
    import jax
    from flashy_tpu.serve import ContinuousBatchingScheduler
    engine = engine_of(kind, temperature=0.8, rng=jax.random.PRNGKey(11))
    scheduler = ContinuousBatchingScheduler(engine)
    handles = [scheduler.submit(prompt, budget)
               for prompt, budget in prompts()]
    scheduler.run()
    return [[int(t) for t in handle.generated] for handle in handles]


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    with open(sys.argv[2], "w") as f:  # one line a kind
        f.write("{\n" + ",\n".join(
            f' "{kind}": {json.dumps(served(kind))}' for kind in KINDS)
            + "\n}\n")
