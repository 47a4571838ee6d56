# The model zoo's numerics-audit registry — the `models/`+`ops/` half
# of the per-program hooks (`DecodeEngine.executables()` covers the
# engine's compiled registry; `parallel.audit` covers training). The
# serving-side numerics contracts live here: the paged int8 attention
# whose scale-folding identity FT203 structurally verifies — in BOTH
# spellings now: the XLA gather reference AND the fused Pallas
# paged-decode/verify kernels (ops/paged_decode.py), whose traced
# pallas_call bodies the ValueGraph stitches through, so a kernel
# rewrite that double-, un- or wrong-side-scales fails `make
# analyze-numerics` before it decodes garbage — and the speculative
# verify forward whose rejection-sampling path is the one place serve
# consumes PRNG keys under load. Entries are plain dicts — never
# analysis types — so the dependency only points analysis -> models.
"""Numerics-audit program registry for models/ and ops/."""
import typing as tp

__all__ = ["numerics_audit_programs"]


def numerics_audit_programs() -> tp.List[tp.Dict[str, tp.Any]]:
    """NumericsProgram kwargs for the serving-side hot programs: the
    gather-based paged int8 attention plus its fused Pallas twin and
    the fused [S, k+1] verify read (labels `attention/...`), the
    [S, k+1] speculative verify forward (labels `serve/...`), and the
    SSD mixer's dual forms — chunked training scan (gather + fused
    Pallas) and the single-token recurrent decode step (labels
    `ssd/...`)."""
    return _attention_entries() + _verify_entries() + _ssd_entries()


def _attention_entries() -> tp.List[tp.Dict[str, tp.Any]]:
    import jax
    import jax.numpy as jnp

    from ..ops.paged_attention import (paged_attention, paged_write,
                                       pool_spec)

    num_blocks, block_size, heads, head_dim = 4, 4, 2, 8
    batch, queries, entries = 2, 1, 3
    key = jax.random.PRNGKey(0)
    # the pool's own leaves: random int8 payloads, every scale 1 / 127
    entry = {
        name: (jax.random.randint(key, shape, -127, 127, jnp.int32
                                  ).astype(dtype) if dtype == jnp.int8
               else jnp.ones(shape, dtype) / 127.0)
        for name, (shape, dtype) in pool_spec(
            num_blocks, block_size, heads, head_dim, jnp.float32,
            "int8").items()}
    q = jax.random.normal(key, (batch, queries, heads, head_dim),
                          jnp.float32)
    table = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    positions = jnp.asarray([[5], [2]], jnp.int32)

    def attend(q_in, entry_in, table_in, positions_in):
        return paged_attention(q_in, entry_in, table_in, positions_in,
                               head_dim=head_dim, dtype=jnp.float32)

    new_k = jax.random.normal(key, (batch, queries, heads, head_dim),
                              jnp.float32)

    def write(entry_in, new_k_in, new_v_in, table_in, positions_in):
        return paged_write(entry_in, new_k_in, new_v_in, table_in,
                           positions_in)

    from ..ops.paged_decode import (fused_paged_attention,
                                    fused_speculative_verify)

    def attend_fused(q_in, entry_in, table_in, positions_in):
        # interpret=True pins the audited program to the same jaxpr the
        # CPU CI traces; the pallas_call eqn (and the FT203 skeleton
        # inside it) is identical with interpret=False on TPU
        return fused_paged_attention(q_in, entry_in, table_in,
                                     positions_in, head_dim=head_dim,
                                     dtype=jnp.float32, interpret=True)

    spec_k = 2
    q_verify = jax.random.normal(
        key, (batch, spec_k + 1, heads, head_dim), jnp.float32)
    verify_positions = positions[:, :1] \
        + jnp.arange(spec_k + 1, dtype=jnp.int32)[None]

    def verify_fused(q_in, entry_in, table_in, positions_in):
        return fused_speculative_verify(q_in, entry_in, table_in,
                                        positions_in, head_dim=head_dim,
                                        dtype=jnp.float32, interpret=True)

    return [
        {"label": "attention/paged-int8",
         "fn": attend,
         "example_args": (q, entry, table, positions)},
        {"label": "attention/paged-int8-fused",
         "fn": attend_fused,
         "example_args": (q, entry, table, positions)},
        {"label": "attention/paged-int8-fused-verify",
         "fn": verify_fused,
         "example_args": (q_verify, entry, table, verify_positions)},
        {"label": "attention/paged-int8-write",
         "fn": write,
         "example_args": (entry, new_k, new_k, table, positions),
         # the write path PRODUCES scales (quantize-on-write); there is
         # no contraction here for FT203 to place them against
         "quant_roles": {}},
    ]


def _verify_entries() -> tp.List[tp.Dict[str, tp.Any]]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .decoding import _apply_step, init_cache, speculative_acceptance
    from .transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=32, dim=16, num_layers=2,
                            num_heads=2, attention="dense",
                            max_seq_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 4), jnp.int32))
    slots, k = 2, 3
    cache = init_cache(cfg, slots, cfg.max_seq_len)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 32, (slots,)), jnp.int32)
    drafts = jnp.asarray(rng.integers(0, 32, (slots, k)), jnp.int32)
    positions = jnp.asarray([4, 7], jnp.int32)
    key = jax.random.key(0)

    def verify(params_in, cache_in, tokens_in, drafts_in, positions_in,
               key_in):
        # the engine's [S, k+1] verify contract (serve/engine.py
        # _build_verify), rejection-sampling leg included so the
        # audited program consumes keys the way production does
        toks = jnp.concatenate([tokens_in[:, None], drafts_in], axis=1)
        pos = positions_in[:, None] \
            + jnp.arange(k + 1, dtype=jnp.int32)[None]
        logits, cache_out = _apply_step(model, params_in, cfg, toks, pos,
                                        cache_in, positions_in)
        out, accepted = speculative_acceptance(
            drafts_in, logits, temperature=0.8, rng=key_in)
        return out, accepted, cache_out

    return [{
        "label": "serve/speculative-verify",
        "fn": verify,
        "example_args": (params, cache, tokens, drafts, positions, key),
    }]


def _ssd_entries() -> tp.List[tp.Dict[str, tp.Any]]:
    import jax
    import jax.numpy as jnp

    from ..ops.ssd_scan import ssd_chunked_scan, ssd_recurrent_scan

    batch, seq, heads, head_dim, dstate, chunk = 2, 16, 2, 8, 4, 8
    key = jax.random.PRNGKey(0)
    kc, kb, kv, ka = jax.random.split(key, 4)
    c = jax.random.normal(kc, (batch, seq, heads, dstate), jnp.bfloat16)
    b = jax.random.normal(kb, (batch, seq, heads, dstate), jnp.bfloat16)
    v = jax.random.normal(kv, (batch, seq, heads, head_dim), jnp.bfloat16)
    log_a = -jax.nn.softplus(
        jax.random.normal(ka, (batch, seq, heads), jnp.float32))
    state = jnp.zeros((batch, heads, head_dim, dstate), jnp.float32)

    # the training/prefill form: bf16 activations, the inter-chunk
    # state carried in f32 through lax.scan — FT201's carry walk must
    # find the widened accumulator, not the bf16 inputs
    def chunked(c_in, b_in, v_in, log_a_in, state_in):
        return ssd_chunked_scan(c_in, b_in, v_in, log_a_in,
                                state=state_in, chunk=chunk,
                                kernel="gather")

    def chunked_fused(c_in, b_in, v_in, log_a_in, state_in):
        # interpret=True pins the audited program to the same jaxpr the
        # CPU CI traces; the pallas_call eqn (and the f32 VMEM carry
        # FT201/FT203 walk inside it) is identical on TPU
        return ssd_chunked_scan(c_in, b_in, v_in, log_a_in,
                                state=state_in, chunk=chunk,
                                kernel="fused", interpret=True)

    # the decode form: one token per call, the [H, Dh, Dstate] slot
    # state advanced in f32 — the program every decode tick runs
    def recurrent(c_in, b_in, v_in, log_a_in, state_in):
        return ssd_recurrent_scan(c_in, b_in, v_in, log_a_in, state_in)

    one = (c[:, :1], b[:, :1], v[:, :1], log_a[:, :1], state)
    # quant_roles={} on all three: the SSD path carries no int8 K/V
    # payloads or scales — there is no quantized contraction for FT203
    # to place (the paged-int8-write opt-out convention)
    return [
        {"label": "ssd/chunked-scan",
         "fn": chunked,
         "example_args": (c, b, v, log_a, state),
         "quant_roles": {}},
        {"label": "ssd/chunked-scan-fused",
         "fn": chunked_fused,
         "example_args": (c, b, v, log_a, state),
         "quant_roles": {}},
        {"label": "ssd/recurrent-step",
         "fn": recurrent,
         "example_args": one,
         "quant_roles": {}},
    ]
