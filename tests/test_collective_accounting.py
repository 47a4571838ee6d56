# Weak-scaling evidence without multi-chip hardware (VERDICT r4 #8):
# compile the sharded train step per mesh shape, extract the collective
# instructions from the HLO, and assert byte totals against analytic
# expectations. Exactness tests cannot catch a sharding spec that
# silently regresses to replication — the numbers stay right while the
# communication pattern (and the scaling story) disappears; these can.
"""Compile-time collective-bytes accounting per mesh shape."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from flashy_tpu.models import (TransformerConfig, TransformerLM,
                               transformer_shardings)
from flashy_tpu.parallel import (collective_stats, make_mesh, shard_batch,
                                 total_collective_bytes)


def _compile_train_step(mesh, cfg, batch, seq, param_specs=None):
    """Lower+compile one full train step on `mesh`; returns
    (compiled, param_bytes). `param_specs` overrides
    transformer_shardings (pass a replicated tree to model the
    regression being guarded against)."""
    model = TransformerLM(cfg, mesh=mesh)
    tokens_host = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens_host))
    variables = {"params": variables["params"]}
    specs = (param_specs if param_specs is not None
             else transformer_shardings(variables))
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    optim = optax.sgd(1e-3)  # sgd: no optimizer-state traffic in the way
    opt_state = jax.jit(optim.init)(params)
    tokens = shard_batch(jnp.asarray(tokens_host), mesh,
                         batch_axes=("data", "fsdp"))

    batch_sharding = NamedSharding(mesh, P(("data", "fsdp")))

    def train_step(params, opt_state, tokens):
        # Pin the batch sharding INSIDE the program: without this the
        # dispatcher may reshard inputs before the compiled module runs
        # and the collectives disappear from its HLO (observed: a
        # replicated-params compile showed zero collectives because the
        # batch was quietly replicated at dispatch).
        tokens = jax.lax.with_sharding_constraint(tokens, batch_sharding)
        def loss_fn(v):
            logits = model.apply(v, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = optim.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    compiled = jax.jit(train_step).lower(params, opt_state, tokens).compile()
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    return compiled, param_bytes


def _compiled_step(mesh, cfg, batch, seq, param_specs=None):
    """collective_stats of the compiled step (see _compile_train_step)."""
    compiled, param_bytes = _compile_train_step(mesh, cfg, batch, seq,
                                                param_specs)
    return collective_stats(compiled), param_bytes


def _replicated_specs(mesh, cfg, batch, seq):
    """The replication CONTROL both regression tests compare against:
    same init as _compile_train_step, every param spec collapsed to P()."""
    model = TransformerLM(cfg, mesh=mesh)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)), jnp.int32)
    variables = {"params": model.init(jax.random.PRNGKey(0), tokens)["params"]}
    return jax.tree_util.tree_map(lambda _: P(), variables)


_CFG = dict(vocab_size=128, dim=64, num_layers=2, num_heads=4,
            attention="dense")


@pytest.mark.slow
def test_fsdp_allgathers_params_replication_regression_fails():
    mesh = make_mesh({"fsdp": 4, "data": 2})
    cfg = TransformerConfig(**_CFG)
    sharded, param_bytes = _compiled_step(mesh, cfg, batch=16, seq=32)
    # FSDP analytic floor: the forward must materialize the sharded
    # parameters at least once -> all-gather output bytes >= the
    # fsdp-sharded parameter footprint (some leaves — norms, biases —
    # stay replicated, hence the 0.5 factor).
    assert sharded["all-gather"]["bytes"] >= 0.5 * param_bytes, sharded
    # ...and the step communicates at all (grads reduced somewhere).
    reduced = (sharded["all-reduce"]["bytes"]
               + sharded["reduce-scatter"]["bytes"]
               + sharded["all-to-all"]["bytes"])
    assert reduced > 0, sharded

    # The regression this test exists for: the same mesh with every
    # param spec silently collapsed to replication. Parameter
    # all-gather traffic must collapse with it — if this assertion
    # ever fails, the accounting itself stopped discriminating.
    replicated, _ = _compiled_step(
        mesh, cfg, batch=16, seq=32,
        param_specs=_replicated_specs(mesh, cfg, 16, 32))
    assert (replicated["all-gather"]["bytes"]
            < sharded["all-gather"]["bytes"] - 0.4 * param_bytes), (
        sharded, replicated)
    # pure DP grad sync: every param byte is all-reduced
    assert replicated["all-reduce"]["bytes"] >= param_bytes, replicated


@pytest.mark.slow
def test_tensor_parallel_allreduces_activations_per_block():
    mesh = make_mesh({"tensor": 2, "data": 4})
    cfg = TransformerConfig(**_CFG)
    batch, seq = 16, 32
    stats, _ = _compiled_step(mesh, cfg, batch=batch, seq=seq)
    # Megatron TP: each block's attention-out and MLP-down row-parallel
    # matmuls end in an activation all-reduce (forward), mirrored in
    # the backward -> at least 2 per layer, here as a conservative
    # floor over fwd+bwd, in bytes of the per-device activation.
    local_act_bytes = (batch // 4) * seq * cfg.dim * 4
    floor = 2 * cfg.num_layers * local_act_bytes
    assert stats["all-reduce"]["count"] >= 2 * cfg.num_layers, stats
    assert stats["all-reduce"]["bytes"] >= floor, (stats, floor)


@pytest.mark.slow
def test_ring_attention_permutes_kv_bytes():
    n_seq = 2
    mesh = make_mesh({"seq": n_seq, "data": 4})
    cfg = TransformerConfig(**dict(_CFG, attention="ring"))
    batch, seq = 8, 32
    stats, _ = _compiled_step(mesh, cfg, batch=batch, seq=seq)
    # Ring schedule: K and V blocks each make (n-1) hops per layer in
    # the forward (the backward re-rotates). Local K block =
    # [B_local, T/n, H, D] f32.
    local_kv = (batch // 4) * (seq // n_seq) * cfg.dim * 4
    floor = 2 * (n_seq - 1) * cfg.num_layers * local_kv
    perm = stats["collective-permute"]
    assert perm["count"] > 0, stats  # replication would erase the ring
    assert perm["bytes"] >= floor, (stats, floor)


@pytest.mark.slow
def test_expert_parallel_dispatches_tokens_all_to_all():
    mesh = make_mesh({"expert": 2, "data": 4})
    cfg = TransformerConfig(**dict(_CFG, moe_experts=4, moe_top_k=2,
                                   moe_dispatch="dropless_ep"))
    model = TransformerLM(cfg, mesh=mesh)
    tokens_host = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)
    variables = {"params": model.init(
        jax.random.PRNGKey(1), jnp.asarray(tokens_host))["params"]}
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), transformer_shardings(variables),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    tokens = shard_batch(jnp.asarray(tokens_host), mesh,
                         batch_axes=("data",))

    def fwd(v, tokens):
        logits, _ = model.apply(v, tokens, mutable=["losses"])
        return logits.sum()

    compiled = jax.jit(fwd).lower(params, tokens).compile()
    stats = collective_stats(compiled)
    # EP dispatch/combine must cross the expert axis as all-to-alls (or
    # degenerate to gathers on tiny shapes — but never to nothing).
    moved = (stats["all-to-all"]["bytes"] + stats["all-gather"]["bytes"]
             + stats["collective-permute"]["bytes"])
    assert stats["all-to-all"]["count"] > 0 or moved > 0, stats
    assert total_collective_bytes(compiled) > 0


def test_hlo_parser_handles_tuples_async_and_comments():
    """Parser unit cases: tuple shapes with /*index=N*/ comments (they
    contain '=' and broke the first regex), async -start/-done pairs
    counted once, and references to collective names not counted."""
    from flashy_tpu.parallel.accounting import collective_stats

    text = "\n".join([
        # tuple all-reduce with index comments: 64*4 + 64*4 + 4 bytes
        "%all-reduce.24 = (f32[64]{0}, /*index=1*/f32[64]{0}, "
        "/*index=2*/f32[]) all-reduce(%a, %b, %c), channel_id=1",
        # async pair: only the -start counts, and only its RESULT tuple
        # element (f32[64,16]) — the f32[8,16] operand alias would double
        # the bytes vs the sync lowering of the same program
        "%ag = (f32[8,16]{1,0}, f32[64,16]{1,0}) "
        "all-gather-start(%x), channel_id=2",
        "%ag.1 = f32[64,16]{1,0} all-gather-done(%ag)",
        # a reference, not an instruction
        "%gte = f32[64]{0} get-tuple-element(%all-reduce.24), index=0",
        # bf16 permute
        "%cp = bf16[4,32]{1,0} collective-permute(%y), channel_id=3",
        # sub-byte + fp8 payloads must not round to zero bytes
        "%q = u4[128]{0} all-gather(%z), channel_id=4",
        "%f8 = f8e4m3fn[64]{0} all-reduce(%w), channel_id=5",
        # ragged MoE dispatch gets its own key, not silence
        "%rag = f32[8,16]{1,0} ragged-all-to-all(%a, %b), channel_id=9",
    ])
    stats = collective_stats(text)
    assert stats["all-reduce"] == {"count": 2,
                                   "bytes": 64 * 4 * 2 + 4 + 64}
    assert stats["all-gather"] == {"count": 2,
                                   "bytes": 64 * 16 * 4 + 64}
    assert stats["collective-permute"] == {"count": 1, "bytes": 4 * 32 * 2}
    assert stats["ragged-all-to-all"] == {"count": 1, "bytes": 8 * 16 * 4}
    assert stats["all-to-all"]["count"] == 0

    # unknown dtypes are LOUD, not silently zero
    with pytest.raises(ValueError, match="unknown HLO dtype"):
        collective_stats("%x = q9[64]{0} all-reduce(%a), channel_id=1")


def test_async_start_counts_match_sync_lowering():
    """The bytes convention is sync-equivalent: for `-start` forms only
    the result element(s) of the output tuple count (ADVICE round 5 —
    the operand alias in the tuple used to double the total), so byte
    assertions calibrated on CPU (sync) hold on TPU (async)."""
    from flashy_tpu.parallel.accounting import collective_stats

    sync = collective_stats(
        "%ag = f32[64,16]{1,0} all-gather(%x), channel_id=1\n"
        "%cp = bf16[4,32]{1,0} collective-permute(%y), channel_id=2\n")
    async_ = collective_stats(
        # all-gather-start: (operand, result)
        "%ag = (f32[8,16]{1,0}, f32[64,16]{1,0}) "
        "all-gather-start(%x), channel_id=1\n"
        "%agd = f32[64,16]{1,0} all-gather-done(%ag)\n"
        # collective-permute-start: (operand, result, context scratch)
        "%cp = (bf16[4,32]{1,0}, bf16[4,32]{1,0}, u32[], u32[]) "
        "collective-permute-start(%y), channel_id=2\n"
        "%cpd = bf16[4,32]{1,0} collective-permute-done(%cp)\n")
    for op in ("all-gather", "collective-permute"):
        assert async_[op] == sync[op], op

    # non-tuple -start output (async all-reduce keeps the plain result
    # shape): counted exactly like the sync form
    sync_ar = collective_stats("%ar = f32[64]{0} all-reduce(%a), channel_id=3")
    async_ar = collective_stats(
        "%ar = f32[64]{0} all-reduce-start(%a), channel_id=3\n"
        "%ard = f32[64]{0} all-reduce-done(%ar)")
    assert async_ar["all-reduce"] == sync_ar["all-reduce"]

    # variadic all-reduce-start: the output tuple holds RESULTS ONLY
    # (no operand aliases, unlike all-gather-start) — count all of it
    sync_var = collective_stats(
        "%ar = (f32[64]{0}, f32[32]{0}) all-reduce(%a, %b), channel_id=4")
    async_var = collective_stats(
        "%ar = (f32[64]{0}, f32[32]{0}) all-reduce-start(%a, %b), channel_id=4\n"
        "%ard = (f32[64]{0}, f32[32]{0}) all-reduce-done(%ar)")
    assert async_var["all-reduce"] == sync_var["all-reduce"]
    assert async_var["all-reduce"]["bytes"] == (64 + 32) * 4


def test_reduce_scatter_sync_and_async_conventions():
    """reduce-scatter joins the table with the same sync-equivalent
    rule: the `-start` output tuple aliases the UNREDUCED full-gradient
    operand ahead of the 1/N result shard, so counting the whole tuple
    would overstate the ZeRO-1 update's traffic by exactly the factor
    the sharded update removes."""
    from flashy_tpu.parallel.accounting import collective_stats

    sync = collective_stats(
        "%rs = f32[8,16]{1,0} reduce-scatter(%x), channel_id=1")
    assert sync["reduce-scatter"] == {"count": 1, "bytes": 8 * 16 * 4}

    async_ = collective_stats(
        # (operand alias, result shard): only the shard counts
        "%rs = (f32[64,16]{1,0}, f32[8,16]{1,0}) "
        "reduce-scatter-start(%x), channel_id=1\n"
        "%rsd = f32[8,16]{1,0} reduce-scatter-done(%rs)")
    assert async_["reduce-scatter"] == sync["reduce-scatter"]

    # variadic: (in1, in2, out1, out2) -> the two output shards only
    stats = collective_stats(
        "%rs = (f32[64,16]{1,0}, bf16[64,16]{1,0}, /*index=2*/f32[8,16]{1,0}, "
        "/*index=3*/bf16[8,16]{1,0}) reduce-scatter-start(%x, %y), "
        "channel_id=2")
    assert stats["reduce-scatter"] == {"count": 1,
                                       "bytes": 8 * 16 * 4 + 8 * 16 * 2}


def test_compare_collective_stats_reports_delta():
    from flashy_tpu.parallel.accounting import compare_collective_stats

    replicated = ("%ar = f32[64]{0} all-reduce(%g), channel_id=1")
    zero1 = ("%rs = f32[8]{0} reduce-scatter(%g), channel_id=1\n"
             "%ag = f32[64]{0} all-gather(%p), channel_id=2")
    delta = compare_collective_stats(zero1, replicated)
    assert delta == {
        "all-reduce": {"count": -1, "bytes": -64 * 4},
        "reduce-scatter": {"count": 1, "bytes": 8 * 4},
        "all-gather": {"count": 1, "bytes": 64 * 4},
    }
    assert compare_collective_stats(replicated, replicated) == {}


def test_scalar_payload_async_start_counts_like_sync():
    """collective-permute of a scalar s32 counter: every element of the
    async output tuple is a 32-bit scalar, so shape alone cannot tell
    payload from context — position (context words trail) plus the
    operand+result floor must keep the 4 payload bytes, matching the
    sync lowering instead of reporting 0."""
    from flashy_tpu.parallel.accounting import collective_stats

    sync = collective_stats(
        "%cp = s32[] collective-permute(%y), channel_id=2")
    async_ = collective_stats(
        "%cp = (s32[], s32[], u32[], u32[]) "
        "collective-permute-start(%y), channel_id=2\n"
        "%cpd = s32[] collective-permute-done(%cp)")
    assert async_["collective-permute"] == sync["collective-permute"]
    assert async_["collective-permute"]["bytes"] == 4


def test_tuple_splitter_handles_layout_braces():
    # commas inside layout annotations {1,0} must not split elements:
    # a mixed-rank async tuple would otherwise fragment and count 0
    from flashy_tpu.parallel.accounting import _split_top_level_tuple

    assert _split_top_level_tuple(
        "(f32[8,16]{1,0}, f32[64,16]{1,0})") == [
            "f32[8,16]{1,0}", "f32[64,16]{1,0}"]
    assert _split_top_level_tuple("f32[8,16]{1,0}") is None


def test_multi_operand_async_start_counts_results_only():
    """Variadic all-gather-start: (in1, in2, out1, out2) -> only the two
    output elements count."""
    from flashy_tpu.parallel.accounting import collective_stats

    stats = collective_stats(
        "%ag = (f32[8,16]{1,0}, bf16[8,16]{1,0}, /*index=2*/f32[64,16]{1,0}, "
        "/*index=3*/bf16[64,16]{1,0}) all-gather-start(%x, %y), channel_id=7")
    assert stats["all-gather"] == {"count": 1,
                                   "bytes": 64 * 16 * 4 + 64 * 16 * 2}


@pytest.mark.slow
def test_memory_stats_fsdp_shrinks_argument_footprint():
    """memory_stats: FSDP-sharded params must cost a fraction of the
    replicated argument footprint per device — an HBM-admission claim
    checked entirely at compile time. Reuses _compile_train_step so the
    batch-pinning fix (dispatch resharding would otherwise falsify the
    replicated control's argument count) applies here too."""
    from flashy_tpu.parallel import memory_stats

    mesh = make_mesh({"fsdp": 4, "data": 2})
    cfg = TransformerConfig(**_CFG)

    compiled, _ = _compile_train_step(mesh, cfg, batch=16, seq=32)
    sharded = memory_stats(compiled)
    if not sharded:
        pytest.skip("backend exposes no memory analysis")

    compiled_r, _ = _compile_train_step(
        mesh, cfg, batch=16, seq=32,
        param_specs=_replicated_specs(mesh, cfg, 16, 32))
    replicated = memory_stats(compiled_r)
    # params (and their optimizer/gradient mirrors) dominate the
    # arguments; fsdp=4 must cut them well below the replicated
    # footprint (some leaves — norms, biases — stay replicated)
    assert sharded["arguments"] < 0.6 * replicated["arguments"], (
        sharded, replicated)
    for stats in (sharded, replicated):
        assert stats["peak"] > 0 and stats["temp"] > 0
    # remat programs flow through the same accounting without error
    # (the temp DIRECTION is backend-specific: the CPU scheduler can
    # make recompute buffers outweigh the saved residuals at small
    # sizes, so no direction is asserted here)
    compiled_rm, _ = _compile_train_step(
        mesh, TransformerConfig(**dict(_CFG, remat=True)), batch=16, seq=32)
    remat = memory_stats(compiled_rm)
    assert remat["peak"] > 0 and remat["temp"] > 0
