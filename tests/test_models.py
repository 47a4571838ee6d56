# Tests for the model zoo: shapes, dtypes, and the flagship guarantee —
# a TransformerLM train step sharded dp+tp+sp over the mesh produces the
# same loss and updates as the replicated single-device computation.
import jax
import jax.numpy as jnp
import pytest
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from flashy_tpu.models import (MLP, TransformerConfig, TransformerLM, resnet18,
                               resnet50, transformer_shardings)
from flashy_tpu.parallel import make_mesh, shard_batch


def test_mlp_shapes():
    model = MLP([8, 3])
    params = model.init(jax.random.PRNGKey(0), jnp.ones((2, 4)))
    out = model.apply(params, jnp.ones((5, 4)))
    assert out.shape == (5, 3)


@pytest.mark.slow
def test_resnet18_forward_and_batchstats():
    model = resnet18(num_classes=10)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 32, 32, 3)),
                           train=False)
    assert "batch_stats" in variables
    out, mutated = model.apply(variables, jnp.ones((2, 32, 32, 3)), train=True,
                               mutable=["batch_stats"])
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    # train step updated the running statistics
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_resnet50_param_count_magnitude():
    model = resnet50(num_classes=1000, small_inputs=False)
    variables = jax.eval_shape(
        lambda key, x: model.init(key, x, train=False),
        jax.random.PRNGKey(0), jnp.ones((1, 224, 224, 3)))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(variables["params"]))
    # torchvision resnet50 has ~25.6M params
    assert 20e6 < n_params < 30e6


def _tiny_cfg(**kwargs):
    defaults = dict(vocab_size=64, dim=32, num_layers=2, num_heads=4,
                    attention="dense")
    defaults.update(kwargs)
    return TransformerConfig(**defaults)


def test_transformer_forward_shapes():
    cfg = _tiny_cfg()
    model = TransformerLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 8), jnp.int32))
    logits = model.apply(variables, jnp.ones((3, 8), jnp.int32))
    assert logits.shape == (3, 8, 64)
    assert logits.dtype == jnp.float32  # f32 head for stable loss


def test_transformer_causality():
    cfg = _tiny_cfg()
    model = TransformerLM(cfg)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 8)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens))
    base = model.apply(variables, jnp.asarray(tokens))
    # changing a future token must not affect past logits
    perturbed = tokens.copy()
    perturbed[0, -1] = (perturbed[0, -1] + 1) % 64
    out = model.apply(variables, jnp.asarray(perturbed))
    np.testing.assert_allclose(np.asarray(base[0, :-1]), np.asarray(out[0, :-1]),
                               atol=1e-5)


def test_transformer_remat_matches():
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 8)), jnp.int32)
    base_model = TransformerLM(_tiny_cfg())
    variables = base_model.init(jax.random.PRNGKey(0), tokens)
    remat_model = TransformerLM(_tiny_cfg(remat=True))
    np.testing.assert_allclose(
        np.asarray(base_model.apply(variables, tokens)),
        np.asarray(remat_model.apply(variables, tokens)), atol=1e-5)


@pytest.mark.slow
def test_transformer_sharded_step_matches_replicated():
    # The flagship oracle: full train step with dp=2, tensor=2, seq=2
    # sharding (ring attention) == replicated dense computation.
    mesh = make_mesh({"data": 2, "tensor": 2, "seq": 2})
    cfg = _tiny_cfg(attention="ring")
    model = TransformerLM(cfg, mesh=mesh)
    tokens = np.random.default_rng(2).integers(0, 64, (8, 16)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 16), jnp.int32))

    specs = transformer_shardings(variables)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    batch = shard_batch(jnp.asarray(tokens), mesh, batch_axes=("data",))

    def loss_fn(variables, tokens):
        logits = model.apply(variables, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)

    ref_model = TransformerLM(_tiny_cfg(attention="dense"))
    ref_loss, ref_grads = jax.value_and_grad(
        lambda v, t: optax.softmax_cross_entropy_with_integer_labels(
            ref_model.apply(v, t)[:, :-1], t[:, 1:]).mean())(variables, jnp.asarray(tokens))

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-3)
    flat_a = jax.tree_util.tree_leaves(grads)
    flat_b = jax.tree_util.tree_leaves(ref_grads)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b, dtype=np.float32),
                                   rtol=5e-2, atol=3e-3)


def test_transformer_shardings_patterns():
    cfg = _tiny_cfg()
    model = TransformerLM(cfg)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.ones((1, 8), jnp.int32))
    specs = transformer_shardings(variables)
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    by_path = {"/".join(str(getattr(p, "key", p)) for p in path): spec
               for path, spec in flat}
    embed = [s for p, s in by_path.items() if "embed" in p]
    assert embed and all(s == P("tensor", "fsdp") for s in embed)
    norms = [s for p, s in by_path.items() if "norm" in p]
    assert norms and all(s == P() for s in norms)


def test_transformer_dropout_active_only_in_train():
    cfg = _tiny_cfg(dropout=0.5)
    model = TransformerLM(cfg)
    tokens = jnp.ones((2, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    eval_a = model.apply(variables, tokens)
    eval_b = model.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(eval_a), np.asarray(eval_b))
    train_a = model.apply(variables, tokens, train=True,
                          rngs={"dropout": jax.random.PRNGKey(1)})
    train_b = model.apply(variables, tokens, train=True,
                          rngs={"dropout": jax.random.PRNGKey(2)})
    assert not np.allclose(np.asarray(train_a), np.asarray(train_b))


def test_transformer_max_seq_len_enforced():
    cfg = _tiny_cfg(max_seq_len=8)
    model = TransformerLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    import pytest
    with pytest.raises(ValueError):
        model.apply(variables, jnp.ones((1, 16), jnp.int32))


@pytest.mark.slow
def test_moe_expert_parallel_matches_replicated():
    mesh = make_mesh({"data": 2, "expert": 2, "tensor": 2})
    cfg = _tiny_cfg(moe_experts=4, moe_top_k=2)
    model = TransformerLM(cfg, mesh=mesh)
    tokens = np.random.default_rng(3).integers(0, 64, (8, 16)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:2]))
    variables = {"params": variables["params"]}  # drop sown collections

    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), transformer_shardings(variables),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    assert params["params"]["block_0"]["moe"]["w_up"].sharding.spec[0] == "expert"
    batch = shard_batch(jnp.asarray(tokens), mesh, batch_axes=("data",))

    def loss_fn(variables, tokens):
        logits, mutated = model.apply(variables, tokens, mutable=["losses"])
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()
        from flashy_tpu.models import moe_aux_loss
        return ce + 0.01 * moe_aux_loss(mutated)

    sharded = float(jax.jit(loss_fn)(params, batch))
    replicated = float(loss_fn(variables, jnp.asarray(tokens)))
    assert abs(sharded - replicated) < 5e-3

    grads = jax.jit(jax.grad(loss_fn))(params, batch)
    norms = [float(jnp.linalg.norm(g)) for g in
             jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    # router and experts actually receive gradient
    g_router = grads["params"]["block_0"]["moe"]["router"]["kernel"]
    assert float(jnp.abs(g_router).max()) > 0


def test_moe_routing_no_slot_collisions_and_capacity():
    # Assert on the model's ACTUAL dispatch tensor: each (expert, slot)
    # receives at most one token even with top_k=2, and capacity scales
    # with top_k.
    from flashy_tpu.models.moe import MoEMLP
    model = MoEMLP(dim=8, hidden=16, num_experts=2, top_k=2,
                   capacity_factor=2.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 16, 8)),
                    jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    _, mutated = model.apply(variables, x,
                             mutable=["intermediates", "losses"])
    (dispatch,) = mutated["intermediates"]["dispatch"]  # [N, E, C]
    occupancy = np.asarray(dispatch).sum(axis=0)        # tokens per slot
    assert occupancy.max() <= 1.0  # no slot collisions
    n_tokens, capacity = 16, dispatch.shape[-1]
    assert capacity == int(2.0 * n_tokens * 2 / 2)  # scales with top_k
    # with generous capacity, every token lands top_k times
    assert np.asarray(dispatch).sum() == n_tokens * 2


def test_scan_layers_stacked_params_and_forward():
    cfg = _tiny_cfg(scan_layers=True)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 64, (2, 8)),
                         jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    qkv = variables["params"]["blocks"]["block"]["attn"]["qkv"]["kernel"]
    assert qkv.shape[0] == cfg.num_layers  # stacked leading dim
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 8, 64)
    # causal: future token change leaves past logits untouched
    perturbed = tokens.at[0, -1].set((tokens[0, -1] + 1) % 64)
    out = model.apply(variables, perturbed)
    np.testing.assert_allclose(np.asarray(logits[0, :-1]),
                               np.asarray(out[0, :-1]), atol=1e-5)


@pytest.mark.slow
def test_pipelined_apply_matches_scan_forward():
    from jax.sharding import NamedSharding
    from flashy_tpu.models.pipelined import pipelined_apply
    cfg = _tiny_cfg(scan_layers=True, num_layers=4)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.default_rng(6).integers(0, 64, (8, 16)),
                         jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:2])
    direct = model.apply(variables, tokens)

    mesh = make_mesh({"pipe": 2, "data": 4})
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), transformer_shardings(variables),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    piped = jax.jit(lambda v, t: pipelined_apply(
        model, v, t, mesh=mesh, num_microbatches=4))(params, tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(direct),
                               rtol=1e-4, atol=1e-4)

    def loss_pipe(v, t):
        logits = pipelined_apply(model, v, t, mesh=mesh, num_microbatches=4)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean()

    def loss_direct(v, t):
        logits = model.apply(v, t)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean()

    g_pipe = jax.jit(jax.grad(loss_pipe))(params, tokens)
    g_direct = jax.grad(loss_direct)(variables, tokens)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_direct)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)


@pytest.mark.slow
def test_moe_sorted_dispatch_matches_einsum():
    from flashy_tpu.models.moe import MoEMLP
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 16, 8)),
                    jnp.float32)
    dense = MoEMLP(dim=8, hidden=16, num_experts=4, top_k=2,
                   capacity_factor=1.0, dtype=jnp.float32)
    sorted_ = MoEMLP(dim=8, hidden=16, num_experts=4, top_k=2,
                     capacity_factor=1.0, dtype=jnp.float32,
                     dispatch="sorted")
    variables = dense.init(jax.random.PRNGKey(0), x)
    variables = {"params": variables["params"]}  # drop stale sown state
    out_a, mut_a = dense.apply(variables, x, mutable=["losses"])
    out_b, mut_b = sorted_.apply(variables, x, mutable=["losses"])
    # identical routing and keep decisions -> near-identical outputs
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-5, atol=1e-5)
    (aux_a,) = jax.tree_util.tree_leaves(mut_a["losses"])
    (aux_b,) = jax.tree_util.tree_leaves(mut_b["losses"])
    np.testing.assert_allclose(float(aux_a), float(aux_b), rtol=1e-6)

    # gradients flow through the sorted path too
    def loss(v):
        return (sorted_.apply(v, x, mutable=["losses"])[0] ** 2).sum()

    grads = jax.grad(loss)(variables)
    g_up = grads["params"]["w_up"]
    assert float(jnp.abs(g_up).max()) > 0


@pytest.mark.slow
def test_pipelined_apply_moe_matches_unpipelined():
    # MoE in the pipeline: expert outputs are exact (capacity high enough
    # that nothing drops); the aux loss is the microbatch-mean estimator.
    from jax.sharding import NamedSharding
    from flashy_tpu.models import moe_aux_loss
    from flashy_tpu.models.pipelined import pipelined_apply
    cfg = _tiny_cfg(scan_layers=True, num_layers=4, moe_experts=4,
                    moe_top_k=2, moe_capacity_factor=8.0)
    model = TransformerLM(cfg)
    tokens = jnp.asarray(np.random.default_rng(9).integers(0, 64, (8, 16)),
                         jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:2])
    variables = {"params": variables["params"]}
    direct, mutated = model.apply(variables, tokens, mutable=["losses"])
    direct_aux = moe_aux_loss(mutated)

    mesh = make_mesh({"pipe": 2, "data": 2, "expert": 2})
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), transformer_shardings(variables),
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(variables, shardings)
    piped, aux = jax.jit(lambda v, t: pipelined_apply(
        model, v, t, mesh=mesh, num_microbatches=4))(params, tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(direct),
                               rtol=1e-4, atol=1e-4)
    # aux: mean over microbatches of per-microbatch values; same scale
    # as the full-batch value, not bit-equal.
    assert np.isfinite(float(aux))
    assert 0.2 * float(direct_aux) < float(aux) < 5.0 * float(direct_aux)

    # gradients flow through the pipelined MoE loss
    def loss(v, t):
        logits, aux = pipelined_apply(model, v, t, mesh=mesh,
                                      num_microbatches=4)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean()
        return ce + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(params, tokens)
    gnorm = optax.global_norm(grads)
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.slow
def test_moe_dropless_matches_einsum_and_drops_nothing():
    from flashy_tpu.models.moe import MoEMLP
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))

    def run(dispatch, cf):
        module = MoEMLP(dim=32, hidden=64, num_experts=4, top_k=2,
                        capacity_factor=cf, dtype=jnp.float32,
                        dispatch=dispatch)
        variables = {"params": module.init(jax.random.PRNGKey(0), x)["params"]}
        out, _ = module.apply(variables, x, mutable=["losses"])
        return variables, out

    # capacity high enough that einsum drops nothing -> exact agreement
    v_e, out_e = run("einsum", cf=8.0)
    _, out_d = run("dropless", cf=8.0)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_e),
                               rtol=1e-4, atol=1e-5)

    # tiny capacity: einsum drops tokens (outputs differ), dropless is
    # invariant to capacity_factor by construction
    _, out_e_tiny = run("einsum", cf=0.25)
    _, out_d_tiny = run("dropless", cf=0.25)
    np.testing.assert_allclose(np.asarray(out_d_tiny), np.asarray(out_d),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(out_e_tiny - out_e).max()) > 1e-3

    # gradients flow through the grouped matmuls (megablox custom VJP)
    def loss(params):
        module = MoEMLP(dim=32, hidden=64, num_experts=4, top_k=2,
                        dtype=jnp.float32, dispatch="dropless")
        out, _ = module.apply({"params": params}, x, mutable=["losses"])
        return (out ** 2).sum()

    gnorm = optax.global_norm(jax.grad(loss)(v_e["params"]))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.slow
def test_moe_dropless_ep_matches_dropless():
    # The expert-parallel dropless hybrid (capacity-bounded a2a between
    # expert shards + grouped matmul on each local slab) must agree with
    # replicated dropless when capacity is generous (nothing drops) —
    # same params, same routing rule, same gates.
    from flashy_tpu.models.moe import MoEMLP
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(4, 8, 32)).astype(np.float32))
    mesh = make_mesh({"expert": 2, "data": 4})

    def build(dispatch, cf):
        return MoEMLP(dim=32, hidden=64, num_experts=4, top_k=2,
                      capacity_factor=cf, dtype=jnp.float32,
                      dispatch=dispatch, mesh=mesh)

    ref_mod = build("dropless", cf=8.0)
    variables = {"params": ref_mod.init(jax.random.PRNGKey(0), x)["params"]}
    out_ref, aux_ref = ref_mod.apply(variables, x, mutable=["losses"])

    ep_mod = build("dropless_ep", cf=8.0)
    out_ep, aux_ep = ep_mod.apply(variables, x, mutable=["losses"])
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_ref),
                               rtol=1e-4, atol=1e-5)
    # identical aux loss (densities pmean over all tokens)
    from flashy_tpu.models import moe_aux_loss
    np.testing.assert_allclose(float(moe_aux_loss(aux_ep)),
                               float(moe_aux_loss(aux_ref)), rtol=1e-5)

    # tiny capacity: the shard exchange drops overflow (Switch behavior)
    out_tiny, _ = build("dropless_ep", cf=0.1).apply(variables, x,
                                                     mutable=["losses"])
    assert float(jnp.abs(out_tiny - out_ref).max()) > 1e-3

    # gradients flow end-to-end (a2a + scatter + gmm custom VJP) and the
    # whole thing jits over the mesh
    def loss(params, x):
        out, mutated = build("dropless_ep", cf=8.0).apply(
            {"params": params}, x, mutable=["losses"])
        return (out ** 2).sum() + 0.01 * moe_aux_loss(mutated)

    grads = jax.jit(jax.grad(loss))(variables["params"], x)
    gnorm = optax.global_norm(grads)
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0
    g_router = grads["router"]["kernel"]
    assert float(jnp.abs(g_router).max()) > 0

    # mesh is mandatory for this mode
    with pytest.raises(ValueError):
        MoEMLP(dim=32, hidden=64, num_experts=4, dispatch="dropless_ep",
               dtype=jnp.float32).init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
@pytest.mark.slow
def test_remat_policy_matches_full_remat(policy):
    # Selective remat changes what is SAVED, never the math: loss and
    # grads must match the full-remat config bit-for-bit (identical
    # graph modulo recompute scheduling) at f32 tolerance.
    import optax
    from flashy_tpu.models import TransformerConfig, TransformerLM

    tokens = jnp.asarray(
        np.random.default_rng(11).integers(0, 64, (2, 32)), jnp.int32)

    def loss_and_grads(remat_policy):
        cfg = TransformerConfig(vocab_size=64, dim=64, num_layers=2,
                                num_heads=2, attention="dense", remat=True,
                                remat_policy=remat_policy, dtype=jnp.float32)
        model = TransformerLM(cfg)
        params = model.init(jax.random.PRNGKey(0), tokens)

        def loss_fn(params):
            logits = model.apply(params, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]).mean()

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        return loss, grads

    loss_full, grads_full = loss_and_grads("full")
    loss_pol, grads_pol = loss_and_grads(policy)
    np.testing.assert_allclose(float(loss_full), float(loss_pol), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        grads_full, grads_pol)


def test_remat_policy_unknown_raises():
    from flashy_tpu.models import TransformerConfig, TransformerLM
    cfg = TransformerConfig(vocab_size=64, dim=64, num_layers=1, num_heads=2,
                            remat=True, remat_policy="bogus")
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat_policy"):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)


def test_transformer_segment_mask_isolates_packed_docs():
    # A packed row (two docs + padding, datapipe.SequencePacker layout)
    # must produce, at each doc's positions, exactly the logits the doc
    # gets when presented alone: the segment-aware mask makes packed
    # neighbours invisible.
    cfg = _tiny_cfg(dtype=jnp.float32)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    doc_a = jnp.asarray(rng.integers(1, 64, 5), jnp.int32)
    doc_b = jnp.asarray(rng.integers(1, 64, 7), jnp.int32)
    length = 16
    tokens = jnp.zeros((1, length), jnp.int32)
    tokens = tokens.at[0, :5].set(doc_a).at[0, 5:12].set(doc_b)
    segments = jnp.asarray([[1] * 5 + [2] * 7 + [0] * 4], jnp.int32)
    positions = jnp.asarray([list(range(5)) + list(range(7)) + [0] * 4],
                            jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    packed = model.apply(variables, tokens, positions=positions,
                         segment_ids=segments)
    alone_a = model.apply(variables, doc_a[None])
    alone_b = model.apply(variables, doc_b[None])
    np.testing.assert_allclose(np.asarray(packed[0, :5]),
                               np.asarray(alone_a[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(packed[0, 5:12]),
                               np.asarray(alone_b[0]), atol=1e-5)
    # without segment_ids the same inputs DO leak across the boundary
    unmasked = model.apply(variables, tokens, positions=positions)
    assert not np.allclose(np.asarray(unmasked[0, 5:12]),
                           np.asarray(alone_b[0]), atol=1e-3)


def test_transformer_segment_mask_scan_layers():
    cfg = _tiny_cfg(dtype=jnp.float32, scan_layers=True)
    model = TransformerLM(cfg)
    tokens = jnp.asarray([[3, 4, 5, 6, 7, 8]], jnp.int32)
    segments = jnp.asarray([[1, 1, 1, 2, 2, 0]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 0, 1, 0]], jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(variables, tokens, positions=positions,
                         segment_ids=segments)
    alone = model.apply(variables, tokens[:, :3])
    np.testing.assert_allclose(np.asarray(logits[0, :3]),
                               np.asarray(alone[0]), atol=1e-5)


def test_transformer_segment_ids_rejects_ring_attention():
    model = TransformerLM(_tiny_cfg(attention="ring"))
    tokens = jnp.ones((1, 8), jnp.int32)
    segs = jnp.ones((1, 8), jnp.int32)
    dense = TransformerLM(_tiny_cfg(dtype=jnp.float32))
    variables = dense.init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="segment_ids is not supported"):
        model.init(jax.random.PRNGKey(0), tokens, segment_ids=segs)
    # dense path still accepts packed inputs
    dense.apply(variables, tokens, segment_ids=segs)
