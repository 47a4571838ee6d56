# What decides which kernel each benchmark cell compiles, pinned on the
# CPU from shapes alone: no OLMo-1B or dots array is ever built. A tile
# comes from the kernel's arguments or from a rule over shapes in the
# kernel's own module — never from the environment, a file or the home
# directory (a run-time tuner once replayed winners from the file the
# variable below names; the first test holds that door shut) — and the
# walks of the serving cells are the ones the ledger's numbers were
# measured with: a change of a constant in ops/paged_decode.py, or of a
# cell's `chunk`, shows here which cell's kernel it changes.
"""Tile choice per benchmark cell, from shapes, without a chip."""
import functools
import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from flashy_tpu.ops import attention, paged_decode, ssd_scan
from flashy_tpu.ops.paged_attention import cfg_pool_spec
from flashy_tpu.ops.paged_decode import Walk
from tests.test_spans import pallas_calls

ROOT = pathlib.Path(__file__).resolve().parent.parent
SERVING_CELLS = ("olmo1b-chat-closed", "olmo1b-fullctx-closed",
                 "dotsvlm1-doc-closed", "mimo25-doc16k-closed",
                 "nemotron3s-reason-closed")


# ----------------------------------------------------------------------
# (a) nothing outside the call decides a tile
# ----------------------------------------------------------------------
def _flash(grad):
    q = jax.ShapeDtypeStruct((1, 256, 2, 16), jnp.bfloat16)

    def forward(q, k, v):
        return attention.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else forward
    return jax.jit(fn).lower(q, q, q).as_text()


def _paged():
    batch, heads, dim, bs, entries = 2, 4, 8, 4, 3
    q = jax.ShapeDtypeStruct((batch, 1, heads, dim), jnp.float32)
    leaf = jax.ShapeDtypeStruct((7, bs, heads, dim), jnp.float32)
    table = jax.ShapeDtypeStruct((batch, entries), jnp.int32)
    positions = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    return jax.jit(lambda q, k, v, table, positions: (
        paged_decode.fused_paged_attention(
            q, {"k": k, "v": v}, table, positions, head_dim=dim,
            dtype=jnp.float32))).lower(q, leaf, leaf, table,
                                       positions).as_text()


def _ssd():
    batch, seq, heads, dim, dstate = 1, 64, 2, 16, 16
    cb = jax.ShapeDtypeStruct((batch, seq, heads, dstate), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((batch, seq, heads, dim), jnp.bfloat16)
    decay = jax.ShapeDtypeStruct((batch, seq, heads), jnp.float32)
    return jax.jit(lambda c, b, v, a: ssd_scan.ssd_chunked_scan(
        c, b, v, a, kernel="fused")).lower(cb, cb, v, decay).as_text()


# toy call -> the entry the deleted tuner would have replayed for it:
# its key after (kernel, jax, jaxlib, device_kind), and another tile
LOWERINGS = {
    "flash_fwd": (lambda: _flash(False), "flash",
                  (1, 256, 2, 16, True, "bfloat16", True), [128, 128]),
    "flash_bwd": (lambda: _flash(True), "flash_bwd",
                  (1, 256, 2, 16, True, "bfloat16"), [128, 128]),
    "paged_decode": (_paged, "paged_decode",
                     (2, 1, 4, 8, 4, 3, False, "float32"), 2),
    "ssd_scan": (_ssd, "ssd_scan", (1, 64, 2, 16, 16, "bfloat16"), 16),
}


@pytest.mark.parametrize("kernel", list(LOWERINGS))
def test_kernel_tiles_come_from_shapes_alone(kernel, tmp_path, monkeypatch):
    import jaxlib
    lower, name, parts, other_tile = LOWERINGS[kernel]
    monkeypatch.delenv("FLASHY_TPU_TUNE_CACHE", raising=False)
    plain = lower()
    key = "/".join(str(part) for part in (
        name, f"jax-{jax.__version__}", f"jaxlib-{jaxlib.__version__}",
        jax.devices()[0].device_kind) + parts)
    home = tmp_path / "home"
    winners = home / ".cache" / "flashy_tpu" / "attn_tune.json"
    winners.parent.mkdir(parents=True)
    winners.write_text(json.dumps({key: other_tile}))
    monkeypatch.setenv("HOME", str(home))
    monkeypatch.setenv("FLASHY_TPU_TUNE_CACHE", str(winners))
    assert lower() == plain


# ----------------------------------------------------------------------
# (b) the serving cells' walks are the recorded ones
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _cell(name):
    """(TransformerConfig, engine block) of a serving cell, from the
    benchmark's own files (read only) through its own config mapping."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    workload, = (w for w in manifest["workloads"] if w["name"] == name)
    config_file, = (c["file"] for c in manifest["configs"]
                    if c["name"] == workload["config"])
    config = json.loads((ROOT / config_file).read_text())
    engine = json.loads(
        (ROOT / "benchmarks" / "workloads" / f"{name}.json").read_text()
    )["engine"]
    harness = importlib.import_module(
        "benchmarks.harness."
        + config.get("harness", {}).get("model", "model"))
    return harness.transformer_config(config, attention="dense",
                                      dtype=jnp.bfloat16), engine


def _walk_and_vmem(cfg, engine, queries):
    """The walk of one read of `queries` rows a slot, as
    `DecodeEngine._kv_read_stats` asks for it, its VMEM estimate and
    the budget its module states."""
    block_size = engine["block_size"]
    entries = engine["max_seq_len"] // block_size
    if cfg.attn_kind == "mla":
        spec = cfg_pool_spec(cfg, 1, block_size, engine["kv_dtype"])
        c, kr = (jax.ShapeDtypeStruct(*spec[leaf]) for leaf in ("c", "kr"))
        walk = paged_decode.latent_call_walk(
            queries, cfg.num_heads, {"c": c, "kr": kr}, entries=entries)
        need = paged_decode._latent_vmem_estimate(
            walk.query_tile, cfg.num_heads, c.shape[-1], kr.shape[-1],
            block_size, walk.group, jnp.dtype(c.dtype).itemsize)
        return walk, need, paged_decode.LATENT_VMEM_LIMIT
    if cfg.attn_kind == "gqa":
        # a full-attention layer: those are the layers that walk
        from flashy_tpu.models import gqa
        kind = next(k for k in gqa.layer_kinds(cfg) if not k.window)
        dk, dv = gqa.key_dim(cfg), gqa.value_dim(cfg)
        walk = paged_decode.grouped_call_walk(
            cfg, kind, queries, block_size=block_size, entries=entries)
        need = paged_decode._grouped_vmem_estimate(
            walk.query_tile, paged_decode.head_parts(
                cfg.num_heads, kind.kv_heads, dk, dv, walk.flat),
            kind.kv_heads * dk, kind.kv_heads * dv, block_size, walk.group,
            jnp.dtype(cfg.dtype).itemsize)
        return walk, need, paged_decode.GROUPED_VMEM_LIMIT
    quantized = engine["kv_dtype"] == "int8"
    itemsize = jnp.dtype(cfg.dtype).itemsize
    walk = paged_decode.call_walk(
        queries, cfg.num_heads, cfg.head_dim, block_size=block_size,
        entries=entries, quantized=quantized, dtype=cfg.dtype)
    need = paged_decode._vmem_estimate(
        walk.query_tile, walk.head_block, cfg.head_dim, block_size,
        walk.group, flat=walk.flat, q_itemsize=itemsize,
        pool_itemsize=1 if quantized else itemsize)
    return walk, need, paged_decode.VMEM_BUDGET


# Recorded at commit a4433f0 (PR 28), the tree whose numbers the ledger
# holds: (group, head_block, query_tile, flat, dma). The OLMo cells: 16
# blocks = 256 keys a decode step, a 256-token slice in two query tiles
# of 128 against 8 blocks (PERF.md section 6, PR 26); the latent cell:
# 128 rows x 1,024 keys a decode step, 2,048 rows x 512 keys a slice
# step (PR 28). Steps: decode T=1, a whole slice T=`chunk`, the tail
# slice T=4 (the engine's `tail_bucket`), speculative verify T=5. The
# window/full cell (PR 32, the walk of its two full-attention layers):
# 64 rows x 1,024 keys a decode step, block-diagonal over the row's 768
# key lanes; a slice's 16 heads of a KV head x 64 positions = 1,024 rows
# a part against 1,024 keys (PERF.md section 6, PR 32). The Mamba-2 /
# latent-expert cell (PR 33, the walk of its one attention layer, 32
# query heads over 2 KV heads of 128 | 128: K and V blocks [16, 256]):
# the same split, 16 heads of a KV head x 64 positions a part.
OLMO_WALKS = {"decode": Walk(16, 16, 1, True, True),
              "slice": Walk(8, 16, 128, False, True),
              "tail": Walk(16, 16, 4, True, True),
              "verify": Walk(16, 16, 5, True, True)}
RECORDED = {
    "olmo1b-chat-closed": OLMO_WALKS,
    "olmo1b-fullctx-closed": OLMO_WALKS,
    "dotsvlm1-doc-closed": {"decode": Walk(64, 128, 1, True, True),
                            "slice": Walk(32, 128, 16, True, True),
                            "tail": Walk(64, 128, 4, True, True),
                            "verify": Walk(64, 128, 5, True, True)},
    "mimo25-doc16k-closed": {"decode": Walk(64, 64, 1, True, True),
                             "slice": Walk(64, 64, 64, False, True),
                             "tail": Walk(64, 64, 4, True, True),
                             "verify": Walk(64, 64, 5, True, True)},
    "nemotron3s-reason-closed": {"decode": Walk(64, 32, 1, True, True),
                                 "slice": Walk(64, 32, 64, False, True),
                                 "tail": Walk(64, 32, 4, True, True),
                                 "verify": Walk(64, 32, 5, True, True)},
}


@pytest.mark.parametrize("step", ["decode", "slice", "tail", "verify"])
@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_cell_walk_is_the_recorded_one(cell, step):
    cfg, engine = _cell(cell)
    queries = {"decode": 1, "slice": engine["chunk"],
               "tail": min(4, engine["chunk"]), "verify": 5}[step]
    walk, need, budget = _walk_and_vmem(cfg, engine, queries)
    assert walk == RECORDED[cell][step]
    assert need <= budget, (need, budget)


# ----------------------------------------------------------------------
# (c) the training cell's flash kernels run the swept schedule
# ----------------------------------------------------------------------
# olmo1b-train-2k: 8 sequences of 2,048 tokens, 16 heads of 128, in
# bfloat16. `ops.attention.TILES` (PERF.md section 6, PR 36): the
# forward is ONE masked 2048 x 2048 tile a head; the backward 1024 x
# 1024, four steps a head of which the one above the diagonal is
# skipped. Through PR 35 both were (128, 8, 8): 8,192 steps, 4,608 of
# them at work. kernel -> (the rule's name, grid, steps at work)
TRAIN_FLASH = {"flash_fwd": ("fwd", (8 * 16, 1, 1), 128),
               "flash_bwd_fused": ("bwd", (8 * 16, 2, 2), 384)}


@pytest.mark.parametrize("kernel", list(TRAIN_FLASH), ids=["fwd", "bwd"])
def test_train_cell_flash_tiles(kernel):
    x = jax.ShapeDtypeStruct((8, 2048, 16, 128), jnp.bfloat16)

    def loss(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr
    calls = {eqn.params["name"]: eqn for eqn in pallas_calls(jaxpr)}
    assert sorted(calls) == sorted(TRAIN_FLASH)
    name, grid, working = TRAIN_FLASH[kernel]
    assert tuple(calls[kernel].params["grid_mapping"].grid) == grid
    schedule = attention.flash_schedule(name, 8 * 16, 2048, 2048, 128, 2,
                                        True)
    tiles_q, tiles_k = 2048 // schedule.block_q, 2048 // schedule.block_k
    assert grid == ((8 * 16, tiles_q, tiles_k) if name == "fwd"
                    else (8 * 16, tiles_k, tiles_q))
    assert (schedule.steps, schedule.working) == (
        grid[0] * grid[1] * grid[2], working)
    assert schedule.vmem_bytes <= attention.VMEM_LIMIT
    # the benchmark's reader finds the calls by their [B*H, T, D]
    # operands: exactly three a forward call, more a backward call
    folded = [v.aval.shape for v in calls[kernel].invars].count(
        (8 * 16, 2048, 128))
    assert folded == 3 if name == "fwd" else folded > 3
    # a head's dQ is summed in VMEM: every output of the backward is
    # [B*H, T, D], none carries a k-tile axis ([B*H, nk, T, D] partials)
    assert [v.aval.shape for v in calls["flash_bwd_fused"].outvars] == (
        [(8 * 16, 2048, 128)] * 3)
    assert attention.fused_backward_fits(2048, 128, 2)


def test_a_dq_too_long_for_vmem_takes_the_split_backward():
    # one budget, `_vmem_estimate` against `VMEM_LIMIT`: at 65,536 rows a
    # head's float32 dQ (32 MiB) and its output block pass it at the
    # smallest tiles, so the backward is the two split kernels, at THEIR
    # schedule (no resident dQ to make room for), not at tiles halved to
    # nothing; at 32,768 rows the fused kernel still fits
    assert attention.fused_backward_fits(32768, 128, 2)
    assert not attention.fused_backward_fits(65536, 128, 2)
    x = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr
    grids = {eqn.params["name"]: tuple(eqn.params["grid_mapping"].grid)
             for eqn in pallas_calls(jaxpr)}
    assert grids == {"flash_fwd": (1, 32, 32), "flash_bwd_dq": (1, 64, 64),
                     "flash_bwd_dkv": (1, 64, 64)}
    schedule = attention.flash_schedule("bwd_split", 1, 65536, 65536, 128,
                                        2, True)
    assert (schedule.block_q, schedule.block_k) == (1024, 1024)
    assert schedule.vmem_bytes <= attention.VMEM_LIMIT


# ----------------------------------------------------------------------
# (d) `kernel: auto` in a serving cell is the fused walk on a TPU
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_cells_resolve_auto_to_the_fused_walk(cell, monkeypatch):
    cfg, engine = _cell(cell)
    assert engine["kernel"] == "auto"
    assert paged_decode.default_kernel(cfg, engine["block_size"]) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_decode.fused_kernel_unsupported_reason(
        cfg, engine["block_size"]) is None
    assert paged_decode.default_kernel(cfg, engine["block_size"]) == "fused"


def test_the_grouped_cell_resolves_auto_to_the_walk_on_a_tpu(monkeypatch):
    # the window/full cell: `auto` is the walk of its full-attention
    # layers on a TPU (4 KV heads of 192 | 128 are 768 | 512 lanes, whole
    # tiles in blocks of 16 bf16 rows), the gather on the CPU; the same
    # config in blocks the kernel cannot copy is refused by its shape
    cfg, engine = _cell("mimo25-doc16k-closed")
    assert engine["kernel"] == "auto"
    assert paged_decode.default_kernel(cfg, engine["block_size"]) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_decode.fused_kernel_unsupported_reason(
        cfg, engine["block_size"]) is None
    assert paged_decode.default_kernel(cfg, engine["block_size"]) == "fused"
    reason = paged_decode.fused_kernel_unsupported_reason(cfg, 8)
    assert "[8, 4 KV heads of 192 | 128] under 64 query heads" in reason
    assert paged_decode.default_kernel(cfg, 8) == "gather"
    # its pool leaves are whole 128-lane rows as stored
    from flashy_tpu.ops.paged_attention import layer_pool_specs, ring_blocks
    ring = ring_blocks(cfg.window, engine["chunk"], engine["block_size"])
    specs = layer_pool_specs(cfg, 9, engine["block_size"], "model",
                             slots=engine["slots"], ring=ring)
    assert [spec["k"][0] for spec in specs] == (
        [(9, 16, 768)] + [(33, 640, 1536)] * 5 + [(9, 16, 768)])
    assert [spec["v"][0][-1] for spec in specs] == [512] + [1024] * 5 + [512]


def test_the_recurrent_cell_keeps_a_state_a_slot_beside_its_one_paged_layer():
    # the Mamba-2 / latent-expert cell: five layers hold a float32 state
    # [128, 64, 128] and a 3-row conv tail a slot (entry 0 the sentinel),
    # five expert layers hold nothing, the one attention layer pages
    # whole-tile blocks; the slice's scan runs the swept chunk and the
    # decode update the swept heads a step
    from flashy_tpu.ops.paged_attention import (block_bytes, layer_pool_specs,
                                                state_bytes)
    cfg, engine = _cell("nemotron3s-reason-closed")
    assert cfg.layer_pattern == "MEMEMEMEM*E"
    specs = layer_pool_specs(cfg, 9, engine["block_size"], "model",
                             slots=engine["slots"])
    shapes = [{name: leaf[0] for name, leaf in spec.items()}
              for spec in specs]
    state = {"state": (129, 128, 64, 128), "conv": (129, 3, 10240)}
    assert shapes == [state, {}] * 4 + [state, {"k": (9, 16, 256),
                                                "v": (9, 16, 256)}, {}]
    assert specs[0]["state"][1] == jnp.float32
    assert block_bytes(cfg, engine["block_size"]) == 16 * 1024
    assert state_bytes(cfg, 0) == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert state_bytes(cfg, engine["slots"]) == 129 * state_bytes(cfg, 0)
    assert ssd_scan.default_chunk(engine["chunk"]) in ssd_scan.CHUNK_CANDIDATES
    assert cfg.ssm_heads % ssd_scan.UPDATE_HEADS == 0
