# The sweep behind the body of `ops/ssd_scan.py:_update_body`, the
# decode run's state update (PERF.md section 6): milliseconds one
# layer's update takes at one shape, for every variant of the body, for
# floors that only copy, and for XLA's gather form, each checked against
# that gather form. Run it on one TPU chip; no cell imports it.
#
#   python3 tools/ssd_update_sweep.py \
#       --module build/probe/parent/flashy_tpu/ops/ssd_scan.py
#
# `--rehearse --shape 4,6,8,64,128,2 --heads 4,8 --calls 2` proves the
# script on the CPU (interpret mode; the times mean nothing).
#
# The shape is the cell `nemotron3s-reason-closed`'s: 128 rows against a
# table of 129 entries of 128 heads x [64, 128] float32, b and c by 8
# groups, two rows parked at the sentinel entry 0. Every call donates the
# table, so the kernels alias it as the model does and a time is the
# update in place; the time is the median over rounds of queued calls.
#
# Variants (`VARIANTS`): `decay` as columns [P, heads] lane-broadcast per
# head (the earlier body) or as scalars in SMEM; `v` as columns, or
# as lane-dense rows whose 128 values a transpose turns into
# lane-broadcast columns; `y` selected into a [P, heads] tile per head
# (the earlier body), stored a column at a time, or written lane-dense,
# [heads x P / 128, 128], by a transpose and a sublane sum or by a
# float32 product on the MXU at precision HIGHEST. `module` rows time
# `_update_call` of a copy of `ops/ssd_scan.py` (the committed one, and
# each `--module`). Two floors do no arithmetic, so their errors are the
# update's size: `floor` has the variants' grid, copies and alias;
# `dma_floor` makes its own copies of a row's whole entry, in several
# pieces and with several entries in flight.
"""Sweep the SSD state update's body on the chip; XLA's form beside."""
import argparse
import functools
import importlib.util
import json
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

LANES = 128
VMEM_LIMIT = 100 * 2**20
# (decay, v, y) of each candidate body, in the order the sweep ranks
# the reckoning: the decay first, then y, then v
VARIANTS = (
    ("smem", "columns", "select"),
    ("smem", "columns", "column"),
    ("smem", "columns", "rows_xlu"),
    ("smem", "columns", "rows_mxu"),
    ("smem", "rows", "rows_xlu"),
    ("smem", "rows", "rows_mxu"),
    ("columns", "columns", "rows_xlu"),
)


def load(path):
    spec = importlib.util.spec_from_file_location(
        "swept_" + pathlib.Path(path).stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _body(rows_ref, decay_ref, v_ref, b_ref, c_ref, state_ref, y_ref,
          out_ref, *, heads, share, decay, v, y):
    del rows_ref  # consumed by the index maps
    dim = state_ref.shape[2]
    per_row = LANES // dim  # heads a lane-dense row of v or y holds
    if decay == "columns":
        decay_cols = decay_ref[0, 0]                       # [P, heads]
    if v == "columns":
        v_cols = v_ref[0, 0]                               # [P, heads]
    lane = jax.lax.broadcasted_iota(jnp.int32, (dim, heads), 1)
    y_tile = jnp.zeros((dim, heads), jnp.float32)
    products = []
    for j in range(heads):
        group = j // share
        b_row, c_row = b_ref[0, group], c_ref[0, group]    # [1, N]
        a = (decay_ref[0, 0, j] if decay == "smem"
             else decay_cols[:, j:j + 1])
        if v == "columns":
            v_col = v_cols[:, j:j + 1]
        else:
            if j % per_row == 0:  # one transpose a row of `per_row` heads
                v_row = v_ref[0, j // per_row:j // per_row + 1, :]
                v_t = jnp.transpose(jnp.broadcast_to(v_row, (LANES, LANES)))
            part = j % per_row
            v_col = v_t[part * dim:(part + 1) * dim]       # [P, 128]
        new = a * state_ref[0, j] + v_col * b_row          # [P, N]
        out_ref[0, j] = new
        if y == "select":
            y_tile = jnp.where(lane == j, jnp.sum(new * c_row, axis=1,
                                                  keepdims=True), y_tile)
        elif y == "column":
            y_ref[0, 0, :, j:j + 1] = jnp.sum(new * c_row, axis=1,
                                              keepdims=True)
        elif y == "rows_xlu":
            products.append(new * c_row)
            if len(products) == per_row:
                pair = jnp.concatenate(products, axis=0)   # [128, N]
                y_ref[0, j // per_row:j // per_row + 1, :] = jnp.sum(
                    jnp.transpose(pair), axis=0, keepdims=True)
                products = []
        else:  # rows_mxu: the row's heads share one group's c
            products.append(new)
            if len(products) == per_row:
                pair = jnp.concatenate(products, axis=0)   # [128, N]
                got = jax.lax.dot_general(
                    jnp.broadcast_to(c_row, (8, c_row.shape[1])), pair,
                    (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)   # [8, 128]
                y_ref[0, j // per_row:j // per_row + 1, :] = got[:1]
                products = []
    if y == "select":
        y_ref[0, 0] = y_tile


def _copy_body(rows_ref, decay_ref, v_ref, b_ref, c_ref, state_ref, y_ref,
               out_ref):
    """The floor: the same grid, copies and alias; no arithmetic."""
    del rows_ref, decay_ref, v_ref, b_ref, c_ref
    out_ref[...] = state_ref[...]
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _dma_floor_body(rows_ref, state_hbm, y_ref, out_hbm, buf, sem_in,
                    sem_out, *, slots, chunks):
    """The floor again with the kernel's own copies: a row's whole entry
    in `chunks` copies, `slots` entries in VMEM, so up to `slots - 1`
    rows' write-backs are in flight beside the next row's read."""
    del state_hbm  # the same buffer as out_hbm (aliased)
    i, n = pl.program_id(0), pl.num_programs(0)
    part = buf.shape[1] // chunks

    def copies(row, slot, read):
        entry = rows_ref[row]
        out = []
        for k in range(chunks):
            hbm = out_hbm.at[entry, pl.ds(k * part, part)]
            vmem = buf.at[slot, pl.ds(k * part, part)]
            out.append(pltpu.make_async_copy(
                hbm, vmem, sem_in.at[slot, k]) if read else
                pltpu.make_async_copy(vmem, hbm, sem_out.at[slot, k]))
        return out

    @pl.when(i == 0)
    def _():
        for cp in copies(0, 0, True):
            cp.start()

    @pl.when(i + 1 >= slots)
    def _():
        for cp in copies(i + 1 - slots, (i + 1) % slots, False):
            cp.wait()

    @pl.when(i + 1 < n)
    def _():
        for cp in copies(i + 1, (i + 1) % slots, True):
            cp.start()

    for cp in copies(i, i % slots, True):
        cp.wait()
    for cp in copies(i, i % slots, False):
        cp.start()
    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(i == n - 1)
    def _():
        for back in range(min(slots - 1, 8)):
            @pl.when(i - back >= 0)
            def _():
                for cp in copies(i - back, (i - back) % slots, False):
                    cp.wait()


def dma_floor(state, rows, decay_in, v_in, b, c, *, slots, chunks,
              interpret):
    batch, heads, dim = v_in.shape
    dstate = state.shape[-1]
    y_out, state = pl.pallas_call(
        functools.partial(_dma_floor_body, slots=slots, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, heads * dim // LANES, LANES),
                                    lambda bi, rows: (bi, 0, 0)),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((slots, heads, dim, dstate),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((slots, chunks)),
                            pltpu.SemaphoreType.DMA((slots, chunks))]),
        out_shape=[jax.ShapeDtypeStruct((batch, heads * dim // LANES, LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_state_update",
    )(rows, state)
    return y_out.reshape(batch, heads, dim), state


def candidate(state, rows, decay_in, v_in, b, c, *, hb, decay, v, y,
              interpret, copy_only=False):
    """One variant's pallas_call: grid (row, head block), the entry
    named by the prefetched `rows`, the table aliased in place."""
    batch, heads, dim = v_in.shape
    groups, dstate = b.shape[1], b.shape[2]
    share = heads // groups
    blocks, per_block = heads // hb, max(1, hb // share)
    lane_rows = hb * dim // LANES

    def columns(x):  # [B, H, P] -> [B, H / hb, P, hb]
        return jnp.swapaxes(x.reshape(batch, blocks, hb, dim), 2, 3)

    def group_index(bi, hi, rows):
        return (bi, hi * hb // (share * per_block), 0, 0)

    column = pl.BlockSpec((1, 1, dim, hb), lambda bi, hi, rows: (bi, hi, 0, 0))
    lane_dense = pl.BlockSpec((1, lane_rows, LANES),
                              lambda bi, hi, rows: (bi, hi, 0))
    if decay == "smem":
        # one [1, hb] row a block: a block's last two dimensions are
        # whole tiles or the array's own
        decay_spec = pl.BlockSpec(
            (1, 1, hb), lambda bi, hi, rows: (bi * blocks + hi, 0, 0),
            memory_space=pltpu.SMEM)
        decay_arg = decay_in.reshape(batch * blocks, 1, hb)
    else:
        decay_spec = column
        decay_arg = columns(jnp.broadcast_to(decay_in[:, :, None],
                                             v_in.shape))
    if v == "columns":
        v_spec, v_arg = column, columns(v_in)
    else:
        v_spec, v_arg = lane_dense, v_in.reshape(batch, -1, LANES)
    dense_y = y.startswith("rows")
    y_spec = lane_dense if dense_y else column
    y_shape = ((batch, heads * dim // LANES, LANES) if dense_y
               else (batch, blocks, dim, hb))
    group = pl.BlockSpec((1, per_block, 1, dstate), group_index)
    entry = pl.BlockSpec((1, hb, dim, dstate),
                         lambda bi, hi, rows: (rows[bi], hi, 0, 0))
    body = (_copy_body if copy_only else functools.partial(
        _body, heads=hb, share=share, decay=decay, v=v, y=y))
    y_out, state = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, blocks),
            in_specs=[decay_spec, v_spec, group, group, entry],
            out_specs=[y_spec, entry]),
        out_shape=[jax.ShapeDtypeStruct(y_shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        # 128 heads a step hold 16 MB of double-buffered entries
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_state_update",
    )(rows, decay_arg, v_arg, b[:, :, None, :], c[:, :, None, :], state)
    if dense_y:
        return y_out.reshape(batch, heads, dim), state
    return jnp.swapaxes(y_out, 2, 3).reshape(batch, heads, dim), state


def operands(shape, seed):
    """The table and one token a row at `shape` (rows, entries, heads,
    head_dim, state, groups), float32; two rows parked at entry 0."""
    rows_n, entries, heads, dim, dstate, groups = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    table = jax.random.normal(keys[0], (entries, heads, dim, dstate))
    order = jax.random.permutation(keys[1], entries - 1)[:rows_n] + 1
    rows = order.astype(jnp.int32).at[:2].set(0)
    decay = jax.nn.sigmoid(jax.random.normal(keys[2], (rows_n, heads)))
    v = jax.random.normal(keys[3], (rows_n, heads, dim))
    b = jax.random.normal(keys[4], (rows_n, groups, dstate))
    c = jax.random.normal(keys[5], (rows_n, groups, dstate))
    return table, rows, decay, v, b, c


def errors(got, want, rows):
    """Largest error against the gather form: y of the rows that advance
    and every entry but the sentinel (which parked rows race for)."""
    live = np.asarray(rows) != 0
    y_err = float(jnp.max(jnp.abs(got[0][live] - want[0][live])))
    s_err = float(jnp.max(jnp.abs(got[1][1:] - want[1][1:])))
    return y_err, s_err


def run_variant(name, fn, ops, want, calls, rounds=3):
    """Check one variant on a fresh copy of the table, then time `calls`
    queued calls, each donating the table the call before returned."""
    table, rows, decay, v, b, c = ops
    row = {"variant": name}
    step = jax.jit(lambda s: fn(s, rows, decay, v, b, c), donate_argnums=0)
    try:
        begin = time.perf_counter()
        got = jax.block_until_ready(step(table + 0.0))
        row["compile_s"] = time.perf_counter() - begin
        row["y_max_err"], row["state_max_err"] = errors(got, want, rows)
        state, times = got[1], []
        for _ in range(rounds):
            begin = time.perf_counter()
            for _ in range(calls):
                _, state = step(state)
            jax.block_until_ready(state)
            times.append(1e3 * (time.perf_counter() - begin) / calls)
        row["ms"] = statistics.median(times)
        row["ms_rounds"] = times
        del state
    except Exception as error:  # Mosaic's refusals are results too
        row["error"] = str(error).splitlines()[0][:300]
    return row


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--shape", default="128,129,128,64,128,8",
                        help="rows,entries,heads,head_dim,state,groups")
    parser.add_argument("--heads", default="32,16,64,128",
                        help="heads a grid step (comma-separated)")
    parser.add_argument("--dma", default="2:1,2:4,3:1,3:4,4:4,2:16",
                        help="slots:copies of the floor that makes its own "
                             "copies (comma-separated)")
    parser.add_argument("--module", action="append", default=[],
                        help="a copy of ops/ssd_scan.py (repeatable)")
    parser.add_argument("--rehearse", action="store_true",
                        help="interpret mode off the chip: proves the "
                             "script at a toy --shape, times nothing real")
    parser.add_argument("--calls", type=int, default=50)
    parser.add_argument("--seed", type=int, default=39)
    parser.add_argument("--out", default="chiprun_out/ssd_update_sweep.json")
    args = parser.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        sys.exit("ssd_update_sweep.py times kernels: it needs the chip")
    interpret = device.platform != "tpu"
    shape = tuple(int(x) for x in args.shape.split(","))
    ops = operands(shape, args.seed)
    from flashy_tpu.ops import ssd_scan
    gather = functools.partial(ssd_scan.ssd_state_update, kernel="gather")
    want = jax.block_until_ready(jax.jit(gather)(*ops))
    bytes_moved = 2 * shape[0] * shape[2] * shape[3] * shape[4] * 4

    results = {"device": device.device_kind, "rehearsal": args.rehearse,
               "shape": args.shape, "calls": args.calls,
               "bytes_ms_at_819GBps": bytes_moved / 819e9 * 1e3, "rows": []}
    path = ROOT / args.out
    path.parent.mkdir(parents=True, exist_ok=True)

    def emit(row):
        results["rows"].append(row)
        print(json.dumps(row), flush=True)
        path.write_text(json.dumps(results, indent=1))

    emit(run_variant("gather", gather, ops, want, args.calls))
    modules = [str(ROOT / "flashy_tpu/ops/ssd_scan.py")] + args.module
    heads = shape[2]
    for hb in (min(int(x), heads) for x in args.heads.split(",")):
        emit({"heads": hb, **run_variant("floor", functools.partial(
            candidate, hb=hb, decay="columns", v="columns", y="select",
            interpret=interpret, copy_only=True), ops, want, args.calls)})
        for module_path in modules:
            module = load(module_path)
            emit({"heads": hb, "module": module_path, **run_variant(
                "module", functools.partial(
                    module._update_call, heads_per_step=hb,
                    interpret=interpret), ops, want, args.calls)})
        for decay, v, y in VARIANTS:
            emit({"heads": hb, **run_variant(
                f"{decay}/{v}/{y}", functools.partial(
                    candidate, hb=hb, decay=decay, v=v, y=y,
                    interpret=interpret), ops, want, args.calls)})
    for pair in args.dma.split(","):
        slots, chunks = (int(x) for x in pair.split(":"))
        emit({"slots": slots, "chunks": chunks, **run_variant(
            "dma_floor", functools.partial(
                dma_floor, slots=slots, chunks=chunks, interpret=interpret),
            ops, want, args.calls)})


if __name__ == "__main__":
    main()
