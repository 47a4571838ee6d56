# The span primitive (`observability.span`) and what it is used for:
# the span tree of one scheduler step on the profiler's clock, the named
# scopes of the decode and train programs, and the kernels' names. None
# of this needs a TPU: `jax.profiler.TraceAnnotation` is replaced by a
# recorder, scopes are read from lowered text, kernel names from jaxprs.
import re

import numpy as np
import pytest

from flashy_tpu.analysis.telemetry_names import TRACK_RE
from flashy_tpu.observability import Tracer, span
from flashy_tpu.serve import ContinuousBatchingScheduler, DecodeEngine
from flashy_tpu.serve import engine as engine_lib, scheduler as scheduler_lib

DECODE_SCOPES = ("embed", "norm", "qkv", "rotary", "kv_write", "attn",
                 "out_proj", "mlp", "head", "sample")
SPAN_NAMES = (
    scheduler_lib.SPAN_STEP, scheduler_lib.SPAN_ADMISSION,
    scheduler_lib.SPAN_GAUGES, scheduler_lib.SPAN_RETIRE,
    scheduler_lib.SPAN_LAUNCH_OUT, scheduler_lib.SPAN_FIRST_TOKEN,
    engine_lib.SPAN_TABLE_UPLOAD, engine_lib.SPAN_PREFILL,
    engine_lib.SPAN_PREFILL_CHUNK,
    engine_lib.SPAN_PREFILL_CHUNK + engine_lib.SPAN_READBACK,
    engine_lib.SPAN_DECODE,
    engine_lib.SPAN_DECODE + engine_lib.SPAN_DISPATCH,
    engine_lib.SPAN_DECODE + engine_lib.SPAN_READBACK,
    engine_lib.SPAN_VERIFY,
    engine_lib.SPAN_VERIFY + engine_lib.SPAN_DISPATCH,
    engine_lib.SPAN_VERIFY + engine_lib.SPAN_READBACK)


class Recorder:
    """Stands in for jax.profiler.TraceAnnotation: every span entered,
    as (depth, name, stats), and the stack of spans still open."""
    entered: list = []
    open: list = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        Recorder.entered.append((len(Recorder.open), self.name, self.stats))
        Recorder.open.append(self.name)
        return self

    def __exit__(self, *exc):
        assert Recorder.open.pop() == self.name
        return False


@pytest.fixture
def recorder(monkeypatch):
    import jax
    Recorder.entered, Recorder.open = [], []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return Recorder


def _tiny_model(remat=False, **sizes):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.models import TransformerConfig, TransformerLM

    sizes = dict(dict(dim=16, num_layers=2, num_heads=2, max_seq_len=32),
                 **sizes)
    cfg = TransformerConfig(vocab_size=32, attention="dense",
                            dtype=jnp.float32, remat=remat, **sizes)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 4), jnp.int32))


@pytest.fixture(scope="module")
def paged():
    """A warm toy paged engine (chunk 4, two slots) and its scheduler."""
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, cache_layout="paged",
                          block_size=4, chunk=4)
    engine.warmup()
    return engine


def _tree(entered, step):
    """[(depth, name)] of the spans of scheduler step number `step`."""
    starts = [i for i, (depth, name, _) in enumerate(entered)
              if name == scheduler_lib.SPAN_STEP]
    end = starts[step + 1] if step + 1 < len(starts) else len(entered)
    return [(depth, name) for depth, name, _ in entered[starts[step]:end]]


def _drain(scheduler):
    while not scheduler.idle:
        scheduler.step()


def test_scheduler_step_span_tree(recorder, paged):
    """One request of 6 prompt tokens through chunk 4: slice, final
    slice + first decode, decode, and a last step that only reads — the
    tree of each step, exactly: a step dispatches its own work, then
    reads the step before it."""
    scheduler = ContinuousBatchingScheduler(paged)
    handle = scheduler.submit(np.arange(1, 7, dtype=np.int32), 3)
    emitted, had = [], []
    for _ in range(4):
        assert not scheduler.idle
        emitted.append(scheduler.step())
        had.append(len(handle.generated))
    # the first token is no decode token: a step returns what it fed
    assert emitted == [0, 0, 1, 1] and had == [0, 0, 2, 3]
    assert scheduler.idle
    head = [(0, "serve/step"), (1, "serve/admission")]
    dispatch = [(1, "serve/decode"), (2, "serve/decode/dispatch")]
    collect = [(1, "serve/decode/readback"), (1, "serve/retire")]
    # step 0: admitted (tables changed), first slice, nothing decodes yet
    assert _tree(recorder.entered, 0) == head + [
        (1, "serve/prefill_chunk"), (2, "serve/table_upload"),
        (1, "serve/gauges")]
    # step 1: the final slice puts the row live on the device, the slot
    # decodes behind it; nothing is read
    assert _tree(recorder.entered, 1) == head + [
        (1, "serve/prefill_chunk"), (1, "serve/gauges")] + dispatch
    # step 2: the last decode (the budget's, by count) is dispatched
    # and its slot given back, THEN step 1 is read: its first token,
    # its decode token
    assert _tree(recorder.entered, 2) == head + [
        (1, "serve/gauges")] + dispatch + [
        (1, "serve/launch_out"), (1, "serve/prefill_chunk/readback"),
        (1, "serve/first_token")] + collect
    # step 3: nothing left to launch; step 2 is read, the request ends
    assert _tree(recorder.entered, 3) == head + [(1, "serve/gauges")] + collect
    stats = [s for _, name, s in recorder.entered if name == "serve/step"]
    assert [s["step"] for s in stats] == [scheduler.steps - 4 + i
                                          for i in range(4)]
    assert (stats[0]["queued"], stats[1]["prefilling"],
            stats[2]["running"]) == (1, 1, 1)
    assert [s["in_flight"] for s in stats] == [0, 0, 1, 1]
    assert [s["late_rows"] for s in stats] == [0, 0, 0, 0]
    assert not recorder.open
    # the two spans `serve/step` used to hide: one slot launched out,
    # the first token of the request in that slot
    by_name = {name: s for _, name, s in recorder.entered}
    assert by_name["serve/launch_out"] == {"rows": 1}
    assert by_name["serve/first_token"] == {"slot": handle.slot}


def _by_step(entered):
    """[(number of the serve/step a span lies in, or None outside every
    step; name; stats)] of the spans below serve/step."""
    out, current = [], None
    for depth, name, stats in entered:
        if name == scheduler_lib.SPAN_STEP:
            current = stats["step"]
        elif depth == 0:
            out.append((None, name, stats))
        else:
            out.append((current, name, stats))
    return out


DISPATCHES = ("serve/decode", "serve/decode/dispatch", "serve/prefill_chunk")


def test_a_readback_carries_the_step_that_dispatched_it(recorder, paged):
    """PR 34 split one call into two that lie a step apart: the stat
    `step` ties them. A dispatch carries its own step's number, the
    read-back in step k + 1 says `step == k`, and each read-back has
    exactly one dispatch of that number."""
    scheduler = ContinuousBatchingScheduler(paged)
    scheduler.submit(np.arange(40, 51, dtype=np.int32), 5)
    scheduler.submit(np.arange(51, 54, dtype=np.int32), 3)
    _drain(scheduler)
    spans = _by_step(recorder.entered)
    for inside, name, stats in spans:
        if name in DISPATCHES:
            assert stats["step"] == inside, name
        elif name.endswith("/readback"):
            assert stats["step"] == inside - 1, name
    decodes = [s["step"] for _, name, s in spans if name == "serve/decode"]
    assert len(decodes) > 3 and decodes == sorted(set(decodes))
    assert decodes == [s["step"] for _, name, s in spans
                       if name == "serve/decode/dispatch"]
    assert decodes == [s["step"] for _, name, s in spans
                       if name == "serve/decode/readback"]
    # a first token is read where its FINAL slice was dispatched
    finals = [s["step"] for _, name, s in spans
              if name == "serve/prefill_chunk" and s["final"]]
    assert len(finals) == 2 and finals == [
        s["step"] for _, name, s in spans
        if name == "serve/prefill_chunk/readback"]


def test_lockstep_readbacks_carry_their_own_step(recorder, paged):
    # `step(); flush()`: the read-back follows its dispatch at once,
    # outside every serve/step, under the number of the step just run
    scheduler = ContinuousBatchingScheduler(paged)
    handle = scheduler.submit(np.arange(54, 60, dtype=np.int32), 3)
    while not handle.done:
        before = len(recorder.entered)
        scheduler.step()
        scheduler.flush()
        for inside, name, stats in _by_step(recorder.entered[before:]):
            if name.endswith("/readback"):
                assert inside is None and stats["step"] == scheduler.steps - 1
    reads = [name for _, name, _ in recorder.entered
             if name.endswith("/readback")]
    assert reads.count("serve/decode/readback") == 2
    assert reads.count("serve/prefill_chunk/readback") == 1
    # by hand there is no scheduler step to name
    slot = paged.acquire_slot()
    prompt = np.arange(60, 63, dtype=np.int32)
    before = len(recorder.entered)
    _, first = paged.prefill_chunk(slot, prompt, paged.admit(slot, prompt, 2))
    paged.decode()
    paged.retire(slot)
    assert first is not None
    assert not any("step" in stats
                   for _, _, stats in recorder.entered[before:])


def test_the_verify_step_carries_its_own_step(recorder):
    from flashy_tpu.serve import NGramDraft
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, cache_layout="paged",
                          block_size=4, chunk=4, spec_k=2)
    scheduler = ContinuousBatchingScheduler(engine,
                                            draft=NGramDraft(2, k=2))
    scheduler.submit(np.arange(1, 6, dtype=np.int32), 6)
    _drain(scheduler)
    verifies = [(inside, name, stats)
                for inside, name, stats in _by_step(recorder.entered)
                if name.startswith("serve/verify")]
    assert {name for _, name, _ in verifies} == {
        "serve/verify", "serve/verify/dispatch", "serve/verify/readback"}
    assert all(stats["step"] == inside for inside, _, stats in verifies)
    # with a draft the slice is read at once: its own step's number
    reads = [(inside, stats["step"])
             for inside, name, stats in _by_step(recorder.entered)
             if name == "serve/prefill_chunk/readback"]
    assert reads and all(inside == step for inside, step in reads)


def test_decode_running_stat_is_the_tokens_emitted(recorder, paged):
    scheduler = ContinuousBatchingScheduler(paged)
    scheduler.submit(np.arange(1, 4, dtype=np.int32), 4)
    scheduler.submit(np.arange(2, 5, dtype=np.int32), 2)
    per_step = []
    while not scheduler.idle:
        before = len(recorder.entered)
        emitted = scheduler.step()
        running = [s["running"] for _, name, s in recorder.entered[before:]
                   if name == "serve/decode"]
        per_step.append((emitted, running))
    assert any(emitted == 2 for emitted, _ in per_step)
    # `live` counts acquired slots; `running` those that emit a token:
    # the tokens the NEXT step delivers, when it reads this one
    launched = [running for _, running in per_step]
    delivered = [emitted for emitted, _ in per_step[1:]] + [0]
    for running, emitted in zip(launched, delivered):
        assert running == ([emitted] if emitted else [])


def test_fused_reads_count_kv_blocks_and_steps(recorder):
    """`kv_blocks` and `kv_steps` ride the fused read's span beside
    `live` and `running`: blocks the walk attends and compute steps it
    runs for them, one layer, from the host's position mirror."""
    # eight 128-wide heads: the walk that groups blocks (16 a step here)
    model, params = _tiny_model(dim=1024, num_layers=1, num_heads=8,
                                max_seq_len=512)
    engine = DecodeEngine(model, params, slots=2, cache_layout="paged",
                          block_size=16, chunk=64, kernel="fused")
    scheduler = ContinuousBatchingScheduler(engine)
    scheduler.submit(np.arange(300, dtype=np.int32) % 32, 3)
    _drain(scheduler)
    decodes = [s for _, name, s in recorder.entered
               if name == "serve/decode"]
    # the prefill emits the first token; positions 300 and 301 decode:
    # 19 live blocks = 2 steps of 16, and the other slot is parked and
    # walks one block in one step
    assert [(s["kv_blocks"], s["kv_steps"]) for s in decodes] \
        == [(19 + 1, 2 + 1)] * 2
    assert all({"live", "running"} <= set(s) for s in decodes)
    slices = [s for _, name, s in recorder.entered
              if name == "serve/prefill_chunk"]
    # a 64-row slice from offset 0, 64, ..: blocks up to its last row
    assert [s["kv_blocks"] for s in slices] == [4, 8, 12, 16, 20]
    assert [s["kv_steps"] for s in slices] == [1, 1, 1, 1, 2]


def test_gather_reads_carry_no_walk_stats(recorder, paged):
    scheduler = ContinuousBatchingScheduler(paged)
    scheduler.submit(np.arange(3, 8, dtype=np.int32), 2)
    _drain(scheduler)
    assert paged.kernel == "gather"
    for _, name, stats in recorder.entered:
        if name in ("serve/decode", "serve/prefill_chunk"):
            assert "kv_steps" not in stats and "kv_blocks" not in stats


def test_prefill_slices_carry_the_request_uid(recorder, paged):
    scheduler = ContinuousBatchingScheduler(paged)
    # prompts no other test used: the shared engine's prefix cache
    # would serve a known first block and skip its slice
    first = scheduler.submit(np.arange(20, 29, dtype=np.int32), 1)
    second = scheduler.submit(np.arange(12, 15, dtype=np.int32), 1)
    _drain(scheduler)
    slices = [s for _, name, s in recorder.entered
              if name == "serve/prefill_chunk"]
    assert [s["uid"] for s in slices] == [first.uid] * 3 + [second.uid]
    assert [s["offset"] for s in slices] == [0, 4, 8, 0]
    assert [bool(s["final"]) for s in slices] == [False, False, True, True]
    assert all({"slot", "size", "length"} <= set(s) for s in slices)


def test_table_upload_only_after_the_tables_changed(recorder, paged):
    scheduler = ContinuousBatchingScheduler(paged)
    scheduler.submit(np.arange(1, 4, dtype=np.int32), 6)
    uploads = []
    while not scheduler.idle:
        before = len(recorder.entered)
        scheduler.step()
        uploads.append(sum(name == "serve/table_upload"
                           for _, name, _ in recorder.entered[before:]))
    # admission dirtied the tables once (the first step prefills and
    # decodes); four more decode steps reuse the device copy, the last
    # step only reads; retirement dirties them for whoever comes next
    assert uploads == [1, 0, 0, 0, 0, 0]
    scheduler.submit(np.arange(1, 4, dtype=np.int32), 1)
    scheduler.step()
    upload = [s for _, name, s in recorder.entered
              if name == "serve/table_upload"][-1]
    assert upload["bytes"] == paged._table_host.nbytes


def test_a_step_that_raises_leaves_no_span_open(recorder, paged, monkeypatch):
    scheduler = ContinuousBatchingScheduler(paged)
    scheduler.submit(np.arange(1, 4, dtype=np.int32), 4)
    scheduler.step()

    def broken(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setitem(paged.compile_cache._fns,
                        paged._key("decode", paged.slots), broken)
    with pytest.raises(RuntimeError, match="device lost"):
        scheduler.step()
    assert not recorder.open
    names = [name for _, name, _ in recorder.entered]
    assert names[-1] == "serve/decode/dispatch"
    monkeypatch.undo()
    for slot in list(paged.allocator.live):
        paged.retire(slot)


def test_span_mirrors_name_and_stats_into_a_tracer(recorder):
    tracer = Tracer()
    with span("unit/outer", tracer, category="serve", slot=3) as got:
        with span("unit/inner", tracer):
            pass
    assert got is tracer
    events = [e for e in tracer.events if e["ph"] == "X"]
    assert [(e["name"], e["cat"], e["args"]) for e in events] == [
        ("unit/inner", "host", {}), ("unit/outer", "serve", {"slot": 3})]
    assert events[1]["dur"] >= events[0]["dur"] >= 0
    assert recorder.entered == [(0, "unit/outer", {"slot": 3}),
                                (1, "unit/inner", {})]
    # Tracer.span is the same primitive
    with tracer.span("unit/method", category="data", n=1):
        pass
    assert recorder.entered[-1] == (0, "unit/method", {"n": 1})
    assert tracer.events[-1]["args"] == {"n": 1}


def test_span_without_a_tracer_records_nothing(recorder):
    from flashy_tpu.observability import get_telemetry
    assert get_telemetry() is None
    with span("unit/alone", size=2) as got:
        pass
    assert got is None
    assert recorder.entered == [(0, "unit/alone", {"size": 2})]


def test_span_falls_back_to_the_active_telemetry(recorder, tmp_path):
    from flashy_tpu.observability import disable_telemetry, enable_telemetry
    telemetry = enable_telemetry(tmp_path, with_device_stats=False)
    try:
        with span("unit/global", k=1) as got:
            pass
        assert got is telemetry.tracer
        assert telemetry.tracer.events[-1]["name"] == "unit/global"
    finally:
        disable_telemetry()


def test_span_really_enters_the_profilers_annotation():
    """Unpatched: the real TraceAnnotation accepts the stats we pass."""
    with span("unit/real", slot=1, final=True, bytes=256):
        pass


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_span_names_follow_the_track_convention(name):
    assert TRACK_RE.match(name), name


def _decode_text(layout):
    import jax.numpy as jnp
    model, params = _tiny_model()
    extra = ({"cache_layout": "paged", "block_size": 4, "chunk": 4}
             if layout == "paged" else {})
    engine = DecodeEngine(model, params, slots=2, **extra)
    step = engine._build_decode()
    return step.lower(engine._params, engine._cache, *engine._layout_args(),
                      engine._tokens, engine._positions, engine._active,
                      jnp.zeros((2,), jnp.uint32)).as_text(debug_info=True)


def _train_text():
    from examples.lm.solver import LMSolver
    from flashy_tpu.xp import Config, temporary_xp
    cfg = Config({
        "model": {"vocab_size": 64, "dim": 32, "num_layers": 2,
                  "num_heads": 2, "mlp_ratio": 2, "attention": "dense",
                  "remat": True},
        "mesh": {"data": -1}, "seq_len": 16, "batch_size": 8, "loss": "chunked",
        "loss_chunk": 8, "accumulate": 1, "steps_per_epoch": 2, "epochs": 1,
        "generate_every": 0, "lr": 1e-2, "warmup_steps": 1,
        "weight_decay": 0.0})
    with temporary_xp():
        solver = LMSolver(cfg)
        return solver._train_step.lower(
            solver.state, solver.batch_at(0)).as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes", [
    ("paged", DECODE_SCOPES), ("dense", DECODE_SCOPES),
    # the model's Flax module paths must survive nn.remat, forward and
    # backward; `loss` and `optimizer` are the solver's own scopes
    # (under value_and_grad: jvp(loss) forward, transpose(jvp(loss)) back)
    ("train", ("block_0/attn/qkv", "block_1/mlp/up", "block_0/norm1",
               "norm_f", "jvp(loss)", "transpose(jvp(loss))", "optimizer",
               "rematted_computation/block_0/attn"))])
def test_lowered_programs_carry_the_named_scopes(program, scopes):
    text = _train_text() if program == "train" else _decode_text(program)
    for scope in scopes:
        assert re.search(f'/{re.escape(scope)}[/"]', text), (program, scope)
    if program == "train":
        backward = [line for line in text.splitlines()
                    if "transpose(" in line and "/attn/" in line]
        assert backward, "attention's backward lost its module path"


def test_compile_cache_setup_keeps_scopes_and_one_key_per_program(
        monkeypatch, tmp_path):
    """`configure_compile_cache` leaves ONE traceback frame in MLIR
    locations: with none, XLA's HLO `op_name` loses every named scope
    (what a device trace shows); with all, the lowered text — and so
    the persistent cache's key — depends on who called."""
    import jax
    import jax.numpy as jnp
    from flashy_tpu.utils import configure_compile_cache
    flags = ("jax_include_full_tracebacks_in_locations",
             "jax_traceback_in_locations_limit")
    saved = {flag: getattr(jax.config, flag) for flag in flags}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def step(x):
        with jax.named_scope("qkv"):
            return jnp.dot(x, x)

    def lower():
        jax.clear_caches()
        return jax.jit(step).lower(jnp.ones((8, 8)))

    def another_caller():
        return (lambda: [lower() for _ in range(1)][0])()

    try:
        configure_compile_cache()
        direct, nested = lower(), another_caller()
        assert (direct.as_text(debug_info=True)
                == nested.as_text(debug_info=True))
        assert 'op_name="jit(step)/qkv/dot_general"' in (
            direct.compile().as_text())
    finally:
        for flag, value in saved.items():
            jax.config.update(flag, value)


def pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from pallas_calls(inner)


def _kernel_names(jaxpr, found):
    found.extend(eqn.params["name"] for eqn in pallas_calls(jaxpr))
    return found


@pytest.mark.parametrize("fused,names", [
    (True, ["flash_fwd", "flash_bwd_fused"]),
    (False, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])])
def test_flash_kernels_carry_their_names(fused, names):
    import jax
    import jax.numpy as jnp
    from flashy_tpu.ops.attention import flash_attention
    q = jnp.ones((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               fused_backward=fused).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert _kernel_names(jaxpr.jaxpr, []) == names


def test_paged_decode_kernel_carries_its_name():
    import jax
    import jax.numpy as jnp
    model, params = _tiny_model()
    engine = DecodeEngine(model, params, slots=2, cache_layout="paged",
                          block_size=4, chunk=4, kernel="fused")
    jaxpr = jax.make_jaxpr(engine._build_decode())(
        engine._params, engine._cache, *engine._layout_args(),
        engine._tokens, engine._positions, engine._active,
        jnp.zeros((2,), jnp.uint32))
    assert _kernel_names(jaxpr.jaxpr, []) == ["paged_decode_fused"] * 2


def test_every_pallas_call_in_the_package_is_named():
    """A kernel without `name=` shows up in a device trace as an
    anonymous `tpu_custom_call` a reader can only find by shape."""
    import ast
    import pathlib
    import flashy_tpu
    unnamed = []
    for path in pathlib.Path(flashy_tpu.__file__).parent.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", None) == "pallas_call"
                    and not any(k.arg == "name" for k in node.keywords)):
                unnamed.append(f"{path.name}:{node.lineno}")
    assert not unnamed, unnamed
