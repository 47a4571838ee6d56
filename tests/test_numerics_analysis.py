# Tests for flashy_tpu.analysis.numerics: the seeded-violation corpus
# (each FT2xx must catch its planted defect — including faithful
# resurrections of the repo's two real PR-4 numerics bugs, which FT201
# must flag), the fixed live code passing where the resurrections
# fail, the ValueGraph machinery, the baseline round trip, SARIF
# emission, the CLI, and — the acceptance gate — the live
# registered-program sweep being clean against the committed (empty)
# numerics baseline.
from pathlib import Path
import importlib.util
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flashy_tpu.analysis import __main__ as cli
from flashy_tpu.analysis.numerics import (
    ALL_AUDITORS, NumericsProgram, ValueGraph, audit_programs,
    auditor_by_code, demo_programs, run_numerics_auditors,
)
from flashy_tpu.analysis.numerics.core import (
    DEFAULT_NUMERICS_BASELINE_NAME, NumericsFinding, is_narrow_float,
    load_numerics_baseline, new_numerics_findings, numerics_fingerprint,
    save_numerics_baseline)

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "analysis_fixtures" / "numerics"


def _load_fixture(name):
    spec = importlib.util.spec_from_file_location(
        f"numerics_fixture_{name}", FIXTURES / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _audit_fixture(name):
    """(findings, EXPECT) for one fixture module's programs."""
    module = _load_fixture(name)
    programs = [NumericsProgram(**kwargs) for kwargs in module.programs()]
    return audit_programs(programs), module.EXPECT


def _assert_expect(findings, expect):
    got = {(f.program, f.code, f.key) for f in findings}
    for label, wanted in expect.items():
        for code, key_prefix in wanted:
            assert any(p == label and c == code
                       and k.startswith(key_prefix)
                       for p, c, k in got), (
                f"missing {code} {key_prefix!r} on {label}; got {got}")


# ----------------------------------------------------------------------
# FT201: the two resurrected PR-4 bug shapes + the fixed live code
# ----------------------------------------------------------------------
def test_ft201_flags_resurrected_bf16_accumulator():
    findings, expect = _audit_fixture("ft201_bf16_accum")
    _assert_expect(findings, expect)
    assert all(f.code == "FT201" for f in findings)


def test_ft201_flags_resurrected_complex_dropping_accumulator():
    findings, expect = _audit_fixture("ft201_complex_drop")
    _assert_expect(findings, expect)


def test_ft201_fixed_live_accumulation_is_clean():
    # the SAME program shapes through the repo's real (fixed)
    # with_grad_accumulation: bf16 grads accumulate in f32, complex
    # grads keep their dtype — neither resurrection fires
    from flashy_tpu.parallel import with_grad_accumulation

    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (16, 16), jnp.bfloat16),
              "w2": jax.random.normal(key, (16, 4), jnp.bfloat16)}
    batch = jax.random.normal(key, (16, 16), jnp.bfloat16)

    def loss(p, mb):
        return jnp.mean((jnp.tanh(mb @ p["w1"]) @ p["w2"]) ** 2)

    fixed = with_grad_accumulation(jax.value_and_grad(loss), 8)
    program = NumericsProgram(label="live/fixed-bf16-accum", fn=fixed,
                              example_args=(params, batch))
    assert audit_programs([program], select=["FT201"]) == []

    cparams = {"w": (jax.random.normal(key, (8, 4))
                     + 1j * jax.random.normal(key, (8, 4))
                     ).astype(jnp.complex64)}
    cbatch = jax.random.normal(key, (8, 8)).astype(jnp.complex64)

    def closs(p, mb):
        return jnp.mean(jnp.abs(mb @ p["w"]) ** 2)

    cfixed = with_grad_accumulation(
        lambda p, mb: (closs(p, mb), jax.grad(closs)(p, mb)), 4)
    program = NumericsProgram(label="live/fixed-complex-accum", fn=cfixed,
                              example_args=(cparams, cbatch))
    assert audit_programs([program], select=["FT201"]) == []


def test_ft201_flags_seeded_bf16_ssd_state_carry():
    # the delta-form resurrection: slot state kept in bf16 and advanced
    # by ADDING the per-token update into the scan carry — the
    # accumulator walk must find the narrow carry behind the add
    findings, expect = _audit_fixture("ft201_ssd_state")
    _assert_expect(findings, expect)
    assert all(f.code == "FT201" for f in findings)


def test_ft201_live_ssd_scan_is_clean():
    # the SAME shapes through the repo's real SSD scan: bf16
    # activations, but the state carried in f32 and updated mul-first
    # (a*S + outer) — the resurrection must not fire on the fix
    from flashy_tpu.ops.ssd_scan import ssd_chunked_scan

    key = jax.random.PRNGKey(0)
    kc, kb, kv, ka = jax.random.split(key, 4)
    c = jax.random.normal(kc, (2, 16, 2, 4), jnp.bfloat16)
    b = jax.random.normal(kb, (2, 16, 2, 4), jnp.bfloat16)
    v = jax.random.normal(kv, (2, 16, 2, 8), jnp.bfloat16)
    log_a = -jax.nn.softplus(jax.random.normal(ka, (2, 16, 2),
                                               jnp.float32))
    program = NumericsProgram(
        label="live/ssd-chunked-scan",
        fn=lambda *args: ssd_chunked_scan(*args, chunk=8),
        example_args=(c, b, v, log_a))
    assert audit_programs([program], select=["FT201"]) == []


def test_ft201_narrow_reduction_operand():
    # NB jnp.sum upcasts narrow operands to f32 by itself (even with
    # dtype=bf16 it reduces in f32 and converts the result) — narrow
    # reductions reach programs through lax-level spellings, which is
    # exactly what a hand-fused kernel would emit
    def narrow_cumsum(grads):
        return jnp.cumsum(grads.astype(jnp.bfloat16))

    program = NumericsProgram(label="seeded/narrow-cumsum",
                              fn=narrow_cumsum,
                              example_args=(jnp.ones((64,), jnp.float32),))
    findings = audit_programs([program], select=["FT201"])
    assert any(f.key.startswith("narrow-reduction:cumsum")
               for f in findings), [f.key for f in findings]

    def narrow_lax_reduce(grads):
        return jax.lax.reduce(grads.astype(jnp.bfloat16),
                              jnp.bfloat16(0), jax.lax.add, (0,))

    program = NumericsProgram(label="seeded/narrow-reduce",
                              fn=narrow_lax_reduce,
                              example_args=(jnp.ones((64,), jnp.float32),))
    findings = audit_programs([program], select=["FT201"])
    assert any(f.key.startswith("narrow-reduction:reduce")
               for f in findings), [f.key for f in findings]

    # ...and a narrow MAX reduction is lossless — must stay clean
    def narrow_max(grads):
        return jax.lax.reduce(grads.astype(jnp.bfloat16),
                              jnp.bfloat16(-jnp.inf), jax.lax.max, (0,))

    program = NumericsProgram(label="seeded/narrow-max", fn=narrow_max,
                              example_args=(jnp.ones((64,), jnp.float32),))
    assert audit_programs([program], select=["FT201"]) == []


def test_ft201_activation_carry_is_not_an_accumulator():
    # a bf16 carry that is OVERWRITTEN (not add-updated) each step is
    # an activation/state carry — flagging it would bury real findings
    def rollout(x0, steps):
        def body(x, w):
            return jnp.tanh(x @ w), None

        out, _ = jax.lax.scan(body, x0, steps)
        return out

    program = NumericsProgram(
        label="seeded/activation-carry", fn=rollout,
        example_args=(jnp.ones((4, 4), jnp.bfloat16),
                      jnp.ones((3, 4, 4), jnp.bfloat16)))
    assert audit_programs([program], select=["FT201"]) == []


# ----------------------------------------------------------------------
# FT202 / FT203 / FT204: seeded corpora
# ----------------------------------------------------------------------
def test_ft202_seeded_casts():
    findings, expect = _audit_fixture("ft202_casts")
    _assert_expect(findings, expect)


def test_ft202_clean_without_narrowing():
    def clean(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((batch @ p) ** 2))(state["params"])
        mu = state["opt_state"]["mu"] * 0.9 + grads * 0.1
        return {"params": state["params"] - 1e-3 * mu,
                "opt_state": {"mu": mu}}, {"loss": loss}

    state = {"params": jnp.ones((8, 4)),
             "opt_state": {"mu": jnp.zeros((8, 4))}}
    program = NumericsProgram(label="live/clean-update", fn=clean,
                              example_args=(state, jnp.ones((4, 8))),
                              protect_outputs=("opt_state",))
    assert audit_programs([program], select=["FT202"]) == []


def test_ft202_vacuous_protect_pattern_is_loud():
    def narrow(params, batch):
        return (batch @ params).astype(jnp.bfloat16)

    program = NumericsProgram(label="seeded/vacuous", fn=narrow,
                              example_args=(jnp.ones((8, 4)),
                                            jnp.ones((4, 8))),
                              protect_outputs=("opt_state",))
    findings = audit_programs([program], select=["FT202"])
    assert "no-protected-outputs" in {f.key for f in findings}


def test_ft203_seeded_scale_misplacements():
    findings, expect = _audit_fixture("ft203_scales")
    _assert_expect(findings, expect)


def test_ft203_live_paged_attention_is_clean():
    from flashy_tpu.ops.paged_attention import paged_attention, pool_spec

    key = jax.random.PRNGKey(0)
    # the pool's own leaves: zero payloads, unit scales
    entry = {name: jnp.full(shape, name.endswith("_scale"), dtype)
             for name, (shape, dtype) in pool_spec(
                 4, 4, 2, 8, jnp.float32, "int8").items()}
    program = NumericsProgram(
        label="live/paged-attention",
        fn=lambda q, e, t, p: paged_attention(q, e, t, p, head_dim=8,
                                              dtype=jnp.float32),
        example_args=(jax.random.normal(key, (2, 1, 2, 8)), entry,
                      jnp.zeros((2, 3), jnp.int32),
                      jnp.zeros((2, 1), jnp.int32)))
    assert audit_programs([program], select=["FT203"]) == []


def test_ft203_skips_unquantized_programs():
    program = NumericsProgram(label="live/dense", fn=lambda x: x @ x,
                              example_args=(jnp.ones((4, 4)),))
    assert audit_programs([program], select=["FT203"]) == []


def test_ft204_seeded_rng():
    findings, expect = _audit_fixture("ft204_rng")
    _assert_expect(findings, expect)


def test_ft204_single_sample_probe_is_not_vacuously_insensitive():
    # seed_samples=1 leaves nothing to compare — a pure, k-sensitive
    # derivation must not be flagged off an empty all()
    program = NumericsProgram(
        label="live/one-sample",
        seed_fns={"pure": lambda seed, k: (seed * 31 + k) % (2 ** 31)},
        seed_samples=1)
    assert audit_programs([program], select=["FT204"]) == []


def test_ft204_fold_in_inside_loop_is_clean():
    def folded(xs, key):
        def body(carry, inputs):
            index, x = inputs
            sub = jax.random.fold_in(key, index)
            keep = jax.random.bernoulli(sub, 0.9, x.shape)
            return carry + jnp.where(keep, x, 0.0), None

        out, _ = jax.lax.scan(body, jnp.zeros(xs.shape[1:]),
                              (jnp.arange(xs.shape[0]), xs))
        return out

    program = NumericsProgram(label="live/folded-loop", fn=folded,
                              example_args=(jnp.ones((3, 4)),
                                            jax.random.key(0)))
    assert audit_programs([program], select=["FT204"]) == []


def test_ft204_split_keys_are_distinct():
    def split_use(x, key):
        key_a, key_b = jax.random.split(key)
        return x + jax.random.normal(key_a, x.shape) \
            + jax.random.normal(key_b, x.shape)

    program = NumericsProgram(label="live/split", fn=split_use,
                              example_args=(jnp.ones((4,)),
                                            jax.random.key(0)))
    assert audit_programs([program], select=["FT204"]) == []


def test_ft204_mixture_pick_contract_is_audited_live():
    # the registered datapipe derivation passes; a broken spelling of
    # the same contract fails — the audit tests the CONTRACT, not the
    # current implementation's text
    from flashy_tpu.datapipe.audit import numerics_audit_programs

    [entry] = numerics_audit_programs()
    assert audit_programs([NumericsProgram(**entry)]) == []


# ----------------------------------------------------------------------
# machinery: ValueGraph, dtype predicates, baseline, noqa
# ----------------------------------------------------------------------
def test_value_graph_walks_scan_boundaries():
    def f(c0, xs):
        def body(c, x):
            return c + x, c * 2.0

        return jax.lax.scan(body, c0, xs)

    graph = ValueGraph(jax.make_jaxpr(f)(jnp.zeros(()), jnp.ones((3,))))
    assert len(graph.scans) == 1
    assert len(graph.scans[0].carries) == 1
    b_in, b_out, outer_out, init = graph.scans[0].carries[0]
    # the xs flow into the carry update, and the init reaches the
    # carried output across the scan boundary
    assert graph.reaches([graph.invars[1]], {b_out})
    assert graph.reaches([init], {outer_out})
    assert graph.dtype(b_out) == jnp.float32


def test_value_graph_stitches_pallas_call_boundaries():
    # the fused-kernel gate's foundation: operands alias onto the
    # kernel body's input refs, out-refs alias onto the call's
    # results, and a ref write-then-read (swap -> get through VMEM
    # scratch) keeps the value's identity — so a quant scale entering
    # a pallas_call is still "the same value" at the mul inside, and
    # what the kernel stores reaches the program outputs.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, s_ref, o_ref, scratch):
        scratch[:] = x_ref[:] * s_ref[:]
        o_ref[:] = scratch[:] + 1.0

    def f(x, s):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            interpret=True)(x, s)

    graph = ValueGraph(jax.make_jaxpr(f)(jnp.ones((8, 128)),
                                         jnp.ones((8, 128))))
    assert "pallas_call" in graph.prims
    mul_nodes = [n for n, p in enumerate(graph.prims) if p == "mul"]
    assert mul_nodes, "kernel body was not walked"
    # the scale operand reaches the in-body mul THROUGH data movement
    # only (ref get), the FT203 scale-identity closure
    from flashy_tpu.analysis.numerics.core import DATA_MOVEMENT_PRIMS
    scale_derived = graph.forward([graph.invars[1]], DATA_MOVEMENT_PRIMS)
    assert graph.nodes_with_input(scale_derived,
                                  frozenset({"mul"})) == mul_nodes
    # and the mul's output reaches the program output across the
    # scratch write/read and the out-ref boundary
    assert graph.reaches([v for n in mul_nodes
                          for v in graph.node_out[n]],
                         set(graph.outvars))


def test_is_narrow_float():
    assert is_narrow_float(jnp.bfloat16)
    assert is_narrow_float(jnp.float16)
    assert not is_narrow_float(jnp.float32)
    assert not is_narrow_float(jnp.int8)
    assert not is_narrow_float(jnp.complex64)


def test_numerics_baseline_round_trip(tmp_path):
    findings = [NumericsFinding("FT201", "train/step", "narrow-accum:x",
                                "measured bf16"),
                NumericsFinding("FT204", "serve/verify", "key-reuse:k",
                                "2 uses")]
    path = tmp_path / "numerics-baseline.json"
    save_numerics_baseline(path, findings)
    assert "numerics baseline" in json.loads(path.read_text())["comment"]
    baseline = load_numerics_baseline(path)
    assert new_numerics_findings(findings, baseline) == []
    extra = findings + [NumericsFinding("FT201", "train/step",
                                        "narrow-accum:y", "m")]
    fresh = new_numerics_findings(extra, baseline)
    assert [f.key for f in fresh] == ["narrow-accum:y"]
    assert numerics_fingerprint(findings[0]) == \
        "train/step::FT201::narrow-accum:x"


def test_numerics_noqa_suppression():
    def reuse(x, key):
        return x + jax.random.normal(key, x.shape) \
            + jax.random.normal(key, x.shape)

    program = NumericsProgram(label="seeded/suppressed", fn=reuse,
                              example_args=(jnp.ones((3,)),
                                            jax.random.key(0)),
                              noqa=frozenset({"FT204"}))
    active, suppressed = run_numerics_auditors([program], ALL_AUDITORS)
    assert active == []
    assert [f.code for f in suppressed] == ["FT204"]


def test_auditor_registry():
    assert [a.code for a in ALL_AUDITORS] == ["FT201", "FT202", "FT203",
                                              "FT204"]
    assert auditor_by_code("FT203").name == "quant-scale-placement"
    with pytest.raises(KeyError):
        auditor_by_code("FT999")


# ----------------------------------------------------------------------
# SARIF
# ----------------------------------------------------------------------
def test_sarif_payload_shapes():
    from flashy_tpu.analysis.core import Finding
    from flashy_tpu.analysis.sarif import sarif_payload, sarif_result

    source = Finding("FT001", "flashy_tpu/x.py", 3, 4, "leak", "hint")
    program = NumericsFinding("FT203", "attention/paged-int8",
                              "double-scale:k", "applied twice")
    payload = sarif_payload(
        [sarif_result("source", source, "fp-a"),
         sarif_result("numerics", program, numerics_fingerprint(program))],
        {"FT001": ("trace-leak", "explain"),
         "FT203": ("quant-scale-placement", "explain")})
    assert payload["version"] == "2.1.0"
    [run] = payload["runs"]
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] \
        == ["FT001", "FT203"]
    src, prog = run["results"]
    region = src["locations"][0]["physicalLocation"]["region"]
    assert (region["startLine"], region["startColumn"]) == (3, 5)
    logical = prog["locations"][0]["logicalLocations"][0]["name"]
    assert logical == "attention/paged-int8"
    assert prog["partialFingerprints"]["flashyFingerprint/v1"] == \
        "attention/paged-int8::FT203::double-scale:k"
    assert "numerics/sweep.py" in \
        prog["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]


def test_cli_sarif_output(tmp_path, capsys):
    out = tmp_path / "analysis.sarif"
    code = cli.main(["--root", str(REPO), "--format", "sarif",
                     "--output", str(out)])
    capsys.readouterr()
    assert code == 0  # live repo is clean, so the document is empty...
    payload = json.loads(out.read_text())
    assert payload["runs"][0]["results"] == []
    # ...but the rule set still ships (code scanning shows coverage)
    assert len(payload["runs"][0]["tool"]["driver"]["rules"]) == 6


# ----------------------------------------------------------------------
# CLI + the live sweep gate
# ----------------------------------------------------------------------
def test_numerics_cli_list_checks(capsys):
    assert cli.main(["--numerics", "--list-checks"]) == 0
    out = capsys.readouterr().out
    for code in ("FT201", "FT202", "FT203", "FT204"):
        assert code in out


def test_numerics_cli_usage_errors(capsys):
    assert cli.main(["--numerics", "--legs", "bogus"]) == 2
    assert cli.main(["--legs", "train"]) == 2     # --legs needs a half
    assert cli.main(["--numerics", "--select", "FT999"]) == 2
    assert cli.main(["--numerics", "flashy_tpu/serve"]) == 2
    assert cli.main(["--numerics", "--write-registry"]) == 2
    assert cli.main(["--trace", "--numerics"]) == 2
    assert cli.main(["--all", "--select", "FT201"]) == 2
    assert cli.main(["--all", "--baseline", "alt.json"]) == 2
    assert cli.main(["--output", "x.sarif"]) == 2  # needs --format sarif
    capsys.readouterr()


def test_live_sweep_clean_against_committed_baseline(capsys):
    # THE acceptance gate: `python -m flashy_tpu.analysis --numerics`
    # (what `make analyze-numerics` runs) exits 0 on this repo with
    # the committed numerics baseline, which is EMPTY
    assert cli.main(["--numerics", "--root", str(REPO), "-q"]) == 0
    capsys.readouterr()
    assert load_numerics_baseline(
        REPO / DEFAULT_NUMERICS_BASELINE_NAME) == {}


def test_sweep_datapipe_leg_only():
    programs = demo_programs(legs=("datapipe",))
    assert [p.label for p in programs] == ["datapipe/mixture-pick"]
    assert audit_programs(programs) == []


def test_sweep_attention_leg_labels():
    programs = demo_programs(legs=("attention",))
    labels = {p.label for p in programs}
    assert labels == {"attention/paged-int8",
                      "attention/paged-int8-fused",
                      "attention/paged-int8-fused-verify",
                      "attention/paged-int8-write"}
    assert audit_programs(programs) == []


def test_sweep_ssd_leg_labels():
    programs = demo_programs(legs=("ssd",))
    labels = {p.label for p in programs}
    assert labels == {"ssd/chunked-scan",
                      "ssd/chunked-scan-fused",
                      "ssd/recurrent-step"}
    assert audit_programs(programs) == []


@pytest.mark.slow
def test_cli_all_merged_summary(capsys):
    # --all runs every half with one merged exit code; on the live
    # repo (empty baselines everywhere) that is exit 0 and the table
    # names all three halves
    assert cli.main(["--all", "--root", str(REPO), "-q"]) == 0
    out = capsys.readouterr().out
    assert "source" in out and "trace" in out and "numerics" in out
    assert "--all: 0 new finding(s)" in out
