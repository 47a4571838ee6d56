"""The reducer on a hand-built trace with known numbers
(data/hand_trace.textproto, written by data/make_trace.py)."""
import os

import pytest

from benchmarks.harness import flops, trace
from benchmarks.readers import trace_reduce


@pytest.fixture(scope="module")
def reduced():
    import jax
    path = os.path.join(os.path.dirname(__file__), "data",
                        "hand_trace.textproto")
    with open(path) as f:
        profile = jax.profiler.ProfileData.from_text_proto(f.read())
    return trace.reduce_profile(profile)


def test_busy_union_and_idle_share(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(1000e-6)
    assert reduced["busy_s"] == pytest.approx(750e-6)  # fusion.4 clipped
    assert trace_reduce.idle_pct({"trace": reduced}) == pytest.approx(25.0)


def test_module_durations(reduced):
    assert reduced["modules"]["jit_train_step"] == pytest.approx(
        [400e-6, 300e-6])
    assert trace_reduce.module_median_ms(
        {"trace": reduced}, module="jit_train_step") == pytest.approx(0.35)
    assert trace_reduce.module_median_ms(
        {"trace": reduced}, module="decode_paged") is None


def test_per_name_op_totals_and_short_names(reduced):
    assert reduced["ops"]["fusion bf16[8,256]"] == [pytest.approx(250e-6), 2]
    assert reduced["ops"]["fusion f32[64]"] == [pytest.approx(300e-6), 1]
    assert reduced["ops"]["tpu_custom_call bf16[4,128,64]"][1] == 1
    top = trace.breakdown(reduced)["device_ops"]
    assert top[0] == ["fusion f32[64]", pytest.approx(300e-6)]
    assert all(len(name) < 80 for name, _ in top)


def test_gap_attribution(reduced):
    assert reduced["idle_gaps"] == {
        "bench/batch": pytest.approx(100e-6),
        "bench/block_until_ready": pytest.approx(150e-6)}
    gaps = trace.breakdown(reduced)["idle_gaps"]
    assert gaps[0][0] == "bench/block_until_ready"


def test_flash_roofline_from_the_calls_own_shapes(reduced):
    peak = flops.peaks("TPU v5 lite")
    run = {"trace": reduced, "peak": peak,
           "host": {"batch_heads": 4, "seq_len": 128, "head_dim": 64}}
    least = flops.roofline_seconds(
        *flops.flash_attention_cost(4, 128, 64, backward=False), peak)
    assert trace_reduce.flash_roofline_pct(run) == pytest.approx(
        100 * least / 200e-6)
    run["host"]["seq_len"] = 256  # no call of that shape: nothing to read
    assert trace_reduce.flash_roofline_pct(run) is None


def test_readers_return_nothing_without_a_trace():
    for reader in (trace_reduce.idle_pct, trace_reduce.flash_roofline_pct,
                   trace_reduce.paged_decode_roofline_pct):
        assert reader({"trace": None, "host": {}}) is None


def test_op_shapes_parse_tuples_and_layouts():
    text = ("%tpu_custom_call.47 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
            "f32[128,8,2048,128]{3,2,1,0:T(8,128)}) custom-call(bf16[128,2048,"
            "128]{2,1,0:T(8,128)(2,1)} %bitcast.1552, s32[32]{0} %b), "
            "custom_call_target=\"tpu_custom_call\"")
    outputs, operands = trace.op_shapes(text)
    assert outputs == ["bf16[128,2048,128]", "f32[128,8,2048,128]"]
    assert operands == ["bf16[128,2048,128]", "s32[32]"]
    assert trace.short_op_name(text) == "tpu_custom_call bf16[128,2048,128]"
    assert trace.short_op_name("fusion.12") == "fusion"
