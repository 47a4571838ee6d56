"""Writes serve_program_trace.textproto and train_program_trace.textproto:
small XSpaces with the program's spans, scopes and collectives, and
known numbers (times in us; the window is 0..1000 in both).

serve: device busy 60-160 (a prefill slice), 200-400 + 410-500 and
700-950 (two decode_paged runs). Idle 360 us over two serve/step spans:
  0-60    middle in serve/table_upload         -> admit      60
  160-200 middle in serve/gauges               -> admit      40
  400-410 middle in serve/decode/readback      -> readback   10 (inside
          a running executable)
  500-700 middle in no serve/step              -> outside   200
  950-1000 middle in serve/decode, no child    -> dispatch   50
`running` 2 and 3; readback spans end 5 and 10 us after their module.
decode_paged by scope, per run: qkv 60, attn 140, kv_write (40+30+20)/2
(20 of it a copy named after the argument `cache[...]`),
mlp (50+0)/2.

train: two devices, one jit_train_step run each, 100-900. Per device
attn 200, mlp 190, loss 140, optimizer 140. Collectives: all-gather
start 300-310 (exposed 10), all-gather done 500-560 on device 0 and
500-530 on device 1 (exposed 60 and 30), reduce-scatter 560-640 under a
fusion that runs 560-700 (hidden), all-reduce 700-760 (exposed 60):
exposed 130 and 100 us, mean 115.
"""
import os

SCOPE_STAT = "tf_op"
D = "jit(decode_paged)/jit(main)/"
T = "jit(train_step)/jit(main)/"


def op(stem, number, scope=None, text="f32[8]{0} fusion(f32[8]{0} %p)"):
    return (f"%{stem}.{number} = {text}", scope)


SERVE_OPS = [
    (op("fusion", 1, "jit(chunk_paged)/jit(main)/qkv/dot_general"), 60, 160),
    (op("fusion", 2, D + "qkv/dot_general"), 200, 260),
    (op("paged_decode_fused", 3, D + "attn/paged_decode_fused",
        "bf16[2,1,2,8]{3,2,1,0} custom-call(bf16[2,1,2,8]{3,2,1,0} %q), "
        "custom_call_target=\\\"tpu_custom_call\\\""), 260, 400),
    (op("copy", 4, D + "kv_write/scatter"), 410, 450),
    (op("fusion", 5, D + "mlp/dot_general"), 450, 500),
    (op("fusion", 2, D + "qkv/dot_general"), 700, 760),
    (op("paged_decode_fused", 3, D + "attn/paged_decode_fused",
        "bf16[2,1,2,8]{3,2,1,0} custom-call(bf16[2,1,2,8]{3,2,1,0} %q), "
        "custom_call_target=\\\"tpu_custom_call\\\""), 760, 900),
    (op("copy", 4, D + "kv_write/scatter"), 900, 930),
    # a layout copy XLA inserted: named after the argument it copies
    (op("copy", 6, "cache['block_0']['k_scale']"), 930, 950),
]
SERVE_MODULES = [(("jit_chunk_paged(3)", None), 60, 160),
                 (("jit_decode_paged(7)", None), 200, 500),
                 (("jit_decode_paged(7)", None), 700, 950)]
# (name, start, end, {stat: int})
SERVE_SPANS = [
    ("bench/traced_window", 0, 1000, {}),
    ("bench/scheduler.step", 0, 525, {}),
    ("serve/step", 1, 520, {"step": 3, "queued": 0, "prefilling": 1,
                            "running": 2}),
    ("serve/admission", 5, 20, {"queued": 0}),
    ("serve/prefill_chunk", 25, 170, {"uid": 9, "slot": 1, "size": 16,
                                      "offset": 0, "length": 30, "final": 0}),
    ("serve/table_upload", 28, 45, {"bytes": 256}),
    ("serve/gauges", 175, 190, {}),
    ("serve/decode", 195, 510, {"live": 3, "running": 2}),
    ("serve/decode/dispatch", 196, 215, {}),
    ("serve/decode/readback", 216, 505, {}),
    ("serve/retire", 511, 518, {}),
    ("bench/clients", 526, 560, {}),
    ("bench/scheduler.step", 605, 995, {}),
    ("serve/step", 610, 990, {"step": 4, "queued": 0, "prefilling": 0,
                              "running": 3}),
    ("serve/admission", 612, 614, {"queued": 0}),
    ("serve/gauges", 615, 618, {}),
    ("serve/decode", 620, 980, {"live": 3, "running": 3}),
    ("serve/decode/dispatch", 622, 640, {}),
    ("serve/decode/readback", 641, 960, {}),
    ("serve/retire", 981, 988, {}),
]

F = T + "jvp(TransformerLM)/block_0/"
B = T + "transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/" \
    "rematted_computation/block_0/"


def train_ops(done_end):
    collective = "f32[8]{0} all-gather(f32[2]{0} %p), dimensions={0}"
    return [
        (op("fusion", 1, F + "attn/qkv/dot_general"), 100, 300),
        (op("all-gather-start", 2, None, collective),  # flashy: noqa[FT005]
         300, 310),
        (op("fusion", 3, B + "mlp/down/dot_general"), 310, 500),
        (op("all-gather-done", 2, None, collective), 500, done_end),
        (op("fusion", 4, T + "transpose(jvp(loss))/while/body/dot_general"),
         560, 700),
        (op("reduce-scatter", 6, None,
            "f32[2]{0} reduce-scatter(f32[8]{0} %g), dimensions={0}"),
         560, 640),
        (op("all-reduce", 5, None, "f32[] all-reduce(f32[] %n)"), 700, 760),
        (op("fusion", 7, T + "optimizer/mul"), 760, 900),
    ]


TRAIN_MODULES = [(("jit_train_step(1)", None), 100, 900)]
TRAIN_SPANS = [("bench/traced_window", 0, 1000, {}),
               ("bench/train_step", 90, 110, {"step": 2})]


def plane(name, lines):
    """`lines`: [(line name, [((event name, scope or None), start, end)
    or (span name, start, end, {stat: int})])]."""
    out, ids, stat_ids = [f'planes {{ name: "{name}"'], {}, {}

    def stat_id(stat):
        return stat_ids.setdefault(stat, len(stat_ids) + 1)

    for line_name, events in lines:
        out.append(f'  lines {{ name: "{line_name}" timestamp_ns: 0')
        for event in events:
            if len(event) == 4:
                key, start, end, stats = (event[0], None), *event[1:]
            else:
                (key, start, end), stats = event, {}
            number = ids.setdefault(key, len(ids) + 1)
            own = "".join(f" stats {{ metadata_id: {stat_id(stat)} "
                          f"int64_value: {value} }}"
                          for stat, value in stats.items())
            out.append(f"    events {{ metadata_id: {number} offset_ps: "
                       f"{start * 1_000_000} duration_ps: "
                       f"{(end - start) * 1_000_000}{own} }}")
        out.append("  }")
    for (text, scope), number in ids.items():
        # as libtpu writes it: '<op_name>:<op_type>', the type empty
        shared = (f' stats {{ metadata_id: {stat_id(SCOPE_STAT)} '
                  f'str_value: "{scope}:" }}' if scope else "")
        out.append(f'  event_metadata {{ key: {number} value {{ id: {number} '
                   f'name: "{text}"{shared} }} }}')
    for stat, number in stat_ids.items():
        out.append(f'  stat_metadata {{ key: {number} value {{ id: {number} '
                   f'name: "{stat}" }} }}')
    out.append("}")
    return "\n".join(out)


def write(name, planes):
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, name), "w") as f:
        f.write("\n".join(planes) + "\n")


if __name__ == "__main__":
    write("serve_program_trace.textproto", [
        plane("/device:TPU:0", [("XLA Modules", SERVE_MODULES),
                                ("XLA Ops", SERVE_OPS)]),
        plane("/host:CPU", [("python3", SERVE_SPANS)])])
    write("train_program_trace.textproto", [
        plane("/device:TPU:0", [("XLA Modules", TRAIN_MODULES),
                                ("XLA Ops", train_ops(560))]),
        plane("/device:TPU:1", [("XLA Modules", TRAIN_MODULES),
                                ("XLA Ops", train_ops(530))]),
        plane("/host:CPU", [("python3", TRAIN_SPANS)])])
