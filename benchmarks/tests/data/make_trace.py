"""Writes hand_trace.textproto: a small XSpace with known numbers.

Device /device:TPU:0, window 0..1000 us (the bench/traced_window span):
  XLA Modules: jit_train_step(1) 100-500 us and 600-900 us
  XLA Ops: fusion.1 100-300, tpu_custom_call.2 (flash forward shapes)
           300-500, fusion.3 600-900, and fusion.4 950-1100 (clipped
           to 50 us by the window)
  busy = 200 + 200 + 300 + 50 = 750 us, idle 25%
Host spans: bench/batch 0-100 us, bench/train_step 100-120 us,
  bench/block_until_ready 120-1000 us
  idle gaps: 0-100 under bench/batch; 500-600 and 900-950 under
  bench/block_until_ready (150 us)
"""
import os

OPS = [
    ("%fusion.1 = bf16[8,256]{1,0:T(8,128)(2,1)} fusion(bf16[8,256]{1,0} %p.1), kind=kLoop", 100, 300),
    ("%tpu_custom_call.2 = (bf16[4,128,64]{2,1,0:T(8,128)(2,1)}, f32[4,128,64]{2,1,0:T(8,128)}) custom-call(bf16[4,128,64]{2,1,0:T(8,128)(2,1)} %q, bf16[4,128,64]{2,1,0} %k, bf16[4,128,64]{2,1,0} %v), custom_call_target=\\\"tpu_custom_call\\\"", 300, 500),
    ("%fusion.3 = f32[64]{0:T(128)} fusion(f32[64]{0} %p.2), kind=kLoop", 600, 900),
    ("%fusion.4 = bf16[8,256]{1,0:T(8,128)(2,1)} fusion(bf16[8,256]{1,0} %p.3), kind=kLoop", 950, 1100),
]
MODULES = [("jit_train_step(1)", 100, 500), ("jit_train_step(1)", 600, 900)]
SPANS = [("bench/traced_window", 0, 1000), ("bench/batch", 0, 100),
         ("bench/train_step", 100, 120), ("bench/block_until_ready", 120, 1000),
         ("PjitFunction(train_step)", 100, 119)]


def plane(name, lines):
    meta, out, ids = [], [f'planes {{ name: "{name}"'], {}
    for line_name, events in lines:
        out.append(f'  lines {{ name: "{line_name}" timestamp_ns: 0')
        for text, start, end in events:
            key = ids.setdefault(text, len(ids) + 1)
            out.append(f"    events {{ metadata_id: {key} offset_ps: "
                       f"{start * 1_000_000} duration_ps: "
                       f"{(end - start) * 1_000_000} }}")
        out.append("  }")
    for text, key in ids.items():
        out.append(f'  event_metadata {{ key: {key} value {{ id: {key} '
                   f'name: "{text}" }} }}')
    out.append("}")
    return "\n".join(out)


if __name__ == "__main__":
    text = "\n".join([
        plane("/device:TPU:0", [("XLA Modules", MODULES), ("XLA Ops", OPS)]),
        plane("/host:CPU", [("python3", SPANS)])]) + "\n"
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "hand_trace.textproto"), "w") as f:
        f.write(text)
