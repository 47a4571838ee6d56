# The benchmark's entry point. One process, one cell, one run:
#
#   python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
#
# It holds no per-cell, per-model, per-traffic or per-metric code.
# Everything is found by name from BENCHMARK.json:
#   workloads/<cell>.json      runner, engine/trainer parameters, checks
#   configs/<config>.json      the model's published keys, as run
#   traffic/<traffic>.json     the mix's parameters; "generator" names
#                              traffic/<generator>.py
#   runners/<runner>.py        run(ctx) -> the run record
#   layer_metrics/<name>.json  "reader": "<module>:<function>" under
#                              readers/, and its args
# The last line of standard output is the one JSON object of the
# contract; everything else worth reading goes on earlier lines,
# prefixed "[bench]". Without a TPU (or with fewer chips than the cell
# asks for) it exits 3 and prints no result. `--rehearse <file>` merges
# toy-size overrides over the data files so the script can be debugged
# on the CPU; its last line is prefixed so that nothing can take it for
# a result.
"""benchmarks/run.py: run one cell of BENCHMARK.json once."""
import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_TAIL_SECONDS = 4.0


def say(message: str) -> None:
    print(f"[bench] {message}", flush=True)


def load(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = merge(out[key], value) if both else value
    return out


def named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def read_layer_metric(name: str, record: dict):
    spec = load("layer_metrics", f"{name}.json")
    module, function = spec["reader"].split(":")
    reader = getattr(importlib.import_module(
        f"benchmarks.readers.{module}"), function)
    return reader(record, **spec.get("args", {}))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", metavar="OVERRIDES.json")
    args = parser.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = named(manifest["workloads"], args.workload, "workload")
    cell = load("workloads", f"{entry['name']}.json")
    config = load("configs", f"{entry['config']}.json")
    traffic = load("traffic", f"{entry['traffic']}.json")
    if args.rehearse:
        with open(args.rehearse) as f:
            toy = json.load(f)
        cell = merge(cell, toy.get("workloads", {}).get(entry["name"], {}))
        config = merge(config, toy.get("configs", {}).get(entry["config"], {}))
        traffic = merge(traffic,
                        toy.get("traffic", {}).get(entry["traffic"], {}))
        say("REHEARSAL at a toy size on whatever backend JAX finds: this "
            "debugs the script and measures nothing")

    sys.path.insert(0, ROOT)
    setup = {}
    begin = time.perf_counter()
    import jax
    from flashy_tpu.utils import configure_compile_cache
    from benchmarks.harness import flops, model as model_lib
    from benchmarks.harness.compile_log import CompileLog
    from benchmarks.harness.trace import Tracing, breakdown
    cache_dir = configure_compile_cache()
    # every program goes to the cache, the eager one-op programs of the
    # program's construction too: a later run compiles nothing
    if not args.rehearse:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compile_log = CompileLog()
    device = model_lib.device_record()
    setup["imports_s"] = time.perf_counter() - begin
    say(f"device: {json.dumps(device)}; cell {entry['name']} (config "
        f"{entry['config']}, traffic {entry['traffic']}, {entry['chips']} "
        f"chip(s)); seed {args.seed}; compile cache {cache_dir}")
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < entry["chips"]):
        say(f"needs {entry['chips']} TPU chip(s): nothing is measured "
            f"without them")
        return 3

    out_dir = os.path.join(ROOT, ".bench_out", entry["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    generator = importlib.import_module(
        f"benchmarks.traffic.{traffic['generator']}")
    say(f"traffic: {json.dumps(generator.describe(traffic))}")
    ctx = types.SimpleNamespace(
        cell_name=entry["name"], cell=cell, config=config, traffic=traffic,
        chips=entry["chips"], seed=args.seed, seconds=args.seconds,
        generator=generator, compile_log=compile_log, out_dir=out_dir,
        setup=setup, say=say,
        tracing=Tracing(os.path.join(out_dir, "trace"), bool(args.trace),
                        min(TRACE_TAIL_SECONDS, args.seconds)))

    def start_window() -> float:
        ctx.setup_s = time.time() - PROCESS_START
        return time.perf_counter()

    ctx.start_window = start_window
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    record = runner.run(ctx)

    split = ", ".join(f"{key[:-2]} {value:.1f}s"
                      for key, value in setup.items())
    say(f"set-up {ctx.setup_s:.1f}s ({split}); compiling "
        f"{compile_log.compile_seconds:.1f}s, persistent cache "
        f"{compile_log.cache_hits} hits / {compile_log.cache_misses} misses")
    say(f"peak device memory {record['memory_peak_bytes'] / 1e9:.2f} GB")

    # a rehearsal has no device whose peaks could be looked up
    record["peak"] = None if args.rehearse else flops.peaks(device["kind"])
    record["config"] = config
    end_to_end = dict(record["end_to_end"], setup_s=ctx.setup_s)
    if args.trace:
        kind, read = "per_layer", lambda m: read_layer_metric(m["name"], record)
    else:
        kind, read = "end_to_end", lambda m: end_to_end[m["name"]]
    values = {}
    for metric in manifest[kind]:
        if entry["name"] in metric.get("workloads", [entry["name"]]):
            value = read(metric)
            if value is not None:
                values[metric["name"]] = {"value": value,
                                          "unit": metric["unit"]}
    say("end to end: " + json.dumps(end_to_end))
    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    line = {"correct": bool(record["correct"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": values, "device": device}
    if args.trace and record.get("trace"):
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = breakdown(record["trace"])
    print(("[rehearsal] " if args.rehearse else "") + json.dumps(line),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
