"""BENCHMARK.json and every data file parse and agree on names."""
import importlib
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


def _load(root, *parts):
    with open(os.path.join(root, "benchmarks", *parts)) as f:
        return json.load(f)


def test_manifest_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    assert "setup_s" in names
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_cells_find_their_files(manifest, root):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for cell in manifest["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        spec = _load(root, "workloads", f"{cell['name']}.json")
        traffic = _load(root, "traffic", f"{cell['traffic']}.json")
        importlib.import_module(f"benchmarks.runners.{spec['runner']}").run
        generator = importlib.import_module(
            f"benchmarks.traffic.{traffic['generator']}")
        assert generator.describe(traffic)
        assert cell["config"] in configs
    assert {c["config"] for c in manifest["workloads"]} == set(configs)


def test_configs_keep_every_published_width(manifest, root):
    published = _load(root, "configs", "olmo-1b.json")
    for entry in manifest["configs"]:
        config = _load(root, *entry["file"].split("/")[1:])
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        for key in ("assumed", "departures", "deployment"):
            assert config[key]
        assert "norm" in config["departures"]
        assert not any(WIDTH.search(key) for key in entry["reduced"])
        changed = [key for key, value in published.items()
                   if isinstance(value, (int, float, bool))
                   and config[key] != value]
        assert changed == entry["reduced"]


def test_per_layer_metrics_have_readers_and_move_what_their_cells_report(
        manifest, root):
    cells = [w["name"] for w in manifest["workloads"]]
    reported = {m["name"]: set(m.get("workloads", cells)) & set(cells)
                for m in manifest["end_to_end"]}
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        spec = _load(root, "layer_metrics", f"{metric['name']}.json")
        module, function = spec["reader"].split(":")
        getattr(importlib.import_module(f"benchmarks.readers.{module}"),
                function)
        mine = set(metric.get("workloads", cells)) & set(cells)
        assert mine and mine <= reported[metric["moves"]], metric["name"]
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    for cell in cells:
        assert cell in reported["setup_s"]
        assert any(cell in where for name, where in reported.items()
                   if name != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_every_file_name_is_made_of_name_characters(root):
    for folder, _, names in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for name in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_peaks_table_names_its_source(root):
    from benchmarks.harness import flops
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["source"]
    try:
        flops.peaks("TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must raise")


def test_flops_arithmetic(root):
    from benchmarks.harness import flops
    full = _load(root, "configs", "olmo-1b.json")
    half = _load(root, "configs", "olmo-1b-train8l.json")
    assert round(flops.lm_param_count(full) / 1e6, 1) == 1176.8
    assert round(flops.lm_param_count(half) / 1e6, 1) == 639.9
    assert round(flops.train_flops_per_token(half, 2048) / 1e9, 2) == 4.04
    assert flops.kv_bytes_per_token_layer(full, "int8") == 2 * 2048 + 128
    forward, _ = flops.flash_attention_cost(128, 2048, 128, backward=False)
    assert forward == 2.0 * 128 * 2048 * 2048 * 128
    backward, _ = flops.flash_attention_cost(128, 2048, 128, backward=True)
    assert backward == 2.5 * forward
