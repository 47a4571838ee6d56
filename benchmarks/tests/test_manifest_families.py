"""Every configuration against the published numbers of ITS OWN source.

`test_manifest.py::test_configs_keep_every_published_width` holds every
configuration to `olmo-1b.json`; with a second family that cannot be
right. Same rules here, per source: a file that cuts the published
model says what was published in a `published` block (the published
value of each key in `reduced`); a file without one is compared with
the configuration of the same source that reduces nothing."""
import json
import os
import re

WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load(root, *parts):
    with open(os.path.join(root, "benchmarks", *parts)) as f:
        return json.load(f)


def _numbers(config: dict) -> dict:
    return {key: value for key, value in config.items()
            if isinstance(value, (int, float, bool))}


def _published(config: dict, entry: dict, files: dict) -> dict:
    """The published top-level numbers of `config`'s source."""
    if "published" in config:
        return dict(_numbers(config), **config["published"])
    whole = [other for other in files.values()
             if other["source"] == entry["source"] and not other["reduced"]]
    assert whole, f"{entry['name']}: no published block and no uncut sibling"
    return _numbers(whole[0])


def test_each_configuration_keeps_its_own_sources_widths(manifest, root):
    files = {entry["name"]: _load(root, *entry["file"].split("/")[1:])
             for entry in manifest["configs"]}
    for entry in manifest["configs"]:
        config = files[entry["name"]]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        for key in ("assumed", "departures", "deployment"):
            assert config[key], (entry["name"], key)
        assert "norm" in config["departures"]
        assert not any(WIDTH.search(key) for key in entry["reduced"])
        published = _published(config, entry, files)
        changed = [key for key, value in published.items()
                   if key in _numbers(config) and config[key] != value]
        assert sorted(changed) == sorted(entry["reduced"]), entry["name"]
        # a published block states only what was cut
        assert set(config.get("published", {})) <= set(entry["reduced"])


def test_catalog_rows_are_kept_key_for_key(manifest, root):
    """Where the catalog beside the model-configs guide is on this
    machine: every number of a row's `config` is in the file under the
    same key, nested groups whole, but for the keys in `reduced`."""
    if not os.path.exists(CATALOG):
        return
    with open(CATALOG) as f:
        rows = {row["source_url"]: row for row in map(json.loads, f)}
    for entry in manifest["configs"]:
        row = rows.get(entry["source"])
        if row is None:
            continue
        config = _load(root, *entry["file"].split("/")[1:])
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert config.get("published", {}).get(key) == value, key
            elif isinstance(value, (int, float, bool, dict)):
                assert config[key] == value, (entry["name"], key)


def test_a_family_names_harness_modules_that_exist(manifest, root):
    import importlib
    for entry in manifest["configs"]:
        config = _load(root, *entry["file"].split("/")[1:])
        for role, module in config.get("harness", {}).items():
            found = importlib.import_module(f"benchmarks.harness.{module}")
            need = {"model": ("transformer_config", "seeded_params"),
                    "reference": ("logits_at",),
                    "flops": ("param_count",)}[role]
            assert all(hasattr(found, name) for name in need), module


def test_dots_arithmetic(root):
    from benchmarks.harness import flops_dots
    config = _load(root, "configs", "dots-vlm1-ep16-6l.json")
    parts = flops_dots.parts(config)
    assert round(parts["attention"] / 1e6, 1) == 187.1
    assert round(parts["dense_mlp"] / 1e6, 1) == 396.4
    assert round(parts["routed_expert"] / 1e6, 2) == 44.04
    assert round(flops_dots.param_count(config) / 1e6) == 5503
    assert flops_dots.latent_bytes_per_token_layer(config) == (512 + 128) * 2
    flops, nbytes = flops_dots.latent_read_cost(config, 1000.0)
    assert flops == 1000.0 * 128 * 2 * (576 + 512) and nbytes == 1.28e6
