# Tests for XP management: signature stability, folder layout, history
# persistence, entry-point decorator — the absorbed Dora contract
# (SURVEY §1).
import json

import pytest
import yaml

from flashy_tpu.xp import (Config, compute_sig, create_xp, flatten_config,
                           get_xp, get_xp_from_sig, is_xp_active, main,
                           parse_overrides, set_by_path, temporary_xp)


def test_config_attribute_access():
    cfg = Config({"optim": {"lr": 0.1}, "epochs": 3})
    assert cfg.optim.lr == 0.1
    assert cfg.epochs == 3
    cfg.optim.lr = 0.2
    assert cfg["optim"]["lr"] == 0.2
    with pytest.raises(AttributeError):
        cfg.missing


def test_flatten_and_set_by_path():
    cfg = Config({"a": {"b": 1}})
    assert flatten_config(cfg) == {"a.b": 1}
    set_by_path(cfg, "a.c.d", 5)
    assert cfg.a.c.d == 5


def test_parse_overrides_yaml_typing():
    out = parse_overrides(["lr=1e-3", "epochs=4", "name=resnet", "layers=[1,2]", "+extra=true"])
    assert out["lr"] == 1e-3 and isinstance(out["lr"], float)
    assert out["epochs"] == 4 and isinstance(out["epochs"], int)
    assert out["name"] == "resnet"
    assert out["layers"] == [1, 2]
    assert out["extra"] is True


def test_sig_stable_and_sensitive():
    base = {"optim": {"lr": 0.1}, "epochs": 3}
    assert compute_sig(base) == compute_sig(dict(reversed(list(base.items()))))
    assert compute_sig(base) != compute_sig({"optim": {"lr": 0.2}, "epochs": 3})


def test_sig_excludes_meta_and_patterns():
    cfg = {"lr": 0.1, "dora": {"dir": "/tmp/x"}, "xp": {"dir": "/y"}, "num_workers": 4}
    other = {"lr": 0.1, "dora": {"dir": "/tmp/z"}, "num_workers": 8}
    assert compute_sig(cfg, ["num_workers"]) == compute_sig(other, ["num_workers"])
    assert compute_sig(cfg) != compute_sig(other)


def test_create_xp_and_reattach(tmp_path):
    xp = create_xp({"lr": 0.5}, root=tmp_path)
    assert xp.folder.exists()
    assert (xp.folder / "config.json").exists()
    xp.link.update_history([{"train": {"loss": 1.0}}])

    again = get_xp_from_sig(xp.sig, root=tmp_path)
    assert again.cfg.lr == 0.5
    assert again.link.history == [{"train": {"loss": 1.0}}]


def test_history_atomic_json(tmp_path):
    xp = create_xp({}, root=tmp_path)
    xp.link.update_history([{"train": {"loss": 0.25}}])
    raw = json.loads((xp.folder / "history.json").read_text())
    assert raw[0]["train"]["loss"] == 0.25


def test_enter_get_xp(tmp_path):
    assert not is_xp_active()
    xp = create_xp({}, root=tmp_path)
    with xp.enter():
        assert get_xp() is xp
    assert not is_xp_active()
    with pytest.raises(RuntimeError):
        get_xp()


def test_temporary_xp_fixture_behavior():
    with temporary_xp({"a": 1}) as xp:
        assert get_xp() is xp
        assert xp.cfg.a == 1


def test_main_decorator_end_to_end(tmp_path):
    config_dir = tmp_path / "conf"
    config_dir.mkdir()
    (config_dir / "config.yaml").write_text(yaml.dump({"lr": 0.1, "epochs": 2}))

    seen = {}

    @main(config_path=str(config_dir))
    def entry(cfg):
        seen["cfg"] = cfg
        seen["xp"] = get_xp()
        return "done"

    entry.dir = tmp_path / "runs"
    result = entry(["lr=0.5"])
    assert result == "done"
    assert seen["cfg"].lr == 0.5
    assert seen["cfg"].epochs == 2
    assert seen["xp"].folder.exists()

    # get_xp without running reproduces the same signature
    xp2 = entry.get_xp(["lr=0.5"])
    assert xp2.sig == seen["xp"].sig
    # and a different override gives a different XP
    assert entry.get_xp(["lr=0.7"]).sig != xp2.sig
    # re-attach by sig
    assert entry.get_xp_from_sig(xp2.sig).cfg.lr == 0.5


def test_main_decorator_dora_alias(tmp_path):
    @main()
    def entry(cfg):
        return get_xp().sig

    entry.dora.dir = tmp_path  # reference-style override spelling
    assert isinstance(entry([]), str)
    assert (tmp_path / "xps").exists()


def test_spawn_workers_refuses_on_a_tpu_host(monkeypatch):
    """--workers=N starts N copies of the script; on a TPU host each
    would claim every chip and all but one would fail or hang. The
    launcher must refuse at once, naming the supported route, and start
    no child."""
    from flashy_tpu import xp

    started = []
    monkeypatch.setattr(xp.subprocess, "Popen",
                        lambda *a, **k: started.append(a))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="mesh.data=4"):
        xp._spawn_workers(2, ["epochs=1"])
    assert not started

    # no platform chosen: the TPU device nodes decide, without touching
    # the backend
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(xp.glob, "glob", lambda pattern: (
        ["/dev/accel0"] if "accel" in pattern else []))
    assert xp._host_has_tpu()
    monkeypatch.setattr(xp.glob, "glob", lambda pattern: [])
    assert not xp._host_has_tpu()
    # an explicit CPU choice on a TPU host is honoured (the test suite's
    # own multi-worker runs)
    monkeypatch.setattr(xp.glob, "glob", lambda pattern: ["/dev/accel0"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not xp._host_has_tpu()
