# Experiment (XP) management: the "Dora contract" the reference assumes
# but does not implement (see reference flashy/solver.py:33,54-56,
# flashy/logging.py:17-18, examples/*/train.py @hydra_main call sites).
# flashy_tpu absorbs it: config loading + CLI overrides, stable signature
# hashing with exclude patterns, XP folder layout, history JSON
# load/update, a `get_xp()` context, and a `main` decorator that doubles
# as a multi-process launcher (the `dora run -d --ddp_workers=N` role).
"""Experiment management: configs, signatures, folders, history.

An *XP* (experiment) is uniquely identified by its *signature* — a stable
hash of its resolved configuration (minus excluded keys). All artifacts of
the run (checkpoints, logs, metric history) live in the XP folder
``<root>/xps/<sig>/``. Re-running with the same config resumes the same
XP; that property is what makes interrupt/resume and grid-search dedup
work.
"""
from contextlib import contextmanager
from dataclasses import dataclass, field
from fnmatch import fnmatchcase
from pathlib import Path
import glob
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import typing as tp

import yaml

from .utils import write_and_rename

AnyPath = tp.Union[str, Path]

# Config keys that configure XP management itself; excluded from the
# signature. `dora.*` is accepted as an alias so reference-style YAML
# files (examples/cifar/config/config.yaml:12-14) work unchanged.
_META_SECTIONS = ("xp", "dora")

# Canonical file names inside an XP folder (single source of truth for
# everything that reads the layout, incl. flashy_tpu.info).
HISTORY_NAME = "history.json"
CONFIG_SNAPSHOT_NAME = "config.json"
RUN_INFO_NAME = "run.json"
# Telemetry artifacts (written by flashy_tpu.observability when enabled;
# rank 0 owns the unsuffixed names, rank r writes `telemetry.{r}.jsonl`
# and `trace.{r}.json`).
TELEMETRY_NAME = "telemetry.jsonl"
TRACE_NAME = "trace.json"
HEARTBEAT_DIR_NAME = "heartbeats"
# Serving status snapshot (written by flashy_tpu.serve's metrics
# surface; flashy_tpu.info shows it next to the training history).
SERVE_STATUS_NAME = "serve.json"
# Per-request lifecycle journal (flashy_tpu.serve.tracing.RequestTracer).
REQUESTS_NAME = "requests.jsonl"
# Fleet topology snapshot (flashy_tpu.serve.fleet.ServingFleet): which
# engines exist, their roles/health/occupancy and per-engine SLO burn.
FLEET_STATUS_NAME = "fleet.json"


class Config(dict):
    """A nested dict with attribute access, the config object solvers see.

    Mirrors the subset of OmegaConf/DictConfig behavior the reference's
    examples rely on (``cfg.epochs``, ``cfg.optim.lr``): attribute reads,
    attribute writes, and nesting. Plain dict semantics otherwise, so
    ``json.dumps(cfg)`` and ``**cfg`` just work.
    """

    def __init__(self, data: tp.Optional[tp.Mapping] = None):
        super().__init__()
        if data:
            for key, value in data.items():
                self[key] = value

    def __setitem__(self, key, value):
        if isinstance(value, dict) and not isinstance(value, Config):
            value = Config(value)
        super().__setitem__(key, value)

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name)

    def __setattr__(self, name, value):
        self[name] = value

    def __delattr__(self, name):
        try:
            del self[name]
        except KeyError:
            raise AttributeError(name)


def flatten_config(cfg: tp.Mapping, prefix: str = "") -> tp.Dict[str, tp.Any]:
    """Flatten nested config into dotted keys: {'optim.lr': 0.1, ...}."""
    out: tp.Dict[str, tp.Any] = {}
    for key, value in cfg.items():
        dotted = f"{prefix}{key}"
        if isinstance(value, dict):
            out.update(flatten_config(value, prefix=dotted + "."))
        else:
            out[dotted] = value
    return out


def set_by_path(cfg: Config, dotted: str, value: tp.Any) -> None:
    """Set `cfg[a][b][c] = value` given the dotted path 'a.b.c'."""
    *path, leaf = dotted.split(".")
    node = cfg
    for part in path:
        if part not in node or not isinstance(node[part], dict):
            node[part] = Config()
        node = node[part]
    node[leaf] = value


def parse_overrides(argv: tp.Sequence[str]) -> tp.Dict[str, tp.Any]:
    """Parse `key=value` CLI overrides; values go through YAML typing.

    `lr=1e-3` → float, `epochs=4` → int, `name=resnet` → str,
    `layers=[2,2,2,2]` → list. A leading `+` (hydra-style "add new key")
    is accepted and stripped.
    """
    overrides: tp.Dict[str, tp.Any] = {}
    for arg in argv:
        if "=" not in arg:
            raise ValueError(f"Expected key=value override, got: {arg!r}")
        key, raw = arg.split("=", 1)
        key = key.lstrip("+")
        value = yaml.safe_load(raw) if raw != "" else None
        if isinstance(value, str):
            # YAML 1.1 misses bare scientific notation ('1e-3'); users
            # mean the number.
            try:
                value = int(value)
            except ValueError:
                try:
                    value = float(value)
                except ValueError:
                    pass
        overrides[key] = value
    return overrides


def compute_sig(cfg: tp.Mapping, exclude: tp.Sequence[str] = ()) -> str:
    """Stable signature of a resolved config.

    Flatten to dotted keys, drop the XP-meta sections and any key matching
    an `exclude` pattern (shell wildcards, like the reference's
    `dora.exclude`), then hash the canonical JSON. Stability of this hash
    across runs is what makes resume find the same folder
    (reference tests/test_integ.py:24-27 semantics).
    """
    flat = flatten_config(cfg)
    kept = {}
    for key, value in sorted(flat.items()):
        if any(key == section or key.startswith(section + ".") for section in _META_SECTIONS):
            continue
        if any(fnmatchcase(key, pattern) for pattern in exclude):
            continue
        kept[key] = value
    payload = json.dumps(kept, sort_keys=True, default=str)
    return hashlib.sha1(payload.encode()).hexdigest()[:8]


class Link:
    """The metric history of an XP, persisted as `history.json`.

    Mirrors the `xp.link` object the reference solver writes through
    (reference flashy/solver.py:50-52,154): `history` is a list of
    per-epoch dicts {stage_name: metrics}; `update_history` persists it
    atomically.
    """

    def __init__(self, folder: Path):
        self.folder = folder
        self.history: tp.List[tp.Dict[str, tp.Any]] = []

    @property
    def history_path(self) -> Path:
        return self.folder / HISTORY_NAME

    def load(self) -> tp.List[tp.Dict[str, tp.Any]]:
        if self.history_path.exists():
            with open(self.history_path) as f:
                self.history = json.load(f)
        return self.history

    def update_history(self, history: tp.List[tp.Dict[str, tp.Any]]) -> None:
        self.history = list(history)

        # Retried: one transient GCS/NFS hiccup on this write must not
        # kill a pod-scale run (the atomic write-and-rename is
        # idempotent, so retrying is safe); a persistent failure still
        # raises — silently losing the history would break resume.
        def write() -> None:
            from .resilience import chaos
            chaos.fault_point("history.write", path=str(self.history_path))
            with write_and_rename(self.history_path, "w") as f:
                json.dump(self.history, f, indent=2, default=float)

        from .resilience.retry import call_with_retry
        call_with_retry(write, name="history.write", retry_on=(OSError,))


@dataclass
class XP:
    """One experiment: a signature, its config, and its folder."""

    sig: str
    cfg: Config
    folder: Path
    link: Link = field(init=False)
    argv: tp.List[str] = field(default_factory=list)

    def __post_init__(self):
        self.folder.mkdir(parents=True, exist_ok=True)
        self.link = Link(self.folder)
        self.link.load()

    @property
    def config_snapshot_path(self) -> Path:
        return self.folder / CONFIG_SNAPSHOT_NAME

    @property
    def run_info_path(self) -> Path:
        return self.folder / RUN_INFO_NAME

    def save_config_snapshot(self) -> None:
        from .distrib import is_rank_zero
        if not is_rank_zero():
            return  # one writer; other processes would race on the file
        # pid-suffixed tmp so even two rank-0s (launcher + re-entry) can't
        # collide on the temp path.
        with write_and_rename(self.config_snapshot_path, "w", pid=True) as f:
            json.dump(self.cfg, f, indent=2, default=str)
        with write_and_rename(self.run_info_path, "w", pid=True) as f:
            json.dump({"argv": self.argv}, f, indent=2)

    @contextmanager
    def enter(self):
        """Make this XP the current one for `get_xp()` lookups."""
        global _current_xp
        previous = _current_xp
        _current_xp = self
        try:
            yield self
        finally:
            _current_xp = previous


_current_xp: tp.Optional[XP] = None


def get_xp() -> XP:
    """The currently active XP. Raises if called outside `XP.enter()`."""
    if _current_xp is None:
        raise RuntimeError(
            "No experiment is active. Use the `flashy_tpu.main` decorator "
            "for your entry point, or `xp.enter()` explicitly.")
    return _current_xp


def is_xp_active() -> bool:
    return _current_xp is not None


def create_xp(cfg: tp.Mapping, root: tp.Optional[AnyPath] = None,
              argv: tp.Optional[tp.List[str]] = None) -> XP:
    """Build an XP from a resolved config.

    The XP root directory is, in priority order: the `root` argument, the
    `FLASHY_TPU_DIR` environment variable, `cfg.xp.dir` / `cfg.dora.dir`,
    else `./outputs`. Exclude patterns come from `cfg.xp.exclude` /
    `cfg.dora.exclude`.
    """
    cfg = Config(cfg)
    meta = {}
    for section in _META_SECTIONS:
        if section in cfg and isinstance(cfg[section], dict):
            meta.update(cfg[section])
    env_dir = os.environ.get("FLASHY_TPU_DIR") or os.environ.get("_FLASHY_TMDIR")
    folder_root = Path(root or env_dir or meta.get("dir") or "./outputs")
    exclude = meta.get("exclude") or []
    sig = compute_sig(cfg, exclude)
    xp = XP(sig=sig, cfg=cfg, folder=folder_root / "xps" / sig, argv=list(argv or []))
    xp.save_config_snapshot()
    return xp


def get_xp_from_sig(sig: str, root: tp.Optional[AnyPath] = None) -> XP:
    """Re-attach to an existing XP by signature (notebook/eval path).

    Loads the config snapshot saved on the XP's first run — the
    `main.get_xp_from_sig` role (reference examples/cifar/train.py:48-53).
    """
    env_dir = os.environ.get("FLASHY_TPU_DIR") or os.environ.get("_FLASHY_TMDIR")
    folder_root = Path(root or env_dir or "./outputs")
    folder = folder_root / "xps" / sig
    snapshot = folder / CONFIG_SNAPSHOT_NAME
    if not snapshot.exists():
        raise FileNotFoundError(f"No XP with sig {sig} under {folder_root}")
    with open(snapshot) as f:
        cfg = Config(json.load(f))
    return XP(sig=sig, cfg=cfg, folder=folder)


class _EntryPoint:
    """The object returned by the `main` decorator.

    Callable as the script entry point; also exposes `get_xp(argv)`,
    `get_xp_from_sig(sig)` and a `.dir` override (plus a `.dora`
    alias namespace so reference-style `main.dora.dir` keeps working).
    """

    def __init__(self, fn: tp.Callable, config_path: tp.Optional[str],
                 config_name: str):
        self.fn = fn
        self.config_name = config_name
        module_file = sys.modules[fn.__module__].__file__
        base = Path(module_file).parent if module_file else Path.cwd()
        self.config_path = (base / config_path) if config_path else None
        self.dir: tp.Optional[AnyPath] = None
        self.dora = self  # `main.dora.dir = ...` compatibility alias
        self.__name__ = fn.__name__
        self.__doc__ = fn.__doc__

    def _load_base_config(self) -> Config:
        if self.config_path is None:
            return Config()
        path = self.config_path / f"{self.config_name}.yaml"
        with open(path) as f:
            return Config(yaml.safe_load(f) or {})

    def _resolve(self, argv: tp.Sequence[str]) -> tp.Tuple[Config, tp.List[str]]:
        flags = [a for a in argv if a.startswith("-")]
        overrides = [a for a in argv if not a.startswith("-")]
        cfg = self._load_base_config()
        for key, value in parse_overrides(overrides).items():
            set_by_path(cfg, key, value)
        return cfg, flags

    def _usage(self) -> str:
        lines = [
            f"usage: {sys.argv[0]} [--clear] [--workers=N] [key=value ...]",
            "",
            "  key=value      override a config key (YAML-typed; nested via dots)",
            "  --clear        delete this config's XP folder and start fresh",
            "  --workers=N    spawn N distributed worker processes on this host",
            "                 (alias: --ddp_workers=N)",
        ]
        if self.config_path is not None:
            lines.append(f"  config: {self.config_path / (self.config_name + '.yaml')}")
        if self.fn.__doc__:
            lines = [self.fn.__doc__.strip(), ""] + lines
        return "\n".join(lines)

    def get_xp(self, argv: tp.Optional[tp.Sequence[str]] = None) -> XP:
        cfg, _ = self._resolve(list(argv or []))
        return create_xp(cfg, root=self.dir, argv=list(argv or []))

    def get_xp_from_sig(self, sig: str) -> XP:
        return get_xp_from_sig(sig, root=self.dir)

    def __call__(self, argv: tp.Optional[tp.Sequence[str]] = None):
        from .utils import configure_compile_cache
        configure_compile_cache()
        argv = list(sys.argv[1:] if argv is None else argv)
        if "--help" in argv or "-h" in argv:
            print(self._usage())
            return None
        cfg, flags = self._resolve(argv)
        xp = create_xp(cfg, root=self.dir, argv=argv)
        is_spawned_worker = "FLASHY_TPU_PROCESS_ID" in os.environ
        if "--clear" in flags and not is_spawned_worker:
            # Only the launcher clears; a spawned worker re-clearing would
            # delete the folder under its siblings' feet.
            import shutil
            shutil.rmtree(xp.folder, ignore_errors=True)
            xp = create_xp(cfg, root=self.dir, argv=argv)

        workers = 0
        for flag in flags:
            if flag.startswith("--workers="):
                workers = int(flag.split("=", 1)[1])
            if flag.startswith("--ddp_workers="):  # reference CLI spelling
                workers = int(flag.split("=", 1)[1])
        if workers > 1 and "FLASHY_TPU_PROCESS_ID" not in os.environ:
            return _spawn_workers(workers, argv)

        with xp.enter():
            return self.fn(xp.cfg)


def _spawn_workers(num_workers: int, argv: tp.List[str]) -> None:
    """Multi-process launch on one host (the `dora run -d` role).

    Re-execs this script `num_workers` times with the coordinator env set;
    `flashy_tpu.distrib.init()` in each child then joins the
    jax.distributed process group. Worker 0 inherits our stdio; failures
    propagate as CalledProcessError.

    Refused on a host with TPU chips: every child would open every
    local chip, and a chip belongs to one process — all but one worker
    would fail or hang. One process drives all local chips there.
    """
    if _host_has_tpu():
        raise RuntimeError(
            f"--workers={num_workers} starts {num_workers} processes that "
            f"would each claim every TPU chip of this host, and a chip "
            f"belongs to one process. Run ONE process over all local "
            f"chips instead: drop --workers and shard over the mesh "
            f"(e.g. mesh.data=4, the default mesh.data=-1 already uses "
            f"every chip).")
    port = _free_port()
    procs = []
    child_argv = [a for a in argv
                  if not (a.startswith("--workers=") or a.startswith("--ddp_workers=")
                          or a == "--clear")]
    # Re-exec exactly how we were launched: `python -m pkg.mod` entry
    # points (relative imports!) must be respawned with -m, not by
    # script path.
    main_spec = getattr(sys.modules.get("__main__"), "__spec__", None)
    if main_spec is not None and main_spec.name:
        command = [sys.executable, "-m", main_spec.name] + child_argv
    else:
        command = [sys.executable, sys.argv[0]] + child_argv
    for process_id in range(num_workers):
        env = dict(os.environ)
        env.update({
            "FLASHY_TPU_COORDINATOR": f"localhost:{port}",
            "FLASHY_TPU_NUM_PROCESSES": str(num_workers),
            "FLASHY_TPU_PROCESS_ID": str(process_id),
        })
        procs.append(subprocess.Popen(command, env=env))
    codes = [p.wait() for p in procs]
    for process_id, code in enumerate(codes):
        if code != 0:
            raise subprocess.CalledProcessError(code, f"worker {process_id}")


def _host_has_tpu() -> bool:
    """Whether this process would run on TPU chips. Decided WITHOUT
    initializing the backend (the launcher must stay off the chip):
    an explicit platform choice wins, else the TPU device nodes."""
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms:
        return "tpu" in platforms.split(",")
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def main(config_path: tp.Optional[str] = None, config_name: str = "config",
         **_ignored) -> tp.Callable[[tp.Callable], _EntryPoint]:
    """Entry-point decorator: the `dora.hydra_main` role.

    Usage::

        @flashy_tpu.main(config_path='config', config_name='config')
        def run(cfg):
            solver = Solver(cfg)
            solver.run()

        if __name__ == '__main__':
            run()

    The decorated function gains `.get_xp(argv)` and `.get_xp_from_sig`
    for notebook re-attachment, and understands `key=value` overrides,
    `--clear`, and `--workers=N` (alias `--ddp_workers=N`) on the command
    line.
    """

    def decorator(fn: tp.Callable) -> _EntryPoint:
        return _EntryPoint(fn, config_path, config_name)

    return decorator


# Alias for drop-in familiarity with reference entry points.
hydra_main = main


@contextmanager
def temporary_xp(cfg: tp.Optional[tp.Mapping] = None):
    """Create and enter a throwaway XP in a temp dir (tests, notebooks)."""
    with tempfile.TemporaryDirectory() as tmp:
        xp = create_xp(Config(cfg or {}), root=tmp)
        with xp.enter():
            yield xp
