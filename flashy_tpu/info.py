# Experiment inspection CLI — the `dora info` role of the absorbed
# launcher contract: list the XPs under an output root with their
# signatures, override argv, progress, and last metrics.
"""`python -m flashy_tpu.info [root]`: list experiments and their status."""
import argparse
import json
import typing as tp
from pathlib import Path


def collect(root: Path):
    """Yield {sig, cfg, argv, history, telemetry, serve, checkpoint} per
    XP under root."""
    from .solver import CHECKPOINT_META_NAME
    from .xp import (CONFIG_SNAPSHOT_NAME, FLEET_STATUS_NAME,
                     HEARTBEAT_DIR_NAME, RUN_INFO_NAME,
                     SERVE_STATUS_NAME, Link)
    from .observability import straggler_report

    xps_dir = root / "xps"
    if not xps_dir.is_dir():
        return
    for folder in sorted(xps_dir.iterdir()):
        if not folder.is_dir():
            continue
        entry = {"sig": folder.name, "cfg": {}, "argv": [], "history": [],
                 "telemetry": {}, "serve": {}, "fleet": {},
                 "checkpoint": {}}
        meta_path = folder / CHECKPOINT_META_NAME
        if meta_path.exists():
            with open(meta_path) as f:
                entry["checkpoint"] = json.load(f)
        config_path = folder / CONFIG_SNAPSHOT_NAME
        if config_path.exists():
            with open(config_path) as f:
                entry["cfg"] = json.load(f)
        run_info_path = folder / RUN_INFO_NAME
        if run_info_path.exists():
            with open(run_info_path) as f:
                entry["argv"] = json.load(f).get("argv", [])
        entry["history"] = Link(folder).load()
        heartbeat_dir = folder / HEARTBEAT_DIR_NAME
        if heartbeat_dir.is_dir():
            entry["telemetry"] = straggler_report(heartbeat_dir)
        serve_path = folder / SERVE_STATUS_NAME
        if serve_path.exists():
            with open(serve_path) as f:
                entry["serve"] = json.load(f)
        fleet_path = folder / FLEET_STATUS_NAME
        if fleet_path.exists():
            with open(fleet_path) as f:
                entry["fleet"] = json.load(f)
        yield entry


def format_entry(entry, verbose: bool = False) -> str:
    history = entry["history"]
    line = f"{entry['sig']}  epochs={len(history)}"
    if entry["argv"]:
        line += "  [" + " ".join(entry["argv"]) + "]"
    if history:
        last = history[-1]
        parts = []
        for stage, metrics in last.items():
            if isinstance(metrics, dict):
                numeric = [(k, v) for k, v in metrics.items()
                           if isinstance(v, (int, float))]
                shown = {k: round(v, 4) for k, v in numeric[:4]}
                parts.append(f"{stage}: {shown}")
        if parts:
            line += "  " + " | ".join(parts)
    if entry.get("telemetry", {}).get("ranks"):
        from .observability import format_straggler_report
        line += "\n  heartbeats: " + format_straggler_report(entry["telemetry"])
    if entry.get("serve"):
        line += "\n  serve: " + format_serve_status(entry["serve"])
    if entry.get("fleet"):
        line += "\n  fleet: " + format_fleet_status(entry["fleet"])
    if entry.get("checkpoint"):
        line += "\n  checkpoint: " + format_checkpoint_meta(entry["checkpoint"])
    if verbose:
        line += "\n  cfg: " + json.dumps(entry["cfg"], default=str)[:500]
    return line


def format_serve_status(status: dict) -> str:
    """One-line view of a `serve.json` snapshot (flashy_tpu.serve).

    Shows the operator headline numbers — request tallies, TTFT and
    inter-token latency percentiles (whatever the snapshot carries:
    p50/p95/p99 by default), occupancy — and ignores keys it does not
    know, so the snapshot schema can grow without breaking info.
    """
    parts = []
    for key in ("requests", "completed", "rejected", "expired"):
        if key in status:
            parts.append(f"{key}={int(status[key])}")
    for base in ("ttft_ms", "itl_ms"):
        for key in sorted((k for k in status
                           if k.startswith(f"{base}_p")
                           and isinstance(status[k], (int, float))),
                          key=lambda k: float(k.rsplit("_p", 1)[1])):
            parts.append(f"{key}={status[key]:.1f}")
    if status.get("slow_steps"):
        # scheduler steps over five times the running median and 50 ms
        # (ServeMetrics.on_step_end; each is a WARNING line in the log)
        parts.append(f"slow_steps={int(status['slow_steps'])}")
        parts.append(f"slowest_step_ms={status['slowest_step_ms']:.1f}")
    if status.get("slo", {}).get("alerting"):
        burning = [name for name, entry
                   in status["slo"].get("budgets", {}).items()
                   if entry.get("alerting")]
        parts.append("SLO-ALERT[" + ",".join(burning) + "]")
    if "occupancy_p50" in status:
        parts.append(f"occupancy_p50={status['occupancy_p50'] * 100:.0f}%")
    if "acceptance_rate" in status:
        # speculative decoding: kept drafts / proposed drafts, plus the
        # per-step accepted-token p50 when present
        parts.append(f"acceptance={status['acceptance_rate'] * 100:.0f}%")
        if "accepted_per_step_p50" in status:
            parts.append("accepted_per_step_p50="
                         f"{status['accepted_per_step_p50']:.1f}")
    # paged KV cache: layout + K/V dtype, block-pool occupancy and the
    # prefix-cache hit rate (prompt tokens served by refcount bump /
    # COW fork instead of prefill)
    if status.get("cache_layout"):
        layout = str(status["cache_layout"])
        if status.get("kv_dtype"):
            layout += f"/{status['kv_dtype']}"
        parts.append(f"cache={layout}")
    if "state_bytes_per_slot" in status:
        # decode-state bytes one slot reserves (constant in context
        # length on the SSD layout — the printed O(1)-cache number)
        parts.append(
            f"state_bytes_per_slot={int(status['state_bytes_per_slot'])}")
    if "pool_occupancy_p50" in status:
        parts.append(f"pool_p50={status['pool_occupancy_p50'] * 100:.0f}%")
    if "pool_occupancy_p95" in status:
        parts.append(f"pool_p95={status['pool_occupancy_p95'] * 100:.0f}%")
    if "prefix_hit_rate" in status:
        parts.append(f"prefix_hit={status['prefix_hit_rate'] * 100:.0f}%")
    return "  ".join(parts) or "(empty serve.json)"


def format_fleet_status(status: dict) -> str:
    """Topology view of a `fleet.json` snapshot (flashy_tpu.serve.fleet).

    One line per engine — role, health, slot/pool occupancy, how many
    requests the router sent it, and any burning SLO budgets — plus a
    fleet-level headline (policy, re-routes, deaths, tenant sheds).
    Unknown keys are ignored so the snapshot schema can grow.
    """
    head = [f"policy={status.get('policy', '?')}"]
    if status.get("reroutes"):
        head.append(f"reroutes={int(status['reroutes'])}")
    if status.get("deaths"):
        head.append("deaths[" + ",".join(status["deaths"]) + "]")
    shed = sum(t.get("shed", 0)
               for t in status.get("tenants", {}).values())
    if shed:
        head.append(f"shed={shed}")
    lines = ["  ".join(head)]
    for name, engine in status.get("engines", {}).items():
        parts = [f"{name}[{engine.get('role', '?')}]",
                 "up" if engine.get("healthy") else "DEAD"]
        if "live" in engine and "slots" in engine:
            parts.append(f"slots={int(engine['live'])}/"
                         f"{int(engine['slots'])}")
        if "pool_occupancy" in engine:
            parts.append(f"pool={engine['pool_occupancy'] * 100:.0f}%")
        if "routed" in engine:
            parts.append(f"routed={int(engine['routed'])}")
        if "prefix_hit_rate" in engine:
            parts.append(
                f"prefix_hit={engine['prefix_hit_rate'] * 100:.0f}%")
        if engine.get("slo_alerting"):
            parts.append("SLO-ALERT["
                         + ",".join(engine["slo_alerting"]) + "]")
        lines.append("    " + "  ".join(parts))
    return "\n".join(lines) if len(lines) > 1 else (
        lines[0] + "  (no engines)")


def format_checkpoint_meta(meta: dict) -> str:
    """One-line view of a `checkpoint_meta.json` snapshot: the save mode
    and the active state-sharding layout the solver will restore with
    (`replicated` / `zero1(data=N)` / `fsdp(...)` — see
    `parallel.zero.describe_state_sharding`)."""
    parts = []
    if meta.get("mode"):
        parts.append(f"mode={meta['mode']}")
    sharding = meta.get("state_sharding") or {}
    summary = sharding.get("summary") or sharding.get("mode")
    if summary:
        parts.append(f"state-sharding={summary}")
    return "  ".join(parts) or "(empty checkpoint_meta.json)"


def format_verify_report(sig: str, report: dict,
                         topology: dict = None,
                         live_devices: int = None) -> str:
    """One-line view of a `resilience.verify_checkpoint` report.

    Shows every checkpoint form found under the XP (single file, A/B
    slots with the active one marked), whether at least one verified
    restore source remains, and — when the checkpoint carries topology
    metadata — the mesh it was SAVED on. When `live_devices` differs
    from the saved device count, a WARN line flags that restoring here
    will reshard (the elastic-resume path), instead of the mismatch
    surfacing only at restore time.
    """
    parts = []
    if report["single"] is not None:
        parts.append("single=" + ("OK" if not report["single"] else "CORRUPT"))
    for slot, problems in sorted(report["slots"].items()):
        label = f"{slot}={'OK' if not problems else 'CORRUPT'}"
        if slot == report.get("active"):
            label += "*"
        parts.append(label)
    if not parts:
        return f"{sig}  no checkpoints"
    verdict = "restorable" if report["restorable"] else "NOT RESTORABLE"
    line = f"{sig}  {' '.join(parts)}  -> {verdict}"
    if topology:
        from .checkpoint import format_topology
        line += f"\n  topology: saved on {format_topology(topology)}"
        saved_devices = topology.get("device_count")
        if (live_devices is not None and saved_devices is not None
                and int(saved_devices) != int(live_devices)):
            line += (f"\n  WARN: live mesh has {live_devices} device(s) "
                     f"but the checkpoint was saved on {saved_devices} — "
                     "restore will reshard (elastic resume)")
    problems = list(report["single"] or [])
    for slot_problems in report["slots"].values():
        problems += slot_problems
    for problem in problems:
        line += f"\n  ! {problem}"
    return line


def verify_checkpoints(root: Path) -> int:
    """Integrity-check every XP's checkpoints under `root`; returns the
    process exit code: 1 when any XP has checkpoints but no verified
    restore source left (the state an operator must act on), or when
    `root` holds no experiments at all (matching the plain `info`
    convention); 0 otherwise — XPs without checkpoints are fine."""
    from .resilience import verify_checkpoint

    xps_dir = root / "xps"
    if not xps_dir.is_dir():
        print(f"no experiments under {root}/xps")
        return 1
    live_devices = None
    bad = 0
    for folder in sorted(xps_dir.iterdir()):
        if not folder.is_dir():
            continue
        report = verify_checkpoint(folder)
        topology = _saved_topology(folder)
        if topology is not None and live_devices is None:
            # lazy: only initialize a JAX backend when some checkpoint
            # actually carries topology metadata to compare against
            try:
                import jax
                live_devices = jax.device_count()
            except Exception:
                live_devices = None
        print(format_verify_report(folder.name, report, topology=topology,
                                   live_devices=live_devices))
        has_any = report["single"] is not None or report["slots"]
        if has_any and not report["restorable"]:
            bad += 1
    return 1 if bad else 0


def _saved_topology(folder: Path):
    """The topology an XP's checkpoint was saved on (the shared
    slot-then-meta lookup; None for pre-elastic checkpoints)."""
    from .checkpoint import load_saved_topology
    from .solver import CHECKPOINT_META_NAME
    return load_saved_topology(folder / "checkpoint.fsy.sharded",
                               folder / CHECKPOINT_META_NAME)


def fault_site_report(strict: bool = False) -> int:
    """Print every fault-injection site with its owning module(s) and
    chaos-campaign coverage status; the operator's one-stop answer to
    "which failure paths does this tree actually exercise?".

    Columns: site, the module(s) whose `fault_point` call fires it
    (from the same AST scan FT003 generates the registry from), and
    one of `covered` (some campaign scenario declares it, with the
    fault kinds it sweeps), `noqa'd` (deliberately excluded, reason
    shown) or `UNCOVERED`. Sites the scan finds that the committed
    registry is missing are flagged `unregistered` (the FT003 gate
    fails on those too). Exit 1 with `strict=True` when anything is
    UNCOVERED or unregistered — the CI form of the coverage promise.
    """
    from .analysis.core import discover_files, extract_fault_sites
    from .analysis.registry import FAULT_SITES, FAULT_SITE_PREFIXES
    from .resilience.campaign import NOQA_SITES, static_coverage

    pkg = Path(__file__).resolve().parent
    owners: dict = {}
    for file in discover_files([pkg], pkg.parent):
        sites, prefixes = extract_fault_sites(file)
        module = file.rel[:-len(".py")].replace("/", ".")
        if module.endswith(".__init__"):
            module = module[:-len(".__init__")]
        for site in set(sites) | {f"{p}*" for p in prefixes}:
            owners.setdefault(site, []).append(module)

    coverage = static_coverage()

    def status(site: str) -> tp.Tuple[str, bool]:
        """(display status, counts-as-covered)."""
        if site in NOQA_SITES:
            return f"noqa'd: {NOQA_SITES[site]}", True
        if site.endswith("*"):  # dynamic prefix site (e.g. logger.*)
            hits = {s: by for s, by in coverage.items()
                    if s.startswith(site[:-1])}
            if hits:
                names = sorted({name for by in hits.values()
                                for name in by})
                return (f"covered via {', '.join(sorted(hits))} "
                        f"[{', '.join(names)}]"), True
            return "UNCOVERED", False
        if site in coverage:
            parts = [f"{name}({','.join(kinds)})"
                     for name, kinds in sorted(coverage[site].items())]
            return "covered by " + " ".join(parts), True
        return "UNCOVERED", False

    registered = set(FAULT_SITES) | {f"{p}*" for p in FAULT_SITE_PREFIXES}
    rows = []
    bad = 0
    for site in sorted(registered | set(owners)):
        verdict, ok = status(site)
        if site not in registered:
            verdict = ("unregistered — run `python -m flashy_tpu."
                       "analysis --write-registry`")
            ok = False
        if not ok:
            bad += 1
        rows.append((site, ", ".join(sorted(set(owners.get(site, []))))
                     or "?", verdict))
    width_site = max(len(r[0]) for r in rows)
    width_owner = max(len(r[1]) for r in rows)
    for site, owner, verdict in rows:
        print(f"{site:<{width_site}}  {owner:<{width_owner}}  {verdict}")
    if bad:
        print(f"\n{bad} site(s) without campaign coverage — add them to "
              "a scenario's sites() in flashy_tpu/resilience/campaign.py "
              "(or NOQA_SITES with a reason)")
        return 1 if strict else 0
    return 0


def format_device_stats() -> str:
    """Live per-device HBM occupancy of THIS host's devices.

    Uses `jax.Device.memory_stats()` — the runtime complement of the
    compile-time `parallel.accounting.memory_stats`. Backends without
    the API (CPU) report only the device list.
    """
    from .observability import device_memory_stats

    lines = []
    for entry in device_memory_stats():
        line = f"device {entry['id']} [{entry['platform']}] {entry['kind']}"
        if "bytes_in_use" in entry:
            line += f"  in_use={entry['bytes_in_use'] / 2**30:.2f}G"
        if "peak_bytes_in_use" in entry:
            line += f"  peak={entry['peak_bytes_in_use'] / 2**30:.2f}G"
        if "bytes_limit" in entry:
            line += f"  limit={entry['bytes_limit'] / 2**30:.2f}G"
        lines.append(line)
    return "\n".join(lines) or "no devices"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m flashy_tpu.info",
        description="List flashy_tpu experiments under an output root.")
    parser.add_argument("root", nargs="?", default="./outputs",
                        help="output root (the folder containing xps/)")
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="also print each XP's config")
    parser.add_argument("-d", "--devices", action="store_true",
                        help="also print live per-device memory stats for "
                             "this host (initializes the JAX backend)")
    parser.add_argument("--slo", action="store_true",
                        help="render each XP's SLO budget/burn table from "
                             "the `slo` block of its serve.json snapshot")
    parser.add_argument("--verify-checkpoint", action="store_true",
                        help="verify checkpoint integrity (sha256 manifests) "
                             "for every XP; exit 1 when any XP's checkpoints "
                             "have no restorable source left (or when no "
                             "experiments exist under the root)")
    parser.add_argument("--faults", action="store_true",
                        help="list every fault-injection site with its "
                             "owning module and chaos-campaign coverage "
                             "status (covered / uncovered / noqa'd)")
    parser.add_argument("--strict", action="store_true",
                        help="with --faults: exit 1 when any site is "
                             "uncovered or unregistered")
    args = parser.parse_args(argv)

    if args.faults:
        return fault_site_report(strict=args.strict)

    if args.verify_checkpoint:
        return verify_checkpoints(Path(args.root))

    if args.devices:
        print(format_device_stats())

    found = False
    for entry in collect(Path(args.root)):
        found = True
        print(format_entry(entry, verbose=args.verbose))
        if args.slo:
            slo = (entry.get("serve") or {}).get("slo")
            if slo:
                from .observability import format_slo_report
                table = format_slo_report(slo)
                print("  slo:\n    " + table.replace("\n", "\n    "))
            else:
                print("  slo: no report in serve.json")
    if not found:
        print(f"no experiments under {args.root}/xps")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
