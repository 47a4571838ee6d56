# Checkpoint serialization. The reference delegates to torch.save/load
# (flashy/solver.py:156-164); here the state dicts assembled by
# `flashy_tpu.state.StateManager` contain JAX pytrees (params, optax
# states), numpy arrays and plain python objects. Three paths:
#
#  * save_state/load_state — single-file pickle of the host-gathered
#    state (device arrays are pulled to numpy first). Matches the
#    single-file `checkpoint.th` semantics, with atomic rename.
#  * save_sharded/restore_sharded — Orbax-backed distributed checkpoint
#    for states too large to gather on one host: every process writes its
#    own shards, restore re-shards onto the current mesh.
#  * to_torch_state_dict/from_torch_state_dict — interop shims so torch
#    checkpoints can seed JAX runs and vice versa.
"""Checkpoint IO: single-file, sharded (Orbax), and torch interop."""
from pathlib import Path
import logging
import math
import pickle
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from .resilience import chaos
from .resilience.integrity import (CheckpointCorrupted, CheckpointError,
                                   verify_file, verify_slot, write_manifest,
                                   write_sidecar)
from .resilience.retry import call_with_retry
from .utils import AnyPath, to_numpy, write_and_rename

logger = logging.getLogger(__name__)

# Orbax's default lets one OCDBT data file grow to 2 GB. A machine that
# caps file size (`ulimit -f`, some network filesystems) then fails the
# whole save with EFBIG, so arrays are cut into chunks of at most this
# many bytes and a data file is closed once it holds this much: no file
# of a sharded checkpoint exceeds twice this value.
DATA_FILE_BYTES = 8 << 20


def _orbax_save_args(arrays: tp.Any) -> tp.Any:
    import orbax.checkpoint as ocp
    return ocp.args.PyTreeSave(arrays,
                               ocdbt_target_data_file_size=DATA_FILE_BYTES)


def _write_state_file(path: AnyPath, payload: tp.Any,
                      sidecar: bool = True) -> None:
    """Atomic pickle write, retried on transient IO failure.

    The retried unit is idempotent (write-and-rename) and contains no
    collective — the rule that makes retrying safe on a pod. `sidecar`
    writes the integrity sidecar for single-file checkpoints (slots use
    a per-slot manifest instead, written by `_commit_slot`).
    """

    def write() -> None:
        chaos.fault_point("ckpt.write", path=str(path))
        with write_and_rename(path, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)
        if sidecar:
            write_sidecar(path)

    call_with_retry(write, name="ckpt.write", retry_on=(OSError,))


def _read_state_file(path: AnyPath, what: str) -> tp.Any:
    """Read + unpickle, retrying transient IO; unpickling failures are
    wrapped in a CheckpointError naming `what` instead of leaking a raw
    pickle traceback as the only clue."""

    def read() -> bytes:
        chaos.fault_point("ckpt.load", path=str(path))
        with open(path, "rb") as f:
            return f.read()

    payload = call_with_retry(read, name="ckpt.load", retry_on=(OSError,))
    try:
        return pickle.loads(payload)
    except Exception as exc:
        raise CheckpointError(
            f"failed to unpickle {what} at {path}: "
            f"{type(exc).__name__}: {exc}") from exc


def save_state(state: tp.Any, path: AnyPath) -> None:
    """Write a state dict to a single file, atomically (single process,
    or already host-gathered state). For multi-host runs use
    `save_state_distributed`, which splits the collective gather from the
    rank-0 write."""
    host_state = to_numpy(state)
    _write_state_file(path, host_state)


def save_state_distributed(state: tp.Any, path: AnyPath) -> None:
    """Multi-host-safe single-file save.

    ALL processes must call this together: the host gather of sharded
    global arrays is a collective. Only process 0 touches the filesystem.
    """
    from . import distrib
    host_state = to_numpy(state)  # collective when leaves are sharded
    if distrib.is_rank_zero():
        _write_state_file(path, host_state)


def load_state(path: AnyPath) -> tp.Any:
    """Load a state dict saved by `save_state`. Arrays come back as numpy;
    they are re-placed on device lazily when used in jitted computations
    (or explicitly via `jax.device_put` with the target sharding).

    When the save left an integrity sidecar (saves do since the
    resilience subsystem landed), the file is verified before
    unpickling; mismatch raises `CheckpointCorrupted`. Unpickling
    failures raise `CheckpointError` naming the path. A checkpoint
    that simply does not exist stays a plain `FileNotFoundError` —
    absence is not corruption.
    """
    if not Path(path).exists():
        raise FileNotFoundError(f"No checkpoint at {path}")
    problems = verify_file(path)
    if problems:
        raise CheckpointCorrupted(
            f"single-file checkpoint {path} failed integrity verification: "
            + "; ".join(problems))
    return _read_state_file(path, "single-file checkpoint")


class ArraySlot:
    """Marker left in a sharded checkpoint's skeleton where a device array
    was extracted into the Orbax-managed array store (keyed by the leaf's
    pytree path)."""

    __slots__ = ("key",)

    def __init__(self, key: str):
        self.key = key

    def __repr__(self) -> str:
        return f"ArraySlot({self.key!r})"

    def __eq__(self, other: tp.Any) -> bool:
        return isinstance(other, ArraySlot) and other.key == self.key

    def __hash__(self) -> int:
        return hash(("ArraySlot", self.key))

    # Pickle support for __slots__.
    def __getstate__(self):
        return self.key

    def __setstate__(self, key):
        self.key = key


def _extract_device_arrays(state: tp.Any):
    """Split `state` into (skeleton, arrays): every `jax.Array` leaf moves
    into the flat `arrays` dict (keyed by pytree path) and leaves an
    `ArraySlot` behind; all host values stay in the skeleton."""
    arrays: tp.Dict[str, jax.Array] = {}

    def visit(path, leaf):
        if isinstance(leaf, jax.Array):
            key = jax.tree_util.keystr(path)
            arrays[key] = leaf
            return ArraySlot(key)
        return leaf

    skeleton = jax.tree_util.tree_map_with_path(visit, state)
    return skeleton, arrays


_POINTER = "CURRENT"
_SLOTS = ("slot0", "slot1")
# Topology metadata written into every committed slot (and mirrored into
# the solver's checkpoint_meta.json): the mesh the state was saved on
# plus each array leaf's LOGICAL sharding spec. It exists so restore can
# treat sharding as a restore-time choice — `load_state_sharded(dir,
# mesh=target)` rebuilds placements on an ARBITRARY target mesh from the
# saved specs, instead of requiring the saving topology back.
TOPOLOGY_NAME = "topology.json"


def _spec_to_json(spec: tp.Any) -> tp.Optional[tp.List[tp.Any]]:
    """A PartitionSpec as JSON: axis name, list of names, or null per dim."""
    if spec is None:
        return None
    return [list(part) if isinstance(part, tuple) else part for part in spec]


def describe_topology(state: tp.Any) -> tp.Dict[str, tp.Any]:
    """The save-time topology record of a state pytree.

    Returns ``{"device_count", "world_size", "mesh": {"axis_names",
    "shape"} | None, "state_sharding", "leaves": {key: {"shape",
    "dtype", "spec"}}}`` where `key` matches the Orbax array-store keys
    (`jax.tree_util.keystr`) and `spec` is the leaf's logical
    PartitionSpec (null when replicated / unsharded). `device_count` is
    the number of chips of the mesh the state actually lives on (the
    "world size" of the accelerator fleet, which in elastic resume is
    the quantity that churns); `world_size` is the host process count.
    """
    leaves: tp.Dict[str, tp.Dict[str, tp.Any]] = {}
    mesh_info: tp.Optional[tp.Dict[str, tp.Any]] = None
    device_ids: tp.Set[int] = set()

    def visit(path, leaf):
        nonlocal mesh_info
        if not isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct)):
            return leaf
        sharding = getattr(leaf, "sharding", None)
        entry: tp.Dict[str, tp.Any] = {
            "shape": [int(s) for s in leaf.shape],
            "dtype": str(np.dtype(leaf.dtype)),
            "spec": _spec_to_json(getattr(sharding, "spec", None)),
        }
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None and hasattr(mesh, "axis_names"):
            info = {"axis_names": list(mesh.axis_names),
                    "shape": [int(mesh.shape[name])
                              for name in mesh.axis_names]}
            # one mesh per state is the framework convention; if several
            # appear, keep the largest (the one resharding must honor)
            if mesh_info is None or (math.prod(info["shape"])
                                     > math.prod(mesh_info["shape"])):
                mesh_info = info
        device_set = getattr(sharding, "device_set", None)
        if device_set:
            device_ids.update(d.id for d in device_set)
        leaves[jax.tree_util.keystr(path)] = entry
        return leaf

    jax.tree_util.tree_map_with_path(visit, state)
    if mesh_info is not None:
        device_count = math.prod(mesh_info["shape"])
    elif device_ids:
        device_count = len(device_ids)
    else:
        device_count = jax.device_count()
    record: tp.Dict[str, tp.Any] = {
        "version": 1,
        "device_count": device_count,
        "world_size": jax.process_count(),
        "mesh": mesh_info,
        "leaves": leaves,
    }
    try:
        from .parallel.zero import describe_state_sharding
        record["state_sharding"] = describe_state_sharding(state)["summary"]
    except Exception:  # classification is advisory, never load-bearing
        record["state_sharding"] = None
    return record


def format_topology(topology: tp.Optional[tp.Mapping[str, tp.Any]]) -> str:
    """One-line human summary of a `describe_topology` record."""
    if not topology:
        return "unknown (no topology metadata)"
    parts = [f"{topology.get('device_count', '?')} device(s)"]
    mesh = topology.get("mesh")
    if mesh:
        axes = ",".join(f"{name}={size}" for name, size
                        in zip(mesh["axis_names"], mesh["shape"])
                        if int(size) != 1) or "1-chip"
        parts.append(f"mesh({axes})")
    if topology.get("state_sharding"):
        parts.append(f"state={topology['state_sharding']}")
    if topology.get("world_size", 1) != 1:
        parts.append(f"{topology['world_size']} host(s)")
    return " ".join(parts)


def topology_differs(saved: tp.Optional[tp.Mapping[str, tp.Any]],
                     live: tp.Optional[tp.Mapping[str, tp.Any]]) -> bool:
    """True when two topology records describe different fleets: the
    device count differs, or — same count — the mesh axis names/shape
    do (losing a slice AND re-axing the survivors is still churn).
    Missing records compare equal: no metadata means no verdict."""
    if not saved or not live:
        return False
    a, b = saved.get("device_count"), live.get("device_count")
    if a is not None and b is not None and int(a) != int(b):
        return True
    mesh_a, mesh_b = saved.get("mesh"), live.get("mesh")
    if mesh_a and mesh_b:
        if list(mesh_a.get("axis_names", ())) != list(
                mesh_b.get("axis_names", ())):
            return True
        if [int(s) for s in mesh_a.get("shape", ())] != [
                int(s) for s in mesh_b.get("shape", ())]:
            return True
    return False


def load_saved_topology(sharded_directory: AnyPath,
                        meta_path: AnyPath) -> tp.Optional[tp.Dict]:
    """The topology a checkpoint was saved on, from either source: the
    sharded slot's hash-verified `topology.json` when one exists, else
    the `checkpoint_meta.json` mirror (covers single-file checkpoints).
    None when neither does — a pre-elastic checkpoint. The one shared
    lookup behind `BaseSolver.restore` and `python -m flashy_tpu.info
    --verify-checkpoint`."""
    import json
    sharded_directory = Path(sharded_directory)
    if sharded_directory.is_dir():
        topology = load_topology(sharded_directory)
        if topology is not None:
            return topology
    meta_path = Path(meta_path)
    if meta_path.exists():
        try:
            with open(meta_path) as f:
                return json.load(f).get("topology")
        except (json.JSONDecodeError, OSError):
            return None
    return None


def load_topology(directory: AnyPath,
                  slot: tp.Optional[str] = None) -> tp.Optional[tp.Dict]:
    """Read the topology record of a committed sharded checkpoint (the
    active slot by default). None when the checkpoint predates topology
    metadata or does not exist."""
    import json
    directory = Path(directory)
    slot = slot or _read_slot_pointer(directory)
    if slot is None:
        return None
    path = directory / slot / TOPOLOGY_NAME
    if not path.exists():
        return None
    try:
        return json.loads(path.read_text())
    except (json.JSONDecodeError, OSError):
        logger.warning("unreadable %s in slot %r of %s", TOPOLOGY_NAME,
                       slot, directory)
        return None


def reshard_placements(topology: tp.Mapping[str, tp.Any],
                       mesh: tp.Any) -> tp.Dict[str, tp.Any]:
    """Build per-leaf placements on a TARGET mesh from saved topology.

    Each saved leaf's logical spec is re-applied onto `mesh`: axes the
    target mesh still has keep sharding that dim (when the dim stays
    divisible by the new axis size); axes the target lost — or dims no
    longer divisible — fall back to replicated for that dim with a
    WARN. Returns `{leaf_key: ShapeDtypeStruct(..., sharding=...)}`,
    the `placements` shape `load_state_sharded` consumes — this is what
    makes an N-chip checkpoint restorable on an M-chip mesh.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    axis_sizes = {name: int(mesh.shape[name]) for name in mesh.axis_names}
    placements: tp.Dict[str, tp.Any] = {}
    for key, entry in (topology.get("leaves") or {}).items():
        shape = tuple(int(s) for s in entry.get("shape", ()))
        spec = entry.get("spec")
        parts: tp.List[tp.Any] = []
        if spec is not None:
            for dim, part in zip(shape, list(spec) + [None] * len(shape)):
                if part is None:
                    parts.append(None)
                    continue
                names = tuple(part) if isinstance(part, list) else (part,)
                size = 1
                known = all(name in axis_sizes for name in names)
                if known:
                    size = math.prod(axis_sizes[name] for name in names)
                if not known or size < 1 or dim % size:
                    logger.warning(
                        "reshard: leaf %s dim %d (spec %r) cannot shard "
                        "onto the target mesh %r — restoring that dim "
                        "replicated", key, dim, part, dict(axis_sizes))
                    parts.append(None)
                else:
                    parts.append(tuple(names) if len(names) > 1
                                 else names[0])
        sharding = NamedSharding(mesh, PartitionSpec(*parts))
        placements[key] = jax.ShapeDtypeStruct(
            shape, np.dtype(entry["dtype"]), sharding=sharding)
    return placements


def _read_slot_pointer(directory: Path) -> tp.Optional[str]:
    pointer = directory / _POINTER
    if not pointer.exists():
        return None
    name = pointer.read_text().strip()
    return name if name in _SLOTS else None


def sharded_checkpoint_exists(directory: AnyPath) -> bool:
    """True when `directory` holds a committed sharded save that at least
    one A/B slot could restore: the pointer must exist, but an active
    slot whose payload went missing does not hide a restorable sibling
    (restore falls back to it with a loud WARN)."""
    directory = Path(directory)
    slot = _read_slot_pointer(directory)
    if slot is None:
        return False
    return any((directory / s / "state.pkl").exists() for s in _SLOTS)


def _prepare_slot(directory: Path) -> str:
    """Pick the inactive A/B slot and clear its commit marker (an aborted
    previous write to it must never look complete). Collective."""
    from . import distrib
    active = _read_slot_pointer(directory)
    target = _SLOTS[1] if active == _SLOTS[0] else _SLOTS[0]
    slot_dir = directory / target
    if distrib.is_rank_zero():
        slot_dir.mkdir(parents=True, exist_ok=True)
        # both the commit marker and the manifest: an aborted write must
        # leave neither a "complete" look nor a stale integrity record
        # (nor a stale topology describing a save that never landed)
        from .resilience.integrity import MANIFEST_NAME
        for name in ("state.pkl", MANIFEST_NAME, TOPOLOGY_NAME):
            stale = slot_dir / name
            if stale.exists():
                stale.unlink()
    distrib.barrier("flashy_tpu_ckpt_slot")
    return target


def _commit_slot(directory: Path, target: str, skeleton: tp.Any,
                 on_commit: tp.Optional[tp.Callable[[], None]] = None,
                 topology: tp.Optional[tp.Dict[str, tp.Any]] = None) -> None:
    """Make slot `target` the active checkpoint: write the skeleton (the
    commit marker) and the topology record, then the integrity manifest,
    then atomically flip the CURRENT pointer. Collective: no rank
    returns before the flip is visible (a rank racing ahead could read
    the OLD checkpoint as current). The manifest is written AFTER the
    all-payload barrier (so it covers every host's Orbax shards AND the
    topology record — restore's rank-0 integrity hashing therefore
    verifies the topology too) and BEFORE the flip (so an active slot
    always carries one). `on_commit` runs on every rank after the flip
    — cleanup that must not precede durability."""
    import json

    from . import distrib
    if distrib.is_rank_zero():
        _write_state_file(directory / target / "state.pkl", skeleton,
                          sidecar=False)
        if topology is not None:
            def write_topology() -> None:
                with write_and_rename(directory / target / TOPOLOGY_NAME,
                                      "w") as f:
                    json.dump(topology, f, indent=2)

            call_with_retry(write_topology, name="ckpt.topology",
                            retry_on=(OSError,))
    distrib.barrier("flashy_tpu_ckpt_written")
    if distrib.is_rank_zero():
        def write_slot_manifest() -> None:
            chaos.fault_point("ckpt.manifest", slot=target)
            write_manifest(directory / target)

        call_with_retry(write_slot_manifest, name="ckpt.manifest",
                        retry_on=(OSError,))

        def flip_pointer() -> None:
            chaos.fault_point("ckpt.pointer", slot=target)
            with write_and_rename(directory / _POINTER, "w") as f:
                f.write(target)

        call_with_retry(flip_pointer, name="ckpt.pointer", retry_on=(OSError,))
    distrib.barrier("flashy_tpu_ckpt_committed")
    if on_commit is not None:
        on_commit()


def save_state_sharded(state: tp.Any, directory: AnyPath) -> None:
    """Distributed checkpoint: device arrays go through Orbax (each host
    writes only its own shards — no host gather, unlike
    `save_state_distributed`), everything else is pickled by process 0.

    Crash safety uses two alternating slots: the new save lands in the
    inactive slot and a CURRENT pointer file is atomically renamed over
    only after every process finished writing, so a run killed mid-save
    always leaves the previous checkpoint readable (costs 2x checkpoint
    disk — the standard A/B tradeoff). ALL processes must call this
    together; the filesystem must be shared across hosts (GCS/NFS).
    """
    directory = Path(directory).absolute()
    topology = describe_topology(state)
    skeleton, arrays = _extract_device_arrays(state)
    target = _prepare_slot(directory)
    if arrays:
        import orbax.checkpoint as ocp
        with ocp.PyTreeCheckpointer() as checkpointer:
            checkpointer.save(directory / target / "arrays",
                              args=_orbax_save_args(arrays), force=True)
    _commit_slot(directory, target, skeleton, topology=topology)


class AsyncShardedCheckpointer:
    """Asynchronous variant of `save_state_sharded`.

    `save()` serializes device arrays to host memory and returns while
    Orbax writes to disk in the background; training continues
    immediately. The slot's commit marker (skeleton pickle) and the
    CURRENT pointer flip are deferred to `finalize_pending()` — called
    automatically at the start of the next `save()` and by `wait()` —
    so a crash mid-write leaves the previous checkpoint active, exactly
    like the synchronous A/B scheme. ALL processes must make the same
    calls in the same order.
    """

    def __init__(self) -> None:
        self._checkpointer = None
        self._pending: tp.Optional[
            tp.Tuple[Path, str, tp.Any, tp.Any, tp.Any]] = None

    def _orbax(self):
        if self._checkpointer is None:
            import orbax.checkpoint as ocp
            self._checkpointer = ocp.AsyncCheckpointer(
                ocp.PyTreeCheckpointHandler())
        return self._checkpointer

    def save(self, state: tp.Any, directory: AnyPath,
             on_commit: tp.Optional[tp.Callable[[], None]] = None) -> None:
        """Start an async save. `on_commit` runs (on every rank) once the
        checkpoint is durable AND active — put cleanup of superseded
        checkpoints there, never before."""
        self.finalize_pending()
        directory = Path(directory).absolute()
        topology = describe_topology(state)
        skeleton, arrays = _extract_device_arrays(state)
        target = _prepare_slot(directory)
        if arrays:
            self._orbax().save(directory / target / "arrays",
                               args=_orbax_save_args(arrays), force=True)
        self._pending = (directory, target, skeleton, on_commit, topology)

    def finalize_pending(self) -> None:
        """Block until the in-flight save is durable, then commit it."""
        if self._pending is None:
            return
        if self._checkpointer is not None:
            self._checkpointer.wait_until_finished()
        directory, target, skeleton, on_commit, topology = self._pending
        self._pending = None
        _commit_slot(directory, target, skeleton, on_commit,
                     topology=topology)

    # `wait` reads naturally at call sites that just need durability.
    wait = finalize_pending

    def close(self) -> None:
        self.finalize_pending()
        if self._checkpointer is not None:
            self._checkpointer.close()
            self._checkpointer = None


def _load_slot_skeleton(directory: Path, slot: str) -> tp.Any:
    """Verify one slot against its manifest and unpickle its skeleton.

    Raises CheckpointError (naming the slot and path) on integrity
    mismatch, a missing commit marker, or an unpicklable skeleton —
    the signal `load_state_sharded` uses to fall back to the sibling.
    """
    slot_dir = directory / slot
    if not (slot_dir / "state.pkl").exists():
        raise CheckpointError(f"slot {slot!r} of {directory} has no "
                              "committed state.pkl")
    problems = verify_slot(slot_dir)
    if problems:
        raise CheckpointError(
            f"slot {slot!r} of {directory} failed integrity verification: "
            + "; ".join(problems))
    return _read_state_file(slot_dir / "state.pkl",
                            f"slot {slot!r} skeleton")


def _mesh_record(mesh: tp.Any) -> tp.Dict[str, tp.Any]:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[name]) for name in mesh.axis_names]}


def _target_topology(placement_by_key: tp.Mapping[str, tp.Any],
                     mesh: tp.Any
                     ) -> tp.Tuple[str, tp.Optional[tp.Dict[str, tp.Any]]]:
    """(human description, topology record) of the restore TARGET —
    from the explicit mesh when given, else from the placements'
    shardings. Feeds `topology_differs` for elastic-resume detection
    and the error messages that must name the two topologies instead
    of leaking a raw Orbax/XLA error; None record = no placement info
    (host restore, no verdict)."""
    if mesh is not None:
        record = {"device_count": int(mesh.size),
                  "mesh": _mesh_record(mesh)}
        return format_topology(record), record
    device_ids: tp.Set[int] = set()
    mesh_info = None
    for target in placement_by_key.values():
        sharding = getattr(target, "sharding", None)
        if sharding is None:
            continue
        device_set = getattr(sharding, "device_set", None)
        if device_set:
            device_ids.update(d.id for d in device_set)
        target_mesh = getattr(sharding, "mesh", None)
        if mesh_info is None and hasattr(target_mesh, "axis_names"):
            mesh_info = _mesh_record(target_mesh)
    if device_ids:
        record = {"device_count": len(device_ids), "mesh": mesh_info}
        return format_topology(record), record
    return f"{jax.device_count()} device(s) (no explicit placements)", None


def load_state_sharded(directory: AnyPath, placements: tp.Any = None, *,
                       mesh: tp.Any = None) -> tp.Any:
    """Restore a `save_state_sharded` checkpoint.

    `placements` is a pytree mirroring (a prefix of) the saved state whose
    `jax.Array` leaves carry the target shardings: those leaves are
    restored by Orbax *directly onto their mesh placement* (each host
    reads only its shards). Leaves without a placement come back as host
    values. ALL processes must call this together.

    Sharding is a RESTORE-TIME choice, not a save-time fact: the target
    shardings need not match the topology the checkpoint was written on.
    With `mesh=` given, leaves without an explicit placement are placed
    by re-applying their SAVED logical spec (the slot's topology record,
    hash-verified with the rest of the slot) onto the target mesh — an
    N-chip checkpoint restores onto an M-chip mesh, and replicated /
    zero1 / fsdp layout changes are expressed simply by passing
    different placements. When the saved and target topologies differ,
    the reshard is logged loudly and passes the ``ckpt.reshard`` fault
    site; the Orbax shard reads are retried on transient IO failure.

    Each slot is verified against its integrity manifest before
    unpickling. When the ACTIVE slot is corrupt or unreadable, restore
    falls back to the sibling A/B slot with a loud WARN (the run resumes
    from the previous committed epoch — the checkpointed history rolls
    back with it, so epoch numbering stays consistent); only when both
    slots are bad does it raise `CheckpointCorrupted`.
    """
    from . import distrib
    directory = Path(directory).absolute()
    active = _read_slot_pointer(directory)
    if active is None:
        raise FileNotFoundError(f"No committed sharded checkpoint in {directory}")
    sibling = _SLOTS[1] if active == _SLOTS[0] else _SLOTS[0]
    skeleton = None
    # Slot selection (integrity hashing + skeleton unpickle) runs on
    # rank 0 only: hashing every host's Orbax shards on every rank
    # would read world_size x the full checkpoint off the shared FS at
    # exactly the post-preemption moment it is busiest. The verdict is
    # broadcast so all ranks restore the SAME slot.
    verdict: tp.Optional[tp.Tuple[str, str]] = None
    if distrib.is_rank_zero():
        slot = active
        errors: tp.List[str] = []
        for candidate in (active, sibling):
            try:
                skeleton = _load_slot_skeleton(directory, candidate)
                slot = candidate
                break
            except CheckpointError as exc:
                errors.append(str(exc))
                logger.warning(
                    "checkpoint slot %r of %s is unreadable or corrupt: %s%s",
                    candidate, directory, exc,
                    " — falling back to the sibling A/B slot"
                    if candidate == active else "")
        verdict = ("ok", slot) if skeleton is not None \
            else ("corrupt", " | ".join(errors))
        if skeleton is not None and slot != active:
            logger.warning(
                "RESTORED FROM FALLBACK SLOT %r of %s: the active slot %r "
                "was corrupt; the run resumes from the previously committed "
                "epoch.", slot, directory, active)
            # Repoint CURRENT at the slot that actually restored: the
            # next save targets the NON-pointed slot, and without this
            # flip it would overwrite the only good copy (this one)
            # while the corrupt ex-active slot survived — a crash
            # mid-save would then leave nothing restorable. Atomic,
            # verified-good target.
            with write_and_rename(directory / _POINTER, "w") as f:
                f.write(slot)
    if distrib.is_distributed():
        verdict = distrib.broadcast_object(verdict)
    assert verdict is not None
    outcome, payload = verdict
    if outcome == "corrupt":
        raise CheckpointCorrupted(
            f"no restorable checkpoint slot in {directory} "
            "(both A/B slots failed): " + payload)
    slot = payload
    if skeleton is None:
        # non-zero ranks: read the selected, already-verified slot
        skeleton = _read_state_file(directory / slot / "state.pkl",
                                    f"slot {slot!r} skeleton")

    slot_keys = [leaf.key for leaf in jax.tree_util.tree_leaves(
        skeleton, is_leaf=lambda x: isinstance(x, ArraySlot))
        if isinstance(leaf, ArraySlot)]

    placement_by_key: tp.Dict[str, tp.Any] = {}
    if placements is not None:
        def note(path, leaf):
            placement_by_key[jax.tree_util.keystr(path)] = leaf
            return leaf

        jax.tree_util.tree_map_with_path(note, placements)

    # Elastic resume: the slot's topology record describes the mesh the
    # checkpoint was WRITTEN on; the placements / `mesh` describe where
    # it is restoring TO. A mismatch is not an error — it is the
    # restore-time reshard this path exists for — but it must be loud,
    # and with `mesh=` the saved logical specs fill in placements for
    # every leaf the caller did not pin explicitly.
    topology = load_topology(directory, slot)
    target_desc, target_record = _target_topology(placement_by_key, mesh)
    saved_devices = (topology or {}).get("device_count")
    target_devices = (target_record or {}).get("device_count")
    resharding = topology_differs(topology, target_record)
    if mesh is not None:
        if topology is None:
            logger.warning(
                "load_state_sharded(%s, mesh=...): the checkpoint carries "
                "no topology record (saved before elastic checkpoints), so "
                "the target mesh cannot place leaves without explicit "
                "placements — they restore as host values.", directory)
        else:
            for key, placement in reshard_placements(topology, mesh).items():
                placement_by_key.setdefault(key, placement)
    if resharding:
        logger.warning(
            "RESHARDING AT RESTORE: checkpoint %s was saved on %s and is "
            "restoring onto %s — sharding is a restore-time choice; the "
            "state is re-placed from the slot's topology record.",
            directory, format_topology(topology), target_desc)

    arrays: tp.Dict[str, tp.Any] = {}
    if slot_keys:
        import orbax.checkpoint as ocp
        item: tp.Dict[str, tp.Any] = {}
        restore_args: tp.Dict[str, tp.Any] = {}
        for key in slot_keys:
            target = placement_by_key.get(key)
            # jax.Array, or an abstract jax.ShapeDtypeStruct carrying a
            # sharding (how BaseSolver.set_state_sharding declares ZeRO/
            # FSDP placements without materializing a template array) —
            # either way each host reads only its own shards.
            target_sharding = getattr(target, "sharding", None)
            if target_sharding is not None and hasattr(target, "shape"):
                item[key] = jax.ShapeDtypeStruct(tuple(target.shape),
                                                 target.dtype,
                                                 sharding=target_sharding)
                restore_args[key] = ocp.ArrayRestoreArgs(
                    sharding=target_sharding,
                    global_shape=tuple(target.shape), dtype=target.dtype)
            else:
                item[key] = 0
                restore_args[key] = ocp.RestoreArgs()

        def restore_arrays() -> tp.Dict[str, tp.Any]:
            # The retried unit is a read (idempotent, no collective);
            # under an active reshard it is also the ckpt.reshard fault
            # site, so elastic drills can prove a transient shard-read
            # failure mid-reshard is absorbed.
            if resharding:
                chaos.fault_point("ckpt.reshard", slot=slot,
                                  saved=saved_devices,
                                  target=target_devices)
            with ocp.PyTreeCheckpointer() as checkpointer:
                return checkpointer.restore(directory / slot / "arrays",
                                            item=item,
                                            restore_args=restore_args)

        try:
            arrays = call_with_retry(restore_arrays, name="ckpt.reshard"
                                     if resharding else "ckpt.load",
                                     retry_on=(OSError,))
        except Exception as exc:
            raise CheckpointError(
                f"Orbax array restore failed for slot {slot!r} under "
                f"{directory / slot / 'arrays'} (checkpoint saved on "
                f"{format_topology(topology)}; restore target "
                f"{target_desc}): {type(exc).__name__}: {exc}") from exc

    def fill(leaf):
        return arrays[leaf.key] if isinstance(leaf, ArraySlot) else leaf

    return jax.tree_util.tree_map(
        fill, skeleton, is_leaf=lambda x: isinstance(x, ArraySlot))


def place_like(template: tp.Any, restored: tp.Any) -> tp.Any:
    """Re-place restored host arrays onto the shardings of matching
    `template` leaves (shape must agree); a structure-tolerant recursive
    walk, so partially-matching or missing templates degrade gracefully
    to returning the restored value untouched.

    This is the framework half of restore: the solver knows the live
    (sharded) attribute values, so a checkpoint loaded as host numpy can
    be put back onto the mesh without every solver hand-rolling it.
    """
    if template is None:
        return restored
    if isinstance(template, jax.Array) or (
            isinstance(template, jax.ShapeDtypeStruct)
            and template.sharding is not None):
        if (hasattr(restored, "shape")
                and tuple(restored.shape) == tuple(template.shape)):
            if not getattr(template, "_committed", True):
                # The live leaf is uncommitted (e.g. `jit(optax.init)`
                # scalars like Adam's `count`, which land on the default
                # device but FOLLOW the other arguments of the next
                # jitted call). A device_put here would pin the restored
                # value to one device and the next multi-device step
                # would reject the mix ("incompatible devices") — keep
                # it uncommitted, exactly like the value it replaces.
                return jnp.asarray(restored)
            return jax.device_put(restored, template.sharding)
        return restored
    if isinstance(template, dict) and isinstance(restored, dict):
        return {key: place_like(template.get(key), value)
                for key, value in restored.items()}
    if (isinstance(template, tuple) and isinstance(restored, tuple)
            and len(template) == len(restored)):
        values = [place_like(t, r) for t, r in zip(template, restored)]
        if hasattr(restored, "_fields"):  # namedtuple (optax states)
            return type(restored)(*values)
        return type(restored)(values)
    if isinstance(template, list) and isinstance(restored, list):
        n = min(len(template), len(restored))
        return [place_like(template[i] if i < n else None, value)
                for i, value in enumerate(restored)]
    return restored


def save_sharded(state: tp.Any, directory: AnyPath) -> None:
    """Distributed checkpoint via Orbax: each host writes its own shards.

    Use for FSDP/model-parallel states that do not fit on one host. All
    processes must call this collectively.
    """
    import orbax.checkpoint as ocp
    path = Path(directory).absolute()
    with ocp.PyTreeCheckpointer() as checkpointer:
        checkpointer.save(path, args=_orbax_save_args(state), force=True)


def restore_sharded(directory: AnyPath, target: tp.Any = None) -> tp.Any:
    """Restore an Orbax checkpoint, re-sharding onto `target`'s shardings
    when a target pytree of abstract/concrete arrays is given."""
    import orbax.checkpoint as ocp
    path = Path(directory).absolute()
    with ocp.PyTreeCheckpointer() as checkpointer:
        if target is None:
            return checkpointer.restore(path)
        return checkpointer.restore(path, item=target)


# ---------------------------------------------------------------------------
# torch interop: the north-star requirement of round-tripping torch
# state_dicts alongside JAX pytrees (BASELINE.json), so existing flashy
# checkpoints can seed flashy_tpu runs and vice versa.
# ---------------------------------------------------------------------------

def to_torch_state_dict(tree: tp.Any, prefix: str = "") -> tp.Dict[str, tp.Any]:
    """Flatten a JAX/numpy pytree into a torch-style flat state dict:
    nested keys joined with '.', leaves as torch tensors."""
    import torch
    flat: tp.Dict[str, tp.Any] = {}

    def visit(node: tp.Any, path: str) -> None:
        if isinstance(node, dict):
            for key, value in node.items():
                visit(value, f"{path}.{key}" if path else str(key))
        elif isinstance(node, (list, tuple)):
            for index, value in enumerate(node):
                visit(value, f"{path}.{index}" if path else str(index))
        elif isinstance(node, (jax.Array, np.ndarray)):
            flat[path] = torch.from_numpy(np.ascontiguousarray(np.asarray(jax.device_get(node))))
        elif node is not None:
            flat[path] = node

    visit(tree, prefix)
    return flat


def import_flashy_checkpoint(path: AnyPath) -> tp.Dict[str, tp.Any]:
    """Load a reference-flashy `checkpoint.th` (torch.save format).

    Returns the solver-level state dict with torch tensors converted to
    numpy (nested flat state dicts are unflattened into pytrees), ready
    to feed `BaseSolver.load_state_dict` or to seed JAX params. Entries
    the reference always writes — 'history', 'xp.cfg', 'xp.sig'
    (reference flashy/solver.py:34-35) — pass through untouched.
    """
    import torch
    raw = torch.load(str(path), map_location="cpu", weights_only=False)

    def convert(node: tp.Any) -> tp.Any:
        # Deep conversion: optimizer states nest tensors several levels
        # down ({'state': {0: {'exp_avg': tensor}}, 'param_groups': ...}).
        if hasattr(node, "detach"):
            return node.detach().cpu().numpy()
        if isinstance(node, tp.Mapping):
            return {key: convert(value) for key, value in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(convert(value) for value in node)
        return node

    def maybe_unflatten(entry: tp.Any) -> tp.Any:
        # Module state dicts are flat with '.'-joined keys
        # ('layers.0.weight'); turn them into nested pytrees so they can
        # seed JAX params directly.
        if isinstance(entry, tp.Mapping) and entry and all(
                isinstance(k, str) for k in entry) and any(
                "." in k for k in entry):
            return from_torch_state_dict(entry)
        return entry

    return {name: maybe_unflatten(convert(entry))
            for name, entry in raw.items()}


def from_torch_state_dict(state_dict: tp.Mapping[str, tp.Any]) -> tp.Dict[str, tp.Any]:
    """Unflatten a torch-style state dict ('.'-joined keys, tensor leaves)
    into a nested dict of numpy arrays usable as a JAX pytree."""
    out: tp.Dict[str, tp.Any] = {}
    for dotted, value in state_dict.items():
        if hasattr(value, "detach"):  # torch tensor
            value = value.detach().cpu().numpy()
        *path, leaf = dotted.split(".")
        node = out
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return out
