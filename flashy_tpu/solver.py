# The Solver abstraction — the public API of the framework. Semantics
# parity with reference flashy/solver.py:30-211: a StateManager of
# registered stateful attributes, named stages, per-epoch metric
# accumulation, atomic commit (history + checkpoint) and restore.
#
# TPU-specific posture: the solver's stage loop stays imperative python
# (progress bars, metric averaging, optimizer-state threading), while the
# per-step work inside a stage should be a jitted function — build it
# with `flashy_tpu.parallel.wrap` for mesh data-parallelism. Host-side IO
# (checkpoint write, history update) is rank-zero gated; collectives must
# never be (see flashy_tpu.distrib notes).
"""BaseSolver: inherit, register stateful attributes, implement run()."""
from pathlib import Path
import logging
import time
import typing as tp

import jax

from . import checkpoint as _checkpoint
from . import distrib
from .utils import AnyPath as AnyPathT
from .distrib import is_rank_zero
from .formatter import Formatter
from .logging import LogProgressBar, ResultLogger
from .resilience.preemption import PreemptionInterrupt
from .state import StateManager, AttributeWrapper, StateDictSource
from .xp import get_xp

StageCallable = tp.Callable
logger = logging.getLogger(__name__)

CHECKPOINT_META_NAME = "checkpoint_meta.json"


def _spec_is_sharded(sharding: tp.Any) -> bool:
    """True for a NamedSharding whose spec names at least one mesh axis."""
    spec = getattr(sharding, "spec", None)
    return spec is not None and any(part is not None for part in spec)


def _tree_has_sharded_spec(shardings: tp.Any) -> bool:
    return any(_spec_is_sharded(leaf)
               for leaf in jax.tree_util.tree_leaves(
                   shardings, is_leaf=lambda x: hasattr(x, "spec")))


def _declared_placements(value: tp.Any, shardings: tp.Any) -> tp.Any:
    """Pair a live value with declared shardings into abstract
    placements: each array leaf becomes a ShapeDtypeStruct carrying the
    declared sharding (shape/dtype from the live leaf). A single
    sharding broadcasts over every leaf; otherwise structures must
    match (ValueError/TypeError propagates to the caller's fallback)."""
    if hasattr(shardings, "spec"):  # one sharding for the whole tree
        shardings = jax.tree_util.tree_map(lambda _: shardings, value)

    def combine(leaf, sharding):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype,
                                        sharding=sharding)
        return leaf

    return jax.tree_util.tree_map(combine, value, shardings)


class BaseSolver:
    """Base class for training solvers.

    A solver owns the experiment (`self.xp`), a registry of stateful
    attributes (`register_stateful`), and a result logger. Subclasses
    implement `run()`, typically::

        def run(self):
            self.restore()
            for epoch in range(self.epoch, self.cfg.epochs + 1):
                self.run_stage('train', self.do_train)
                self.run_stage('valid', self.do_valid)
                self.commit()

    Epochs are atomic: `commit()` appends the epoch's stage metrics to the
    history and writes the checkpoint, both atomically, so a preempted run
    resumes exactly at the last committed epoch.
    """

    checkpoint_name = "checkpoint.fsy"
    # How commit() persists state: 'single' = one pickle file (host
    # gather of sharded arrays — fine for small/replicated states);
    # 'sharded' = Orbax distributed save, each host writes only its own
    # shards (use at FSDP/model-parallel scale; needs a shared FS on
    # pods); 'auto' = sharded when the state is multi-host sharded or
    # larger than `sharded_checkpoint_min_bytes`.
    checkpoint_mode = "auto"
    sharded_checkpoint_min_bytes = 1 << 30
    # With sharded mode, write checkpoints asynchronously: commit()
    # returns once arrays are snapshotted and Orbax writes in the
    # background; the checkpoint becomes *active* (pointer flip) at the
    # next commit/restore, an explicit finalize_checkpoints(), or clean
    # interpreter exit (atexit). A crash mid-write keeps the previous
    # checkpoint restorable.
    checkpoint_async = False

    def __init__(self) -> None:
        self.stateful = StateManager()
        self.xp = get_xp()
        self.register_stateful("history")
        self.register_stateful("xp.cfg", "xp.sig", write_only=True)
        self.logger = logger
        self.result_logger = ResultLogger(self.logger)

        self._current_stage: tp.Optional[str] = None
        self._current_formatter: tp.Optional[Formatter] = None
        self._profile_folder: tp.Optional[Path] = None
        self._profile_stages: tp.Optional[tp.Set[str]] = None
        self._async_checkpointer: tp.Optional[tp.Any] = None
        # async-checkpoint bookkeeping: how many history entries the
        # in-flight save covers / the last finalized save covered, so a
        # deferred write failure (surfacing one commit later) can roll
        # history back to the last DURABLE epoch, not the current one.
        self._async_pending_epochs: tp.Optional[int] = None
        self._async_durable_epochs: tp.Optional[int] = None
        self._step_timers: tp.Dict[str, tp.Any] = {}
        self._state_shardings: tp.Dict[str, tp.Any] = {}
        self._recompiles_reported = 0
        self._preemption_guard: tp.Optional[tp.Any] = None
        self._preemption_mode = "finish_stage"
        self._hang_watchdog: tp.Optional[tp.Any] = None
        self._start_epoch()

    def _start_epoch(self) -> None:
        self._pending_metrics: tp.Dict[str, tp.Any] = {}

    @property
    def checkpoint_path(self) -> Path:
        return self.folder / self.checkpoint_name

    @property
    def sharded_checkpoint_path(self) -> Path:
        """Directory used by the Orbax sharded checkpoint mode."""
        return self.folder / (self.checkpoint_name + ".sharded")

    @property
    def history(self) -> tp.List[tp.Dict[str, tp.Any]]:
        """Per-epoch list of {stage_name: metrics} dicts."""
        return self.xp.link.history

    @property
    def folder(self) -> Path:
        return self.xp.folder

    @property
    def epoch(self) -> int:
        """Current epoch, starting at 1; resumes from history length."""
        return len(self.history) + 1

    # ------------------------------------------------------------------
    # logging
    # ------------------------------------------------------------------
    def init_tensorboard(self, **kwargs: tp.Any) -> None:
        """Attach a TensorBoard backend; see TensorboardLogger.from_xp."""
        self.result_logger.init_tensorboard(**kwargs)

    def init_wandb(self, **kwargs: tp.Any) -> None:
        """Attach a Weights & Biases backend; see WandbLogger.from_xp."""
        self.result_logger.init_wandb(**kwargs)

    def _check_in_stage(self) -> None:
        if self._current_stage is None:
            raise RuntimeError(
                "No stage is active: call this from within run_stage().")

    def log_progress(self, stage_name: str, iterable: tp.Iterable,
                     total: tp.Optional[int] = None, updates: int = 5,
                     **kwargs: tp.Any) -> LogProgressBar:
        """Wrap an iterable in a progress-logging iterator for this stage.

        With telemetry enabled (`enable_telemetry`), the progress bar
        also drives a `StepTimer`: every iteration is split into
        data-wait / host / device time, journaled per step, and the
        p50/p95/max summary lands in the stage metrics when the stage
        ends. Call `progress.observe(outputs)` with the step's jitted
        outputs to bound device time (the blocking wait at the observe
        call is charged to `device`, the rest of the step to `host`).
        """
        from . import observability
        telemetry = observability.get_telemetry()
        if telemetry is not None and "step_timer" not in kwargs:
            previous = self._step_timers.get(stage_name)
            if previous is not None:
                # a second loader in the same stage: journal the first
                # loader's in-flight step before handing over the slot
                # (its summary is superseded by the new timer's).
                previous.finish()
            timer = telemetry.step_timer(stage_name)
            self._step_timers[stage_name] = timer
            kwargs["step_timer"] = timer
        return self.result_logger.get_log_progress_bar(
            stage_name, iterable, total=total, updates=updates,
            step=self.epoch, step_name="epoch", formatter=self.formatter, **kwargs)

    def log_hyperparams(self, params: dict, metrics: tp.Optional[dict] = None) -> None:
        self.result_logger.log_hyperparams(params, metrics)

    def log_metrics(self, stage_name: str, metrics: dict,
                    formatter: tp.Optional[Formatter] = None) -> None:
        """Log metrics for a stage of the current epoch.

        Stage metrics from `run_stage` are logged automatically; use this
        for additional stages. Each stage name can be logged once per
        epoch. Outside a stage, pass `formatter` explicitly.
        """
        if stage_name in self._pending_metrics:
            raise RuntimeError(
                f"Metrics for stage {stage_name!r} were already logged during "
                f"epoch {self.epoch}; each stage may be logged once per epoch.")
        self._pending_metrics[stage_name] = metrics
        if formatter is None:
            formatter = self.formatter
        self.result_logger.log_metrics(stage_name, metrics, step=self.epoch,
                                       step_name="epoch", formatter=formatter)

    def log_audio(self, stage_name: str, key: str, audio: tp.Any, sample_rate: int,
                  **kwargs: tp.Any) -> None:
        self.result_logger.log_audio(stage_name, key, audio, sample_rate,
                                     self.epoch, **kwargs)

    def log_image(self, stage_name: str, key: str, image: tp.Any, **kwargs: tp.Any) -> None:
        self.result_logger.log_image(stage_name, key, image, self.epoch, **kwargs)

    def log_text(self, stage_name: str, key: str, text: str, **kwargs: tp.Any) -> None:
        self.result_logger.log_text(stage_name, key, text, self.epoch, **kwargs)

    # ------------------------------------------------------------------
    # state / checkpointing
    # ------------------------------------------------------------------
    def register_stateful(self, *args: str, write_only: bool = False) -> None:
        """Track attributes (dotted paths allowed) in the checkpoint.

        Registered attributes are saved on `commit()` and restored by
        `restore()`. Attributes may be JAX pytrees (params, optax states),
        objects with state_dict/load_state_dict, lists, dicts, or plain
        values. With `write_only=True` the value is recorded for forensics
        but never restored (used for `xp.cfg` / `xp.sig`).

        Registering the outermost stage of a `flashy_tpu.datapipe`
        pipeline (they implement the same protocol) makes `commit()`
        persist the exact input cursor, so a preempted run resumes
        token-exact mid-epoch — see `flashy_tpu.datapipe`.
        """
        for name in args:
            owner = self
            *path, leaf = name.split(".")
            for part in path:
                owner = getattr(owner, part)
            self.stateful.register(name, AttributeWrapper(owner, leaf), write_only)

    def _registered_datapipes(self) -> tp.List[tp.Tuple[str, tp.Any]]:
        """Registered stateful attributes that are datapipe iterators
        (CheckpointableIterator protocol: cursor state + close()). Their
        cursors ride the normal commit/restore path; this lookup exists
        so the preemption exit can also CLOSE them — stopping background
        prefetch threads from racing the emergency checkpoint finalize
        for file IO."""
        from .datapipe import CheckpointableIterator
        pipes = []
        for name, source in self.stateful.sources.items():
            if isinstance(source, AttributeWrapper):
                value = getattr(source.owner, source.name, None)
                if isinstance(value, CheckpointableIterator):
                    pipes.append((name, value))
        return pipes

    def set_state_sharding(self, name: str, shardings: tp.Any) -> None:
        """Declare target shardings for a registered stateful attribute.

        `shardings` is a pytree of `NamedSharding`s matching the
        attribute's structure (e.g. `parallel.zero_sharding(...)` for a
        ZeRO-1 optimizer state, `parallel.fsdp_sharding(...)` for FSDP
        params), or a single sharding applied to every leaf. Declaring a
        non-replicated sharding threads it through checkpointing both
        ways: `commit()`'s 'auto' mode picks the sharded (Orbax) path —
        the state is never gathered onto one host — and `restore()`
        places each restored leaf directly onto its declared sharding
        (each host reads only its own shards), instead of inheriting
        whatever placement the live attribute happened to have.
        """
        if name not in self.stateful.sources:
            raise KeyError(f"{name!r} is not a registered stateful "
                           f"attribute; call register_stateful({name!r}) "
                           "first.")
        self._state_shardings[name] = shardings

    def state_dict(self) -> tp.Any:
        return self.stateful.state_dict()

    def load_state_dict(self, state: tp.Any) -> None:
        self.stateful.load_state_dict(state)

    def _resolve_checkpoint_mode(self, state: tp.Any) -> str:
        if self.checkpoint_mode != "auto":
            return self.checkpoint_mode
        if any(_tree_has_sharded_spec(shardings)
               for shardings in self._state_shardings.values()):
            # Declared ZeRO/FSDP intent: never gather the state to one
            # host just because it happens to be small/addressable.
            return "sharded"
        arrays = [leaf for leaf in jax.tree_util.tree_leaves(state)
                  if isinstance(leaf, jax.Array)]
        if any(not leaf.is_fully_addressable for leaf in arrays):
            # Multi-host sharded state: a single-file save would allgather
            # every leaf onto each host — exactly what sharded mode avoids.
            return "sharded"
        total = sum(leaf.size * leaf.dtype.itemsize for leaf in arrays)
        return "sharded" if total >= self.sharded_checkpoint_min_bytes else "single"

    def commit(self, save_checkpoint: bool = True) -> None:
        """Close the epoch: append pending metrics to the history; write
        the checkpoint, then persist the history, both atomically.

        All processes append to their in-memory history (they computed the
        same metrics), so `epoch` stays consistent everywhere. Both save
        paths must run on EVERY process (single-file gathers sharded
        leaves — a collective; the Orbax path has every host write its own
        shards); only process 0 performs single-file/pointer IO.

        A failed checkpoint save rolls the in-memory history append back
        (and `history.json` is only updated after the save) — `epoch`
        must never run ahead of what is restorable, or the next commit
        would write history for an epoch no checkpoint ever saw.

        With a preemption guard enabled, a successfully committed epoch
        is also the preferred stop point: the boundary check here exits
        with the requeue code right after the epoch became durable.
        """
        pending = self._pending_metrics
        # Land the PREVIOUS epoch's in-flight async save before this
        # epoch's append: a deferred write failure belongs to the epochs
        # that save covered (rolled back inside), never to the epoch
        # being committed now.
        if save_checkpoint and self._async_checkpointer is not None:
            self._finalize_async_attributed()
        self.history.append(pending)
        self._start_epoch()
        try:
            if save_checkpoint:
                # the state snapshot happens after the append, so the
                # checkpointed history includes the epoch being committed
                state = self.state_dict()
                mode = self._resolve_checkpoint_mode(state)
                if mode == "sharded":
                    # Never leave a stale single-file checkpoint shadowing
                    # the newer sharded one — but only remove it once the
                    # sharded save is durable AND active, or a crash in the
                    # window would leave nothing restorable at all.
                    def drop_single_file():
                        if is_rank_zero() and self.checkpoint_path.exists():
                            self.checkpoint_path.unlink()

                    if self.checkpoint_async:
                        if self._async_checkpointer is None:
                            self._async_checkpointer = \
                                _checkpoint.AsyncShardedCheckpointer()
                            # A clean process exit must not discard the
                            # final epoch's in-flight save.
                            import atexit
                            atexit.register(self.finalize_checkpoints)

                        def on_async_commit(mode=mode, state=state):
                            # meta only once the save is durable AND
                            # active: a failed async save must not leave
                            # a fresh meta describing a checkpoint that
                            # never landed
                            drop_single_file()
                            if is_rank_zero():
                                self._write_checkpoint_meta(mode, state)

                        self._async_checkpointer.save(
                            state, self.sharded_checkpoint_path,
                            on_commit=on_async_commit)
                        self._async_pending_epochs = len(self.history)
                    else:
                        _checkpoint.save_state_sharded(
                            state, self.sharded_checkpoint_path)
                        drop_single_file()
                else:
                    _checkpoint.save_state_distributed(state, self.checkpoint_path)
                    if is_rank_zero() and self.sharded_checkpoint_path.exists():
                        import shutil
                        shutil.rmtree(self.sharded_checkpoint_path,
                                      ignore_errors=True)
                if is_rank_zero():
                    if not (mode == "sharded" and self.checkpoint_async):
                        # async saves write their meta from on_commit
                        self._write_checkpoint_meta(mode, state)
                    self.logger.debug("Checkpoint saved (%s mode) under %s",
                                      mode, self.folder)
        except BaseException:
            # Roll back so epoch/history never run ahead of the last
            # restorable checkpoint; the epoch's metrics stay pending, so
            # a retried commit() (or a restart) stays consistent.
            self.history.pop()
            self._pending_metrics = pending
            raise
        if is_rank_zero():
            self.xp.link.update_history(self.history)
        self._maybe_preempt(
            f"commit boundary (epoch {len(self.history)} committed)")

    def _write_checkpoint_meta(self, mode: str, state: tp.Any) -> None:
        """Persist how this checkpoint's state was laid out (rank 0).

        `checkpoint_meta.json` records the save mode and the
        `parallel.zero.describe_state_sharding` classification
        (replicated / zero1 / fsdp + axes) so `python -m
        flashy_tpu.info` can show the state-sharding mode a restored
        solver will come back with. Best-effort: a failure here must
        never fail the commit the checkpoint already survived.
        """
        import json

        from .utils import write_and_rename
        try:
            from .parallel.zero import describe_state_sharding
            # Declared shardings (set_state_sharding) describe the
            # layout the state restores INTO, which is what an operator
            # wants to see — overlay them over the live placements.
            state = dict(state)
            for name, shardings in self._state_shardings.items():
                if state.get(name) is not None:
                    try:
                        state[name] = _declared_placements(state[name],
                                                           shardings)
                    except (ValueError, TypeError):
                        pass
            topology = _checkpoint.describe_topology(state)
            topology.pop("leaves", None)  # per-leaf specs live in the slot
            meta = {"mode": mode, "time": time.time(),
                    "state_sharding": describe_state_sharding(state),
                    "topology": topology}
            with write_and_rename(self.folder / CHECKPOINT_META_NAME,
                                  "w") as f:
                json.dump(meta, f, indent=2)
        except Exception:
            self.logger.exception("could not write %s", CHECKPOINT_META_NAME)

    def finalize_checkpoints(self) -> None:
        """Block until any in-flight async checkpoint is durable and
        active. Call at the end of `run()` when `checkpoint_async` is on
        (commit() and restore() also finalize the previous save)."""
        if self._async_checkpointer is not None:
            self._finalize_async_attributed()

    def _finalize_async_attributed(self) -> None:
        """`finalize_pending` with deferred-failure attribution.

        An async save's write failure surfaces here, one commit after
        the epoch it covered — so on failure, every history entry past
        the last DURABLE save is rolled back (in memory and in
        `history.json`) before re-raising. This keeps `epoch` from
        running ahead of what `restore()` can deliver on the async path
        too, the same invariant `commit()`'s rollback provides for
        synchronous saves.
        """
        assert self._async_checkpointer is not None
        try:
            self._async_checkpointer.finalize_pending()
        except BaseException:
            durable = self._async_durable_epochs or 0
            if self._async_pending_epochs is not None \
                    and len(self.history) > durable:
                self.logger.warning(
                    "async checkpoint failed: rolling history back from "
                    "%d to the last durable epoch %d.",
                    len(self.history), durable)
                del self.history[durable:]
                if is_rank_zero():
                    try:
                        self.xp.link.update_history(self.history)
                    except OSError:
                        self.logger.exception(
                            "could not re-sync history.json after the "
                            "failed async checkpoint; restore() recovers "
                            "the consistent history from the last durable "
                            "checkpoint.")
            self._async_pending_epochs = None
            raise
        if self._async_pending_epochs is not None:
            self._async_durable_epochs = self._async_pending_epochs
            self._async_pending_epochs = None

    def _detect_checkpoint(self) -> int:
        """0 = none, 1 = single-file, 2 = sharded (preferred when both)."""
        self.finalize_checkpoints()
        if _checkpoint.sharded_checkpoint_exists(self.sharded_checkpoint_path):
            return 2
        if self.checkpoint_path.exists():
            return 1
        return 0

    def _restore_placements(self) -> tp.Dict[str, tp.Any]:
        """Current live values of plain stateful attributes, used as
        sharding templates when re-placing a restored checkpoint onto the
        mesh. Protocol objects restore themselves and are skipped.
        Attributes with declared shardings (`set_state_sharding`) are
        overlaid as abstract ShapeDtypeStructs carrying those shardings,
        so restore places them as DECLARED — the live value only
        contributes shapes/dtypes."""
        placements: tp.Dict[str, tp.Any] = {}
        for name, source in self.stateful.sources.items():
            if isinstance(source, AttributeWrapper):
                value = getattr(source.owner, source.name, None)
                if not isinstance(value, StateDictSource):
                    shardings = self._state_shardings.get(name)
                    if shardings is not None and value is not None:
                        try:
                            value = _declared_placements(value, shardings)
                        except (ValueError, TypeError):
                            self.logger.warning(
                                "declared state sharding for %r does not "
                                "match the live value's structure; restore "
                                "falls back to the live placements.", name)
                    placements[name] = value
        return placements

    def _saved_topology(self) -> tp.Optional[tp.Dict[str, tp.Any]]:
        """The topology record the checkpoint was written with: the slot's
        hash-verified `topology.json` for sharded checkpoints, the
        `checkpoint_meta.json` mirror for single-file ones. None when the
        checkpoint predates topology metadata."""
        return _checkpoint.load_saved_topology(
            self.sharded_checkpoint_path, self.folder / CHECKPOINT_META_NAME)

    def _note_elastic_resume(self, saved: tp.Dict[str, tp.Any],
                             live: tp.Dict[str, tp.Any]) -> None:
        """The loud half of an elastic resume: the topology the
        checkpoint was saved on differs from the one it is restoring
        onto — WARN and journal an `elastic_resume` record through the
        Tracer (with the datapipe cursors that will re-split), so fleet
        churn is reconstructible post-mortem."""
        datapipes = [name for name, _ in self._registered_datapipes()]
        self.logger.warning(
            "ELASTIC RESUME: checkpoint was saved on %s and is restoring "
            "onto %s — state will be re-placed (resharded) onto the live "
            "topology and datapipe cursors re-split (%s).",
            _checkpoint.format_topology(saved),
            _checkpoint.format_topology(live),
            ", ".join(repr(n) for n in datapipes) or "none registered")
        from . import observability
        telemetry = observability.get_telemetry()
        if telemetry is not None:
            telemetry.record({
                "type": "elastic_resume", "epoch": self.epoch,
                "saved_device_count": saved.get("device_count"),
                "live_device_count": live.get("device_count"),
                "saved_topology": _checkpoint.format_topology(saved),
                "live_topology": _checkpoint.format_topology(live),
                "datapipes": datapipes})

    def restore(self) -> bool:
        """Load the checkpoint if one exists. Returns True on success.

        Restored device arrays are automatically placed back onto the
        shardings of the corresponding live attributes — solvers never
        hand-roll `device_put` after restore. Sharding is a restore-time
        choice: when the saved topology (mesh shape / device count,
        recorded at commit) differs from the live one — fleet churn,
        the elastic-resume case — the mismatch is WARNed and journaled
        as an `elastic_resume` record, the state is resharded onto the
        live placements at load (`ckpt.reshard` fault site), and
        registered datapipe cursors re-split onto the new world size
        (`datapipe.resplit`). In multi-host runs, all processes verify
        they see the same checkpoint (a pod without a shared filesystem
        would otherwise silently diverge: rank 0 restores epoch N while
        the others restart at epoch 1, and the next collective
        deadlocks)."""
        kind = self._detect_checkpoint()
        if distrib.is_distributed():
            kind_on_zero = distrib.broadcast_object(kind)
            if kind_on_zero != kind:
                raise RuntimeError(
                    f"Checkpoint mismatch across hosts: process 0 sees "
                    f"checkpoint kind {kind_on_zero}, process {distrib.rank()} "
                    f"sees {kind} (0=none, 1=single, 2=sharded). Checkpoints "
                    f"must live on a filesystem shared by all hosts.")
        if kind == 0:
            return False
        placements = self._restore_placements()
        saved_topology = self._saved_topology()
        if saved_topology is not None:
            live_topology = _checkpoint.describe_topology(
                {name: value for name, value in placements.items()
                 if value is not None})
            if _checkpoint.topology_differs(saved_topology, live_topology):
                self._note_elastic_resume(saved_topology, live_topology)
        if kind == 2:
            state = _checkpoint.load_state_sharded(
                self.sharded_checkpoint_path, placements)
        else:
            state = _checkpoint.load_state(self.checkpoint_path)
            state = {name: _checkpoint.place_like(placements.get(name), entry)
                     for name, entry in state.items()}
        self.load_state_dict(state)
        self.logger.debug("Checkpoint restored (kind %d) from %s", kind, self.folder)
        return True

    # ------------------------------------------------------------------
    # stages
    # ------------------------------------------------------------------
    def enable_profiling(self, folder: tp.Optional[AnyPathT] = None,
                         stages: tp.Optional[tp.Sequence[str]] = None) -> None:
        """Capture a TPU profiler trace around each (selected) stage.

        Traces land in `<xp.folder>/profiles/` (TensorBoard-viewable, XLA
        op-level timeline incl. collectives) on process 0. The reference
        ships no profiler (SURVEY §5: absent as a subsystem — only the
        per-stage `duration` metric); this is the additive TPU-native
        counterpart. Call once before `run()`.
        """
        self._profile_folder = Path(folder) if folder else self.folder / "profiles"
        self._profile_stages = set(stages) if stages else None

    def _should_profile(self, stage_name: str) -> bool:
        if self._profile_folder is None or not is_rank_zero():
            return False
        return self._profile_stages is None or stage_name in self._profile_stages

    def enable_telemetry(self, **kwargs: tp.Any) -> tp.Any:
        """Turn runtime telemetry on for this run (host-side tracing,
        per-step data-wait/host/device timing, recompile watchdog,
        per-rank heartbeats). Artifacts land in the XP folder:
        `trace.json` (Perfetto-loadable), `telemetry.jsonl` and
        `heartbeats/`. Complements `enable_profiling` (the XLA device
        trace); both can be on at once. Call once before `run()`;
        returns the `observability.Telemetry` (e.g. to
        `telemetry.watch(jitted_step)` the step functions).
        """
        from . import observability
        kwargs.setdefault("folder", self.folder)
        return observability.enable_telemetry(**kwargs)

    # ------------------------------------------------------------------
    # fault tolerance (flashy_tpu.resilience)
    # ------------------------------------------------------------------
    def enable_preemption_guard(self, mode: str = "finish_stage",
                                **kwargs: tp.Any) -> tp.Any:
        """Stop cooperatively (and pod-consistently) on SIGTERM/SIGINT.

        The one switch, mirroring `enable_telemetry`. Once a signal
        lands on ANY rank, all ranks agree on it at the next stage or
        commit boundary (one cheap distrib reduction — a single rank
        must never skip a collective unilaterally); the solver then
        finalizes any in-flight async checkpoint, writes a
        `preempted.json` marker, and exits with the requeue-friendly
        code `resilience.EXIT_PREEMPTED` (75, EX_TEMPFAIL). The epoch
        in flight is never half-committed: the run resumes exactly at
        the last committed epoch.

        `mode`: ``'finish_stage'`` (default) lets the in-flight stage
        run to completion and stops at the next boundary (if that
        boundary is `commit()`, the full epoch lands first);
        ``'abandon_stage'`` additionally makes `check_preemption()`
        raise inside the stage, abandoning it mid-flight — wire
        `self.check_preemption()` into your step loop to use it.
        Remaining kwargs go to `resilience.enable_preemption_guard`
        (e.g. ``install=False`` to skip real signal handlers in tests).
        Call once before `run()`; returns the guard.
        """
        if mode not in ("finish_stage", "abandon_stage"):
            raise ValueError(f"unknown preemption mode {mode!r}; expected "
                             "'finish_stage' or 'abandon_stage'")
        from . import resilience
        self._preemption_guard = resilience.enable_preemption_guard(**kwargs)
        self._preemption_mode = mode
        return self._preemption_guard

    def check_preemption(self, every: int = 1) -> bool:
        """Cooperative in-stage preemption check for step loops.

        COLLECTIVE when it syncs: every rank must call it the same
        number of times (step loops run in lockstep, so calling it once
        per step — optionally throttled with `every=N` by call count,
        never wall time — is safe). In ``'abandon_stage'`` mode it
        raises `PreemptionInterrupt` once the pod agrees to stop; in
        ``'finish_stage'`` mode it only returns the verdict.
        """
        guard = self._preemption_guard
        if guard is None:
            return False
        agreed = guard.check(every=every)
        if (agreed and self._preemption_mode == "abandon_stage"
                and self._current_stage is not None):
            raise PreemptionInterrupt(
                f"preemption agreed mid-stage {self._current_stage!r} "
                f"(epoch {self.epoch})")
        return agreed

    def _maybe_preempt(self, where: str) -> None:
        """Stage/commit-boundary check: collective agreement, then the
        emergency exit path. Call sites must be reached by every rank."""
        if self._preemption_guard is not None \
                and self._preemption_guard.should_stop():
            self._preempt_exit(where)

    def _preempt_exit(self, where: str) -> tp.NoReturn:
        """The emergency commit: make everything already committed
        durable and active (finalize the in-flight async checkpoint),
        flush telemetry, leave a requeue marker, and exit with the
        requeue code. Never commits a partial epoch — that is what
        keeps resume exact."""
        guard = self._preemption_guard
        assert guard is not None
        committed = len(self.history)
        for name, pipe in self._registered_datapipes():
            # Freeze the input pipeline first: its cursor was already
            # captured at the last commit; letting prefetch workers keep
            # streaming would only contend with the checkpoint finalize.
            try:
                pipe.close()
            except Exception:
                self.logger.exception("could not close datapipe %r", name)
        self.logger.warning(
            "preemption (%s): stopping at %s; last committed epoch is %d; "
            "exiting with code %d — requeue and rerun to resume.",
            guard.signal_name or "requested", where, committed,
            guard.exit_code)
        try:
            self.finalize_checkpoints()
        finally:
            from . import observability
            telemetry = observability.get_telemetry()
            if telemetry is not None:
                telemetry.heartbeat.beat(epoch=self.epoch, stage="preempted",
                                         force=True)
                telemetry.record({"type": "preempted", "where": where,
                                  "epoch": self.epoch,
                                  "committed_epochs": committed,
                                  "signal": guard.signal_name})
                telemetry.export()
            if is_rank_zero():
                import json
                from .utils import write_and_rename
                with write_and_rename(self.folder / "preempted.json",
                                      "w") as f:
                    json.dump({"time": time.time(), "where": where,
                               "committed_epochs": committed,
                               "signal": guard.signal_name,
                               "exit_code": guard.exit_code}, f, indent=2)
        raise SystemExit(guard.exit_code)

    def enable_hang_watchdog(self, warn_after: float = 120.0,
                             abort_after: tp.Optional[float] = None,
                             **kwargs: tp.Any) -> tp.Any:
        """Start a background `resilience.HangWatchdog` over this XP's
        heartbeat files: WARNs with a straggler report when any rank's
        heartbeat stalls past `warn_after` seconds, and (optionally)
        aborts the process past `abort_after` — turning a silent hung
        pod into a loud requeueable crash. Requires `enable_telemetry()`
        (heartbeats are its artifact). Returns the started watchdog.
        """
        from .resilience import HangWatchdog
        from .xp import HEARTBEAT_DIR_NAME
        if self._hang_watchdog is not None:
            self._hang_watchdog.stop()
        self._hang_watchdog = HangWatchdog(
            self.folder / HEARTBEAT_DIR_NAME, warn_after=warn_after,
            abort_after=abort_after, **kwargs)
        return self._hang_watchdog.start()

    def get_formatter(self, stage_name: str) -> Formatter:
        """Override to customize metric display per stage."""
        return Formatter()

    @property
    def formatter(self) -> Formatter:
        self._check_in_stage()
        assert self._current_formatter is not None
        return self._current_formatter

    @property
    def current_stage(self) -> str:
        self._check_in_stage()
        assert self._current_stage is not None
        return self._current_stage

    def run_stage(self, stage_name: str, method: StageCallable,
                  *args: tp.Any, **kwargs: tp.Any) -> tp.Dict[str, tp.Any]:
        """Run one named stage of the current epoch.

        The returned metrics dict (or {}) gets a `duration` entry injected
        and is logged under `stage_name`. Stage state (current_stage,
        formatter) is cleared even on exception; metrics of a failed stage
        are never committed.

        With a preemption guard enabled, the stage boundary is where all
        ranks agree on a pending stop: an agreed preemption exits here
        (requeue-friendly) instead of starting the new stage, and a
        stage abandoned mid-flight by `check_preemption()` takes the
        same emergency exit — its metrics are never committed.
        """
        self._maybe_preempt(f"boundary before stage {stage_name!r} "
                            f"(epoch {self.epoch})")
        assert self._current_stage is None, "stages cannot nest"
        self._current_stage = stage_name
        self._current_formatter = self.get_formatter(stage_name)

        from . import observability
        telemetry = observability.get_telemetry()
        begin = time.time()
        try:
            if telemetry is not None:
                telemetry.heartbeat.beat(epoch=self.epoch, stage=stage_name,
                                         force=True)
            if self._should_profile(stage_name):
                import jax.profiler
                self._profile_folder.mkdir(parents=True, exist_ok=True)
                with jax.profiler.trace(str(self._profile_folder)):
                    metrics = self._run_stage_traced(telemetry, stage_name,
                                                     method, *args, **kwargs)
            else:
                metrics = self._run_stage_traced(telemetry, stage_name,
                                                 method, *args, **kwargs)
            if metrics is None:
                metrics = {}
            if telemetry is not None:
                timer = self._step_timers.pop(stage_name, None)
                if timer is not None:
                    timer.finish()
                    for key, value in timer.summary().items():
                        metrics.setdefault(key, value)
                # per-stage delta, not the run-wide total: one recompile
                # long ago must not read as "recompiling every stage"
                recompiles = sum(telemetry.watchdog.summary().values())
                if recompiles > self._recompiles_reported:
                    metrics.setdefault(
                        "recompiles", recompiles - self._recompiles_reported)
                self._recompiles_reported = recompiles
            metrics["duration"] = time.time() - begin
            self.log_metrics(stage_name, metrics)
        except PreemptionInterrupt:
            # cooperative mid-stage abandonment ('abandon_stage' mode):
            # the finally below still journals/flushes, then we take the
            # emergency exit — this stage's metrics are never committed.
            self.logger.warning("stage %r abandoned mid-flight on "
                                "preemption.", stage_name)
            self._preempt_exit(f"mid-stage {stage_name!r} abandoned "
                               f"(epoch {self.epoch})")
        finally:
            self._current_stage = None
            self._current_formatter = None
            if telemetry is not None:
                # no-op on the success path (already popped above); on a
                # raising stage this journals the crashing step — the
                # record you want post-mortem — before the export below.
                timer = self._step_timers.pop(stage_name, None)
                if timer is not None:
                    timer.finish()
                telemetry.heartbeat.beat(epoch=self.epoch, stage=stage_name,
                                         force=True)
                telemetry.record({"type": "stage", "stage": stage_name,
                                  "epoch": self.epoch,
                                  "duration": time.time() - begin})
                telemetry.export()
        return metrics

    def _run_stage_traced(self, telemetry: tp.Any, stage_name: str,
                          method: StageCallable, *args: tp.Any,
                          **kwargs: tp.Any) -> tp.Any:
        if telemetry is None:
            return method(*args, **kwargs)
        with telemetry.span(f"stage/{stage_name}", epoch=self.epoch):
            return method(*args, **kwargs)

    def run(self) -> None:
        raise NotImplementedError()
