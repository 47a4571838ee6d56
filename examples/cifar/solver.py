# CIFAR solver — the role of reference examples/cifar/solver.py:12-63
# (ResNet-18, per-stage Formatter with acc/loss formats, image logging,
# cross-worker metric averaging), re-designed for TPU: the train/eval
# steps are jitted and data-parallel over the mesh via
# `flashy_tpu.parallel.wrap` (the DDP-replacement path the reference got
# from `distrib.sync_model`, examples/cifar/solver.py:51), batches are
# double-buffer prefetched host→HBM, and metrics come back as device
# scalars averaged on the host.
"""CIFAR-10 solver: flax ResNet on a data-parallel mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import optax

import flashy_tpu
from flashy_tpu import distrib
from flashy_tpu.data import prefetch_to_device
from flashy_tpu.models import resnet18, resnet50, vit_tiny
from flashy_tpu.parallel import make_mesh, wrap


class Solver(flashy_tpu.BaseSolver):
    def __init__(self, cfg, loaders, is_real: bool = False):
        super().__init__()
        self.cfg = cfg
        self.loaders = loaders
        self.is_real = is_real
        model_fn = {"resnet18": resnet18, "resnet50": resnet50,
                    "vit_tiny": vit_tiny}[cfg.model]
        self.model = model_fn(num_classes=10)

        n_data = cfg.data_parallel if cfg.data_parallel > 0 else len(jax.devices())
        self.mesh = make_mesh({"data": n_data})

        variables = self.model.init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 32, 32, 3)), train=False)
        steps_per_epoch = max(1, len(loaders["train"]))
        if cfg.max_batches is not None:
            # budgeted runs (max_batches caps each stage) must anneal
            # over the steps that will actually run, or the cosine never
            # leaves its peak and the run plateaus early
            steps_per_epoch = min(steps_per_epoch, cfg.max_batches)
        schedule = optax.cosine_decay_schedule(
            cfg.lr, cfg.epochs * steps_per_epoch)
        self.optim = optax.chain(
            optax.add_decayed_weights(cfg.weight_decay),
            optax.sgd(schedule, momentum=cfg.momentum, nesterov=True))
        # ViT has no BatchNorm: batch_stats is an empty dict then, and
        # the shared step functions thread it through untouched.
        self.state = {
            "params": variables["params"],
            "batch_stats": variables.get("batch_stats", {}),
            "opt_state": self.optim.init(variables["params"]),
        }
        self.register_stateful("state")
        self._train_step = wrap(self._make_train_step(), mesh=self.mesh)
        self._eval_step = wrap(self._make_eval_step(), mesh=self.mesh,
                               donate_state=False)

    def _make_train_step(self):
        model, optim = self.model, self.optim

        def step(state, batch):
            has_bn = bool(state["batch_stats"])

            def loss_fn(params):
                if has_bn:
                    logits, mutated = model.apply(
                        {"params": params,
                         "batch_stats": state["batch_stats"]},
                        batch["image"], train=True, mutable=["batch_stats"])
                    stats = mutated["batch_stats"]
                else:
                    logits = model.apply({"params": params}, batch["image"],
                                         train=True)
                    stats = state["batch_stats"]
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["label"]).mean()
                return loss, (logits, stats)

            (loss, (logits, batch_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"])
            updates, opt_state = optim.update(grads, state["opt_state"],
                                              state["params"])
            params = optax.apply_updates(state["params"], updates)
            acc = (logits.argmax(-1) == batch["label"]).mean()
            new_state = {"params": params, "batch_stats": batch_stats,
                         "opt_state": opt_state}
            return new_state, {"loss": loss, "acc": acc}

        return step

    def _make_eval_step(self):
        model = self.model

        def step(state, batch):
            # The valid loader is padded/masked (pad_to_even): batches
            # carry a "valid" 0/1 row mask. Sums (not means) come back so
            # the host can weight by the true valid count — padding rows
            # contribute nothing and sharded eval equals unsharded eval
            # exactly.
            variables = {"params": state["params"]}
            if state["batch_stats"]:
                variables["batch_stats"] = state["batch_stats"]
            logits = model.apply(variables, batch["image"], train=False)
            valid = batch["valid"]
            loss_vec = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["label"])
            correct = (logits.argmax(-1) == batch["label"]).astype(jnp.float32)
            return state, {"loss_sum": (loss_vec * valid).sum(),
                           "acc_sum": (correct * valid).sum(),
                           "n": valid.sum()}

        return step

    def get_formatter(self, stage_name):
        return flashy_tpu.Formatter({"acc": ".1%", "loss": ".5f",
                                     "images_per_sec": ".0f"})

    def _run_epoch(self, train: bool):
        import time
        loader = self.loaders["train" if train else "valid"]
        loader.set_epoch(self.epoch)
        step_fn = self._train_step if train else self._eval_step
        average = flashy_tpu.averager()
        progress = self.log_progress(self.current_stage, loader, updates=5)
        metrics = {}
        count = 0.0
        begin = time.time()
        if train:
            source = progress
        else:
            # fold the validity mask into the batch so it shards with it
            source = ({**batch, "valid": mask.astype(np.float32)}
                      for batch, mask in progress)
        batches = prefetch_to_device(source, size=2, mesh=self.mesh,
                                     batch_axes=("data",))
        for index, batch in enumerate(batches):
            if self.cfg.max_batches is not None and index >= self.cfg.max_batches:
                break
            self.state, step_metrics = step_fn(self.state, batch)
            if train:
                weight = len(batch["label"])
                metrics = average(step_metrics, weight=weight)
            else:
                sums = jax.device_get(step_metrics)
                weight = float(sums["n"])
                if weight:
                    metrics = average({"loss": sums["loss_sum"] / weight,
                                       "acc": sums["acc_sum"] / weight},
                                      weight=weight)
            progress.update(**metrics)
            count += weight
        jax.block_until_ready(self.state["params"])
        metrics["images_per_sec"] = count / max(time.time() - begin, 1e-9)
        if not train:
            self.log_image("valid", "sample",
                           np.asarray(jax.device_get(batch["image"][0])) * 0.25 + 0.5)
        # cross-process weighted average (no-op single process); within a
        # process the mesh already averaged over devices in-graph.
        return distrib.average_metrics(metrics, count)

    def run(self):
        restored = self.restore()
        self.logger.info("Restored: %s; starting at epoch %d", restored, self.epoch)
        self.log_hyperparams(dict(self.cfg))
        for epoch in range(self.epoch, self.cfg.epochs + 1):
            self.run_stage("train", self._run_epoch, train=True)
            self.run_stage("valid", self._run_epoch, train=False)
            self.commit()
        self._report_target_acc()

    def _report_target_acc(self):
        """BASELINE.md #2: to-baseline accuracy, judged on REAL data only."""
        target = self.cfg.get("target_acc")
        if not target or not self.history:
            return
        acc = self.history[-1].get("valid", {}).get("acc")
        if acc is None:
            return
        if not self.is_real:
            self.logger.info(
                "valid acc %.2f%% on SYNTHETIC data; target_acc=%.2f%% only "
                "applies to real CIFAR-10 (set data_root / FLASHY_TPU_CIFAR)",
                100 * acc, 100 * target)
        elif acc >= target:
            self.logger.info("baseline accuracy REACHED: %.2f%% >= %.2f%%",
                             100 * acc, 100 * target)
        else:
            self.logger.warning("baseline accuracy MISSED: %.2f%% < %.2f%%",
                                100 * acc, 100 * target)
