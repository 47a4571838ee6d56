# Masked-LM (BERT-style encoder) example — the bidirectional
# counterpart of examples/lm, exercising `TransformerConfig.causal=
# False` end-to-end: the same shared blocks, sharding rules, and solver
# machinery train an ENCODER with the standard 80/10/10 masking recipe.
# (The reference is model-agnostic and ships no encoder example either;
# this one exists because the bidirectional path is a first-class
# config here and deserves a runnable workload.)
#
# TPU-first details, same as examples/lm: jitted sharded step (XLA
# inserts the collectives from the param/batch shardings), masked-mean
# loss as sum/count (exact under data-parallel sharding), host-side
# masking kept to cheap numpy on the already-generated batch.
"""Masked-LM solver: bidirectional encoder training."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import flashy_tpu
from flashy_tpu.models import TransformerConfig, TransformerLM, transformer_shardings
from flashy_tpu.parallel import make_mesh, shard_batch

from ..lm.solver import synthetic_token_stream


class MLMSolver(flashy_tpu.BaseSolver):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        model_cfg = TransformerConfig(
            vocab_size=cfg.model.vocab_size, dim=cfg.model.dim,
            num_layers=cfg.model.num_layers, num_heads=cfg.model.num_heads,
            mlp_ratio=cfg.model.mlp_ratio, attention=cfg.model.attention,
            remat=cfg.model.get("remat", False),
            causal=False)
        self.mesh = make_mesh({k: v for k, v in cfg.mesh.items()})
        self.model = TransformerLM(model_cfg, mesh=self.mesh)

        tokens0 = jnp.zeros((1, min(cfg.seq_len, 128)), jnp.int32)
        variables = {"params": self.model.init(
            jax.random.PRNGKey(0), tokens0)["params"]}
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            transformer_shardings(variables),
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(variables, shardings)

        total_steps = max(cfg.epochs * cfg.steps_per_epoch, 2)
        warmup = min(cfg.warmup_steps, total_steps // 2)
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, cfg.lr, warmup, total_steps)
        self.optim = optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(schedule, weight_decay=cfg.weight_decay))
        opt_state = jax.jit(self.optim.init)(params)
        self.state = {"params": params, "opt_state": opt_state,
                      "step": jnp.zeros((), jnp.int32)}
        self.register_stateful("state")

        self._stream = synthetic_token_stream(cfg.model.vocab_size)
        model, optim = self.model, self.optim

        def loss_fn(variables, batch):
            # Loss over the SELECTED positions only, as masked sum /
            # count — exact under batch sharding (the mean of a masked
            # mean would weight shards unevenly).
            logits = model.apply(variables, batch["inputs"])
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["labels"])
            sel = batch["selected"].astype(jnp.float32)
            return (per_tok * sel).sum() / jnp.maximum(sel.sum(), 1.0)

        def train_step(state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
            updates, opt_state = optim.update(grads, state["opt_state"],
                                              state["params"])
            params = optax.apply_updates(state["params"], updates)
            return ({"params": params, "opt_state": opt_state,
                     "step": state["step"] + 1},
                    {"loss": loss, "grad_norm": optax.global_norm(grads)})

        self._train_step = jax.jit(train_step, donate_argnums=(0,))
        self._eval_step = jax.jit(loss_fn)

    def get_formatter(self, stage_name):
        return flashy_tpu.Formatter({"loss": ".4f", "ppl": ".1f",
                                     "grad_norm": ".2f"})

    def batch_at(self, step: int, eval_set: bool = False):
        """One masked batch: (inputs, labels, selected) sharded on the mesh.

        BERT recipe over `mask_prob` of the positions: 80% replaced by
        the [MASK] id, 10% by a random token, 10% kept — the model must
        predict the ORIGINAL token at every selected position. The
        stream emits tokens over vocab-1 ids with the configured
        `mask_token` id skipped, so [MASK] never occurs naturally.
        """
        cfg = self.cfg
        mask_id = int(cfg.mask_token)
        vocab = cfg.model.vocab_size
        if not 0 <= mask_id < vocab:
            raise ValueError(f"mask_token {mask_id} outside vocab {vocab}")
        tokens = self._stream(cfg.batch_size, cfg.seq_len, step,
                              subset=1 if eval_set else 0)
        # reserve the CONFIGURED [MASK] id: generate over V-1 ids and
        # shift everything >= mask_id up by one, so the id never occurs
        # naturally whatever the user picked
        tokens = tokens % (vocab - 1)
        tokens = tokens + (tokens >= mask_id)
        rng = np.random.default_rng([17, int(eval_set), step])
        sel = rng.random(tokens.shape) < cfg.mask_prob
        action = rng.random(tokens.shape)
        rand_tok = rng.integers(0, vocab - 1, tokens.shape)
        rand_tok = rand_tok + (rand_tok >= mask_id)
        inputs = tokens.copy()
        inputs[sel & (action < 0.8)] = mask_id
        swap = sel & (action >= 0.8) & (action < 0.9)
        inputs[swap] = rand_tok[swap]
        batch = {"inputs": inputs.astype(np.int32),
                 "labels": tokens.astype(np.int32),
                 "selected": sel}
        return {k: shard_batch(jnp.asarray(v), self.mesh,
                               batch_axes=("data", "fsdp"))
                for k, v in batch.items()}

    def train(self):
        average = flashy_tpu.averager()
        steps = range(self.cfg.steps_per_epoch)
        progress = self.log_progress("train", steps, updates=5)
        metrics = {}
        for index in progress:
            global_step = (self.epoch - 1) * self.cfg.steps_per_epoch + index
            self.state, step_metrics = self._train_step(
                self.state, self.batch_at(global_step))
            metrics = average(step_metrics)
            progress.update(**metrics)
        jax.block_until_ready(self.state["params"])
        metrics["ppl"] = float(np.exp(min(metrics["loss"], 20.0)))
        return metrics

    def valid(self):
        average = flashy_tpu.averager()
        steps = range(self.cfg.get("valid_steps", 4))
        progress = self.log_progress("valid", steps, updates=2)
        metrics = {}
        for index in progress:
            loss = self._eval_step(self.state["params"],
                                   self.batch_at(index, eval_set=True))
            metrics = average({"loss": loss})
            progress.update(**metrics)
        metrics["ppl"] = float(np.exp(min(metrics["loss"], 20.0)))
        return metrics

    def run(self):
        restored = self.restore()
        self.logger.info("Restored: %s; starting at epoch %d",
                         restored, self.epoch)
        for epoch in range(self.epoch, self.cfg.epochs + 1):
            self.run_stage("train", self.train)
            if self.cfg.get("valid_steps", 4):
                self.run_stage("valid", self.valid)
            self.commit()


@flashy_tpu.main(config_path="config")
def main(cfg):
    flashy_tpu.setup_logging()
    flashy_tpu.distrib.init()
    MLMSolver(cfg).run()


if __name__ == "__main__":
    main()
