# Seq2seq example — the encoder-decoder family through the full solver
# surface (the third member of the triad next to examples/lm and
# examples/mlm). Trains on synthetic sequence-transduction tasks
# (reverse/copy — solvable only through the cross-attention alignment)
# with teacher forcing, evaluates held-out loss AND exact-sequence
# accuracy via the KV-cached greedy decoder, and checkpoints/resumes
# like every other solver.
#
# TPU-first, same recipe as the siblings: one jitted sharded train
# step (param shardings via seq2seq_shardings -> XLA inserts the
# collectives), fused-KV cross-attention, f32 softmax/logits, cached
# O(T)-per-step decode for the accuracy stage.
"""Seq2seq solver: synthetic translation with cached greedy decode."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import flashy_tpu
from flashy_tpu.models import (Seq2SeqConfig, Seq2SeqTransformer,
                               cached_translate, seq2seq_shardings)
from flashy_tpu.parallel import make_mesh, shard_batch


def synthetic_pairs(vocab_size: int, task: str = "reverse", seed: int = 0):
    """(src, tgt) pair generator over (seed, subset, step) SeedSequence
    namespacing (same held-out discipline as examples/lm)."""
    if task not in ("reverse", "copy"):
        raise ValueError(f"task must be 'reverse' or 'copy', got {task!r}")

    def batch(batch_size: int, seq_len: int, step: int, subset: int = 0):
        gen = np.random.default_rng([seed, subset, step])
        # ids >= 2: 0 is reserved padding-ish, 1 is BOS
        src = gen.integers(2, vocab_size, (batch_size, seq_len)).astype(np.int32)
        tgt = src[:, ::-1].copy() if task == "reverse" else src.copy()
        return src, tgt

    return batch


class TranslateSolver(flashy_tpu.BaseSolver):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        model_cfg = Seq2SeqConfig(
            vocab_size=cfg.model.vocab_size, dim=cfg.model.dim,
            enc_layers=cfg.model.enc_layers,
            dec_layers=cfg.model.dec_layers,
            num_heads=cfg.model.num_heads, mlp_ratio=cfg.model.mlp_ratio,
            attention=cfg.model.attention,
            max_seq_len=max(int(cfg.src_len) + 1, 128))
        self.mesh = make_mesh({k: v for k, v in cfg.mesh.items()})
        self.model = Seq2SeqTransformer(model_cfg, mesh=self.mesh)

        src0 = jnp.zeros((1, cfg.src_len), jnp.int32)
        tgt0 = jnp.zeros((1, cfg.src_len), jnp.int32)
        variables = {"params": self.model.init(
            jax.random.PRNGKey(0), src0, tgt0)["params"]}
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            seq2seq_shardings(variables),
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

        self._pairs = synthetic_pairs(cfg.model.vocab_size,
                                      cfg.get("task", "reverse"))
        model, optim = self.model, self.optim
        bos = int(cfg.bos_token)

        def loss_fn(variables, batch):
            logits = model.apply(variables, batch["src"], batch["dec_in"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["tgt"]).mean()

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
        self._bos = bos

    def get_formatter(self, stage_name):
        return flashy_tpu.Formatter({"loss": ".4f", "grad_norm": ".2f",
                                     "seq_acc": ".1%", "tok_acc": ".1%"})

    def batch_at(self, step: int, eval_set: bool = False):
        cfg = self.cfg
        src, tgt = self._pairs(cfg.batch_size, cfg.src_len, step,
                               subset=1 if eval_set else 0)
        dec_in = np.concatenate(
            [np.full((src.shape[0], 1), self._bos, np.int32),
             tgt[:, :-1]], axis=1)
        batch = {"src": src, "tgt": tgt, "dec_in": dec_in}
        return {k: shard_batch(jnp.asarray(v), self.mesh,
                               batch_axes=("data", "fsdp"))
                for k, v in batch.items()}

    def train(self):
        average = flashy_tpu.averager()
        progress = self.log_progress(
            "train", range(self.cfg.steps_per_epoch), updates=5)
        metrics = {}
        for index in progress:
            global_step = (self.epoch - 1) * self.cfg.steps_per_epoch + index
            self.state, step_metrics = self._train_step(
                self.state, self.batch_at(global_step))
            metrics = average(step_metrics)
            progress.update(**metrics)
        jax.block_until_ready(self.state["params"])
        return metrics

    def valid(self):
        """Held-out teacher-forced loss + cached-decode accuracy."""
        average = flashy_tpu.averager()
        progress = self.log_progress(
            "valid", range(self.cfg.get("valid_steps", 4)), updates=2)
        metrics = {}
        for index in progress:
            batch = self.batch_at(index, eval_set=True)
            loss = self._eval_step(self.state["params"], batch)
            metrics = average({"loss": loss})
            progress.update(**metrics)
        every = int(self.cfg.get("translate_every", 1))
        if not every or self.epoch % every:
            return metrics
        # exact-sequence accuracy through the cached greedy decoder
        batch = self.batch_at(0, eval_set=True)
        out = cached_translate(self.model, self.state["params"],
                               batch["src"], max_new_tokens=self.cfg.src_len,
                               bos_id=self._bos)
        tgt = np.asarray(jax.device_get(batch["tgt"]))
        out = np.asarray(jax.device_get(out))
        metrics["tok_acc"] = float((out == tgt).mean())
        metrics["seq_acc"] = float((out == tgt).all(axis=1).mean())
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
    TranslateSolver(cfg).run()


if __name__ == "__main__":
    main()
