# Transformer-LM solver — the flagship workload (the AudioCraft-style
# "downstream Flashy user" of BASELINE.json configs[4]). Demonstrates
# the full parallelism surface on one mesh: data parallelism, FSDP
# parameter sharding, megatron-style tensor parallelism and ring
# attention sequence parallelism, all expressed as shardings on a single
# jitted train step (placement propagates from the arrays; XLA inserts
# the collectives).
"""LM solver: sharded decoder-only language model training."""
from dataclasses import replace as dataclasses_replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import flashy_tpu
from flashy_tpu.models import TransformerConfig, TransformerLM, transformer_shardings
from flashy_tpu.parallel import make_mesh, shard_batch


def synthetic_token_stream(vocab_size: int, seed: int = 0):
    """Deterministic Markov-ish token generator: next-token structure a
    model can actually learn, so loss curves are meaningful without a
    real corpus (zero-egress environments).

    `subset` namespaces independent sample streams over the SAME token
    distribution (the Markov transition table depends only on `seed`):
    train draws subset 0, eval subset 1. The streams are separated by
    feeding (seed, subset, step) to numpy's SeedSequence — proper
    entropy hashing, unlike an arithmetic step offset, which collides
    once training steps walk into the offset range."""
    rng = np.random.default_rng(seed)
    mixing = rng.integers(1, vocab_size - 1, size=257)

    def batch(batch_size: int, seq_len: int, step: int,
              subset: int = 0) -> np.ndarray:
        gen = np.random.default_rng([seed, subset, step])
        tokens = np.empty((batch_size, seq_len), np.int64)
        tokens[:, 0] = gen.integers(0, vocab_size, batch_size)
        noise = gen.random((batch_size, seq_len)) < 0.15
        jumps = gen.integers(0, vocab_size, (batch_size, seq_len))
        for t in range(1, seq_len):
            follow = (tokens[:, t - 1] * 31 + mixing[tokens[:, t - 1] % 257]) % vocab_size
            tokens[:, t] = np.where(noise[:, t], jumps[:, t], follow)
        return tokens.astype(np.int32)

    return batch


class LMSolver(flashy_tpu.BaseSolver):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.pipe_stages = int(cfg.mesh.get("pipe", 1))
        # Pipeline parallelism streams the scan-stacked block params
        # over the 'pipe' axis (models/pipelined.py), so pipe>1 forces
        # the stacked layout.
        scan_layers = bool(cfg.model.get("scan_layers", False)) or self.pipe_stages > 1
        model_cfg = TransformerConfig(
            vocab_size=cfg.model.vocab_size, dim=cfg.model.dim,
            num_layers=cfg.model.num_layers, num_heads=cfg.model.num_heads,
            mlp_ratio=cfg.model.mlp_ratio, attention=cfg.model.attention,
            remat=cfg.model.get("remat", False),
            remat_policy=cfg.model.get("remat_policy", "full"),
            scan_layers=scan_layers,
            moe_experts=cfg.model.get("moe_experts", 0),
            moe_top_k=cfg.model.get("moe_top_k", 1),
            moe_capacity_factor=cfg.model.get("moe_capacity_factor", 1.25),
            moe_dispatch=cfg.model.get("moe_dispatch", "einsum"))
        self.mesh = make_mesh({k: v for k, v in cfg.mesh.items()})
        self.model = TransformerLM(model_cfg, mesh=self.mesh)

        # Params are identical across attention implementations and MoE
        # dispatch modes (all share _router_and_weights), so init
        # through a dense/replicated twin: cheap, shape-unconstrained,
        # no collectives at init time (dropless_ep would shard_map).
        init_dispatch = cfg.model.get("moe_dispatch", "einsum")
        if init_dispatch == "dropless_ep":
            init_dispatch = "einsum"
        init_model = TransformerLM(
            dataclasses_replace(model_cfg, attention="dense",
                                moe_dispatch=init_dispatch))
        tokens0 = jnp.zeros((1, min(cfg.seq_len, 128)), jnp.int32)
        variables = init_model.init(jax.random.PRNGKey(0), tokens0)
        # keep only real parameters — init may also return sown
        # collections (MoE aux losses) that must not enter the optimizer
        variables = {"params": variables["params"]}
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            transformer_shardings(variables),
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(variables, shardings)

        total_steps = max(cfg.epochs * cfg.steps_per_epoch, 2)
        warmup = min(cfg.warmup_steps, total_steps // 2)  # short-run safe
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, cfg.lr, warmup, total_steps)
        self.optim = optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(schedule, weight_decay=cfg.weight_decay))
        # Every state leaf gets a DECLARED layout (the train step pins
        # its outputs to it, see _jit_train_step): moments live exactly
        # where their parameters live — FSDP'd optimizer state — and
        # counters are replicated. Left to propagation, `zeros_like`
        # carries no layout and the moments come back replicated.
        replicated = NamedSharding(self.mesh, P())
        opt_shardings = optax.tree_map_params(
            self.optim, lambda _, sharding: sharding,
            jax.eval_shape(self.optim.init, params), shardings,
            transform_non_params=lambda _: replicated)
        opt_state = jax.jit(self.optim.init,
                            out_shardings=opt_shardings)(params)
        self.state = {"params": params, "opt_state": opt_state,
                      "step": jax.device_put(jnp.zeros((), jnp.int32),
                                             replicated)}
        # Optional parameter EMA (ema_decay > 0): the f32 shadow lives
        # INSIDE the jitted step (co-sharded with the params — zero
        # extra collectives, 1/N HBM under FSDP) and eval runs on it.
        self.ema_decay = float(cfg.get("ema_decay", 0.0))
        if self.ema_decay > 0.0:
            self.state["ema"] = self._ema_shadow(params)
        # restore() re-places every restored leaf onto the live state's
        # shardings automatically — no hand-rolled device_put needed.
        self.register_stateful("state")

        self._stream = synthetic_token_stream(cfg.model.vocab_size)

        model, optim = self.model, self.optim

        moe = model_cfg.moe_experts > 0
        aux_weight = cfg.model.get("moe_aux_weight", 0.01)
        pipe_stages = self.pipe_stages
        pipe_micro = cfg.get("pipeline_microbatches", None)
        # Schedule selection: 'gpipe' (fill-drain, O(M) activations),
        # '1f1b' (PipeDream-flush, O(S) activation stash; interleave>1
        # adds virtual stages that divide the bubble), or 'packed_1f1b'
        # (training ticks ~halved: steady-state F and B co-scheduled
        # into one tick, gradients bit-identical to '1f1b').
        self.pipe_schedule = cfg.get("pipeline_schedule", "gpipe")
        self.pipe_interleave = int(cfg.get("pipeline_interleave", 1))
        from flashy_tpu.parallel.schedules import KNOWN_SCHEDULES
        if self.pipe_schedule not in KNOWN_SCHEDULES:
            raise ValueError(f"pipeline_schedule must be one of "
                             f"{KNOWN_SCHEDULES}, got "
                             f"{self.pipe_schedule!r}")
        mesh = self.mesh

        if (cfg.get("loss", "dense") == "chunked"
                and (moe or pipe_stages > 1)):
            raise ValueError(
                "loss=chunked is not supported with MoE or pipeline "
                "parallelism (those paths need logits + aux losses); "
                "use loss=dense.")

        pipe_schedule, pipe_interleave = self.pipe_schedule, self.pipe_interleave

        def loss_fn(variables, tokens):
            if pipe_stages > 1:
                from flashy_tpu.models import pipelined_apply
                # packed has no forward-only schedule (nothing to pack
                # without a backward lane): eval forwards route through
                # the plain 1f1b placement, which is numerically the
                # same forward.
                eval_schedule = ("1f1b" if pipe_schedule == "packed_1f1b"
                                 else pipe_schedule)
                out = pipelined_apply(model, variables, tokens, mesh=mesh,
                                      num_microbatches=pipe_micro,
                                      schedule=eval_schedule,
                                      interleave=pipe_interleave)
                logits, aux = out if moe else (out, 0.0)
                aux = aux_weight * aux if moe else 0.0
            elif moe:
                from flashy_tpu.models import moe_aux_loss
                logits, mutated = model.apply(variables, tokens,
                                              mutable=["losses"])
                aux = aux_weight * moe_aux_loss(mutated)
            elif cfg.get("loss", "dense") == "chunked":
                # Large-vocab HBM saver: never materialize [B, T, V]
                # (ops.losses.chunked_softmax_cross_entropy).
                from flashy_tpu.ops import lm_next_token_loss
                return lm_next_token_loss(
                    model, variables, tokens, mode="chunked",
                    chunk_size=int(cfg.get("loss_chunk", 256)))
            else:
                logits = model.apply(variables, tokens)
                aux = 0.0
            with jax.named_scope("loss"):
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], tokens[:, 1:]).mean()
            return ce + aux

        from flashy_tpu.parallel import with_grad_accumulation
        if pipe_stages > 1 and pipe_schedule in ("1f1b", "packed_1f1b"):
            # Train through the explicit 1F1B forward/backward program
            # (packed: steady-state F and B co-scheduled into one tick):
            # same (loss, grads) signature, so grad accumulation (and
            # zero_update, were it enabled) compose unchanged — the
            # gradient leaves the pipeline once per step, after the
            # last backward tick.
            from flashy_tpu.models import pipelined_value_and_grad
            base_grad_fn = pipelined_value_and_grad(
                model, mesh=mesh, num_microbatches=pipe_micro,
                interleave=pipe_interleave, schedule=pipe_schedule,
                aux_weight=aux_weight if moe else 0.0)
        else:
            base_grad_fn = jax.value_and_grad(loss_fn)
        grad_fn = with_grad_accumulation(base_grad_fn,
                                         cfg.get("accumulate", 1))

        ema_decay = self.ema_decay

        def train_step(state, tokens):
            # scopes a device trace splits the step by: the model's
            # Flax module paths (.../attn, .../mlp), `loss` (the head
            # and cross-entropy, ops.losses) and `optimizer`
            loss, grads = grad_fn(state["params"], tokens)
            with jax.named_scope("optimizer"):
                updates, opt_state = optim.update(
                    grads, state["opt_state"], state["params"])
                params = optax.apply_updates(state["params"], updates)
                grad_norm = optax.global_norm(grads)
            new_state = {"params": params, "opt_state": opt_state,
                         "step": state["step"] + 1}
            if "ema" in state:
                from flashy_tpu.ema import ema_update
                new_state["ema"] = ema_update(state["ema"], params,
                                              ema_decay, step=state["step"])
            return new_state, {"loss": loss, "grad_norm": grad_norm}

        self._train_step_fn = train_step
        self._jit_train_step()
        self._eval_step = jax.jit(lambda params, tokens: loss_fn(params, tokens))

    @staticmethod
    def _ema_shadow(params):
        """f32 copy of the params, laid out exactly like them."""
        return jax.jit(
            lambda p: jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32), p),
            out_shardings=jax.tree_util.tree_map(
                lambda x: x.sharding, params))(params)

    def _jit_train_step(self) -> None:
        """(Re)build the jitted train step with the new state pinned to
        the CURRENT state's shardings. Left to propagation, XLA hands the
        state back under different (if equivalent) shardings, the second
        call no longer matches the first call's signature, and the whole
        step compiles twice."""
        state_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, self.state)
        self._train_step = jax.jit(
            self._train_step_fn, donate_argnums=(0,),
            out_shardings=(state_shardings, None))

    def get_formatter(self, stage_name):
        return flashy_tpu.Formatter({"loss": ".4f", "ppl": ".1f",
                                     "grad_norm": ".2f", "tokens_per_sec": ".0f",
                                     "bubble_frac": ".3f"})

    def _pipeline_stats(self):
        """Host-static schedule numbers for the active pipeline config:
        bubble fraction, idle ticks and the exact stash-ring bytes (1F1B)
        or the GPipe residency bound — the stage-metric /
        `pipeline/bubble`-track payload. None when pipe=1."""
        if self.pipe_stages <= 1:
            return None
        num_micro = self.cfg.get("pipeline_microbatches") or self.pipe_stages
        accumulate = self.cfg.get("accumulate", 1)
        mb = max(self.cfg.batch_size // accumulate // num_micro, 1)
        mb_shape = (mb, self.cfg.seq_len, self.cfg.model.dim)
        from flashy_tpu.parallel.schedules import (
            gpipe_bubble_fraction, gpipe_stash_bytes, schedule_stats)
        if self.pipe_schedule in ("1f1b", "packed_1f1b"):
            from flashy_tpu.parallel.pipeline import default_overlap
            packed = self.pipe_schedule == "packed_1f1b"
            return schedule_stats(self.pipe_stages, num_micro,
                                  self.pipe_interleave, packed=packed,
                                  overlap=default_overlap(
                                      packed, self.pipe_interleave,
                                      self.mesh),
                                  microbatch_shape=mb_shape)
        return {"schedule": "gpipe",
                "bubble_frac": round(gpipe_bubble_fraction(
                    self.pipe_stages, num_micro), 6),
                "peak_stash_bytes": gpipe_stash_bytes(
                    self.pipe_stages, num_micro, mb_shape)}

    def batch_at(self, step: int, eval_set: bool = False) -> jax.Array:
        # Held-out data: the eval stream is an independently-seeded
        # subset of the same distribution (SeedSequence-namespaced, not
        # a step offset — see synthetic_token_stream).
        host = self._stream(self.cfg.batch_size, self.cfg.seq_len,
                            step, subset=1 if eval_set else 0)
        return shard_batch(jnp.asarray(host), self.mesh,
                           batch_axes=("data", "fsdp"))

    def train(self):
        import time
        average = flashy_tpu.averager()
        steps = range(self.cfg.steps_per_epoch)
        progress = self.log_progress("train", steps, updates=5)
        metrics = {}
        begin = time.time()
        tokens_seen = 0
        pipe_stats = self._pipeline_stats()
        from flashy_tpu.observability import get_telemetry
        telemetry = get_telemetry()
        for index in progress:
            global_step = (self.epoch - 1) * self.cfg.steps_per_epoch + index
            self.state, step_metrics = self._train_step(
                self.state, self.batch_at(global_step))
            metrics = average(step_metrics)
            tokens_seen += self.cfg.batch_size * self.cfg.seq_len
            if telemetry is not None and pipe_stats is not None:
                # per-step sample of the schedule's idle-tick budget —
                # the Perfetto `pipeline/bubble` counter track
                telemetry.counter("pipeline/bubble", bubble_frac=float(
                    pipe_stats["bubble_frac"]), idle_ticks_per_device=float(
                        pipe_stats.get("idle_ticks_per_device", 0.0)))
            progress.update(**metrics)
        jax.block_until_ready(self.state["params"])
        metrics["ppl"] = float(np.exp(min(metrics["loss"], 20.0)))
        metrics["tokens_per_sec"] = tokens_seen / (time.time() - begin)
        if pipe_stats is not None:
            metrics["bubble_frac"] = float(pipe_stats["bubble_frac"])
            metrics["peak_stash_bytes"] = int(pipe_stats["peak_stash_bytes"])
        return metrics

    def valid(self):
        """Held-out loss: same loss function, no update, no donation."""
        average = flashy_tpu.averager()
        steps = range(self.cfg.get("valid_steps", 4))
        progress = self.log_progress("valid", steps, updates=2)
        metrics = {}
        # eval on the EMA shadow when enabled — the standard serving/
        # eval weights; falls back to the live params otherwise
        eval_params = self.state.get("ema", self.state["params"])
        for index in progress:
            loss = self._eval_step(eval_params,
                                   self.batch_at(index, eval_set=True))
            metrics = average({"loss": loss})
            progress.update(**metrics)
        metrics["ppl"] = float(np.exp(min(metrics["loss"], 20.0)))
        return metrics

    def generate(self):
        """Sample a continuation with the KV-cache decoder and log it."""
        from flashy_tpu.models import generate as lm_generate
        import time
        if not hasattr(self, "_generate_jit"):
            # One compiled decoder reused every epoch; params keep their
            # mesh shardings through the jit (sharded inference).
            self._generate_jit = jax.jit(lambda params, prompt, rng: lm_generate(
                self.model, params, prompt, max_new_tokens=32,
                temperature=1.0, rng=rng))
        prompt = jnp.asarray(self._stream(2, 16, step=0)[:, :16])
        begin = time.time()
        out = self._generate_jit(self.state["params"], prompt,
                                 jax.random.PRNGKey(self.epoch))
        out = jax.device_get(out)
        self.log_text("generate", "sample",
                      " ".join(str(int(t)) for t in out[0]))
        return {"gen_tokens_per_sec": out.shape[0] * 32 / (time.time() - begin)}

    def _reconcile_ema(self) -> None:
        """Align the restored state with THIS run's ema_decay config.

        restore() replaces self.state wholesale, so a pre-EMA checkpoint
        resumed with ema_decay>0 would silently train without the
        shadow (train_step keys on the state's contents), and a
        checkpoint WITH a shadow resumed at ema_decay=0 would keep
        updating a degenerate copy. Reconcile loudly instead."""
        if self.ema_decay > 0.0 and "ema" not in self.state:
            self.logger.warning(
                "checkpoint has no EMA shadow but ema_decay=%s: "
                "re-initializing the shadow from the restored params",
                self.ema_decay)
            self.state["ema"] = self._ema_shadow(self.state["params"])
        elif self.ema_decay <= 0.0 and "ema" in self.state:
            self.logger.warning(
                "ema_decay=0 but the checkpoint carries an EMA shadow: "
                "dropping it (eval will use the live params)")
            del self.state["ema"]
        else:
            return
        self._jit_train_step()  # the state's structure changed

    def run(self):
        restored = self.restore()
        if restored:
            self._reconcile_ema()
        self.logger.info("Restored: %s; starting at epoch %d", restored, self.epoch)
        want_generate = bool(self.cfg.get("generate_every"))
        for epoch in range(self.epoch, self.cfg.epochs + 1):
            self.run_stage("train", self.train)
            if self.cfg.get("valid_steps", 4):
                self.run_stage("valid", self.valid)
            if want_generate and epoch % self.cfg.generate_every == 0:
                self.run_stage("generate", self.generate)
            self.commit()


@flashy_tpu.main(config_path="config")
def main(cfg):
    flashy_tpu.setup_logging()
    flashy_tpu.distrib.init()
    LMSolver(cfg).run()


if __name__ == "__main__":
    main()
