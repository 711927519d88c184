"""PipelineEngine — train/eval over the SPMD pipeline.

Capability parity with the reference's ``runtime/pipe/engine.py``
(PipelineEngine(DeepSpeedEngine): train_batch/eval_batch as the only public
step APIs, micro_batches == gradient_accumulation_steps, forward/backward/step
redirected). Two placements of the same schedules (round 13,
docs/PIPELINE.md):

* ``pipeline.placement="spmd"`` (default): one jitted train step whose
  pipeline loop lives inside the model's apply (models/pipeline.py +
  runtime/pipe/spmd.py / one_f_one_b.py); XLA overlaps the ppermute
  transfers with stage compute.
* ``pipeline.placement="mpmd"``: the reference's own shape — an
  instruction-stream interpreter over per-stage programs and an explicit
  transfer layer (runtime/pipe/mpmd) — as a host-driven step plus one
  jitted finalize tail.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..engine import DeepSpeedEngine


class PipelineEngine(DeepSpeedEngine):
    """Engine whose model pipelines its own microbatch loop.

    The model's apply consumes the FULL global batch (splitting it into
    pipeline microbatches internally), so the parent's gas-scan is bypassed:
    one apply == gas microbatches == one optimizer step.
    """

    def _make_train_step(self):
        schedule = self.config.pipeline.schedule
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown pipeline.schedule '{schedule}' "
                             "(gpipe | 1f1b)")
        placement = getattr(self.config.pipeline, "placement", "spmd")
        if placement not in ("spmd", "mpmd"):
            raise ValueError(f"unknown pipeline.placement '{placement}' "
                             "(spmd | mpmd)")
        mpmd = placement == "mpmd"
        if mpmd and not hasattr(self.module, "mpmd_value_and_grad"):
            raise ValueError(
                "pipeline.placement='mpmd' needs a model exposing "
                "mpmd_value_and_grad (models.pipeline.PipelinedTransformer)")
        use_1f1b = schedule == "1f1b" and not mpmd
        if use_1f1b and not hasattr(self.module, "train_value_and_grad"):
            raise ValueError(
                "pipeline.schedule='1f1b' needs a model exposing "
                "train_value_and_grad (models.pipeline.PipelinedTransformer); "
                "this module only supports the gpipe schedule")
        custom_loss = None
        aux_weight = None
        if use_1f1b or mpmd:
            from ..engine import _default_loss_fn
            from ...models.transformer import causal_lm_loss
            lf = self.loss_fn
            if getattr(lf, "_moe_loss", False):
                # MoE losses split: the aux term is computed by the executor
                # itself (the scalar rides the pipe); only the BASE task
                # loss goes to the last stage
                aux_weight = lf._moe_aux_weight
                lf = lf._moe_base_loss
            if lf not in (causal_lm_loss, _default_loss_fn):
                # a user loss runs per-micro at the last stage (per-micro
                # losses averaged — the reference _aggregate_total_loss)
                custom_loss = lf
                if getattr(getattr(self.module, "cfg", None),
                           "moe_experts", 0) > 0 and aux_weight is None:
                    # the 1F1B executor computes the aux term itself (the
                    # scalar rides the pipe) and hands the last stage BARE
                    # logits — a gpipe-style loss_fn expecting the model's
                    # (logits, aux) tuple would silently index the batch
                    # dim instead, and one folding aux in itself would
                    # double-count it
                    raise ValueError(
                        "the hand-scheduled pipeline executors (1f1b / "
                        "placement='mpmd') with an MoE model need the loss "
                        "built by models.make_moe_loss(aux_weight, "
                        "base_loss=...): the executor computes the aux "
                        "term itself and passes the base loss bare logits, "
                        "so a raw loss_fn written against the model's "
                        "(logits, aux) output would misread its input.")
                from ...utils.logging import warning_once
                warning_once(
                    "the hand-scheduled pipeline executors (1f1b / "
                    "placement='mpmd') compute a custom loss_fn "
                    "PER MICROBATCH and average the results (the "
                    "reference's _aggregate_total_loss semantics). For "
                    "per-token-mean losses this equals the full-batch "
                    "value; losses normalized over data-dependent counts "
                    "(e.g. valid -100-masked tokens) will weight micros "
                    "differently than the gpipe schedule's full-batch "
                    "evaluation.")

        if mpmd:
            return self._make_train_step_mpmd(schedule, custom_loss,
                                              aux_weight)

        def train_step(state, batch, rng, lr_arg):
            if use_1f1b:
                # hand-scheduled interleave: loss+grads straight from the
                # 1F1B executor (runtime/pipe/one_f_one_b), no AD through
                # the pipeline scan. fp16: the scale seeds the backward and
                # grads come out scaled — _finalize_step's standard
                # unscale/overflow tail applies.
                loss, grads = self.module.train_value_and_grad(
                    state.params, batch, mesh=self.mesh, rng=rng,
                    loss_scale=(state.scale.scale
                                if self.loss_scaler.enabled else None),
                    loss_fn=custom_loss, aux_weight=aux_weight)
            else:
                def scaled_loss(p):
                    out = self.apply_fn(p, batch, rng, True)
                    loss = self.loss_fn(out, batch)
                    return (loss * state.scale.scale).astype(jnp.float32), loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params)
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g.astype(jnp.float32), s), grads, self.grad_shardings)
            # loss is already the mean over all microbatches -> n_micro=1
            new_state, metrics = self._finalize_step(state, grads, 1.0, lr_arg)
            metrics["loss"] = loss
            return new_state, metrics

        return jax.jit(train_step, donate_argnums=(0,))

    def _make_train_step_mpmd(self, schedule, custom_loss, aux_weight):
        """MPMD placement: the step is HOST-driven — the executor walks
        the per-stage instruction streams calling each stage's own
        compiled program (runtime/pipe/mpmd/executor), so there is no
        single whole-pipeline jit to build. Only the shared finalize
        tail (unscale/clip/optimize/skip — identical math to every other
        step path) is one compiled program over the global mesh.
        """
        finalize = None

        def train_step(state, batch, rng, lr_arg):
            nonlocal finalize
            loss, grads = self.module.mpmd_value_and_grad(
                state.params, batch, mesh=self.mesh, rng=rng,
                loss_scale=(state.scale.scale
                            if self.loss_scaler.enabled else None),
                loss_fn=custom_loss, aux_weight=aux_weight,
                schedule=schedule)
            if finalize is None:
                def _finalize(state, grads, lr_arg):
                    grads = jax.tree.map(
                        lambda g, s: jax.lax.with_sharding_constraint(
                            g.astype(jnp.float32), s),
                        grads, self.grad_shardings)
                    return self._finalize_step(state, grads, 1.0, lr_arg)
                finalize = jax.jit(_finalize, donate_argnums=(0,))
            new_state, metrics = finalize(state, grads, lr_arg)
            metrics["loss"] = loss
            return new_state, metrics

        return train_step

    def train_batch(self, data_iter_or_batch) -> Dict[str, Any]:
        batch = (next(data_iter_or_batch)
                 if hasattr(data_iter_or_batch, "__next__")
                 else data_iter_or_batch)
        if self.optimizer is None:
            raise RuntimeError("PipelineEngine needs an optimizer")
        rec = self.rec
        with rec.step_span("train.step", step_num=self.global_steps):
            with rec.span("train.h2d"):
                batch = self.shard_batch(batch)
            with rec.span("train.dispatch"):
                self.state, metrics = self._train_step(
                    self.state, batch, self.next_rng(), self._current_lr())
            # the per-step wait on the loss, as in DeepSpeedEngine
            with rec.span("train.sync"):
                jax.block_until_ready(metrics["loss"])
            with rec.span("train.after_step"):
                self._after_step(metrics)
        self._report_spans()
        return metrics

    def eval_batch(self, data_iter_or_batch):
        batch = (next(data_iter_or_batch)
                 if hasattr(data_iter_or_batch, "__next__")
                 else data_iter_or_batch)
        batch = self.shard_batch(batch)
        return self._eval_step(self.state.params, batch, self.next_rng(),
                               self.state.step)

    # the reference redirects these for pipeline engines (engine.py:1246-1256)
    def forward(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch/eval_batch instead "
                           "of forward()")

    def backward(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch/eval_batch instead "
                           "of backward()")

    def step(self, *a, **k):
        raise RuntimeError("PipelineEngine: use train_batch/eval_batch instead "
                           "of step()")
