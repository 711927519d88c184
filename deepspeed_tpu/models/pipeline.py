"""Pipelined flagship transformer — the model-side of SPMD pipeline parallelism.

Parameter structure is IDENTICAL to the non-pipelined scan-layers Transformer
(models/transformer.py) — wte/wpe/blocks[L,...]/ln_f — so checkpoints move
freely between pp=1 and pp=N topologies (the reference needs an offline
3D-reshape tool for this, deepspeed/checkpoint/; here it is true by
construction). The apply path differs: blocks are reshaped [L,...] ->
[pp, L/pp, ...] and executed with runtime/pipe/spmd.pipeline_apply; embedding
and head run replicated on every pipe rank (redundant compute, zero
communication — tied-embedding gradients need no ReduceTiedGrads step, unlike
the reference's tied-weight allreduce, pipe/engine.py _exec_reduce_tied_grads).

Per-micro side inputs generalize both executors (round-3 Missing #3):
attention masks and dropout rng keys ride next to the activations; the rng
for a (micro, stage, layer) is fold_in(fold_in(fold_in(base, micro), stage),
layer) in BOTH the gpipe and 1F1B paths, so the two schedules produce
bit-identical dropout masks and their grads stay comparable.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..runtime.pipe.spmd import (pipeline_apply, stack_stage_params,
                                 unstack_stage_params)
from .transformer import Block, Transformer, TransformerConfig

PyTree = Any


def _pad_mask(attention_mask):
    """[B, S] padding mask -> [B, 1, 1, S] boolean attention mask (matches
    models/transformer.Transformer's mask construction)."""
    if attention_mask is None:
        return None
    return attention_mask.astype(jnp.bool_)[:, None, None, :]


class PipelinedTransformer:
    """Engine-compatible model object (init/apply) that pipelines its blocks.

    n_micro: microbatches fed through the pipeline per train step (the
    reference's gradient_accumulation_steps == pipeline micro_batches,
    engine.py:  micro_batches = gas).
    backward: '1F1B' backward mode — 'recompute' (default; stage body re-run
    from the saved input, nothing but boundaries stored) or 'store' (vjp
    residuals ride the rings; no recompute, more live memory).
    """

    def __init__(self, cfg: TransformerConfig, pp: int, n_micro: int,
                 mesh=None, backward: str = "recompute"):
        if cfg.num_layers % pp != 0:
            raise ValueError(f"num_layers {cfg.num_layers} not divisible by "
                             f"pp {pp}")
        if backward not in ("recompute", "store"):
            raise ValueError(f"backward must be recompute|store, "
                             f"got {backward!r}")
        if cfg.layer_windows is not None:
            # the stage body calls blocks without the per-layer window arg;
            # silently running a Mistral-class model with GLOBAL attention
            # would be a wrong answer, not a degraded one
            raise NotImplementedError(
                "pipelined model does not thread per-layer sliding "
                "windows (layer_windows); run windowed models on the "
                "non-pipelined engine")
        if cfg.moe_is_dropless:
            # a dropless block hands out [2, E] balance statistics that the
            # model averages over ALL layers; the pipe carries a scalar
            raise NotImplementedError(
                "pipelined model does not support dropless MoE (moe_k > 2 "
                "or moe_dropless); run it on the non-pipelined engine")
        for knob, why in (("layer_rope", "per-layer rotary flags"),
                          ("dense_layers", "a second stack of leading dense "
                           "layers")):
            # the stage body runs ONE kind of block over equal stage slices
            if getattr(cfg, knob):
                raise NotImplementedError(
                    f"pipelined model does not thread {why} ({knob}); run "
                    "this architecture on the non-pipelined engine")
        for knob in ("embed_ln", "token_type_vocab", "mlm_head",
                     "no_lm_head"):
            # same fail-loud contract: the pipelined embed/head plumbing
            # implements none of these, and running without them (BLOOM's
            # ln_emb, BERT segments/MLM head) silently changes the math
            if getattr(cfg, knob):
                raise NotImplementedError(
                    f"pipelined model does not support {knob}; run this "
                    "architecture on the non-pipelined engine")
        self.cfg = cfg
        self.pp = pp
        self.n_micro = n_micro
        self.mesh = mesh
        self.backward = backward
        #: MPMD placement: per-(train, schedule, loss) pipeline objects —
        #: each holds its per-stage jit programs, so a training loop
        #: compiles each stage exactly once (runtime/pipe/mpmd/executor).
        self._mpmd_cache: Dict[Any, Any] = {}
        # reference model for param init: identical param structure
        self._ref = Transformer(
            cfg if cfg.scan_layers else
            TransformerConfig(**{**cfg.__dict__, "scan_layers": True}))
        self._block = Block(cfg)
        norm_cls = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
        self._ln_f = norm_cls(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                              param_dtype=jnp.float32, name="ln_f")

    # -- engine model contract -----------------------------------------------

    def init(self, rng, batch, **kwargs):
        return self._ref.init(rng, batch, **kwargs)

    def _parse_batch(self, batch):
        if isinstance(batch, dict):
            return (batch["input_ids"], batch.get("attention_mask"),
                    batch.get("labels"))
        return batch, None, None

    def _micro_extras(self, attention_mask, rng, train, B, S):
        """Per-micro side-input pytree for the executors: padding masks and
        per-micro dropout rng keys (folded further per stage and layer
        inside the stage body)."""
        cfg = self.cfg
        extras = {}
        if attention_mask is not None:
            extras["mask"] = attention_mask.reshape(
                self.n_micro, B // self.n_micro, S)
        if train and cfg.dropout > 0.0:
            if rng is None:
                raise ValueError("dropout>0 training needs an rng")
            extras["rng"] = jax.vmap(
                lambda i: jax.random.fold_in(rng, i))(
                    jnp.arange(self.n_micro))
        return extras

    def _embed_micros(self, embed_inputs, ids_micros, S):
        """[n_micro, mb, S] ids -> embedded activations. ``embed_inputs``
        holds the raw embedding tables ({"wte": [V,H]} plus "wpe" for
        learned positions) so the 1F1B path can jax.vjp through this
        directly. Rotary/ALiBi positions need nothing here — the blocks
        apply them internally from default arange positions."""
        cfg = self.cfg
        e = embed_inputs["wte"].astype(cfg.dtype)[ids_micros]
        if cfg.embed_scale is not None:
            e = e * jnp.asarray(cfg.embed_scale, cfg.dtype)
        if cfg.pos_embed == "learned":
            e = e + embed_inputs["wpe"].astype(cfg.dtype)[
                jnp.arange(S)][None, None]
        return e

    def _embed_inputs(self, params):
        out = {"wte": params["wte"]["embedding"]}
        if self.cfg.pos_embed == "learned":
            out["wpe"] = params["wpe"]["embedding"]
        return out

    def _head_logits(self, head_p, h):
        """Final-norm'd hidden states -> logits; tied einsum against wte or
        the untied (optionally biased) lm_head kernel. Applies the Gemma-2
        final-logit softcap (returns f32 then — every caller casts to f32
        anyway, and a bf16 round-trip of capped logits can flip near-tie
        argmaxes)."""
        if self.cfg.tie_embeddings:
            wte = head_p["wte"].astype(h.dtype)
            logits = jnp.einsum("...sh,vh->...sv", h, wte)
        else:
            k = head_p["lm_head"]["kernel"].astype(h.dtype)
            logits = jnp.einsum("...sh,hv->...sv", h, k)
            if "bias" in head_p["lm_head"]:
                logits = logits + head_p["lm_head"]["bias"].astype(h.dtype)
        if self.cfg.final_logit_softcap:
            from ..ops.attention import apply_softcap
            logits = apply_softcap(logits, self.cfg.final_logit_softcap)
        return logits

    def _head_params(self, params):
        head = {"ln_f": params["ln_f"]}
        if self.cfg.tie_embeddings:
            head["wte"] = params["wte"]["embedding"]
        else:
            head["lm_head"] = params["lm_head"]
        return head

    def _block_stage_fn(self, train):
        """stage_fn(block_stack, h, extra, stage) for both executors."""
        cfg = self.cfg
        moe = cfg.moe_experts > 0
        dropout = train and cfg.dropout > 0.0

        def stage_fn(block_stack, h, extra, stage):
            mask = _pad_mask(extra.get("mask")) \
                if isinstance(extra, dict) else None
            stage_rng = (jax.random.fold_in(extra["rng"], stage)
                         if dropout else None)
            n_layers = jax.tree.leaves(block_stack)[0].shape[0]

            def layer(carry, xs):
                h, li = carry
                p = xs
                rngs = {}
                if dropout:
                    rngs["dropout"] = jax.random.fold_in(stage_rng, li)
                if moe and stage_rng is not None:
                    # top-2 gating's Gumbel second pick; noise-free gating
                    # without a per-micro rng (the pre-round-4 behavior)
                    rngs["gating"] = jax.random.fold_in(stage_rng, 1000 + li)
                out, aux = self._block.apply(
                    {"params": p}, h, mask, train,
                    rngs=rngs or None)
                return (out, li + 1), aux

            (h, _), auxes = jax.lax.scan(
                layer, (h, jnp.zeros((), jnp.int32)), block_stack)
            if moe:
                return h, jnp.sum(auxes)
            return h

        return stage_fn

    def apply(self, variables, batch, train: bool = False, rngs=None,
              mesh=None):
        params = variables["params"]
        cfg = self.cfg
        mesh = mesh or self.mesh
        if mesh is None:
            from ..parallel.mesh import get_global_mesh
            mesh = get_global_mesh().mesh
        input_ids, attention_mask, _ = self._parse_batch(batch)
        B, S = input_ids.shape
        if B % self.n_micro != 0:
            raise ValueError(f"batch {B} not divisible by n_micro {self.n_micro}")
        if isinstance(rngs, dict):
            base_rng = rngs.get("dropout")
            if base_rng is None:
                base_rng = rngs.get("params")
        else:
            base_rng = rngs

        # reshape the INTEGER ids to microbatches first: ids carry no
        # cotangent, so the data-axis reshard of the [B]->[n_micro, mb] split
        # never transposes into a low-precision collective (XLA SPMD miscompiles
        # bf16 resharding copies on some backends)
        ids_micros = input_ids.reshape(self.n_micro, B // self.n_micro, S)
        micros = self._embed_micros(self._embed_inputs(params), ids_micros, S)
        # pin the microbatched layout: micro dim replicated, the PER-MICRO
        # batch dim carries the (data, expert) sharding. Left to inference
        # the partitioner may split the micro dim instead (seen on the
        # pp x ep ladder mesh), and the head's reshape back to [B, S, V]
        # then pays involuntary replicate-and-reshard round trips.
        from .transformer import _spec_constraint
        mspec = P(None, ("data", "expert"), None, None)
        micros = _spec_constraint(micros, mspec)
        stage_params = stack_stage_params(params["blocks"], self.pp)

        moe = cfg.moe_experts > 0
        extras = self._micro_extras(attention_mask, base_rng, train, B, S)
        stage_fn = self._block_stage_fn(train)

        res = pipeline_apply(stage_fn, stage_params, micros, mesh=mesh,
                             pp=self.pp, remat=cfg.remat, with_aux=moe,
                             extras=extras)
        outs, aux_total = res if moe else (res, None)
        outs = _spec_constraint(outs, mspec)
        # head runs per-micro; only the fp32 logits are reshaped back to the
        # flat batch (fp32 resharding avoids the bf16 SPMD copy bug above)
        h = self._ln_f.apply({"params": params["ln_f"]}, outs)
        logits = self._head_logits(self._head_params(params),
                                   h).astype(jnp.float32)
        logits = logits.reshape((B, S, cfg.vocab_size))
        logits = _spec_constraint(logits, P(("data", "expert"), None, None))
        if moe:
            return logits, aux_total
        return logits

    __call__ = apply

    # -- 1F1B training path --------------------------------------------------

    def train_value_and_grad(self, params, batch, mesh=None, rng=None,
                             loss_scale=None, loss_fn=None, train=True,
                             aux_weight=None):
        """Loss + grads via the hand-scheduled 1F1B executor
        (runtime/pipe/one_f_one_b): activation memory ∝ pp (not n_micro) and
        the boundary stays bf16. Returns (loss, grads) with grads matching
        the params tree.

        Accepts everything the gpipe path does (round-3 Missing #3 closed):
        attention_mask batches, dropout (per-micro/stage/layer rng folding,
        bit-identical to gpipe's), MoE (the aux scalar flows through the
        manual backward via its constant cotangent), fp16 loss scaling
        (``loss_scale`` seeds the backward; grads come out scaled for the
        engine's standard unscale/overflow tail), and a custom last-stage
        ``loss_fn(logits, micro_batch)`` (per-micro losses averaged over
        micros — the reference's _aggregate_total_loss semantics).
        """
        cfg = self.cfg
        mesh = mesh or self.mesh
        if mesh is None:
            from ..parallel.mesh import get_global_mesh
            mesh = get_global_mesh().mesh
        from ..runtime.pipe.one_f_one_b import pipeline_1f1b_value_and_grad
        input_ids, attention_mask, labels = self._parse_batch(batch)
        if labels is None:
            labels = input_ids
        B, S = input_ids.shape
        mb = B // self.n_micro
        ids_micros = input_ids.reshape(self.n_micro, mb, S)
        lab_micros = labels.reshape(self.n_micro, mb, S)

        micros, embed_vjp = jax.vjp(
            lambda ep: self._embed_micros(ep, ids_micros, S),
            self._embed_inputs(params))
        stage_params = stack_stage_params(params["blocks"], self.pp)
        extras = self._micro_extras(attention_mask, rng, train, B, S)
        stage_fn = self._block_stage_fn(train)
        moe = cfg.moe_experts > 0

        head = self._head_params(params)

        if loss_fn is None:
            # default causal-LM objective with GLOBAL token mean: the
            # executor averages per-micro losses, so each micro contributes
            # its nll SUM scaled by n_micro/total_valid — with unevenly
            # -100-masked micros a per-micro mean would overweight sparse
            # ones vs the gpipe/causal_lm_loss objective
            total_valid = jnp.maximum(
                jnp.sum((lab_micros[:, :, 1:] != -100).astype(jnp.float32)),
                1.0)

            def head_loss(head_p, y, lab):
                h = self._ln_f.apply({"params": head_p["ln_f"]}, y)
                logits = self._head_logits(head_p, h)
                logits = logits[:, :-1].astype(jnp.float32)
                tgt = lab[:, 1:]
                valid = tgt != -100
                safe = jnp.where(valid, tgt, 0)
                logz = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, safe[..., None],
                                           axis=-1)[..., 0]
                nll_sum = jnp.sum((logz - gold) * valid)
                return nll_sum * (self.n_micro / total_valid)

            head_labels = lab_micros
        else:
            # custom objective: loss_fn(model_output, micro_batch) per
            # micro, averaged over micros. EVERY [B, ...] leaf of the batch
            # reshapes to [n_micro, mb, ...]; batch-independent leaves ride
            # replicated per micro — the user's loss sees the same fields
            # it would on the gpipe schedule.
            def to_micros(leaf):
                leaf = jnp.asarray(leaf)
                if leaf.ndim >= 1 and leaf.shape[0] == B:
                    return leaf.reshape((self.n_micro, mb) + leaf.shape[1:])
                return jnp.broadcast_to(leaf[None],
                                        (self.n_micro,) + leaf.shape)

            micro_batches = (jax.tree.map(to_micros, batch)
                             if isinstance(batch, dict)
                             else {"input_ids": ids_micros,
                                   "labels": lab_micros})

            def head_loss(head_p, y, lab):
                h = self._ln_f.apply({"params": head_p["ln_f"]}, y)
                out = self._head_logits(head_p, h).astype(jnp.float32)
                return loss_fn(out, lab).astype(jnp.float32)

            head_labels = micro_batches

        aux_w = (aux_weight if aux_weight is not None
                 else cfg.moe_aux_weight)
        loss, aux, gs, gh, dmicros = pipeline_1f1b_value_and_grad(
            stage_fn, head_loss, stage_params, head, micros,
            lab_micros if loss_fn is None else head_labels,
            mesh=mesh, pp=self.pp, extras=extras,
            with_aux=moe,
            aux_cotangent=(aux_w if moe else 0.0),
            loss_scale=loss_scale,
            store_outputs=(self.backward == "store"))
        (dembed,) = embed_vjp(dmicros)
        dwte = dembed["wte"]
        if cfg.tie_embeddings:
            dwte = dwte + gh["wte"]           # head grad rides the tie
        grads = {
            "wte": {"embedding": dwte},
            "blocks": unstack_stage_params(gs),
            "ln_f": gh["ln_f"],
        }
        if cfg.pos_embed == "learned":
            grads["wpe"] = {"embedding": dembed["wpe"]}
        if not cfg.tie_embeddings:
            grads["lm_head"] = gh["lm_head"]
        if moe:
            # reported loss matches make_moe_loss: task + aux_weight * aux
            loss = loss + aux_w * aux
        return loss, grads

    # -- MPMD training path --------------------------------------------------

    def mpmd_value_and_grad(self, params, batch, mesh=None, rng=None,
                            loss_scale=None, loss_fn=None, train=True,
                            aux_weight=None, schedule="1f1b", channel=None):
        """Loss + grads via the MPMD placement (runtime/pipe/mpmd): each
        stage is its own jit program on its own submesh of ``mesh``'s
        'pipe' axis, activations/cotangents ride the explicit transfer
        channel, and the SAME clock tables as the SPMD executors drive
        the ticks (``schedule`` = 'gpipe' | '1f1b').

        Accepts the 1F1B path's full generality (masks, dropout rng
        folding — bit-identical per (micro, stage, layer) — MoE aux via
        its constant cotangent, fp16 loss_scale seeding, custom per-micro
        last-stage loss). The per-stage pipelines are cached on the
        model, so a training loop compiles each stage exactly once.
        ``backward='store'`` is SPMD-only (residual rings are a
        stacked-scan construct) and is refused loudly.
        """
        cfg = self.cfg
        if self.backward == "store":
            raise ValueError(
                "backward='store' is an SPMD-executor mode (vjp residual "
                "rings inside the stacked scan); the MPMD placement's "
                "fused per-stage backward is the recompute regime — "
                "build the model with backward='recompute'")
        mesh = mesh or self.mesh
        if mesh is None:
            from ..parallel.mesh import get_global_mesh
            mesh = get_global_mesh().mesh
        from ..runtime.pipe.mpmd.executor import MPMDPipeline
        input_ids, attention_mask, labels = self._parse_batch(batch)
        if labels is None:
            labels = input_ids
        B, S = input_ids.shape
        mb = B // self.n_micro
        ids_micros = input_ids.reshape(self.n_micro, mb, S)
        lab_micros = labels.reshape(self.n_micro, mb, S)

        micros, embed_vjp = jax.vjp(
            lambda ep: self._embed_micros(ep, ids_micros, S),
            self._embed_inputs(params))
        stage_params = stack_stage_params(params["blocks"], self.pp)
        extras = self._micro_extras(attention_mask, rng, train, B, S)
        moe = cfg.moe_experts > 0
        head = self._head_params(params)

        if loss_fn is None:
            # same GLOBAL token-mean objective as the 1F1B path — the
            # batch-dependent valid count rides the per-call ``loss_ctx``
            # arg so it never bakes into the cached per-stage trace
            loss_ctx = jnp.maximum(
                jnp.sum((lab_micros[:, :, 1:] != -100).astype(jnp.float32)),
                1.0)
            head_labels = lab_micros
        else:
            def to_micros(leaf):
                leaf = jnp.asarray(leaf)
                if leaf.ndim >= 1 and leaf.shape[0] == B:
                    return leaf.reshape((self.n_micro, mb) + leaf.shape[1:])
                return jnp.broadcast_to(leaf[None],
                                        (self.n_micro,) + leaf.shape)

            head_labels = (jax.tree.map(to_micros, batch)
                           if isinstance(batch, dict)
                           else {"input_ids": ids_micros,
                                 "labels": lab_micros})
            loss_ctx = ()

        # keyed on mesh and channel too: a later call with a different
        # mesh must NOT reuse submesh programs built for the old device
        # layout, and a caller-supplied channel is honored per call.
        # (Callers passing a fresh lambda loss_fn per call defeat the
        # cache — per-stage re-jits every step; pass a stable function.)
        key = (bool(train), schedule, loss_fn, moe, mesh,
               None if channel is None else id(channel))
        pipe = self._mpmd_cache.get(key)
        if pipe is None:
            n_micro = self.n_micro

            if loss_fn is None:
                def head_loss(head_p, y, lab, ctx):
                    h = self._ln_f.apply({"params": head_p["ln_f"]}, y)
                    logits = self._head_logits(head_p, h)
                    logits = logits[:, :-1].astype(jnp.float32)
                    tgt = lab[:, 1:]
                    valid = tgt != -100
                    safe = jnp.where(valid, tgt, 0)
                    logz = jax.nn.logsumexp(logits, axis=-1)
                    gold = jnp.take_along_axis(logits, safe[..., None],
                                               axis=-1)[..., 0]
                    nll_sum = jnp.sum((logz - gold) * valid)
                    return nll_sum * (n_micro / ctx)
            else:
                def head_loss(head_p, y, lab, ctx):
                    h = self._ln_f.apply({"params": head_p["ln_f"]}, y)
                    out = self._head_logits(head_p, h).astype(jnp.float32)
                    return loss_fn(out, lab).astype(jnp.float32)

            pipe = MPMDPipeline(self._block_stage_fn(train), head_loss,
                                pp=self.pp, schedule=schedule, mesh=mesh,
                                with_aux=moe, channel=channel)
            self._mpmd_cache[key] = pipe

        aux_w = (aux_weight if aux_weight is not None
                 else cfg.moe_aux_weight)
        loss, aux, gs, gh, dmicros = pipe.value_and_grad(
            stage_params, head, micros,
            lab_micros if loss_fn is None else head_labels,
            extras=extras, loss_ctx=loss_ctx,
            aux_cotangent=(aux_w if moe else 0.0),
            loss_scale=loss_scale)
        (dembed,) = embed_vjp(dmicros)
        dwte = dembed["wte"]
        if cfg.tie_embeddings:
            dwte = dwte + gh["wte"]
        grads = {
            "wte": {"embedding": dwte},
            "blocks": unstack_stage_params(gs),
            "ln_f": gh["ln_f"],
        }
        if cfg.pos_embed == "learned":
            grads["wpe"] = {"embedding": dembed["wpe"]}
        if not cfg.tie_embeddings:
            grads["lm_head"] = gh["lm_head"]
        if moe:
            loss = loss + aux_w * aux
        return loss, grads

    # -- sharding rules ------------------------------------------------------

    def tp_rules(self) -> Dict[str, P]:
        """Blocks lead with the 'pipe' axis on the layer dim; embed/head as in
        the non-pipelined rules."""
        def block(*spec):
            return P(*(("pipe",) + spec))

        return {
            r"blocks/.*attn_qkv/kernel": block(None, "model"),
            r"blocks/.*attn_qkv/bias": block("model"),
            r"blocks/.*attn_proj/kernel": block("model", None),
            r"blocks/.*mlp_fc/kernel": block(None, "model"),
            r"blocks/.*mlp_fc/bias": block("model"),
            r"blocks/.*mlp_gate/kernel": block(None, "model"),
            r"blocks/.*mlp_gate/bias": block("model"),
            r"blocks/.*mlp_proj/kernel": block("model", None),
            # MoE expert stacks [L, E, in, out]: the layer dim carries the
            # pipe axis (as for every block param), expert axis on E,
            # row/col TP inside — the non-pipelined rules with the layer
            # lead swapped from None to 'pipe'
            r"blocks/.*experts/fc/kernel": block("expert", None, "model"),
            r"blocks/.*experts/fc/bias": block("expert", "model"),
            r"blocks/.*experts/gate/kernel": block("expert", None, "model"),
            r"blocks/.*experts/gate/bias": block("expert", "model"),
            r"blocks/.*experts/proj/kernel": block("expert", "model", None),
            r"blocks/.*experts/proj/bias": block("expert", None),
            r"blocks/.*moe/gate/kernel": block(),
            r"blocks/": P("pipe"),           # ln scales/biases: pipe only
            r"wte/embedding": P("model", None),
            r"lm_head/kernel": P(None, "model"),
        }


def build_pipelined_model(name_or_cfg, pp: int, n_micro: int, **overrides):
    from .transformer import get_config
    backward = overrides.pop("backward", "recompute")
    cfg = (name_or_cfg if isinstance(name_or_cfg, TransformerConfig)
           else get_config(name_or_cfg, **overrides))
    return (PipelinedTransformer(cfg, pp=pp, n_micro=n_micro,
                                 backward=backward), cfg)
