"""DeepSpeedEngine — config-driven training engine, TPU-native.

Capability parity with the reference's ``deepspeed/runtime/engine.py``
(DeepSpeedEngine: forward/backward/step, train_batch, checkpoint save/load,
monitor/timer integration, ZeRO dispatch) — rebuilt around one jitted,
donated, sharded train step instead of module hooks + streams + buckets:

  reference mechanism                          TPU-native replacement
  -------------------------------------------  --------------------------------
  per-param grad hooks + bucketed allreduce    grads are scan-carried; a sharding
    (stage_1_and_2.py:836,942)                 constraint makes XLA emit fused
                                               reduce-scatter/all-reduce, overlapped
                                               by the latency-hiding scheduler
  ZeRO-3 submodule hooks + prefetch trace      params sharded by NamedSharding;
    (parameter_offload.py, coordinator)        XLA all-gathers per layer and
                                               prefetches automatically
  fp16 flat master buffers (fused_optimizer)   fp32 master pytree, ZeRO-sharded
  DynamicLossScaler python branch              lax.cond inside the compiled step
  CPU optimizer offload (CPUAdam + pinned)     host-memory donation (future: C++
                                               AVX path in ops/cpu)

The public surface keeps the reference's names: ``forward``/``backward``/
``step`` (micro-batch API), ``train_batch``/``eval_batch`` (fused API),
``save_checkpoint``/``load_checkpoint``, ``save_16bit_model``, plus the config
accessor properties user code relies on (engine.py:498-879).
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import DeepSpeedConfig, load_config
from ..monitor.monitor import MonitorMaster
from ..ops.optimizers import Optimizer, build_optimizer
from ..parallel.mesh import MeshManager, build_mesh_from_config
from ..utils.logging import log_dist, logger
from ..utils.partitioning import build_tp_specs
from ..utils import telemetry
from ..testing import chaos
from . import checkpointing as ckpt_lib
from . import heartbeat as hb
from . import sentinel as sentinel_lib
from . import straggler as straggler_lib
from .loss_scaler import LossScaler
from .lr_schedules import LRScheduler, build_schedule
# NonFiniteError moved into the sentinel ladder (round 7) — re-exported
# here because user code and tests import it from the engine module
from .sentinel import (NonFiniteError, TrainingIntegrityError,  # noqa: F401
                       TrainingSentinel)
from .state import TrainState
from .zero.stages import ZeroShardingPolicy

PyTree = Any


def _default_loss_fn(outputs, batch):
    """By default the model is assumed to return the scalar loss (the usual
    DeepSpeed contract: loss = engine(batch))."""
    return outputs


def wire_attention_config(model, config: DeepSpeedConfig):
    """Map the ``sparse_attention`` and ``sequence_parallel.mode`` config
    sections onto the model's ``attention_impl`` (reference: the
    sparse-attention section configures SparseSelfAttention modules at init,
    runtime/config.py:270-453; sequence parallelism is a TPU-native section).

    Returns the (possibly rebuilt) model. Contract: unknown sparse modes and
    unknown sequence-parallel modes RAISE — a parsed-but-ignored section
    silently running dense attention is a wrong answer, not a default.
    Models that hand-set a conflicting ``attention_impl`` also fail loudly.
    """
    sp = config.sequence_parallel
    if sp.mode not in ("ring", "ulysses"):
        raise ValueError(
            f"sequence_parallel.mode '{sp.mode}' is not supported; "
            "expected 'ring' or 'ulysses'")
    sa = config.sparse_attention
    if sa is not None:
        from ..ops.sparse_attention import SPARSITY_CONFIGS
        if sa.mode not in SPARSITY_CONFIGS:
            raise ValueError(
                f"unknown sparse attention mode '{sa.mode}'; "
                f"have {sorted(SPARSITY_CONFIGS)}")
    wants_sp = sp.sp_size > 1
    if sa is None and not wants_sp:
        return model
    from ..models.transformer import TransformerConfig
    mcfg = getattr(model, "cfg", None)
    if not isinstance(mcfg, TransformerConfig):
        if sa is not None:
            raise ValueError(
                "the sparse_attention config section requires the in-tree "
                "transformer family (models.build_model); this model has no "
                "TransformerConfig to wire the layout into")
        # sequence parallelism over a custom apply_fn: the mesh still carries
        # the seq axis; the model is responsible for its own SP attention
        logger.warning("sequence_parallel.sp_size > 1 with a non-in-tree "
                       "model: attention_impl cannot be auto-selected")
        return model
    import dataclasses as _dc
    updates = {}
    if sa is not None:
        if wants_sp:
            raise ValueError(
                "sparse_attention and sequence_parallel.sp_size > 1 cannot "
                "be combined (the layout-skip kernel is not sequence-"
                "parallel); drop one of the two sections")
        if mcfg.attention_impl not in ("auto", "sparse"):
            raise ValueError(
                f"sparse_attention config conflicts with the model's "
                f"hand-set attention_impl='{mcfg.attention_impl}'")
        items = tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in sa.model_dump().items()))
        updates = {"attention_impl": "sparse", "sparse_attention": items}
    elif wants_sp:
        if mcfg.attention_impl == "auto":
            updates = {"attention_impl": sp.mode}
        elif mcfg.attention_impl in ("ring", "ulysses") \
                and mcfg.attention_impl != sp.mode:
            raise ValueError(
                f"sequence_parallel.mode='{sp.mode}' conflicts with the "
                f"model's hand-set attention_impl='{mcfg.attention_impl}'")
        elif mcfg.attention_impl not in ("ring", "ulysses"):
            # an explicit flash/reference/sparse impl wins, but the user
            # asked for sequence parallelism — don't leave the section
            # silently dead
            logger.warning(
                "sequence_parallel.sp_size=%d with hand-set attention_impl="
                "'%s': no %s attention will run; set attention_impl='auto' "
                "to let the config section select it",
                sp.sp_size, mcfg.attention_impl, sp.mode)
    if not updates:
        return model
    new_cfg = _dc.replace(mcfg, **updates)
    if hasattr(model, "clone"):                 # flax module (Transformer)
        model = model.clone(cfg=new_cfg)
    elif hasattr(model, "pp"):                  # PipelinedTransformer
        model = type(model)(new_cfg, pp=model.pp, n_micro=model.n_micro,
                            mesh=model.mesh, backward=model.backward)
    else:
        raise ValueError(
            f"cannot rebuild model {type(model).__name__} with "
            f"attention_impl='{updates['attention_impl']}'")
    log_dist(f"attention config wired: attention_impl="
             f"'{updates['attention_impl']}'", ranks=[0])
    return model


def wire_low_precision(model, config: DeepSpeedConfig):
    """Consume ``compression_training.activation_quantization`` by rewiring
    the model's ``activation_quant`` (round 17 — the section parsed into
    ``CompressionSpec.activation_bits`` since round 6 but nothing read it:
    a parsed-but-dead section, the wire_attention_config contract).

    The low-precision step is an EXPERIMENT, not a default: it requires
    the integrity sentinel (``config.integrity.enabled``) so a quantized
    step that degrades loss rides the skip -> rollback -> abort ladder
    instead of silently training through it. Unknown bit widths and
    activation schedule offsets RAISE — silently running full precision
    would be a wrong answer.
    """
    act = (config.compression_training.model_dump()
           .get("activation_quantization") or {})
    shared = act.get("shared_parameters") or {}
    from ..models.transformer import TransformerConfig
    mcfg = getattr(model, "cfg", None)
    if not shared.get("enabled", False):
        # model-knob route (build_model(activation_quant=...)) still gets
        # the sentinel gate below when training through the engine
        if isinstance(mcfg, TransformerConfig) and mcfg.activation_quant \
                and not config.integrity.enabled:
            raise ValueError(
                "activation_quant is a gated experiment: enable the "
                "integrity sentinel (config.integrity.enabled) so bad "
                "quantized steps hit the skip/rollback ladder")
        return model
    if int(shared.get("schedule_offset", 0)):
        raise NotImplementedError(
            "activation_quantization.schedule_offset is not supported for "
            "the low-precision step (the quant lives inside the model, "
            "which does not see the step counter)")
    bits_list = [int((g.get("params") or {}).get("bits", 8))
                 for g in (act.get("different_groups") or {}).values()]
    bits = min(bits_list) if bits_list else 8
    if bits != 8:
        raise ValueError(
            f"activation_quantization bits={bits}: only 8 (blockwise int8 "
            "fake-quant; fp8 emulation rides the model knob "
            "activation_quant='fp8')")
    if not isinstance(mcfg, TransformerConfig):
        raise ValueError(
            "activation_quantization requires the in-tree transformer "
            "family (models.build_model); this model has no "
            "TransformerConfig to wire activation_quant into")
    if not config.integrity.enabled:
        raise ValueError(
            "activation_quantization is a gated experiment: enable the "
            "integrity sentinel (config.integrity.enabled) so bad "
            "quantized steps hit the skip/rollback ladder")
    if mcfg.activation_quant not in (None, "int8"):
        raise ValueError(
            f"activation_quantization conflicts with the model's hand-set "
            f"activation_quant={mcfg.activation_quant!r}")
    import dataclasses as _dc
    model = model.clone(cfg=_dc.replace(mcfg, activation_quant="int8")) \
        if hasattr(model, "clone") else model
    if getattr(getattr(model, "cfg", None), "activation_quant", None) \
            != "int8":
        raise ValueError(
            f"cannot rebuild model {type(model).__name__} with "
            "activation_quant='int8'")
    log_dist("low-precision experiment wired: activation_quant='int8' "
             "(sentinel-gated)", ranks=[0])
    return model


class DeepSpeedEngine:
    def __init__(self,
                 model,
                 config: Optional[DeepSpeedConfig | dict | str] = None,
                 model_parameters: Optional[PyTree] = None,
                 loss_fn: Optional[Callable] = None,
                 apply_fn: Optional[Callable] = None,
                 example_batch: Optional[PyTree] = None,
                 rng: Optional[jax.Array] = None,
                 sharding_rules: Optional[Dict[str, P]] = None,
                 mesh_manager: Optional[MeshManager] = None,
                 optimizer: Optional[Optimizer] = None,
                 lr_scheduler=None,
                 mpu=None):
        # the one recorder (utils/telemetry.py), made first: train.* spans,
        # counters, and what wall_clock_breakdown, the monitor and the
        # autotuner read; all the constructor does lies in train.init, so
        # set-up and its compiles are recorded like any step
        self.rec = telemetry.Recorder("train")
        self.rec.counters.update(dict.fromkeys(
            ("train.h2d_bytes",) + telemetry.COMPILE_COUNTERS, 0))
        with self.rec.span("train.init"):
            self._init(model, config, model_parameters, loss_fn, apply_fn,
                       example_batch, rng, sharding_rules, mesh_manager,
                       optimizer, lr_scheduler, mpu)
        # the per-step report of train.* spans starts at the first step
        self._spans_reported_ns = time.monotonic_ns()

    def _init(self, model, config, model_parameters, loss_fn, apply_fn,
              example_batch, rng, sharding_rules, mesh_manager, optimizer,
              lr_scheduler, mpu) -> None:
        self.config = load_config(config)
        # sparse_attention / sequence_parallel.mode consume their config
        # sections by rewiring the model's attention_impl (VERDICT: the two
        # parsed-but-dead sections). Must happen before apply_fn is built.
        model = wire_attention_config(model, self.config)
        # compression_training.activation_quantization -> the round-17
        # low-precision step (sentinel-gated; also before apply_fn)
        model = wire_low_precision(model, self.config)
        self.module = model
        self.mesh_mgr = mesh_manager or build_mesh_from_config(self.config)
        self.mesh = self.mesh_mgr.mesh
        # ranks that receive distinct batch slices (the reference's DP world size)
        dp = self.mesh_mgr.shape["data"] * self.mesh_mgr.shape["expert"]
        self.config.resolve_batch_sizes(dp_world_size=dp)
        self.dp_world_size = dp

        # precision ----------------------------------------------------------
        self.compute_dtype = {"float16": jnp.float16, "bfloat16": jnp.bfloat16,
                              "float32": jnp.float32}[self.config.precision_dtype]
        self.keep_master = self.compute_dtype != jnp.float32
        self._pure_bf16 = (self.config.bf16.enabled
                           and not self.config.bf16.master_weights)
        if self._pure_bf16:
            # pure-bf16: params are the master, moments bf16 (config.py
            # BF16Config.master_weights) — no fp32 state anywhere.
            # (validated against the RESOLVED optimizer below)
            self.keep_master = False
        # reference: data_types.grad_accum_dtype (config.py:907) — the dtype
        # microbatch grads accumulate in; fp32 default, bf16 halves the
        # accumulator footprint (update math stays f32 in _finalize_step)
        gad = (self.config.data_types.grad_accum_dtype or "fp32").lower()
        _gad_map = {"fp32": jnp.float32, "float32": jnp.float32,
                    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                    "fp16": jnp.float16, "float16": jnp.float16}
        if gad not in _gad_map:
            raise ValueError(
                f"data_types.grad_accum_dtype '{gad}' is not supported; "
                f"expected one of {sorted(_gad_map)}")
        self.grad_accum_dtype = _gad_map[gad]
        fp16 = self.config.fp16
        self.loss_scaler = LossScaler(
            static_scale=fp16.loss_scale,
            initial_scale_power=fp16.initial_scale_power,
            scale_window=fp16.loss_scale_window,
            min_scale=fp16.min_loss_scale,
            hysteresis=fp16.hysteresis,
            enabled=fp16.enabled)

        # model fns ----------------------------------------------------------
        self.loss_fn = loss_fn or _default_loss_fn
        self._rng = rng if rng is not None else jax.random.PRNGKey(self.config.seed)
        self.apply_fn = apply_fn or self._build_apply_fn(model)

        # activation checkpointing section (reference:
        # runtime/activation_checkpointing/checkpointing.py:748,830): for the
        # in-house model family remat is a per-layer model knob (better
        # segmentation); for ARBITRARY user models the engine wraps the whole
        # apply_fn in jax.checkpoint under a selective policy, so the config
        # section is behavior, not a warning.
        act = self.config.activation_checkpointing
        mcfg = getattr(model, "cfg", None)
        act_on = bool(act.partition_activations or act.cpu_checkpointing
                      or act.number_checkpoints)
        if act_on and mcfg is not None and getattr(mcfg, "remat", False):
            # in-house family already segments remat per layer — honor the
            # cpu_checkpointing knob by checking the model's policy matches
            if act.cpu_checkpointing and \
                    getattr(mcfg, "remat_policy", None) != "offload":
                logger.warning(
                    "activation_checkpointing.cpu_checkpointing is set but the "
                    "model's remat_policy is %r — build the model with "
                    "remat=True, remat_policy='offload' to host-offload saved "
                    "activations", getattr(mcfg, "remat_policy", None))
        elif act_on:
            from .act_checkpoint import configure as act_configure, remat as act_remat
            act_configure(
                partition_activations=act.partition_activations,
                contiguous_checkpointing=act.contiguous_memory_optimization,
                num_checkpoints=act.number_checkpoints,
                checkpoint_in_cpu=act.cpu_checkpointing,
                profile=act.profile)
            # whole-fn remat under "full" saves nothing (backward would
            # re-materialize every residual anyway); selective "dots" /
            # host-"offload" policies are where an unsegmented wrap wins
            policy = "offload" if act.cpu_checkpointing else "dots"
            # train (argnum 3) is a python bool the apply_fn branches on
            self.apply_fn = act_remat(self.apply_fn, policy_name=policy,
                                      static_argnums=(3,))
            log_dist(f"activation checkpointing: engine-level remat of the "
                     f"user apply_fn (policy={policy})", ranks=[0])

        # compression training (QAT / pruning) --------------------------------
        # the spec transforms params INSIDE the jitted step; grads flow
        # straight-through to the raw master weights (reference: compress.py
        # init_compression wraps linears; engine.py:1395 scheduler hook)
        from ..compression import init_compression
        spec = init_compression({"compression_training":
                                 self.config.compression_training.model_dump()})
        # MoQ (reference: runtime/quantize.py) compiles into the same
        # weight-quantization machinery
        from .quantize import build_moq_spec
        moq = build_moq_spec(self.config.quantize_training)
        if moq is not None:
            spec.groups.extend(moq.groups)
        self.compression_spec = spec if spec.enabled else None
        self._moq_enabled = moq is not None
        if self.compression_spec is not None:
            log_dist(f"compression training: "
                     f"{[g.kind + ':' + g.name for g in spec.groups]}",
                     ranks=[0])

        # params -------------------------------------------------------------
        if model_parameters is None:
            if example_batch is None:
                raise ValueError("need model_parameters or example_batch to initialize")
            with self.rec.span("train.init.params"):
                model_parameters = self._init_params(example_batch)
        params_f32 = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32), model_parameters)

        # sharding policy ----------------------------------------------------
        stage = self.config.zero_optimization.stage
        self.zero_policy = ZeroShardingPolicy(
            stage, self.mesh_mgr,
            param_persistence_threshold=(
                self.config.zero_optimization.param_persistence_threshold))
        self.tp_specs = build_tp_specs(params_f32, sharding_rules)
        # expert params (path under an "experts" module, reference: MoE expert
        # groups carved from DP, utils/groups.py) shard ZeRO state over the
        # non-expert DP axes only
        from ..utils.partitioning import path_str
        expert_fn = lambda path: "experts" in path_str(path)
        self.param_shardings = self.zero_policy.param_shardings(
            params_f32, self.tp_specs, expert_fn)
        self.master_shardings = self.zero_policy.master_shardings(
            params_f32, self.tp_specs, expert_fn)
        self.grad_shardings = self.zero_policy.grad_shardings(
            params_f32, self.tp_specs, expert_fn)
        self.batch_sharding = self.mesh_mgr.batch_sharding()
        self._qw_gathers = None
        if self.config.zero_optimization.zero_quantized_weights:
            if stage != 3:
                raise ValueError("zero_quantized_weights needs ZeRO stage 3 "
                                 "(it quantizes the stage-3 param gathers)")
            self._qw_gathers = self._build_qw_gathers()

        # optimizer ----------------------------------------------------------
        # client-passed functional optimizer wins over the config section
        # (reference: deepspeed.initialize honors the client optimizer object)
        opt_cfg = self.config.optimizer
        if optimizer is not None:
            if not isinstance(optimizer, Optimizer):
                raise TypeError(
                    "optimizer must be a deepspeed_tpu.ops.optimizers.Optimizer "
                    "(build one with e.g. ops.optimizers.adamw(lr=...)); torch "
                    f"optimizers are not usable on TPU. Got {type(optimizer)}")
            self.optimizer: Optional[Optimizer] = optimizer
            self.base_lr = float(opt_cfg.params.get("lr", 1e-3)) if opt_cfg else 1e-3
        elif opt_cfg is not None:
            self.optimizer = build_optimizer(opt_cfg.type, opt_cfg.params)
            self.base_lr = float(opt_cfg.params.get("lr", 1e-3))
        else:
            self.optimizer = None
            self.base_lr = 0.0
        if self._pure_bf16 and (self.optimizer is None or
                                self.optimizer.name not in ("adam", "adamw")):
            # only Adam/AdamW implement the dtype round-trip; other
            # optimizers keep fp32 state, which would silently triple the
            # 6-bytes/param budget this mode exists for
            raise ValueError(
                "bf16.master_weights=false (pure-bf16 state) supports "
                "Adam/AdamW only; got optimizer "
                f"'{self.optimizer.name if self.optimizer else None}'")

        # lr schedule --------------------------------------------------------
        # lr_fn (step->lr, evaluated in-jit) when we own the schedule; an
        # external scheduler object instead feeds its lr into the step as an arg.
        self.lr_fn = None
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
            if isinstance(lr_scheduler, LRScheduler):
                self.lr_fn = lr_scheduler.fn
        elif self.config.scheduler is not None and self.config.scheduler.type:
            self.lr_fn = build_schedule(self.config.scheduler.type,
                                        self.config.scheduler.params)
            self.lr_scheduler = LRScheduler(self.lr_fn)
        else:
            self.lr_scheduler = None

        # ZeRO-Offload --------------------------------------------------------
        # optimizer state + fp32 master live off-device (host RAM or NVMe);
        # the device round-trips grads out / compute-dtype params in.
        off = self.config.zero_optimization.offload_optimizer
        # offload_param: TRANSIENT device params (reference: ZeRO-3 param
        # offload keeps weights host-side and pages them in per use,
        # partition_parameters.py) — HBM holds the weights only while a
        # compiled step runs; they re-materialize from the host (cpu) or
        # NVMe (ZeRO-Infinity param tier, partitioned_param_swapper.py:35)
        # master maintained by the host optimizer.
        off_p = self.config.zero_optimization.offload_param
        self.offload = None
        if off_p is not None and off_p.device in ("cpu", "nvme") \
                and (off is None or off.device not in ("cpu", "nvme")):
            raise ValueError(
                "offload_param needs offload_optimizer (the host-resident "
                "master the transient params re-materialize from)")
        if off is not None and off.device in ("cpu", "nvme"):
            if optimizer is not None:
                raise ValueError(
                    "offload_optimizer needs the optimizer declared in the "
                    "config (type + params) so the host kernel can be built; "
                    "a client optimizer object cannot be offloaded")
            if opt_cfg is None:
                raise ValueError("offload_optimizer requires an 'optimizer' "
                                 "config section")
            from .zero.offload import HostOffloadOptimizer
            self.offload = HostOffloadOptimizer(
                opt_cfg.type, opt_cfg.params, params_f32,
                self.param_shardings, self.compute_dtype,
                device=off.device, nvme_path=off.nvme_path,
                buffer_count=off.buffer_count,
                aio_config=self.config.aio.model_dump(),
                param_device=("nvme" if off_p is not None
                              and off_p.device == "nvme" else "ram"),
                param_nvme_path=(off_p.nvme_path if off_p is not None
                                 else None),
                param_buffer_count=(off_p.buffer_count if off_p is not None
                                    else 5))
        self._transient_params = bool(
            self.offload is not None and off_p is not None
            and off_p.device in ("cpu", "nvme"))

        # 1-bit explicit-collective mode --------------------------------------
        # onebit optimizers only save wire bytes if the grad sync is explicit:
        # the OneBitRunner owns the whole train step (per-rank grads out of
        # shard_map, compressed momentum exchange after freeze_step).
        self.onebit = None
        opt_key = (opt_cfg.type.lower().replace("_", "")
                   if opt_cfg is not None else "")
        if (self.offload is None and optimizer is None
                and self.optimizer is not None
                and opt_key in ("onebitadam", "zerooneadam", "onebitlamb")
                and self.mesh_mgr.shape["data"] > 1):
            for ax in ("model", "seq", "pipe", "expert"):
                if self.mesh_mgr.shape[ax] != 1:
                    raise ValueError(
                        f"1-bit optimizers support pure data parallelism; "
                        f"mesh axis '{ax}' has size {self.mesh_mgr.shape[ax]}")
            if stage > 1:
                raise ValueError(
                    "1-bit optimizers compose with ZeRO stage 0 or 1 "
                    "(optimizer-state sharding); stages >= 2 shard GRADS, "
                    "which defeats the stacked per-rank layout the "
                    "compressed momentum exchange is built on")
            if self.compression_spec is not None:
                raise ValueError(
                    "compression_training is not threaded through the 1-bit "
                    "explicit-collective step yet — disable one of the two")
            if opt_key == "zerooneadam":
                # 0/1 Adam is a DIFFERENT algorithm from 1-bit Adam
                # (adaptive variance freezing + 1-bit sync with local
                # steps, reference onebit/zoadam.py) — own runner
                from .zeroone import ZeroOneRunner
                runner_cls, head = ZeroOneRunner, ()
            else:
                from .onebit import OneBitRunner
                runner_cls = OneBitRunner
                head = ("lamb" if "lamb" in opt_key else "adam",)
            self.onebit = runner_cls(
                *head, opt_cfg.params, self.mesh, "data",
                self.apply_fn, self.loss_fn,
                self.config.gradient_accumulation_steps,
                compute_dtype=self.compute_dtype,
                grad_clip=self.config.gradient_clipping,
                loss_scaler=self.loss_scaler,
                zero_stage=stage)

        # comm-plan: per-collective algorithm selection (round 10;
        # docs/COMM.md) ------------------------------------------------------
        # Policy resolves HERE (programs are static); execution routes
        # through comm.planned -> runtime/comm/quantized.py. The MoE
        # dispatch reads the same context at trace time via the apply_fn
        # wrap, so one plan steers both wire-hot seams.
        self.comm_plan_ctx = None
        self._cp_guard = None
        self._train_step_q = None
        self._overlap_gathers = None
        cp = self.config.comm_plan
        if cp.enabled:
            from ..comm_plan import CommPlan
            from ..comm_plan.runtime import AccuracyGuard, PlanContext
            plan = CommPlan.load(cp.plan_path) if cp.plan_path else None
            self.comm_plan_ctx = PlanContext(
                plan=plan, overrides=dict(cp.overrides or {}),
                bits=cp.quant_bits, block=cp.quant_block,
                size_threshold=int(cp.size_threshold_mb * 2 ** 20),
                overlap_chunks=cp.overlap_chunks)
            self.apply_fn = self._wrap_apply_comm_plan(self.apply_fn)
            self._resolve_grad_sync_algo(params_f32)
            self._resolve_param_gather(params_f32)
            if cp.guard_min_grad_norm > 0:
                self._cp_guard = AccuracyGuard(cp.guard_min_grad_norm)
            log_dist(
                "comm plan: "
                f"plan={'recorded:' + cp.plan_path if cp.plan_path else 'heuristic'} "
                f"grad_sync={self.comm_plan_ctx.resolved.get('grad_reduce_scatter')} "
                f"param_gather={self.comm_plan_ctx.resolved.get('param_all_gather')} "
                f"overlap_chunks={cp.overlap_chunks} "
                f"overrides={dict(cp.overrides or {})} "
                f"guard={cp.guard_min_grad_norm}", ranks=[0])

        # device placement of state -----------------------------------------
        # fp32 training: params ARE the master copy — TrainState.master is kept
        # empty so the same buffers aren't donated twice through the pytree.
        with self.rec.span("train.init.place"):
            if self.onebit is not None:
                # fp32 params, replicated (pure DP); runner casts for compute
                params = jax.device_put(params_f32,
                                        NamedSharding(self.mesh, P()))
                master = ()
            elif self.offload is not None:
                params = (() if self._transient_params
                          else self.offload.current_params_device())
                master = ()
            elif self.keep_master:
                master = jax.device_put(params_f32, self.master_shardings)
                params = jax.jit(  # graftlint: disable=TPU002 (engine init: one trace per engine)
                    lambda m: jax.tree.map(lambda x: x.astype(self.compute_dtype), m),
                    out_shardings=self.param_shardings)(master)
            else:
                # fp32 (params are f32 already — no transient host copy) or
                # pure-bf16 (cast down; no master)
                cast = (params_f32 if self.compute_dtype == jnp.float32
                        else jax.tree.map(
                            lambda x: x.astype(self.compute_dtype), params_f32))
                params = jax.device_put(cast, self.param_shardings)
                master = ()
        opt_state = {}
        with self.rec.span("train.init.opt_state"):
            if self.onebit is not None:
                opt_state = {"onebit": self.onebit.init_state(params_f32)}
                self.opt_shardings = jax.tree.map(lambda x: x.sharding, opt_state)
            elif self.offload is not None:
                self.opt_shardings = {}
            else:
                self.opt_shardings = self._opt_state_shardings(params_f32)
                if self.optimizer is not None:
                    opt_state = jax.jit(self.optimizer.init,  # graftlint: disable=TPU002 (engine init: one trace per engine)
                                        out_shardings=self.opt_shardings)(
                                            master if self.keep_master else params)
        # scalars placed REPLICATED ON THE MESH, matching the canonical
        # sharding the compiled step emits for its outputs — a
        # SingleDeviceSharding here is a different jit cache key and cost a
        # spurious retrace of the whole program on the second step
        rep = NamedSharding(self.mesh, P())
        self.state = TrainState(
            step=jax.device_put(jnp.asarray(0, jnp.int32), rep),
            params=params,
            master=master,
            opt_state=opt_state,
            scale=jax.tree.map(lambda x: jax.device_put(x, rep),
                               self.loss_scaler.init()),
            skipped_steps=jax.device_put(jnp.asarray(0, jnp.int32), rep),
            nonfinite_streak=jax.device_put(jnp.asarray(0, jnp.int32), rep))
        # offload mode applies updates on host — its consecutive
        # non-finite count lives host-side too (no extra device traffic)
        self._host_nonfinite_streak = 0

        # compiled fns -------------------------------------------------------
        if self.offload is not None:
            self._grads_step = self._make_grads_step()
            self._train_step = None
        elif self.onebit is not None:
            self._grads_step = None
            self._train_step = None           # the runner owns the step
        else:
            self._grads_step = None
            self._train_step = self._make_train_step()
        self._micro_grad = self._make_micro_grad()
        self._fwd_loss = self._make_fwd_loss(train=True)
        self._fwd_loss_eval = None          # built lazily on first eval use
        self._apply_update = self._make_apply_update()
        self._eval_step = self._make_eval_step()

        # fwd/bwd/step emulation buffers -------------------------------------
        self._accum_grads = None
        self._accum_losses = []
        self._micro_count = 0
        self._last_metrics: Dict[str, Any] = {}

        # observability ------------------------------------------------------
        self.monitor = MonitorMaster(self.config)
        self.global_steps = 0
        self.micro_steps = 0

        # training-integrity sentinel (round 7; docs/RESILIENCE.md): host
        # detector over the in-jit step statistics, remediation ladder
        # (skip -> rollback -> abort), cross-replica SDC audit. The PR-3
        # nonfinite_guard streak/abort lives inside observe() — one code
        # path for every "wrong numbers" verdict.
        self.sentinel = TrainingSentinel(self.config.integrity)
        self._audit_fn = None
        # the checkpoint dir the audit marker lands in and the rollback
        # default — tracks the last save/load; an explicit
        # integrity.load_dir always wins at rollback time (a pinned
        # known-good archive must not be clobbered by a routine save)
        self._ckpt_dir: Optional[str] = self.config.integrity.load_dir
        # global batches consumed since data start: checkpointed, NOT
        # rolled back by a sentinel rollback (the poisoned span is
        # fast-forwarded past, never replayed); feeds
        # fast_forward_dataloader at resume
        self.data_position = 0
        if self.sentinel.enabled or self.config.integrity.audit_interval > 0:
            log_dist(
                f"integrity sentinel: metrics={self.config.integrity.metrics} "
                f"zmax={self.config.integrity.zmax} "
                f"skip={self.config.integrity.skip} "
                f"rollback_after={self.config.integrity.rollback_after} "
                f"audit_interval={self.config.integrity.audit_interval}",
                ranks=[0])

        # phase-aware watchdog + rank heartbeat channel (rounds 4+6;
        # docs/RESILIENCE.md): the engine reports lifecycle phases
        # (RESTORE -> COMPILE -> STEP -> SAVE), each with its own deadline;
        # a gap beyond the current phase's deadline dumps all stacks and
        # exits STALL_EXIT_CODE so the supervisor can tear the world down.
        # The heartbeat writer (opt-in via DSTPU_HEARTBEAT_DIR, exported by
        # dstpu --heartbeat-dir) mirrors every phase/step transition to a
        # per-rank file so LAUNCHER-side monitors get liveness even for
        # ranks whose ssh pipe (or scheduler) is silent.
        self.heartbeat = hb.HeartbeatWriter.from_env(
            rank=jax.process_index())
        self._step_phase_reached = False
        self.watchdog = None
        wd = self.config.watchdog
        pre_step = {hb.PHASE_COMPILE: wd.compile_timeout,
                    hb.PHASE_RESTORE: wd.restore_timeout,
                    hb.PHASE_SAVE: wd.save_timeout}
        if wd.stall_timeout > 0 or any(t > 0 for t in pre_step.values()):
            from .watchdog import StallWatchdog
            self.watchdog = StallWatchdog(
                wd.stall_timeout or 0.0,
                poll_interval=wd.poll_interval or None,
                phase_timeouts=pre_step,
                heartbeat=self.heartbeat,
                phase=hb.PHASE_INIT)
            if any(t > 0 for t in pre_step.values()):
                # pre-step deadlines need the monitor BEFORE the first
                # completed step — the round-4 blind spot (a compile or
                # restore hang) is exactly what they bound. The INIT
                # phase itself stays unbounded here (init_deadline's
                # jurisdiction); the clock starts mattering at the first
                # phase transition.
                self.watchdog.start()
            log_dist(f"watchdog configured: stall={wd.stall_timeout}s "
                     f"compile={wd.compile_timeout}s "
                     f"restore={wd.restore_timeout}s "
                     f"save={wd.save_timeout}s", ranks=[0])
        if self.heartbeat is not None:
            self.heartbeat.write(hb.PHASE_INIT, 0, force=True)

        # straggler defense (round 15; runtime/straggler.py,
        # docs/RESILIENCE.md): the rolling step_ms gauge is stamped into
        # every STEP heartbeat unconditionally (it is just timekeeping —
        # `dstpu health` renders it as RATE); the cross-rank detector is
        # opt-in. Each rank runs the SAME detector over the SAME shared
        # channel snapshot and acts only on verdicts against ITSELF (the
        # SDC self-flagging pattern): rung 1 stamps the sticky STRAGGLER
        # flag, rung 3 (straggler.abort_after > 0) exits rc 117 so the
        # elastic agent relaunches the world without this host.
        self._step_clock = straggler_lib.StepClock(
            window=self.config.straggler.window)
        self.straggler: Optional[straggler_lib.StragglerDetector] = None
        self._straggler_next_check = 0.0
        self._straggler_flagged = False
        if self.config.straggler.enabled and self.heartbeat is not None:
            self.straggler = straggler_lib.StragglerDetector(
                self.config.straggler)
            log_dist(
                f"straggler detector: zmax={self.config.straggler.zmax} "
                f"rel_threshold={self.config.straggler.rel_threshold} "
                f"strike_window={self.config.straggler.strike_window} "
                f"abort_after={self.config.straggler.abort_after}"
                + (" (evidence-only)"
                   if self.config.straggler.abort_after <= 0 else ""),
                ranks=[0])

        # progressive layer drop + eigenvalue (reference: engine hooks for
        # runtime/progressive_layer_drop.py + runtime/eigenvalue.py) ---------
        self.progressive_layer_drop = None
        if self.config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop
            pld_cfg = self.config.progressive_layer_drop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.theta, gamma=pld_cfg.gamma)
        self.eigenvalue = None
        if self.config.eigenvalue.enabled:
            from .eigenvalue import Eigenvalue
            ev = self.config.eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=ev.verbose, max_iter=ev.max_iter, tol=ev.tol,
                stability=ev.stability,
                gas_boundary_resolution=ev.gas_boundary_resolution,
                layer_name=ev.layer_name, layer_num=ev.layer_num)

        # data efficiency: seqlen curriculum (reference: engine curriculum
        # hooks + data_pipeline/data_sampling) -------------------------------
        self.curriculum = None
        cl_cfg = None
        if self.config.curriculum_learning.enabled:
            cl_cfg = self.config.curriculum_learning.model_dump()
        elif self.config.data_efficiency.enabled:
            clc = (self.config.data_efficiency.data_sampling or {}).get(
                "curriculum_learning") or {}
            if clc.get("enabled"):
                cl_cfg = clc
        if cl_cfg is not None:
            from .data_pipeline import CurriculumBatchTransform
            self.curriculum = CurriculumBatchTransform(cl_cfg)
            log_dist(f"curriculum learning: {cl_cfg.get('curriculum_type', 'seqlen')} "
                     f"{cl_cfg['min_difficulty']}->{cl_cfg['max_difficulty']} "
                     f"({cl_cfg.get('schedule_type', 'fixed_linear')})",
                     ranks=[0])

        from ..config.config import warn_unconsumed
        warn_unconsumed(self.config)
        log_dist(f"DeepSpeedEngine initialized: ZeRO stage {stage}, "
                 f"dtype {self.config.precision_dtype}, mesh {self.mesh_mgr.describe()}, "
                 f"batch {self.config.train_batch_size} "
                 f"(micro {self.config.train_micro_batch_size_per_gpu} x gas "
                 f"{self.config.gradient_accumulation_steps} x dp {dp})", ranks=[0])

    # ------------------------------------------------------------------ setup

    def _build_apply_fn(self, model) -> Callable:
        """Adapt a flax module (or raw callable) to (params, batch, rng, train)."""
        if model is None:
            raise ValueError("model must be a flax module or apply_fn given")
        if not hasattr(model, "apply"):
            # raw callable(params, batch) -> outputs
            return lambda params, batch, rng, train: model(params, batch)
        sig = None
        try:
            sig = inspect.signature(model.__call__)
        except (TypeError, ValueError):
            pass
        takes_train = sig is not None and "train" in sig.parameters

        # probe once whether .apply accepts rngs (flax does; plain objects with
        # an .apply attribute may not) — a runtime try/except would swallow
        # genuine TypeErrors raised inside the model
        takes_rngs = True
        try:
            apply_sig = inspect.signature(model.apply)
            takes_rngs = ("rngs" in apply_sig.parameters or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in apply_sig.parameters.values()))
        except (TypeError, ValueError):
            pass

        # the "pld" stream is threaded only when the engine actually runs
        # progressive layer drop — unused extra rng streams through nn.scan
        # disturb the remat policy (measured bench regression)
        wants_pld = self.config.progressive_layer_drop.enabled

        def apply_fn(params, batch, rng, train):
            kwargs = {"train": train} if takes_train else {}
            if takes_rngs:
                if train:
                    r_drop, r_gate, r_pld = jax.random.split(rng, 3)
                    kwargs["rngs"] = {"dropout": r_drop, "gating": r_gate}
                    if wants_pld:
                        kwargs["rngs"]["pld"] = r_pld
                else:
                    kwargs["rngs"] = None
            return model.apply({"params": params}, batch, **kwargs)

        return apply_fn

    def _init_params(self, example_batch) -> PyTree:
        init_rng, self._rng = jax.random.split(self._rng)
        sig = None
        try:
            sig = inspect.signature(self.module.__call__)
        except (TypeError, ValueError):
            pass
        kwargs = {"train": False} if sig is not None and "train" in sig.parameters else {}
        # jit: abstract init is faster and partial-auto shard_map regions in
        # the model (ring attention, explicit-a2a MoE) require a jit context
        example_batch = jax.tree.map(jnp.asarray, example_batch)
        variables = jax.jit(  # graftlint: disable=TPU002 (param init: one trace per engine)
            lambda rng, batch: self.module.init(rng, batch, **kwargs)
        )(init_rng, example_batch)
        return variables["params"]

    def _opt_state_shardings(self, params_f32):
        """Optimizer-state slots are param-shaped trees (m/v/momentum/...);
        shard each exactly like the fp32 master so updates stay local."""
        if self.optimizer is None:
            return {}
        shape_state = jax.eval_shape(self.optimizer.init, params_f32)
        treedef = jax.tree.structure(params_f32)
        master_flat = jax.tree.leaves(self.master_shardings)

        def per_slot(sub):
            try:
                treedef.flatten_up_to(sub)
                return jax.tree.unflatten(treedef, master_flat)
            except (ValueError, TypeError):
                return jax.tree.map(
                    lambda ls: NamedSharding(
                        self.mesh, self.zero_policy.master_spec(ls.shape, None)),
                    sub)

        return {k: per_slot(v) for k, v in shape_state.items()}

    # ----------------------------------------------------------- compiled fns

    def _build_qw_gathers(self):
        """ZeRO++ qwZ: one quantized-gather fn per ZeRO-sharded param leaf
        (reference: ZeRO++'s quantized weight communication; the int8 gather
        replaces the implicit bf16 stage-3 all-gather)."""
        from .comm.compressed import make_quantized_gather

        def per_leaf(sharding):
            spec = sharding.spec
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                names = (entry,) if isinstance(entry, str) else tuple(entry)
                zero_names = [n for n in names if n in
                              ("data", "expert", "seq")]
                if zero_names and any(
                        self.mesh_mgr.shape.get(n, 1) > 1
                        for n in zero_names):
                    return make_quantized_gather(
                        self.mesh, tuple(names), dim, spec=spec)
            return None

        return jax.tree.map(per_leaf, self.param_shardings)

    def _qw_gather_params(self, params):
        if self._qw_gathers is None:
            return params
        return jax.tree.map(
            lambda fn, p: p if fn is None else fn(p),
            self._qw_gathers, params,
            is_leaf=lambda x: x is None or callable(x))

    def _grads_of_micro(self, params, scale_state, micro, rng, step=None):
        """Scaled-loss grads for one microbatch; returns (grads, unscaled loss)."""

        def scaled_loss(p):
            # qwZ: int8 gather inside the differentiated closure so the
            # custom-vjp slice maps grads back to the shards; overlap:
            # chunked gather inside it so its transpose reduce-scatters
            # the grads in the same chunks
            with jax.named_scope("zero.gather"):
                p = self._qw_gather_params(p)
                p = self._overlap_gather_params(p)
            if self.compression_spec is not None:
                from ..compression import apply_compression
                p = apply_compression(
                    p, self.compression_spec,
                    step if step is not None else jnp.asarray(0, jnp.int32))
            out = self.apply_fn(p, micro, rng, True)
            with jax.named_scope("loss"):
                loss = self.loss_fn(out, micro)
            return (loss * scale_state.scale).astype(jnp.float32), loss

        grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
        # where ZeRO places the gradients' reduce-scatter: the constraint
        # to their sharded layout
        with jax.named_scope("zero.scatter"):
            grads = jax.tree.map(lambda g, s: lax.with_sharding_constraint(
                g.astype(self.grad_accum_dtype), s), grads,
                self.grad_shardings)
        return grads, loss

    @jax.named_scope("optimizer")
    def _finalize_step(self, state: TrainState, grads_sum, n_micro, lr_arg,
                       spike_limit=None):
        """Shared tail: unscale, clip, optimize, loss-scale bookkeeping.

        ``lr_arg``: host-computed lr (external scheduler objects); ignored when
        the schedule is an in-jit lr_fn.

        ``spike_limit``: the sentinel's grad-norm ceiling (remediation
        ladder rung 1; +inf during warmup). A step whose raw global norm
        exceeds it is skipped through the SAME keep-old-state path the
        fp16 overflow skip uses — one skip semantics for scaler overflow,
        non-finite grads, and detected spikes. ``None`` (integrity off)
        compiles the check away entirely."""
        master = state.master if self.keep_master else state.params
        denom = n_micro * state.scale.scale
        grads = jax.tree.map(lambda g: g / denom, grads_sum)
        overflow = LossScaler.has_overflow(grads)

        # global grad norm: at jit level grads are logically global, so this IS
        # the global norm; XLA inserts cross-shard reductions (reference:
        # get_global_norm + clip_grad_norm_ w/ allreduce, runtime/utils.py)
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        global_norm = jnp.sqrt(sq)
        spiked = None
        skip = overflow
        if spike_limit is not None:
            spiked = global_norm > spike_limit
            skip = overflow | spiked
        clip = self.config.gradient_clipping
        if clip > 0:
            coef = jnp.minimum(clip / (global_norm + 1e-6), 1.0)
            grads = jax.tree.map(lambda g: g * coef, grads)

        lr = self.lr_fn(state.step) if self.lr_fn is not None else lr_arg

        new_master, new_opt = self.optimizer.update(
            grads, state.opt_state, master, state.step, lr_t=lr)
        master_sh = self.master_shardings if self.keep_master else self.param_shardings
        new_master = jax.tree.map(lambda x, s: lax.with_sharding_constraint(x, s),
                                  new_master, master_sh)

        # skip → keep old state, count a skipped step (reference: engine.step
        # overflow path engine.py:2105-2112; sentinel spikes ride the same arm)
        keep = lambda old, new: jax.tree.map(
            lambda a, b: jnp.where(skip, a, b), old, new)
        new_master = keep(master, new_master)
        new_opt = keep(state.opt_state, new_opt)

        if self.keep_master:
            new_params = jax.tree.map(
                lambda m, s: lax.with_sharding_constraint(
                    m.astype(self.compute_dtype), s),
                new_master, self.param_shardings)
        else:
            new_params = new_master

        # skip streak: consecutive skipped steps of ANY kind, counted
        # in-jit (a bf16 run has no loss scaler to notice divergence; fp16
        # counts too — a scale already at min_scale that still overflows
        # is the same signal; a sentinel spike skip is the same verdict).
        # The host only reads this in _after_step's batched pull.
        prev_streak = (state.nonfinite_streak
                       if state.nonfinite_streak is not None
                       else jnp.asarray(0, jnp.int32))
        new_streak = jnp.where(skip, prev_streak + 1, 0).astype(jnp.int32)

        # a skip does not advance the optimizer step (Adam bias correction /
        # in-jit lr schedules stay put), matching the reference's skip path;
        # the loss scale reacts to GENUINE overflow only — a finite spike
        # must not shrink a healthy fp16 scale
        new_state = TrainState(
            step=state.step + 1 - skip.astype(jnp.int32),
            params=new_params,
            master=new_master if self.keep_master else (),
            opt_state=new_opt,
            scale=self.loss_scaler.update(state.scale, overflow),
            skipped_steps=state.skipped_steps + skip.astype(jnp.int32),
            nonfinite_streak=new_streak)
        metrics = {"grad_norm": global_norm, "lr": lr, "overflow": skip,
                   "loss_scale": state.scale.scale,
                   "nonfinite_streak": new_streak}
        if spiked is not None:
            metrics["anomaly_skip"] = spiked
        integ = self.config.integrity
        if integ.enabled:
            # sentinel statistics, computed in-jit so they ride the one
            # batched host pull: the update norm (0 on a skipped step) and
            # the param norm — divergence signals a grad norm alone misses
            if "update_norm" in integ.metrics:
                usq = sum(jnp.sum(jnp.square((a - b).astype(jnp.float32)))
                          for a, b in zip(jax.tree.leaves(new_master),
                                          jax.tree.leaves(master)))
                metrics["update_norm"] = jnp.sqrt(usq)
            if "param_norm" in integ.metrics:
                psq = sum(jnp.sum(jnp.square(p.astype(jnp.float32)))
                          for p in jax.tree.leaves(new_master))
                metrics["param_norm"] = jnp.sqrt(psq)
        return new_state, metrics

    def _make_train_step(self):
        gas = self.config.gradient_accumulation_steps

        def train_step(state: TrainState, micros, rng, lr_arg,
                       spike_limit=None):
            # micros: [gas, global_micro, ...], dim 1 sharded over the DP axes
            rngs = jax.random.split(rng, gas)
            zero_grads = jax.tree.map(
                lambda p, s: lax.with_sharding_constraint(
                    jnp.zeros(p.shape, self.grad_accum_dtype), s),
                state.params, self.grad_shardings)

            def micro_step(acc, xs):
                micro, r = xs
                grads, loss = self._grads_of_micro(state.params, state.scale,
                                                   micro, r, state.step)
                with jax.named_scope("grad_accum"):
                    acc = jax.tree.map(
                        lambda a, g, s: lax.with_sharding_constraint(a + g, s),
                        acc, grads, self.grad_shardings)
                return acc, loss

            grads_sum, losses = lax.scan(micro_step, zero_grads, (micros, rngs))
            new_state, metrics = self._finalize_step(
                state, grads_sum, float(gas), lr_arg, spike_limit=spike_limit)
            metrics["loss"] = jnp.mean(losses)
            return new_state, metrics

        return jax.jit(train_step, donate_argnums=(0,))

    # ------------------------------------------------- comm-plan grad sync

    def _wrap_apply_comm_plan(self, apply_fn):
        """Install the engine's plan context around every model trace so
        trace-time seams (the MoE dispatch) read THIS engine's plan —
        thread-local and scoped, so a second engine in the same process
        never inherits it."""
        from ..comm_plan.runtime import use_context
        ctx = self.comm_plan_ctx

        def wrapped(params, batch, rng, train):
            with use_context(ctx):
                return apply_fn(params, batch, rng, train)

        return wrapped

    def _grad_sync_envelope(self) -> Tuple[bool, str]:
        """Can the explicit stacked-grads sync replace the implicit XLA
        grad reduction here? Mirrors the 1-bit runner's envelope: the
        stacked per-rank layout needs a fused step the engine owns, and
        data parallelism optionally COMPOSED with TP (round 14): the
        model axis stays auto in the partial-auto stacked region, each
        leaf syncing over its own stacked layout."""
        if self.onebit is not None:
            return False, "the 1-bit runner owns the train step"
        if self.offload is not None:
            return False, "offload mode splits the step across host/device"
        if self.compression_spec is not None:
            return False, ("compression_training is not threaded through "
                           "the stacked-grads step")
        ok, why = self.zero_policy.grad_sync_viable()
        if not ok:
            return False, why
        for ax in ("seq", "pipe"):
            if self.mesh_mgr.shape[ax] != 1:
                return False, (f"mesh axis '{ax}' has size "
                               f"{self.mesh_mgr.shape[ax]} (data "
                               "parallelism, optionally with TP, "
                               "required)")
        if self.mesh_mgr.shape["data"] <= 1:
            return False, "a single DP rank has nothing to sync"
        return True, ""

    def _resolve_grad_sync_algo(self, params_f32) -> None:
        """Init-time resolution of the ZeRO-2 grad-sync wire format
        (programs are static, so the verdict is per-engine, modulo the
        accuracy guard's host-side exact fallback). A verdict outside
        the envelope — forced or selected — DEGRADES to exact with a
        warning (round 14: selection and overrides must never brick a
        launch; the envelope test pins which configs degrade)."""
        from ..comm_plan.runtime import resolve_algo
        ctx = self.comm_plan_ctx
        itemsize = jnp.dtype(self.grad_accum_dtype).itemsize
        grad_bytes = sum(
            int(np.prod(np.shape(p)) if np.shape(p) else 1)
            for p in jax.tree.leaves(params_f32)) * itemsize
        n = self.mesh_mgr.shape["data"] * self.mesh_mgr.shape["expert"]
        algo = resolve_algo(ctx, "grad_reduce_scatter", "data", grad_bytes,
                            axis_size=n)
        if algo != "exact":
            ok, why = self._grad_sync_envelope()
            if not ok:
                forced = any((ctx.overrides or {}).get(k)
                             for k in ("grad_reduce_scatter",
                                       "reduce_scatter"))
                logger.warning(
                    "comm_plan: grad sync %s %r but %s — running exact",
                    "forced" if forced else "selected", algo, why)
                algo = "exact"
                ctx.resolved["grad_reduce_scatter"] = "exact"
        self._grad_sync_algo = algo

    # --------------------------------------------- comm-plan param gather

    def _param_gather_viable(self) -> Tuple[bool, str]:
        """Engine-side envelope for the explicit chunked ZeRO-3 param
        fetch (per-leaf checks live in ``_resolve_param_gather``)."""
        if self.zero_policy.stage < 3:
            return False, ("ZeRO stage < 3 keeps compute params whole — "
                           "there is no param gather to overlap")
        if self._qw_gathers is not None:
            return False, ("zero_quantized_weights already owns the "
                           "explicit param gather (qwZ)")
        if self.offload is not None:
            return False, "offload mode splits the step across host/device"
        if self.onebit is not None:
            return False, "the 1-bit runner owns the train step"
        return True, ""

    def _resolve_param_gather(self, params_f32) -> None:
        """Per-LEAF init-time resolution of the ZeRO-3 param-fetch wire
        schedule: each ZeRO-sharded leaf queries the plan in its own
        size bucket (site ``param_all_gather`` -> kind ``all_gather``),
        and leaves the overlap family covers get an explicit chunked
        gather replacing the implicit whole-tensor stage-3 allgather.
        Leaves outside the per-leaf envelope (TP-composed specs, tiny
        leaves under ``overlap_min_leaf_elems``) stay implicit —
        downgrade, never raise."""
        from ..comm.planned import planned_param_gather
        from ..comm_plan.runtime import resolve_algo
        ctx = self.comm_plan_ctx
        cp = self.config.comm_plan
        ctx.resolved.setdefault("param_all_gather", "exact")
        ok, why = self._param_gather_viable()
        if not ok:
            forced = any((ctx.overrides or {}).get(k)
                         for k in ("param_all_gather", "all_gather"))
            if forced and self.zero_policy.stage >= 3:
                logger.warning(
                    "comm_plan: param gather forced but %s — running the "
                    "implicit gather", why)
            return
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        n_overlap = 0

        def per_leaf(sharding, leaf):
            nonlocal n_overlap
            site = self.zero_policy.zero_gather_site(sharding.spec)
            numel = int(np.prod(np.shape(leaf)) if np.shape(leaf) else 1)
            if site is None or numel < cp.overlap_min_leaf_elems:
                return None
            zero_dim, zero_names = site
            algo = resolve_algo(ctx, "param_all_gather", "data",
                                numel * itemsize,
                                axis_size=int(np.prod(
                                    [self.mesh_mgr.shape[a]
                                     for a in zero_names])))
            if algo not in ("overlap", "overlap_int8"):
                return None
            n_overlap += 1
            return planned_param_gather(
                self.mesh, zero_names, zero_dim, algo=algo,
                chunks=cp.overlap_chunks, bits=cp.quant_bits,
                block=cp.quant_block)

        gathers = jax.tree.map(per_leaf, self.param_shardings, params_f32)
        if n_overlap:
            self._overlap_gathers = gathers
        # the aggregate audit tag: overlap iff ANY leaf left the
        # implicit path (per-leaf verdicts differ across size buckets)
        ctx.resolved["param_all_gather"] = (
            "overlap" if n_overlap else "exact")

    def _overlap_gather_params(self, params):
        if self._overlap_gathers is None:
            return params
        return jax.tree.map(
            lambda fn, p: p if fn is None else fn(p),
            self._overlap_gathers, params,
            is_leaf=lambda x: x is None or callable(x))

    def _make_train_step_quantized(self):
        """The comm-plan train step: per-rank grads come out of a
        shard_map UNREDUCED (the 1-bit runner's stacked layout), the sync
        is the explicit reduce-scatter + all-gather in the resolved wire
        format — blockwise-int8, or the chunked ``overlap`` schedule
        (``comm.planned_grad_sync``) — and everything from the synced
        grads on — clip, optimizer, skip arms, sentinel — is the shared
        ``_finalize_step`` tail, so the two programs differ ONLY in how
        grad bytes cross the wire. With TP composed (round 14) the model
        axis stays AUTO: params ride in
        TP-sharded, the model trace keeps its TP constraints (the
        local region strips only the manual DP axes), and each grad
        leaf syncs over its own stacked layout."""
        gas = self.config.gradient_accumulation_steps
        axes = self.zero_policy.grad_sync_axes()
        cp = self.config.comm_plan
        algo = self._grad_sync_algo
        mesh = self.mesh
        tp_composed = self.mesh_mgr.shape["model"] > 1
        from ..comm.planned import planned_grad_sync
        from ..comm_plan.runtime import local_region

        def local(params, micros_all, rng, scale):
            r = jax.random.fold_in(rng, lax.axis_index(axes))
            rngs = jax.random.split(r, gas)

            def body(acc, xs):
                micro, rr = xs

                def scaled_loss(p):
                    # shard-local model trace: manual-axis mesh
                    # constraints don't apply here (local_region makes
                    # _spec_constraint a no-op / strips the manual axes
                    # when TP rides along as an auto axis)
                    with local_region(manual_axes=set(axes)
                                      if tp_composed else None):
                        out = self.apply_fn(p, micro, rr, True)
                        loss = self.loss_fn(out, micro)
                    return (loss * scale).astype(jnp.float32), loss

                grads, loss = jax.grad(scaled_loss, has_aux=True)(params)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(self.grad_accum_dtype),
                    acc, grads)
                return acc, loss

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, self.grad_accum_dtype), params)
            gsum, losses = lax.scan(body, zero, (micros_all, rngs))
            return (jax.tree.map(lambda g: g[None], gsum), losses[None])

        mapped = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(), P(None, axes), P(), P()),
                           out_specs=(P(axes), P(axes)),
                           axis_names=set(axes), check_vma=False)

        def train_step(state, micros, rng, lr_arg, spike_limit=None):
            grads_st, losses_st = mapped(state.params, micros, rng,
                                         state.scale.scale)
            synced = jax.tree.map(
                lambda g: planned_grad_sync(
                    g, mesh=mesh, axis=axes, algo=algo,
                    bits=cp.quant_bits, block=cp.quant_block, mean=True,
                    chunks=cp.overlap_chunks),
                grads_st)
            grads_sum = jax.tree.map(
                lambda g, s: lax.with_sharding_constraint(
                    g.astype(self.grad_accum_dtype), s),
                synced, self.grad_shardings)
            new_state, metrics = self._finalize_step(
                state, grads_sum, float(gas), lr_arg,
                spike_limit=spike_limit)
            metrics["loss"] = jnp.mean(losses_st)
            return new_state, metrics

        return jax.jit(train_step, donate_argnums=(0,))

    def _active_train_step(self):
        """Pick the per-step program: the explicit-sync step when the
        plan routed it, unless the accuracy guard latched exact (both
        stay compiled — switching is free after the first use of each).
        The guard applies to LOSSY wire formats only: ``overlap`` moves
        exact values, so forcing it back to the whole-tensor schedule
        would change nothing numerically."""
        from ..comm_plan.plan import QUANTIZED_ALGOS
        algo = getattr(self, "_grad_sync_algo", "exact")
        guard_latched = (self._cp_guard is not None
                         and self._cp_guard.use_exact
                         and algo in QUANTIZED_ALGOS)
        if (self.comm_plan_ctx is not None and algo != "exact"
                and not guard_latched):
            if self._train_step_q is None:
                self._train_step_q = self._make_train_step_quantized()
            return self._train_step_q, algo
        return self._train_step, "exact"

    def _make_grads_step(self):
        """Offload mode: the compiled step ends at the summed grads — the
        optimizer runs on the host (reference: cpu_offload grads land in CPU
        buffers and CPUAdam consumes them, stage_1_and_2.py:1074)."""
        gas = self.config.gradient_accumulation_steps

        def grads_step(params, scale_state, micros, rng, step):
            rngs = jax.random.split(rng, gas)
            zero_grads = jax.tree.map(
                lambda p, s: lax.with_sharding_constraint(
                    jnp.zeros(p.shape, self.grad_accum_dtype), s),
                params, self.grad_shardings)

            def micro_step(acc, xs):
                micro, r = xs
                grads, loss = self._grads_of_micro(params, scale_state, micro,
                                                   r, step)
                acc = jax.tree.map(
                    lambda a, g, s: lax.with_sharding_constraint(a + g, s),
                    acc, grads, self.grad_shardings)
                return acc, loss

            grads_sum, losses = lax.scan(micro_step, zero_grads, (micros, rngs))
            overflow = LossScaler.has_overflow(grads_sum)
            sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads_sum))
            return grads_sum, jnp.mean(losses), jnp.sqrt(sq), overflow

        return jax.jit(grads_step)

    def _apply_offload_update(self, grads_sum, n_micro: float, loss,
                              raw_norm, overflow) -> Dict[str, Any]:
        """Host tail of the offload step: unscale/clip folded into the C++
        kernel's grad_scale, loss-scale bookkeeping on host."""
        state = self.state
        overflow_h = bool(jax.device_get(overflow))
        scale = float(jax.device_get(state.scale.scale))
        denom = n_micro * scale
        gnorm = float(jax.device_get(raw_norm)) / denom
        # sentinel rung 1 on the host tail (the offload optimizer runs
        # host-side, so the skip decision can too — same semantics as the
        # in-jit arm, same keep-old-state outcome)
        limit = self.sentinel.spike_limit()
        spiked = bool(limit is not None and gnorm > limit)
        skip = overflow_h or spiked
        new_scale = self.loss_scaler.update(state.scale,
                                            jnp.asarray(overflow_h))
        clip = self.config.gradient_clipping
        coef = min(clip / (gnorm + 1e-6), 1.0) if clip > 0 else 1.0
        if self.lr_fn is not None:
            lr = float(jax.device_get(self.lr_fn(state.step)))
        else:
            lr = float(jax.device_get(self._current_lr()))
        self._host_nonfinite_streak = (
            self._host_nonfinite_streak + 1 if skip else 0)
        if skip:
            self.state = state.replace(
                scale=new_scale,
                skipped_steps=state.skipped_steps + 1,
                nonfinite_streak=jnp.asarray(self._host_nonfinite_streak,
                                             jnp.int32))
        else:
            step_1based = int(jax.device_get(state.step)) + 1
            new_params = self.offload.apply(
                grads_sum, step_1based, lr, grad_scale=denom / coef,
                materialize=not self._transient_params)
            self.state = state.replace(
                step=state.step + 1,
                params=() if self._transient_params else new_params,
                scale=new_scale,
                nonfinite_streak=jnp.asarray(0, jnp.int32))
        out = {"loss": loss, "lr": lr, "grad_norm": gnorm,
               "overflow": skip, "loss_scale": scale,
               "nonfinite_streak": self._host_nonfinite_streak}
        if limit is not None:
            out["anomaly_skip"] = spiked
        return out

    def _make_micro_grad(self):
        def micro_grad(params, scale_state, batch, rng, step):
            grads, loss = self._grads_of_micro(params, scale_state, batch, rng,
                                               step)
            return grads, loss

        return jax.jit(micro_grad)

    def _make_fwd_loss(self, train: bool = True):
        """Forward-only loss for one microbatch — no backward pass compiled
        in. ``train`` feeds the model's mode flag: the eval-mode program
        runs deterministically (dropout off), the reference's eval/no_grad
        forward."""
        def fwd_loss(params, batch, rng, step):
            params = self._qw_gather_params(params)
            params = self._overlap_gather_params(params)
            if self.compression_spec is not None:
                from ..compression import apply_compression
                params = apply_compression(params, self.compression_spec, step)
            out = self.apply_fn(params, batch, rng, train)
            return self.loss_fn(out, batch)

        return jax.jit(fwd_loss)

    def _make_apply_update(self):
        def apply_update(state, grads_sum, n_micro, lr_arg, spike_limit=None):
            return self._finalize_step(state, grads_sum, n_micro, lr_arg,
                                       spike_limit=spike_limit)

        return jax.jit(apply_update, donate_argnums=(0,))

    def _make_eval_step(self):
        def eval_step(params, batch, rng, step):
            params = self._qw_gather_params(params)
            params = self._overlap_gather_params(params)
            if self.compression_spec is not None:
                from ..compression import apply_compression
                params = apply_compression(params, self.compression_spec, step)
            out = self.apply_fn(params, batch, rng, False)
            return out

        return jax.jit(eval_step)

    # -------------------------------------------------------------- public API

    def _current_lr(self):
        """Host-side lr for the next step (used when no in-jit lr_fn owns it)."""
        if self.lr_fn is None and self.lr_scheduler is not None and \
                hasattr(self.lr_scheduler, "get_lr"):
            return jnp.asarray(float(self.lr_scheduler.get_lr()[0]), jnp.float32)
        return jnp.asarray(self.base_lr, jnp.float32)

    def _params_device(self):
        """Device params for a compute call — in offload_param transient mode
        the weights live host-side and materialize here (freed when the
        returned pytree is dropped after the step)."""
        if self._transient_params:
            return self.offload.current_params_device()
        return self.state.params

    def shard_batch(self, batch):
        """Place a host batch onto the mesh, split over the DP axes."""
        return jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), self.batch_sharding), batch)

    def next_rng(self):
        self._rng, out = jax.random.split(self._rng)
        return out

    # --- lifecycle phase reporting (watchdog deadlines + heartbeat file) ----

    def _report_phase(self, phase: str) -> None:
        """Move the watchdog clock into ``phase`` and mirror the
        transition to the per-rank heartbeat file (phase transitions
        always write; only same-phase repeats are throttled)."""
        if self.watchdog is not None:
            self.watchdog.start().enter_phase(phase, step=self.global_steps)
        if self.heartbeat is not None:
            self.heartbeat.write(phase, self.global_steps, force=True)
        if phase != hb.PHASE_STEP:
            # the gap spanning a non-step phase (COMPILE, RESTORE) must
            # not be charged to the step_ms gauge as a step
            self._step_clock.reset()

    def _phase_scope(self, phase: str):
        """Bracket a bounded lifecycle section (RESTORE/SAVE): the phase's
        own deadline applies inside, and the previous phase resumes with a
        fresh clock on exit."""
        import contextlib
        if self.heartbeat is not None:
            self.heartbeat.write(phase, self.global_steps, force=True)
        # the section's duration must not pollute the step_ms gauge (a
        # checkpoint save is not a slow step); the next step boundary
        # re-baselines the clock
        self._step_clock.reset()
        if self.watchdog is not None:
            self.watchdog.start()
            return self.watchdog.phase_scope(phase)
        return contextlib.nullcontext()

    def train_batch(self, batch) -> Dict[str, Any]:
        """Run one full global batch (all gas microbatches) in one compiled step.

        The fused fast path — equivalent to gas x (forward+backward) + step of
        the reference, with comm/compute overlap handled by XLA. The batch's
        leading dim is the global batch size; it is split [gas, micro] on the
        host so each microbatch stays contiguous per DP shard."""
        if self.optimizer is None:
            raise RuntimeError(
                "engine has no optimizer: add an 'optimizer' section to the "
                "config or pass optimizer= to initialize()")
        rec = self.rec
        with rec.step_span("train.step", step_num=self.global_steps):
            with rec.span("train.prepare"):
                batch = self._prepare_batch(batch)
            with rec.span("train.h2d"):
                micros = self._put_micro_batches(batch)
            # the fused step is one program, named as a device trace's
            # "XLA Modules" line names its runs (the 1-bit and offload
            # paths launch several)
            fused = self._train_step
            launch = {} if fused is None else {
                "program": "jit_" + fused.__name__}
            with rec.span("train.dispatch", **launch):
                metrics = self._dispatch_step(micros)
            # the engine blocks on the loss every step: the watchdog's
            # liveness rule (_after_step: "a wedged collective never
            # reaches this line") and zeroone's host mean lean on it
            with rec.span("train.sync"):
                jax.block_until_ready(metrics["loss"])
            with rec.span("train.after_step"):
                self._after_step(metrics)
            # counted AFTER remediation: a sentinel rollback preserves the
            # pipeline position (the poisoned span is never replayed), and
            # this batch was consumed regardless of its verdict
            self.data_position += 1
        self._report_spans()
        return metrics

    def _prepare_batch(self, batch):
        """Host work on the batch before it goes to the device: the
        run-phase failpoints, the first-call COMPILE phase, curriculum and
        progressive layer drop."""
        # run-phase failpoints (testing/chaos.py; armed via DSTPU_CHAOS in
        # subprocess chaos tests, no-ops otherwise): a crashing, preempted
        # or wedged rank at a step boundary
        chaos.failpoint("run.kill")
        chaos.failpoint("run.preempt")
        chaos.failpoint("run.hang")
        # degraded-not-dead: sleep mode (with every=/p= jitter) makes THIS
        # rank slow while it keeps stepping — the straggler-defense shape
        # no dead/wrong check can see (spec e.g.
        # "run.slow:sleep:ms=300:times=0")
        chaos.failpoint("run.slow")
        # sentinel chaos: a poisoned batch — float features scaled by
        # `factor`, producing the finite-but-huge grad spike the integrity
        # ladder exists to remediate (spec e.g.
        # "sentinel.spike:flag:skip=10:times=3:factor=1000")
        spike = chaos.flag("sentinel.spike")
        if spike is not None:
            batch = jax.tree.map(
                lambda x: (np.asarray(x) * spike
                           if np.issubdtype(np.asarray(x).dtype, np.floating)
                           else x), batch)
        if not self._step_phase_reached:
            # the window from the FIRST train_batch entry to the first
            # completed step is COMPILE (XLA compile + sharded-restore
            # materialization) — bounded by watchdog.compile_timeout, a
            # hang the round-4 step-armed clock could never see
            self._report_phase(hb.PHASE_COMPILE)
            chaos.failpoint("run.compile_hang")
        if self.curriculum is not None:
            batch = self.curriculum(batch, self.global_steps)
        if self.progressive_layer_drop is not None and isinstance(batch, dict):
            theta = self.progressive_layer_drop.update_state(self.global_steps)
            bsz = len(next(iter(batch.values())))
            batch = dict(batch, pld_theta=np.full((bsz,), theta, np.float32))
        return batch

    def _put_micro_batches(self, batch):
        """Split the global batch [gas, micro] on the host and put it on
        the mesh (counter ``train.h2d_bytes``)."""
        from ..parallel.mesh import BATCH_AXES
        gas = self.config.gradient_accumulation_steps
        micro_sharding = NamedSharding(self.mesh, P(None, BATCH_AXES))
        micros = jax.tree.map(
            lambda x: jax.device_put(
                jnp.asarray(x).reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                micro_sharding),
            batch)
        self.rec.count("train.h2d_bytes",
                       sum(x.nbytes for x in jax.tree.leaves(micros)))
        return micros

    def _dispatch_step(self, micros) -> Dict[str, Any]:
        """Enqueue one optimizer step over the micro-batches on whichever
        path the config picked (1-bit, offload, or the fused program);
        updates ``self.state`` and returns the step's metrics."""
        gas = self.config.gradient_accumulation_steps
        if self.onebit is not None:
            if self.lr_fn is not None:
                lr = float(jax.device_get(self.lr_fn(self.state.step)))
            else:
                lr = float(jax.device_get(self._current_lr()))
            # the runner's program schedule (warmup freeze / v-update and
            # local-step intervals) must count only EFFECTIVE steps: an
            # fp16 overflow reverts the optimizer state in-jit, and the
            # reference's zoadam/onebit counters do not advance on a
            # skipped torch step. state.step is exactly that count (step +
            # 1 - overflow) and survives checkpoint resume; reading it
            # costs one scalar D2H only when a scaler can actually skip
            scaler = getattr(self.onebit, "loss_scaler", None)
            sched_step = (int(jax.device_get(self.state.step))
                          if scaler is not None and scaler.enabled
                          else self.global_steps)
            new_p, new_s, loss, norm, overflow, new_scale = self.onebit.step(
                self.state.params, self.state.opt_state["onebit"], micros,
                self.next_rng(), lr, sched_step,
                scale_state=self.state.scale)
            # bookkeeping stays on device (no host sync mid-dispatch), the
            # fused path's step + 1 - overflow convention: overflow does not
            # advance the optimizer step
            ovf_i32 = overflow.astype(jnp.int32)
            prev_streak = (self.state.nonfinite_streak
                           if self.state.nonfinite_streak is not None
                           else jnp.asarray(0, jnp.int32))
            new_streak = jnp.where(overflow, prev_streak + 1,
                                   0).astype(jnp.int32)
            self.state = self.state.replace(
                step=self.state.step + 1 - ovf_i32, params=new_p,
                opt_state={"onebit": new_s}, scale=new_scale,
                skipped_steps=self.state.skipped_steps + ovf_i32,
                nonfinite_streak=new_streak)
            metrics = {"loss": loss, "lr": lr, "grad_norm": norm,
                       "overflow": overflow,
                       "loss_scale": new_scale.scale,
                       "nonfinite_streak": new_streak}
        elif self.offload is not None:
            grads_sum, loss, raw_norm, overflow = self._grads_step(
                self._params_device(), self.state.scale, micros,
                self.next_rng(), self.state.step)
            metrics = self._apply_offload_update(grads_sum, float(gas), loss,
                                                 raw_norm, overflow)
        else:
            step_fn, sync_algo = self._active_train_step()
            limit = self._spike_limit_arg()
            if limit is None:
                self.state, metrics = step_fn(
                    self.state, micros, self.next_rng(), self._current_lr())
            else:
                self.state, metrics = step_fn(
                    self.state, micros, self.next_rng(), self._current_lr(),
                    limit)
            if self.comm_plan_ctx is not None:
                # host-side audit tags: which wire format this step's grad
                # sync actually ran (tests + the guard's visibility), and
                # whether the ZeRO-3 param fetch left the implicit path
                metrics["grad_sync_algo"] = sync_algo
                metrics["param_gather_algo"] = \
                    self.comm_plan_ctx.resolved.get("param_all_gather",
                                                    "exact")
        return metrics

    def samples_per_sec(self, start_step: int = 2) -> float:
        """Sustained samples/s over the recorder's ring: global batch x
        steps over the time their ``train.dispatch`` + ``train.sync``
        spans took (enqueue to loss on the host; the batch's way to the
        device and the host work after the step are not in it), steps
        before ``start_step`` left out as warm-up."""
        busy_ns = step_ns = steps = 0
        for name, _, start, end, attrs in list(self.rec.ring):
            if name in ("train.dispatch", "train.sync"):
                step_ns += end - start
            elif name == "train.step":
                if attrs["step_num"] >= start_step:
                    busy_ns += step_ns
                    steps += 1
                step_ns = 0
        if step_ns and self.global_steps > start_step:
            # called from inside a step (the autotuner's hook): its wait
            # is over, its train.step entry not yet written
            busy_ns += step_ns
            steps += 1
        if not busy_ns:
            return 0.0
        return steps * self.config.train_batch_size / (busy_ns / 1e9)

    def _report_spans(self) -> None:
        """Every ``steps_per_print`` steps: the per-step host time of each
        ``train.*`` span since the last report (self time: a span less
        its children), logged under ``wall_clock_breakdown`` and written
        to the monitor where one is enabled."""
        if self.global_steps % self.config.steps_per_print or not (
                self.config.wall_clock_breakdown or self.monitor.enabled):
            return
        times = self.rec.span_times(since_ns=self._spans_reported_ns)
        self._spans_reported_ns = time.monotonic_ns()
        steps = max(times.get("train.step", {}).get("count", 0), 1)
        per_step = {name: row["self_ms"] / steps
                    for name, row in sorted(times.items())
                    if name.startswith("train.")}
        if self.config.wall_clock_breakdown:
            log_dist(" | ".join(f"{name}: {ms:.2f}ms"
                                for name, ms in per_step.items()), ranks=[0])
            log_dist(f"throughput: {self.samples_per_sec():.1f} samples/sec",
                     ranks=[0])
        if self.monitor.enabled:
            self.monitor.write_events(
                [(f"Train/Telemetry/{name}_self_ms", ms, self.global_steps)
                 for name, ms in per_step.items()])

    def telemetry(self) -> Dict[str, Any]:
        """The recorder's snapshot (utils/telemetry.py): counters, gauges
        and per-span count / total / self time over the ring.
        ``engine.rec.dump(path)`` writes the ring itself."""
        return self.rec.snapshot()

    def eval_batch(self, batch):
        batch = self.shard_batch(batch)
        out = self._eval_step(self._params_device(), batch, self.next_rng(),
                              self.state.step)
        if self.watchdog is not None:
            # evaluation progress is liveness too: a long validation pass
            # between optimizer steps must not read as a training stall
            self.watchdog.beat()
        if self.heartbeat is not None:
            self.heartbeat.write(hb.PHASE_STEP, self.global_steps)
        return out

    # --- micro-batch API (reference forward/backward/step contract) ----------

    def train(self, mode: bool = True):
        """Switch the micro-batch API to training mode (reference: the
        engine is an nn.Module — users call engine.train()/engine.eval()).
        In training mode forward() runs the fused value-and-grad program and
        caches the grads for backward() — the XLA analogue of torch autograd
        'building the graph' during a training forward — so a
        forward/backward pair costs exactly one fwd+bwd, the same FLOPs as
        the fused train_batch path (round-3 Weak #4: the recompute made it
        ~1.5x)."""
        self._train_mode = bool(mode)
        return self

    def eval(self):
        """Inference mode: forward() compiles only the forward pass (no
        gradient residuals — the cost model of the reference's eval/no_grad
        forward)."""
        return self.train(False)

    def forward(self, batch):
        """Loss for one microbatch.

        Training mode (default, reference parity: torch modules start in
        train mode): fused value_and_grad — the loss comes back immediately
        and the microbatch's grads are cached for backward(). Eval mode:
        deterministic forward-only program (dropout off), no backward
        compiled in — scoring loops should call engine.eval() first.
        """
        batch = self.shard_batch(batch)
        rng = self.next_rng()
        params_dev = self._params_device()
        train_mode = getattr(self, "_train_mode", True)
        if train_mode and self.onebit is None:
            grads, loss = self._micro_grad(params_dev, self.state.scale,
                                           batch, rng, self.state.step)
        elif train_mode:
            # 1-bit mode: training goes through the runner's train_batch;
            # a bare forward is still the train-mode (stochastic) forward
            grads = None
            loss = self._fwd_loss(params_dev, batch, rng, self.state.step)
        else:
            grads = None
            if self._fwd_loss_eval is None:
                self._fwd_loss_eval = self._make_fwd_loss(train=False)
            loss = self._fwd_loss_eval(params_dev, batch, rng,
                                       self.state.step)
        # transient (offload_param) mode: the grads were computed from this
        # materialization already; dropping params_dev here frees the
        # full-model device copy between forward and backward
        del params_dev
        prev = getattr(self, "_pending", None)
        if grads is not None and prev is not None and prev[3] is not None:
            # a fused-gradient forward whose predecessor's grads were never
            # consumed: scoring loops that never call backward() are paying
            # the fused fwd+bwd program (FLOPs + a full gradient pytree)
            # per call — make the train-mode default diagnosable instead of
            # silent. (The 1-bit branch runs a forward-only program, so
            # it never counts; backward() resets the streak.)
            self._fwd_no_bwd = getattr(self, "_fwd_no_bwd", 0) + 1
            if self._fwd_no_bwd >= 3:
                from ..utils.logging import warning_once
                warning_once(
                    "3+ train-mode forward() calls without backward(): "
                    "each one runs the fused forward+backward program and "
                    "materializes gradients. For scoring/inference call "
                    "engine.eval() first (forward-only program, no "
                    "gradient residuals).")
        self._pending = (batch, rng, loss, grads)
        if self.watchdog is not None:
            # micro-API liveness: scoring loops (eval-mode forward, no
            # step()) must not read as a training stall
            self.watchdog.beat()
        if self.heartbeat is not None:
            self.heartbeat.write(hb.PHASE_STEP, self.global_steps)
        return loss

    __call__ = forward

    def backward(self, loss=None):
        """Accumulate grads for the last forward's microbatch (reference:
        engine.backward scales by 1/gas and fires reduction hooks). The
        grads were already produced by the training forward's fused program
        — this call only accumulates them into the gas window."""
        if self.onebit is not None:
            # inference-style forward() is fine in 1-bit mode; the TRAINING
            # micro API is not — the compressed momentum exchange needs
            # per-rank grads, which only the fused train_batch step produces
            raise NotImplementedError(
                "backward()/step() are not supported with 1-bit optimizers "
                "on a multi-rank mesh — use train_batch()")
        if not hasattr(self, "_pending") or self._pending is None:
            raise RuntimeError("backward() called before forward()")
        batch, rng, loss_val, grads = self._pending
        self._pending = None
        self._fwd_no_bwd = 0          # the pair completed: not a scoring loop
        if grads is None:
            # eval-mode forward has no gradient residuals (that is its cost
            # model); silently differentiating a DIFFERENT computation
            # (train-mode dropout) here would be wrong numerics
            raise RuntimeError(
                "backward() after an eval-mode forward — call "
                "engine.train() before training forwards (grads are "
                "computed by the training forward and cached)")
        if self._accum_grads is None:
            self._accum_grads = grads
        else:
            self._accum_grads = jax.tree.map(jnp.add, self._accum_grads, grads)
        self._accum_losses.append(loss_val)
        self._micro_count += 1
        self.micro_steps += 1
        return loss_val

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._micro_count >= self.config.gradient_accumulation_steps

    def step(self):
        """Apply the optimizer at the gas boundary; no-op otherwise."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self.offload is not None:
            grads = self._accum_grads
            overflow = LossScaler.has_overflow(grads)
            # norm stays on device: float() per leaf was one blocking D2H
            # transfer per param tensor per step (graftlint TPU001); the
            # single sync happens in _apply_offload_update's device_get
            sq = sum(jnp.sum(jnp.square(g))
                     for g in jax.tree.leaves(grads))
            metrics = self._apply_offload_update(
                grads, float(self._micro_count),
                jnp.mean(jnp.stack(self._accum_losses)),
                jnp.sqrt(sq), overflow)
        else:
            n = jnp.asarray(float(self._micro_count), jnp.float32)
            limit = self._spike_limit_arg()
            if limit is None:
                self.state, metrics = self._apply_update(
                    self.state, self._accum_grads, n, self._current_lr())
            else:
                self.state, metrics = self._apply_update(
                    self.state, self._accum_grads, n, self._current_lr(),
                    limit)
            metrics["loss"] = jnp.mean(jnp.stack(self._accum_losses))
        # one shared tail: _after_step (and the SDC audit's collective
        # inside it) runs on every arm — a per-arm tail would put a
        # conditional return between paired collectives (TPU013)
        self._accum_grads = None
        self._accum_losses = []
        self._micro_count = 0
        self._after_step(metrics)
        self.data_position += 1
        return metrics

    def _after_step(self, metrics):  # graftlint: hotpath
        self.global_steps += 1
        self._step_phase_reached = True
        with self.rec.span("train.after_step.heartbeat"):
            self._beat_step()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._last_metrics = metrics
        print_step = self.global_steps % self.config.steps_per_print == 0
        if print_step or self.sentinel.wants_every_step \
                or self._cp_guard is not None:
            # one batched D2H pull for every scalar the logging tier AND
            # the integrity sentinel read (graftlint TPU001: per-scalar
            # float() here was 3-4 separate blocking transfers per print
            # step). The skip streak and the sentinel statistics ride the
            # SAME pull — enabling the detector costs per-step cadence on
            # this one transfer, never an extra sync. The comm-plan
            # accuracy guard reads grad_norm off the same pull (its
            # documented cost: per-step cadence when enabled).
            keys = set(self.sentinel.metric_keys)
            if self._cp_guard is not None:
                keys.add("grad_norm")
            with self.rec.span("train.after_step.pull"):
                host = jax.device_get({k: metrics[k]
                                       for k in keys if k in metrics})
            if self._cp_guard is not None and "grad_norm" in host:
                self._cp_guard.observe(float(host["grad_norm"]))
            # one code path for every "wrong numbers" verdict: the folded
            # nonfinite_guard streak abort (NonFiniteError), anomaly
            # strikes, and the post-rollback abort all live in observe()
            verdict = self.sentinel.observe(self.global_steps, host)
            if print_step:
                with self.rec.span("train.after_step.monitor"):
                    self._print_step(host)
            if verdict == sentinel_lib.ROLLBACK:
                self._sentinel_rollback()
        self._maybe_sdc_audit()
        self._autotuning_hook()

    def _beat_step(self) -> None:
        if self.watchdog is not None:
            # step progress IS the liveness signal (dispatch completed; a
            # wedged collective never reaches this line). start() is
            # idempotent — the first completed step arms the clock, and
            # entering STEP retires the COMPILE deadline.
            self.watchdog.start().enter_phase(hb.PHASE_STEP,
                                              step=self.global_steps)
        if self.heartbeat is not None:
            # throttled: same-phase records within min_interval are dropped.
            # The rolling step_ms gauge rides along (None before the first
            # completed step gap — `dstpu health` shows '-' until then)
            gauge = self._step_clock.mark()
            self.heartbeat.write(
                hb.PHASE_STEP, self.global_steps,
                extra=({straggler_lib.STEP_MS_GAUGE: gauge}
                       if gauge is not None else None))
            self._maybe_check_straggler()

    def _print_step(self, host) -> None:
        if self.monitor.enabled:
            events = [("Train/Samples/train_loss",
                       float(host["loss"]), self.global_steps),
                      ("Train/Samples/lr", float(host["lr"]),
                       self.global_steps)]
            if self.loss_scaler.enabled:
                events.append(("Train/Samples/loss_scale",
                               float(host["loss_scale"]),
                               self.global_steps))
            self.monitor.write_events(events)
        log_dist(f"step={self.global_steps} "
                 f"loss={float(host['loss']):.4f} "
                 f"lr={float(host['lr']):.3e} "
                 f"grad_norm={float(host['grad_norm']):.3f}",
                 ranks=[0])

    def _autotuning_hook(self):
        """Script-mode autotuning (reference: engine autotuning exit after
        end_profile_step): when the autotuner launched this run, write the
        measured throughput and stop."""
        import os
        at = self.config.autotuning
        metric_file = os.environ.get("DS_AUTOTUNING_METRIC_FILE")
        if not (at.enabled and metric_file):
            return
        if self.global_steps < at.end_profile_step:
            return
        import json
        import sys
        tput = self.samples_per_sec()
        metrics = {"throughput": float(tput) if tput else 0.0,
                   "train_batch_size": self.config.train_batch_size,
                   "steps": self.global_steps}
        with open(metric_file, "w") as f:
            json.dump(metrics, f)
        log_dist(f"autotuning: wrote {metric_file}, exiting", ranks=[0])
        sys.exit(0)

    # --------------------------------------------- training-integrity sentinel

    def _spike_limit_arg(self):
        """The sentinel's grad-norm ceiling as a device scalar for the
        compiled step, or None when rung 1 is off. Always a float (+inf
        during warmup) once the rung is on, so the compiled program's arg
        structure — and its cache entry — never changes mid-run."""
        thr = self.sentinel.spike_limit()
        if thr is None:
            return None
        return jnp.asarray(thr, jnp.float32)

    def _maybe_check_straggler(self):
        """Worker-side straggler ladder (runtime/straggler.py), run at
        ``straggler.check_interval`` cadence off the step path: read the
        shared heartbeat channel, run the cross-rank detector, and act on
        verdicts against THIS rank — rung 1 stamps the sticky STRAGGLER
        flag (blacklist evidence, health-visible), rung 3 exits rc 117
        so the degraded world relaunches without this host. Every rank
        sees the same snapshot, so self-verdicts need no coordination."""
        det = self.straggler
        if det is None:
            return
        now = time.monotonic()
        if now < self._straggler_next_check:
            return
        self._straggler_next_check = now + \
            self.config.straggler.check_interval
        records = hb.read_heartbeats(self.heartbeat.directory)
        mine = det.observe(records).get(self.heartbeat.rank)
        if mine is None:
            return
        if not self._straggler_flagged:
            self._straggler_flagged = True
            logger.error(
                "straggler: this rank's step time is %s MADs above the "
                "world median for %d consecutive windows — stamping the "
                "STRAGGLER heartbeat flag (host %s)",
                self.config.straggler.zmax,
                self.config.straggler.strike_window, self.heartbeat.host)
            self.heartbeat.add_flag(straggler_lib.STRAGGLER_FLAG,
                                    lock_timeout=5.0)
        if mine == straggler_lib.ABORT:
            # the rc-117 path: the terminal STALLED record lets
            # scheduler-flattening backends reconstruct the rc, and the
            # voluntary 117 exit + the flag are the agent's strike
            self.heartbeat.stamp_terminal(hb.PHASE_STALLED,
                                          lock_timeout=5.0)
            raise straggler_lib.StragglerAbort(
                f"rank {self.heartbeat.rank} ({self.heartbeat.host}) "
                f"persistently slow past straggler.abort_after="
                f"{self.config.straggler.abort_after} windows — exiting "
                f"rc {straggler_lib.STALL_EXIT_CODE} so the elastic agent "
                "relaunches the world without this host")

    def _sentinel_rollback(self):
        """Remediation rung 2: restore the newest intact checkpoint via
        the PR-3 verified loader; the data pipeline is NOT rewound — its
        position survives the restore, so the poisoned span is
        deterministically fast-forwarded past rather than replayed."""
        load_dir = self.config.integrity.load_dir or self._ckpt_dir
        if not load_dir:
            raise TrainingIntegrityError(
                "sentinel rollback requested (strikes: "
                f"{self.sentinel.last_anomaly}) but no checkpoint directory "
                "is known — set integrity.load_dir or save a checkpoint "
                "before enabling the rollback rung")
        from_step = self.global_steps
        position = self.data_position
        logger.error(
            "integrity sentinel: rolling back from step %d (%s) to the "
            "newest intact checkpoint under %s", from_step,
            self.sentinel.last_anomaly, load_dir)
        try:
            # an explicit resolve (newest intact) rather than tag=None: the
            # post-SDC audited-clean preference must not apply to an
            # in-run anomaly rollback, where latest-intact is the target
            tag = ckpt_lib.resolve_load_tag(
                load_dir, check_digests=self.config.checkpoint.verify_load)
            self.load_checkpoint(load_dir, tag=tag)
        except (FileNotFoundError, OSError,
                ckpt_lib.CheckpointIntegrityError) as e:
            raise TrainingIntegrityError(
                f"sentinel rollback from step {from_step} failed: no intact "
                f"checkpoint under {load_dir} ({e}); aborting with rc "
                f"{sentinel_lib.INTEGRITY_EXIT_CODE}") from e
        self.data_position = position
        self.sentinel.note_rollback(self.global_steps)
        log_dist(
            f"integrity sentinel: rolled back to step {self.global_steps} "
            f"(tag {tag}); data pipeline continues at batch {position} — "
            "the poisoned span is skipped, not replayed", ranks=[0])

    def fast_forward_dataloader(self, loader, batches_per_step: int = 1):
        """Deterministically position ``loader`` past the data this
        engine's (restored) state already consumed: ``data_position``
        global batches, checkpointed in client state. The resume path
        after a rollback-abort or an SDC relaunch — re-feeding the
        poisoned span would re-trigger the very anomaly the restart is
        recovering from. ``batches_per_step`` scales for loaders yielding
        microbatches. Returns the number of batches skipped."""
        ff = getattr(loader, "fast_forward", None)
        if ff is None:
            raise TypeError(
                f"{type(loader).__name__} has no fast_forward(n); wrap it "
                "in deepspeed_tpu.runtime.dataloader.RepeatingLoader or use "
                "DeepSpeedDataLoader")
        n = self.data_position * int(batches_per_step)
        ff(n)
        return n

    # -- cross-replica SDC audit ---------------------------------------------

    def _maybe_sdc_audit(self):
        iv = self.config.integrity.audit_interval
        if iv <= 0 or self.global_steps % iv != 0:
            return
        self._run_sdc_audit()

    def _audit_state_leaves(self):
        """(path, leaf) for every FULLY-REPLICATED leaf of params + master
        + optimizer state. Only replicated leaves are auditable: each
        device holds its own complete copy, so a checksum program with no
        collectives yields per-device values that MUST agree — a sharded
        leaf's per-device bytes differ legitimately, and a global
        reduction would mix a corrupted replica's bytes into every
        device's answer, hiding the minority."""
        tree = {"params": self.state.params, "master": self.state.master,
                "opt_state": self.state.opt_state}
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            sharding = getattr(leaf, "sharding", None)
            if sharding is None or not getattr(
                    sharding, "is_fully_replicated", False):
                continue
            if getattr(leaf, "dtype", None) is None or \
                    leaf.dtype.itemsize not in (1, 2, 4) or leaf.ndim == 0:
                # scalars (step counters, scale) churn every step and are
                # cheap to recompute; the audit exists for the big state
                continue
            out.append((ckpt_lib.path_str(path), leaf))
        return out

    def _make_audit_fn(self):
        """Bit-exact checksum program over the auditable leaves: bitcast
        to unsigned words, position-weight (so two swapped elements can't
        cancel), wraparound-sum to one uint32. No collectives — each
        device audits its own replica's bytes."""
        def checksum(leaves):
            total = jnp.zeros((), jnp.uint32)
            words = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
            for x in leaves:
                if x.dtype == jnp.bool_:
                    x = x.astype(jnp.uint8)
                u = lax.bitcast_convert_type(x, words[x.dtype.itemsize])
                u = u.astype(jnp.uint32).reshape(-1)
                # idx+1: every position gets a DISTINCT nonzero weight —
                # an |1-style weight would give neighbors 2k/2k+1 the same
                # one, letting a swapped or compensating pair cancel
                idx = jnp.arange(u.size, dtype=jnp.uint32)
                total = total + jnp.sum(u * (idx + jnp.uint32(1)))
            return total

        return jax.jit(checksum)

    def _run_sdc_audit(self):
        """One cross-replica audit: per-device checksums, a host-side
        majority vote (cross-process via one small allgather), SDC flag +
        abort on a minority replica. The audit's device_get happens every
        ``audit_interval`` steps, never on the step hot path."""
        # chaos: silent per-process bit corruption, keyed by process index
        # ("sentinel.sdc:flag:match=1" flips a bit on rank 1 only)
        if chaos.flag("sentinel.sdc",
                      key=str(jax.process_index())) is not None:
            self._inject_sdc_bitflip()
        named = self._audit_state_leaves()
        if not named:
            from ..utils.logging import warning_once
            warning_once(
                "integrity.audit_interval is set but no state leaf is "
                "fully replicated (ZeRO-3 shards everything): the "
                "cross-replica SDC audit has nothing to compare")
            return
        if self._audit_fn is None:
            self._audit_fn = self._make_audit_fn()
        out = self._audit_fn(tuple(leaf for _, leaf in named))
        local = np.asarray(
            [[jax.process_index(), sh.device.id, int(sh.data)]
             for sh in out.addressable_shards], np.uint32)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            world = np.asarray(multihost_utils.process_allgather(local))
            rows = world.reshape(-1, 3)
        else:
            rows = local
        pairs = [(f"proc{int(p)}/dev{int(d)}", int(v)) for p, d, v in rows]
        bad = sentinel_lib.compare_replica_checksums(pairs)
        if not bad:
            self.sentinel.note_clean_audit(self.global_steps)
            if self._ckpt_dir:
                tag = ckpt_lib.get_latest_tag(self._ckpt_dir)
                if tag:
                    # the newest tag existed under a clean audit: the safe
                    # resume point for a post-SDC relaunch
                    sentinel_lib.write_last_audited_clean(self._ckpt_dir,
                                                          tag)
            return
        mine = f"proc{jax.process_index()}/"
        logger.error(
            "integrity audit: cross-replica checksum MISMATCH at step %d — "
            "implicated replicas: %s (checksums: %s)", self.global_steps,
            bad, pairs)
        if self.heartbeat is not None and any(k.startswith(mine)
                                              for k in bad):
            # blacklist evidence: the elastic agent strikes this host via
            # the PR-6 quarantine path; bounded lock — the abort below
            # must not wait on a wedged refresher
            self.heartbeat.add_flag(sentinel_lib.SDC_FLAG,
                                    step=self.global_steps,
                                    lock_timeout=2.0)
        raise TrainingIntegrityError(
            f"cross-replica SDC detected at step {self.global_steps}: "
            f"replica checksums diverged (implicated: {bad}). The live "
            "state is not trustworthy; relaunch resumes from the last "
            "audited-clean checkpoint")

    def _inject_sdc_bitflip(self):
        """Chaos-only: flip one bit in the LAST local device's copy of the
        first auditable leaf — the userspace approximation of a chip
        silently corrupting memory (every other replica keeps the true
        bytes, which is exactly what the majority vote needs)."""
        named = self._audit_state_leaves()
        if not named:
            return
        path, leaf = next(((p, l) for p, l in named
                           if p.startswith("params/")), named[0])
        shards = list(leaf.addressable_shards)
        bufs = [np.array(np.asarray(s.data)) for s in shards]
        flat = bufs[-1].view(np.uint8).reshape(-1)
        flat[0] ^= 1
        arrs = [jax.device_put(b, s.device) for b, s in zip(bufs, shards)]
        flipped = jax.make_array_from_single_device_arrays(
            leaf.shape, leaf.sharding, arrs)
        tree = {"params": self.state.params, "master": self.state.master,
                "opt_state": self.state.opt_state}
        flat_tree, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = [flipped if ckpt_lib.path_str(p) == path else l
                  for p, l in flat_tree]
        new_tree = jax.tree_util.tree_unflatten(treedef, leaves)
        self.state = self.state.replace(params=new_tree["params"],
                                        master=new_tree["master"],
                                        opt_state=new_tree["opt_state"])
        logger.warning("chaos sentinel.sdc: flipped one bit of %s on "
                       "device %s", path, shards[-1].device)

    # ------------------------------------------------------------- accessors

    def profile_trace(self, log_dir: str, batches, warmup: int = 1):
        """Capture a jax profiler trace (xplane, TensorBoard-loadable) over
        the given train batches — the TPU face of the reference's tracing
        aux (SURVEY §5: torch profiler ranges -> jax.profiler.trace).

        ``batches``: iterable of global batches; the first ``warmup`` steps
        run OUTSIDE the trace so compile time doesn't drown the timeline.
        Returns log_dir."""
        batches = list(batches)
        if len(batches) <= warmup:
            raise ValueError(
                f"profile_trace needs more than warmup={warmup} batches "
                f"(got {len(batches)}) — the traced region would be empty")
        for batch in batches[:warmup]:
            self.train_batch(batch)
        with jax.profiler.trace(log_dir):
            for batch in batches[warmup:]:
                m = self.train_batch(batch)
            jax.block_until_ready(m["loss"])
        log_dist(f"profiler trace written to {log_dir}", ranks=[0])
        return log_dir

    def compute_eigenvalue(self, batch):
        """Max Hessian eigenvalue of the loss on ``batch`` (reference:
        engine eigenvalue hook at gas boundaries, feeding MoQ)."""
        if self.eigenvalue is None:
            raise RuntimeError("enable the 'eigenvalue' config section")
        batch = self.shard_batch(batch)
        return self.eigenvalue.compute_eigenvalue(
            self._ensure_eig_loss(), self._params_device(), self.next_rng(),
            loss_args=(batch, self.next_rng()))

    def _ensure_eig_loss(self):
        """STABLE loss closure (batch/rng flow through loss_args) so the
        eigenvalue's jitted HVP step caches across calls."""
        if not hasattr(self, "_eig_loss"):
            def _eig_loss(p, batch, rng):
                out = self.apply_fn(p, batch, rng, True)
                return self.loss_fn(out, batch)
            self._eig_loss = _eig_loss
        return self._eig_loss

    def moq_rescale(self, batch):
        """Curvature-paced MoQ (reference: quantize.py eigenvalue gating):
        measure the Hessian eigenvalue on ``batch`` and stretch the MoQ bit
        schedule's period proportionally. Recompiles the train step with the
        updated spec."""
        if not getattr(self, "_moq_enabled", False) or self.eigenvalue is None:
            raise RuntimeError("moq_rescale needs both quantize_training and "
                               "eigenvalue enabled")
        if not hasattr(self, "_moq_scheduler"):
            from .quantize import MoQScheduler
            self._moq_scheduler = MoQScheduler(self.compression_spec,
                                               self.eigenvalue)
        sharded = self.shard_batch(batch)
        new_spec = self._moq_scheduler.maybe_rescale(
            self._ensure_eig_loss(), self._params_device(), self.next_rng(),
            loss_args=(sharded, self.next_rng()))
        if new_spec is not self.compression_spec:
            self.compression_spec = new_spec
            if self._train_step is not None:
                self._train_step = self._make_train_step()
        return self.compression_spec

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_lr()
        return [self.base_lr]

    def get_global_grad_norm(self) -> float:
        m = self._last_metrics.get("grad_norm")
        return float(m) if m is not None else 0.0

    def get_loss_scale(self) -> float:
        return float(jax.device_get(self.state.scale.scale))

    @property
    def skipped_steps(self) -> int:
        """Reference-parity overflow-skip counter; the truth lives on device
        in TrainState (no per-step host sync)."""
        return int(jax.device_get(self.state.skipped_steps))

    @property
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.config.zero_optimization.stage

    def set_train_batch_size(self, train_batch_size: int):
        """reference: engine.set_train_batch_size (engine.py:440) — adjusts gas."""
        if train_batch_size % (self.config.train_micro_batch_size_per_gpu *
                               self.dp_world_size) != 0:
            raise ValueError(f"train_batch_size {train_batch_size} incompatible")
        self.config.gradient_accumulation_steps = train_batch_size // (
            self.config.train_micro_batch_size_per_gpu * self.dp_world_size)
        self.config.train_batch_size = train_batch_size
        if self.offload is not None:
            self._grads_step = self._make_grads_step()
        else:
            self._train_step = self._make_train_step()

    def module_state_dict(self) -> Dict[str, np.ndarray]:
        return ckpt_lib._tree_to_flat_dict(self._params_device())

    def load_module_state_dict(self, state_dict: Dict[str, np.ndarray],
                               strict: bool = True):
        """Load weights only (reference: engine.load_module_state_dict,
        engine.py:2582) — the inverse of ``module_state_dict``. Leaves are
        re-placed with the engine's param shardings, and EVERY weight
        representation follows: the fp32 master (else the next step would
        recompute params from the stale master, silently discarding the
        load) and the offloaded host master. Optimizer state, loss scale,
        and counters are untouched (use load_checkpoint for full resume).
        ``strict=False`` keeps current values for missing keys and ignores
        unexpected ones."""
        from jax.tree_util import tree_flatten_with_path
        if self.offload is not None:
            # reference the host masters LAZILY (thunk leaves): no device
            # materialization (transient mode exists because the model
            # doesn't fit), no eager copy of the optimizer slots — only
            # the leaves MISSING from the state_dict are ever read
            ref_tree = self.offload.state_dict(lazy=True)["master"]
        else:
            ref_tree = self.state.params
        keys = [ckpt_lib.path_str(p)
                for p, _ in tree_flatten_with_path(ref_tree)[0]]
        if strict:
            missing = sorted(set(keys) - set(state_dict))
            unexpected = sorted(set(state_dict) - set(keys))
            if missing or unexpected:
                raise KeyError(
                    f"state_dict mismatch: missing={missing[:5]} "
                    f"unexpected={unexpected[:5]} (strict=True)")

        if self.offload is not None:
            # fp32 masters take loaded values ONLY for keys present in the
            # state_dict (merging absent keys from the bf16 device params
            # would round them — the lossy-master failure this method
            # exists to prevent); absent leaves are never even read —
            # a partial load costs I/O proportional to what it loads
            updates = {j: state_dict[k]
                       for j, k in enumerate(keys) if k in state_dict}
            if updates:
                self.offload.update_master_leaves(updates)
            if self._transient_params:
                return                      # nothing device-resident to touch

        def place_present(tree):
            # present keys re-place onto the leaf's sharding; ABSENT keys
            # keep the live device leaf — no host gather, no re-upload
            clp, ctd = tree_flatten_with_path(tree)
            return jax.tree.unflatten(ctd, [
                jax.device_put(jnp.asarray(state_dict[k], dtype=leaf.dtype),
                               leaf.sharding)
                if (k := ckpt_lib.path_str(p)) in state_dict else leaf
                for p, leaf in clp])

        params = place_present(self.state.params)
        master = self.state.master
        if self.keep_master and master != ():
            master = place_present(master)
        self.state = self.state.replace(params=params, master=master)

    # ----------------------------------------------------------- checkpointing

    def _ckpt_view(self, lazy: bool = False):
        """State as checkpointed: fp32 mode aliases params into the master slot;
        offload mode surfaces the host-resident master/opt-state pytrees.

        ``lazy=True`` (sync saves only): offload leaves become thunks so the
        streaming writer never holds more than one leaf — with an async
        engine the copies must be eager or the writer thread would race the
        next step's in-place master updates."""
        if self.offload is not None:
            sd = self.offload.state_dict(lazy=lazy)
            params = (self.offload.host_params(lazy=lazy)
                      if self._transient_params else self.state.params)
            return self.state.replace(params=params,
                                      master=sd["master"],
                                      opt_state={"offload": sd["state"]})
        return self.state if self.keep_master else self.state.replace(
            master=self.state.params)

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None):
        if not hasattr(self, "checkpoint_engine"):
            from ..checkpoint.engine import build_checkpoint_engine
            self.checkpoint_engine = build_checkpoint_engine(self.config)
        return self._save_checkpoint_with(self.checkpoint_engine, save_dir,
                                          tag, client_state)

    def _save_checkpoint_with(self, ckpt_engine, save_dir: str,
                              tag: Optional[str],
                              client_state: Optional[dict] = None):
        """Shared body of the periodic save and the preemption-time
        emergency save (which forces a synchronous engine). Runs in the
        SAVE phase: save time is IO-bound and legitimately unbounded by
        step time (save_timeout=0, the default, keeps it unbounded; a
        positive save_timeout bounds a save wedged on dead storage)."""
        with self._phase_scope(hb.PHASE_SAVE):
            self._ckpt_dir = save_dir      # the sentinel's rollback source
            tag = tag or f"global_step{self.global_steps}"
            client_state = dict(client_state or {})
            client_state["global_steps"] = self.global_steps
            client_state["data_position"] = self.data_position
            if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "state_dict"):
                client_state["lr_scheduler"] = self.lr_scheduler.state_dict()
            lazy = getattr(ckpt_engine, "wants_lazy", True)
            ckpt = self.config.checkpoint
            return ckpt_lib.save_checkpoint(
                save_dir, tag, self._ckpt_view(lazy=lazy), client_state,
                master_aliases_params=(not self.keep_master
                                       and self.offload is None),
                ckpt_engine=ckpt_engine,
                keep_last=ckpt.keep_last,
                keep_every=ckpt.keep_every)

    def wait_for_checkpoints(self):
        """Durability barrier for async checkpointing (reference: Nebula
        commit semantics); no-op with the sync engine. Returns a truthy
        CommitResult on success; on failure it names the failed paths."""
        if hasattr(self, "checkpoint_engine"):
            return self.checkpoint_engine.commit("all")
        return True

    def close(self):
        """Explicit resource shutdown: drain + stop the async checkpoint
        writer (previously only ``__del__`` did, losing pending writes at
        interpreter teardown) and stop the stall watchdog."""
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.heartbeat is not None:
            # terminal record: launcher-side monitors must read a closed
            # engine as "concluded", not "went silent". Bounded lock: a
            # refresher wedged on dead storage must not hang the clean
            # shutdown it is merely annotating
            self.heartbeat.write(hb.PHASE_EXIT, self.global_steps,
                                 force=True, lock_timeout=2.0)
        if hasattr(self, "checkpoint_engine"):
            return self.checkpoint_engine.close()
        return True

    def _emergency_save(self, save_dir: str,
                        client_state: Optional[dict] = None) -> str:
        """Preemption-time save: drain any pending async writes (their tag
        must not interleave with ours on the FIFO worker), then write
        synchronously — the grace window is no place for a fire-and-forget
        thread.

        Overlap contract (round-4): if the drain itself just published an
        intact checkpoint of THIS step — an async save was in flight when
        the signal landed — the emergency save must NOT rewrite the same
        tag. The rewrite would burn grace-window seconds re-serializing
        the whole model, and dying mid-rewrite would leave `latest` on a
        tag whose staging debris shadows the drained publish."""
        from ..checkpoint.engine import NpzCheckpointEngine
        drained_ok = True
        if hasattr(self, "checkpoint_engine"):
            try:
                drained_ok = bool(self.checkpoint_engine.commit(
                    "preempt-drain"))
            except Exception as e:       # a failed past save must not
                drained_ok = False
                logger.error("preempt: drain of pending checkpoint "
                             "writes failed: %s", e)   # block THIS save
        tag = f"global_step{self.global_steps}"
        if drained_ok and ckpt_lib.get_latest_tag(save_dir) == tag:
            path = os.path.join(save_dir, tag)
            if ckpt_lib.verify_tag(path) is None:
                log_dist(f"preempt: drained in-flight save already "
                         f"published intact {tag}; skipping the duplicate "
                         "emergency write", ranks=[0])
                return path
        client_state = dict(client_state or {})
        client_state["preempted"] = True
        return self._save_checkpoint_with(NpzCheckpointEngine(), save_dir,
                                          None, client_state)

    def install_preemption_handler(self, save_dir: str,
                                   grace_secs: float = 30.0,
                                   client_state: Optional[dict] = None,
                                   exit_fn=None):
        """SIGTERM/SIGINT -> emergency synchronous checkpoint -> exit with
        ``PREEMPTION_EXIT_CODE`` (the rc ``DSElasticAgent`` treats as
        "resume, don't count against max_restarts").

        ``grace_secs`` is a hard deadline: if the save outruns it (TPU
        preemption notices give finite warning), a watchdog still exits
        with the preemption rc — the previous intact checkpoint carries
        the restart, which the rollback-verified loader guarantees exists.
        A second signal during the save also exits immediately.
        Returns the installed handler (tests invoke it directly)."""
        import signal
        import threading
        from ..elasticity.elastic_agent import PREEMPTION_EXIT_CODE
        exit_fn = exit_fn or os._exit
        state = {"fired": False}

        def _handler(signum=None, frame=None):
            if state["fired"]:
                exit_fn(PREEMPTION_EXIT_CODE)
                return
            state["fired"] = True
            if self.watchdog is not None:
                # the grace window is save time, not step time — the stall
                # watchdog must not shoot us mid-emergency-save (never
                # resumed: this process only leaves via exit_fn)
                self.watchdog.suspend()
            # the grace timer arms BEFORE any other work: everything past
            # this point (the heartbeat stamp, the save itself) can block
            # on dead storage, and only the timer guarantees the rc-114
            # exit still happens
            watchdog = threading.Timer(
                max(grace_secs, 0.1),
                lambda: exit_fn(PREEMPTION_EXIT_CODE))
            watchdog.daemon = True
            watchdog.start()
            if self.heartbeat is not None:
                # terminal evidence: scheduler backends flatten rc 114, so
                # the PREEMPTED record is how BackendSupervisor restores it.
                # Bounded lock: the signal may have landed INSIDE a
                # step-path heartbeat.write on this same thread — a
                # blocking re-acquire of that non-reentrant lock would
                # deadlock the handler
                self.heartbeat.write(hb.PHASE_PREEMPTED, self.global_steps,
                                     force=True, lock_timeout=2.0)
            log_dist(f"preemption (signal {signum}): emergency checkpoint "
                     f"to {save_dir} within {grace_secs}s", ranks=[0])
            try:
                self._emergency_save(save_dir, client_state)
            except Exception as e:
                logger.error("emergency save failed: %s — exiting with the "
                             "resume rc anyway (previous checkpoint stands)",
                             e)
            finally:
                watchdog.cancel()
                exit_fn(PREEMPTION_EXIT_CODE)

        signal.signal(signal.SIGTERM, _handler)
        signal.signal(signal.SIGINT, _handler)
        return _handler

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_module_only: bool = False):
        # RESTORE phase: a restore wedged on dead storage or a hung
        # sharded materialization is bounded by watchdog.restore_timeout
        # (and visible as RESTORE in the heartbeat channel) instead of
        # hanging the rank silently before its first step
        with self._phase_scope(hb.PHASE_RESTORE):
            return self._load_checkpoint_impl(load_dir, tag,
                                              load_module_only)

    def _load_checkpoint_impl(self, load_dir: str, tag: Optional[str],
                              load_module_only: bool):
        self._ckpt_dir = load_dir          # the sentinel's rollback source
        tag = self._prefer_audited_clean(load_dir, tag)
        if self.offload is not None:
            return self._load_checkpoint_offload(load_dir, tag, load_module_only)
        loaded, client_state = ckpt_lib.load_checkpoint(
            load_dir, tag, self._ckpt_view(),
            param_shardings=self.param_shardings,
            master_shardings=(self.master_shardings if self.keep_master
                              else self.param_shardings),
            opt_shardings=self.opt_shardings,
            verify=self.config.checkpoint.verify_load)
        if self.keep_master:
            self.state = loaded
        else:
            self.state = loaded.replace(params=loaded.master, master=())
        if not load_module_only:
            self.global_steps = client_state.get("global_steps", 0)
            # data-pipeline position: 1 global batch per step unless the
            # checkpoint recorded better (fast_forward_dataloader consumes)
            self.data_position = client_state.get("data_position",
                                                  self.global_steps)
            if self.lr_scheduler is not None and "lr_scheduler" in client_state:
                self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        return load_dir, client_state

    def _prefer_audited_clean(self, load_dir: str,
                              tag: Optional[str]) -> Optional[str]:
        """With the SDC audit on, a ``tag=None`` resume prefers the
        ``last_audited_clean`` marker over ``latest``: tags written AFTER
        the last clean cross-replica audit may carry the corruption the
        audit later caught. An explicit tag (user intent, or the
        sentinel's own rollback resolve) is never overridden, and a
        marker naming a missing/corrupt tag falls back to the normal
        newest-intact resolution."""
        if tag is not None or self.config.integrity.audit_interval <= 0:
            return tag
        clean = sentinel_lib.read_last_audited_clean(load_dir)
        if not clean:
            return None
        reason = ckpt_lib.verify_tag(
            os.path.join(load_dir, clean),
            check_digests=self.config.checkpoint.verify_load)
        if reason is not None:
            logger.warning(
                "integrity: last_audited_clean names %r but it fails "
                "verification (%s); resuming from newest intact instead",
                clean, reason)
            return None
        log_dist(f"integrity: resuming from last audited-clean checkpoint "
                 f"'{clean}'", ranks=[0])
        return clean

    def _load_checkpoint_offload(self, load_dir, tag, load_module_only):
        """Offload mode: optimizer state stays host-side numpy — no device
        shardings are applied to masters/moments."""
        import os
        verify = self.config.checkpoint.verify_load
        if tag is None:
            tag = ckpt_lib.resolve_load_tag(load_dir, check_digests=verify)
        elif verify:
            reason = ckpt_lib.verify_tag(os.path.join(load_dir, tag))
            if reason is not None:
                raise ckpt_lib.CheckpointIntegrityError(
                    f"checkpoint {os.path.join(load_dir, tag)} failed "
                    f"verification: {reason}")
        ckpt_dir = os.path.join(load_dir, tag)
        import json
        with open(os.path.join(ckpt_dir, "meta.json")) as f:
            meta = json.load(f)
        sd_like = self.offload.state_dict()
        flat = ckpt_lib.read_flat_npz(
            os.path.join(ckpt_dir, "optim_states.npz"))
        optim = ckpt_lib._flat_dict_to_tree(
            flat, {"master": sd_like["master"],
                   "opt_state": {"offload": sd_like["state"]}})
        self.offload.load_state_dict({"master": optim["master"],
                                      "state": optim["opt_state"]["offload"]})
        from .loss_scaler import LossScaleState
        self._host_nonfinite_streak = int(meta.get("nonfinite_streak", 0))
        self.state = self.state.replace(
            step=jnp.asarray(meta["step"], jnp.int32),
            skipped_steps=jnp.asarray(meta["skipped_steps"], jnp.int32),
            nonfinite_streak=jnp.asarray(self._host_nonfinite_streak,
                                         jnp.int32),
            params=(() if self._transient_params
                    else self.offload.current_params_device()),
            scale=LossScaleState(
                scale=jnp.asarray(meta["loss_scale"], jnp.float32),
                good_steps=jnp.asarray(meta["scale_good_steps"], jnp.int32),
                hysteresis=jnp.asarray(meta["scale_hysteresis"], jnp.int32)))
        client_state = meta.get("client_state", {})
        if not load_module_only:
            self.global_steps = client_state.get("global_steps", 0)
            self.data_position = client_state.get("data_position",
                                                  self.global_steps)
            if self.lr_scheduler is not None and "lr_scheduler" in client_state:
                self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        return load_dir, client_state

    def save_16bit_model(self, save_dir: str, save_filename: str = "pytorch_model.npz"):
        import os
        os.makedirs(save_dir, exist_ok=True)
        state = self.state
        if self._transient_params:
            state = state.replace(params=self.offload.host_params())
        ckpt_lib.save_16bit_model(state, os.path.join(save_dir, save_filename))
