"""Explicit-collective 1-bit optimizers — OneBitAdam / OneBitLamb with real
wire-byte savings.

Role of the reference's ``runtime/fp16/onebit/adam.py`` + ``onebit/lamb.py``
over the compressed comm backends (``runtime/comm/nccl.py:52-204``): after a
warmup stage of exact Adam/LAMB, the variance term freezes and the per-step
exchange becomes the COMPRESSED momentum (packed sign bits + scales through
runtime/comm/compressed.compressed_allreduce) instead of a full-precision
gradient allreduce — ~32x fewer bytes on the wire.

The SPMD engine's default grad sync lets XLA insert psums, which cannot be
compressed. This runner therefore owns the whole train step: local (per-DP-
rank) grads come out of a shard_map unsummed, the momentum update runs on the
stacked per-rank grads, and the only cross-rank traffic in the compression
stage is the 1-bit exchange. Warmup/compression are two separately-jitted
programs switched host-side at freeze_step (a static branch — no dead
collectives in either HLO, which also makes the wire-byte accounting in
tests/test_onebit.py auditable from the compiled module).

Composition envelope: pure DP mesh.  fp16 loss scaling composes (the
reference default — onebit/adam.py:11 runs under FP16_Optimizer): the scale
rides into the local grad stage, overflow is detected on the global norm and
the whole update (including the compressed exchange) is skipped under
``lax.cond`` while the scale state adjusts.  ZeRO stage 1 composes: the
optimizer state (m/v and friends) is sharded leaf-dim-0 across the DP axis —
XLA turns the momentum update into reduce-scatter + sharded math + param
all-gather, the standard ZeRO-1 wire pattern.  ZeRO>=2 stays out: sharding
GRADS would defeat the stacked-per-rank layout the compressed exchange needs.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .comm.compressed import chunk_elems, compressed_allreduce

PyTree = Any


def stacked_local_grads(runner, params, micros, rng, scale):
    """shard_map over the DP axis: grads stacked [n, ...] (dim0 sharded),
    NO cross-rank reduction — the whole point of the explicit-collective
    optimizers. Shared by the 1-bit (OneBitRunner) and 0/1
    (ZeroOneRunner) gradient stages.

    ``scale`` is the fp16 loss scale (1.0 when scaling is off): the loss
    is scaled inside the backward and the stacked grads come out UNSCALED
    (divided back out with the gas normalization), so inf/nan from a
    genuine fp16 overflow still propagates for detection. Returns
    (grads_st, loss_st, sq_st), every leaf stacked per-rank on dim0."""
    gas = runner.gas

    def local(params, micros_l, rng, scale):
        r = jax.random.fold_in(rng, lax.axis_index(runner.axis))
        rngs = jax.random.split(r, gas)

        def body(acc, xs):
            micro, rr = xs
            cparams = jax.tree.map(
                lambda p: p.astype(runner.compute_dtype), params)

            def lossf(p):
                out = runner.apply_fn(p, micro, rr, True)
                # scale in f32: casting the scale itself to fp16 turns
                # 2^16 into inf and every step would spuriously overflow
                return runner.loss_fn(out, micro).astype(jnp.float32) * scale

            l, g = jax.value_and_grad(lossf)(cparams)
            return jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32), acc, g), l

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        gsum, losses = lax.scan(body, zero, (micros_l, rngs))
        g = jax.tree.map(lambda x: x[None] / (gas * scale), gsum)
        sq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g))
        return g, (jnp.mean(losses) / scale)[None], sq[None]

    mapped = jax.shard_map(
        local, mesh=runner.mesh,
        in_specs=(P(), P(None, runner.axis), P(), P()),
        out_specs=(P(runner.axis), P(runner.axis), P(runner.axis)),
        axis_names={runner.axis}, check_vma=False)
    return mapped(params, micros, rng, scale)


class OneBitRunner:
    """Owns optimizer state + the two-stage compiled train step."""

    def __init__(self,
                 kind: str,                      # "adam" | "lamb"
                 hyper: Dict,
                 mesh,
                 axis: str,
                 apply_fn: Callable,
                 loss_fn: Callable,
                 gas: int,
                 compute_dtype=jnp.float32,
                 grad_clip: float = 0.0,
                 loss_scaler=None,
                 zero_stage: int = 0):
        self.kind = kind
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.gas = gas
        self.apply_fn = apply_fn
        self.loss_fn = loss_fn
        self.compute_dtype = compute_dtype
        self.grad_clip = grad_clip
        self.loss_scaler = loss_scaler          # LossScaler or None
        self.zero_stage = int(zero_stage)

        h = dict(hyper or {})
        self.lr = float(h.pop("lr", 1e-3))
        b = h.pop("betas", (0.9, 0.999))
        self.betas = (float(b[0]), float(b[1]))
        self.eps = float(h.pop("eps", 1e-8))
        self.weight_decay = float(h.pop("weight_decay", 0.0))
        self.freeze_step = int(h.pop("freeze_step", 100))
        self.max_coeff = float(h.pop("max_coeff", 10.0))
        self.min_coeff = float(h.pop("min_coeff", 0.01))
        self.coeff_beta = float(h.pop("coeff_beta", 0.9))
        self.factor_max = float(h.pop("factor_max", 4.0))
        self.factor_min = float(h.pop("factor_min", 0.5))
        self.factor_threshold = float(h.pop("factor_threshold", 0.1))

        self._step_warm = None
        self._step_frozen = None

    # -- state ---------------------------------------------------------------

    def _mv_sharding(self, p) -> NamedSharding:
        """ZeRO-1: shard optimizer-state leaves dim-0 across DP where the
        size divides (reference granularity: partition what fits evenly,
        replicate the rest); stage 0 replicates everything."""
        if self.zero_stage >= 1 and np.ndim(p) >= 1 \
                and p.shape[0] % self.n == 0:
            return NamedSharding(self.mesh, P(self.axis))
        return NamedSharding(self.mesh, P())

    def init_state(self, params_f32: PyTree) -> Dict[str, PyTree]:
        rep = NamedSharding(self.mesh, P())
        sh = NamedSharding(self.mesh, P(self.axis))
        mv = lambda: jax.tree.map(
            lambda p: jax.device_put(jnp.zeros(p.shape, jnp.float32),
                                     self._mv_sharding(p)), params_f32)
        state = {"m": mv(), "v": mv()}
        state["w_err"] = jax.tree.map(
            lambda p: jax.device_put(jnp.zeros((self.n, p.size), jnp.float32), sh),
            params_f32)
        state["s_err"] = jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((self.n, chunk_elems(p.size, self.n)), jnp.float32), sh),
            params_f32)
        if self.kind == "lamb":
            state["v_fresh"] = mv()
            scalar = lambda val: jax.tree.map(
                lambda p: jnp.asarray(val, jnp.float32), params_f32)
            state["coeff_freeze"] = jax.device_put(scalar(0.0), rep)
            state["last_factor"] = jax.device_put(scalar(1.0), rep)
        return state

    # -- the per-rank grad stage ---------------------------------------------

    def _local_grads(self, params, micros, rng, scale):
        grads_st, loss_st, sq_st = stacked_local_grads(
            self, params, micros, rng, scale)
        return grads_st, jnp.mean(loss_st), sq_st

    # -- update math ---------------------------------------------------------

    def _mv_constrain(self, tree):
        """Pin optimizer-state outputs to their ZeRO-1 shardings so donation
        round-trips don't let XLA drift them to replicated."""
        if self.zero_stage < 1:
            return tree
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, self._mv_sharding(x)), tree)

    def _warm_update(self, params, state, grads_st, lr):
        b1, b2 = self.betas
        g_mean = jax.tree.map(lambda g: jnp.mean(g, 0), grads_st)  # psum here
        new_m = self._mv_constrain(jax.tree.map(
            lambda m, g: b1 * m + (1 - b1) * g, state["m"], g_mean))
        new_v = self._mv_constrain(jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], g_mean))
        out = dict(state, m=new_m, v=new_v)
        if self.kind == "adam":
            # reference OnebitAdam applies NO bias correction (onebit/adam.py)
            new_p = jax.tree.map(
                lambda p, m, v: p - lr * (m / (jnp.sqrt(v) + self.eps) +
                                          self.weight_decay * p),
                params, new_m, new_v)
            return new_p, out
        # lamb warmup: full trust-ratio LAMB + coeff EMA tracking
        from ..ops.optimizers import lamb_warm_leaf

        def leaf(p, m, v, cf):
            upd, coeff, new_cf = lamb_warm_leaf(
                p, m, v, cf, eps=self.eps, weight_decay=self.weight_decay,
                min_coeff=self.min_coeff, max_coeff=self.max_coeff,
                coeff_beta=self.coeff_beta)
            return p - lr * coeff * upd, new_cf

        flat_p, treedef = jax.tree.flatten(params)
        res = [leaf(p, m, v, cf) for p, m, v, cf in zip(
            flat_p, treedef.flatten_up_to(new_m), treedef.flatten_up_to(new_v),
            treedef.flatten_up_to(state["coeff_freeze"]))]
        out["coeff_freeze"] = treedef.unflatten([r[1] for r in res])
        out["v_fresh"] = new_v
        return treedef.unflatten([r[0] for r in res]), out

    def _frozen_update(self, params, state, grads_st, lr):
        """Compression stage: the ONLY cross-rank traffic per leaf is the
        1-bit momentum exchange (+ f32 scales).

        ZeRO-1 note: the error-feedback exchange needs the FULL momentum on
        every rank (m_locals = b1*m + (1-b1)*g_local), so after the freeze m
        lives replicated — one all-gather at the transition, none after.
        The variance v (frozen, read-only here) and lamb's v_fresh keep
        their ZeRO-1 shardings, so the state-memory saving persists on the
        v-side leaves."""
        b1, b2 = self.betas
        flat_p, treedef = jax.tree.flatten(params)
        m_l = treedef.flatten_up_to(state["m"])
        v_l = treedef.flatten_up_to(state["v"])
        g_l = treedef.flatten_up_to(grads_st)
        we_l = treedef.flatten_up_to(state["w_err"])
        se_l = treedef.flatten_up_to(state["s_err"])

        new_p, new_m, new_we, new_se = [], [], [], []
        extras = {}
        if self.kind == "lamb":
            vf_l = treedef.flatten_up_to(state["v_fresh"])
            cf_l = treedef.flatten_up_to(state["coeff_freeze"])
            lf_l = treedef.flatten_up_to(state["last_factor"])
            new_vf, new_lf = [], []

        for j, (p, m, g_st, we, se) in enumerate(
                zip(flat_p, m_l, g_l, we_l, se_l)):
            m_locals = b1 * m[None] + (1 - b1) * g_st       # [n, ...]
            m_new, we2, se2 = compressed_allreduce(
                m_locals, we, se, mesh=self.mesh, axis=self.axis)
            new_m.append(m_new)
            new_we.append(we2)
            new_se.append(se2)
            v = v_l[j]
            if self.kind == "adam":
                upd = m_new / (jnp.sqrt(v) + self.eps) + self.weight_decay * p
                new_p.append(p - lr * upd)
                continue
            # lamb compression stage (reference onebit/lamb.py:337-386);
            # per-leaf math shared with ops/optimizers.onebit_lamb
            from ..ops.optimizers import lamb_frozen_leaf
            upd, factor, vf = lamb_frozen_leaf(
                p, m, m_new, v, vf_l[j], lf_l[j], b1=b1, b2=b2, eps=self.eps,
                weight_decay=self.weight_decay, factor_min=self.factor_min,
                factor_max=self.factor_max,
                factor_threshold=self.factor_threshold)
            new_p.append(p - lr * (cf_l[j] * factor) * upd)
            new_vf.append(vf)
            new_lf.append(factor)

        # commit the replicated layout of the frozen-phase m: without this
        # pin, XLA's layout choice under ZeRO-1 may re-shard m and pay a
        # re-gather every step (the docstring's "one all-gather at the
        # transition" contract)
        rep = NamedSharding(self.mesh, P())
        new_m = [jax.lax.with_sharding_constraint(m, rep) for m in new_m]
        out = dict(state,
                   m=treedef.unflatten(new_m),
                   w_err=treedef.unflatten(new_we),
                   s_err=treedef.unflatten(new_se))
        if self.kind == "lamb":
            out["v_fresh"] = self._mv_constrain(treedef.unflatten(new_vf))
            out["last_factor"] = treedef.unflatten(new_lf)
        return treedef.unflatten(new_p), out

    # -- compiled steps -------------------------------------------------------

    def _build(self, frozen: bool):
        scaling = self.loss_scaler is not None and self.loss_scaler.enabled

        def step(params, state, micros, rng, lr, scale_state):
            scale = (scale_state.scale if scaling
                     else jnp.asarray(1.0, jnp.float32))
            grads_st, loss, sq_st = self._local_grads(params, micros, rng,
                                                      scale)
            # norm: in the compression stage, avoid the full f32 allreduce the
            # exact global norm would cost (it would dwarf the 1-bit savings)
            # — use sqrt(mean of per-rank ||g_local||^2), a scalar psum. The
            # warmup stage gets the exact norm for free off the mean grads.
            if frozen:
                norm = jnp.sqrt(jnp.mean(sq_st))
            else:
                norm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(jnp.mean(g, 0)))
                    for g in jax.tree.leaves(grads_st)))
            if self.grad_clip > 0:
                coef = jnp.minimum(self.grad_clip / (norm + 1e-6), 1.0)
                grads_st = jax.tree.map(lambda g: g * coef, grads_st)

            def do_update(args):
                params, state, grads_st = args
                if frozen:
                    new_p, new_s = self._frozen_update(params, state,
                                                       grads_st, lr)
                else:
                    new_p, new_s = self._warm_update(params, state,
                                                     grads_st, lr)
                # ZeRO-1 sharded m/v make the raw update come out sharded;
                # params stay replicated (the all-gather IS the ZeRO-1 wire
                # pattern)
                if self.zero_stage >= 1:
                    rep = NamedSharding(self.mesh, P())
                    new_p = jax.lax.with_sharding_constraint(new_p, rep)
                return new_p, new_s

            if scaling:
                # fp16 overflow: skip the WHOLE update (momentum, compressed
                # exchange, params) and let the scaler state react — the
                # reference's FP16_Optimizer skip path (onebit runs under it,
                # onebit/adam.py:11)
                overflow = ~jnp.isfinite(norm)
                new_p, new_s = lax.cond(
                    overflow, lambda a: (a[0], a[1]), do_update,
                    (params, state, grads_st))
                new_scale_state = self.loss_scaler.update(scale_state,
                                                          overflow)
            else:
                overflow = jnp.asarray(False)
                new_p, new_s = do_update((params, state, grads_st))
                new_scale_state = scale_state
            return new_p, new_s, loss, norm, overflow, new_scale_state

        return jax.jit(step, donate_argnums=(0, 1))

    def step(self, params, state, micros, rng, lr, global_step: int,
             scale_state=None
             ) -> Tuple[PyTree, Dict, jnp.ndarray, jnp.ndarray,
                        jnp.ndarray, Any]:
        from .loss_scaler import LossScaleState
        if scale_state is None:
            # with an enabled scaler the caller must not silently train at
            # scale 1.0 — start from the scaler's own initial state
            scale_state = (self.loss_scaler.init()
                           if self.loss_scaler is not None
                           and self.loss_scaler.enabled
                           else LossScaleState.identity())
        frozen = global_step >= self.freeze_step
        if frozen:
            if self._step_frozen is None:
                self._step_frozen = self._build(True)
            fn = self._step_frozen
        else:
            if self._step_warm is None:
                self._step_warm = self._build(False)
            fn = self._step_warm
        return fn(params, state, micros, rng,
                  jnp.asarray(lr, jnp.float32), scale_state)

    # -- auditability ---------------------------------------------------------

    def collective_bytes(self, params, state, micros, rng,
                         frozen: bool) -> int:
        """Total bytes moved by cross-replica collectives in one compiled
        step — parsed from the optimized HLO, so the 1/32 wire claim is a
        measured property, not a docstring."""
        from .loss_scaler import LossScaleState
        fn = self._build(frozen)
        scale_state = LossScaleState.identity()
        lowered = jax.jit(
            lambda p, s, mi, r, lr, ss: fn(p, s, mi, r, lr, ss)).lower(
            params, state, micros, rng, jnp.asarray(self.lr, jnp.float32),
            scale_state)
        txt = lowered.compile().as_text()
        return hlo_collective_bytes(txt)


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


def hlo_collective_bytes(hlo_text: str) -> int:
    """Sum output bytes of cross-replica collective ops in optimized HLO.

    Async pairs are handled: '-start' op tuples are (operand, result, ...
    context scalars) — only the result (element 1) is counted — and '-done'
    ops (which alias the start's buffers) are skipped, so bytes aren't
    double- or triple-counted on real TPU HLO."""
    import re
    total = 0
    pat = re.compile(
        r"=\s*(?:\(([^)]*)\)|([a-z0-9]+)\[([0-9,]*)\][^ ]*)\s*"
        r"(all-reduce|all-gather|all-to-all|reduce-scatter|"
        r"collective-permute)(-start|-done)?\b")
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for mt in pat.finditer(hlo_text):
        suffix = mt.group(5)
        if suffix == "-done":
            continue
        if mt.group(1) is not None:      # tuple result
            shapes = shape_pat.findall(mt.group(1))
            if suffix == "-start" and len(shapes) > 1:
                # async-start tuples are (operand, result[, u32 context
                # scalars]); the wire payload is the RESULT at index 1 —
                # the last element can be a context scalar
                shapes = shapes[1:2]
        else:
            shapes = [(mt.group(2), mt.group(3))]
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue
            numel = 1
            for d in dims.split(","):
                if d.strip():
                    numel *= int(d)
            total += numel * _DTYPE_BYTES[dt]
    return total
