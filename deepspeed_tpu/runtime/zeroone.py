"""Explicit-collective 0/1 Adam — the real ZeroOneAdam algorithm, multi-rank.

Role of the reference's ``runtime/fp16/onebit/zoadam.py:11-377`` (paper
arXiv:2202.06009). 0/1 Adam is NOT 1-bit Adam with different defaults; it has
two distinct mechanisms the OneBitRunner doesn't have:

* **Adaptive variance freezing**: in the variance phase the second moment v
  updates only every ``var_interval`` steps, and the interval doubles after
  every ``var_update_scaler`` v-updates. v-update steps pay an exact
  (uncompressed) gradient mean; the steps in between exchange the gradient
  1-bit compressed with error feedback.
* **1-bit sync with local steps**: past ``var_freeze_step`` every rank takes
  purely LOCAL steps — zero cross-rank traffic of any kind — accumulating its
  parameter drift in ``u``; only at interval boundaries
  (``step % local_interval == 0``, the interval doubling every
  ``local_step_scaler`` steps up to ``local_step_clipper``) does a compressed
  exchange of the accumulated momentum resync params and momentum.

SPMD realization: the engine's params stay the REPLICATED synced base the
whole time. The per-rank drift u and per-rank momentum live stacked [n, ...]
(dim 0 sharded over the data axis). Local steps run entirely inside a
shard_map with no collective ops — each rank differentiates at its own
effective params ``base + u_rank`` — so the compiled HLO of the local-step
program contains zero cross-replica collectives (auditable via
``collective_bytes``; tests/test_onebit.py asserts it). At a boundary the
drift is converted to momentum units, pushed through
``compressed_allreduce``, and folded back into the base params.

Composition envelope mirrors OneBitRunner: pure-DP mesh; ZeRO-1 shards m/v
during the variance phase (v is gathered once at the freeze transition — it
is read-only afterwards and every local step needs it in full); fp16 loss
scaling composes, at the documented cost of one scalar overflow psum in the
otherwise collective-free local step.  Loss/grad-norm in the local phase are
reported as the mean over this process's addressable ranks (combining them
on-device would itself be a collective).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .comm.compressed import chunk_elems, compressed_allreduce
from .onebit import hlo_collective_bytes  # noqa: F401  (re-export for tests)

PyTree = Any


class _VarSchedule:
    """var_interval in effect when processing 1-indexed step ``t`` — an
    incremental replay of the reference's var_counter/var_interval
    bookkeeping (O(1) amortized per training step; a checkpoint resume just
    replays forward from 1 once)."""

    def __init__(self, kappa: int):
        self.kappa = kappa
        self._s, self._iv, self._vc = 1, 1, 0     # next step to process

    def at(self, t: int) -> int:
        if t < self._s:
            self._s, self._iv, self._vc = 1, 1, 0
        while self._s < t:
            if self._s % self._iv == 0:
                self._vc += 1
                if self._vc == self.kappa:
                    self._vc = 0
                    self._iv *= 2
            self._s += 1
        return self._iv


class _LocalSchedule:
    """local_step_interval in effect at 1-indexed step ``t`` (counting from
    the end of the variance phase)."""

    def __init__(self, freeze: int, scaler: int, clipper: int):
        self.freeze, self.scaler, self.clipper = freeze, scaler, clipper
        self._s, self._li, self._lc = freeze + 1, 1, 0

    def at(self, t: int) -> int:
        if t < self._s:
            self._s, self._li, self._lc = self.freeze + 1, 1, 0
        while self._s < t:
            self._lc += 1
            if self._lc == self.scaler:
                self._lc = 0
                self._li = min(self.clipper, self._li * 2)
            self._s += 1
        return self._li


class ZeroOneRunner:
    """Owns optimizer state + the four compiled step programs
    (vstep / cstep in the variance phase, local / boundary after it)."""

    def __init__(self,
                 hyper: Dict,
                 mesh,
                 axis: str,
                 apply_fn,
                 loss_fn,
                 gas: int,
                 compute_dtype=jnp.float32,
                 grad_clip: float = 0.0,
                 loss_scaler=None,
                 zero_stage: int = 0):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.gas = gas
        self.apply_fn = apply_fn
        self.loss_fn = loss_fn
        self.compute_dtype = compute_dtype
        self.grad_clip = grad_clip
        self.loss_scaler = loss_scaler
        self.zero_stage = int(zero_stage)

        h = dict(hyper or {})
        self.lr = float(h.pop("lr", 1e-3))
        b = h.pop("betas", (0.9, 0.999))
        self.betas = (float(b[0]), float(b[1]))
        self.eps = float(h.pop("eps", 1e-8))
        self.weight_decay = float(h.pop("weight_decay", 0.0))
        self.var_freeze_step = int(h.pop("var_freeze_step", 100000))
        self.var_update_scaler = int(h.pop("var_update_scaler", 16))
        self.local_step_scaler = int(h.pop("local_step_scaler", 32678))
        self.local_step_clipper = int(h.pop("local_step_clipper", 16))
        # accepted-for-compat reference knobs (transport / unused-by-the-
        # reference's-own-math); amsgrad raises there too (zoadam.py)
        if h.pop("amsgrad", False):
            raise ValueError("0/1 Adam does not support amsgrad")
        for k in ("cuda_aware", "comm_backend_name", "bias_correction",
                  "eps_inside_sqrt", "max_grad_norm"):
            h.pop(k, None)

        self._programs: Dict[str, Any] = {}
        self._transitioned = False
        self._vsched = _VarSchedule(self.var_update_scaler)
        self._lsched = _LocalSchedule(self.var_freeze_step,
                                      self.local_step_scaler,
                                      self.local_step_clipper)

    # -- state ---------------------------------------------------------------

    def _mv_sharding(self, p) -> NamedSharding:
        if self.zero_stage >= 1 and np.ndim(p) >= 1 \
                and p.shape[0] % self.n == 0:
            return NamedSharding(self.mesh, P(self.axis))
        return NamedSharding(self.mesh, P())

    def init_state(self, params_f32: PyTree) -> Dict[str, PyTree]:
        st = NamedSharding(self.mesh, P(self.axis))
        mv = lambda: jax.tree.map(
            lambda p: jax.device_put(jnp.zeros(p.shape, jnp.float32),
                                     self._mv_sharding(p)), params_f32)
        stacked = lambda: jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((self.n,) + p.shape, jnp.float32), st), params_f32)
        state = {"m": mv(), "v": mv(),
                 # per-rank momentum + drift for the local-step phase;
                 # allocated up front so the state pytree (and therefore the
                 # engine's checkpoint layout) never changes shape
                 "m_local": stacked(), "u": stacked(),
                 "lrs": jnp.asarray(0.0, jnp.float32)}
        state["w_err"] = jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((self.n, p.size), jnp.float32), st), params_f32)
        state["s_err"] = jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((self.n, chunk_elems(p.size, self.n)), jnp.float32),
                st), params_f32)
        return state

    # -- per-rank grad stage ---------------------------------------------------

    def _stacked_grads(self, params, micros, rng, scale):
        """Stacked per-rank grads at the shared base params, no reduction
        (variance-phase programs) — the shared 1-bit/0-1 gradient stage."""
        from .onebit import stacked_local_grads
        return stacked_local_grads(self, params, micros, rng, scale)

    # -- variance-phase programs ----------------------------------------------

    def _build_var(self, is_v: bool):
        b1, b2 = self.betas
        scaling = self.loss_scaler is not None and self.loss_scaler.enabled

        def step(params, state, micros, rng, lr, scale_state):
            scale = (scale_state.scale if scaling
                     else jnp.asarray(1.0, jnp.float32))
            grads_st, loss_st, sq_st = self._stacked_grads(
                params, micros, rng, scale)
            loss = jnp.mean(loss_st)

            def do_update(args):
                params, state, grads_st = args
                new_s = dict(state)
                if is_v:
                    # exact-gradient step: update momentum AND variance
                    g = jax.tree.map(lambda g: jnp.mean(g, 0), grads_st)
                    norm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
                    if self.grad_clip > 0:
                        coef = jnp.minimum(
                            self.grad_clip / (norm + 1e-6), 1.0)
                        g = jax.tree.map(lambda x: x * coef, g)
                    new_s["m"] = self._mv_pin(jax.tree.map(
                        lambda m, gg: b1 * m + (1 - b1) * gg,
                        state["m"], g))
                    new_s["v"] = self._mv_pin(jax.tree.map(
                        lambda v, gg: b2 * v + (1 - b2) * gg * gg,
                        state["v"], g))
                else:
                    # compressed-gradient step: v untouched (the freeze)
                    flat_g, treedef = jax.tree.flatten(grads_st)
                    we = treedef.flatten_up_to(state["w_err"])
                    se = treedef.flatten_up_to(state["s_err"])
                    g_sync, nwe, nse = [], [], []
                    for g_st, w, s in zip(flat_g, we, se):
                        gsy, w2, s2 = compressed_allreduce(
                            g_st, w, s, mesh=self.mesh, axis=self.axis)
                        g_sync.append(gsy)
                        nwe.append(w2)
                        nse.append(s2)
                    norm = jnp.sqrt(sum(
                        jnp.sum(jnp.square(x)) for x in g_sync))
                    if self.grad_clip > 0:
                        coef = jnp.minimum(
                            self.grad_clip / (norm + 1e-6), 1.0)
                        g_sync = [x * coef for x in g_sync]
                    g = treedef.unflatten(g_sync)
                    new_s["m"] = self._mv_pin(jax.tree.map(
                        lambda m, gg: b1 * m + (1 - b1) * gg,
                        state["m"], g))
                    new_s["w_err"] = treedef.unflatten(nwe)
                    new_s["s_err"] = treedef.unflatten(nse)
                new_p = jax.tree.map(
                    lambda p, m, v: p - lr * (
                        m / (jnp.sqrt(v) + self.eps)
                        + self.weight_decay * p),
                    params, new_s["m"], new_s["v"])
                rep = NamedSharding(self.mesh, P())
                new_p = jax.lax.with_sharding_constraint(new_p, rep)
                return new_p, new_s, norm

            if scaling:
                gnorm = jnp.sqrt(jnp.mean(sq_st))
                overflow = ~jnp.isfinite(gnorm)
                new_p, new_s, norm = lax.cond(
                    overflow,
                    lambda a: (a[0], a[1], gnorm), do_update,
                    (params, state, grads_st))
                new_scale_state = self.loss_scaler.update(scale_state,
                                                          overflow)
            else:
                overflow = jnp.asarray(False)
                new_p, new_s, norm = do_update((params, state, grads_st))
                new_scale_state = scale_state
            return new_p, new_s, loss, norm, overflow, new_scale_state

        return jax.jit(step, donate_argnums=(0, 1))

    def _mv_pin(self, tree):
        if self.zero_stage < 1:
            return tree
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, self._mv_sharding(x)), tree)

    # -- local-step-phase programs ---------------------------------------------

    def _build_local(self, boundary: bool):
        b1, _b2 = self.betas
        scaling = self.loss_scaler is not None and self.loss_scaler.enabled

        def step(params, state, micros, rng, lr, scale_state):
            scale = (scale_state.scale if scaling
                     else jnp.asarray(1.0, jnp.float32))
            # read-only frozen variance, needed whole by every rank
            v_rep = jax.lax.with_sharding_constraint(
                state["v"], NamedSharding(self.mesh, P()))
            gas = self.gas

            def local(params, v, m_l, u_l, micros_l, rng, scale, lr):
                """One purely-local step for this rank: no collectives."""
                m_r = jax.tree.map(lambda x: x[0], m_l)
                u_r = jax.tree.map(lambda x: x[0], u_l)
                p_eff = jax.tree.map(lambda p, u: p + u, params, u_r)
                r = jax.random.fold_in(rng, lax.axis_index(self.axis))
                rngs = jax.random.split(r, gas)

                def body(acc, xs):
                    micro, rr = xs
                    cparams = jax.tree.map(
                        lambda p: p.astype(self.compute_dtype), p_eff)

                    def lossf(p):
                        out = self.apply_fn(p, micro, rr, True)
                        return (self.loss_fn(out, micro)
                                .astype(jnp.float32) * scale)

                    l, g = jax.value_and_grad(lossf)(cparams)
                    return jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32),
                        acc, g), l

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), p_eff)
                gsum, losses = lax.scan(body, zero, (micros_l, rngs))
                g = jax.tree.map(lambda x: x / (gas * scale), gsum)
                sq = sum(jnp.sum(jnp.square(x))
                         for x in jax.tree.leaves(g))
                norm_r = jnp.sqrt(sq)
                if self.grad_clip > 0:
                    # per-rank clip: a global norm would need a psum the
                    # collective-free local step must not pay
                    coef = jnp.minimum(
                        self.grad_clip / (norm_r + 1e-6), 1.0)
                    g = jax.tree.map(lambda x: x * coef, g)
                m_new = jax.tree.map(
                    lambda m, gg: b1 * m + (1 - b1) * gg, m_r, g)
                upd = jax.tree.map(
                    lambda m, vv, pe: m / (jnp.sqrt(vv) + self.eps)
                    + self.weight_decay * pe, m_new, v, p_eff)
                u_new = jax.tree.map(lambda u, up: u - lr * up, u_r, upd)
                stack = lambda t: jax.tree.map(lambda x: x[None], t)
                return (stack(m_new), stack(u_new),
                        (jnp.mean(losses) / scale)[None], norm_r[None])

            mapped = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(), P(self.axis), P(self.axis),
                          P(None, self.axis), P(), P(), P()),
                out_specs=(P(self.axis), P(self.axis), P(self.axis),
                           P(self.axis)),
                axis_names={self.axis}, check_vma=False)
            m_st, u_st, loss_st, norm_st = mapped(
                params, v_rep, state["m_local"], state["u"], micros, rng,
                scale, lr)
            lrs_new = state["lrs"] + lr

            new_s = dict(state)
            new_p = params
            if boundary:
                # params are ALREADY the synced base (drift lives in u):
                # convert drift to momentum units, compressed-exchange it,
                # fold the averaged drift into the base and recover the
                # averaged momentum m = -u_sync / sum(lr)
                # (reference zoadam.py:253-276)
                flat_u, treedef = jax.tree.flatten(u_st)
                flat_v = treedef.flatten_up_to(v_rep)
                we = treedef.flatten_up_to(state["w_err"])
                se = treedef.flatten_up_to(state["s_err"])
                flat_p = treedef.flatten_up_to(params)
                nwe, nse, n_p, n_ml, n_u = [], [], [], [], []
                for u, v, w, s, p in zip(flat_u, flat_v, we, se, flat_p):
                    denom = jnp.sqrt(v) + self.eps
                    u_m = u * denom[None]
                    u_sync, w2, s2 = compressed_allreduce(
                        u_m, w, s, mesh=self.mesh, axis=self.axis)
                    nwe.append(w2)
                    nse.append(s2)
                    # the recovered average momentum (reference: exp_avg =
                    # -comm_buffer/lrs) lives only in the per-rank stack;
                    # state["m"] stays the stale variance-phase value by
                    # design — nothing reads it after the freeze
                    m_rep = -u_sync / lrs_new
                    n_ml.append(jax.lax.with_sharding_constraint(
                        jnp.broadcast_to(m_rep[None],
                                         (self.n,) + m_rep.shape),
                        NamedSharding(self.mesh, P(self.axis))))
                    n_p.append(p + u_sync / denom)
                    n_u.append(jnp.zeros_like(u))
                rep = NamedSharding(self.mesh, P())
                new_p = jax.lax.with_sharding_constraint(
                    treedef.unflatten(n_p), rep)
                new_s["m_local"] = treedef.unflatten(n_ml)
                # pin the reset drift to its stacked sharding: unconstrained
                # fresh zeros let XLA REPLICATE u — measured at 32 B/param/
                # device instead of 4 (scripts/onebit_envelope.py caught it)
                new_s["u"] = jax.tree.map(
                    lambda z: jax.lax.with_sharding_constraint(
                        z, NamedSharding(self.mesh, P(self.axis))),
                    treedef.unflatten(n_u))
                new_s["w_err"] = treedef.unflatten(nwe)
                new_s["s_err"] = treedef.unflatten(nse)
                new_s["lrs"] = jnp.asarray(0.0, jnp.float32)
            else:
                new_s["m_local"] = m_st
                new_s["u"] = u_st
                new_s["lrs"] = lrs_new

            if scaling:
                # scalar overflow psum — the one collective the fp16 local
                # step pays (bf16 runs are strictly collective-free)
                overflow = ~jnp.isfinite(jnp.sum(norm_st))
                sel = lambda old, new: jax.tree.map(
                    lambda o, nn: jnp.where(overflow, o, nn), old, new)
                new_p = sel(params, new_p)
                new_s = sel(state, new_s)
                new_scale_state = self.loss_scaler.update(scale_state,
                                                          overflow)
            else:
                overflow = jnp.asarray(False)
                new_scale_state = scale_state
            return new_p, new_s, loss_st, norm_st, overflow, new_scale_state

        return jax.jit(step, donate_argnums=(0, 1))

    # -- freeze transition -----------------------------------------------------

    def _transition(self, state):
        """One-time restructure entering the local-step phase: broadcast the
        synced momentum into the per-rank stack, gather the (now frozen)
        variance whole, reset the error buffers (reference
        reinitial_error_buffer: they switch metrics from gradients to
        accumulated momentum)."""
        rep = NamedSharding(self.mesh, P())
        st = NamedSharding(self.mesh, P(self.axis))
        out = dict(state)
        out["v"] = jax.device_put(state["v"], rep)
        bcast = jax.jit(
            lambda m: jnp.broadcast_to(m[None], (self.n,) + m.shape),
            out_shardings=st)
        out["m_local"] = jax.tree.map(bcast, jax.device_put(state["m"], rep))
        zero = lambda t: jax.tree.map(
            lambda x: jax.device_put(jnp.zeros_like(x), x.sharding), t)
        out["w_err"] = zero(state["w_err"])
        out["s_err"] = zero(state["s_err"])
        out["u"] = zero(state["u"])
        out["lrs"] = jnp.asarray(0.0, jnp.float32)
        return out

    # -- host-side schedule + dispatch ----------------------------------------

    def _program(self, key: str):
        if key not in self._programs:
            if key in ("vstep", "cstep"):
                self._programs[key] = self._build_var(key == "vstep")
            else:
                self._programs[key] = self._build_local(key == "boundary")
        return self._programs[key]

    def program_key(self, global_step: int) -> str:
        """Which compiled program step ``global_step`` (0-indexed) runs —
        pure function of the step, so checkpoint resume replays it."""
        t = global_step + 1
        if t <= self.var_freeze_step:
            iv = self._vsched.at(t)
            return "vstep" if t % iv == 0 else "cstep"
        li = self._lsched.at(t)
        return "boundary" if t % li == 0 else "local"

    def step(self, params, state, micros, rng, lr, global_step: int,
             scale_state=None) -> Tuple[PyTree, Dict, Any, Any, Any, Any]:
        from .loss_scaler import LossScaleState
        if scale_state is None:
            scale_state = (self.loss_scaler.init()
                           if self.loss_scaler is not None
                           and self.loss_scaler.enabled
                           else LossScaleState.identity())
        key = self.program_key(global_step)
        if key in ("vstep", "cstep"):
            # back in the variance phase (e.g. a pre-freeze checkpoint was
            # restored after the freeze had been crossed) — re-arm the
            # transition so re-crossing var_freeze_step re-broadcasts m and
            # resets the error buffers
            self._transitioned = False
        if key in ("local", "boundary") and not self._transitioned:
            if global_step == self.var_freeze_step:
                state = self._transition(state)
            else:
                # resumed from a post-transition checkpoint — state already
                # carries the broadcast m_local / reset errors, but the
                # engine restored v with its init-time (ZeRO-1) sharding;
                # re-gather it once here or every local step would pay the
                # all-gather the collective-free program must not contain
                state = dict(state)
                state["v"] = jax.device_put(
                    state["v"], NamedSharding(self.mesh, P()))
            self._transitioned = True
        out = self._program(key)(params, state, micros, rng,
                                 jnp.asarray(lr, jnp.float32), scale_state)
        if key in ("local", "boundary"):
            # per-rank stacked loss/norm -> host mean over addressable
            # shards (an on-device mean would be a collective in the
            # otherwise collective-free program). The host read adds no new
            # pipeline bubble: the engine blocks on the loss every step
            # anyway (train_batch's train.sync span).
            new_p, new_s, loss_st, norm_st, overflow, nss = out
            loss = jnp.asarray(self._host_mean(loss_st), jnp.float32)
            norm = jnp.asarray(self._host_mean(norm_st), jnp.float32)
            return new_p, new_s, loss, norm, overflow, nss
        return out

    @staticmethod
    def _host_mean(arr) -> float:
        vals = [np.asarray(sh.data).reshape(-1)
                for sh in arr.addressable_shards]
        return float(np.mean(np.concatenate(vals))) if vals else float("nan")

    # -- auditability ----------------------------------------------------------

    def collective_bytes(self, params, state, micros, rng, key: str) -> int:
        """Bytes moved by cross-replica collectives in one compiled step of
        program ``key`` — parsed from optimized HLO. The headline claims:
        'local' is 0 (bf16) and 'cstep'/'boundary' are ~1/32 of the exact
        exchange."""
        from .loss_scaler import LossScaleState
        lowered = self._program(key).lower(
            params, state, micros, rng, jnp.asarray(self.lr, jnp.float32),
            LossScaleState.identity())
        return hlo_collective_bytes(lowered.compile().as_text())
