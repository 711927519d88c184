"""1F1B pipeline execution — hand-scheduled forward/backward interleave.

The GPipe executor (spmd.py) differentiates THROUGH a lax.scan, so autodiff
saves every tick's carry: activation memory grows with n_micro. This module
is the reference's actual 1F1B regime (runtime/pipe/schedule.py TrainSchedule
+ engine.py _exec_schedule): gradients are computed by a hand-written
interleave where each stage holds at most ``pp`` saved boundary inputs —
activation memory ∝ stages, not microbatches. Two backward modes:

  * ``store_outputs=False`` (default): backward recomputes the stage body
    from the saved input — one extra forward per micro per stage, nothing
    but the [mb, ...] boundary stored (the same deal as the reference's
    activation-checkpointing interleave, module.py:309).
  * ``store_outputs=True``: the forward tick runs the stage body under
    jax.vjp and the residuals ride slot rings to the backward tick — no
    recompute (the reference's own store-outputs design,
    engine.py:630-781), at the cost of holding ~pp ticks of stage-internal
    residuals live.

Generality (round-3 Missing #3 closed): per-micro side inputs (attention
masks, dropout rng keys) ride along via ``extras``; MoE's load-balance aux
scalar flows through the manual backward via ``with_aux``/``aux_cotangent``;
an fp16 ``loss_scale`` seeds the backward (grads come out scaled, the
engine's standard unscale/overflow tail applies); any per-micro last-stage
loss_fn is accepted.

Mechanics, all inside one SPMD program over the 'pipe' mesh axis:
  * a host-side event simulation produces clock-aligned instruction tables
    (fwd/bwd micro id per [tick, stage], plus the matching receive tables);
    one tick = one compute slot, sends land one tick later — the alignment
    TrainSchedule's abstract clock doesn't guarantee;
  * the scan body does (masked) one forward + one backward per tick: ring
    buffers hold received activations/cotangents and saved inputs (or vjp
    residuals), keyed by micro % pp; the stage vjp yields dx (sent upstream
    via the reversed ppermute) and accumulated param grads;
  * the last stage computes the per-micro loss in-tick and seeds its own
    backward; the loss head's grads psum over 'pipe' at the end.

Because no AD runs through the scan or the collectives, the boundary stays
in the COMPUTE dtype (bf16) end to end — the f32 crossing the GPipe path
needs to dodge the low-precision-collective transpose bug does not apply.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# the schedule/placement split (round 13): the clock tables live in the
# schedule layer — this module is the SPMD *placement* of that schedule.
# Re-exported here for backwards compatibility (benchmarks, tests).
from .schedule import build_1f1b_tables

PyTree = Any


def pipeline_1f1b_value_and_grad(
        stage_fn: Callable,
        loss_fn: Callable[[PyTree, jnp.ndarray, jnp.ndarray], jnp.ndarray],
        stage_params: PyTree,
        head_params: PyTree,
        micros: jnp.ndarray,
        labels: PyTree,
        *,
        mesh,
        pp: int,
        pipe_axis: str = "pipe",
        extras: Optional[PyTree] = None,
        with_aux: bool = False,
        aux_cotangent: float = 0.0,
        loss_scale=None,
        store_outputs: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, PyTree, PyTree, jnp.ndarray]:
    """One 1F1B pass. Returns (mean task loss, mean aux, stage grads,
    head grads, dmicros).

    stage_fn(one_stage_params, x [mb, ...], extra, stage_idx) -> y, or
        (y, aux_scalar) when with_aux — every stage's body. ``extra`` is the
        per-micro slice of ``extras`` (attention masks, rng keys, ...);
        ``stage_idx`` is this rank's pipe index (for rng folding).
    loss_fn(head_params, y, labels_micro) -> scalar   LAST stage only (head
        + per-micro loss; its grads seed the backward)
    micros [n_micro, mb, ...] stage-0 inputs (e.g. embedded tokens);
    labels: pytree of [n_micro, ...] per-micro targets; dmicros lets the
    caller backprop the embedding outside the pipe.
    loss_scale: optional scalar seeding the backward (fp16) — grads and
        dmicros come out SCALED; aux_cotangent is scaled internally.
    """
    n_micro = micros.shape[0]
    tables = build_1f1b_tables(n_micro, pp)
    fwd_t = jnp.asarray(tables["fwd"])
    bwd_t = jnp.asarray(tables["bwd"])
    rf_t = jnp.asarray(tables["recv_f"])
    rb_t = jnp.asarray(tables["recv_b"])
    T = tables["ticks"]
    slots = min(pp, n_micro)                    # 1F1B in-flight bound
    if extras is None:
        extras = {}

    def inner(stage_params, head_params, micros, labels, extras):
        local = jax.tree.map(lambda x: x[0], stage_params)
        stage = jax.lax.axis_index(pipe_axis)
        mshape = micros.shape[1:]
        zero_m = jnp.zeros(mshape, micros.dtype)
        scale = (jnp.asarray(1.0, jnp.float32) if loss_scale is None
                 else loss_scale.astype(jnp.float32))
        aux_ct = jnp.asarray(aux_cotangent, jnp.float32) * scale

        def extra_of(mid):
            return jax.tree.map(lambda e: e[jnp.maximum(mid, 0)], extras)

        def body(p, x, extra):
            """Uniform (y, aux) stage body closure."""
            out = stage_fn(p, x, extra, stage)
            if with_aux:
                return out
            return out, jnp.zeros((), jnp.float32)

        rings = {
            "in_act": jnp.zeros((slots,) + mshape, micros.dtype),
            "in_grad": jnp.zeros((slots,) + mshape, micros.dtype),
        }
        res_treedef = None
        res_static = None
        static_vals = None
        if not store_outputs:
            rings["saved_x"] = jnp.zeros((slots,) + mshape, micros.dtype)
        if store_outputs:
            # probe the vjp residual structure (shapes are tick-invariant;
            # the probe computation is unused and DCE'd by XLA)
            _, vjp_probe = jax.vjp(
                lambda p, x: body(p, x, extra_of(jnp.asarray(0))),
                local, zero_m)
            res_leaves, res_treedef = jax.tree.flatten(vjp_probe)
            # residual leaves that ARE the stage weights (jax forwards the
            # kernels as residuals for dx = dy @ W^T) are tick-invariant:
            # ring-buffering them would hold slots x stage-params of live
            # copies — reinject the live values at backward instead.
            # (Identity matching catches pass-through leaves; residuals
            # DERIVED from weights — e.g. a sharding-constraint or dtype
            # cast output — still ride the rings, so the saving is partial
            # for bodies that transform their kernels before use.)
            param_ids = {id(l) for l in jax.tree.leaves(local)}
            res_static = [id(l) in param_ids for l in res_leaves]
            static_vals = [l for l, st in zip(res_leaves, res_static) if st]
            # the id() match is best-effort: if jax stops passing weights
            # through as identical objects (or the body casts/constrains
            # its kernels first), everything classifies dynamic and the
            # rings hold slots x stage-weights of live copies — the exact
            # memory this mode exists to bound. Make that degradation
            # loud instead of silent.
            par_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                            for l in jax.tree.leaves(local))
            # degradation signal: WEIGHT-SHAPED residuals that failed the
            # id() match (a cast/constrained kernel riding the rings) —
            # plain activation residuals are the mode's normal cost and
            # must not trip this
            par_shapes = {l.shape for l in jax.tree.leaves(local)}
            stray_bytes = sum(
                int(np.prod(l.shape)) * l.dtype.itemsize
                for l, st in zip(res_leaves, res_static)
                if not st and l.shape in par_shapes)
            if par_bytes and stray_bytes >= par_bytes // 2:
                from ...utils.logging import warning_once
                warning_once(
                    "1F1B store_outputs: "
                    f"{stray_bytes / 1e6:.1f} MB/slot of weight-shaped "
                    "vjp residuals failed the tick-invariance match "
                    f"(stage params: {par_bytes / 1e6:.1f} MB; "
                    f"{sum(res_static)} of {len(res_leaves)} leaves "
                    "matched). The ring buffers hold that much live PER "
                    "SLOT — if memory matters here, use "
                    "backward='recompute'.")
            rings["res"] = [
                jnp.zeros((slots,) + l.shape, l.dtype)
                for l, st in zip(res_leaves, res_static) if not st]
            rings["out_y"] = jnp.zeros((slots,) + mshape, micros.dtype)

        grads0 = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), local)
        hgrads0 = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                               head_params)
        dmicros0 = jnp.zeros_like(micros)
        loss0 = jnp.zeros((), jnp.float32)
        aux0 = jnp.zeros((), jnp.float32)
        send0 = (zero_m, zero_m)                # (fwd payload, bwd payload)

        down = [(i, i + 1) for i in range(pp - 1)]
        up = [(i + 1, i) for i in range(pp - 1)]

        def head_bwd(y, lab):
            loss, head_vjp = jax.vjp(
                lambda h, yy: loss_fn(h, yy, lab), head_params, y)
            dh, head_dy = head_vjp(scale.astype(loss.dtype))
            return loss, dh, head_dy

        def tick(carry, t):
            rings, grads, hgrads, dmicros, loss_acc, aux_acc, send = carry
            prev_y, prev_dx = send

            # -- receive what was sent last tick ------------------------------
            got_f = jax.lax.ppermute(prev_y, pipe_axis, down)
            # chain the second permute on the first: independent collectives
            # may be scheduled in different orders on different devices,
            # deadlocking the rendezvous (observed on the 8-device CPU
            # runtime); the zero-valued dependency forces a global order
            token = jnp.zeros((), prev_dx.dtype) * jnp.sum(got_f).astype(
                prev_dx.dtype)
            got_b = jax.lax.ppermute(prev_dx + token, pipe_axis, up)
            rf = rf_t[t, stage]
            rb = rb_t[t, stage]
            rings["in_act"] = jnp.where(
                rf >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    rings["in_act"], got_f, jnp.maximum(rf, 0) % slots, 0),
                rings["in_act"])
            rings["in_grad"] = jnp.where(
                rb >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    rings["in_grad"], got_b, jnp.maximum(rb, 0) % slots, 0),
                rings["in_grad"])

            # -- forward ------------------------------------------------------
            f_id = fwd_t[t, stage]
            f_on = f_id >= 0
            f_slot = jnp.maximum(f_id, 0) % slots
            x = jnp.where(stage == 0,
                          micros[jnp.maximum(f_id, 0)],
                          rings["in_act"][f_slot])
            f_extra = extra_of(f_id)
            if store_outputs:
                (y, f_aux), f_vjp = jax.vjp(
                    lambda p, xx: body(p, xx, f_extra), local, x)
                dyn = [l for l, st in zip(jax.tree.flatten(f_vjp)[0],
                                          res_static) if not st]
                rings["res"] = [
                    jnp.where(f_on,
                              jax.lax.dynamic_update_index_in_dim(
                                  r, l, f_slot, 0), r)
                    for r, l in zip(rings["res"], dyn)]
                rings["out_y"] = jnp.where(
                    f_on,
                    jax.lax.dynamic_update_index_in_dim(rings["out_y"], y,
                                                        f_slot, 0),
                    rings["out_y"])
            else:
                y, f_aux = body(local, x, f_extra)
                rings["saved_x"] = jnp.where(
                    f_on,
                    jax.lax.dynamic_update_index_in_dim(rings["saved_x"], x,
                                                        f_slot, 0),
                    rings["saved_x"])
            aux_acc = aux_acc + jnp.where(f_on, f_aux.astype(jnp.float32),
                                          0.0)

            # -- backward -----------------------------------------------------
            b_id = bwd_t[t, stage]
            b_on = b_id >= 0
            b_slot = jnp.maximum(b_id, 0) % slots
            lab = jax.tree.map(lambda L: L[jnp.maximum(b_id, 0)], labels)
            ring_dy = rings["in_grad"][b_slot]
            is_last = stage == pp - 1
            b_extra = extra_of(b_id)

            # executed UNCONDITIONALLY on every rank with where-selects: a
            # lax.cond here diverges by pipe rank, and any collective XLA
            # partitions into a branch would deadlock the rendezvous
            if store_outputs:
                yb = rings["out_y"][b_slot]
                lloss, dh, head_dy = head_bwd(yb, lab)
                dy = jnp.where(is_last, head_dy.astype(yb.dtype),
                               ring_dy.astype(yb.dtype))
                # interleave the live (tick-invariant) weight residuals
                # with the ring-buffered dynamic ones, in probe order
                ring_it = iter([r[b_slot] for r in rings["res"]])
                stat_it = iter(static_vals)
                res_now = [next(stat_it) if st else next(ring_it)
                           for st in res_static]
                b_vjp = jax.tree.unflatten(res_treedef, res_now)
                dp, dx = b_vjp((dy, aux_ct))
            else:
                xb = rings["saved_x"][b_slot]
                (y2, _aux2), stage_vjp = jax.vjp(
                    lambda p, xx: body(p, xx, b_extra), local, xb)
                lloss, dh, head_dy = head_bwd(y2, lab)
                dy = jnp.where(is_last, head_dy.astype(y2.dtype),
                               ring_dy.astype(y2.dtype))
                dp, dx = stage_vjp((dy, aux_ct))
            mask = b_on.astype(jnp.float32)
            last_f = is_last.astype(jnp.float32)
            grads = jax.tree.map(
                lambda g, d: g + mask * d.astype(jnp.float32), grads, dp)
            hgrads = jax.tree.map(
                lambda g, d: g + (mask * last_f) * d.astype(jnp.float32),
                hgrads, dh)
            loss_acc = loss_acc + jnp.where(b_on & is_last,
                                            lloss.astype(jnp.float32), 0.0)
            dx = dx.astype(micros.dtype)
            # stage 0's dx is the embedding cotangent
            dmicros = jnp.where(
                b_on & (stage == 0),
                jax.lax.dynamic_update_index_in_dim(
                    dmicros, dx, jnp.maximum(b_id, 0), 0),
                dmicros)

            send = (jnp.where(f_on, y, zero_m).astype(micros.dtype),
                    jnp.where(b_on, dx, zero_m))
            return (rings, grads, hgrads, dmicros, loss_acc, aux_acc,
                    send), None

        carry0 = (rings, grads0, hgrads0, dmicros0, loss0, aux0, send0)
        (rings, grads, hgrads, dmicros, loss_acc, aux_acc, _), _ = \
            jax.lax.scan(tick, carry0, jnp.arange(T))

        # loss + head grads live on the last stage; dmicros on stage 0; aux
        # accumulates per stage — psum replicates (the masks above zero the
        # other stages' terms)
        loss = jax.lax.psum(loss_acc, pipe_axis) / n_micro
        aux = jax.lax.psum(aux_acc, pipe_axis) / n_micro
        hgrads = jax.tree.map(
            lambda g: jax.lax.psum(g / n_micro, pipe_axis), hgrads)
        dmicros = jax.lax.psum(dmicros.astype(jnp.float32),
                               pipe_axis).astype(micros.dtype) / n_micro
        grads = jax.tree.map(lambda g: g[None] / n_micro, grads)
        return loss, aux, grads, hgrads, dmicros

    spec_params = jax.tree.map(lambda _: P(pipe_axis), stage_params)
    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_params, P(), P(), P(), P()),
        out_specs=(P(), P(), spec_params, P(), P()),
        axis_names={pipe_axis},
        check_vma=False)
    return mapped(stage_params, head_params, micros, labels, extras)
