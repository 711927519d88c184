"""MPMD pipeline executor — per-stage jit programs over explicit transfers.

The SPMD 1F1B executor (``..one_f_one_b``) compiles ONE stacked-stage
program over the 'pipe' mesh axis; this module is the same *schedule*
under the other *placement*: each stage owns its own jit-compiled
forward and fused forward+backward programs on its own submesh, and a
host-side interpreter walks :func:`..schedule.stage_instruction_stream`
tick by tick, moving activations and cotangents through an explicit
:mod:`channel`. Nothing here touches ``shard_map`` or collectives — a
stage program only ever sees its own devices, which is exactly why a
stage can die, recompile, and rejoin alone (driver.py).

Numerical contract: identical accumulation ORDER to the SPMD 1F1B
executor — grads and the last-stage loss accumulate in backward-table
tick order, the aux side channel in forward-table order — so the two
placements are loss-parity-testable against each other (and against
plain autodiff of the stacked stages; tests/test_mpmd.py pins both).
Backward is the recompute regime (the fused per-stage program re-runs
the stage body under ``jax.vjp`` from the saved boundary input — the
SPMD executor's default); the SPMD-only ``store`` residual-ring mode is
refused loudly at the engine seam.

Dispatch is host-sequential but execution is not: jax dispatch is
async, and stage programs live on disjoint devices, so downstream ticks
overlap upstream ones exactly as the clock tables intend.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..schedule import (BackwardPass, ForwardPass, LoadMicroBatch,
                        RecvActivation, RecvGrad, SendActivation, SendGrad,
                        build_tables, stage_instruction_stream)
from .channel import LocalChannel

PyTree = Any


def stage_submeshes(mesh: Mesh, pp: int, pipe_axis: str = "pipe"
                    ) -> List[Mesh]:
    """Split a global mesh along its pipe axis into one submesh per stage
    (the remaining axes survive, so intra-stage dp/tp device sets are
    preserved)."""
    names = list(mesh.axis_names)
    if pipe_axis not in names:
        raise ValueError(f"mesh {names} has no '{pipe_axis}' axis")
    i = names.index(pipe_axis)
    if mesh.devices.shape[i] != pp:
        raise ValueError(f"mesh '{pipe_axis}' axis is "
                         f"{mesh.devices.shape[i]}, expected pp={pp}")
    rest = [n for n in names if n != pipe_axis]
    subs = []
    for s in range(pp):
        dev = np.take(mesh.devices, s, axis=i)
        if not rest:
            subs.append(Mesh(dev.reshape(1), ("stage",)))
        else:
            subs.append(Mesh(dev, tuple(rest)))
    return subs


def build_stage_programs(stage_fn: Callable, loss_fn: Optional[Callable],
                         stage: int, pp: int, with_aux: bool = False
                         ) -> Dict[str, Any]:
    """The three compiled programs ONE stage needs — shared by the
    in-process executor and the cross-process stage worker, so both
    placements run byte-identical per-stage math.

    fwd(p, x, extra) -> (y, aux)
    bwd(p, x, extra, dy, aux_ct, acc) -> (acc', dx)           mid stages
    last_bwd(p, head_p, x, extra, lab, ctx, scale, aux_ct,
             acc, hacc, lacc) -> (acc', hacc', lacc', dx)     last stage

    Backward is the fused recompute regime: the stage body re-runs under
    ``jax.vjp`` from the saved boundary input (the SPMD executor's
    default mode), so nothing but [mb, ...] boundaries is ever stored
    between ticks.
    """
    from ....comm_plan.runtime import local_region
    f32 = jnp.float32
    if with_aux:
        def body(p, x, e):
            return stage_fn(p, x, e, stage)
    else:
        def body(p, x, e):
            return stage_fn(p, x, e, stage), jnp.zeros((), f32)

    # every program traces under local_region: a stage program is by
    # definition shard-LOCAL, so the model's global-mesh
    # _spec_constraint sites must no-op (the submesh is not the mesh
    # those specs name) — same seam the comm-plan unreduced trace uses
    def fwd(p, x, extra):
        with local_region():
            return body(p, x, extra)

    def bwd(p, x, extra, dy, aux_ct, acc):
        with local_region():
            (y, _aux), vjp = jax.vjp(
                lambda pl, xl: body(pl, xl, extra), p, x)
            dp, dx = vjp((dy.astype(y.dtype), aux_ct))
        acc = jax.tree.map(lambda a, d: a + d.astype(f32), acc, dp)
        return acc, dx.astype(x.dtype)

    progs = {"fwd": jax.jit(fwd), "bwd": jax.jit(bwd, donate_argnums=(5,)),
             "last_bwd": None}
    if stage == pp - 1 and loss_fn is not None:
        def last_bwd(p, head_p, x, extra, lab, ctx, scale, aux_ct,
                     acc, hacc, lacc):
            with local_region():
                (y, _aux), vjp = jax.vjp(
                    lambda pl, xl: body(pl, xl, extra), p, x)
                loss, hvjp = jax.vjp(
                    lambda h, yy: loss_fn(h, yy, lab, ctx), head_p, y)
                dh, head_dy = hvjp(scale.astype(loss.dtype))
                dp, dx = vjp((head_dy.astype(y.dtype), aux_ct))
            acc = jax.tree.map(lambda a, d: a + d.astype(f32), acc, dp)
            hacc = jax.tree.map(lambda a, d: a + d.astype(f32), hacc, dh)
            lacc = lacc + loss.astype(f32)
            return acc, hacc, lacc, dx.astype(x.dtype)
        progs["last_bwd"] = jax.jit(last_bwd, donate_argnums=(8, 9, 10))
    return progs


class MPMDPipeline:
    """Per-stage programs for one (stage_fn, loss_fn) pipeline.

    Built ONCE and reused across steps — the per-stage jits are cached on
    the instance, so a training loop pays compile exactly once per stage.

    stage_fn(one_stage_params, x, extra, stage_idx) -> y  (or (y, aux)
        when ``with_aux``) — the same body contract as both SPMD
        executors.
    loss_fn(head_params, y, labels_micro, ctx) -> scalar — LAST stage
        only. ``ctx`` is a per-call traced pytree (e.g. the global
        valid-token count) so batch-dependent loss constants never bake
        into the trace.
    devices: explicit one-device-per-stage placement (toy/tests);
    mesh: a global mesh with a '{pipe_axis}' axis of size pp (engine) —
        exactly one of the two.
    Payloads within a stage are replicated over its submesh (the
    CPU-testable reference placement; intra-stage sharded transfers ride
    the same channel seam later).
    """

    def __init__(self, stage_fn: Callable, loss_fn: Callable, *,
                 pp: int, schedule: str = "1f1b",
                 mesh: Optional[Mesh] = None,
                 devices: Optional[List] = None,
                 pipe_axis: str = "pipe",
                 with_aux: bool = False,
                 channel=None,
                 recv_timeout: Optional[float] = None):
        if (mesh is None) == (devices is None):
            raise ValueError("pass exactly one of mesh= or devices=")
        if devices is not None:
            if len(devices) != pp:
                raise ValueError(f"{len(devices)} devices for pp={pp}")
            self.submeshes = [Mesh(np.asarray([d]), ("stage",))
                              for d in devices]
        else:
            self.submeshes = stage_submeshes(mesh, pp, pipe_axis)
        self.pp = pp
        self.schedule = schedule
        self.with_aux = with_aux
        self.recv_timeout = recv_timeout
        self.placements = {s: NamedSharding(self.submeshes[s], P())
                           for s in range(pp)}
        self.channel = channel if channel is not None else LocalChannel(
            placements=self.placements)
        self._stage_fn = stage_fn
        self._loss_fn = loss_fn
        self._streams: Dict[Tuple[int, str], list] = {}
        self._progs = [build_stage_programs(stage_fn, loss_fn, s, pp,
                                            with_aux=with_aux)
                       for s in range(pp)]
        self._fwd = [p["fwd"] for p in self._progs]
        self._bwd = [p["bwd"] for p in self._progs]
        self._last_bwd = self._progs[pp - 1]["last_bwd"]

    # ---------------------------------------------------------------- helpers

    def _stream(self, n_micro: int):
        key = (n_micro, self.schedule)
        if key not in self._streams:
            tables = build_tables(self.schedule, n_micro, self.pp)
            self._streams[key] = [stage_instruction_stream(tables, s)
                                  for s in range(self.pp)]
        return self._streams[key]

    def _place(self, s: int, tree):
        return jax.tree.map(
            lambda x: jax.device_put(x, self.placements[s]), tree)

    # ------------------------------------------------------------------- step

    def value_and_grad(self, stage_params: PyTree, head_params: PyTree,
                       micros, labels, *,
                       extras: Optional[PyTree] = None,
                       loss_ctx: PyTree = (),
                       aux_cotangent: float = 0.0,
                       loss_scale=None):
        """One full pipeline step under the built schedule. Same contract
        as ``pipeline_1f1b_value_and_grad``: returns (mean task loss,
        mean aux, stage grads [pp, ...], head grads, dmicros) — grads
        SCALED when ``loss_scale`` seeds the backward."""
        pp = self.pp
        n_micro = int(micros.shape[0])
        streams = self._stream(n_micro)
        extras = {} if extras is None else extras
        f32 = jnp.float32

        scale_f = (1.0 if loss_scale is None
                   else float(jax.device_get(loss_scale)))
        aux_ct_f = float(aux_cotangent) * scale_f

        local = [self._place(s, jax.tree.map(lambda x, s=s: x[s],
                                             stage_params))
                 for s in range(pp)]
        head_local = self._place(pp - 1, head_params)
        extras_s = [self._place(s, extras) for s in range(pp)]
        labels_last = self._place(pp - 1, labels)
        ctx_last = self._place(pp - 1, loss_ctx)
        scale = jnp.asarray(scale_f, f32)
        aux_ct = jnp.asarray(aux_ct_f, f32)

        acc = [jax.tree.map(lambda x: jnp.zeros(x.shape, f32), loc)
               for loc in local]
        acc = [self._place(s, a) for s, a in enumerate(acc)]
        hacc = self._place(pp - 1, jax.tree.map(
            lambda x: jnp.zeros(x.shape, f32), head_local))
        lacc = self._place(pp - 1, jnp.zeros((), f32))
        aux_acc = [self._place(s, jnp.zeros((), f32)) for s in range(pp)]

        in_act: List[Dict[int, Any]] = [dict() for _ in range(pp)]
        in_grad: List[Dict[int, Any]] = [dict() for _ in range(pp)]
        saved_x: List[Dict[int, Any]] = [dict() for _ in range(pp)]
        out_y: List[Dict[int, Any]] = [dict() for _ in range(pp)]
        out_dx: List[Dict[int, Any]] = [dict() for _ in range(pp)]
        dmicros: Dict[int, Any] = {}

        def extra_of(s, mid):
            return jax.tree.map(lambda e: e[mid], extras_s[s])

        T = len(streams[0])
        ch = self.channel
        for t in range(T):
            for s in range(pp):
                for inst in streams[s][t]:
                    mid = inst.buffer_id
                    if isinstance(inst, RecvActivation):
                        in_act[s][mid] = ch.recv(
                            "act", s, mid, timeout=self.recv_timeout)
                    elif isinstance(inst, RecvGrad):
                        in_grad[s][mid] = ch.recv(
                            "grad", s, mid, timeout=self.recv_timeout)
                    elif isinstance(inst, LoadMicroBatch):
                        in_act[s][mid] = jax.device_put(
                            micros[mid], self.placements[s])
                    elif isinstance(inst, ForwardPass):
                        x = in_act[s].pop(mid)
                        saved_x[s][mid] = x
                        if s == pp - 1 and not self.with_aux:
                            # the fused last_bwd recomputes this body
                            # anyway and no aux rides the fwd tick —
                            # dispatching the forward here would be pure
                            # double compute on the critical-path stage
                            continue
                        y, aux = self._fwd[s](local[s], x, extra_of(s, mid))
                        aux_acc[s] = aux_acc[s] + aux
                        if s < pp - 1:
                            out_y[s][mid] = y
                    elif isinstance(inst, SendActivation):
                        ch.send("act", s, s + 1, mid, out_y[s].pop(mid))
                    elif isinstance(inst, BackwardPass):
                        xb = saved_x[s].pop(mid)
                        if s == pp - 1:
                            acc[s], hacc, lacc, dx = self._last_bwd(
                                local[s], head_local, xb, extra_of(s, mid),
                                jax.tree.map(lambda L: L[mid], labels_last),
                                ctx_last, scale, aux_ct,
                                acc[s], hacc, lacc)
                        else:
                            dy = in_grad[s].pop(mid)
                            acc[s], dx = self._bwd[s](
                                local[s], xb, extra_of(s, mid), dy, aux_ct,
                                acc[s])
                        if s == 0:
                            dmicros[mid] = dx
                        else:
                            out_dx[s][mid] = dx
                    elif isinstance(inst, SendGrad):
                        ch.send("grad", s, s - 1, mid, out_dx[s].pop(mid))

        # -- outputs (the host-bounce gather: per-stage results re-assemble
        # on host — the reference-path analogue of the SPMD psum tail)
        loss = jnp.asarray(jax.device_get(lacc), f32) / n_micro
        aux = sum(float(jax.device_get(a)) for a in aux_acc) / n_micro
        aux = jnp.asarray(aux, f32)
        grads = _stack_stage_trees([jax.device_get(a) for a in acc])
        grads = jax.tree.map(lambda g: jnp.asarray(g) / n_micro, grads)
        hgrads = jax.tree.map(lambda g: jnp.asarray(jax.device_get(g))
                              / n_micro, hacc)
        dm = np.stack([np.asarray(jax.device_get(dmicros[m]))
                       for m in range(n_micro)])
        dm = jnp.asarray(dm).astype(micros.dtype) / n_micro
        return loss, aux, grads, hgrads, dm


def _stack_stage_trees(per_stage: List[PyTree]) -> PyTree:
    """[tree_of_stage_0, ...] -> tree with a leading [pp] dim per leaf."""
    leaves0, treedef = jax.tree.flatten(per_stage[0])
    stacked = []
    for i in range(len(leaves0)):
        stacked.append(np.stack(
            [np.asarray(jax.tree.leaves(t)[i]) for t in per_stage]))
    return jax.tree.unflatten(treedef, stacked)


def mpmd_value_and_grad(stage_fn: Callable, loss_fn: Callable,
                        stage_params: PyTree, head_params: PyTree,
                        micros, labels, *,
                        pp: int,
                        mesh: Optional[Mesh] = None,
                        devices: Optional[List] = None,
                        schedule: str = "1f1b",
                        pipe_axis: str = "pipe",
                        extras: Optional[PyTree] = None,
                        with_aux: bool = False,
                        aux_cotangent: float = 0.0,
                        loss_scale=None,
                        loss_ctx: PyTree = (),
                        channel=None):
    """One-shot functional wrapper (tests, parity oracles): builds an
    :class:`MPMDPipeline` and runs a single step. Training loops should
    hold the pipeline object instead — it caches the per-stage compiles.
    """
    pipe = MPMDPipeline(stage_fn, loss_fn, pp=pp, schedule=schedule,
                        mesh=mesh, devices=devices, pipe_axis=pipe_axis,
                        with_aux=with_aux, channel=channel)
    return pipe.value_and_grad(stage_params, head_params, micros, labels,
                               extras=extras, loss_ctx=loss_ctx,
                               aux_cotangent=aux_cotangent,
                               loss_scale=loss_scale)
