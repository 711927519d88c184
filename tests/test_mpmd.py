"""MPMD pipeline placement (round 13): schedule/placement split, explicit
transfer channel, per-stage programs, one-stage elastic restart.

Parity strategy: the oracle is plain autodiff of the SAME parameters
through the non-pipelined model, plus MPMD-vs-SPMD legs over the same
schedule tables. The MPMD path itself never touches shard_map.
"""

import json
import os
import re
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from util import require_devices

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, causal_lm_loss
from deepspeed_tpu.models.pipeline import build_pipelined_model
from deepspeed_tpu.runtime.pipe.schedule import (
    BackwardPass, ForwardPass, LoadMicroBatch, RecvActivation, RecvGrad,
    SendActivation, SendGrad, TrainSchedule, build_1f1b_tables,
    build_gpipe_tables, build_tables, stage_instruction_stream)
from deepspeed_tpu.runtime.pipe.mpmd import (LocalChannel, MPMDPipeline,
                                             MPMDStageSupervisor,
                                             StageWorkerSpec,
                                             mpmd_value_and_grad)
from deepspeed_tpu.testing import chaos


# -- schedule layer: tables + instruction streams -----------------------------

def test_gpipe_tables_valid():
    """Full fill/drain then the backward wave: every micro forwards and
    backwards exactly once per stage, forwards strictly ordered down the
    pipe, backwards strictly ordered up it, and the in-flight bound is
    the GPipe regime (n_micro), not 1F1B's min(pp, m)."""
    for m, pp in [(4, 2), (8, 4), (3, 4), (6, 3)]:
        t = build_gpipe_tables(m, pp)
        fwd, bwd = t["fwd"], t["bwd"]
        for s in range(pp):
            assert sorted(x for x in fwd[:, s] if x >= 0) == list(range(m))
            assert sorted(x for x in bwd[:, s] if x >= 0) == list(range(m))
            inflight = np.cumsum(fwd[:, s] >= 0) - np.cumsum(bwd[:, s] >= 0)
            assert inflight.max() == m          # GPipe memory regime
        for s in range(1, pp):
            for f in range(m):
                assert int(np.where(fwd[:, s] == f)[0][0]) > \
                    int(np.where(fwd[:, s - 1] == f)[0][0])
                assert int(np.where(bwd[:, s - 1] == f)[0][0]) > \
                    int(np.where(bwd[:, s] == f)[0][0])


def test_build_tables_dispatch():
    t1 = build_tables("1f1b", 4, 2)
    t2 = build_tables("gpipe", 4, 2)
    assert t1["ticks"] <= t2["ticks"]       # 1f1b interleaves, gpipe waits
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        build_tables("zigzag", 4, 2)


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_instruction_stream_matches_schedule_vocabulary(schedule):
    """The per-stage instruction stream rendered from the clock tables
    carries the SAME instruction counts as the reference-API generator
    schedule — the schedule/placement split's contract: one schedule,
    two executions."""
    m, pp = 6, 3
    tables = build_tables(schedule, m, pp)
    for sid in range(pp):
        stream = stage_instruction_stream(tables, sid)
        flat = [c for tick in stream for c in tick]
        assert sum(isinstance(c, ForwardPass) for c in flat) == m
        assert sum(isinstance(c, BackwardPass) for c in flat) == m
        if sid == 0:
            assert sum(isinstance(c, LoadMicroBatch) for c in flat) == m
            assert not any(isinstance(c, (RecvActivation, SendGrad))
                           for c in flat)
        else:
            assert sum(isinstance(c, RecvActivation) for c in flat) == m
            assert sum(isinstance(c, SendGrad) for c in flat) == m
        if sid < pp - 1:
            assert sum(isinstance(c, SendActivation) for c in flat) == m
            assert sum(isinstance(c, RecvGrad) for c in flat) == m
        else:
            assert not any(isinstance(c, (SendActivation, RecvGrad))
                           for c in flat)
        # legacy generator agreement (1f1b == the reference TrainSchedule)
        if schedule == "1f1b":
            ref = [c for step in TrainSchedule(m, pp, sid) for c in step]
            for cls in (ForwardPass, BackwardPass, RecvActivation,
                        SendActivation, RecvGrad, SendGrad, LoadMicroBatch):
                assert sum(isinstance(c, cls) for c in flat) == \
                    sum(isinstance(c, cls) for c in ref), (sid, cls)


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_instruction_stream_send_recv_pairing(schedule):
    """Every send at tick t has its matching recv at tick t+1 on the
    neighbor — the one-tick transfer alignment both placements rely on."""
    m, pp = 5, 3
    tables = build_tables(schedule, m, pp)
    streams = [stage_instruction_stream(tables, s) for s in range(pp)]
    T = len(streams[0])
    for t in range(T):
        for s in range(pp):
            for c in streams[s][t]:
                if isinstance(c, SendActivation):
                    assert t + 1 < T
                    assert any(isinstance(r, RecvActivation)
                               and r.buffer_id == c.buffer_id
                               for r in streams[s + 1][t + 1])
                if isinstance(c, SendGrad):
                    assert any(isinstance(r, RecvGrad)
                               and r.buffer_id == c.buffer_id
                               for r in streams[s - 1][t + 1])


def test_spmd_executor_imports_tables_from_schedule_layer():
    """The placement split: one_f_one_b consumes the SAME table builder
    the schedule layer owns (a re-export, not a copy)."""
    from deepspeed_tpu.runtime.pipe import one_f_one_b, schedule
    assert one_f_one_b.build_1f1b_tables is schedule.build_1f1b_tables


# -- transfer channel ---------------------------------------------------------

def test_local_channel_fifo_and_schedule_violation():
    ch = LocalChannel()
    ch.send("act", 0, 1, 0, "a0")
    ch.send("act", 0, 1, 1, "a1")
    assert ch.recv("act", 1, 0) == "a0"
    with pytest.raises(RuntimeError, match="schedule violation"):
        ch.recv("act", 1, 2)                  # expected micro 2, queued 1
    ch.clear()
    from deepspeed_tpu.runtime.pipe.mpmd.channel import ChannelTimeout
    with pytest.raises(ChannelTimeout):
        ch.recv("act", 1, 0)


def test_local_channel_xfer_failpoint():
    ch = LocalChannel()
    chaos.arm("pipe.xfer", "raise", match="act:0->1")
    with pytest.raises(IOError):
        ch.send("act", 0, 1, 0, "x")
    # keyed: the grad edge is untouched
    ch.send("grad", 1, 0, 0, "g")
    assert chaos.fired("pipe.xfer") == ["pipe.xfer"]


# -- MPMD executor: parity oracles --------------------------------------------

def _toy_problem(pp=4, n_micro=6, mb=2, H=8):
    rng = np.random.RandomState(0)
    sp = {"w": jnp.asarray(rng.randn(pp, H, H) * 0.3, jnp.float32),
          "b": jnp.asarray(rng.randn(pp, H) * 0.1, jnp.float32)}
    head = {"v": jnp.asarray(rng.randn(H) * 0.5, jnp.float32)}
    micros = jnp.asarray(rng.randn(n_micro, mb, H), jnp.float32)
    labels = jnp.asarray(rng.randn(n_micro, mb), jnp.float32)

    def stage_fn(p, x, extra, stage):
        return jnp.tanh(x @ p["w"] + p["b"])

    def loss_fn(h, y, lab, ctx):
        return jnp.mean((y @ h["v"] - lab) ** 2)

    return sp, head, micros, labels, stage_fn, loss_fn


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_mpmd_executor_matches_autodiff(schedule):
    require_devices(4)
    """Loss + every grad (stage, head, dmicros) == plain autodiff of the
    stacked stages — the executor's correctness oracle, shard_map-free."""
    pp, n_micro = 4, 6
    sp, head, micros, labels, stage_fn, loss_fn = _toy_problem(pp, n_micro)

    def ref_loss(sp, hp, mi):
        def one(m, lab):
            x = m
            for s in range(pp):
                x = stage_fn(jax.tree.map(lambda a: a[s], sp), x, {}, s)
            return loss_fn(hp, x, lab, ())
        return jnp.mean(jax.vmap(one)(mi, labels))

    ref_l, (rgs, rgh, rgm) = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2))(sp, head, micros)
    loss, _aux, gs, gh, gm = mpmd_value_and_grad(
        stage_fn, loss_fn, sp, head, micros, labels,
        pp=pp, devices=jax.devices()[:pp], schedule=schedule)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-6)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(gs[k]), np.asarray(rgs[k]),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gh["v"]), np.asarray(rgh["v"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(rgm), rtol=1e-5,
                               atol=1e-6)


def test_mpmd_executor_matches_spmd_executor():
    require_devices(4)
    """Both placements of the SAME schedule tables produce the same loss
    and grads — the schedule/placement split's acceptance oracle."""
    from jax.sharding import Mesh
    from deepspeed_tpu.runtime.pipe.one_f_one_b import \
        pipeline_1f1b_value_and_grad
    pp, n_micro = 4, 6
    sp, head, micros, labels, stage_fn, loss_fn = _toy_problem(pp, n_micro)
    mesh = Mesh(np.asarray(jax.devices()[:pp]).reshape(pp), ("pipe",))
    l_s, _a, gs_s, gh_s, gm_s = jax.jit(
        lambda a, b, c, d: pipeline_1f1b_value_and_grad(
            stage_fn, lambda h, y, lab: loss_fn(h, y, lab, ()),
            a, b, c, d, mesh=mesh, pp=pp))(sp, head, micros, labels)
    l_m, _a2, gs_m, gh_m, gm_m = mpmd_value_and_grad(
        stage_fn, loss_fn, sp, head, micros, labels,
        pp=pp, devices=jax.devices()[:pp], schedule="1f1b")
    np.testing.assert_allclose(float(l_m), float(l_s), rtol=1e-6)
    for a, b in zip(jax.tree.leaves((gs_m, gh_m, gm_m)),
                    jax.tree.leaves((gs_s, gh_s, gm_s))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_mpmd_executor_xfer_failpoint_surfaces():
    require_devices(4)
    """An armed pipe.xfer fault in the in-process channel surfaces to the
    caller as the IOError it is — no silent wrong answer."""
    pp, n_micro = 2, 4
    sp, head, micros, labels, stage_fn, loss_fn = _toy_problem(pp, n_micro)
    chaos.arm("pipe.xfer", "raise")
    with pytest.raises(IOError):
        mpmd_value_and_grad(stage_fn, loss_fn,
                            jax.tree.map(lambda x: x[:pp], sp), head,
                            micros, labels, pp=pp,
                            devices=jax.devices()[:pp])


# -- model + engine integration -----------------------------------------------

def _tiny_kw(**over):
    kw = dict(hidden_size=64, num_layers=4, num_heads=4, vocab_size=256,
              max_seq_len=64, dtype=jnp.float32, attention_impl="reference")
    kw.update(over)
    return kw


def _mk_batch(rng, vocab, b, s):
    return {"input_ids": rng.integers(0, vocab, size=(b, s))}


def _mpmd_engine(piped, schedule="1f1b", loss_fn=causal_lm_loss,
                 extra_cfg=None, batch=None):
    config = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 4,
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
        "zero_optimization": {"stage": 0},
        "pipeline": {"stages": piped.pp, "schedule": schedule,
                     "placement": "mpmd"},
        "seed": 11,
    }
    if extra_cfg:
        config.update(extra_cfg)
    if batch is None:
        batch = _mk_batch(np.random.default_rng(2), 256, 16, 32)
    engine, *_ = ds.initialize(model=piped, config=config, loss_fn=loss_fn,
                               example_batch=batch,
                               rng=jax.random.PRNGKey(7))
    return engine


# tier-2 (round-19 budget sweep, ~11s): the cheaper tier-1 cousins are
# test_mpmd_executor_matches_autodiff (stage-graph value+grad parity,
# both schedules) and test_mpmd_engine_loss_parity_vs_spmd_pipeline_engine
# (model-level loss parity through the engine); scripts/tier2.sh runs this
@pytest.mark.slow
def test_mpmd_model_matches_plain_autodiff():
    require_devices(2)
    """pp=2 transformer through the MPMD placement: loss and every grad
    match plain autodiff of the same params through the non-pipelined
    model (identical param structure by construction)."""
    kw = _tiny_kw()
    plain, _ = build_model("gpt2-tiny", scan_layers=True, **kw)
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4, **kw)
    engine = _mpmd_engine(piped)
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(
        np.random.default_rng(5), 256, 16, 32).items()}
    params = engine.state.params
    l1, g1 = piped.mpmd_value_and_grad(params, batch, mesh=engine.mesh)
    l2, g2 = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        plain.apply({"params": p}, batch), batch)))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g1)[0],
            jax.tree_util.tree_flatten_with_path(g2)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=str(pa))


@pytest.mark.slow
def test_mpmd_engine_trains_and_8step_losses_match_plain_engine():
    require_devices(2)
    """Engine-level acceptance on shard_map-less hosts: 8 training steps
    under placement='mpmd' descend and track a NON-pipelined engine fed
    identical batches (same init, same gas) step for step.

    slow (round-14 budget sweep, 25s): the cheaper tier-1 cousins are
    test_mpmd_engine_loss_parity_vs_spmd_pipeline_engine (single-step
    loss parity) and test_two_process_mpmd_two_stage_run (engine e2e)."""
    kw = _tiny_kw()
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4, **kw)
    engine = _mpmd_engine(piped)
    plain, _ = build_model("gpt2-tiny", scan_layers=True, **kw)
    pconfig = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
        "zero_optimization": {"stage": 0},
        "seed": 11,
    }
    peng, *_ = ds.initialize(model=plain, config=pconfig,
                             loss_fn=causal_lm_loss,
                             example_batch=_mk_batch(
                                 np.random.default_rng(2), 256, 16, 32),
                             rng=jax.random.PRNGKey(7))
    mp_losses, pl_losses = [], []
    for i in range(8):
        b = _mk_batch(np.random.default_rng(60 + i), 256, 16, 32)
        mp_losses.append(float(engine.train_batch(b)["loss"]))
        pl_losses.append(float(peng.train_batch(b)["loss"]))
    assert mp_losses[-1] < mp_losses[0], mp_losses
    for i, (a, b) in enumerate(zip(mp_losses, pl_losses)):
        assert abs(a - b) < 2e-3, (i, a, b, mp_losses, pl_losses)


def test_mpmd_engine_loss_parity_vs_spmd_pipeline_engine():
    require_devices(2)
    """The acceptance leg verbatim: MPMD vs SPMD pipeline engines on the
    SAME 1f1b schedule, identical batches, >= 8 steps — per-step losses
    agree."""
    kw = _tiny_kw()

    def make(placement):
        piped, _ = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4, **kw)
        config = {
            "train_batch_size": 16,
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 4,
            "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
            "zero_optimization": {"stage": 0},
            "pipeline": {"stages": 2, "schedule": "1f1b",
                         "placement": placement},
            "seed": 11,
        }
        engine, *_ = ds.initialize(
            model=piped, config=config, loss_fn=causal_lm_loss,
            example_batch=_mk_batch(np.random.default_rng(2), 256, 16, 32),
            rng=jax.random.PRNGKey(7))
        return engine

    e_s, e_m = make("spmd"), make("mpmd")
    for i in range(8):
        b = _mk_batch(np.random.default_rng(70 + i), 256, 16, 32)
        ls = float(e_s.train_batch(b)["loss"])
        lm = float(e_m.train_batch(b)["loss"])
        assert abs(ls - lm) < 2e-4, (i, ls, lm)


@pytest.mark.slow
def test_mpmd_model_remat_matches_plain_autodiff():
    require_devices(2)
    """remat=True models run the MPMD placement unchanged (the fused
    per-stage backward IS the recompute regime) — values still match
    plain autodiff.

    slow (round-14 budget sweep, 13s): the cheaper tier-1 cousin is
    test_mpmd_executor_matches_autodiff (same parity regime, remat
    off, stage-graph level)."""
    kw = _tiny_kw(remat=True)
    plain, _ = build_model("gpt2-tiny", scan_layers=True, **kw)
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4, **kw)
    engine = _mpmd_engine(piped)
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(
        np.random.default_rng(5), 256, 16, 32).items()}
    params = engine.state.params
    l1, g1 = piped.mpmd_value_and_grad(params, batch, mesh=engine.mesh)
    l2, g2 = jax.jit(jax.value_and_grad(lambda p: causal_lm_loss(
        plain.apply({"params": p}, batch), batch)))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g1)[0],
            jax.tree_util.tree_flatten_with_path(g2)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg=str(pa))


@pytest.mark.slow
def test_mpmd_model_moe_aux_matches_plain_autodiff():
    # tier-2 (budget guardrail, ~22s): the dense-model parity twin
    # (test_mpmd_model_matches_plain_autodiff) and the executor aux
    # machinery stay tier-1; scripts/tier2.sh runs this variant
    require_devices(2)
    """with_aux through the MPMD placement: the MoE load-balance scalar
    rides the per-stage programs via its constant cotangent — loss AND
    grads match autodiff of the plain model under make_moe_loss. The
    oracle averages PER-MICRO losses (the pipeline's semantics — the
    load-balance term is nonlinear in batch composition, so a full-batch
    aux would legitimately differ)."""
    from deepspeed_tpu.models import make_moe_loss
    kw = _tiny_kw(moe_experts=2, moe_capacity_factor=2.0)
    plain, _ = build_model("gpt2-tiny", scan_layers=True, **kw)
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4, **kw)
    moe_loss = make_moe_loss(cfg.moe_aux_weight)
    engine = _mpmd_engine(piped, loss_fn=moe_loss)
    batch = {k: jnp.asarray(v) for k, v in _mk_batch(
        np.random.default_rng(5), 256, 16, 32).items()}
    params = engine.state.params
    l1, g1 = piped.mpmd_value_and_grad(params, batch, mesh=engine.mesh)

    def ref(p):
        losses = []
        for m in range(4):
            mb = {k: v.reshape((4, 4) + v.shape[1:])[m]
                  for k, v in batch.items()}
            losses.append(moe_loss(plain.apply({"params": p}, mb), mb))
        return sum(losses) / 4

    l2, g2 = jax.jit(jax.value_and_grad(ref))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-4)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(g1)[0],
            jax.tree_util.tree_flatten_with_path(g2)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3,
                                   atol=3e-4, err_msg=str(pa))


@pytest.mark.slow
def test_mpmd_fp16_loss_scaling_through_engine():
    # tier-2 (budget guardrail, ~14s): the f32 engine path
    # (test_mpmd_engine_trains_and_8step_losses_match_plain_engine)
    # keeps gating tier-1
    require_devices(2)
    """fp16 + MPMD: the dynamic scale seeds every per-stage backward as a
    traced argument (no per-step recompile), grads unscale in the shared
    finalize tail, training stays finite."""
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4,
                                       **_tiny_kw(dtype=jnp.float16))
    engine = _mpmd_engine(
        piped, extra_cfg={"fp16": {"enabled": True,
                                   "initial_scale_power": 8,
                                   "hysteresis": 1}})
    losses = []
    for i in range(4):
        b = _mk_batch(np.random.default_rng(20 + i), 256, 16, 32)
        losses.append(float(engine.train_batch(b)["loss"]))
    assert np.all(np.isfinite(losses)), losses


def test_mpmd_store_backward_refused():
    require_devices(2)
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4,
                                       backward="store", **_tiny_kw())
    engine = _mpmd_engine(piped)
    with pytest.raises(ValueError, match="recompute"):
        engine.train_batch(_mk_batch(np.random.default_rng(1), 256, 16, 32))


def test_unknown_placement_rejected():
    require_devices(2)
    piped, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=4,
                                       **_tiny_kw())
    with pytest.raises(ValueError, match="placement"):
        _mpmd_engine(piped, extra_cfg={
            "pipeline": {"stages": 2, "placement": "hybrid"}})


# -- cross-process: driver + stage workers ------------------------------------

def _collect_losses(log_path):
    losses = {}
    with open(log_path) as f:
        for m in re.finditer(r'mpmd_step: ({.*})', f.read()):
            d = json.loads(m.group(1))
            losses[d["step"]] = d["loss"]
    return losses


def _start_last_stage_first(sup):
    """Start the stages from the last to the first, each after the one
    downstream of it has said hello. The driver's router drops a frame
    whose destination is not connected (``driver._serve_conn``), so a
    stage 0 that reaches its first send before stage 1's hello loses
    micro 0's activation: both sit out the 60 s transfer barrier, stage 0
    exits 117 and the run ends rc 0 with ``restarts == [1, 0]``. Which
    worker wins is the machine's load (ROADMAP D13); this makes the wait
    explicit. No stage sends upstream before a frame from there reached
    it, so the other direction cannot race."""
    with mock.patch.object(sup, "_spawn"):
        sup.start()                 # the server and its router, no worker
    try:
        for s in reversed(range(sup.pp)):
            sup._spawn(s)
            deadline = time.monotonic() + 180.0
            while s not in sup.conns:
                assert sup.procs[s].poll() is None, f"stage {s} exited"
                assert time.monotonic() < deadline, f"stage {s}: no hello"
                time.sleep(0.02)
    except BaseException:
        sup._teardown()
        raise


def _run_driver(workdir, steps=6, specs=None, **kw):
    sup = MPMDStageSupervisor(2, workdir=os.path.join(workdir, "wd"),
                              steps=steps, n_micro=4, schedule="1f1b",
                              log_dir=os.path.join(workdir, "logs"),
                              specs=specs, **kw)
    _start_last_stage_first(sup)
    rc = sup.run()
    losses = _collect_losses(os.path.join(workdir, "logs", "stage1.log"))
    return rc, losses, sup


def test_two_process_mpmd_two_stage_run(tmp_path):
    """The cross-process reference path: two stage WORKER processes over
    the socket channel, per-stage checkpoints, rc 0, one loss per step."""
    rc, losses, sup = _run_driver(str(tmp_path), steps=4)
    assert rc == 0 and sup.restarts == [0, 0]
    assert set(losses) == set(range(4))
    # per-stage durable tags exist for every step (save_interval=1)
    for s in (0, 1):
        tags = os.listdir(os.path.join(str(tmp_path), "wd", f"stage{s}"))
        assert "global_step4" in tags


@pytest.mark.slow
def test_stage_kill_recovers_one_stage_with_loss_parity(tmp_path):
    """Acceptance: pipe.stage_kill takes out stage 1 at step 3; the
    driver restarts ONLY that stage (stage 0's process survives), the
    run completes rc 0, and the loss trajectory is IDENTICAL to an
    uninjected twin — no microbatch applied twice, none lost."""
    rc0, clean, sup0 = _run_driver(str(tmp_path / "clean"), steps=8)
    assert rc0 == 0
    specs = [StageWorkerSpec(),
             StageWorkerSpec(env_first={
                 "DSTPU_CHAOS": "pipe.stage_kill:kill:skip=3"})]
    rc1, injected, sup = _run_driver(str(tmp_path / "chaos"), steps=8,
                                     specs=specs)
    assert rc1 == 0
    assert sup.restarts == [0, 1], sup.restarts      # ONLY stage 1
    assert set(injected) == set(range(8))
    for k in clean:
        assert abs(clean[k] - injected[k]) < 1e-9, (k, clean, injected)


@pytest.mark.slow
def test_xfer_fault_recovers_with_loss_parity(tmp_path):
    """A transfer fault (pipe.xfer raise on stage 0's send) is a counted
    crash: one-stage restart, full-run loss parity with the clean twin."""
    rc0, clean, _ = _run_driver(str(tmp_path / "clean"), steps=8)
    specs = [StageWorkerSpec(env_first={
                 "DSTPU_CHAOS": "pipe.xfer:raise:skip=5"}),
             StageWorkerSpec()]
    rc1, injected, sup = _run_driver(str(tmp_path / "chaos"), steps=8,
                                     specs=specs)
    assert rc0 == 0 and rc1 == 0
    assert sup.restarts == [1, 0]
    for k in clean:
        assert abs(clean[k] - injected[k]) < 1e-9, (k, clean, injected)


@pytest.mark.slow
def test_stage_hang_watchdog_117_then_recovery(tmp_path):
    """A WEDGED stage (pipe.stage_kill:hang) is caught by the in-worker
    StallWatchdog (rc 117, STALLED heartbeat), counted, restarted — the
    run still completes with clean-twin loss parity. The rc 117 leg of
    the contract, end to end."""
    rc0, clean, _ = _run_driver(str(tmp_path / "clean"), steps=8)
    hbdir = str(tmp_path / "hb")
    specs = [StageWorkerSpec(),
             StageWorkerSpec(env_first={
                 "DSTPU_CHAOS": "pipe.stage_kill:hang:skip=3"})]
    rc1, injected, sup = _run_driver(
        str(tmp_path / "chaos"), steps=8, specs=specs,
        heartbeat_dir=hbdir, worker_args=["--stall-timeout", "3"])
    assert rc0 == 0 and rc1 == 0
    assert sup.restarts == [0, 1]
    for k in clean:
        assert abs(clean[k] - injected[k]) < 1e-9, (k, clean, injected)
    # the heartbeat channel carries STAGE-tagged records (dstpu health's
    # STAGE column reads exactly this gauge)
    from deepspeed_tpu.runtime import heartbeat as hb
    recs = hb.read_heartbeats(hbdir)
    assert recs and all(r.get("gauges", {}).get("stage") == r["rank"]
                        for r in recs.values())


@pytest.mark.slow
def test_restart_budget_exhausted_propagates_rc(tmp_path):
    """max_restarts=0: the first counted death tears the world down and
    the chaos kill's exit code survives aggregation (the rc contract is
    preserved upward, like RunSupervisor's)."""
    specs = [StageWorkerSpec(),
             StageWorkerSpec(env={  # re-arms every restart: always fatal
                 "DSTPU_CHAOS": "pipe.stage_kill:kill:skip=1"})]
    rc, _losses, sup = _run_driver(str(tmp_path), steps=6, specs=specs,
                                   max_restarts=0)
    assert rc == chaos.KILL_EXIT_CODE


def test_stageconn_send_raises_when_write_lock_starved():
    """Regression (TPU017 sweep): a peer wedged mid-read used to keep
    the per-connection write lock — and every later sender (welcome,
    broadcast) — stuck forever. A starved writer now fails like a dead
    peer, which every caller already handles."""
    import socket
    import time
    from deepspeed_tpu.runtime.pipe.mpmd.driver import _StageConn

    a, b = socket.socketpair()
    try:
        conn = _StageConn(a, 0)
        conn.wlock.acquire()            # the wedged sender
        try:
            t0 = time.monotonic()
            with pytest.raises(OSError, match="starved"):
                conn.send({"cmd": "ping"}, lock_timeout=0.05)
            assert time.monotonic() - t0 < 2
        finally:
            conn.wlock.release()
        conn.send({"cmd": "ping"}, lock_timeout=0.05)   # lock free: sends
    finally:
        a.close()
        b.close()


def test_socket_channel_send_raises_when_write_lock_starved():
    """Same contract on the worker side: the frame lock — now owned by
    the fabric endpoint the channel rides (round 18) — is bounded, and
    starvation surfaces as the OSError a dead driver socket raises."""
    import socket
    import threading as _th
    from collections import deque

    from deepspeed_tpu.runtime.fabric import SocketEndpoint
    from deepspeed_tpu.runtime.pipe.mpmd.channel import SocketChannel

    a, b = socket.socketpair()
    try:
        ep = SocketEndpoint.__new__(SocketEndpoint)
        ep.ident = "stage-0"
        ep._sock = a
        ep._wlock = _th.Lock()
        ep._redial = None
        ep._closed = False
        ep.generation = 0
        ch = SocketChannel.__new__(SocketChannel)
        ch.stage = 0
        ch._ep = ep
        ch._data = {}
        ch._control = deque()
        ep._wlock.acquire()
        try:
            with pytest.raises(OSError, match="starved"):
                ch.send_control({"cmd": "parked"}, lock_timeout=0.05)
            with pytest.raises(OSError, match="starved"):
                ch.send("act", 0, 1, 0, np.zeros(2, np.float32),
                        lock_timeout=0.05)
        finally:
            ep._wlock.release()
        ch.send_control({"cmd": "parked"}, lock_timeout=0.05)
    finally:
        a.close()
        b.close()
