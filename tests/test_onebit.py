"""1-bit optimizer tests: explicit-collective mode wire-byte accounting,
warmup parity with exact Adam, convergence through the freeze transition,
and the real OneBitLamb (vs the round-1 silent lamb alias).

Mirrors the reference's tests/unit/test_onebit.py (TestOneBitAdamBasic /
TestOneBitLambBasic) plus a wire-byte audit the reference can't do (we parse
the compiled HLO's collective ops).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from util import require_devices


@pytest.fixture(autouse=True)
def _multidevice():
    """This module's features are inherently multi-device (virtual CPU mesh
    in the default suite); skip on platforms with fewer devices."""
    require_devices(8)


import deepspeed_tpu as ds
from deepspeed_tpu.runtime.onebit import hlo_collective_bytes

from util import SimpleModel, random_batch


def _onebit_config(opt_type="OneBitAdam", freeze_step=4, lr=1e-2):
    return {
        "train_batch_size": 16,
        "optimizer": {"type": opt_type,
                      "params": {"lr": lr, "freeze_step": freeze_step,
                                 "weight_decay": 0.01}},
        "seed": 7,
    }


def _make(opt_type="OneBitAdam", freeze_step=4, **kw):
    engine, *_ = ds.initialize(model=SimpleModel(), example_batch=random_batch(16),
                               config=_onebit_config(opt_type, freeze_step, **kw))
    return engine


def test_onebit_engine_explicit_mode_active():
    engine = _make()
    assert engine.onebit is not None
    assert engine.onebit.n == 8


def test_onebit_adam_warmup_matches_exact_adam():
    """During warmup the explicit-collective path is exact (uncompressed)
    Adam without bias correction — losses must track a same-hyper reference
    run step for step."""
    e1 = _make("OneBitAdam", freeze_step=1000)
    cfg = _onebit_config("Adam")
    cfg["optimizer"]["params"].pop("freeze_step")
    cfg["optimizer"]["params"]["bias_correction"] = False
    # 1-bit Adam's weight decay is decoupled (reference onebit/adam.py adds
    # wd*p to the update); match it
    cfg["optimizer"]["params"]["adamw_mode"] = True
    e2, *_ = ds.initialize(model=SimpleModel(), example_batch=random_batch(16),
                           config=cfg)
    for i in range(4):
        b = random_batch(16, seed=i)
        l1 = float(e1.train_batch(b)["loss"])
        l2 = float(e2.train_batch(b)["loss"])
        assert abs(l1 - l2) < 3e-3, (i, l1, l2)


@pytest.mark.slow
def test_onebit_adam_trains_through_freeze():
    """Warmup long enough for v to stabilize (the algorithm's intended regime
    — reference docs put freeze at 15-25% of total steps), then the
    compressed stage must keep training without blowup."""
    engine = _make("OneBitAdam", freeze_step=12, lr=2e-3)
    losses = [float(engine.train_batch(random_batch(16, seed=i))["loss"])
              for i in range(36)]
    assert np.mean(losses[-5:]) < losses[0]
    assert all(np.isfinite(losses)), losses
    # the compressed stage must actually run
    assert engine.onebit._step_frozen is not None
    assert engine.onebit._step_warm is not None


@pytest.mark.slow
def test_onebit_lamb_trains_through_freeze():
    engine = _make("OneBitLamb", freeze_step=12, lr=1e-2)
    losses = [float(engine.train_batch(random_batch(16, seed=i))["loss"])
              for i in range(36)]
    assert np.mean(losses[-5:]) < losses[0]
    assert all(np.isfinite(losses)), losses
    assert engine.onebit._step_frozen is not None


def test_onebit_wire_bytes_compressed():
    """The compression-stage step must move far fewer collective bytes than
    the warmup step (which allreduces f32 grads): the 1-bit exchange carries
    packed sign bits + scales. Audited from the optimized HLO."""
    engine = _make("OneBitAdam", freeze_step=5)
    micros = jax.tree.map(
        lambda x: jnp.asarray(x)[None], random_batch(16))
    rng = jax.random.PRNGKey(0)
    params = engine.state.params
    state = engine.state.opt_state["onebit"]
    runner = engine.onebit

    def bytes_for(frozen):
        from deepspeed_tpu.runtime.loss_scaler import LossScaleState
        fn = runner._build(frozen)
        lowered = fn.lower(params, state, micros, rng,
                           jnp.asarray(1e-2, jnp.float32),
                           LossScaleState.identity())
        return hlo_collective_bytes(lowered.compile().as_text())

    warm = bytes_for(False)
    frozen = bytes_for(True)
    assert warm > 0 and frozen > 0
    # sign-bit traffic alone is 1/32 of f32; scales/loss/norm overhead means
    # the end-to-end step must still be >=6x cheaper on the wire
    assert frozen * 6 <= warm, (warm, frozen)


def test_onebit_rejects_zero_stage():
    cfg = _onebit_config()
    cfg["zero_optimization"] = {"stage": 2}
    with pytest.raises(ValueError, match="ZeRO"):
        ds.initialize(model=SimpleModel(), example_batch=random_batch(16),
                      config=cfg)


def test_onebit_lamb_numeric_dp1():
    """The functional onebit_lamb (dp=1 numeric form) must run both stages
    and differ from plain lamb after freeze (the round-1 alias bug)."""
    from deepspeed_tpu.ops.optimizers import build_optimizer, lamb
    ob = build_optimizer("OneBitLamb", {"lr": 1e-2, "freeze_step": 3})
    pl = lamb(lr=1e-2)
    assert ob.name == "onebitlamb"
    params = {"w": jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)}
    s_ob, s_pl = ob.init(params), pl.init(params)
    p_ob = p_pl = params
    diverged = False
    for step in range(8):
        g = {"w": jnp.asarray(np.random.RandomState(step + 1).randn(64),
                              jnp.float32)}
        p_ob, s_ob = ob.update(g, s_ob, p_ob, jnp.asarray(step, jnp.int32))
        p_pl, s_pl = pl.update(g, s_pl, p_pl, jnp.asarray(step, jnp.int32))
        if step >= 3 and not np.allclose(np.asarray(p_ob["w"]),
                                         np.asarray(p_pl["w"]), atol=1e-6):
            diverged = True
    assert diverged, "onebit_lamb behaved identically to plain lamb"
    assert np.all(np.isfinite(np.asarray(p_ob["w"])))


def test_quantized_gather_fwd_bwd_parity():
    """ZeRO++-style qwZ: the int8 quantized weight gather reconstructs the
    full tensor within int8 tolerance; its custom-vjp backward is the exact
    zero-communication shard slice (STE through the quantization)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.runtime.comm.compressed import make_quantized_gather

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("data",))
    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 16)).astype(np.float32)
    w_sh = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P("data", None)))

    qg = make_quantized_gather(mesh, "data", dim=0)
    # forward: int8-accurate reconstruction (per-shard scale, 127 levels)
    full = jax.jit(qg)(w_sh)
    assert full.shape == w.shape
    per_shard_tol = np.abs(w).reshape(4, 2, 16).max(axis=(1, 2)) / 127.0
    err = np.abs(np.asarray(full) - w).reshape(4, -1).max(axis=1)
    assert (err <= per_shard_tol * 1.01).all()

    # backward: STE through the quantization — d/dw sum(full * c) is exactly
    # each shard's slice of c (the cotangent is already globally reduced at
    # this seam; gradient-side quantization lives in quantized_allreduce)
    c = rng.standard_normal((8, 16)).astype(np.float32)
    g = jax.jit(jax.grad(lambda x: jnp.sum(qg(x) * jnp.asarray(c))))(w_sh)
    np.testing.assert_allclose(np.asarray(g), c, rtol=0, atol=1e-6)

    # wire audit: the gather in the compiled forward moves int8, not f32
    txt = jax.jit(qg).lower(w_sh).compile().as_text()
    assert "all-gather" in txt and "s8" in txt


def test_hierarchical_quantized_allreduce():
    """Two-level scheme: exact psum over the intra (ICI) axis, int8
    error-feedback exchange over the inter (DCN) axis — result converges to
    the plain mean as error feedback accumulates."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.runtime.comm.compressed import (
        hierarchical_quantized_allreduce)

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("inter", "intra"))
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((8, 64)).astype(np.float32)
    x = jax.device_put(jnp.asarray(vals),
                       NamedSharding(mesh, P(("inter", "intra"))))
    err = jax.device_put(jnp.zeros((2, 64), jnp.float32),
                         NamedSharding(mesh, P("inter")))
    want = vals.mean(axis=0)
    out, err = hierarchical_quantized_allreduce(
        x, err, mesh=mesh, intra_axis="intra", inter_axis="inter")
    # single shot: int8-accurate
    np.testing.assert_allclose(np.asarray(out), want, atol=np.abs(
        want).max() / 127 * 3 + 1e-6)
    # repeated same-input rounds: worker error feedback compensates the
    # chunk-exchange quantization; what remains is the (feedback-free)
    # server-side re-quant, bounded by one int8 step of the served mean
    for _ in range(4):
        out, err = hierarchical_quantized_allreduce(
            x, err, mesh=mesh, intra_axis="intra", inter_axis="inter")
    server_step = np.abs(want).max() / 127.0
    np.testing.assert_allclose(np.asarray(out), want,
                               atol=2 * server_step + 1e-6)


@pytest.mark.slow
def test_onebit_fp16_loss_scaling_composes():
    """onebit + fp16 dynamic loss scaling (the reference default envelope:
    onebit/adam.py:11 runs under FP16_Optimizer): trains through the freeze
    transition, and an overflow batch skips the step and halves the scale."""
    cfg = _onebit_config("OneBitAdam", freeze_step=12, lr=2e-3)
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 8, "hysteresis": 1}
    engine, *_ = ds.initialize(model=SimpleModel(),
                               example_batch=random_batch(16), config=cfg)
    assert engine.onebit is not None and engine.onebit.loss_scaler.enabled
    losses = [float(engine.train_batch(random_batch(16, seed=i))["loss"])
              for i in range(20)]
    assert engine.onebit._step_frozen is not None   # compressed stage ran
    assert np.mean(losses[-4:]) < losses[0]

    # overflow: huge inputs blow up the fp16 backward
    scale_before = float(jax.device_get(engine.state.scale.scale))
    p_before = jax.tree.map(np.asarray, jax.device_get(engine.state.params))
    bad = random_batch(16, seed=99)
    bad["x"] = (bad["x"] * 1e30).astype(np.float32)
    m = engine.train_batch(bad)
    assert bool(m["overflow"]) is True
    assert m["loss_scale"] <= scale_before / 2
    p_after = jax.tree.map(np.asarray, jax.device_get(engine.state.params))
    for a, b in zip(jax.tree.leaves(p_before), jax.tree.leaves(p_after)):
        np.testing.assert_array_equal(a, b)   # step skipped: params untouched

    # recovery: training continues after the skip
    m2 = engine.train_batch(random_batch(16, seed=100))
    assert np.isfinite(float(m2["loss"])) and not bool(m2["overflow"])


# tier-2 (round-19 budget sweep, ~6s): the cheaper tier-1 cousins are
# test_onebit_adam_warmup_matches_exact_adam (optimizer math) and
# test_onebit_lamb_numeric_dp1 (sharded-state numerics);
# scripts/tier2.sh runs this ZeRO-1 composition leg
@pytest.mark.slow
def test_onebit_zero1_composes():
    """onebit + ZeRO-1: optimizer state leaves whose dim0 divides the DP
    world are sharded across it (memory /8 on the big leaves), and the math
    is unchanged — losses track the stage-0 run step for step."""
    from jax.sharding import PartitionSpec as P

    cfg0 = _onebit_config("OneBitAdam", freeze_step=5)
    cfg1 = _onebit_config("OneBitAdam", freeze_step=5)
    cfg1["zero_optimization"] = {"stage": 1}
    e0, *_ = ds.initialize(model=SimpleModel(),
                           example_batch=random_batch(16), config=cfg0)
    e1, *_ = ds.initialize(model=SimpleModel(),
                           example_batch=random_batch(16), config=cfg1)
    assert e1.onebit is not None and e1.onebit.zero_stage == 1

    # m/v leaves with divisible dim0 carry the DP axis in their sharding
    mv = e1.state.opt_state["onebit"]["m"]
    sharded = [l for l in jax.tree.leaves(mv)
               if l.ndim >= 1 and l.shape[0] % 8 == 0]
    assert sharded, "model has no dividable leaves to shard"
    for l in sharded:
        assert l.sharding.spec == P("data"), l.sharding
    # ...and the replicated-fallback leaves stay replicated
    for l in jax.tree.leaves(mv):
        if l.ndim >= 1 and l.shape[0] % 8 != 0:
            assert l.sharding.spec == P()

    for i in range(12):
        b = random_batch(16, seed=i)
        l0 = float(e0.train_batch(b)["loss"])
        l1 = float(e1.train_batch(b)["loss"])
        assert abs(l0 - l1) < 5e-4, (i, l0, l1)

    # after frozen steps the v-side leaves KEEP their ZeRO-1 sharding (m is
    # replicated post-freeze by design: the error-feedback exchange needs
    # the full momentum per rank)
    assert e1.onebit._step_frozen is not None
    v_after = e1.state.opt_state["onebit"]["v"]
    for l in jax.tree.leaves(v_after):
        if l.ndim >= 1 and l.shape[0] % 8 == 0:
            assert l.sharding.spec == P("data"), l.sharding


# -- 0/1 Adam (the real algorithm, not the round-3 onebit alias) --------------


class _SmoothModel:
    """tanh MLP factory: every parameter sees a nonzero gradient each step —
    the healthy regime for sign-compression (elements with exactly-zero grad
    AND zero variance would receive +-scale momentum over eps, a property
    the reference algorithm shares)."""

    def __new__(cls):
        import flax.linen as nn

        class M(nn.Module):
            hidden: int = 32
            nclass: int = 8

            @nn.compact
            def __call__(self, batch, train=False):
                x, y = batch["x"], batch["y"]
                h = nn.tanh(nn.Dense(self.hidden)(x))
                h = nn.tanh(nn.Dense(self.hidden)(h))
                logits = nn.Dense(self.nclass)(h)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.sum(
                    jax.nn.one_hot(y, self.nclass) * logp, -1))

        return M()


def _zeroone_config(**params):
    p = {"lr": 2e-3, "var_freeze_step": 12, "var_update_scaler": 4,
         "local_step_scaler": 4, "local_step_clipper": 4,
         "weight_decay": 0.01}
    p.update(params)
    return {"train_batch_size": 16,
            "optimizer": {"type": "ZeroOneAdam", "params": p},
            "seed": 7}


def test_zeroone_alias_removed():
    """'ZeroOneAdam' must resolve to the real 0/1 Adam algorithm, not an
    alias of onebit_adam (round-3 Missing #2)."""
    from deepspeed_tpu.ops.optimizers import build_optimizer
    zo = build_optimizer("ZeroOneAdam", {"lr": 1e-2})
    assert zo.name == "zerooneadam"
    ob = build_optimizer("OneBitAdam", {"lr": 1e-2})
    params = {"w": jnp.ones((8,), jnp.float32)}
    # 0/1 Adam state carries the interval machinery 1-bit Adam doesn't have
    st = zo.init(params)
    assert "var_interval" in st and "local_interval" in st and "u" in st
    assert "var_interval" not in ob.init(params)


def test_zeroone_interval_doubling():
    """The variance-update interval doubles after every var_update_scaler
    v-updates, v is untouched between v-steps and frozen after
    var_freeze_step; the local-step interval doubles every local_step_scaler
    steps up to local_step_clipper (reference zoadam.py:283-303)."""
    from deepspeed_tpu.ops.optimizers import zero_one_adam
    opt = zero_one_adam(lr=1e-2, var_freeze_step=6, var_update_scaler=2,
                        local_step_scaler=3, local_step_clipper=4)
    params = {"w": jnp.ones((4,), jnp.float32)}
    st = opt.init(params)
    rng = np.random.RandomState(0)
    p = params
    v_hist, iv_hist, li_hist, u_zero = [], [], [], []
    for t in range(16):
        g = {"w": jnp.asarray(rng.randn(4), jnp.float32)}
        p, st = opt.update(g, st, p, jnp.asarray(t, jnp.int32))
        v_hist.append(np.asarray(st["v"]["w"]).copy())
        iv_hist.append(int(st["var_interval"]))
        li_hist.append(int(st["local_interval"]))
        u_zero.append(float(jnp.abs(st["u"]["w"]).sum()) == 0.0)
    # kappa=2: steps 1,2 at interval 1 -> doubles; v-steps 4, 6 -> doubles
    assert iv_hist == [1, 2, 2, 2, 2, 4, 4, 4, 4, 4, 4, 4, 4, 4, 4, 4]
    # v changes exactly at steps 1,2,4,6 (indices 0,1,3,5), frozen afterwards
    changed = [True] + [not np.array_equal(v_hist[i], v_hist[i - 1])
                        for i in range(1, 16)]
    assert changed == [True, True, False, True, False, True] + [False] * 10
    # local phase from step 7: interval 1 for 3 steps, then 2, then 4 (clip)
    assert li_hist[5] == 1 and li_hist[8] == 2 and li_hist[11] == 4
    assert li_hist[15] == 4  # clipper caps further doubling
    # u resets exactly at boundaries (step % interval == 0)
    assert u_zero[9] and not u_zero[10] and u_zero[11]  # li=2: steps 10,11,12


def test_zeroone_differs_from_onebit():
    """0/1 Adam and 1-bit Adam are different algorithms: same grads, same
    shared hyperparameters, different trajectories."""
    from deepspeed_tpu.ops.optimizers import onebit_adam, zero_one_adam
    zo = zero_one_adam(lr=1e-2, var_freeze_step=6, var_update_scaler=2,
                       local_step_scaler=3, local_step_clipper=4)
    ob = onebit_adam(lr=1e-2, freeze_step=6)
    params = {"w": jnp.asarray(np.random.RandomState(0).randn(64),
                               jnp.float32)}
    s_zo, s_ob = zo.init(params), ob.init(params)
    p_zo = p_ob = params
    for t in range(12):
        g = {"w": jnp.asarray(np.random.RandomState(100 + t).randn(64),
                              jnp.float32)}
        p_zo, s_zo = zo.update(g, s_zo, p_zo, jnp.asarray(t, jnp.int32))
        p_ob, s_ob = ob.update(g, s_ob, p_ob, jnp.asarray(t, jnp.int32))
    diff = float(jnp.abs(p_zo["w"] - p_ob["w"]).max())
    assert diff > 1e-4, "0/1 Adam produced 1-bit Adam's trajectory"
    assert np.all(np.isfinite(np.asarray(p_zo["w"])))


def test_zeroone_engine_program_schedule():
    """The engine must dispatch the right compiled program per step: exact
    v-steps and compressed steps interleaved per the doubling interval in
    the variance phase, local/boundary steps after the freeze."""
    engine, *_ = ds.initialize(model=_SmoothModel(),
                               example_batch=random_batch(16),
                               config=_zeroone_config(
                                   var_freeze_step=8, var_update_scaler=2,
                                   local_step_scaler=4, local_step_clipper=4))
    from deepspeed_tpu.runtime.zeroone import ZeroOneRunner
    assert isinstance(engine.onebit, ZeroOneRunner)
    keys = [engine.onebit.program_key(t) for t in range(14)]
    assert keys == ["vstep", "vstep", "cstep", "vstep", "cstep", "vstep",
                    "cstep", "vstep",
                    "boundary", "boundary", "boundary", "boundary",
                    "local", "boundary"]


# tier-2 (round 10 budget): fattest passing legs demoted per the standing
# guardrail — tier-1 crept past ~80% of the 870s budget once the comm-plan
# legs landed; cheaper cousins still gate tier-1
@pytest.mark.slow
def test_zeroone_trains_and_local_steps_are_collective_free():
    """End-to-end: 0/1 Adam trains through all four program kinds, and the
    HLO of the local-step program contains ZERO cross-replica collective
    bytes — the algorithm's whole point (1-bit sync with local steps)."""
    engine, *_ = ds.initialize(model=_SmoothModel(),
                               example_batch=random_batch(16),
                               config=_zeroone_config())
    losses = [float(engine.train_batch(random_batch(16, seed=i))["loss"])
              for i in range(40)]
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-6:]) < losses[0]

    micros = jax.tree.map(lambda x: jnp.asarray(x)[None], random_batch(16))
    rng = jax.random.PRNGKey(0)
    params = engine.state.params
    st = engine.state.opt_state["onebit"]
    audit = {k: engine.onebit.collective_bytes(params, st, micros, rng, k)
             for k in ("vstep", "cstep", "local", "boundary")}
    assert audit["local"] == 0, audit
    # compressed steps move far fewer bytes than the exact v-step
    assert audit["cstep"] * 3 <= audit["vstep"], audit
    assert audit["boundary"] * 3 <= audit["vstep"], audit


def test_overflow_does_not_consume_schedule_steps():
    """An fp16 overflow reverts the optimizer state in-jit; the runner's
    program schedule (freeze / v-update / local-step intervals) must not
    advance past the skipped step — the reference's onebit/zoadam counters
    only move on executed torch steps."""
    cfg = _onebit_config("OneBitAdam", freeze_step=12, lr=2e-3)
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 8, "hysteresis": 1}
    engine, *_ = ds.initialize(model=SimpleModel(),
                               example_batch=random_batch(16), config=cfg)
    seen = []
    orig = engine.onebit.step

    def spy(params, state, micros, rng, lr, step, **kw):
        seen.append(step)
        return orig(params, state, micros, rng, lr, step, **kw)

    engine.onebit.step = spy
    m0 = engine.train_batch(random_batch(16, seed=0))
    assert not bool(m0["overflow"])
    bad = random_batch(16, seed=99)
    bad["x"] = (bad["x"] * 1e30).astype(np.float32)
    m1 = engine.train_batch(bad)
    assert bool(m1["overflow"])
    m2 = engine.train_batch(random_batch(16, seed=1))
    assert not bool(m2["overflow"])
    # good step consumed slot 0; the overflow attempted slot 1 and was
    # skipped; the next good step must RETRY slot 1, not move to slot 2
    assert seen == [0, 1, 1], seen


# tier-2 (round-19 budget sweep, ~8s): the cheaper tier-1 cousins are
# test_zeroone_interval_doubling + test_zeroone_differs_from_onebit
# (phase machinery) and test_zeroone_engine_program_schedule (program
# selection); scripts/tier2.sh runs this measured-bytes envelope leg
@pytest.mark.slow
def test_zeroone_local_phase_state_memory_model():
    """Post-freeze per-device state bytes must match the documented envelope
    (docs/BENCHMARKS.md 1-bit table): m_local / u / w_err are one
    full-model copy per DEVICE (stacked [n, ...] dim-0-sharded), v is
    replicated by design (every local step reads it whole), m / s_err stay
    ZeRO-1 sharded — ~17 B/param/device total. Round 5's measurement caught
    the boundary program silently REPLICATING the reset drift u
    (32 B/param/device) because its fresh zeros carried no sharding pin."""
    engine, *_ = ds.initialize(model=_SmoothModel(),
                               example_batch=random_batch(16),
                               config=_zeroone_config(
                                   var_freeze_step=2, var_update_scaler=2,
                                   local_step_scaler=2, local_step_clipper=4))
    for i in range(8):          # vstep/cstep, then boundary + local steps
        engine.train_batch(random_batch(16, seed=i))
    st = engine.state.opt_state["onebit"]
    n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))
    # the same shard-byte accounting the envelope table was measured with
    import pathlib, sys as _sys
    _sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                            / "scripts"))
    from onebit_envelope import per_device_bytes

    for key, expect in [("u", 4.0), ("m_local", 4.0), ("w_err", 4.0)]:
        got = per_device_bytes(st[key]) / n_params
        assert got <= expect * 1.5, \
            f"{key}: {got:.1f} B/param/device (stacked sharding lost?)"
    total = per_device_bytes({k: v for k, v in st.items() if k != "lrs"})
    assert total / n_params <= 17.0 * 1.3, total / n_params
