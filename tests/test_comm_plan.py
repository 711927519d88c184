"""Comm-plan subsystem tests: plan round-trip + selection determinism,
the resolution ladder, the blockwise-int8 collectives (value + wire-byte
audits in test_onebit.py's HLO-parsing style), engine integration for the
ZeRO-2 int8 grad sync (multi-step parity vs the exact twin, accuracy
guard), the MoE int8 dispatch, the comm_bench record format, and the
``dstpu comm-plan`` CLI.
"""

import json
import os
import pathlib
import random
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu import comm_plan as cp
from deepspeed_tpu.comm_plan.runtime import (AccuracyGuard, PlanContext,
                                             resolve_algo)
from deepspeed_tpu.runtime.onebit import hlo_collective_bytes

from util import SimpleModel, random_batch, require_devices

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)


# ---------------------------------------------------------------- plan format

def _rows(shuffle_seed=None):
    rows = [
        {"op": "reduce_scatter", "algo": "exact", "axis": "all",
         "size_mb": 8.0, "size_bytes": 8 * 2 ** 20, "latency_us": 900.0},
        {"op": "reduce_scatter", "algo": "int8", "axis": "all",
         "size_mb": 8.0, "size_bytes": 8 * 2 ** 20, "latency_us": 400.0},
        {"op": "all_to_all", "algo": "exact", "axis": "all",
         "size_mb": 8.0, "size_bytes": 8 * 2 ** 20, "latency_us": 500.0},
        {"op": "all_to_all", "algo": "int8", "axis": "all",
         "size_mb": 8.0, "size_bytes": 8 * 2 ** 20, "latency_us": 700.0},
        {"op": "all_reduce", "algo": "exact", "axis": "all",
         "size_mb": 1.0, "size_bytes": 2 ** 20, "latency_us": 120.0},
        # a tie: exact must win (ALGOS-order tie-break, safer first)
        {"op": "all_reduce", "algo": "int8", "axis": "all",
         "size_mb": 1.0, "size_bytes": 2 ** 20, "latency_us": 120.0},
    ]
    if shuffle_seed is not None:
        random.Random(shuffle_seed).shuffle(rows)
    return rows


def test_plan_json_round_trip(tmp_path):
    plan = cp.select_plan(_rows(), meta={"n_devices": 8})
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = cp.CommPlan.load(path)
    assert loaded.to_json() == plan.to_json()
    assert loaded.meta == {"n_devices": 8}
    # entries survive with their provenance
    e = loaded.entry_for("reduce_scatter", "all", 8 * 2 ** 20)
    assert e.algo == "int8" and e.source == "sweep" and e.est_us == 400.0


def test_selector_deterministic_under_record_order():
    base = cp.select_plan(_rows()).to_json()
    for seed in range(5):
        assert cp.select_plan(_rows(shuffle_seed=seed)).to_json() == base


def test_selector_picks_fastest_and_breaks_ties_safely():
    plan = cp.select_plan(_rows())
    assert plan.choose("reduce_scatter", "all", 8 * 2 ** 20) == "int8"
    assert plan.choose("all_to_all", "all", 8 * 2 ** 20) == "exact"
    # tied latency: exact (lower ALGOS index) wins
    assert plan.choose("all_reduce", "all", 2 ** 20) == "exact"


def test_plan_rejects_unknown_algo_and_newer_version():
    bad = {"version": 1, "entries": [
        {"kind": "all_reduce", "axis": "all", "bucket": 20,
         "algo": "fp4"}]}
    with pytest.raises(ValueError, match="unknown algo"):
        cp.CommPlan.from_json(json.dumps(bad))
    with pytest.raises(ValueError, match="newer"):
        cp.CommPlan.from_json(json.dumps({"version": 99, "entries": []}))


def test_axis_wildcard_and_unknown_bucket():
    plan = cp.select_plan(_rows())
    # the "all" sweep row answers a query on a named axis
    assert plan.choose("reduce_scatter", "data", 8 * 2 ** 20) == "int8"
    # a bucket no sweep covered -> None (callers fall to heuristic)
    assert plan.choose("reduce_scatter", "data", 512 * 2 ** 20) is None


# ----------------------------------------------------------- resolution ladder

def test_resolve_unknown_bucket_falls_back_to_heuristic():
    ctx = PlanContext(plan=cp.select_plan(_rows()))
    # 512 MB: no plan entry -> heuristic -> int8 (over threshold)
    assert resolve_algo(ctx, "grad_reduce_scatter", "data",
                        512 * 2 ** 20, axis_size=8) == "int8"
    # 64 KB: no plan entry -> heuristic -> exact (latency floor)
    assert resolve_algo(ctx, "grad_reduce_scatter", "data",
                        64 * 2 ** 10, axis_size=8) == "exact"
    # single-member axis: always exact
    assert resolve_algo(ctx, "grad_reduce_scatter", "data",
                        512 * 2 ** 20, axis_size=1) == "exact"


def test_resolve_override_wins_and_validates():
    ctx = PlanContext(plan=cp.select_plan(_rows()),
                      overrides={"grad_reduce_scatter": "exact"})
    # the plan says int8 at 8MB; the site override forces exact
    assert resolve_algo(ctx, "grad_reduce_scatter", "data",
                        8 * 2 ** 20, axis_size=8) == "exact"
    # wire-kind override reaches the site too
    ctx2 = PlanContext(overrides={"all_to_all": "int8"})
    assert resolve_algo(ctx2, "moe_all_to_all", "expert",
                        1024, axis_size=2) == "int8"
    # unexecutable forced algo raises (never silently degrades)
    ctx3 = PlanContext(overrides={"grad_reduce_scatter": "onebit"})
    with pytest.raises(ValueError, match="not executable"):
        resolve_algo(ctx3, "grad_reduce_scatter", "data", 1024,
                     axis_size=8)


def test_plan_entry_with_site_unsupported_algo_falls_through():
    plan = cp.CommPlan()
    plan.add(cp.PlanEntry("reduce_scatter", "all",
                          cp.bucket_of(8 * 2 ** 20), "hierarchical"))
    ctx = PlanContext(plan=plan)
    # the entry names an algo the grad-sync seam can't execute: the
    # heuristic answers instead (int8 at 8MB)
    assert resolve_algo(ctx, "grad_reduce_scatter", "data",
                        8 * 2 ** 20, axis_size=8) == "int8"


def test_accuracy_guard_latches_on_small_norms():
    g = AccuracyGuard(0.5)
    assert not g.use_exact          # no observation yet: plan's choice
    g.observe(2.0)
    assert not g.use_exact
    g.observe(0.1)
    assert g.use_exact
    g.observe(float("nan"))         # overflow step: ignored
    assert g.use_exact
    g.observe(3.0)
    assert not g.use_exact


# ------------------------------------------------------ quantized collectives

@pytest.fixture()
def mesh8():
    require_devices(8)
    return Mesh(np.asarray(jax.devices()[:8]), ("data",))


def test_quantized_reduce_scatter_value(mesh8):
    from deepspeed_tpu.runtime.comm.quantized import quantized_reduce_scatter
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((8, 5000)).astype(np.float32)
    x = jax.device_put(jnp.asarray(vals), NamedSharding(mesh8, P("data")))
    out = np.asarray(quantized_reduce_scatter(x, mesh=mesh8, axis="data",
                                              mean=True))
    want = vals.mean(axis=0)
    got = out.reshape(-1)[:5000]
    # blockwise scales: the error bound is per-BLOCK absmax / 127, far
    # tighter than a per-tensor scale on heavy-tailed data
    per_elem = np.abs(vals).max() / 127.0
    assert np.abs(got - want).max() <= per_elem * 1.01


def test_grad_sync_matches_mean_and_propagates_nonfinite(mesh8):
    from deepspeed_tpu.runtime.comm.quantized import grad_sync
    rng = np.random.default_rng(1)
    vals = rng.standard_normal((8, 4097)).astype(np.float32)  # odd size
    x = jax.device_put(jnp.asarray(vals), NamedSharding(mesh8, P("data")))
    want = vals.mean(axis=0)
    out_e = np.asarray(grad_sync(x, mesh=mesh8, axis="data", algo="exact"))
    np.testing.assert_allclose(out_e, want, rtol=0, atol=1e-6)
    out_q = np.asarray(grad_sync(x, mesh=mesh8, axis="data", algo="int8"))
    assert out_q.shape == want.shape
    assert np.abs(out_q - want).max() <= np.abs(vals).max() / 127 * 2
    # an inf on ONE rank must poison the synced result (overflow
    # detection downstream relies on propagation)
    bad = vals.copy()
    bad[3, 17] = np.inf
    xb = jax.device_put(jnp.asarray(bad), NamedSharding(mesh8, P("data")))
    out_b = np.asarray(grad_sync(xb, mesh=mesh8, axis="data", algo="int8"))
    assert not np.isfinite(out_b).all()


def test_quantized_all_to_all_matches_exact(mesh8):
    from deepspeed_tpu.runtime.comm.quantized import quantized_all_to_all
    rng = np.random.default_rng(2)
    vals = rng.standard_normal((64, 48)).astype(np.float32)
    x = jax.device_put(jnp.asarray(vals), NamedSharding(mesh8, P("data")))
    got = np.asarray(quantized_all_to_all(x, mesh=mesh8, axis="data"))
    exact = jax.shard_map(
        lambda xl: jax.lax.all_to_all(xl, "data", split_axis=0,
                                      concat_axis=0, tiled=True),
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False)
    want = np.asarray(jax.jit(exact)(x))
    assert np.abs(got - want).max() <= np.abs(vals).max() / 127 * 1.01


def test_queue_exchange_roundtrip_and_expert_alignment():
    require_devices(8)
    from deepspeed_tpu.runtime.comm.quantized import make_queue_exchange
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(1, 2, 2, 2, 1),
                ("pipe", "data", "expert", "seq", "model"))
    G, E, Cg, H = 8, 4, 3, 16
    rng = np.random.default_rng(3)
    sh = NamedSharding(mesh, P(("data", "expert", "seq"), None, None, None))
    x = jax.device_put(jnp.asarray(
        rng.standard_normal((G, E, Cg, H)).astype(np.float32)), sh)
    for algo, tol in (("exact", 1e-6), ("int8", None)):
        disp, comb = make_queue_exchange(mesh, algo=algo)
        rt = np.asarray(jax.jit(lambda v: comb(disp(v)))(x))
        bound = tol if tol is not None else \
            2 * np.abs(np.asarray(x)).max() / 127
        assert np.abs(rt - np.asarray(x)).max() <= bound, algo
    # expert alignment: rows tagged with their expert index land intact
    tag = np.zeros((G, E, Cg, H), np.float32)
    for e in range(E):
        tag[:, e] = e
    disp, _ = make_queue_exchange(mesh, algo="exact")
    full = np.asarray(jax.jit(disp)(jax.device_put(jnp.asarray(tag), sh)))
    assert full.shape == (E, G * Cg, H)
    for e in range(E):
        assert (full[e] == e).all()


# ------------------------------------------------------------ wire-byte audit

def test_wire_bytes_grad_sync_int8_vs_exact(mesh8):
    """Acceptance: the int8 grad sync moves <= ~28% of the exact path's
    collective bytes — audited from optimized HLO over IDENTICAL op
    structures (f32 vs int8 payload + the f32 per-block scales)."""
    from deepspeed_tpu.runtime.comm.quantized import grad_sync
    x = jax.device_put(jnp.ones((8, 65536), jnp.float32),
                       NamedSharding(mesh8, P("data")))

    def audit(algo):
        fn = jax.jit(lambda v: grad_sync(v, mesh=mesh8, axis="data",
                                         algo=algo))
        txt = fn.lower(x).compile().as_text()
        return txt, hlo_collective_bytes(txt)

    txt_e, bytes_e = audit("exact")
    txt_q, bytes_q = audit("int8")
    assert bytes_e > 0 and bytes_q > 0
    assert "s8" in txt_q and "s8" not in txt_e
    assert bytes_q <= 0.28 * bytes_e, (bytes_q, bytes_e,
                                       bytes_q / bytes_e)


def test_wire_bytes_all_to_all_int8_vs_exact(mesh8):
    from deepspeed_tpu.runtime.comm.quantized import quantized_all_to_all
    x = jax.device_put(jnp.ones((64, 4096), jnp.float32),
                       NamedSharding(mesh8, P("data")))
    exact = jax.jit(jax.shard_map(
        lambda xl: jax.lax.all_to_all(xl, "data", split_axis=0,
                                      concat_axis=0, tiled=True),
        mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
        axis_names={"data"}, check_vma=False))
    quant = jax.jit(lambda v: quantized_all_to_all(v, mesh=mesh8,
                                                   axis="data"))
    bytes_e = hlo_collective_bytes(exact.lower(x).compile().as_text())
    txt_q = quant.lower(x).compile().as_text()
    bytes_q = hlo_collective_bytes(txt_q)
    assert "s8" in txt_q
    assert bytes_q <= 0.28 * bytes_e, (bytes_q, bytes_e,
                                       bytes_q / bytes_e)


# --------------------------------------------------------- engine integration

def _engine(cfg_extra=None, seed=7):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 2}, "seed": seed}
    cfg.update(cfg_extra or {})
    engine, *_ = ds.initialize(model=SimpleModel(),
                               example_batch=random_batch(16), config=cfg)
    return engine


def test_engine_int8_grad_sync_training_parity():
    """Acceptance: multi-step training parity — the quantized-sync twin's
    loss curve tracks the exact engine within tolerance, and the audit
    tag proves the int8 program actually ran every step."""
    require_devices(8)
    e0 = _engine()
    e1 = _engine({"comm_plan": {"enabled": True,
                                "overrides": {"grad_reduce_scatter":
                                              "int8"}}})
    assert e1.comm_plan_ctx.resolved["grad_reduce_scatter"] == "int8"
    l0, l1 = [], []
    for i in range(12):
        b = random_batch(16, seed=i)
        l0.append(float(e0.train_batch(b)["loss"]))
        m = e1.train_batch(b)
        l1.append(float(m["loss"]))
        assert m["grad_sync_algo"] == "int8"
    assert np.isfinite(l1).all()
    assert l1[-1] < l1[0]                     # it trains
    assert max(abs(a - b) for a, b in zip(l0, l1)) < 0.05, (l0, l1)


def test_engine_accuracy_guard_forces_exact():
    """Acceptance: the guard forces the exact program once the observed
    grad norm is below the threshold — with a huge threshold, step 1 runs
    the plan's int8 choice (nothing observed yet) and every later step
    runs exact."""
    require_devices(8)
    e = _engine({"comm_plan": {"enabled": True,
                               "guard_min_grad_norm": 1e9,
                               "overrides": {"grad_reduce_scatter":
                                             "int8"}}})
    algos = [e.train_batch(random_batch(16, seed=i))["grad_sync_algo"]
             for i in range(3)]
    assert algos == ["int8", "exact", "exact"], algos
    # and with a tiny threshold the guard never trips
    e2 = _engine({"comm_plan": {"enabled": True,
                                "guard_min_grad_norm": 1e-9,
                                "overrides": {"grad_reduce_scatter":
                                              "int8"}}})
    algos2 = [e2.train_batch(random_batch(16, seed=i))["grad_sync_algo"]
              for i in range(3)]
    assert algos2 == ["int8", "int8", "int8"], algos2


def test_engine_forced_sync_outside_envelope_degrades():
    """Round-14 contract change: a forced non-exact grad sync OUTSIDE
    the envelope degrades to exact with a warning instead of raising
    (TP now sits inside the envelope on native-shard_map hosts; the
    full degrade matrix is pinned in test_comm_overlap.py)."""
    require_devices(8)
    e = _engine({"zero_optimization": {"stage": 3},
                 "comm_plan": {"enabled": True,
                               "overrides": {"grad_reduce_scatter":
                                             "int8"}}})
    assert e.comm_plan_ctx.resolved["grad_reduce_scatter"] == "exact"
    assert np.isfinite(float(e.train_batch(random_batch(16))["loss"]))


# tier-2 (round-17 budget sweep, ~10s): the cheaper tier-1 cousins are
# test_engine_forced_sync_outside_envelope_degrades (same degrade path,
# forced) and test_resolve_unknown_bucket_falls_back_to_heuristic;
# scripts/tier2.sh runs this unforced-selection leg
@pytest.mark.slow
def test_engine_unforced_selection_degrades_to_exact_outside_envelope():
    """A plan-driven (not forced) int8 verdict on an incompatible mesh
    logs and runs exact — selection must never brick a launch."""
    require_devices(8)
    plan = cp.CommPlan()
    # a wildcard entry that covers EVERY grad-sync bucket this model hits
    for bucket in range(10, 34):
        plan.add(cp.PlanEntry("reduce_scatter", "all", bucket, "int8"))
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        f.write(plan.to_json())
        path = f.name
    try:
        from deepspeed_tpu.models import build_model, causal_lm_loss
        model, mcfg = build_model("gpt2-tiny", hidden_size=64,
                                  num_layers=1, num_heads=4,
                                  vocab_size=128, max_seq_len=32,
                                  attention_impl="reference")
        cfg = {"train_batch_size": 4,
               "train_micro_batch_size_per_gpu": 1,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "tensor_parallel": {"tp_size": 2},
               "comm_plan": {"enabled": True, "plan_path": path}}
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, 128, size=(4, 16))}
        eng, *_ = ds.initialize(model=model, config=cfg,
                                loss_fn=causal_lm_loss,
                                example_batch=batch,
                                sharding_rules=mcfg.tp_rules())
        assert eng.comm_plan_ctx.resolved["grad_reduce_scatter"] == "exact"
        assert np.isfinite(float(eng.train_batch(batch)["loss"]))
    finally:
        os.unlink(path)


@pytest.mark.slow
def test_engine_moe_int8_dispatch_training_parity():
    """The MoE expert all-to-all routed through the explicit int8
    exchange: loss curve tracks the exact (implicit-SPMD) twin. Tier-1
    covers the same composition through the dryrun's moe_q leg; this is
    the closer-tolerance twin comparison."""
    require_devices(8)
    from deepspeed_tpu.models import build_model, make_moe_loss

    def mk(extra):
        model, mcfg = build_model(
            "gpt2-tiny", hidden_size=64, num_layers=2, num_heads=4,
            vocab_size=256, max_seq_len=64, moe_experts=4,
            moe_capacity_factor=2.0, attention_impl="reference")
        cfg = {"train_batch_size": 16,
               "train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
               "bf16": {"enabled": True},
               "zero_optimization": {"stage": 2},
               "moe": {"enabled": True, "ep_size": 2}, "seed": 3, **extra}
        batch = {"input_ids": np.random.default_rng(3).integers(
            0, 256, size=(16, 32))}
        e, *_ = ds.initialize(model=model, config=cfg,
                              loss_fn=make_moe_loss(mcfg.moe_aux_weight),
                              example_batch=batch,
                              sharding_rules=mcfg.tp_rules())
        return e, batch

    e0, batch = mk({})
    e1, _ = mk({"comm_plan": {"enabled": True,
                              "overrides": {"moe_all_to_all": "int8"}}})
    l0 = [float(e0.train_batch(batch)["loss"]) for _ in range(8)]
    l1 = [float(e1.train_batch(batch)["loss"]) for _ in range(8)]
    assert e1.comm_plan_ctx.resolved["moe_all_to_all"] == "int8"
    assert np.isfinite(l1).all()
    assert l1[-1] < l1[0]
    assert max(abs(a - b) for a, b in zip(l0, l1)) < 0.05, (l0, l1)


# ------------------------------------------------- comm_bench record format

def test_parse_bench_lines_and_selector_ingest():
    out = "\n".join([
        "irrelevant noise",
        'comm_bench: {"op": "reduce_scatter", "algo": "exact", '
        '"axis": "all", "size_mb": 8.0, "size_bytes": 8388608, '
        '"latency_us": 900.0}',
        "comm_bench: {broken json",
        'comm_bench: {"op": "reduce_scatter", "algo": "int8", '
        '"axis": "all", "size_mb": 8.0, "size_bytes": 8388608, '
        '"latency_us": 300.0}',
    ])
    rows = cp.parse_bench_lines(out)
    assert len(rows) == 2
    plan = cp.select_plan(rows)
    assert plan.choose("reduce_scatter", "all", 8 * 2 ** 20) == "int8"


def test_sweep_regression_compare():
    from deepspeed_tpu.benchmarks.communication import (
        check_sweep_regression)
    base = [{"op": "all_to_all", "algo": "int8", "axis": "all",
             "size_mb": 8.0, "latency_us": 100.0}]
    ok = [{"op": "all_to_all", "algo": "int8", "axis": "all",
           "size_mb": 8.0, "latency_us": 150.0}]
    slow = [{"op": "all_to_all", "algo": "int8", "axis": "all",
             "size_mb": 8.0, "latency_us": 250.0}]
    other = [{"op": "all_to_all", "algo": "exact", "axis": "all",
              "size_mb": 8.0, "latency_us": 250.0}]
    assert check_sweep_regression(ok, base) == []
    probs = check_sweep_regression(slow, base)
    assert len(probs) == 1 and "2.5x" in probs[0]
    # a row with no matching recorded cell is not a regression
    assert check_sweep_regression(other, base) == []


def test_latest_comm_sweep_discovery(tmp_path):
    from deepspeed_tpu.benchmarks.communication import latest_comm_sweep
    a = tmp_path / "comm_sweep_old.json"
    a.write_text(json.dumps({"n": 8, "rows": [{"op": "x",
                                               "latency_us": 1.0}]}))
    os.utime(a, (1, 1))
    b = tmp_path / "COMMBENCH_r02.json"
    b.write_text(json.dumps({"n": 8, "rows": [{"op": "y",
                                               "latency_us": 2.0}]}))
    name, rows = latest_comm_sweep(str(tmp_path), 8)
    assert name == "COMMBENCH_r02.json" and rows[0]["op"] == "y"
    # device-count mismatch: skipped
    name, rows = latest_comm_sweep(str(tmp_path), 2)
    assert name is None and rows == []


# ----------------------------------------------------------------------- CLI

def test_comm_plan_cli_show(tmp_path, capsys):
    from deepspeed_tpu.comm_plan.cli import main as cli_main
    plan = cp.select_plan(_rows())
    path = str(tmp_path / "plan.json")
    plan.save(path)
    rc = cli_main(["show", path, "--query",
                   f"reduce_scatter:data:{8 * 2 ** 20}"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "reduce_scatter" in out and "int8" in out
    assert "-> int8 (plan entry)" in out


def test_comm_plan_cli_sweep_records_and_selects(tmp_path, capsys):
    """End-to-end on the virtual mesh: one op, exact+int8, selection via
    the autotuning grid, plan written + parseable, comm_bench lines in
    the selector-ingestible format."""
    require_devices(8)
    from deepspeed_tpu.comm_plan.cli import main as cli_main
    out_path = str(tmp_path / "plan.json")
    rec_path = str(tmp_path / "sweep.json")
    rc = cli_main(["sweep", "--ops", "reduce_scatter", "--algos",
                   "exact,int8", "--sizes-mb", "0.25", "--iters", "2",
                   "--out", out_path, "--record", rec_path])
    out = capsys.readouterr().out
    assert rc == 0
    rows = cp.parse_bench_lines(out)
    assert {(r["op"], r["algo"]) for r in rows} == {
        ("reduce_scatter", "exact"), ("reduce_scatter", "int8")}
    plan = cp.CommPlan.load(out_path)
    assert plan.entries and plan.meta["n_devices"] == len(jax.devices())
    rec = json.loads(open(rec_path).read())
    assert rec["n"] == len(jax.devices()) and len(rec["rows"]) == 2


# ------------------------------------------------------------- 2-proc gloo

WORKER_INT8_ZERO2 = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])

import numpy as np
import deepspeed_tpu as ds

ds.init_distributed()
rank = ds.comm.get_rank()
assert ds.comm.get_world_size() == 2

sys.path.insert(0, os.path.join(os.environ["DSTPU_TEST_REPO"], "tests"))
from util import SimpleModel, random_batch

config = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 2},
    "comm_plan": {"enabled": True,
                  "overrides": {"grad_reduce_scatter": "int8"}},
    "seed": 11,
}
engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                           example_batch=random_batch(8))
assert engine.comm_plan_ctx.resolved["grad_reduce_scatter"] == "int8"
losses = []
for i in range(8):
    m = engine.train_batch(random_batch(8, seed=i))
    assert m["grad_sync_algo"] == "int8"
    losses.append(float(m["loss"]))
assert np.isfinite(losses).all(), losses
assert losses[-1] < losses[0], losses
print(f"RANK{rank} OK last={losses[-1]:.6f}", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_zero2_int8_grad_sync(tmp_path):
    """Acceptance satellite: a REAL 2-process gloo world runs ZeRO-2
    training with the int8 grad reduce-scatter — the cross-PROCESS wire
    really carries the quantized exchange, and both ranks see identical
    losses (the sync synced)."""
    worker = tmp_path / "worker_int8.py"
    worker.write_text(WORKER_INT8_ZERO2)
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ,
                   DSTPU_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
                   DSTPU_NUM_PROCESSES="2",
                   DSTPU_PROCESS_ID=str(pid),
                   DSTPU_TEST_REPO=REPO_ROOT)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out[-3000:]}"
        assert f"RANK{pid} OK" in out, out[-2000:]
    l0 = outs[0].split("last=")[1].split()[0]
    l1 = outs[1].split("last=")[1].split()[0]
    assert l0 == l1, (l0, l1)
