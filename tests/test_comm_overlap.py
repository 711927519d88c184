"""Overlap-schedule comm-plan algorithms (round 14, docs/COMM.md):
chunked allgather→matmul for the ZeRO-3 param fetch and chunked grad
reduce-scatter for the ZeRO-2 sync, registered as the
``overlap``/``overlap_int8`` algorithm family.

Coverage: registration + plan round-trip, selector picks overlap from
recorded rows only (never the heuristic), executor values, HLO
chunk-structure audits for BOTH seams in the test_onebit wire-byte
style (>= chunks chunk-sized collectives, no full-tensor collective on
the overlapped path, no full-remat of the model body), chunk-count
compile invariance, exact-vs-overlap multi-step loss parity through the
shared ``_finalize_step`` tail, the widened-envelope degrade matrix,
per-axis sweeps, the ds_bench overlap rows (``overlap_ratio``), and a
2-proc gloo ZeRO-2 overlap e2e (tier-2).
"""

import json
import os
import pathlib
import re
import socket
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu import comm_plan as cp
from deepspeed_tpu.comm_plan.plan import (ALGOS, QUANTIZED_ALGOS,
                                          SITE_ALGOS, SITE_KIND)
from deepspeed_tpu.runtime.comm.overlap import (chunked_ag_matmul,
                                                chunked_matmul_rs,
                                                effective_chunks,
                                                make_overlap_gather,
                                                overlap_grad_sync)
from deepspeed_tpu.runtime.onebit import hlo_collective_bytes

from util import SimpleModel, random_batch, require_devices

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)


def _count_ops(hlo_text, name):
    """Collective ops by result shape (first tuple element for
    tuple-shaped results), async-pair aware ('-start' counted, '-done'
    skipped): [(dtype, dims tuple), ...]."""
    out = []
    op_pat = re.compile(r"\s" + name + r"(-start|-done)?\(")
    shape_pat = re.compile(r"=\s*\(?\s*([a-z0-9]+)\[([0-9,]*)\]")
    for line in hlo_text.splitlines():
        m = op_pat.search(line)
        if not m or m.group(1) == "-done":
            continue
        s = shape_pat.search(line)
        if s:
            dims = tuple(int(d) for d in s.group(2).split(",") if d)
            out.append((s.group(1), dims))
    return out


# ------------------------------------------------------------- registration

def test_overlap_algos_registered_and_plan_round_trip(tmp_path):
    for algo in ("overlap", "overlap_int8"):
        assert algo in ALGOS
    assert set(SITE_ALGOS["grad_reduce_scatter"]) >= {"exact", "int8",
                                                      "overlap",
                                                      "overlap_int8"}
    assert set(SITE_ALGOS["param_all_gather"]) >= {"exact", "overlap"}
    assert SITE_KIND["param_all_gather"] == "all_gather"
    # overlap moves exact values: the accuracy guard must not latch it
    assert "overlap" not in QUANTIZED_ALGOS
    assert "overlap_int8" in QUANTIZED_ALGOS
    plan = cp.CommPlan()
    plan.add(cp.PlanEntry("all_gather", "all", 20, "overlap"))
    plan.add(cp.PlanEntry("reduce_scatter", "data", 23, "overlap_int8"))
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = cp.CommPlan.load(path)
    assert loaded.choose("all_gather", "data", 2 ** 20) == "overlap"
    assert loaded.choose("reduce_scatter", "data",
                         8 * 2 ** 20) == "overlap_int8"


def _overlap_rows(kind, size_bytes, overlap_us=100.0, exact_us=300.0):
    return [
        {"op": kind, "algo": "exact", "axis": "all",
         "size_bytes": size_bytes, "latency_us": exact_us},
        {"op": kind, "algo": "overlap", "axis": "all",
         "size_bytes": size_bytes, "latency_us": overlap_us,
         "overlap_ratio": 0.6, "chunks": 4},
    ]


def test_selector_picks_overlap_where_its_latency_wins():
    rows = (_overlap_rows("reduce_scatter", 8 * 2 ** 20)
            + _overlap_rows("all_gather", 2 ** 20))
    plan = cp.select_plan(rows)
    assert plan.choose("reduce_scatter", "data", 8 * 2 ** 20) == "overlap"
    assert plan.choose("all_gather", "data", 2 ** 20) == "overlap"
    # and where it loses, exact stays
    plan2 = cp.select_plan(_overlap_rows("all_gather", 2 ** 20,
                                         overlap_us=500.0))
    assert plan2.choose("all_gather", "data", 2 ** 20) == "exact"
    # a tie breaks toward the SAFER algorithm: exact < overlap in ALGOS
    plan3 = cp.select_plan(_overlap_rows("all_gather", 2 ** 20,
                                         overlap_us=300.0))
    assert plan3.choose("all_gather", "data", 2 ** 20) == "exact"


def test_heuristic_never_returns_overlap():
    """Overlap is selected from recorded rows or forced — never
    hard-coded by the no-sweep fallback (acceptance: 'never
    hard-coded')."""
    for kind in ("all_gather", "reduce_scatter", "all_to_all",
                 "all_reduce"):
        for nbytes in (2 ** 12, 2 ** 23, 2 ** 30):
            assert cp.heuristic_algo(kind, nbytes, axis_size=8) in (
                "exact", "int8")


def test_effective_chunks_divisibility():
    assert effective_chunks(16, 4) == 4
    assert effective_chunks(6, 4) == 3      # largest divisor <= 4
    assert effective_chunks(7, 4) == 1
    assert effective_chunks(2, 8) == 2      # floored at the length


# ----------------------------------------------------------------- executors

@pytest.fixture()
def mesh8():
    require_devices(8)
    return Mesh(np.asarray(jax.devices()[:8]), ("data",))


def test_overlap_grad_sync_value(mesh8):
    rng = np.random.default_rng(0)
    vals = rng.standard_normal((8, 4097)).astype(np.float32)  # odd size
    x = jax.device_put(jnp.asarray(vals), NamedSharding(mesh8, P("data")))
    want = vals.mean(axis=0)
    out = np.asarray(overlap_grad_sync(x, mesh=mesh8, axis="data",
                                       chunks=4, algo="overlap"))
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-6)
    out8 = np.asarray(overlap_grad_sync(x, mesh=mesh8, axis="data",
                                        chunks=4, algo="overlap_int8"))
    assert np.abs(out8 - want).max() <= np.abs(vals).max() / 127 * 2
    # nonfinite propagation (overflow detection relies on it)
    bad = vals.copy()
    bad[5, 99] = np.inf
    xb = jax.device_put(jnp.asarray(bad), NamedSharding(mesh8, P("data")))
    outb = np.asarray(overlap_grad_sync(xb, mesh=mesh8, axis="data",
                                        chunks=4, algo="overlap_int8"))
    assert not np.isfinite(outb).all()


def test_overlap_gather_fwd_bwd_parity(mesh8):
    rng = np.random.default_rng(1)
    w_np = rng.standard_normal((256, 64)).astype(np.float32)
    w = jax.device_put(jnp.asarray(w_np),
                       NamedSharding(mesh8, P("data", None)))
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((4, 256)).astype(np.float32)),
        NamedSharding(mesh8, P()))
    ref = jax.jit(jax.value_and_grad(
        lambda w, x: jnp.sum((x @ w) ** 2)))
    v_ref, g_ref = ref(w, x)
    for algo in ("overlap", "overlap_int8"):
        g = make_overlap_gather(mesh8, ("data",), 0, chunks=4, algo=algo)
        got = np.asarray(jax.jit(g)(w))
        if algo == "overlap":
            np.testing.assert_allclose(got, w_np, rtol=0, atol=0)
        else:
            assert np.abs(got - w_np).max() <= \
                np.abs(w_np).max() / 127 * 1.01
        v, gr = jax.jit(jax.value_and_grad(
            lambda w, x: jnp.sum((x @ g(w)) ** 2)))(w, x)
        scale = np.abs(np.asarray(g_ref)).max()
        tol = 1e-5 if algo == "overlap" else 0.05
        assert abs(float(v - v_ref)) <= tol * abs(float(v_ref))
        assert np.abs(np.asarray(gr) - np.asarray(g_ref)).max() <= \
            tol * scale


# ------------------------------------------------------- HLO structure audit

def test_hlo_grad_sync_overlap_is_chunked_no_full_collective(mesh8):
    """The overlapped sync's wire is >= chunks chunk-sized hops and has
    NO whole-buffer collective; the int8 variant's payload is s8 with
    scales riding per chunk, at <= 28% of the chunked-exact bytes."""
    numel = 65536
    x = jax.device_put(jnp.ones((8, numel), jnp.float32),
                       NamedSharding(mesh8, P("data")))

    def hlo(algo, chunks):
        fn = jax.jit(lambda v: overlap_grad_sync(
            v, mesh=mesh8, axis="data", chunks=chunks, algo=algo))
        return fn.lower(x).compile().as_text()

    txt = hlo("overlap", 4)
    a2a = _count_ops(txt, "all-to-all")
    ag = _count_ops(txt, "all-gather")
    assert len(a2a) >= 4 and len(ag) >= 4, (len(a2a), len(ag))
    # full-buffer hop would move numel/8 columns at once
    full_cols = numel // 8
    assert all(full_cols not in dims for _, dims in a2a), a2a
    txt8 = hlo("overlap_int8", 4)
    assert "s8" in txt8 and "s8" not in txt
    bytes_exact = hlo_collective_bytes(txt)
    bytes_int8 = hlo_collective_bytes(txt8)
    assert bytes_int8 <= 0.28 * bytes_exact, (bytes_int8, bytes_exact)


HLO_Z3_AUDIT = textwrap.dedent(r"""
    import os, sys, json, re
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, os.environ["DSTPU_TEST_REPO"])
    sys.path.insert(0, os.path.join(os.environ["DSTPU_TEST_REPO"],
                                    "tests"))
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from util import SimpleModel, random_batch

    H = 128
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 3,
                                 "stage3_param_persistence_threshold": 0},
           "comm_plan": {"enabled": True, "overlap_min_leaf_elems": 256,
                         "overlap_chunks": 4,
                         "overrides": {"param_all_gather": "overlap"}},
           "seed": 7}
    engine, *_ = ds.initialize(model=SimpleModel(hidden=H),
                               example_batch=random_batch(16), config=cfg)
    assert engine.comm_plan_ctx.resolved["param_all_gather"] == "overlap"
    micros = jax.tree.map(lambda x: jnp.asarray(x)[None],
                          random_batch(16))
    txt = jax.jit(engine._train_step).lower(
        engine.state, micros, jax.random.PRNGKey(0),
        jnp.asarray(5e-3, jnp.float32)).compile().as_text()
    op_pat = re.compile(
        r"\s(all-gather|reduce-scatter)(-start|-done)?"
        r"\(([a-z0-9]+)\[([0-9,]*)\]")
    shape_pat = re.compile(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]")
    ags, rss = [], []
    for line in txt.splitlines():
        m = op_pat.search(line)
        if not m or m.group(2) == "-done":
            continue
        s = shape_pat.search(line)
        if not s:
            continue
        res = tuple(int(d) for d in s.group(1).split(",") if d)
        opnd = tuple(int(d) for d in m.group(4).split(",") if d)
        (ags if m.group(1) == "all-gather" else rss).append((opnd, res))
    # chunk-sized gathers of the HxH kernel: local [H/8, H] sliced into
    # 4 chunks -> gathered chunk [8, H/32, H]. A FULL-tensor param
    # gather would move the whole [H/8, H] shard to [H, H] in one op
    # (the cotangent replication at the transposed region boundary also
    # lands on [H, H] but from a [H, H/8] column operand — that one is
    # XLA's resharding of the grad, not a param fetch).
    chunk = (8, H // 32, H)
    out = {"chunk_ags": sum(1 for o, r in ags if r == chunk),
           "full_param_ags": sum(1 for o, r in ags
                                 if o == (H // 8, H) and r == (H, H)),
           "chunk_rss": sum(1 for o, r in rss if r == (1,) + chunk[1:]),
           "n_rss": len(rss)}
    print("AUDIT: " + json.dumps(out))
""")


@pytest.mark.xfail(strict=True, reason=(
    "ROADMAP D6 (the comm-compression zoo, not yet judged against the dp4 "
    "cell): on jax 0.9.0 the audit reads chunk_ags 0, the overlap plan's "
    "chunked param gather is not in the compiled ZeRO-3 step. Failed in "
    "every driver run since before PR 21; strict, so a jax that brings the "
    "gather back turns this red and the mark goes"))
def test_hlo_zero3_overlap_step_chunked_no_full_gather_no_remat(tmp_path):
    """Acceptance audit, subprocess so XLA's stderr is capturable: the
    overlapped ZeRO-3 step holds >= overlap_chunks chunk-sized
    allgathers of the HxH kernel and ZERO full-tensor gathers of it,
    the backward reduce-scatters in the same chunks, and the compile
    emits no involuntary full rematerialization of the model body."""
    require_devices(8)
    script = tmp_path / "z3_audit.py"
    script.write_text(HLO_Z3_AUDIT)
    env = dict(os.environ, DSTPU_TEST_REPO=REPO_ROOT,
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    audit = json.loads(proc.stdout.split("AUDIT: ")[1].splitlines()[0])
    assert audit["chunk_ags"] >= 4, audit
    assert audit["full_param_ags"] == 0, audit
    assert audit["chunk_rss"] >= 4, audit
    assert "Involuntary full rematerialization" not in proc.stderr, \
        [l for l in proc.stderr.splitlines()
         if "rematerialization" in l][:4]


# --------------------------------------------------------- engine integration

def _engine(cfg_extra=None, seed=7, hidden=32):
    cfg = {"train_batch_size": 16,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 2}, "seed": seed}
    cfg.update(cfg_extra or {})
    engine, *_ = ds.initialize(model=SimpleModel(hidden=hidden),
                               example_batch=random_batch(16), config=cfg)
    return engine


# tier-2 (round-17 budget sweep, ~10s): the cheaper tier-1 cousins are
# test_overlap_grad_sync_value (wire values) and
# test_hlo_grad_sync_overlap_is_chunked_no_full_collective (structure);
# scripts/tier2.sh runs this 12-step engine parity leg
@pytest.mark.slow
def test_engine_zero2_overlap_12step_loss_parity():
    """Acceptance: exact-vs-overlap 12-step loss parity through the
    shared _finalize_step tail. The overlap wire moves exact values, so
    the twin tracks the exact engine to float tolerance; overlap_int8
    tracks within the blockwise-int8 band."""
    require_devices(8)
    e0 = _engine()
    e1 = _engine({"comm_plan": {"enabled": True,
                                "overrides": {"grad_reduce_scatter":
                                              "overlap"}}})
    e2 = _engine({"comm_plan": {"enabled": True,
                                "overrides": {"grad_reduce_scatter":
                                              "overlap_int8"}}})
    assert e1.comm_plan_ctx.resolved["grad_reduce_scatter"] == "overlap"
    l0, l1, l2 = [], [], []
    for i in range(12):
        b = random_batch(16, seed=i)
        l0.append(float(e0.train_batch(b)["loss"]))
        m1 = e1.train_batch(b)
        assert m1["grad_sync_algo"] == "overlap"
        l1.append(float(m1["loss"]))
        m2 = e2.train_batch(b)
        assert m2["grad_sync_algo"] == "overlap_int8"
        l2.append(float(m2["loss"]))
    assert np.isfinite(l1).all() and np.isfinite(l2).all()
    assert l1[-1] < l1[0]                     # it trains
    assert max(abs(a - b) for a, b in zip(l0, l1)) < 1e-4, (l0, l1)
    assert max(abs(a - b) for a, b in zip(l0, l2)) < 0.05, (l0, l2)


def test_engine_zero3_overlap_param_gather_parity():
    """The chunked explicit stage-3 gather is numerically the implicit
    gather: twin loss curves match to float tolerance, and the audit
    tag proves every step ran the overlapped program."""
    require_devices(8)
    z3 = {"zero_optimization": {"stage": 3,
                                "stage3_param_persistence_threshold": 0}}
    e0 = _engine(dict(z3), hidden=128)
    e1 = _engine({**z3, "comm_plan": {"enabled": True,
                                      "overlap_min_leaf_elems": 256,
                                      "overrides": {"param_all_gather":
                                                    "overlap"}}},
                 hidden=128)
    assert e1.comm_plan_ctx.resolved["param_all_gather"] == "overlap"
    assert e1._overlap_gathers is not None
    l0, l1 = [], []
    for i in range(8):
        b = random_batch(16, seed=i)
        l0.append(float(e0.train_batch(b)["loss"]))
        m = e1.train_batch(b)
        assert m["param_gather_algo"] == "overlap"
        l1.append(float(m["loss"]))
    assert np.isfinite(l1).all()
    assert max(abs(a - b) for a, b in zip(l0, l1)) < 1e-4, (l0, l1)


def test_chunk_count_compile_invariance():
    """Changing overlap_chunks recompiles ONCE (it is a static trace
    constant), never per step: 3 steps at chunks=4 hit one compiled
    program, and the chunk count actually shapes the wire (different
    chunks -> different collective counts)."""
    require_devices(8)
    e = _engine({"comm_plan": {"enabled": True, "overlap_chunks": 4,
                               "overrides": {"grad_reduce_scatter":
                                             "overlap"}}})
    for i in range(3):
        assert e.train_batch(
            random_batch(16, seed=i))["grad_sync_algo"] == "overlap"
    cache_size = getattr(e._train_step_q, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1, (
            f"overlap step traced {cache_size()}x across 3 steps")
    # chunk count shapes the program: 2 vs 4 chunks -> 2x collectives
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    x = jax.device_put(jnp.ones((8, 4096), jnp.float32),
                       NamedSharding(mesh, P("data")))

    def n_a2a(chunks):
        fn = jax.jit(lambda v: overlap_grad_sync(
            v, mesh=mesh, axis="data", chunks=chunks, algo="overlap"))
        return len(_count_ops(fn.lower(x).compile().as_text(),
                              "all-to-all"))

    assert n_a2a(4) > n_a2a(2) >= 2


def test_engine_overlap_selected_from_recorded_plan(tmp_path):
    """Acceptance: overlap is selected PER CELL by the plan built from
    sweep rows — no override, no hard-coding. Rows make overlap win the
    grad-sync reduce-scatter buckets and the param-fetch all_gather
    buckets; both engines resolve and run it."""
    require_devices(8)
    rows = []
    for b in range(10, 27):
        rows += _overlap_rows("reduce_scatter", 2 ** b)
        rows += _overlap_rows("all_gather", 2 ** b)
    plan = cp.select_plan(rows)
    path = str(tmp_path / "plan.json")
    plan.save(path)
    e = _engine({"comm_plan": {"enabled": True, "plan_path": path}})
    assert e.comm_plan_ctx.resolved["grad_reduce_scatter"] == "overlap"
    assert e.train_batch(random_batch(16))["grad_sync_algo"] == "overlap"
    z3 = {"zero_optimization": {"stage": 3,
                                "stage3_param_persistence_threshold": 0},
          "comm_plan": {"enabled": True, "plan_path": path,
                        "overlap_min_leaf_elems": 256}}
    e3 = _engine(z3, hidden=128)
    assert e3.comm_plan_ctx.resolved["param_all_gather"] == "overlap"
    m = e3.train_batch(random_batch(16))
    assert m["param_gather_algo"] == "overlap"
    assert np.isfinite(float(m["loss"]))


# tier-2 (round-17 budget sweep, ~11s): the cheaper tier-1 cousins are
# test_comm_plan.test_engine_accuracy_guard_forces_exact (lossy latch)
# and test_engine_zero3_overlap_param_gather_parity (exact-wire overlap
# keeps running); scripts/tier2.sh runs this exemption matrix
@pytest.mark.slow
def test_accuracy_guard_exempts_exact_wire_overlap():
    """The guard forces exact only for LOSSY formats: overlap_int8
    latches to exact, plain overlap keeps running (it already moves
    exact values)."""
    require_devices(8)
    e = _engine({"comm_plan": {"enabled": True,
                               "guard_min_grad_norm": 1e9,
                               "overrides": {"grad_reduce_scatter":
                                             "overlap"}}})
    algos = [e.train_batch(random_batch(16, seed=i))["grad_sync_algo"]
             for i in range(3)]
    assert algos == ["overlap", "overlap", "overlap"], algos
    e2 = _engine({"comm_plan": {"enabled": True,
                                "guard_min_grad_norm": 1e9,
                                "overrides": {"grad_reduce_scatter":
                                              "overlap_int8"}}})
    algos2 = [e2.train_batch(random_batch(16, seed=i))["grad_sync_algo"]
              for i in range(3)]
    assert algos2 == ["overlap_int8", "exact", "exact"], algos2


# ------------------------------------------------------------- envelope pins

# tier-2 (round-17 budget sweep, ~12s): the cheaper tier-1 cousins are
# test_comm_plan.test_engine_forced_sync_outside_envelope_degrades (same
# degrade contract, one site) and test_effective_chunks_divisibility;
# scripts/tier2.sh runs the full forced/unforced matrix
@pytest.mark.slow
def test_envelope_degrade_matrix():
    """Round-14 contract: a forced non-exact grad sync OUTSIDE the
    envelope degrades to exact with a warning instead of raising, and
    this pins exactly which configs degrade. TP sits INSIDE the envelope."""
    require_devices(8)
    import logging
    from deepspeed_tpu.utils.logging import logger as ds_logger
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    ds_logger.addHandler(handler)
    try:
        # stage 3 shards compute params: degrades everywhere
        e = _engine({"zero_optimization": {"stage": 3},
                     "comm_plan": {"enabled": True,
                                   "overrides": {"grad_reduce_scatter":
                                                 "int8"}}})
    finally:
        ds_logger.removeHandler(handler)
    assert e.comm_plan_ctx.resolved["grad_reduce_scatter"] == "exact"
    assert any("running exact" in m for m in records), records
    assert np.isfinite(float(e.train_batch(random_batch(16))["loss"]))
    # TP composition: envelope membership depends on native shard_map
    from deepspeed_tpu.models import build_model, causal_lm_loss
    model, mcfg = build_model("gpt2-tiny", hidden_size=64, num_layers=1,
                              num_heads=4, vocab_size=128, max_seq_len=32,
                              attention_impl="reference")
    cfg = {"train_batch_size": 4, "train_micro_batch_size_per_gpu": 1,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2},
           "tensor_parallel": {"tp_size": 2},
           "comm_plan": {"enabled": True,
                         "overrides": {"grad_reduce_scatter": "int8"}}}
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, size=(4, 16))}
    records.clear()
    ds_logger.addHandler(handler)
    try:
        eng, *_ = ds.initialize(model=model, config=cfg,
                                loss_fn=causal_lm_loss,
                                example_batch=batch,
                                sharding_rules=mcfg.tp_rules())
    finally:
        ds_logger.removeHandler(handler)
    # TP composes — the forced verdict holds
    assert eng.comm_plan_ctx.resolved["grad_reduce_scatter"] == "int8"
    # an unexecutable forced algo NAME still raises (never silently runs
    # something else)
    with pytest.raises(ValueError, match="not executable"):
        _engine({"comm_plan": {"enabled": True,
                               "overrides": {"grad_reduce_scatter":
                                             "onebit"}}})


@pytest.mark.slow
def test_tp_composed_explicit_sync_parity():
    """The widened envelope actually syncing under TP: int8 grad sync
    with tp_size=2 tracks the exact twin."""
    require_devices(8)
    from deepspeed_tpu.models import build_model, causal_lm_loss

    def mk(extra):
        model, mcfg = build_model("gpt2-tiny", hidden_size=64,
                                  num_layers=1, num_heads=4,
                                  vocab_size=128, max_seq_len=32,
                                  attention_impl="reference")
        cfg = {"train_batch_size": 8,
               "train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "tensor_parallel": {"tp_size": 2}, "seed": 5, **extra}
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, 128, size=(8, 16))}
        e, *_ = ds.initialize(model=model, config=cfg,
                              loss_fn=causal_lm_loss,
                              example_batch=batch,
                              sharding_rules=mcfg.tp_rules())
        return e, batch

    e0, batch = mk({})
    e1, _ = mk({"comm_plan": {"enabled": True,
                              "overrides": {"grad_reduce_scatter":
                                            "int8"}}})
    assert e1.comm_plan_ctx.resolved["grad_reduce_scatter"] == "int8"
    l0 = [float(e0.train_batch(batch)["loss"]) for _ in range(6)]
    l1 = [float(e1.train_batch(batch)["loss"]) for _ in range(6)]
    assert np.isfinite(l1).all()
    assert max(abs(a - b) for a, b in zip(l0, l1)) < 0.05, (l0, l1)


# --------------------------------------------------- per-axis sweeps + bench

def test_per_axis_sweep_records_one_row_per_mesh_axis(tmp_path, capsys):
    """Satellite: on a >1-axis mesh the sweep records one row per axis
    (hierarchical ICI/DCN selection needs per-axis measurements); the
    selected plan carries per-axis entries the wildcard resolution
    prefers over 'all'."""
    require_devices(8)
    from deepspeed_tpu.comm_plan.cli import main as cli_main
    out_path = str(tmp_path / "plan.json")
    rc = cli_main(["sweep", "--ops", "reduce_scatter", "--algos",
                   "exact", "--sizes-mb", "0.25", "--iters", "2",
                   "--mesh", "data=2,model=4", "--out", out_path])
    out = capsys.readouterr().out
    assert rc == 0
    rows = cp.parse_bench_lines(out)
    assert {r["axis"] for r in rows} == {"data", "model"}
    assert {r["n"] for r in rows} == {2, 4}
    plan = cp.CommPlan.load(out_path)
    kinds = {(e.kind, e.axis) for e in plan.entries.values()}
    assert ("reduce_scatter", "data") in kinds
    assert ("reduce_scatter", "model") in kinds
    # per-axis entry answers the exact-axis query (no wildcard needed)
    nbytes = next(iter(plan.entries.values())).bucket
    e = plan.entry_for("reduce_scatter", "model", 2 ** nbytes)
    assert e is not None and e.axis == "model"


def test_comm_bench_overlap_rows_have_ratio(mesh8):
    """ds_bench's overlap cells: latency_us is the EXPOSED comm time,
    the wall/comm/compute split and overlap_ratio ride the row, and the
    selector ingests them unchanged."""
    from deepspeed_tpu.benchmarks.communication import run_op_sweep
    rows = run_op_sweep("all_gather", [0.25], jnp.float32, iters=2,
                        algo="overlap", mesh=mesh8, axis="data")
    rows += run_op_sweep("reduce_scatter", [0.25], jnp.float32, iters=2,
                         algo="overlap_int8", mesh=mesh8, axis="data")
    for r in rows:
        assert r["algo"] in ("overlap", "overlap_int8")
        assert r["latency_us"] > 0
        assert r["overlap_ratio"] > 0
        assert r["chunks"] >= 2
        assert r["wall_us"] >= r["latency_us"]
    plan = cp.select_plan(rows)
    assert plan.entries          # rows are selector-ingestible


def test_bench_pipeline_values(mesh8):
    """The benchmark payloads compute what they claim (a wrong payload
    would time garbage): chunked ag->matmul == x @ w; chunked
    matmul->rs chunks reconstruct the mean-reduced grads."""
    rng = np.random.default_rng(3)
    w = jax.device_put(
        jnp.asarray(rng.standard_normal((64, 32)).astype(np.float32)),
        NamedSharding(mesh8, P("data", None)))
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32)),
        NamedSharding(mesh8, P()))
    got = np.asarray(chunked_ag_matmul(x, w, mesh=mesh8, axis="data",
                                       chunks=4))
    np.testing.assert_allclose(got, np.asarray(x) @ np.asarray(w),
                               rtol=1e-5, atol=1e-4)
    u = jax.device_put(
        jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
        NamedSharding(mesh8, P("data")))
    v = jax.device_put(
        jnp.asarray(rng.standard_normal((16, 40)).astype(np.float32)),
        NamedSharding(mesh8, P()))
    got = np.asarray(chunked_matmul_rs(u, v, mesh=mesh8, axis="data",
                                       chunks=4))
    want = (np.asarray(u) @ np.asarray(v)).mean(axis=0)    # [40]
    # per-chunk scattered layout: chunk k's served piece (padded to
    # ceil(seg/n)) sits at column k*c per rank; reassemble and compare
    segs = [(0, 10), (10, 20), (20, 30), (30, 40)]
    c = got.shape[1] // 4
    for k, (lo, hi) in enumerate(segs):
        piece = np.concatenate([got[r, k * c:(k + 1) * c]
                                for r in range(8)])[:hi - lo]
        np.testing.assert_allclose(piece, want[lo:hi], rtol=1e-5,
                                   atol=1e-5)


# --------------------------------------------------------------- 2-proc gloo

WORKER_OVERLAP_ZERO2 = r"""
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
    "comm_plan": {"enabled": True, "overlap_chunks": 4,
                  "overrides": {"grad_reduce_scatter": "overlap"}},
    "seed": 11,
}
engine, *_ = ds.initialize(model=SimpleModel(), config=config,
                           example_batch=random_batch(8))
assert engine.comm_plan_ctx.resolved["grad_reduce_scatter"] == "overlap"
losses = []
for i in range(8):
    m = engine.train_batch(random_batch(8, seed=i))
    assert m["grad_sync_algo"] == "overlap"
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
def test_two_process_zero2_overlap_grad_sync(tmp_path):
    """Acceptance satellite (tier-2, scripts/tier2.sh): a REAL
    2-process gloo world runs ZeRO-2 with the chunked overlap sync —
    the cross-process wire carries the chunk hops, and both ranks see
    identical losses (the sync synced)."""
    worker = tmp_path / "worker_overlap.py"
    worker.write_text(WORKER_OVERLAP_ZERO2)
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
    assert outs[0].split("last=")[1].split()[0] == \
        outs[1].split("last=")[1].split()[0]
