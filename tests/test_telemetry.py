"""The program's one recorder (utils/telemetry.py) and what the two engines
record through it: span nesting and self time, the ring's bound, the
``serve.*`` / ``train.*`` spans and counters, the request stamps, compiles by
phase and program, the constructors' ``*.init`` spans and the device scopes
(metadata only: the optimized HLO keeps its instructions).
"""

import contextlib
import json
import logging
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import build_model, fused_loss_passthrough
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.utils import telemetry
from tests.util import SimpleModel, random_batch

MS = 1_000_000

SERVE_SPANS = {
    "serve.submit", "serve.step", "serve.admit", "serve.shed",
    "serve.admit.peek", "serve.admit.evict", "serve.admit.alloc",
    "serve.prefill", "serve.prefill.build", "serve.prefill.dispatch",
    "serve.prefill.fetch", "serve.prefill.prefix_insert",
    "serve.prefill.install", "serve.decode", "serve.decode.build",
    "serve.decode.dispatch", "serve.decode.fetch", "serve.decode.bookkeep",
    "serve.heartbeat", "serve.req.admitted", "serve.req.first_token",
    "serve.req.finished", "serve.init"}
TRAIN_SPANS = {
    "train.step", "train.prepare", "train.h2d", "train.dispatch",
    "train.sync", "train.after_step", "train.after_step.heartbeat",
    "train.after_step.pull", "train.after_step.monitor",
    "train.init", "train.init.params", "train.init.place",
    "train.init.opt_state"}
PHASE_COUNTERS = {"trace": "compile.trace_us", "lower": "compile.lower_us",
                  "backend": "compile.backend_us"}


# ------------------------------------------------------------- the recorder


def test_self_time_is_a_span_less_its_children_on_a_hand_made_ring():
    # step 0..100 ms holds admit 10..30 (which holds alloc 12..20) and
    # decode 40..90 (which holds fetch 50..85)
    ring = [("serve.admit.alloc", "serve.admit", 12 * MS, 20 * MS, {}),
            ("serve.admit", "serve.step", 10 * MS, 30 * MS, {}),
            ("serve.decode.fetch", "serve.decode", 50 * MS, 85 * MS, {}),
            ("serve.decode", "serve.step", 40 * MS, 90 * MS, {}),
            ("serve.step", None, 0, 100 * MS, {"step": 0})]
    t = telemetry.span_times(ring)
    assert {k: v["self_ms"] for k, v in t.items()} == pytest.approx({
        "serve.admit.alloc": 8, "serve.admit": 12, "serve.decode.fetch": 35,
        "serve.decode": 15, "serve.step": 30})
    assert sum(v["self_ms"] for v in t.values()) == pytest.approx(100)
    assert t["serve.step"]["total_ms"] == pytest.approx(100)
    # a later start cuts the earlier entries out
    late = telemetry.span_times(ring, since_ns=35 * MS)
    assert set(late) == {"serve.decode", "serve.decode.fetch"}


def test_nesting_gives_the_parent_and_a_step_carries_its_counter_gains():
    rec = telemetry.Recorder("t", keep=False)
    rec.count("a", 5)
    with rec.step_span("s", step=7):
        with rec.span("s.x", k=1):
            rec.count("a", 2)
            rec.event("s.e", rid=3)
        rec.count("b")
    by = {e[0]: e for e in rec.ring}
    assert by["s.x"][1] == "s" and by["s.e"][1] == "s.x"
    assert by["s"][1] is None
    assert by["s"][4] == {"step": 7, "d": {"a": 2, "b": 1}}
    assert by["s.x"][4] == {"k": 1} and by["s.e"][4] == {"rid": 3}
    assert by["s.e"][3] - by["s.e"][2] < MS          # zero length, nearly
    assert by["s"][2] <= by["s.x"][2] <= by["s.x"][3] <= by["s"][3]
    snap = rec.snapshot()
    assert snap["counters"] == {"a": 7, "b": 1}
    assert snap["spans"]["s"]["count"] == 1
    assert snap["spans"]["s"]["self_ms"] <= snap["spans"]["s"]["total_ms"]


def test_ring_is_bounded_and_counts_what_it_drops(tmp_path):
    rec = telemetry.Recorder("t", ring_size=4, keep=False)
    for i in range(10):
        rec.event("e", i=i)
    assert len(rec.ring) == 4 and rec.dropped == 6
    assert [e[4]["i"] for e in rec.ring] == [6, 7, 8, 9]
    assert rec.snapshot()["ring_dropped"] == 6
    rows = [json.loads(l) for l in open(rec.dump(str(tmp_path / "r.jsonl")))]
    assert [r["attrs"]["i"] for r in rows] == [6, 7, 8, 9]
    assert set(rows[0]) == {"name", "parent", "start_ns", "end_ns", "attrs"}


def test_the_module_keeps_the_last_few_recorders_only():
    made = [telemetry.Recorder(f"r{i}") for i in range(telemetry.KEPT + 2)]
    assert telemetry.recent() == made[-telemetry.KEPT:]


def slow_to_trace(n: int):
    """A jitted function whose trace takes well over
    ``telemetry.TRACE_ENTRY_NS`` (a few hundred equations)."""
    def f(x):
        for i in range(n):
            x = jnp.sin(x) * (i + 1.5)
        return x
    f.__name__ = f"slow_{n}"
    return jax.jit(f)


def test_a_compile_inside_a_span_is_counted_in_each_phase_with_its_name():
    rec = telemetry.Recorder("t")
    f, x = slow_to_trace(300), jnp.ones((3,))
    with rec.step_span("s", step=0):
        with rec.span("s.work"):
            f(x).block_until_ready()
    with rec.step_span("s", step=1):
        f(x).block_until_ready()                     # cached: no compile
    assert rec.counters["compiles"] == 1
    compiles = [e for e in rec.ring if e[0] == "compile"]
    assert all(e[1] == "s.work" and e[4]["step"] == 0 for e in compiles)
    # (on a busy machine a helper traced inside f may pass TRACE_ENTRY_NS
    # and be written too: f's own trace is the last and the longest)
    traces = [e for e in compiles if e[4]["phase"] == "trace"]
    assert [e[4]["phase"] for e in compiles[len(traces) - 1:]] == [
        "trace", "lower", "backend"]
    assert traces[-1] is max(traces, key=lambda e: e[3] - e[2])
    compiles = compiles[len(traces) - 1:]
    for name, parent, start, end, attrs in compiles:
        assert "slow_300" in attrs["fun_name"]
        # the counter of the phase holds the entry's microseconds (a
        # trace's less the helpers traced inside it, counted on their own)
        assert rec.counters[PHASE_COUNTERS[attrs["phase"]]] == \
            pytest.approx((end - start) / 1e3, rel=0.05, abs=1.0)
    assert "compile.cache_load_us" not in rec.counters
    steps = [e for e in rec.ring if e[0] == "s"]
    assert steps[0][4]["d"]["compiles"] == 1
    assert set(PHASE_COUNTERS.values()) <= set(steps[0][4]["d"])
    assert not any(k.startswith("compile") for k in steps[1][4]["d"])


def test_a_recompile_in_a_late_step_is_found_by_one_ring_query():
    rec = telemetry.Recorder("t")
    f, usual, other = slow_to_trace(200), jnp.ones((3,)), jnp.ones((5,))
    for step in range(6):
        with rec.step_span("serve.step", step=step):
            with rec.span("serve.decode.dispatch"):
                # step 4 meets a shape the program was not compiled for
                f(other if step == 4 else usual).block_until_ready()
    late = [(e[4]["step"], e[4]["phase"], e[1])
            for e in rec.ring if e[0] == "compile" and e[4]["step"] > 0
            and "slow_200" in e[4]["fun_name"]]
    assert late == [(4, ph, "serve.decode.dispatch")
                    for ph in ("trace", "lower", "backend")]


def test_a_jit_traced_inside_another_is_counted_once():
    rec = telemetry.Recorder("t", keep=False)
    telemetry._listen()
    inner = slow_to_trace(150)
    outer, x = jax.jit(lambda x: inner(x) + inner(x * 2.0)), jnp.ones((4,))
    with rec.span("s"):
        outer(x).block_until_ready()
    traces = [e for e in rec.ring
              if e[0] == "compile" and e[4]["phase"] == "trace"]
    assert len(traces) >= 2                    # the inner one and the outer
    # summed, the entries count the inner trace twice; the counter does not
    held = sum(e[3] - e[2] for e in traces) / 1e3
    outermost = max(e[3] - e[2] for e in traces) / 1e3
    assert rec.counters["compile.trace_us"] < held
    assert rec.counters["compile.trace_us"] == pytest.approx(outermost,
                                                             rel=0.05)
    wall_us = (rec.ring[-1][3] - rec.ring[-1][2]) / 1e3
    assert sum(rec.counters[c] for c in PHASE_COUNTERS.values()) <= wall_us


def test_the_listener_books_a_cache_load_apart_and_keeps_short_traces_out():
    rec = telemetry.Recorder("t", keep=False)
    base = "/jax/core/compile/"
    with rec.step_span("train.step", step_num=9):
        with rec.span("train.dispatch"):
            # a helper's trace, too short for the ring; the counter has it
            telemetry._on_compile(base + "jaxpr_trace_duration", 0.0002,
                                  fun_name="helper")
            telemetry._on_compile(base + "jaxpr_to_mlir_module_duration",
                                  0.003, fun_name="jit(step)")
            # a persistent-cache hit reports inside the backend event
            telemetry._on_compile(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.004)
            telemetry._on_compile(base + "backend_compile_duration", 0.005,
                                  fun_name="jit(step)")
            # the next program misses the cache
            telemetry._on_compile(base + "backend_compile_duration", 0.007,
                                  fun_name="jit(other)")
            telemetry._on_compile("/jax/some/other_duration", 1.0)
    step_us = rec.counters.pop("train.step_us")
    assert rec.counters == {
        "compile.trace_us": 200, "compile.lower_us": 3000,
        "compile.cache_load_us": 5000, "compile.backend_us": 7000,
        "compiles": 2, "compile.backend_compiles": 1,
        # the phases claim 15.2 ms of a span that took microseconds: the
        # rest of the first call is cut at nothing, never negative
        "compile.first_calls": 1, "compile.first_call_rest_us": 0}
    step = next(e for e in rec.ring if e[0] == "train.step")
    assert step_us == step[3] // 1000 - step[2] // 1000
    entries = [(e[4]["phase"], e[4]["fun_name"], e[4]["step"], e[1])
               for e in rec.ring if e[0] == "compile"]
    assert entries == [
        ("lower", "jit(step)", 9, "train.dispatch"),
        ("cache_load", "jit(step)", 9, "train.dispatch"),
        ("backend", "jit(other)", 9, "train.dispatch")]
    # outside every span of a recorder nothing is booked anywhere
    telemetry._on_compile(base + "backend_compile_duration", 0.5,
                          fun_name="jit(nobody)")
    assert rec.counters["compiles"] == 2


def test_spans_appear_in_a_profiler_trace_under_the_ds_prefix(tmp_path):
    from jax.profiler import ProfileData
    rec = telemetry.Recorder("t", keep=False)
    with jax.profiler.trace(str(tmp_path)):
        with rec.step_span("train.step", step_num=4):
            with rec.span("train.sync", why="x"):
                jnp.ones((8,)).block_until_ready()
    pb = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
          if f.endswith(".xplane.pb")]
    events = {}
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(telemetry.PREFIX):
                        events[e.name] = dict(e.stats)
    assert set(events) == {"ds/train.step", "ds/train.sync"}
    assert int(events["ds/train.step"]["step_num"]) == 4
    assert events["ds/train.sync"]["why"] == "x"


# ------------------------------------------------------------------ serving


@pytest.fixture(scope="module")
def tiny_lm():
    model, cfg = build_model(
        "gpt2-tiny", hidden_size=32, num_layers=2, num_heads=2,
        vocab_size=64, max_seq_len=256, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


def held_from_outside(srv) -> int:
    """Distinct blocks of the decode lanes and the prompt in prefill: what
    the benchmark's driver counts before each ``srv.step()``."""
    held = [s.blocks for s in srv._slots if s is not None]
    if srv._prefilling is not None:
        held.append(srv._prefilling.blocks)
    return len(set().union(*held))


@pytest.fixture(scope="module")
def served(tiny_lm):
    """A tiny engine (chunked prefill, a pool small enough to block
    admissions and to evict prefix entries) run to idle; what an outside
    observer counted before every step."""
    cfg, params = tiny_lm
    srv = ServingEngine(cfg, params, interpret=True, serving={
        "block_size": 16, "pool_blocks": 24, "max_batch": 3,
        "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32})
    rng = np.random.default_rng(0)
    sizes = [(40, 12), (70, 20), (20, 5), (50, 30), (33, 8), (90, 10),
             (17, 4), (64, 9)]
    reqs = [srv.submit(list(rng.integers(1, 64, size=n)), max_new_tokens=k)
            for n, k in sizes]
    outside = []
    while not srv.idle:
        outside.append({"held": held_from_outside(srv), "lanes": srv.active,
                        "queue": srv.scheduler.pending})
        srv.step()
    return srv, reqs, outside


def test_a_served_run_yields_every_serve_span(served):
    srv, reqs, outside = served
    names = {e[0] for e in srv.rec.ring}
    assert SERVE_SPANS <= names
    assert {n for n in names if n.startswith("serve.")} == SERVE_SPANS
    steps = [e for e in srv.rec.ring if e[0] == "serve.step"]
    assert [e[4]["step"] for e in steps] == list(range(len(outside)))
    parents = {e[0]: e[1] for e in srv.rec.ring}
    assert parents["serve.admit"] == "serve.step"
    assert parents["serve.admit.peek"] == "serve.admit"
    assert parents["serve.admit.alloc"] == "serve.admit"
    assert parents["serve.decode.fetch"] == "serve.decode"
    # a prompt's first token is fetched and booked behind the launch of
    # the decode call its lane joins (PR 40): inside serve.decode
    assert parents["serve.prefill.install"] == "serve.prefill"
    assert parents["serve.prefill.fetch"] == "serve.decode"
    assert parents["serve.prefill.prefix_insert"] == "serve.decode"
    assert parents["serve.req.first_token"] == "serve.decode"
    assert parents["serve.submit"] is None
    snap = srv.telemetry()
    assert snap["counters"] == srv.stats
    assert snap["spans"]["serve.step"]["count"] == len(outside)
    assert snap["ring_dropped"] == 0


def test_serve_init_is_the_first_entry_and_holds_the_constructors_compiles(
        served):
    srv, reqs, outside = served
    ring = list(srv.rec.ring)
    spans_only = [e for e in ring if e[0] != "compile"]
    assert spans_only[0][0] == "serve.init" and spans_only[0][1] is None
    init = spans_only[0]
    # what the constructor compiles (the pool's zeros, the sampling key)
    # lies inside the span, is booked to it and is written before it ends
    inside = ring[:ring.index(init)]
    assert inside and all(e[0] == "compile" and e[1] == "serve.init"
                          and "step" not in e[4] for e in inside)
    assert all(init[2] <= e[2] <= e[3] <= init[3] for e in inside)
    assert {e[4]["phase"] for e in inside} >= {"lower", "backend"}
    assert [e[0] for e in ring].count("serve.init") == 1
    # no serve.submit or step began before the constructor ended
    assert all(e[2] >= init[3] for e in spans_only[1:])


def test_the_dispatch_spans_name_the_program_they_launch(served):
    srv, reqs, outside = served
    programs = {(e[0], e[4].get("program")) for e in srv.rec.ring
                if e[0].endswith(".dispatch")}
    # (a step that advances a chunk launches the mixed program, the chunk's
    # rows and the lanes': the cache is not latent)
    assert programs == {("serve.decode.dispatch", "jit__decode"),
                        ("serve.prefill.dispatch", "jit__mixed")}
    # the names a device trace gives their runs: "jit_" + the function's
    assert srv._decode_fn.__name__ == "_decode"
    assert srv._prefill_fn.__name__ == "_prefill"
    assert srv._mixed_fn.__name__ == "_mixed"
    # nothing else was added to any span of a step
    assert {k for e in srv.rec.ring for k in e[4]
            if e[0] not in ("compile", "serve.decode.dispatch",
                            "serve.prefill.dispatch")} == {
        "step", "d", "rid", "tokens", "final", "lanes", "arrival_ts", "ts"}


def test_inside_and_outside_count_the_same_thing_step_for_step(served):
    srv, reqs, outside = served
    steps = [e for e in srv.rec.ring if e[0] == "serve.step"]
    assert len(steps) == len(outside) == srv.stats["steps"]
    for entry, seen in zip(steps, outside):
        d = entry[4]["d"]
        assert d.get("kv.held_blocks_sum", 0) == seen["held"]
        assert d.get("lane_sum", 0) == seen["lanes"]
        assert d.get("queue_len_sum", 0) == seen["queue"]
        assert d.get("steps_with_queue", 0) == (seen["queue"] > 0)
    assert srv.stats["kv.held_blocks_sum"] == sum(o["held"] for o in outside)
    assert srv.rec.gauges["kv.held_blocks_peak"] == \
        max(o["held"] for o in outside)
    assert srv.stats["lane_sum"] == sum(o["lanes"] for o in outside)
    # a reservation is never smaller than what was written into it
    assert 0 < srv.stats["kv.tokens_written_sum"] <= \
        srv.stats["kv.blocks_reserved_sum"] * srv.block_size
    # the pool's own ledger: what went out came back, but for what the
    # prefix cache still holds
    assert srv.stats["kv.alloc"] - srv.stats["kv.release"] == \
        srv.pool.used_count
    assert srv.stats["prefix.prompt_tokens"] == \
        sum(len(r.prompt) for r in reqs)
    assert srv.stats["prefix.lookups"] == len(reqs)
    assert srv.stats["prefix.inserted_entries"] >= len(reqs)
    assert srv.stats["prefix.evicted_entries"] >= 1
    assert srv.stats["prefix.evict_scanned_entries"] >= \
        srv.stats["prefix.evicted_entries"]


def test_prefill_calls_count_the_pages_they_walk_and_name_their_path(
        served, tiny_lm):
    """``paged.chunk_live_pages_sum`` / ``paged.chunk_table_pages_sum`` (PR
    37) grow by one reading a PREFILL call, the pages up to the call's last
    real token and the table's width, and not with decode steps; the gauge
    ``paged.prefill_path`` says which way the prefill programs' attention
    went, by the query rows of each program."""
    srv, reqs, outside = served
    steps = [e for e in srv.rec.ring if e[0] == "serve.step"]
    calls = [e for e in srv.rec.ring if e[0] == "serve.prefill.dispatch"]
    gains = [e[4]["d"].get("paged.chunk_table_pages_sum", 0) for e in steps]
    assert sum(gains) == srv.stats["paged.chunk_table_pages_sum"] == \
        len(calls) * srv.nbk
    assert 0 < gains.count(0) < len(steps)          # decode-only steps: none
    assert all(g in (0, srv.nbk) for g in gains)    # one chunk a step
    assert len(calls) <= srv.stats["paged.chunk_live_pages_sum"] <= \
        srv.stats["paged.chunk_table_pages_sum"]
    for e in steps:
        d = e[4]["d"]
        assert bool(d.get("paged.chunk_live_pages_sum")) == \
            bool(d.get("paged.chunk_table_pages_sum"))
    # interpret=True: every chunk shape rode the kernel
    assert srv.telemetry()["gauges"]["paged.prefill_path"] == \
        {"kernel": [32, 16]}
    # exactly: 40 tokens in chunks of 32 over blocks of 16 are the calls
    # (q0 0, 32 tokens: 2 pages) and (q0 32, 8 tokens: 3 pages); on the CPU
    # without the interpreter the reference serves, and no reason is owed
    cfg, params = tiny_lm
    one = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 24, "max_batch": 3,
        "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32})
    one.submit(list(range(1, 41)), max_new_tokens=6)
    one.run_until_idle()
    assert one.stats["paged.chunk_live_pages_sum"] == 2 + 3
    assert one.stats["paged.chunk_table_pages_sum"] == 2 * 8
    assert one.stats["paged.live_pages_sum"] > 0
    assert one.telemetry()["gauges"]["paged.prefill_path"] == \
        {"reference": [32, 16]}


@pytest.mark.parametrize("window", [0, 24], ids=["global", "window24"])
def test_prefill_calls_count_their_turns_by_the_kernels_rule(tiny_lm, window):
    """``paged.chunk_turns_sum`` / ``paged.chunk_key_tiles_sum`` /
    ``paged.chunk_key_tiles_live_sum`` (PR 51): what the paged kernel's
    chunk programs do for a prefill call, by the kernel's own rule
    (``paged_attention.chunk_plan`` / ``chunk_walk``: held to the kernel in
    tests/test_paged_attention.py), over programs and layers; a call the
    reference serves counts none."""
    import dataclasses
    from deepspeed_tpu.ops.pallas.paged_attention import (chunk_plan,
                                                          chunk_walk)
    cfg, params = tiny_lm
    if window:
        cfg = dataclasses.replace(
            cfg, layer_windows=(window,) + (0,) * (cfg.num_layers - 1))
    serving = {"block_size": 16, "pool_blocks": 24, "max_batch": 3,
               "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32}
    srv = ServingEngine(cfg, params, serving=serving, interpret=True)
    srv.submit(list(range(1, 71)), max_new_tokens=3)
    srv.run_until_idle()
    c = dict(srv.telemetry()["counters"])
    pool = srv.pools["k"]
    want = np.zeros(3, np.int64)
    for q0, n, Tb in ((0, 32, 32), (32, 32, 32), (64, 6, 16)):
        programs, P, lanes = chunk_plan(
            cfg.num_heads, pool.shape[1], 16, cfg.head_dim,
            pool.dtype.itemsize, 8, Tb)
        for w in cfg.layer_windows or (0,) * cfg.num_layers:
            want += programs * np.asarray(
                chunk_walk(q0, q0 + n, w, Tb, P, lanes, 16, 8))
    srv.close()
    assert [c["paged.chunk_turns_sum"], c["paged.chunk_key_tiles_sum"],
            c["paged.chunk_key_tiles_live_sum"]] == want.tolist()
    # a table of 8 pages of 16 is one group of 128 keys: a turn a call,
    # program and layer, one lane tile each, every one of them live
    assert want[0] == want[1] == want[2] > 0
    assert want[0] == 3 * cfg.num_layers * programs
    ref = ServingEngine(cfg, params, serving=serving)
    ref.submit(list(range(1, 71)), max_new_tokens=3)
    ref.run_until_idle()
    assert ref.stats["paged.chunk_turns_sum"] == 0 \
        == ref.stats["paged.chunk_key_tiles_sum"]
    ref.close()


def test_a_blocked_step_counts_exactly_one_cause(served):
    srv, reqs, outside = served
    causes = ("admit_blocked.no_lane", "admit_blocked.no_blocks",
              "admit_blocked.prefilling")
    blocked = 0
    for entry in (e for e in srv.rec.ring if e[0] == "serve.step"):
        d = entry[4]["d"]
        n = sum(d.get(c, 0) for c in causes)
        assert n in (0, 1)
        if n:
            assert d.get("steps_with_queue", 0) == 1
            assert "prefix.prompt_tokens" not in d      # nobody admitted
        blocked += n
    assert blocked == sum(srv.stats[c] for c in causes)
    assert 0 < blocked <= srv.stats["steps_with_queue"]
    # this pool and these lanes block for more than one reason
    assert sum(1 for c in causes if srv.stats[c]) >= 2


def test_a_request_has_four_stamps_in_order(served):
    srv, reqs, outside = served
    for r in reqs:
        assert r.arrival_ts <= r.admitted_ts <= r.first_token_ts \
            <= r.finish_ts
    events = {(e[0], e[4]["rid"]) for e in srv.rec.ring
              if e[0].startswith("serve.req.")}
    for r in reqs:
        for what in ("admitted", "first_token", "finished"):
            assert (f"serve.req.{what}", r.rid) in events


# ------------------------------------------------- a mixture of experts


@pytest.fixture(scope="module")
def served_moe():
    """A tiny dropless MoE (6 experts, top-3) served with chunked prefill
    (prompts of two and three chunks: their middle chunks' tokens are never
    fetched) beside every fetch the engine made."""
    from deepspeed_tpu.models import TransformerConfig
    from deepspeed_tpu.parallel import mesh as mesh_mod
    # an earlier test of this worker may have left a mesh with an expert
    # axis behind, on which a dropless mixture refuses to build
    mesh_mod.set_global_mesh(mesh_mod.MeshManager(devices=jax.devices()[:1]))
    model, cfg = build_model(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=256, mlp_dim_override=24, gated_mlp=True,
        activation="silu", norm="rmsnorm", pos_embed="rotary",
        use_bias=False, tie_embeddings=False, moe_experts=6, moe_k=3,
        moe_norm_topk=False, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(1),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    srv = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 24, "max_batch": 3,
        "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32})
    fetched = []
    count = srv._count_experts
    srv._count_experts = lambda out, call: fetched.append(out) or count(
        out, call)
    rng = np.random.default_rng(3)
    sizes = [(40, 6), (70, 9), (20, 5)]
    reqs = [srv.submit(list(rng.integers(1, 64, size=n)), max_new_tokens=k)
            for n, k in sizes]
    srv.run_until_idle()
    return srv, cfg, reqs, sizes, fetched


def test_a_served_moe_counts_its_routers_load(served_moe):
    srv, cfg, reqs, sizes, fetched = served_moe
    L, E, k = cfg.num_layers, cfg.moe_experts, cfg.moe_k
    c = srv.telemetry()["counters"]
    # every real token met k experts in every layer, padding none: prompt
    # tokens once, generated tokens but each request's last (never fed back)
    real = sum(n + new - 1 for n, new in sizes)
    assert c["moe.assignments"] == real * k * L
    # one (call, layer) pair for each device call that held a real token
    calls = sum(-(-n // 32) for n, _ in sizes) + srv.stats["steps"]
    assert 0 < c["moe.layer_steps"] <= calls * L
    assert c["moe.layer_steps"] % L == 0
    # the fullest expert over the mean is between 1 and E / k ... E
    mean_load = c["moe.load_max_over_mean_sum"] / c["moe.layer_steps"]
    assert 1.0 <= mean_load <= E
    assert 0 <= c["moe.experts_idle_sum"] <= (E - k) * c["moe.layer_steps"]
    # a step's ring entry carries the gains like any counter's
    gains = sum(e[4].get("d", {}).get("moe.assignments", 0)
                for e in srv.rec.ring if e[0] == "serve.step")
    assert gains == c["moe.assignments"]


def test_moe_counts_ride_the_tokens_own_fetch(served_moe):
    srv, cfg, reqs, sizes, fetched = served_moe
    L, E = cfg.num_layers, cfg.moe_experts
    # one int32 vector a fetch: the tokens, then the [L, E] counts
    for out in fetched:
        assert out.dtype == np.int32
        assert out.size - L * E in (1, srv.max_batch)   # a prompt's, a step's
    # as many fetches as a dense engine makes: one a decode call, one a
    # chunk (since PR 40 a step waits for its chunk, a middle one too,
    # behind the launch of the next decode call: nothing is left pending)
    spans = [e[0] for e in srv.rec.ring if e[0].endswith(".fetch")]
    assert len(fetched) == len(spans)
    chunks = sum(1 for e in srv.rec.ring if e[0] == "serve.prefill")
    assert spans.count("serve.prefill.fetch") == chunks > len(reqs)
    assert not srv._moe_pending
    assert all(r.state == "FINISHED" or r.done for r in reqs)


def test_a_dense_engines_fetch_is_what_it_was(served, tiny_lm):
    srv, reqs, outside = served
    assert not [k for k in srv.stats if k.startswith("moe.")]
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 24, "max_batch": 3,
        "max_blocks_per_seq": 8})
    fn, args = decode_program(eng)
    out, _ = fn.lower(*args).compile().out_info
    assert out.shape == (3,)                    # the tokens and nothing else
    assert not eng._moe_pending


# ----------------------------------------------------------------- training


def lm_engine(extra=None):
    model, cfg = build_model(
        "gpt2-tiny", hidden_size=32, num_layers=2, num_heads=2,
        vocab_size=64, max_seq_len=64, remat=True, remat_policy="dots",
        fused_loss=True, attention_impl="reference")
    n = len(jax.devices())
    config = {"train_batch_size": 2 * n,
              "train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 3,
                                    "stage3_param_persistence_threshold": 0},
              "bf16": {"enabled": True}, **(extra or {})}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=config, loss_fn=fused_loss_passthrough,
        example_batch={"input_ids": np.zeros((2, 32), np.int32)})
    return engine


def lm_batch(engine, seed):
    rows = engine.config.train_batch_size
    return {"input_ids": np.random.RandomState(seed).randint(
        0, 64, (rows, 32)).astype(np.int32)}


def test_three_train_batches_yield_the_train_spans():
    engine = lm_engine({"steps_per_print": 1})
    for i in range(3):
        engine.train_batch(lm_batch(engine, i))
    ring = list(engine.rec.ring)
    names = {e[0] for e in ring if e[0].startswith("train.")}
    assert names == TRAIN_SPANS
    steps = [e for e in ring if e[0] == "train.step"]
    assert [e[4]["step_num"] for e in steps] == [0, 1, 2]
    parents = {e[0]: e[1] for e in ring}
    assert parents["train.sync"] == "train.step"
    assert parents["train.after_step.pull"] == "train.after_step"
    # the first step compiled inside its dispatch, the others did not
    assert steps[0][4]["d"]["compiles"] >= 1
    assert "compiles" not in steps[2][4]["d"]
    h2d = lm_batch(engine, 0)["input_ids"].nbytes
    assert [e[4]["d"]["train.h2d_bytes"] for e in steps] == [h2d] * 3
    snap = engine.telemetry()
    assert snap["counters"]["train.h2d_bytes"] == 3 * h2d
    assert snap["spans"]["train.sync"]["count"] == 3
    assert engine.samples_per_sec() > 0            # step 2 is past warm-up


def test_train_init_is_first_holds_its_parts_and_the_constructors_compiles():
    engine = lm_engine()
    engine.train_batch(lm_batch(engine, 0))
    ring = list(engine.rec.ring)
    init = next(e for e in ring if e[0] == "train.init")
    before = ring[:ring.index(init)]
    # everything recorded before the constructor ended lies inside it: its
    # three parts, each once, and compiles booked to it or to a part
    parts = [e for e in before if e[0] != "compile"]
    assert [e[0] for e in parts] == ["train.init.params", "train.init.place",
                                     "train.init.opt_state"]
    assert all(e[1] == "train.init" for e in parts)
    compiles = [e for e in before if e[0] == "compile"]
    assert compiles and all(e[1].startswith("train.init") and
                            "step" not in e[4] for e in compiles)
    assert any(e[1] == "train.init.params" and e[4]["phase"] == "backend"
               for e in compiles)                # the model's init program
    assert all(init[2] <= e[2] <= e[3] <= init[3] for e in before)
    # the counters count set-up's compiles like any other: the first
    # step's gains are what came after the constructor
    counted = engine.rec.counters["compiles"]
    in_init = sum(1 for e in compiles
                  if e[4]["phase"] in ("backend", "cache_load"))
    step0 = next(e for e in ring if e[0] == "train.step")
    assert counted == in_init + step0[4]["d"]["compiles"]
    # the step's launch names its program
    dispatch = next(e for e in ring if e[0] == "train.dispatch")
    assert dispatch[4] == {"program": "jit_train_step"}
    assert any(e[0] == "compile" and e[1] == "train.dispatch"
               and e[4] == {"phase": "backend",
                            "fun_name": "jit(train_step)", "step": 0}
               for e in ring)


def test_wall_clock_breakdown_logs_parts_and_the_monitor_gets_them(tmp_path):
    class Capture(logging.Handler):
        def __init__(self):
            super().__init__()
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    from deepspeed_tpu.utils.logging import logger
    cap = Capture()
    logger.addHandler(cap)
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(), example_batch=random_batch(4), config={
                "train_batch_size": 8, "steps_per_print": 2,
                "wall_clock_breakdown": True,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "csv_monitor": {"enabled": True, "job_name": "t",
                                "output_path": str(tmp_path)}})
        for i in range(4):
            engine.train_batch(random_batch(8, seed=i))
    finally:
        logger.removeHandler(cap)
    parts = [l for l in cap.lines if "train.sync:" in l]
    assert len(parts) == 2                              # steps 2 and 4
    for name in ("train.prepare", "train.h2d", "train.dispatch",
                 "train.after_step"):
        assert f"{name}: " in parts[-1]
    assert re.search(r"train\.step: \d+\.\d\dms", parts[-1])
    assert sum("samples/sec" in l for l in cap.lines) == 2
    written = os.listdir(os.path.join(str(tmp_path), "t"))
    assert "Train_Telemetry_train.sync_self_ms.csv" in written


def test_autotuner_metric_file_gets_its_throughput(tmp_path, monkeypatch):
    metric = tmp_path / "metric.json"
    monkeypatch.setenv("DS_AUTOTUNING_METRIC_FILE", str(metric))
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(), example_batch=random_batch(4), config={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "autotuning": {"enabled": True, "end_profile_step": 4}})
    with pytest.raises(SystemExit):
        for i in range(10):
            engine.train_batch(random_batch(8, seed=i))
    out = json.load(open(metric))
    assert out["steps"] == 4 and out["throughput"] > 0



# ------------------------------- the start, booked: init, steps, waits, rests


def us(ns: int) -> int:
    """A stamp cut to whole microseconds, as the recorder cuts it before
    it takes a difference: sums of such differences telescope exactly."""
    return ns // 1000


KINDS = ("serve", "train")
WAITS = {"serve": {"serve.decode.fetch", "serve.prefill.fetch"},
         "train": {"train.sync"}}
PHASES_AND_REST = ("compile.trace_us", "compile.lower_us",
                   "compile.backend_us", "compile.cache_load_us",
                   telemetry.FIRST_CALL_REST)


def start_engine(kind: str, tiny_lm):
    """An engine of ``kind`` at a tiny size run for a few steps (the
    serving one with chunks of two shapes): its recorder and its own
    ``telemetry()``."""
    if kind == "serve":
        cfg, params = tiny_lm
        srv = ServingEngine(cfg, params, serving={
            "block_size": 16, "pool_blocks": 24, "max_batch": 3,
            "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32})
        rng = np.random.default_rng(0)
        for n, k in [(40, 6), (70, 9), (20, 5)]:
            srv.submit(list(rng.integers(1, 64, size=n)), max_new_tokens=k)
        srv.run_until_idle()
        return srv.rec, srv.telemetry
    engine = lm_engine()
    for i in range(3):
        engine.train_batch(lm_batch(engine, i))
    return engine.rec, engine.telemetry


@pytest.fixture(scope="module", params=KINDS)
def started(request, tiny_lm):
    rec, snapshot = start_engine(request.param, tiny_lm)
    return request.param, rec, snapshot()


def steps_with_their_entries(rec, kind):
    """[(step entry, the entries recorded since the step before)]: a span
    is written when it ends, so a step's children come before it."""
    out, inside = [], []
    for e in rec.ring:
        if e[0] == f"{kind}.step":
            out.append((e, inside))
            inside = []
        else:
            inside.append(e)
    return out


def test_from_the_constructor_to_any_step_init_steps_and_outside_are_the_wall(
        started):
    kind, rec, snap = started
    ring = list(rec.ring)
    init = next(e for e in ring if e[0] == f"{kind}.init")
    steps = [e for e in ring if e[0] == f"{kind}.step"]
    c = snap["counters"]
    assert len(steps) >= 3 and rec.dropped == 0
    assert snap["t0_ns"] == rec.t0_ns <= init[2]
    assert c[f"{kind}.init_us"] == us(init[3]) - us(init[2])
    assert c[f"{kind}.step_us"] == sum(us(e[3]) - us(e[2]) for e in steps)
    # the gaps an outside observer sees: before the constructor's span,
    # between it and the first step, between two steps
    edges = [(rec.t0_ns, rec.t0_ns), (init[2], init[3])] + \
        [(e[2], e[3]) for e in steps]
    gaps = [us(b[0]) - us(a[1]) for a, b in zip(edges, edges[1:])]
    assert all(g >= 0 for g in gaps)
    step_us = 0
    for k, e in enumerate(steps):
        wall = us(e[2]) - us(rec.t0_ns)
        # a step's own time is in the gains it carries, so a reader that
        # sums d over the steps before this one has their time
        assert step_us == sum(s[4]["d"][f"{kind}.step_us"]
                              for s in steps[:k])
        assert c[f"{kind}.init_us"] + step_us + sum(gaps[:k + 2]) == wall
        step_us += us(e[3]) - us(e[2])


def outermost(spans):
    """Of ring entries, those that lie inside no other of them."""
    return [e for e in spans
            if not any(o is not e and o[2] <= e[2] and e[3] <= o[3]
                       for o in spans)]


def test_inside_a_step_phases_rest_wait_and_host_are_its_time(started):
    kind, rec, snap = started
    seen = dict.fromkeys(PHASES_AND_REST + (f"{kind}.wait_us",), 0)
    for step, inside in steps_with_their_entries(rec, kind):
        d = step[4]["d"]
        assert d[f"{kind}.step_us"] == us(step[3]) - us(step[2])
        # the spans that book their own time: the waits, and whichever a
        # compile phase fell in (a compile entry names it as its parent)
        compiled = {e[1] for e in inside if e[0] == "compile"}
        inside = [e for e in inside if step[2] <= e[2]]
        booking = [e for e in inside
                   if e[0] in compiled or e[0] in WAITS[kind]]
        booked = sum(d.get(k, 0) for k in seen)
        assert booked == sum(us(e[3]) - us(e[2]) for e in outermost(booking))
        host = d[f"{kind}.step_us"] - booked
        assert host >= 0
        # the rest of a first call is never negative, and a step in which
        # nothing compiled books none and counts no first call
        assert d.get(telemetry.FIRST_CALL_REST, 0) >= 0
        if compiled:
            assert d["compile.first_calls"] == len(
                [e for e in inside if e[0] in compiled])
        else:
            assert not any(k.startswith("compile") for k in d)
            assert d[f"{kind}.wait_us"] == sum(
                us(e[3]) - us(e[2]) for e in inside if e[0] in WAITS[kind])
        for k in seen:
            seen[k] += d.get(k, 0)
    # every step waited for the device, the first ones compiled
    assert seen[f"{kind}.wait_us"] > 0 and seen["compile.lower_us"] > 0
    assert seen[telemetry.FIRST_CALL_REST] > 0
    # and what the steps did not gain, the constructor's span did (the
    # model's init program; a serving engine's few helpers may be in
    # JAX's memory from an earlier test)
    c = snap["counters"]
    in_init = {k: c.get(k, 0) - seen[k] for k in PHASES_AND_REST}
    assert all(v >= 0 for v in in_init.values())
    assert sum(in_init.values()) <= c[f"{kind}.init_us"]
    assert sum(in_init.values()) > 0 or kind == "serve"


def test_programs_has_a_row_a_function_and_shape_with_what_it_cost(started):
    kind, rec, snap = started
    rows = snap["programs"]
    assert len({(r["fun_name"], r["shape"]) for r in rows}) == len(rows)
    c = snap["counters"]
    for part in ("trace_us", "lower_us", "backend_us", "cache_load_us",
                 "saved_us"):
        assert sum(r[part] for r in rows) == c.get("compile." + part, 0)
    assert sum(r["rest_us"] for r in rows) == c[telemetry.FIRST_CALL_REST]
    assert sum(r["compiles"] for r in rows) == c["compiles"]
    assert sum(r["hit"] for r in rows) == c.get("compile.cache_hits", 0)
    assert "compile.programs_dropped" not in c
    by = {(r["fun_name"], r["shape"]): r for r in rows}
    if kind == "serve":
        # 40, 70 and 20 tokens in chunks of 32: calls of 32, 8, 32, 32, 6
        # and 20 tokens in that order, in programs of 32 and of 16 rows: a
        # row is keyed by the tokens of the call that compiled it
        # (the mixed program of that shape: the chunk's rows and the lanes')
        assert not any(k[0] == "jit(_prefill)" for k in by)
        prefills = {k[1]: r for k, r in by.items() if k[0] == "jit(_mixed)"}
        assert set(prefills) == {32, 8}
        assert all(r["span"] == "serve.prefill.dispatch" and r["compiles"]
                   == 1 and r["step"] is not None and r["rest_us"] > 0
                   for r in prefills.values())
        assert by[("jit(_decode)", None)]["span"] == "serve.decode.dispatch"
        step = by[("jit(_decode)", None)]
    else:
        step = by[("jit(train_step)", None)]
        assert step["span"] == "train.dispatch" and step["step"] == 0
    # a program's row holds its whole trace, the helpers traced inside it
    # too, short ones the ring leaves out with them
    entries = [e for e in rec.ring if e[0] == "compile"
               and e[4]["phase"] == "trace"
               and e[4]["fun_name"] in step["fun_name"]]
    # (each of a few hundred parts cut to whole microseconds)
    assert step["trace_us"] >= 0.99 * max(e[3] - e[2] for e in entries) / 1e3
    assert step["lower_us"] > 0 and step["compiles"] == 1
    # the constructor's compiles fell in no step
    outside = [r for r in rows if r["step"] is None]
    assert all(r["span"].startswith(f"{kind}.init") for r in outside)
    assert outside or kind == "serve"


@pytest.mark.parametrize("kind", KINDS)
def test_what_the_ring_drops_the_counters_and_the_programs_keep(kind,
                                                                tiny_lm,
                                                                monkeypatch):
    # the engines make their recorder themselves: give it a small ring
    small = telemetry.Recorder.__init__.__defaults__
    monkeypatch.setattr(telemetry.Recorder.__init__, "__defaults__",
                        (8,) + small[1:])
    rec, snapshot = start_engine(kind, tiny_lm)
    snap = snapshot()
    assert rec.ring.maxlen == 8 and snap["ring_dropped"] > 0
    names = {e[0] for e in rec.ring}
    assert f"{kind}.init" not in names             # pushed out long ago
    assert snap["counters"][f"{kind}.init_us"] > 0
    assert snap["counters"][f"{kind}.step_us"] > sum(
        us(e[3]) - us(e[2]) for e in rec.ring if e[0] == f"{kind}.step")
    held = {"serve": "jit(_decode)", "train": "jit(train_step)"}[kind]
    assert not any(e[0] == "compile" and e[4]["fun_name"] == held
                   for e in rec.ring)
    row = next(r for r in snap["programs"] if r["fun_name"] == held)
    assert row["lower_us"] > 0 and row["compiles"] == 1


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in ``tmp_path``, its thresholds
    lowered so that tiny programs are written; as it was, afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
             "jax_persistent_cache_min_entry_size_bytes": -1,
             "jax_persistent_cache_min_compile_time_secs": 0.0}
    before = {k: getattr(jax.config, k) for k in names}
    try:
        for k, v in names.items():
            jax.config.update(k, v)
        cc.reset_cache()
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


@pytest.mark.parametrize("kind", KINDS)
def test_a_second_engine_finds_the_firsts_programs_in_the_cache(
        kind, tiny_lm, persistent_cache):
    rec, snapshot = start_engine(kind, tiny_lm)
    cold = snapshot()["counters"]
    assert cold.get("compile.cache_hits", 0) == 0
    assert cold["compile.backend_compiles"] > 0 and cold[
        "compile.backend_us"] > 0
    # the thresholds are down: every program JAX looked up, it wrote
    assert cold["compile.cache_misses"] == cold["compile.cache_requests"] \
        <= cold["compile.backend_compiles"]
    assert cold["compile.saved_us"] == 0 == cold["compile.cache_load_us"]
    rec, snapshot = start_engine(kind, tiny_lm)
    snap = snapshot()
    warm = snap["counters"]
    assert warm["compile.cache_hits"] == warm["compile.cache_requests"] > 0
    # (an engine's compile counters start at zero: a served start reads 0)
    assert warm["compile.backend_us"] == 0 == warm[
        "compile.backend_compiles"] == warm["compile.cache_misses"]
    assert warm["compile.cache_load_us"] > 0
    assert warm["compiles"] == warm["compile.cache_hits"]
    # JAX keeps an entry's compile time in whole seconds, cut: a program
    # that compiled in under one says nothing was saved (the next test
    # feeds the listener an entry that took three)
    assert warm["compile.saved_us"] % 1_000_000 == 0
    assert warm["compile.saved_us"] <= cold["compile.backend_us"]
    assert all(r["hit"] == r["compiles"] for r in snap["programs"])
    # tracing and lowering are paid again, cache or no cache
    assert warm["compile.lower_us"] > 0.2 * cold["compile.lower_us"]


def test_a_hit_saves_what_its_entry_says_the_compile_took():
    rec = telemetry.Recorder("t", keep=False)
    base, cache = "/jax/core/compile/", "/jax/compilation_cache/"
    with rec.span("serve.prefill", tokens=96):
        with rec.span("serve.prefill.dispatch"):
            telemetry._on_compile(base + "jaxpr_to_mlir_module_duration",
                                  0.002, fun_name="_prefill")
            telemetry._on_cache_event(cache + "compile_requests_use_cache")
            telemetry._on_cache_event(cache + "cache_hits")
            # an entry that took 3 s (JAX keeps whole seconds), found in
            # 0.25 s: JAX reports 2.75 s saved, then the retrieval
            telemetry._on_compile(cache + "compile_time_saved_sec", 2.75)
            telemetry._on_compile(cache + "cache_retrieval_time_sec", 0.25)
            telemetry._on_compile(base + "backend_compile_duration", 0.26,
                                  fun_name="jit(_prefill)")
            # the next program's entry was never written: compiled
            telemetry._on_cache_event(cache + "compile_requests_use_cache")
            telemetry._on_compile(base + "backend_compile_duration", 0.001,
                                  fun_name="jit(tiny)")
            telemetry._on_cache_event(cache + "some_other_event")
    c = rec.counters
    assert (c["compile.cache_requests"], c["compile.cache_hits"],
            c["compile.backend_compiles"], c["compiles"]) == (2, 1, 1, 2)
    assert "compile.cache_misses" not in c
    assert c["compile.saved_us"] == 3_000_000
    assert c["compile.cache_load_us"] == 260_000
    rows = {r["fun_name"]: r for r in rec.snapshot()["programs"]}
    assert rows["jit(_prefill)"] == {
        "fun_name": "jit(_prefill)", "shape": 96, "trace_us": 0,
        "lower_us": 2000, "backend_us": 0, "cache_load_us": 260_000,
        "saved_us": 3_000_000, "rest_us": 0, "compiles": 1, "hit": 1,
        "span": "serve.prefill.dispatch", "step": None}
    assert rows["jit(tiny)"]["backend_us"] == 1000 and \
        rows["jit(tiny)"]["hit"] == 0 and rows["jit(tiny)"]["shape"] == 96
    # outside every span of a recorder the events are nobody's
    telemetry._on_cache_event(cache + "cache_hits")
    assert c["compile.cache_hits"] == 1


def test_rows_beyond_the_bound_are_counted_not_kept(monkeypatch):
    monkeypatch.setattr(telemetry, "PROGRAM_ROWS", 2)
    rec = telemetry.Recorder("t", keep=False)
    with rec.span("s"):
        for i in range(4):
            telemetry._on_compile(
                "/jax/core/compile/backend_compile_duration", 0.001,
                fun_name=f"jit(f{i})")
        # a row that is there still takes what its program costs again
        telemetry._on_compile("/jax/core/compile/backend_compile_duration",
                              0.001, fun_name="jit(f0)")
    assert [r["fun_name"] for r in rec.snapshot()["programs"]] == [
        "jit(f0)", "jit(f1)"]
    assert rec.programs[("jit(f0)", None)]["compiles"] == 2
    assert rec.counters["compile.programs_dropped"] == 2
    assert rec.counters["compiles"] == 5


def test_a_trace_nothing_compiled_still_gets_its_row_when_its_span_ends():
    rec = telemetry.Recorder("t")
    f, x = slow_to_trace(120), jnp.ones((5,))
    with rec.span("s.lowering"):
        f.trace(x).lower()
    (row,) = rec.snapshot()["programs"]
    assert "slow_120" in row["fun_name"] and row["span"] == "s.lowering"
    assert row["trace_us"] > 0 and row["lower_us"] > 0
    assert row["compiles"] == 0 == row["backend_us"]
    assert row["rest_us"] == rec.counters[telemetry.FIRST_CALL_REST]
    assert rec.counters["compile.first_calls"] == 1
    assert "compiles" not in rec.counters


def test_what_a_thread_has_booked_is_kept_no_longer_than_a_span_can_ask():
    rec = telemetry.Recorder("t", keep=False)
    for n in range(3):
        with rec.step_span("train.step", step_num=n):
            for _ in range(400):
                with rec.span("train.sync"):
                    pass
    waits = sum(us(e[3]) - us(e[2]) for e in rec.ring if e[0] == "train.sync")
    assert rec.counters["train.wait_us"] == waits
    # 1 200 waits were booked; what lies before the open step went
    assert len(telemetry._stack().booked) <= 256 + 400


def test_a_wait_inside_a_span_that_compiled_is_booked_once():
    rec = telemetry.Recorder("t", keep=False)
    with rec.step_span("train.step", step_num=0):
        with rec.span("train.dispatch"):
            telemetry._on_compile(
                "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.0,
                fun_name="f")
            with rec.span("train.sync"):
                pass
    c = rec.counters
    by = {e[0]: us(e[3]) - us(e[2]) for e in rec.ring}
    assert c["train.wait_us"] == by["train.sync"]
    assert c[telemetry.FIRST_CALL_REST] + c["train.wait_us"] == \
        by["train.dispatch"] <= by["train.step"] == c["train.step_us"]

# ------------------------------------------------------------ device scopes


def instructions(compiled) -> list:
    """The optimized module's instructions in order: result shape and
    layout, opcode, operands and attributes. Metadata is cut out, and so
    are the numbers the compiler hands out to equal names (``%copy.39``):
    they follow how many instructions were made and merged on the way, and
    a scope that keeps two equal ops from merging early moves them."""
    return [re.sub(r"(%[A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)(?:\.\d+)+", r"\1",
                   re.sub(r", metadata=\{[^}]*\}", "", line)).strip()
            for line in compiled.as_text().splitlines() if " = " in line]


def train_step_program(engine):
    step_fn, _ = engine._active_train_step()
    gas = engine.config.gradient_accumulation_steps
    micros = {"input_ids": jax.ShapeDtypeStruct(
        (gas, engine.config.train_batch_size // gas, 32), jnp.int32)}
    return step_fn, (engine.state, micros, jax.random.PRNGKey(0),
                     jnp.float32(1e-3))


def decode_program(srv):
    return srv._decode_fn, (srv.params, srv.pools, srv._lanes.buf,
                            srv._dec_out, srv._pre_out)


def without_scopes(monkeypatch):
    """Every ``jax.named_scope`` of the program (and flax's) made a no-op."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(DeepSpeedEngine, "_finalize_step",
                        DeepSpeedEngine._finalize_step.__wrapped__)


def test_scopes_leave_the_train_steps_instructions_alone(monkeypatch):
    fn, args = train_step_program(lm_engine())
    scoped = fn.lower(*args).compile()
    assert "block.attn" in scoped.as_text() and "optimizer" in \
        scoped.as_text()
    with monkeypatch.context() as m:
        without_scopes(m)
        fn, args = train_step_program(lm_engine())
        bare = fn.lower(*args).compile()
    assert "block.attn" not in bare.as_text()
    assert instructions(scoped) == instructions(bare)


def test_scopes_leave_the_decode_steps_instructions_alone(tiny_lm,
                                                          monkeypatch):
    cfg, params = tiny_lm
    serving = {"block_size": 16, "pool_blocks": 24, "max_batch": 3,
               "max_blocks_per_seq": 8}
    fn, args = decode_program(ServingEngine(cfg, params, serving=serving))
    scoped = fn.lower(*args).compile()
    assert "kv_write" in scoped.as_text()
    with monkeypatch.context() as m:
        without_scopes(m)
        fn, args = decode_program(ServingEngine(cfg, params,
                                                serving=serving))
        bare = fn.lower(*args).compile()
    assert "kv_write" not in bare.as_text()
    assert instructions(scoped) == instructions(bare)


def compiled_scopes(fn, *args) -> set:
    """The scope paths (``telemetry.scope_of``) of the ``op_name`` of every
    instruction of the program ``fn`` compiles for ``args``."""
    text = fn.lower(*args).compile().as_text()
    return {telemetry.scope_of(op)
            for op in re.findall(r'op_name="([^"]*)"', text)}


def test_every_scope_of_the_train_step_is_in_its_compiled_program():
    fn, args = train_step_program(lm_engine(
        {"zero_optimization": {"stage": 3, "zero_quantized_weights": True,
                               "stage3_param_persistence_threshold": 0}}))
    found = compiled_scopes(fn, *args)
    for scope in ("embed", "layers", "block.attn", "block.mlp", "head",
                  "loss", "grad_accum", "optimizer", "zero.scatter",
                  "backward:block.attn", "backward:block.mlp",
                  "recompute:block.mlp", "backward:loss"):
        assert scope in found, scope
    assert any("zero.gather" in p for p in found)
    for p in found:
        for seg in p.split(":")[-1].split("."):
            assert not seg or any(seg in s.split(".")
                                  for s in telemetry.SCOPES), p


def test_every_scope_of_the_decode_step_is_in_its_compiled_program(tiny_lm):
    cfg, params = tiny_lm
    srv = ServingEngine(cfg, params, serving={
        "block_size": 16, "pool_blocks": 24, "max_batch": 3,
        "max_blocks_per_seq": 8})
    fn, args = decode_program(srv)
    found = compiled_scopes(fn, *args)
    for scope in ("embed", "layers", "block.attn.qkv", "block.attn.kv_write",
                  "block.attn.attend", "block.attn.out", "block.mlp",
                  "head", "sample"):
        assert scope in found, scope


def test_scope_of_reads_jaxs_op_names():
    assert telemetry.scope_of(
        "jit(train_step)/while/body/closed_call/transpose(jvp(Transformer))"
        "/while/body/closed_call/blocks.body/checkpoint/blocks/block.attn/"
        "attn_qkv/dot_general") == "backward:block.attn"
    assert telemetry.scope_of(
        "jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/blocks/block.mlp/mlp_fc/dot_general") == \
        "recompute:block.mlp"
    assert telemetry.scope_of(
        "jit(_decode)/while/body/closed_call/block.attn/kv_write/"
        "scatter") == "block.attn.kv_write"
    assert telemetry.scope_of("jit(train_step)/optimizer/add") == "optimizer"
    assert telemetry.scope_of(
        "jit(train_step)/transpose(jvp(Transformer))/layers/while/body/"
        "squeeze") == "backward:layers"
    assert telemetry.scope_of(
        "jit(_decode)/layers/while/body/closed_call/block.mlp/add") == \
        "block.mlp"
    assert telemetry.scope_of("jit(_decode)/jit(clip)/min") == ""
    assert not hasattr(telemetry, "scope_paths")
    assert not hasattr(telemetry, "hlo_op_names")
