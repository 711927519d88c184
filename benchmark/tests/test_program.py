"""``benchmark/clock.py`` and ``benchmark/program.py``: the device plane's
clock against the host plane's, the idle time split by what the host was
doing, set-up by phase. By hand, on a few steps recorded on the chip
(``data/trace_*_clock.json.gz``, PR 38: cut by ``program.py --cut`` from runs
of this PR's tree, with the runtime's events) and on a CPU rehearsal."""
import dataclasses
import gzip
import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from benchmark import clock, harness, program, reduce, spans
from benchmark.tests.test_spans import TINY_SERVE
from benchmark.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6          # nanoseconds
TPU = "/device:TPU:0"
METRICS = sorted(fn[:-5] for fn in os.listdir(program.METRICS_DIR))


def recorded(kind: str) -> dict:
    return json.load(gzip.open(
        os.path.join(DATA, f"trace_{kind}_clock.json.gz"), "rt"))


# ------------------------------------------------------------------ the clock


def hand_made(offset_ms: float = 0.0, steps: int = 3, ppm: float = 0.0):
    """Three serve steps of 20 ms on the HOST's clock. In step k (base 20k):
    dispatch 1..2 launches ``jit__decode``, which runs 2.5..17 on the device;
    the fetch 2..18 waits for it. The runtime enqueues it at 1.9 and reads
    its completion from 17.2 (callbacks from 17.4). Step 1 (6, 11, ...) also launches
    ``jit__prefill`` at 0.2..0.6, which runs 0.9..2.4. The device plane's
    timestamps are the host's plus ``offset_ms``, and plus ``ppm`` millionths
    of the time since 0."""
    runs, prog, rt = [], [], {n: [] for n in clock.RUNTIME_EVENTS}
    rid = 100
    for k in range(steps):
        b = 20 * k
        offset_ms += 20 * ppm * 1e-6
        launches = [("serve.decode.dispatch", "jit__decode", 1, 2, 2.5, 17,
                     1.9, 17.2)]
        if k % 5 == 1:
            launches.insert(0, ("serve.prefill.dispatch", "jit__prefill",
                                0.2, 0.6, 0.9, 2.4, 0.5, 2.45))
        prog.append(("serve.step", b * MS, 19 * MS, {"step": k}, "py"))
        for name, p, a, e, ra, re_, enq, seen in launches:
            rid += 1
            prog.append((name, (b + a) * MS, (e - a) * MS, {"program": p},
                         "py"))
            runs.append((f"{p}(123)", (b + ra + offset_ms) * MS,
                         (re_ - ra) * MS, rid))
            rt[clock.LAUNCHED].append(((b + enq) * MS, 0.03 * MS, rid, "q"))
            rt[clock.DONE_READ].append(((b + seen) * MS, 0.2 * MS, -1, "f"))
            rt[clock.DONE].append(((b + seen + 0.2) * MS, 0.05 * MS, rid,
                                   "f"))
        prog.append(("serve.decode.fetch", (b + 2) * MS, 16 * MS, {}, "py"))
    prog.sort(key=lambda s: (s[1], -s[2]))
    return runs, prog, rt


@pytest.mark.parametrize("offset_ms", [0.0, 1.3, -1.5])
def test_the_bracket_holds_the_offset_and_the_runtime_tightens_it(offset_ms):
    runs, prog, rt = hand_made(offset_ms)
    loose = clock.offset(runs, prog)
    # launch spans: a run starts 1.5 ms after its dispatch began at least
    # (0.7 for the prefill); the fetch ends 1 ms after the run
    assert loose["upper_ns"] == pytest.approx((offset_ms + 0.7) * MS)
    assert loose["lower_ns"] == pytest.approx((offset_ms - 1.0) * MS)
    assert loose["pairs"] == 4 and loose["waits"] == 3
    tight = clock.offset(runs, prog, rt)
    # enqueued 0.4 ms before the prefill's run; completion read 0.05 after
    assert tight["upper_ns"] == pytest.approx((offset_ms + 0.4) * MS)
    assert tight["lower_ns"] == pytest.approx((offset_ms - 0.05) * MS)
    assert tight["by"] == {"upper": clock.LAUNCHED,
                           "lower": f"{clock.DONE_READ} / {clock.DONE}"}
    for c in (loose, tight):
        assert c["lower_ns"] <= offset_ms * MS <= c["upper_ns"]
        assert c["offset_ns"] == (c["lower_ns"] + c["upper_ns"]) / 2
        assert c["violations"] == 0
    assert tight["slope"] == 0 == tight["drift_ns"]       # too few to tell
    shifted = clock.shift(prog, tight)
    assert [s[0] for s in shifted] == [s[0] for s in prog]
    assert shifted[0][1] - prog[0][1] == tight["offset_ns"]


@pytest.mark.parametrize("ppm", [40.0, -25.0, 66.0])
def test_a_drift_wider_than_the_bracket_is_taken_out_first(ppm):
    # 250 steps = 5 s: the clocks move 200 / 125 us against each other,
    # where the runtime's events bracket an offset to 0.45 ms
    runs, prog, rt = hand_made(-1.5, steps=250, ppm=ppm)
    c = clock.offset(runs, prog, rt)
    assert c["slope"] == pytest.approx(ppm * 1e-6, rel=0.02)
    assert c["drift_ns"] == pytest.approx(ppm * 1e-6 * 5e9, rel=0.03)
    assert c["upper_ns"] - c["lower_ns"] == pytest.approx(0.45 * MS, rel=0.02)
    assert c["violations"] == 0
    # the offset applied follows the clocks: at either end it lies inside
    # what that end's own steps bracket
    for k in (0, 249):
        true = -1.5 + 20 * (k + 1) * ppm * 1e-6
        got = clock.offset_at(c, 20 * k * MS) / MS
        assert true - 0.05 - 0.01 <= got <= true + 0.4 + 0.01
    first, last = clock.shift(prog, c)[0], clock.shift(prog, c)[-1]
    assert (last[1] - prog[-1][1]) - (first[1] - prog[0][1]) == \
        pytest.approx(c["slope"] * (prog[-1][1] - prog[0][1]))


def test_a_launch_or_a_run_the_trace_cut_off_pairs_with_nothing():
    runs, prog, rt = hand_made(1.3)
    # the trace began after step 0's launch and ended before step 2's run
    prog = [s for s in prog if not (s[0] == "serve.decode.dispatch"
                                    and s[1] < 5 * MS)]
    runs = [r for r in runs if r[1] < 42 * MS]
    pairs = clock.pair_launches(runs, prog)
    assert [(s[0], round((r[1] - s[1]) / MS, 1)) for s, r in pairs] == [
        ("serve.prefill.dispatch", 2.0), ("serve.decode.dispatch", 2.8)]
    c = clock.offset(runs, prog, rt)
    assert c["pairs"] == 2 and c["violations"] == 0
    assert c["lower_ns"] <= 1.3 * MS <= c["upper_ns"]
    # spans that name no program (the parent commit's) align nothing
    bare = [(n, a, d, {}, t) for n, a, d, _, t in prog]
    assert clock.offset(runs, bare, rt) is None


@pytest.mark.parametrize("kind", ["serve", "train"])
@pytest.mark.parametrize("put_in_ms", [0.7, -2.3])
def test_a_shift_put_into_a_recorded_trace_is_recovered(kind, put_in_ms):
    d = recorded(kind)
    before = program.from_json(d)["clock"]
    assert before["violations"] == 0 and before["pairs"] >= 2
    assert before["by"]["upper"] == clock.LAUNCHED     # the runtime's events
    width = before["upper_ns"] - before["lower_ns"]
    assert 0 < width < 0.5 * MS
    moved = dict(d, runs=[(n, a + put_in_ms * MS, dur, rid)
                          for n, a, dur, rid in d["runs"]],
                 devices={p: [(n, r, a + put_in_ms * MS, dur)
                              for n, r, a, dur in ops]
                          for p, ops in d["devices"].items()})
    after = program.from_json(moved)["clock"]
    for key in ("offset_ns", "lower_ns", "upper_ns"):
        assert after[key] - before[key] == pytest.approx(put_in_ms * MS,
                                                         abs=1.0)
    assert after["violations"] == 0 and after["pairs"] == before["pairs"]
    # without the runtime's events the spans alone bracket it, wider
    pt = spans.ProgramTrace.from_json(dict(moved, op_names={}))
    loose = clock.offset(moved["runs"], pt.spans)
    assert loose["lower_ns"] <= after["lower_ns"] <= after["upper_ns"] \
        <= loose["upper_ns"]
    assert loose["violations"] == 0


# ------------------------------------------------------------ the idle split


def test_deepest_segments_by_hand():
    sp = [("step", 0, 100, {}, "t"), ("admit", 10, 20, {}, "t"),
          ("alloc", 15, 5, {}, "t"), ("event", 40, 0, {}, "t"),
          ("fetch", 50, 10, {}, "t"), ("other", 55, 60, {}, "u"),
          ("submit", 200, 10, {}, "t")]
    segs = program.deepest_segments(sp)
    assert segs == [(0, 10, "step"), (10, 15, "admit"), (15, 20, "alloc"),
                    (20, 30, "admit"), (30, 50, "step"), (50, 55, "fetch"),
                    # another thread's span, begun later, is the deepest
                    # from then on, past the end of the step
                    (55, 115, "other"), (200, 210, "submit")]
    got, rest = program._overlaps([(5, 17), (25, 52), (150, 205)], segs)
    assert got == {"step": 5 + 20, "admit": 5 + 5, "alloc": 2, "fetch": 2,
                   "submit": 5}
    assert rest == 50


def test_the_three_parts_by_hand():
    """One chip, a window of 40 ms, two steps (``hand_made``, the device
    1.3 ms ahead). On the device's clock step k's run is busy 2.5..17 of
    base 20k + 1.3, and step 1's prefill run 0.9..2.4."""
    runs, prog, rt = hand_made(1.3)
    runs, prog = [r for r in runs if r[1] < 41 * MS], \
        [s for s in prog if s[1] < 40 * MS]
    ops = [(n.split("(")[0], "", a, d) for n, a, d, _ in runs]
    trace = Trace({TPU: ops}, [("window", 0.0, 40 * MS)])
    p = program.align({"kind": "serve", "runs": runs, "runtime": rt,
                       "trace": spans.ProgramTrace(trace, {}, prog),
                       "shifted": None})
    off = p["clock"]["offset_ns"] / MS            # 1.3 + (0.4 - 0.05) / 2
    assert off == pytest.approx(1.475)
    parts = {k: v / MS for k, v in program.idle_parts_ns(p).items()}
    idle = 40 - (14.5 + 14.5 + 1.5)
    assert sum(parts.values()) == pytest.approx(idle)
    # after the shift a step spans base + off .. base + 19 + off, its fetch
    # base + 2 + off .. base + 18 + off. The device idles 0 .. 3.8,
    # 18.3 .. 22.2, 23.7 .. 23.8 (between step 1's two runs) and 38.3 .. 40.
    # Outside a step: 0 .. off, and the 1 ms between the two steps
    assert parts["outside_step"] == pytest.approx(off + 1)
    # in a fetch: from its start to the first run's (3.8), from a run's end
    # to the fetch's (18 + off - 18.3, twice), and between the two runs
    assert parts["in_fetch"] == pytest.approx(
        (3.8 - (2 + off)) + 2 * (18 + off - 18.3) + 0.1)
    assert parts["before_launch"] == pytest.approx(
        idle - parts["outside_step"] - parts["in_fetch"])
    by = program.idle_by_program_span(p)
    assert set(by) <= {"between_spans", "serve.step", "serve.decode.fetch",
                       "serve.decode.dispatch", "serve.prefill.dispatch"}


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_parts_partition_the_idle_time_of_a_recorded_trace(kind):
    p = program.from_json(recorded(kind))
    parts = program.idle_parts_ns(p)
    busy, window = reduce.busy_and_window_s(p["trace"].trace)
    assert sum(parts.values()) / 1e9 == pytest.approx(window - busy,
                                                      rel=1e-9)
    assert all(v >= 0 for v in parts.values())
    by = program.idle_by_program_span(p)
    assert sum(by.values()) == pytest.approx(sum(parts.values()))
    names = {k[:-len(program.MID_RUN)] if k.endswith(program.MID_RUN) else k
             for k in by}
    assert all(n == "between_spans" or n.startswith(kind + ".")
               for n in names)
    # the old reader's window and idle time are what they were
    assert reduce.idle_share({}, {"trace": p["trace"].trace}) == \
        pytest.approx(100 * (1 - busy / window))
    if kind == "serve":
        # with the clocks left apart nearly all of it is booked to a fetch
        apart = dict(p, shifted=spans.ProgramTrace(
            Trace(p["trace"].trace.devices, p["trace"].trace.host), {},
            p["trace"].spans))
        del apart["idle_by_span"], apart["idle_parts"]
        unshifted = program.idle_parts_ns(apart)
        assert unshifted["in_fetch"] > 0.8 * sum(unshifted.values())
        assert parts["before_launch"] > 3 * unshifted["before_launch"]
        assert parts["in_fetch"] > 0.25 * sum(parts.values())


def test_breakdown_and_checks_of_a_recorded_trace():
    p = program.from_json(recorded("serve"))
    obs = {"program": p, "trace": p["trace"].trace,
           "counters": {"traced_steps": 4}}
    b = program.breakdown(obs)
    assert set(b) == {"clock", "idle_gaps_by_program_span"}
    assert set(b["clock"]) == {"offset_ms", "lower_ms", "upper_ms",
                               "drift_ms", "pairs", "violations"}
    assert b["clock"]["violations"] == 0
    assert b["clock"]["lower_ms"] <= b["clock"]["offset_ms"] \
        <= b["clock"]["upper_ms"]
    rows = b["idle_gaps_by_program_span"]
    assert 1 <= len(rows) <= 10 and rows == sorted(rows,
                                                   key=lambda r: -r[1])
    checks = program.checks(obs)
    assert len(checks) == 2 and all(checks.values())
    ms = {m: program.REDUCERS["program_idle_ms"](
        {"part": m, "per": "traced_steps"}, obs)
        for m in (program.BEFORE_LAUNCH, program.IN_FETCH,
                  program.OUTSIDE_STEP)}
    busy, window = reduce.busy_and_window_s(p["trace"].trace)
    assert sum(ms.values()) == pytest.approx(1e3 * (window - busy) / 4)
    host = program.REDUCERS["program_host_ms"](
        {"prefix": "serve.", "skip": [".fetch"],
         "skip_names": ["serve.submit"], "per": "traced_steps"}, obs)
    assert 0.3 < host < 5.0


def test_a_capture_with_a_hole_in_its_device_plane_splits_nothing(capsys):
    # seen once on the chip (PR 38): half the device's runs missing, the rest
    # seconds away from their launches. No offset fits; nothing is split
    d = recorded("serve")
    runs = sorted(d["runs"], key=lambda r: r[1])
    late = [(n, a + 3e9, dur, rid) for n, a, dur, rid in runs[len(runs) // 2:]]
    p = program.from_json(dict(d, runs=runs[:len(runs) // 2] + late))
    assert p["clock"]["violations"] > 0 and p["shifted"] is None
    assert "device plane is not whole" in capsys.readouterr().out
    obs = {"program": p, "trace": p["trace"].trace,
           "counters": {"traced_steps": 4}}
    assert program.idle_parts_ns(p) is None
    assert program.REDUCERS["program_idle_ms"](
        {"part": program.IN_FETCH, "per": "traced_steps"}, obs) is None
    b = program.breakdown(obs)
    assert set(b) == {"clock"} and b["clock"]["violations"] > 0
    assert list(program.checks(obs).values()) == [False]
    # the host's own time needs no device plane
    assert program.REDUCERS["program_host_ms"](
        {"prefix": "serve.", "skip": [".fetch"], "per": "traced_steps"},
        obs) > 0


# ------------------------------------------------- counters at the first step


def test_setup_counters_are_the_snapshot_less_the_steps_from_the_window_on():
    ring = [("serve.init", None, 0, 5, {}),
            ("compile", "serve.init", 1, 2, {"phase": "lower"})]
    for n in range(6):
        d = {"compile.trace_us": 1000, "compiles": 1} if n < 2 else {}
        if n == 4:
            d = {"compile.backend_us": 70, "compiles": 1}    # in the window
        ring.append(("serve.step", None, 10 + n, 11 + n, {"step": n, "d": d}))
    trace = Trace({}, [("window", 100.0, 100.0)])
    pt = spans.ProgramTrace(trace, {}, [
        ("serve.step", 110.0, 10.0, {"step": 3}, "t"),
        ("serve.step", 130.0, 10.0, {"step": 4}, "t")])
    prog = {"kind": "serve", "ring": ring, "trace": pt,
            "snapshot": {"ring_dropped": 0, "counters": {
                "compile.trace_us": 2500, "compile.lower_us": 400,
                "compile.backend_us": 70, "compiles": 4}}}
    at = program.setup_counters(prog)
    assert at == {"compile.trace_us": 2500, "compile.lower_us": 400,
                  "compile.backend_us": 0, "compiles": 3}
    obs = {"program": prog}
    r = program.REDUCERS["program_setup_counter_s"]
    assert r({"counters": ["compile.trace_us", "compile.lower_us"]}, obs) \
        == pytest.approx(0.0029)
    assert r({"counters": ["compile.backend_us",
                           "compile.cache_load_us"]}, obs) == 0.0
    # a parent commit's recorder has none of these counters
    assert r({"counters": ["compile.no_such_us"]}, obs) is None
    assert program.REDUCERS["program_init_s"]({}, obs) == 5e-9
    # the ring dropped the window's first step: nothing, and no exception
    lost = dict(prog, ring=ring[-2:])
    lost.pop("setup_counters")
    assert program.setup_counters(lost) is None
    assert program.REDUCERS["program_init_s"]({}, {"program": lost}) is None


# ----------------------------------------------------- metric files, reducers


def test_the_metric_files_are_shaped_like_layer_metrics():
    assert len(METRICS) == 13
    listed = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    e2e = {m["name"] for m in listed["end_to_end"]}
    old = {m["name"] for m in listed["per_layer"]}
    layers = {m["layer"] for m in listed["per_layer"]} | {"entry points"}
    by_kind = {"serve": [], "train": []}
    for m in program.load_metrics("serve") + program.load_metrics("train"):
        assert set(m) == {"name", "what", "unit", "better", "layer",
                          "source", "moves", "kinds", "reducer", "args"}
        assert m["name"] not in old and m["moves"] in e2e
        assert m["layer"] in layers and m["better"] in ("lower", "higher")
        assert m["source"] in ("program_span", "program_counter")
        assert m["reducer"] in program.REDUCERS
        assert not set(program.REDUCERS) & set(reduce.REDUCERS)
    for kind in by_kind:
        by_kind[kind] = [m["name"] for m in program.load_metrics(kind)]
    assert len(by_kind["serve"]) == 9 + 3 and len(by_kind["train"]) == 1 + 3
    assert set(by_kind["serve"]) | set(by_kind["train"]) == set(METRICS)


@pytest.mark.parametrize("name", METRICS)
def test_a_reducer_returns_none_where_the_program_recorded_nothing(
        name, capsys):
    m = harness.load_json(os.path.join(program.METRICS_DIR, name + ".json"))
    reducer, args = program.REDUCERS[m["reducer"]], m.get("args", {})
    bare = {"clocks": {}, "counters": {}, "trace": None, "context": {}}
    assert reducer(args, bare) is None                     # no "program"
    for kind in m["kinds"]:
        # an untraced run's recorder, and one whose keys are all missing
        empty = {"kind": kind, "ring": [], "serving": {}, "trace": None,
                 "shifted": None, "clock": None, "runs": [], "runtime": {},
                 "snapshot": {"counters": {}, "ring_dropped": 7}}
        assert reducer(args, dict(bare, program=empty)) is None
        assert reducer(args, dict(bare, program={"kind": kind})) is None
    said = capsys.readouterr().out
    assert all(line.startswith("[bench] program: ")
               for line in said.splitlines())


def test_an_untraced_run_imports_none_of_the_reader():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.argv=['run.py']; import benchmark.run; "
         "print(sorted(m for m in sys.modules if m.startswith('benchmark.')))"],
        cwd=harness.ROOT, capture_output=True, text=True, check=True).stdout
    assert "benchmark.run" in out and "benchmark.reduce" in out
    for new in ("benchmark.program", "benchmark.clock", "benchmark.spans"):
        assert new not in out


# ------------------------------------------------------------ a CPU rehearsal


@pytest.fixture(scope="module")
def rehearsed():
    """The serve driver at a tiny size on the CPU, traced, the program's
    observations attached as a traced run attaches them."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    real = harness.load_cell("serve-mistral-7b-l16-chat")
    cell = dataclasses.replace(
        real, config=TINY_SERVE["config"], traffic=TINY_SERVE["traffic"],
        system={**real.system, **TINY_SERVE["system"]})
    with tempfile.TemporaryDirectory(prefix="program_rehearsal_") as tdir:
        out = harness.load_driver("serve").run(
            cell, seed=2 ** 31 + 11, seconds=4.0, trace=True,
            t0=time.perf_counter(), trace_dir=tdir, rehearsal=True)
        program.attach(cell, out, tdir)
    return cell, out


def test_the_counter_request_and_setup_metrics_on_a_cpu_rehearsal(rehearsed):
    cell, out = rehearsed
    obs = out["obs"]
    prog = obs["program"]
    assert prog["clock"] is None and prog["runs"] == []    # no device plane
    got = program.metrics("serve", obs)
    # no device: nothing of the idle split; the rest is read
    assert set(got) == set(program.load_metrics("serve")[i]["name"]
                           for i in range(12)) - {
        "device_idle_ms_per_step.before_launch",
        "device_idle_ms_per_step.in_fetch",
        "device_idle_ms_per_step.outside_step"}
    # the same numbers spans.py prints by hand
    by_hand = spans.span_metrics("serve", dict(
        obs, program=dict(prog, trace=spans.ProgramTrace(
            prog["trace"].trace, {}, prog["trace"].spans))))
    for name in ("serve_queue_wait_p95_ms", "kv_reserved_unused_pct",
                 "serve_admit_to_first_token_p50_ms",
                 "serve_admit_blocked_pct.no_blocks",
                 "serve_admit_blocked_pct.prefilling"):
        assert got[name]["value"] == pytest.approx(by_hand[name]["value"])
    host = sum(v["value"] for k, v in by_hand.items()
               if k.startswith("serve_host_self_ms_per_step.serve.")
               and not k.endswith(".fetch") and not k.endswith(".submit"))
    # (spans.py leaves the zero-length serve.req.* events out of its list)
    assert host <= got["serve_host_ms_per_step"]["value"] < host + 0.02
    # set-up: the constructor's span; every compile of the run happened
    # before the window and is in the two counters' sum
    ring = prog["ring"]
    init = next(e for e in ring if e[0] == "serve.init")
    assert got["setup_engine_init_s"]["value"] == \
        pytest.approx((init[3] - init[2]) / 1e9)
    c = prog["snapshot"]["counters"]
    assert got["setup_trace_lower_s"]["value"] == pytest.approx(
        (c["compile.trace_us"] + c["compile.lower_us"]) / 1e6)
    assert got["setup_compile_or_load_s"]["value"] == pytest.approx(
        (c.get("compile.backend_us", 0)
         + c.get("compile.cache_load_us", 0)) / 1e6)
    assert got["setup_trace_lower_s"]["value"] > 0.5
    assert program.breakdown(obs) == {} and program.checks(obs) == {}


def test_the_result_line_keeps_what_run_py_prints_and_adds_to_it(rehearsed):
    from benchmark import run
    cell, out = rehearsed
    old = json.loads(run.finish(cell, out, True))
    line = json.loads(program.finish(cell, out))
    assert set(line) == set(old)
    assert line["correct"] is old["correct"]
    for key in ("attempted", "failed", "device"):
        assert line[key] == old[key]
    assert line["breakdown"] == old["breakdown"]            # no device here
    for name, value in old["metrics"].items():
        assert line["metrics"][name] == value
    assert set(line["metrics"]) - set(old["metrics"]) == set(
        program.metrics("serve", out["obs"]))
    # a run whose reader fell over is the run it was
    broken = dict(out, obs=dict(out["obs"], program={"kind": "serve"}))
    assert json.loads(program.finish(cell, broken))["metrics"] == \
        old["metrics"]
