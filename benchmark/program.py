#!/usr/bin/env python3
"""What the PROGRAM recorded in a traced run, as per-layer metrics: its spans
on the device's clock, the device's idle time split by what the host was
doing, and set-up counted by phase.

    python3 benchmark/program.py --workload <cell> --seed <n> --seconds <s>

prints what ``run.py --trace 1`` prints for that run (the same driver, the
same ``run.finish`` on the same observations) with the metrics of
``program_metrics/*.json`` beside the old ones and two more keys in
``breakdown``: ``clock`` and ``idle_gaps_by_program_span``.

The reader is :func:`attach`: it puts the program's own observations under
``obs["program"]`` from ``deepspeed_tpu.utils.telemetry.recent()`` (the
drivers hand back no engine) and the trace directory the run left. It reads
the profile's first device's ``XLA Modules`` line, the program's ``ds/`` host
spans and three kinds of runtime events and nothing else: the device ops are
the ones the driver read already (``obs["trace"]``), and no ``op_name`` is
decoded. ``clock.offset`` then says how far the device plane's clock lies from
the host plane's, and the program's spans are moved onto the device's clock
BEFORE any idle gap is put down to a span. The window, the device ops and the
``bench/`` spans stay where ``trace.read`` put them, so the idle time split
here is the idle time ``reduce.idle_share`` reports.

A reducer below is a function of ``(args, obs)`` like ``reduce.REDUCERS``'
and returns ``None``, never an exception, where the program recorded nothing
(an ``obs`` without ``program``, a parent commit without the span or counter,
a ring that dropped the steps): it prints one ``[bench]`` line then. A metric
is a file ``program_metrics/<name>.json`` in the shape of a
``layer_metrics/*.json``.

**Joining ``run.py``** takes edits to files the benchmark has, which is a
``benchmark`` PR's: ``reduce.REDUCERS.update(program.REDUCERS)``, the files of
``program_metrics/`` moved to ``layer_metrics/`` with their ``BENCHMARK.json``
entries, and in ``run.finish``, traced, ``program.attach(cell, out,
harness.TRACE_DIR)`` before the metrics and ``program.breakdown(obs)`` merged
into ``breakdown``.
"""

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse                     # noqa: E402
import functools                    # noqa: E402
import glob                         # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import clock, harness, reduce, spans  # noqa: E402
from benchmark import trace as tracing              # noqa: E402

METRICS_DIR = os.path.join(harness.HERE, "program_metrics")
#: the span a constructor wraps itself in, per kind of cell
INIT = {"serve": "serve.init", "train": "train.init"}
#: which part of a serving step an idle instant falls in, by the deepest
#: program span open then (after the shift)
BEFORE_LAUNCH, IN_FETCH, OUTSIDE_STEP = "before_launch", "in_fetch", \
    "outside_step"


def say(what: str) -> None:
    print(f"[bench] program: {what}", flush=True)


# ------------------------------------------------------------- the reader


def read_program_planes(trace_dir: str, first_device: Optional[str]
                        ) -> Dict[str, Any]:
    """Of the newest ``.xplane.pb`` under ``trace_dir``: the runs of the
    plane ``first_device`` (``clock.Run``; none on a CPU rehearsal), the
    program's ``ds/`` spans with their attributes (``spans.ProgramSpan``)
    and the runtime events ``clock.RUNTIME_EVENTS`` about that device."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ordinal = int(first_device.rsplit(":", 1)[-1]) if first_device else 0
    runs, program_spans = [], []
    runtime: Dict[str, list] = {name: [] for name in clock.RUNTIME_EVENTS}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name == first_device:
            for line in plane.lines:
                if line.name == clock.DEVICE_RUNS_LINE:
                    for e in line.events:
                        stats = dict(e.stats)
                        runs.append((e.name, float(e.start_ns),
                                     float(e.duration_ns),
                                     int(stats.get("run_id", -1))))
        elif plane.name.startswith(tracing.HOST_PLANE):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(spans.PROGRAM):
                        attrs = {k: v for k, v in e.stats
                                 if not k.startswith("_")}
                        program_spans.append(
                            (e.name[len(spans.PROGRAM):], float(e.start_ns),
                             float(e.duration_ns), attrs, line.name))
                    elif e.name in runtime:
                        stats = dict(e.stats)
                        if int(stats.get("device_ordinal", ordinal)) \
                                == ordinal:
                            runtime[e.name].append(
                                (float(e.start_ns), float(e.duration_ns),
                                 int(stats.get("run_id", -1)), line.name))
    program_spans.sort(key=lambda s: (s[1], -s[2]))
    return {"runs": runs, "spans": program_spans, "runtime": runtime}


def program_obs(kind: str, serving: Dict[str, Any], obs: Dict[str, Any],
                trace_dir: str) -> Optional[Dict[str, Any]]:
    """``obs["program"]``: ``kind``, ``trace`` (a ``spans.ProgramTrace`` over
    the driver's own ``obs["trace"]``, no ``op_name``), ``runs``,
    ``runtime``, ``ring``, ``snapshot``, ``serving``; ``clock`` and
    ``shifted`` (the same trace with the program's spans on the device's
    clock) where the clocks could be aligned. ``None`` where no engine of
    this kind recorded anything."""
    from deepspeed_tpu.utils import telemetry
    recs = [r for r in telemetry.recent() if r.kind == kind]
    if not recs:
        say(f"no {kind} engine recorded anything "
            "(deepspeed_tpu.utils.telemetry.recent() is empty)")
        return None
    rec = recs[-1]
    prog = {"kind": kind, "ring": list(rec.ring), "snapshot": rec.snapshot(),
            "serving": serving, "runs": [], "runtime": {}, "clock": None,
            "trace": None, "shifted": None}
    trace = obs.get("trace")
    if trace is None:
        say("the run was not traced: no span, window or idle metric")
        return prog
    planes = read_program_planes(
        trace_dir, sorted(trace.devices)[0] if trace.devices else None)
    prog.update(runs=planes["runs"], runtime=planes["runtime"],
                trace=spans.ProgramTrace(trace, {}, planes["spans"]))
    return align(prog)


def align(prog: Dict[str, Any]) -> Dict[str, Any]:
    """``prog`` with ``clock`` and ``shifted`` worked out from its ``runs``,
    ``runtime`` and ``trace``."""
    pt = prog["trace"]
    prog["clock"] = clock.offset(prog["runs"], pt.spans, prog["runtime"])
    if prog["clock"] is None:
        say("no launch span names a program that ran on the device: the "
            "clocks stay apart and no idle time is put down to a span")
    elif prog["clock"]["violations"]:
        # seen once on the chip: a capture whose device plane has a hole of
        # seconds (half its runs missing, idle 63%): no one offset fits it
        say(f"{prog['clock']['violations']} runs lie before their launch or "
            "after their wait whatever the offset: the capture's device "
            "plane is not whole, no idle time is put down to a span")
    else:
        # the same window, device ops and bench/ spans; a cache of its own
        prog["shifted"] = spans.ProgramTrace(
            tracing.Trace(pt.trace.devices, pt.trace.host), {},
            clock.shift(pt.spans, prog["clock"]))
    return prog


def cut_json(prog: Dict[str, Any], steps: int = 4,
             margin_ns: float = 4e6) -> Dict[str, Any]:
    """A few steps from the middle of a traced window, as plain lists (how
    ``tests/data/trace_*_clock.json.gz`` were written, ``--cut``): the
    first device's ops and runs, the runtime's events, the program's and
    the benchmark's spans, the ``window`` span cut to those steps."""
    pt = prog["trace"]
    step_name = spans.STEP[prog["kind"]][0]
    ss = [s for s in spans.window_spans(pt) if s[0] == step_name]
    mid = ss[len(ss) // 2:len(ss) // 2 + steps]
    t0, t1 = mid[0][1], mid[-1][1] + mid[-1][2]
    lo, hi = t0 - margin_ns, t1 + margin_ns
    keep = lambda start, dur: lo <= start and start + dur <= hi
    first = sorted(pt.trace.devices)[0]
    return {
        "kind": prog["kind"],
        "devices": {first: [o for o in pt.trace.devices[first]
                            if keep(o[2], o[3])]},
        "host": [("window", t0, t1 - t0)] + [
            h for h in pt.trace.host if h[0] != tracing.WINDOW_SPAN
            and keep(h[1], h[2])],
        "spans": [s for s in pt.spans if keep(s[1], s[2])],
        "runs": [r for r in prog["runs"] if keep(r[1], r[2])],
        "runtime": {name: [e for e in events if keep(e[0], e[1])]
                    for name, events in prog["runtime"].items()}}


def from_json(d: Dict[str, Any]) -> Dict[str, Any]:
    """``obs["program"]`` of a :func:`cut_json` (no ring, no counters)."""
    pt = spans.ProgramTrace.from_json(dict(d, op_names={}))
    return align({
        "kind": d["kind"], "ring": [], "serving": {}, "trace": pt,
        "snapshot": {"counters": {}, "ring_dropped": 0},
        "runs": [tuple(r) for r in d["runs"]], "shifted": None,
        "runtime": {k: [tuple(e) for e in v]
                    for k, v in d["runtime"].items()}})


def attach(cell: harness.Cell, out: Dict[str, Any], trace_dir: str) -> None:
    """The one call of a traced run: ``out["obs"]["program"]``. Whatever the
    reader trips over is said in one line and costs the run nothing."""
    try:
        prog = program_obs(cell.kind, cell.system.get("serving", {}),
                           out["obs"], trace_dir)
    except Exception as e:              # a traced run never fails on this
        say(f"reader failed, no program metric: {e!r}")
        prog = None
    if prog is not None:
        out["obs"]["program"] = prog


# -------------------------------------------------------------- arithmetic


MID_RUN = " (mid-run)"


def deepest_segments(program_spans):
    """``[(start, end, name)]``, disjoint and in order: at every instant
    covered by a program span of non-zero length, the DEEPEST one open then
    (the latest-started that has not ended; ``spans.idle_by_overlap``'s
    rule). ``program_spans`` sorted by start."""
    segs, stack = [], []                      # stack: (end, name), innermost last
    cursor = float("-inf")

    def advance(to):
        nonlocal cursor
        while stack:
            end, name = stack[-1]
            if end <= cursor:                 # ended under a later-started one
                stack.pop()
            elif end <= to:
                segs.append((cursor, end, name))
                cursor = end
                stack.pop()
            else:
                break
        if stack and to > cursor:
            segs.append((cursor, to, stack[-1][1]))
        cursor = max(cursor, to)

    for name, start, dur, *_ in program_spans:
        if dur > 0:
            advance(start)
            stack.append((start + dur, name))
    advance(float("inf"))
    return segs


def _pieces(gaps, segs):
    """``(start, end, x)`` of every overlap of the sorted disjoint ``gaps``
    with the sorted disjoint segments ``(start, end, x)``."""
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(segs[k][0], a), min(segs[k][1], b)
            if hi > lo:
                yield lo, hi, segs[k][2]
            k += 1


def _overlaps(gaps, segs):
    """Nanoseconds of ``gaps`` that each segment covers, summed by the
    segments' labels; and what no segment covers."""
    total: Dict[str, float] = {}
    for lo, hi, name in _pieces(gaps, segs):
        total[name] = total.get(name, 0.0) + hi - lo
    return total, sum(b - a for a, b in gaps) - sum(total.values())


def idle_by_program_span(prog: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The first device's idle nanoseconds inside the window, every instant
    put down to the deepest program span open then, AFTER the shift onto the
    device's clock; ``between_spans`` where none is. Idle time between the
    ops of a run that is still going (no host change removes it) carries
    the suffix :data:`MID_RUN`."""
    pt = prog.get("shifted")
    if pt is None:
        return None
    if "idle_by_span" not in prog:
        gaps, _, _ = spans._idle_gaps(pt)
        runs = sorted((start, start + dur, "") for _, start, dur, _
                      in prog["runs"])
        mid_run = [(lo, hi) for lo, hi, _ in _pieces(gaps, runs)]
        segs = deepest_segments(pt.spans)
        whole, whole_between = _overlaps(gaps, segs)
        mid, mid_between = _overlaps(mid_run, segs)
        whole["between_spans"], mid["between_spans"] = whole_between, \
            mid_between
        total = {name: ns - mid.get(name, 0.0) for name, ns in whole.items()}
        total.update((name + MID_RUN, ns) for name, ns in mid.items())
        # (a part that is all mid-run leaves a rounding residue behind)
        prog["idle_by_span"] = {k: v for k, v in total.items() if v > 1e-3}
    return prog["idle_by_span"]


def idle_parts_ns(prog: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """:func:`idle_by_program_span` summed into three parts: inside a
    ``*.fetch`` / ``*.sync`` span (the device is done, or between two ops of
    a run, and the host waits for the result), inside a step otherwise
    (admission, build, dispatch, bookkeeping: the host's way to the next
    launch), and outside every step (the caller between two steps)."""
    by_span = idle_by_program_span(prog)
    if by_span is None:
        return None
    if "idle_parts" not in prog:
        parts = {BEFORE_LAUNCH: 0.0, IN_FETCH: 0.0, OUTSIDE_STEP: 0.0}
        inside = spans.STEP[prog["kind"]][0].rsplit(".", 1)[0] + "."
        for label, ns in by_span.items():
            name = label[:-len(MID_RUN)] if label.endswith(MID_RUN) else label
            if name.endswith(clock.WAIT_SUFFIXES):
                parts[IN_FETCH] += ns
            elif name.startswith(inside) and name not in (
                    "serve.submit", INIT[prog["kind"]]):
                parts[BEFORE_LAUNCH] += ns
            else:
                parts[OUTSIDE_STEP] += ns
        prog["idle_parts"] = parts
    return prog["idle_parts"]


def setup_counters(prog: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The recorder's cumulative counters as they stood when the traced
    window's first step began: the snapshot less what the steps from that
    one on gained (``attrs["d"]``). ``None`` where the ring no longer holds
    that step."""
    if "setup_counters" not in prog:
        prog["setup_counters"] = None
        pt = prog.get("trace")
        step_name, attr = spans.STEP[prog["kind"]]
        traced = [int(s[3][attr]) for s in spans.window_spans(pt)
                  if s[0] == step_name] if pt is not None else []
        steps = spans.steps_of(prog["ring"], prog["kind"])
        if not traced or not steps or steps[0]["n"] > min(traced):
            say("the ring does not hold the window's first step "
                f"(ring_dropped {prog['snapshot']['ring_dropped']})")
        else:
            first = min(traced)
            at = dict(prog["snapshot"]["counters"])
            for s in steps:
                if s["n"] >= first:
                    for k, v in s["entry"][4].get("d", {}).items():
                        at[k] = at.get(k, 0) - v
            prog["setup_counters"] = at
    return prog["setup_counters"]


# --------------------------------------------------------------- reducers


def guarded(fn: Callable) -> Callable:
    """A reducer that returns ``None`` on an ``obs`` without ``program``
    and says what it tripped over instead of raising."""
    @functools.wraps(fn)
    def reducer(args, obs):
        prog = obs.get("program")
        if prog is None:
            return None
        try:
            return fn(args, obs, prog)
        except Exception as e:
            say(f"{fn.__name__}({args}) found nothing to read: {e!r}")
            return None
    return reducer


@guarded
def host_ms(args, obs, prog):
    """Host self time inside the traced window of the program spans whose
    name starts with ``prefix``, those ending in one of ``skip`` left out,
    per ``per`` (a counter of the traced part: steps), in ms."""
    per = obs["counters"].get(args["per"])
    if prog.get("trace") is None or not per:
        return None
    skip = tuple(args.get("skip", ()))
    ns = [v for k, v in spans.span_self_ns(prog["trace"]).items()
          if k.startswith(args["prefix"]) and not k.endswith(skip)
          and k not in args.get("skip_names", ())]
    return sum(ns) / 1e6 / per if ns else None


@guarded
def idle_ms(args, obs, prog):
    """One of :func:`idle_parts_ns`' three parts (``part``), per ``per``,
    in ms."""
    parts = idle_parts_ns(prog)
    per = obs["counters"].get(args["per"])
    return None if parts is None or not per \
        else parts[args["part"]] / 1e6 / per


def of_spans(reducer: Callable) -> Callable:
    """One of ``spans.py``'s reducers of ``(args, obs)``, guarded."""
    @guarded
    @functools.wraps(reducer)
    def wrapped(args, obs, prog):
        return reducer(args, obs) if prog.get("trace") is not None else None
    return wrapped


@guarded
def init_s(args, obs, prog):
    """Seconds of the engine's ``serve.init`` / ``train.init`` span."""
    for name, _, start, end, _ in prog["ring"]:
        if name == INIT[prog["kind"]]:
            return (end - start) / 1e9
    say(f"no {INIT[prog['kind']]} entry in the ring (ring_dropped "
        f"{prog['snapshot']['ring_dropped']})")
    return None


@guarded
def setup_counter_s(args, obs, prog):
    """The sum of the microsecond counters ``counters`` as they stood at the
    window's first step, in seconds."""
    at = setup_counters(prog)
    if at is None or not any(name in at for name in args["counters"]):
        return None
    return sum(at.get(name, 0) for name in args["counters"]) / 1e6


REDUCERS: Dict[str, Callable[[Dict[str, Any], Dict[str, Any]],
                             Optional[float]]] = {
    "program_host_ms": host_ms,
    "program_idle_ms": idle_ms,
    "program_request_quantile": of_spans(spans.request_quantile),
    "program_window_ratio": of_spans(spans.window_ratio),
    "program_init_s": init_s,
    "program_setup_counter_s": setup_counter_s,
}


def load_metrics(kind: str, base: str = METRICS_DIR) -> List[Dict[str, Any]]:
    """Every ``program_metrics/*.json`` whose ``kinds`` holds ``kind``."""
    out = []
    for fn in sorted(os.listdir(base)):
        if fn.endswith(".json"):
            m = harness.load_json(os.path.join(base, fn))
            if m["name"] + ".json" != fn:
                raise ValueError(f"program_metrics/{fn} names itself "
                                 f"{m['name']!r}")
            if kind in m["kinds"]:
                out.append(m)
    return out


def metrics(kind: str, obs: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in load_metrics(kind):
        value = REDUCERS[m["reducer"]](m.get("args", {}), obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------------------ breakdown and checks


def breakdown(obs: Dict[str, Any]) -> Dict[str, Any]:
    """``clock`` (``offset_ms``, ``lower_ms``, ``upper_ms``, ``drift_ms``,
    ``pairs``, ``violations``) and ``idle_gaps_by_program_span`` (the ten largest, by
    overlap, after the shift, in seconds, under the program's span names;
    :data:`MID_RUN` marks idle time between the ops of a run still going;
    left out where the capture's device plane is not whole); ``{}`` where no
    launch pairs with a run."""
    prog = obs.get("program")
    try:
        c = prog.get("clock") if prog else None
        if c is None:
            return {}
        out = {"clock": {"offset_ms": c["offset_ns"] / 1e6,
                         "lower_ms": c["lower_ns"] / 1e6,
                         "upper_ms": c["upper_ns"] / 1e6,
                         "drift_ms": c["drift_ns"] / 1e6,
                         "pairs": c["pairs"],
                         "violations": c["violations"]}}
        by_span = idle_by_program_span(prog)
        if by_span is not None:
            rows = sorted(by_span.items(), key=lambda kv: -kv[1])
            out["idle_gaps_by_program_span"] = [[k, v / 1e9]
                                                for k, v in rows[:10]]
        return out
    except Exception as e:
        say(f"no breakdown: {e!r}")
        return {}


def checks(obs: Dict[str, Any]) -> Dict[str, bool]:
    """What the reader holds itself to, where it read anything: after the
    shift no run starts before its launch span or ends after its wait span;
    the three parts of the idle time are the window's idle time (to 1%)."""
    prog = obs.get("program")
    if prog is None or prog.get("clock") is None:
        return {}
    c = prog["clock"]
    out = {f"no run before its launch or after its wait, clocks aligned "
           f"(offset {c['offset_ns'] / 1e6:+.3f} ms in "
           f"[{c['lower_ns'] / 1e6:+.3f}, {c['upper_ns'] / 1e6:+.3f}] by "
           f"{c['by']['lower']} and {c['by']['upper']}, drift "
           f"{c['drift_ns'] / 1e6:+.3f} ms; {c['pairs']} launches, "
           f"{c['violations']} violations)": c["violations"] == 0}
    parts = idle_parts_ns(prog)
    bw = reduce.busy_and_window_s(obs["trace"])
    if parts is not None and bw is not None and len(obs["trace"].devices) == 1:
        idle, split = bw[1] - bw[0], sum(parts.values()) / 1e9
        out[f"the idle parts add up to the window's idle time (to 1%): "
            f"{split:.4f} s of {idle:.4f} s"] = \
            abs(split - idle) <= 0.01 * max(idle, 1e-9)
    return out


def finish(cell: harness.Cell, out: Dict[str, Any]) -> str:
    """``run.finish``'s traced line with the program's metrics and the two
    breakdown keys beside what it holds; ``correct`` is ``run.finish``'s."""
    from benchmark import run
    line = json.loads(run.finish(cell, out, True))
    obs = out["obs"]
    # the reader's checks of ITSELF: said, and no part of ``correct`` (one
    # capture in fifteen came with a hole in its device plane, PR 38: the
    # profiler's, not the program's)
    for what, good in checks(obs).items():
        say(f"{'ok  ' if good else 'FAIL'} {what}")
    line["metrics"].update(metrics(cell.kind, obs))
    line.setdefault("breakdown", {}).update(breakdown(obs))
    return json.dumps(line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cut", metavar="FILE.json.gz",
                    help="also write a few steps of the trace there")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=True, t0=T0,
        trace_dir=harness.TRACE_DIR)
    attach(cell, out, harness.TRACE_DIR)
    if args.cut and out["obs"].get("program", {}).get("trace") is not None:
        import gzip
        with gzip.open(args.cut, "wt") as f:
            json.dump(cut_json(out["obs"]["program"]), f)
    print(finish(cell, out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
