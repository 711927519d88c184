#!/usr/bin/env python3
"""What the PROGRAM recorded in a traced run of a cell: its own spans,
counters and device scopes, read from the outside.

    python3 benchmark/spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's existing driver with ``trace=True`` (as ``run.py --trace 1``
does), then reads the ``.xplane.pb`` the run left in ``harness.TRACE_DIR`` a
second time with the reader below, and takes the ring and the counters of the
engine the driver made from ``deepspeed_tpu.utils.telemetry.recent()`` (the
drivers hand back no engine). It prints one JSON line shaped like
``run.py``'s: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown``.

Where each number is read:

* span and scope metrics over the traced seconds: the ``ds/`` host spans and
  the device ops that lie inside the benchmark's ``bench/window`` span;
* counter and request metrics over the untraced window that follows: its
  steps are the W steps after the last traced one, W being the number of step
  clocks the driver returns in ``obs``. Ring and trace are joined by the
  ``step`` / ``step_num`` attribute that step spans carry in both, never by
  comparing clocks.

The device ops' ``op_name`` (JAX's path of ``jax.named_scope`` names) is in
the xplane as the ``tf_op`` stat of each event's METADATA, which
``jax.profiler.ProfileData`` does not hand out; :func:`event_op_names` reads
it with a minimal protobuf decoder (looked at by hand on a v5e trace, PR 24,
jax 0.9.0: the events' own stats are ``device_offset_ps``,
``device_duration_ps`` only).

The reducers below are functions of ``(args, obs)`` like those of
``reduce.py`` and use its ``self_times`` / ``busy_intervals`` /
``window_of``: a later ``benchmark`` PR moves them into ``REDUCERS`` and
makes each metric a ``layer_metrics/*.json``. ``obs["program"]`` is what
that PR's drivers would put there: ``spans``, ``op_names``, ``ring``,
``snapshot``, ``serving``.
"""

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse                     # noqa: E402
import bisect                       # noqa: E402
import dataclasses                  # noqa: E402
import functools                    # noqa: E402
import glob                         # noqa: E402
import os                           # noqa: E402
import statistics                   # noqa: E402
import sys                          # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, reduce              # noqa: E402
from benchmark import trace as tracing             # noqa: E402

#: prefix of the program's host spans (deepspeed_tpu.utils.telemetry.PREFIX)
PROGRAM = "ds/"
#: the span of one step, and the attribute that numbers it, per kind of cell
STEP = {"serve": ("serve.step", "step"), "train": ("train.step", "step_num")}
#: (name, start_ns, duration_ns, attrs, thread)
ProgramSpan = Tuple[str, float, float, Dict[str, Any], str]


@dataclasses.dataclass
class ProgramTrace:
    """One ``.xplane.pb`` as this file reads it (``to_json`` is how the
    few steps under ``tests/data/`` were written, cut out of a chip run)."""
    trace: tracing.Trace                       # device ops + bench/ spans
    op_names: Dict[str, List[str]]             # plane -> op_name of each op
    spans: List[ProgramSpan]                   # the program's ds/ spans

    def to_json(self):
        return {"devices": self.trace.devices, "host": self.trace.host,
                "op_names": self.op_names, "spans": self.spans}

    @staticmethod
    def from_json(d) -> "ProgramTrace":
        return ProgramTrace(
            tracing.Trace.from_json(d),
            d["op_names"],
            [(n, s, dur, a, th) for n, s, dur, a, th in d["spans"]])


# ------------------------------------------------------------- the reader


def _fields(buf):
    """(field, wire type, value) of one protobuf message: a varint as an
    int, a length-delimited field as a memoryview; fixed-width ones are
    skipped."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return val

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, wire, varint()
        elif wire == 2:
            ln = varint()
            yield field, wire, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def event_op_names(path: str, plane_prefix: str = tracing.DEVICE_PLANE
                   ) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: op_name}}`` of an ``.xplane.pb``: the
    ``tf_op`` stat of each ``XEventMetadata`` (XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, .stat_metadata=5; XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .str_value=5, .ref_value=7; XStatMetadata.id=1,
    .name=2). An event's name is its instruction's whole HLO text."""
    out: Dict[str, Dict[str, str]] = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        name, events, stat_names = "", [], {}
        for pf, _, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                for ef, ew, ev in _fields(v):        # map entry: value = 2
                    if ef == 2 and ew == 2:
                        meta = {mf: mv for mf, _, mv in _fields(ev)}
                        stat_names[meta.get(1, 0)] = \
                            bytes(meta.get(2, b"")).decode()
        if not name.startswith(plane_prefix):
            continue
        table = out.setdefault(name, {})
        for entry in events:
            for ef, ew, ev in _fields(entry):
                if ef != 2 or ew != 2:
                    continue
                event_name = op = ""
                for mf, _, mv in _fields(ev):
                    if mf == 2:
                        event_name = bytes(mv).decode()
                    elif mf == 5:
                        stat = {sf: sv for sf, _, sv in _fields(mv)}
                        if stat_names.get(stat.get(1)) == "tf_op":
                            op = (bytes(stat[5]).decode() if 5 in stat
                                  else stat_names.get(stat.get(7), ""))
                if op:
                    table[event_name] = op.rstrip(":")
    return out


def read(trace_dir: str) -> ProgramTrace:
    """The newest ``.xplane.pb`` under ``trace_dir``: device ops and
    ``bench/`` spans as ``trace.read`` gives them, each op's ``op_name``,
    and the program's ``ds/`` spans with their attributes."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    named = event_op_names(files[-1])
    devices, op_names, host, spans = {}, {}, [], []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith(tracing.DEVICE_PLANE):
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    table = named.get(plane.name, {})
                    events = list(line.events)
                    devices[plane.name] = [
                        (*tracing.split_hlo(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in events]
                    op_names[plane.name] = [table.get(e.name, "")
                                            for e in events]
        elif plane.name.startswith(tracing.HOST_PLANE):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM):
                        attrs = {k: v for k, v in e.stats
                                 if not k.startswith("_")}
                        spans.append((e.name[len(PROGRAM):],
                                      float(e.start_ns),
                                      float(e.duration_ns), attrs,
                                      line.name))
                    elif e.name.startswith(tracing.ANNOTATION):
                        host.append((e.name[len(tracing.ANNOTATION):],
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    host.sort(key=lambda s: s[1])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return ProgramTrace(tracing.Trace(devices, host), op_names, spans)


# ------------------------------------------------------- span arithmetic


@functools.lru_cache(maxsize=None)
def scope_of(op_name: str) -> str:
    """The program's own rule (``telemetry.scope_of``); ``unscoped`` where
    the op lies under no scope of the program, ``no_op_name`` where the
    compiler made it with no metadata at all (a layout copy it inserted),
    which no scope of the program can name."""
    from deepspeed_tpu.utils import telemetry
    if not op_name:
        return "no_op_name"
    path = telemetry.scope_of(op_name)
    return path if path.split(":")[-1] else (path + "unscoped")


def phase_of(scope: str) -> str:
    """``forward`` / ``backward`` / ``recompute`` / ``optimizer`` /
    ``accumulate`` (gradient accumulation and ZeRO's placements) /
    ``unscoped`` / ``no_op_name`` of one scope path."""
    prefix, _, path = scope.rpartition(":")
    if prefix:
        return prefix
    if path in ("unscoped", "no_op_name"):
        return path
    if path.startswith("optimizer"):
        return "optimizer"
    if path.startswith(("grad_accum", "zero.")):
        return "accumulate"
    return "forward"


def window_spans(pt: ProgramTrace) -> List[ProgramSpan]:
    """The program's spans that start inside the traced window."""
    win = reduce.window_of(pt.trace)
    if win is None:
        return []
    return [s for s in pt.spans if win[0] <= s[1] <= win[1]]


def span_self_ns(pt: ProgramTrace) -> Dict[str, float]:
    """Self nanoseconds by span name inside the window: a span less what
    the spans nested in it (same thread) cover."""
    if "span_self" not in pt.trace.cache:
        total: Dict[str, float] = defaultdict(float)
        by_thread: Dict[str, list] = defaultdict(list)
        for name, start, dur, _, thread in window_spans(pt):
            by_thread[thread].append((name, "", start, dur))
        for rows in by_thread.values():
            for name, _, ns in reduce.self_times(rows):
                total[name] += ns
        pt.trace.cache["span_self"] = dict(total)
    return pt.trace.cache["span_self"]


def _idle_gaps(pt: ProgramTrace):
    """(idle gaps of the first device inside the window as (start, end),
    the program's spans of non-zero length sorted by start, their starts)."""
    win = reduce.window_of(pt.trace)
    if win is None or not pt.trace.devices:
        return [], [], []
    lo, hi = win
    ops = pt.trace.devices[sorted(pt.trace.devices)[0]]
    busy = reduce.busy_intervals(ops, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in pt.spans if s[2] > 0]
    return gaps, spans, [s[1] for s in spans]


def idle_by_span(pt: ProgramTrace) -> Dict[str, float]:
    """Idle nanoseconds of the first device inside the window, each gap put
    down to the DEEPEST program span that holds its middle: the
    latest-starting one (``reduce.idle_gaps``' rule, on the program's
    spans); ``between_spans`` where none does."""
    if "idle_by_span" in pt.trace.cache:
        return pt.trace.cache["idle_by_span"]
    total: Dict[str, float] = defaultdict(float)
    gaps, spans, starts = _idle_gaps(pt)
    for a, b in gaps:
        mid, label = (a + b) / 2, "between_spans"
        j = bisect.bisect_right(starts, mid) - 1
        while j >= 0 and mid - spans[j][1] < 60e9:
            if spans[j][1] + spans[j][2] >= mid:
                label = spans[j][0]
                break
            j -= 1
        total[label] += b - a
    pt.trace.cache["idle_by_span"] = dict(total)
    return pt.trace.cache["idle_by_span"]


def idle_by_overlap(pt: ProgramTrace, longer_than_ns: float = 50e3
                    ) -> Dict[str, float]:
    """The same idle time, shared out: each gap longer than
    ``longer_than_ns`` is split over the program spans it overlaps, every
    instant going to the deepest span open then (``between_spans`` where
    none is); shorter gaps keep the middle rule. Where one gap runs from
    the end of a step's program through the next step's build and dispatch
    into its fetch, this says how much of it each of them held."""
    total: Dict[str, float] = defaultdict(float)
    gaps, spans, starts = _idle_gaps(pt)
    for a, b in gaps:
        if b - a <= longer_than_ns:
            continue
        over = []
        j = bisect.bisect_right(starts, b) - 1
        while j >= 0 and a - spans[j][1] < 2e9:
            if spans[j][1] + spans[j][2] > a:
                over.append(spans[j])
            j -= 1
        # outermost first (``pt.spans`` is sorted so); nesting is decided
        # on the spans as recorded, the arithmetic done on their parts
        # inside the gap (clipped, a span and its child can coincide)
        open_spans: List[Tuple[float, str]] = []
        covered = 0.0
        for name, start, dur, _, _ in reversed(over):
            while open_spans and open_spans[-1][0] <= start:
                open_spans.pop()
            part = min(start + dur, b) - max(start, a)
            total[name] += part
            if open_spans:
                total[open_spans[-1][1]] -= part
            else:
                covered += part
            open_spans.append((start + dur, name))
        total["between_spans"] += (b - a) - covered
    return {k: v for k, v in total.items() if v > 0}


def _window_op_selfs(pt: ProgramTrace) -> Dict[Tuple[str, str], float]:
    """Self nanoseconds of the device ops that lie inside the window, by
    (instruction and shape, scope path), summed over chips and divided by
    their number; worked out once a trace."""
    if "op_selfs" not in pt.trace.cache:
        win = reduce.window_of(pt.trace)
        total: Dict[Tuple[str, str], float] = defaultdict(float)
        if win is not None:
            lo, hi = win
            for plane, ops in pt.trace.devices.items():
                keep = [i for i, o in enumerate(ops)
                        if o[2] >= lo and o[2] + o[3] <= hi]
                selfs = reduce.self_times([ops[i] for i in keep])
                for i, (name, result, ns) in zip(keep, selfs):
                    label = f"{name} {result}" if result else name
                    total[(label, scope_of(pt.op_names[plane][i]))] += ns
        chips = max(len(pt.trace.devices), 1)
        pt.trace.cache["op_selfs"] = {k: v / chips
                                      for k, v in total.items()}
    return pt.trace.cache["op_selfs"]


def device_ns_by_scope(pt: ProgramTrace) -> Dict[str, float]:
    """Device self nanoseconds inside the window by scope path."""
    total: Dict[str, float] = defaultdict(float)
    for (_, scope), ns in _window_op_selfs(pt).items():
        total[scope] += ns
    return dict(total)


def ops_by_scope(pt: ProgramTrace, n: int = 10) -> List[List[Any]]:
    """The ``device_ops`` of ``run.py``'s breakdown with the scope path of
    each: [instruction and shape, scope, self seconds]."""
    rows = sorted(_window_op_selfs(pt).items(), key=lambda kv: -kv[1])[:n]
    return [[label, scope, ns / 1e9] for (label, scope), ns in rows]


# ------------------------------------------------ the ring, cut into steps


def steps_of(ring: Sequence[tuple], kind: str) -> List[Dict[str, Any]]:
    """The ring cut into steps, oldest first: ``{"n": the step's number,
    "entry": its step span, "inside": the entries recorded since the step
    before}`` (a span is written when it ends, so a step's children come
    before it)."""
    step_name, attr = STEP[kind]
    out, inside = [], []
    for entry in ring:
        if entry[0] == step_name:
            out.append({"n": int(entry[4][attr]), "entry": entry,
                        "inside": inside})
            inside = []
        else:
            inside.append(entry)
    return out


def window_steps(obs) -> List[Dict[str, Any]]:
    """The steps of the untraced window: the W steps after the last traced
    one, W being the number of step clocks in ``obs``."""
    prog = obs["program"]
    if "window_steps" not in prog:                    # once a run
        step_name, attr = STEP[prog["kind"]]
        traced = [int(s[3][attr]) for s in window_spans(prog["trace"])
                  if s[0] == step_name]
        last = max(traced, default=None)
        width = sum(len(v) for k, v in obs["clocks"].items() if k != "ttft")
        prog["window_steps"] = [] if last is None else [
            s for s in steps_of(prog["ring"], prog["kind"])
            if last < s["n"] <= last + width]
    return prog["window_steps"]


def window_counter(obs, name: str) -> float:
    return sum(s["entry"][4]["d"].get(name, 0) for s in window_steps(obs))


def window_requests(obs) -> List[Dict[str, float]]:
    """The stamps (seconds, the program's monotonic clock) of the requests
    ADMITTED inside the window that also had their first token before the
    ring ended: ``arrival``, ``admitted``, ``first_token``, and
    ``step_end``, the end of the ``srv.step()`` that made the first token
    (where the benchmark's driver stamps it)."""
    ring_steps = steps_of(obs["program"]["ring"], obs["program"]["kind"])
    firsts = {}
    for s in ring_steps:
        for e in s["inside"]:
            if e[0] == "serve.req.first_token":
                firsts[e[4]["rid"]] = (e[4]["ts"], s["entry"][3] / 1e9)
    out = []
    for s in window_steps(obs):
        for e in s["inside"]:
            if e[0] == "serve.req.admitted" and e[4]["rid"] in firsts:
                first, step_end = firsts[e[4]["rid"]]
                out.append({"arrival": e[4]["arrival_ts"],
                            "admitted": e[4]["ts"], "first_token": first,
                            "step_end": step_end})
    return out


# --------------------------------------------------------------- reducers
# functions of (args, obs) like reduce.REDUCERS'; None where there is
# nothing to read


def span_self_ms(args, obs):
    """Host self time of the program span ``span`` inside the traced
    window, per ``per`` (a counter of the traced part: steps), in ms."""
    ns = span_self_ns(obs["program"]["trace"]).get(args["span"])
    per = obs["counters"].get(args["per"])
    return None if ns is None or not per else ns / 1e6 / per


def span_idle_ms(args, obs):
    """Device idle time put down to the program span ``span``, per ``per``,
    in ms."""
    ns = idle_by_span(obs["program"]["trace"]).get(args["span"])
    per = obs["counters"].get(args["per"])
    return None if ns is None or not per else ns / 1e6 / per


def scope_device_ms(args, obs):
    """Device self time of the ops under scope path ``scope`` (exactly), or
    of the phase ``phase`` (:func:`phase_of`), per ``per``, in ms."""
    by = device_ns_by_scope(obs["program"]["trace"])
    per = obs["counters"].get(args["per"])
    if "scope" in args:
        ns = by.get(args["scope"])
    else:
        hits = [v for k, v in by.items() if phase_of(k) == args["phase"]]
        ns = sum(hits) if hits else None
    return None if ns is None or not per else ns / 1e6 / per


def request_quantile(args, obs):
    """Quantile ``q`` (0.5 or 0.95) of ``to - from`` (two of the stamps of
    :func:`window_requests`), in ms."""
    xs = [r[args["to"]] - r[args["from"]] for r in window_requests(obs)]
    if len(xs) < 2:
        return None
    return 1e3 * (statistics.median(xs) if args["q"] == 0.5
                  else reduce.p95(xs))


def window_ratio(args, obs):
    """``scale * num / (den * den_factor)`` of two counters summed over the
    window's steps; ``complement`` gives ``scale * (1 - ratio)``.
    ``den_factor`` names a key of the cell's ``serving`` section."""
    num = window_counter(obs, args["num"])
    den = window_counter(obs, args["den"])
    if "den_factor" in args:
        den *= float(obs["program"]["serving"][args["den_factor"]])
    if not den:
        return None
    ratio = num / den
    if args.get("complement"):
        ratio = 1.0 - ratio
    return float(args.get("scale", 1.0)) * ratio


def window_count(args, obs):
    """One counter summed over the window's steps, times ``scale``."""
    if not window_steps(obs):
        return None
    return float(args.get("scale", 1.0)) * window_counter(obs, args["name"])


def window_mean(args, obs):
    """One counter's gain a step over the window's steps, times ``scale``."""
    steps = window_steps(obs)
    if not steps:
        return None
    return float(args.get("scale", 1.0)) \
        * window_counter(obs, args["name"]) / len(steps)


SPAN_REDUCERS = {
    "span_self_ms": span_self_ms, "span_idle_ms": span_idle_ms,
    "scope_device_ms": scope_device_ms, "request_quantile": request_quantile,
    "window_ratio": window_ratio, "window_count": window_count,
    "window_mean": window_mean}


def metric_defs(kind: str, obs) -> List[Dict[str, Any]]:
    """The metrics of this file for one kind of cell, in the shape of a
    ``layer_metrics/*.json`` (name, unit, reducer, args): one entry per span
    and scope the run actually recorded."""
    pt = obs["program"]["trace"]
    per = "traced_steps"
    defs = [{"name": f"{kind}_host_self_ms_per_step.{span}", "unit": "ms",
             "reducer": "span_self_ms", "args": {"span": span, "per": per}}
            for span in sorted(span_self_ns(pt))
            if span.startswith(kind + ".") and ".req." not in span]
    defs += [{"name": f"device_idle_ms_per_step.{span}", "unit": "ms",
              "reducer": "span_idle_ms", "args": {"span": span, "per": per}}
             for span in sorted(idle_by_span(pt))]
    by = device_ns_by_scope(pt)
    defs += [{"name": f"device_ms_per_step.{scope}", "unit": "ms",
              "reducer": "scope_device_ms",
              "args": {"scope": scope, "per": per}}
             for scope in sorted(by) if ":" not in scope]
    if kind == "train":
        defs += [{"name": f"device_ms_per_step.{phase}", "unit": "ms",
                  "reducer": "scope_device_ms",
                  "args": {"phase": phase, "per": per}}
                 for phase in ("forward", "backward", "recompute",
                               "optimizer", "accumulate")]
        defs.append({"name": "train_h2d_mb_per_step", "unit": "MB",
                     "reducer": "window_mean",
                     "args": {"name": "train.h2d_bytes", "scale": 1e-6}})
    else:
        ms = lambda name, a, b, q: {
            "name": name, "unit": "ms", "reducer": "request_quantile",
            "args": {"from": a, "to": b, "q": q}}
        defs += [
            ms("serve_queue_wait_p50_ms", "arrival", "admitted", 0.5),
            ms("serve_queue_wait_p95_ms", "arrival", "admitted", 0.95),
            ms("serve_admit_to_first_token_p50_ms", "admitted",
               "first_token", 0.5)]
        defs += [{"name": f"serve_admit_blocked_pct.{why}", "unit": "%",
                  "reducer": "window_ratio",
                  "args": {"num": f"admit_blocked.{why}",
                           "den": "steps_with_queue", "scale": 100.0}}
                 for why in ("no_lane", "no_blocks", "prefilling")]
        defs += [
            {"name": "kv_reserved_unused_pct", "unit": "%",
             "reducer": "window_ratio",
             "args": {"num": "kv.tokens_written_sum",
                      "den": "kv.blocks_reserved_sum",
                      "den_factor": "block_size", "complement": True,
                      "scale": 100.0}},
            {"name": "prefix_hit_pct", "unit": "%",
             "reducer": "window_ratio",
             "args": {"num": "prefix_hit_tokens",
                      "den": "prefix.prompt_tokens", "scale": 100.0}},
            {"name": "prefix_evict_scanned_per_step", "unit": "entries",
             "reducer": "window_mean",
             "args": {"name": "prefix.evict_scanned_entries"}}]
    defs.append({"name": "compiles_in_window", "unit": "count",
                 "reducer": "window_count", "args": {"name": "compiles"}})
    return defs


def span_metrics(kind: str, obs) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metric_defs(kind, obs):
        value = SPAN_REDUCERS[m["reducer"]](m["args"], obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------- inside against outside, sums


def agreement(kind: str, obs, outside: Dict[str, Dict[str, Any]]
              ) -> Dict[str, Dict[str, float]]:
    """What the program counted against what the benchmark's driver counted
    from outside, on the same steps of the same run: ``{name: {"inside",
    "outside", "tolerance"}}``."""
    steps = window_steps(obs)
    if not steps:
        return {}
    out = {}
    step_ms = [(s["entry"][3] - s["entry"][2]) / 1e6 for s in steps]
    clocks = [1e3 * x for k, v in obs["clocks"].items() if k != "ttft"
              for x in v]
    out["median_step_ms"] = {"inside": statistics.median(step_ms),
                             "outside": statistics.median(clocks),
                             "tolerance": 0.5}
    if kind == "serve":
        serving = obs["program"]["serving"]
        usable = obs["counters"]["usable_blocks"]
        held = [s["entry"][4]["d"].get("kv.held_blocks_sum", 0)
                for s in steps]
        lanes = window_counter(obs, "lane_sum")
        for name, inside in (
                ("kv_pool_mean_used_pct",
                 100.0 * sum(held) / (usable * len(steps))),
                ("kv_pool_peak_used_pct", 100.0 * max(held) / usable),
                ("serve_lane_occupancy_pct",
                 100.0 * lanes / (serving["max_batch"] * len(steps)))):
            if name in outside:
                out[name] = {"inside": inside,
                             "outside": outside[name]["value"],
                             "tolerance": 0.1}
    return out


def sums(pt: ProgramTrace) -> Dict[str, float]:
    """How far the attributions add up, in seconds: idle by span against
    the window less device 0's busy time, device time by scope against the
    busy time, and the shares left unexplained."""
    win = reduce.window_of(pt.trace)
    if win is None or not pt.trace.devices:
        return {}
    lo, hi = win
    first = pt.trace.devices[sorted(pt.trace.devices)[0]]
    busy0 = sum(b - a for a, b in reduce.busy_intervals(first, lo, hi))
    idle = idle_by_span(pt)
    by = device_ns_by_scope(pt)
    busy = reduce.busy_and_window_s(pt.trace)[0]
    scoped = sum(by.values()) / 1e9
    unscoped = sum(v for k, v in by.items()
                   if k.endswith("unscoped")) / 1e9
    return {"idle_s": (hi - lo - busy0) / 1e9,
            "idle_by_span_s": sum(idle.values()) / 1e9,
            "idle_between_spans_s": idle.get("between_spans", 0.0) / 1e9,
            "busy_s": busy, "by_scope_s": scoped, "unscoped_s": unscoped,
            "no_op_name_s": by.get("no_op_name", 0.0) / 1e9}


# -------------------------------------------------------------------- main


def program_obs(cell: harness.Cell, out: Dict[str, Any], trace_dir: str
                ) -> Dict[str, Any]:
    """``out["obs"]`` with the program's own observations beside the
    driver's, under ``program``."""
    from deepspeed_tpu.utils import telemetry
    recs = [r for r in telemetry.recent() if r.kind == cell.kind]
    if not recs:
        raise RuntimeError(f"no {cell.kind} engine recorded anything: "
                           "deepspeed_tpu.utils.telemetry.recent() is empty")
    rec = recs[-1]
    return dict(out["obs"], program={
        "kind": cell.kind, "trace": read(trace_dir), "ring": list(rec.ring),
        "snapshot": rec.snapshot(),
        "serving": cell.system.get("serving", {})})


def finish(cell: harness.Cell, out: Dict[str, Any], obs: Dict[str, Any]
           ) -> str:
    """The result line: the driver's checks, the agreement of inside with
    outside, the metrics of this file and the two breakdowns."""
    pt = obs["program"]["trace"]
    checks = dict(out["checks"])
    outside = reduce.layer_metrics(
        harness.load_layer_metrics(cell.kind, cell=cell.name), obs)
    agree = agreement(cell.kind, obs, outside)
    for name, a in agree.items():
        checks[f"inside agrees with outside on {name} (to "
               f"{a['tolerance']})"] = \
            abs(a["inside"] - a["outside"]) <= a["tolerance"]
    checks["the ring holds the window's steps"] = bool(window_steps(obs)) \
        and obs["program"]["snapshot"]["ring_dropped"] == 0
    busy = reduce.busy_and_window_s(pt.trace)
    kw = {}
    if busy:
        kw = {"busy_s": busy[0], "window_s": busy[1]}
    breakdown = {
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            idle_by_span(pt).items(), key=lambda kv: -kv[1])[:12]],
        "idle_gaps_over_50us_by_overlap": [[k, v / 1e9] for k, v in sorted(
            idle_by_overlap(pt).items(), key=lambda kv: -kv[1])[:12]],
        "device_ops_by_scope": ops_by_scope(pt),
        "device_s_by_scope": [[k, v / 1e9] for k, v in sorted(
            device_ns_by_scope(pt).items(), key=lambda kv: -kv[1])],
        "sums": sums(pt), "agreement": agree}
    if cell.kind == "serve":
        reqs = window_requests(obs)
        part = lambda a, b: [1e3 * (r[b] - r[a]) for r in reqs]
        if len(reqs) >= 2:
            breakdown["ttft_parts_ms"] = {
                "requests": len(reqs),
                **{f"{name}_{q}": (statistics.median(xs) if q == "p50"
                                   else reduce.p95(xs))
                   for name, xs in (
                       ("queue_wait", part("arrival", "admitted")),
                       ("admit_to_first_token",
                        part("admitted", "first_token")),
                       ("rest_of_step", part("first_token", "step_end")),
                       ("arrival_to_step_end", part("arrival", "step_end")))
                   for q in ("p50", "p95")}}
    for what, ok in checks.items():
        print(f"[spans] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    return harness.result_line(
        correct=all(checks.values()), attempted=out["attempted"],
        failed=out["failed"], metrics=span_metrics(cell.kind, obs),
        devices=out["devices"], memory_peak=out["memory_peak"],
        breakdown=breakdown, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=True, t0=T0,
        trace_dir=harness.TRACE_DIR)
    print(finish(cell, out, program_obs(cell, out, harness.TRACE_DIR)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
