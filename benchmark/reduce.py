"""From what a run observed to per-layer metrics: the fixed set of reducers.

A per-layer metric is a file ``layer_metrics/<name>.json`` that names one of
the reducers below and its arguments. A reducer gets the run's observations
and returns a number, or ``None`` where there was nothing to read (the
harness then leaves the metric out of the line).

Observations (``obs``), filled by the driver:

``clocks``    name -> list of host-clock seconds, each ended by a device
              sync (``block_until_ready`` or a fetch of the tokens)
``counters``  name -> number counted by the program or the driver
``trace``     a ``trace.Trace`` of the traced part, or ``None``
``context``   ``dims`` (the family's sizes), ``traffic``, ``peaks``
"""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from benchmark import flops
from benchmark.trace import WINDOW_SPAN, DeviceOp, Trace


# ------------------------------------------------------- trace arithmetic


def self_times(ops: Sequence[DeviceOp]) -> List[Tuple[str, str, float]]:
    """(name, result, self nanoseconds) of every op of one device line: its
    duration less the part its children cover. The line nests (a ``while``
    spans the ops of its body), and summing plain durations would count the
    body twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    out = [0.0] * len(ops)
    stack: List[int] = []                       # indices of open ancestors
    for i in order:
        _, _, start, dur = ops[i]
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= start:
            stack.pop()
        out[i] = dur
        if stack:
            out[stack[-1]] -= dur
        stack.append(i)
    return [(ops[i][0], ops[i][1], max(out[i], 0.0)) for i in range(len(ops))]


def busy_intervals(ops: Sequence[DeviceOp], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """Union of the op intervals, cut to [lo, hi]."""
    merged: List[List[float]] = []
    for _, _, start, dur in sorted(ops, key=lambda o: o[2]):
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_self_times(trace: Trace) -> List[Tuple[str, str, float]]:
    """:func:`self_times` of the ops that lie inside the window, all chips
    together; worked out once a trace (a 5 s trace holds ~200 000 ops)."""
    if "self_times" not in trace.cache:
        win = window_of(trace)
        out: List[Tuple[str, str, float]] = []
        if win is not None:
            lo, hi = win
            for ops in trace.devices.values():
                out += self_times([o for o in ops
                                   if o[2] >= lo and o[2] + o[3] <= hi])
        trace.cache["self_times"] = out
    return trace.cache["self_times"]


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    spans = [s for s in trace.host if s[0] == WINDOW_SPAN]
    if not spans:
        return None
    _, start, dur = spans[-1]
    return start, start + dur


def busy_and_window_s(trace: Trace) -> Optional[Tuple[float, float]]:
    """(seconds in which an op ran, averaged over the device planes; seconds
    of the traced window)."""
    win = window_of(trace)
    if win is None or not trace.devices:
        return None
    lo, hi = win
    busy = [sum(b - a for a, b in busy_intervals(ops, lo, hi))
            for ops in trace.devices.values()]
    return statistics.fmean(busy) / 1e9, (hi - lo) / 1e9


def top_ops(trace: Trace, n: int = 10) -> List[List[Any]]:
    """The device operations that took most self time inside the window,
    summed by name over all chips and divided by their number."""
    total: Dict[str, float] = defaultdict(float)
    for name, result, ns in window_self_times(trace):
        total[_label(name, result)] += ns
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(trace.devices) / 1e9] for name, ns in rows]


def _label(name: str, result: str) -> str:
    """Instruction name and the shape it produces: a pool-sized copy shows
    as ``copy.39.remat bf16[16,32,12288,128]``."""
    return f"{name} {result}" if result else name


def idle_gaps(trace: Trace, n: int = 10) -> List[List[Any]]:
    """Idle seconds of the first device inside the window, summed by what
    the host was doing: the benchmark's own span that holds the middle of
    each gap (``between_spans`` where none does)."""
    win = window_of(trace)
    if win is None or not trace.devices:
        return []
    lo, hi = win
    ops = trace.devices[sorted(trace.devices)[0]]
    busy = busy_intervals(ops, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted((s for s in trace.host if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    total: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) / 2
        label = "between_spans"
        # the latest-starting span that holds the middle (spans may nest)
        j = bisect.bisect_right(starts, mid) - 1
        while j >= 0 and mid - spans[j][1] < 60e9:
            if spans[j][1] + spans[j][2] >= mid:
                label = spans[j][0]
                break
            j -= 1
        total[label] += b - a
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def scope_seconds(trace: Trace, match: Sequence[str]) -> Optional[float]:
    """Self seconds, inside the window, of the instructions whose name holds
    any of ``match`` (a Pallas kernel's custom call is named after the
    kernel); averaged over chips. ``None`` if no such instruction ran."""
    hits = [ns for name, _, ns in window_self_times(trace)
            if any(m in name for m in match)]
    return sum(hits) / len(trace.devices) / 1e9 if hits else None


# --------------------------------------------------------------- reducers


def p95(xs: Sequence[float]) -> float:
    """The last of the 19 cut points of ``statistics.quantiles(xs, n=20)``."""
    return statistics.quantiles(xs, n=20)[-1]


def clock_median(args, obs):
    """Median of a host clock, times ``scale`` (1000: seconds to ms)."""
    xs = obs["clocks"].get(args["clock"])
    if not xs:
        return None
    return statistics.median(xs) * float(args.get("scale", 1.0))


def clock_p95(args, obs):
    """95th percentile (:func:`p95`) of a host clock, times ``scale``."""
    xs = obs["clocks"].get(args["clock"], ())
    if len(xs) < 2:
        return None
    return p95(xs) * float(args.get("scale", 1.0))


def counter_ratio(args, obs):
    """``scale * num / den`` of two counters."""
    c = obs["counters"]
    if args["num"] not in c or not c.get(args["den"]):
        return None
    return float(args.get("scale", 1.0)) * c[args["num"]] / c[args["den"]]


def counter(args, obs):
    """One counter, times ``scale``."""
    v = obs["counters"].get(args["name"])
    return None if v is None else float(args.get("scale", 1.0)) * v


def train_mfu(args, obs):
    """Needed FLOPs a token (``flops.train_flops_per_token``, causal, no
    recompute) x tokens/s/chip over the chip's bf16 peak, in %. An end-to-end
    utilization, not a kernel's roofline share."""
    rate = obs["counters"].get(args["rate"])
    ctx = obs["context"]
    if not rate or ctx.get("peaks") is None:
        return None
    need = flops.train_flops_per_token(ctx["dims"], ctx["traffic"]["seq_len"])
    return 100.0 * need * rate / (ctx["peaks"]["bf16_tflops"] * 1e12)


def scope_time(args, obs):
    """Device self time of the ops matching ``match``, per ``per`` (a
    counter of the traced part: steps), times ``scale``."""
    if obs.get("trace") is None:
        return None
    secs = scope_seconds(obs["trace"], args["match"])
    per = obs["counters"].get(args["per"])
    if secs is None or not per:
        return None
    return float(args.get("scale", 1.0)) * secs / per


def idle_share(args, obs):
    """100 x (1 - device busy / traced window)."""
    if obs.get("trace") is None:
        return None
    bw = busy_and_window_s(obs["trace"])
    if bw is None or bw[1] <= 0:
        return None
    return 100.0 * (1.0 - bw[0] / bw[1])


REDUCERS: Dict[str, Callable[[Dict[str, Any], Dict[str, Any]],
                             Optional[float]]] = {
    "clock_median": clock_median,
    "clock_p95": clock_p95,
    "counter": counter,
    "counter_ratio": counter_ratio,
    "train_mfu": train_mfu,
    "scope_time": scope_time,
    "idle_share": idle_share,
}


def layer_metrics(defs: Sequence[Dict[str, Any]], obs: Dict[str, Any]
                  ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in defs:
        value = REDUCERS[m["reducer"]](m.get("args", {}), obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
