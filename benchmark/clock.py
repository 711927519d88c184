"""One clock for a profile: the device plane's offset against the host plane.

``trace.py`` says of a profile "all planes share one clock". They do not: in
a TPU profile the device planes' timestamps lie about a millisecond apart
from the host plane's (looked at by hand, PR 38, jax 0.9.0: -1.4 to -1.6 ms
in one run of a serving cell, -0.3 to -0.5 ms in a run of the training cell,
another offset every capture). An idle gap of the device put down to
"the host span open at that instant" lands in the wrong span unless the
program's spans are moved onto the device's clock first.

:func:`offset` brackets that offset from causality alone:

* no run of a program on the device can START before the host began to
  launch it: ``run.start - launch.start`` is an UPPER bound of the offset,
  and the smallest over all launches is the bound;
* no run can END after the host saw its result: ``run.end - seen.end`` is
  a LOWER bound, and the largest over all waits is the bound.

A launch is a program span that names its program (``serve.decode.dispatch``,
``serve.prefill.dispatch``, ``train.dispatch``: attribute ``program``, the
name the device's ``XLA Modules`` line gives the run); the wait for a result
is a ``*.fetch`` / ``*.sync`` span. Where the host plane holds the runtime's
own events, they tighten both sides, paired with the run by the ``run_id``
both carry: ``DoEnqueueProgram`` (the runtime hands the program to the chip's
queue; on the thread ``tfrt-non-blocking-queue``) for the launch, and for the
result ``CompleteCallbacks`` together with the ``ReadSyncFlag`` that ends
where it begins on the same thread: the runtime reads the flag the device set
when the run ended (its START follows the run's end at a nearly constant
distance, its length varies: it is woken by the end and does not wait for it).

The two clocks also DRIFT against each other: tens of parts in a million,
so 50 to 350 us over a 5 s window, more than the bracket is wide. The drift
is a line's slope (:func:`_slope`: the one under which the bounds of both
kinds leave the widest bracket), the bracket is taken of what is left, and
its middle is applied: ``offset_at(c, t)`` is what is ADDED to the host-plane
time ``t`` to put it on the device's clock.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: a program's run on one device: (name on the ``XLA Modules`` line,
#: start_ns, duration_ns, run_id or -1)
Run = Tuple[str, float, float, int]
#: a program span of the host plane: (name, start_ns, duration_ns, attrs,
#: thread), as ``spans.ProgramSpan``
ProgramSpan = Tuple[str, float, float, Dict[str, Any], str]
#: a runtime event of the host plane about the first device: (start_ns,
#: duration_ns, run_id or -1, thread)
RuntimeEvent = Tuple[float, float, int, str]

DEVICE_RUNS_LINE = "XLA Modules"
#: runtime events read, by what they bound
LAUNCHED, DONE, DONE_READ = "DoEnqueueProgram", "CompleteCallbacks", \
    "ReadSyncFlag"
RUNTIME_EVENTS = (LAUNCHED, DONE, DONE_READ)
#: a launch is paired with the first run of its program that starts no
#: earlier than this before it (the offset is far smaller than the distance
#: of two launches of one program, a step)
PAIRING_SLACK_NS = 5e6
#: spans that end when a result has reached the host
WAIT_SUFFIXES = (".fetch", ".sync")


def program_of(run_name: str) -> str:
    """``jit__decode(13313319965775238769)`` -> ``jit__decode``."""
    return run_name.split("(", 1)[0]


def pair_launches(runs: Sequence[Run], program_spans: Sequence[ProgramSpan]
                  ) -> List[Tuple[ProgramSpan, Run]]:
    """(launch span, the run it launched) in the order of the launches: a
    span that names its ``program`` with the first run of that program, not
    yet taken, that starts at most :data:`PAIRING_SLACK_NS` before the span.
    A launch whose run the trace does not hold (it ended first) and a run
    whose launch it does not hold (it started later) pair with nothing."""
    by_program: Dict[str, List[Run]] = {}
    for run in sorted(runs, key=lambda r: r[1]):
        by_program.setdefault(program_of(run[0]), []).append(run)
    taken = {p: 0 for p in by_program}
    pairs = []
    for span in sorted(program_spans, key=lambda s: s[1]):
        program = span[3].get("program")
        rs = by_program.get(program)
        if not rs:
            continue
        i = taken[program]
        while i < len(rs) and rs[i][1] < span[1] - PAIRING_SLACK_NS:
            i += 1
        if i < len(rs):
            pairs.append((span, rs[i]))
            i += 1
        taken[program] = i
    return pairs


def pair_waits(launches: Sequence[Tuple[ProgramSpan, Run]],
               program_spans: Sequence[ProgramSpan]
               ) -> List[Tuple[ProgramSpan, Run]]:
    """(wait span, the run whose result it waited for): a ``*.fetch`` /
    ``*.sync`` span with the run of the last launch begun before it on its
    thread (the device runs a thread's programs in the order launched, so
    whatever it waits for has ended when that one has)."""
    by_thread: Dict[str, Tuple[List[float], List[Run]]] = {}
    for span, run in launches:                      # in order of their start
        starts, launched = by_thread.setdefault(span[4], ([], []))
        starts.append(span[1])
        launched.append(run)
    pairs = []
    for span in program_spans:
        if not span[0].endswith(WAIT_SUFFIXES) or span[4] not in by_thread:
            continue
        starts, launched = by_thread[span[4]]
        j = bisect.bisect_right(starts, span[1]) - 1
        if j >= 0:
            pairs.append((span, launched[j]))
    return pairs


def _done_seen(runtime: Dict[str, List[RuntimeEvent]]) -> Dict[int, float]:
    """``{run_id: when the runtime began to read that run's completion}``:
    the start of the ``ReadSyncFlag`` that ends where the run's
    ``CompleteCallbacks`` begins on the same thread, else of
    ``CompleteCallbacks`` itself."""
    reads: Dict[str, Tuple[List[float], List[float]]] = {}   # starts, ends
    for start, dur, _, thread in sorted(runtime.get(DONE_READ, ())):
        starts, ends = reads.setdefault(thread, ([], []))
        starts.append(start)
        ends.append(start + dur)
    seen = {}
    last_done: Dict[str, float] = {}
    for start, dur, run_id, thread in sorted(runtime.get(DONE, ())):
        at = start
        starts, ends = reads.get(thread, ((), ()))
        j = bisect.bisect_right(starts, start) - 1
        if j >= 0 and ends[j] <= start \
                and starts[j] >= last_done.get(thread, float("-inf")):
            at = starts[j]
        last_done[thread] = start + dur
        if run_id >= 0:
            seen[run_id] = at
    return seen


#: the drift is fitted only to this many bounds of either kind, spread over
#: this long (a few steps say nothing about parts in a million), and is
#: looked for within this many ns a ns (quartz oscillators differ by tens of
#: parts in a million)
DRIFT_MIN_BOUNDS, DRIFT_MIN_NS, DRIFT_MAX = 8, 1e9, 5e-4


def _slope(uppers: Sequence[Tuple[float, float]],
           lowers: Sequence[Tuple[float, float]], at: float) -> float:
    """How fast the device's clock gains on the host's, in ns a ns: the
    slope under which the ``(time, bound)`` pairs leave the widest bracket,
    ``min(upper - s (t - at)) - max(lower - s (t - at))``. Every offset and
    slope that breaks no bound lies where that width is positive, so its
    maximum is the middle of what causality allows (the linear program of
    skew estimation from one-way delays); the width is concave in ``s``, a
    ternary search finds its top. 0.0 where the bounds are too few or too
    close in time to tell (:data:`DRIFT_MIN_BOUNDS`, :data:`DRIFT_MIN_NS`)."""
    times = [t for t, _ in uppers]
    if min(len(uppers), len(lowers)) < DRIFT_MIN_BOUNDS \
            or max(times) - min(times) < DRIFT_MIN_NS:
        return 0.0

    def width(s: float) -> float:
        return min(b - s * (t - at) for t, b in uppers) \
            - max(b - s * (t - at) for t, b in lowers)

    lo, hi = -DRIFT_MAX, DRIFT_MAX
    for _ in range(60):
        a, b = lo + (hi - lo) / 3, hi - (hi - lo) / 3
        if width(a) < width(b):
            lo = a
        else:
            hi = b
    return (lo + hi) / 2


def offset(runs: Sequence[Run], program_spans: Sequence[ProgramSpan],
           runtime: Optional[Dict[str, List[RuntimeEvent]]] = None
           ) -> Optional[Dict[str, Any]]:
    """The first device's clock against the host plane's (module docstring):
    ``offset_ns`` (applied at ``at_ns``, the middle of the launches: the
    bracket's middle), ``lower_ns``, ``upper_ns`` (the bracket, the drift
    taken out), ``slope`` (the drift, ns a ns) and ``drift_ns`` (over the
    launches' span of time), ``pairs`` (launches paired with their runs),
    ``violations`` (after the shift: runs that start before their launch
    span starts or end after their wait span ends) and ``by`` (which events
    gave each bound). ``None`` where no launch span pairs with a run."""
    runtime = runtime or {}
    launches = pair_launches(runs, program_spans)
    if not launches:
        return None
    waits = pair_waits(launches, program_spans)
    # (time, bound) of every pair: the spans', then the runtime's by run_id
    uppers = [(run[1], run[1] - span[1]) for span, run in launches]
    lowers = [(run[1] + run[2], run[1] + run[2] - span[1] - span[2])
              for span, run in waits]
    by = {"upper": "launch spans",
          "lower": "wait spans" if lowers else "none"}
    launched = {e[2]: e[0] for e in runtime.get(LAUNCHED, ()) if e[2] >= 0}
    seen = _done_seen(runtime)
    tight = [(start, start - launched[run_id])
             for _, start, _, run_id in runs if run_id in launched]
    if tight:
        uppers, by["upper"] = uppers + tight, LAUNCHED
    if seen:
        lowers += [(start + dur, start + dur - seen[run_id])
                   for _, start, dur, run_id in runs if run_id in seen]
        by["lower"] = f"{DONE_READ} / {DONE}"
    # the drift out, the bracket of what is left, its middle applied
    times = [t for t, _ in uppers]
    at = (min(times) + max(times)) / 2
    slope = _slope(uppers, lowers, at)
    upper = min(b - slope * (t - at) for t, b in uppers)
    lower = max((b - slope * (t - at) for t, b in lowers), default=upper)
    c = {"offset_ns": (lower + upper) / 2, "at_ns": at, "slope": slope,
         "lower_ns": lower, "upper_ns": upper,
         "drift_ns": slope * (max(times) - min(times)),
         "pairs": len(launches), "waits": len(waits), "by": by}
    c["violations"] = sum(
        1 for span, run in launches if run[1] < span[1] + offset_at(c, span[1]))
    c["violations"] += sum(
        1 for span, run in waits if run[1] + run[2]
        > span[1] + span[2] + offset_at(c, span[1] + span[2]))
    return c


def offset_at(c: Dict[str, Any], host_ns: float) -> float:
    """What is added to the host-plane time ``host_ns`` to put it on the
    device's clock."""
    return c["offset_ns"] + c["slope"] * (host_ns - c["at_ns"])


def shift(program_spans: Sequence[ProgramSpan], c: Dict[str, Any]
          ) -> List[ProgramSpan]:
    """The program's spans on the device's clock."""
    return [(name, start + offset_at(c, start), dur, attrs, thread)
            for name, start, dur, attrs, thread in program_spans]
