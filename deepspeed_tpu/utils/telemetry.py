"""The program's one recorder: host spans, counters, compiles by phase and
program, and where the time from an engine's constructor to a step went.

Both engines (``runtime/engine.py``, ``serving/engine.py``) hold one
:class:`Recorder` and record through it; there is no switch.

* :meth:`Recorder.span` is a context manager. It enters a
  ``jax.profiler.TraceAnnotation("ds/" + name, **attrs)``, so that whenever
  a profiler session is active (``engine.profile_trace``, any
  ``jax.profiler.trace``) the span lies in the ``/host:CPU`` plane, on the
  HOST plane's clock: in a TPU profile the device planes' clock runs about
  a millisecond apart from it, so a reader that sets a span against device
  ops aligns the two first (``benchmark/clock.py`` does, from causality:
  no program runs before the span that launched it began). It also appends
  ``(name, parent, start_ns, end_ns, attrs)`` to a bounded ring (oldest
  dropped, the drop counted). With no session the ring append and one
  ``TraceMe.is_enabled()`` are nearly the whole cost (below).
  The ring's clock is ``time.monotonic_ns()``, the clock of the ``Request``
  stamps; ring and profiler trace are joined by the ``step`` /
  ``step_num`` attribute that step spans carry in both, never by
  comparing clocks.
* :meth:`Recorder.count` / :meth:`Recorder.gauge` are plain dict
  arithmetic on ``counters`` / ``gauges``. Counters are cumulative; a step
  span (:meth:`Recorder.step_span`) carries, under ``attrs["d"]``, what
  each counter gained inside that step, so a reader can sum any range of
  steps.
* No span adds a device sync. A span round a wait the program already
  makes is named ``*.fetch`` / ``*.sync`` and is the only kind that holds
  device time.
* Some spans also add their time to a counter when they end (:data:`TIMED`,
  looked up once, when the span is made; cumulative microseconds, from the
  two stamps the span takes anyway), so that what they say outlives the
  ring: the constructors' spans to ``serve.init_us`` / ``train.init_us``,
  a step span to ``serve.step_us`` / ``train.step_us`` (BEFORE it works out
  ``attrs["d"]``, which so holds the step's own time), a wait span to
  ``serve.wait_us`` / ``train.wait_us``.
* Two ``jax.monitoring`` listeners (one of durations, one of events; both
  registered once a process) book what JAX spends on a program while a
  span of a recorder is open on the compiling thread, to that recorder:

  - by phase (:data:`COMPILE_PHASES`): counters ``compile.trace_us``,
    ``compile.lower_us``, ``compile.backend_us``, ``compile.cache_load_us``
    (cumulative microseconds; a trace inside a trace counted once) beside
    ``compiles`` (programs compiled by the backend or loaded from the
    persistent cache), and a ring entry ``compile`` with ``phase``,
    ``fun_name`` and, inside a step span, the step's number under ``step``;
    its parent is the span it fell in;
  - the persistent cache's own events (:data:`CACHE_EVENTS`):
    ``compile.cache_requests`` (programs JAX looked up there),
    ``compile.cache_hits``, ``compile.cache_misses`` (an entry WRITTEN) and
    ``compile.backend_compiles`` (a backend phase that was no hit), so that
    ``backend_compiles - cache_misses`` counts the programs compiled at
    every start because JAX never writes their entry (too small, too
    quick); ``compile.saved_us``, what the entries that hit say their
    compiles took (JAX's ``compile_time_saved_sec`` with the retrieval it
    subtracts put back): ``backend_us + saved_us`` is a warm start's own
    estimate of a cold start's backend compiles. JAX keeps an entry's time
    in WHOLE seconds, cut, so it reads low by half a second a hit on
    average and 0 for a program that compiled in under a second;
  - the rest of a first call: the span a phase was booked in (the
    innermost open one: ``serve.decode.dispatch``, ``serve.prefill.
    dispatch``, ``train.dispatch``, the constructors' spans, any other
    that launches a jitted function) adds, when it ends, its duration less
    everything booked inside it to ``compile.first_call_rest_us`` and one
    to ``compile.first_calls``: the jit's own miss path, the executable's
    load to the device, the first transfer and run. It is never negative,
    and a span in which nothing compiled books nothing;
  - by program, outside the ring: :attr:`Recorder.programs`, one row a
    ``(fun_name, shape)`` (:func:`shape_of` is the rule that tells one
    function's programs apart), with ``trace_us`` (the helpers traced
    inside it too, short ones the ring leaves out included), ``lower_us``,
    ``backend_us``, ``cache_load_us``, ``saved_us``, ``rest_us``,
    ``compiles``, ``hit`` (how many of them the cache served), the
    ``span`` and the ``step`` it last fell in; at most
    :data:`PROGRAM_ROWS`, the overflow counted as
    ``compile.programs_dropped``.

  The listeners run only when JAX compiles: nothing on the hot path.
* **The identity** (``tests/test_telemetry.py`` holds it to the
  microsecond: every part is a sum of differences of the same stamps, each
  cut to whole microseconds first). From the recorder's making
  (``snapshot()["t0_ns"]``: the constructor's start) to the start of any
  step, ``init_us + step_us + (time outside steps)`` is the wall. Inside
  the steps, ``trace + lower + backend + cache_load + first_call_rest +
  wait + host`` is ``step_us``, where host is what is left; a span's time
  goes to ONE of them (a wait span or a span that compiled books its own
  time less what the spans inside it booked). So every second between an
  engine's constructor and a step is one of: init, tracing, lowering,
  backend compile, cache load, rest of first calls, waiting for the
  device, host inside steps, outside steps (the caller).
* What a span costs: with no profiler session the ring append, one
  ``TraceMe.is_enabled()`` and one lookup in :data:`TIMED` (~1.3 us a span
  on a 2026 server core); a wait span a walk over what the step has booked
  so far (two or three entries) and two additions more; a step span the
  copy and the diff of the counters.
* Both engines make their recorder first and wrap their constructor in
  ``serve.init`` / ``train.init``, so set-up and its compiles are recorded
  like any step.
* The module keeps the recorders of the last few engines
  (:func:`recent`): rings and counters, not the engines, for a reader that
  never held the engine.
* :func:`scope_of` reads a ``jax.named_scope`` path out of a device op's
  ``op_name`` (a profile carries it), for a trace reader to group device
  ops by the source scope that made them.

``jax.profiler`` is imported on the first span and nothing else is.
"""

from __future__ import annotations

import collections
import json
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

#: every span of the program starts with this in a profiler trace
PREFIX = "ds/"
#: ring entries a recorder keeps: minutes of steps (a serving step is ~25
#: entries, a train step ~10), a few MB
RING_SIZE = 65536
#: recorders of the last engines that :func:`recent` can still hand out
KEPT = 4

#: (name, parent, start_ns, end_ns, attrs)
Entry = Tuple[str, Optional[str], int, int, Dict[str, Any]]

_tls = threading.local()
_recent: "collections.deque[Recorder]" = collections.deque(maxlen=KEPT)
_annotations = None            # (TraceAnnotation, StepTraceAnnotation)
_listening = False
_listen_lock = threading.Lock()


class _Thread(list):
    """The open spans of one thread, innermost last, beside what the thread
    has booked and what the listeners carry from one event of a program to
    the next."""
    __slots__ = ("booked", "traces", "loaded", "saved", "pending", "last")

    def __init__(self):
        super().__init__()
        #: (stamp_ns, microseconds) of whatever this thread booked to a
        #: phase, to the rest of a first call or to a wait, oldest first: a
        #: span that books its own time takes what was booked since it
        #: began out of its duration (:func:`_book`)
        self.booked: List[Tuple[int, int]] = []
        #: (start, end) of the traces reported since the last backend phase
        self.traces: list = []
        #: a persistent-cache hit was reported; its backend event follows
        self.loaded = False
        #: seconds JAX said that hit saved; its retrieval's event follows
        self.saved: Optional[float] = None
        #: what the program now being traced, lowered or looked up has
        #: spent so far (its backend phase closes the row), or None
        self.pending: Optional[Dict[str, Any]] = None
        #: (span, row) of the last program a span of this thread compiled
        self.last: tuple = (None, None)


def _stack() -> _Thread:
    """Open spans of this thread, innermost last."""
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = _Thread()
        return _tls.stack


def _load_annotations():
    global _annotations
    from jax import profiler
    _annotations = (profiler.TraceAnnotation, profiler.StepTraceAnnotation)
    return _annotations


_CACHE_LOAD = ("cache_load", "compile.cache_load_us")
#: ``jax.monitoring`` duration event -> (phase of a ``compile`` ring entry,
#: the counter its microseconds go to). The first three carry ``fun_name``.
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace", "compile.trace_us"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "compile.lower_us"),
    "/jax/core/compile/backend_compile_duration":
        ("backend", "compile.backend_us"),
    "/jax/compilation_cache/cache_retrieval_time_sec": _CACHE_LOAD,
}
#: the duration event of a persistent-cache hit that holds what the entry's
#: compile took (whole seconds), less the time of its retrieval
SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
#: ``jax.monitoring`` event -> the counter it adds one to (``cache_misses``
#: is recorded where JAX WRITES an entry, not where it finds none)
CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        "compile.cache_requests",
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
#: a program's trace reports every jitted helper traced inside it (thousands
#: of sub-millisecond events a serving engine): the counter and the
#: program's row take them all, the ring those at least this long
TRACE_ENTRY_NS = 1_000_000
#: rows :attr:`Recorder.programs` keeps
PROGRAM_ROWS = 1024
#: where the time of a span in which a phase was booked goes, less the
#: phases and whatever else was booked inside it
FIRST_CALL_REST = "compile.first_call_rest_us"
#: what the listeners count, for an engine to start at zero: a start the
#: cache served whole then READS ``compile.backend_us`` 0 and not nothing
COMPILE_COUNTERS = (
    "compiles", "compile.trace_us", "compile.lower_us", "compile.backend_us",
    "compile.cache_load_us", FIRST_CALL_REST, "compile.first_calls",
    "compile.saved_us", "compile.backend_compiles") + tuple(
        CACHE_EVENTS.values())
#: span name -> (the counter its whole duration goes to, the counter its
#: OWN time goes to: its duration less what was booked inside it). The
#: constructors and the steps are wholes; the waits (``benchmark/clock.py``
#: calls their suffixes ``WAIT_SUFFIXES``) are disjoint parts of a step
TIMED = {
    "serve.init": ("serve.init_us", None),
    "serve.step": ("serve.step_us", None),
    "serve.decode.fetch": (None, "serve.wait_us"),
    "serve.prefill.fetch": (None, "serve.wait_us"),
    "train.init": ("train.init_us", None),
    "train.step": ("train.step_us", None),
    "train.sync": (None, "train.wait_us"),
}


def shape_of(stack, rec: "Recorder"):
    """What tells the programs of ONE jitted function apart, read off the
    spans open where it compiled: the ``tokens`` attribute of the innermost
    span of ``rec`` that carries one (``serve.prefill``'s: a prefill
    program a count of rows), else ``None`` (a function with one program,
    or programs that :attr:`Recorder.programs` keeps in one row)."""
    for span in reversed(stack):
        if span.rec is rec and "tokens" in span.attrs:
            return span.attrs["tokens"]
    return None


def _step_of(stack, rec: "Recorder") -> Dict[str, Any]:
    """``{"step": n}`` of the step span of ``rec`` open on this thread;
    ``{}`` outside every step."""
    for span in reversed(stack):
        if span.rec is rec and span._before is not None:
            return {"step": span.attrs.get("step",
                                           span.attrs.get("step_num"))}
    return {}


def _book(stack: _Thread, stamp_ns: int, us: int) -> None:
    """``us`` microseconds booked on this thread at ``stamp_ns``. What lies
    before the outermost open span can matter to nobody and goes, now and
    then."""
    booked = stack.booked
    if len(booked) >= 256:
        oldest = stack[0].start_ns
        booked[:] = [b for b in booked if b[0] >= oldest]
    booked.append((stamp_ns, us))


def _pending(stack: _Thread) -> Dict[str, Any]:
    if stack.pending is None:
        stack.pending = {"fun_name": "", "trace_us": 0, "lower_us": 0,
                         "saved_us": 0}
    return stack.pending


def _close_program(stack: _Thread, **last) -> None:
    """The pending program's phases go to its row of the innermost span's
    recorder: after its backend phase (``last``: what that phase adds), or
    when the span ends on a trace that nothing compiled."""
    pending, stack.pending = _pending(stack), None
    span = stack[-1]
    rec = span.rec
    key = (pending.pop("fun_name"), shape_of(stack, rec))
    row = rec.programs.get(key)
    if row is None:
        if len(rec.programs) >= PROGRAM_ROWS:
            rec.count("compile.programs_dropped")
            stack.last = (span, None)
            return
        row = rec.programs[key] = {
            "fun_name": key[0], "shape": key[1], "trace_us": 0,
            "lower_us": 0, "backend_us": 0, "cache_load_us": 0,
            "saved_us": 0, "rest_us": 0, "compiles": 0, "hit": 0}
    for k, v in {**pending, **last}.items():
        row[k] += v
    row["span"] = span.name
    row["step"] = _step_of(stack, rec).get("step")
    stack.last = (span, row)


def _on_compile(event: str, secs: float, **kw) -> None:
    stack = _stack()
    if not stack:
        return
    top = stack[-1]
    rec = top.rec
    if event == SAVED_EVENT:
        stack.saved = secs
        return
    phase_counter = COMPILE_PHASES.get(event)
    if phase_counter is None:
        return
    if phase_counter is _CACHE_LOAD:
        # JAX reports a persistent-cache hit INSIDE the backend event of
        # the same program, which follows at once and holds this time. What
        # it says the hit saved is the entry's compile time (which it keeps
        # in WHOLE seconds, cut) less this retrieval: the entry's own time
        # is booked, so a program that compiled in under a second saves 0
        stack.loaded = True
        if stack.saved is not None:
            us = max(round((stack.saved + secs) * 1e6), 0)
            stack.saved = None
            rec.count("compile.saved_us", us)
            _pending(stack)["saved_us"] += us
        return
    phase, counter = phase_counter
    now = time.monotonic_ns()
    start = now - int(secs * 1e9)
    self_ns = now - start
    if phase == "trace":
        # a jitted function traced inside another's trace reports first:
        # the outer one's counter takes its own part only
        inner = stack.traces
        while inner and inner[-1][0] >= start:
            a, b = inner.pop()
            self_ns -= b - a
        inner.append((start, now))
    elif phase == "backend":
        stack.traces = []
        if stack.loaded:
            phase, counter = _CACHE_LOAD
        else:
            rec.count("compile.backend_compiles")
        rec.count("compiles")
    us = max(self_ns, 0) // 1000
    rec.count(counter, us)
    fun_name = str(kw.get("fun_name", ""))
    pending = _pending(stack)
    pending["fun_name"] = fun_name
    part = counter[len("compile."):]
    if phase in ("trace", "lower"):
        pending[part] += us
    else:
        _close_program(stack, compiles=1, hit=int(stack.loaded),
                       **{part: us})
        stack.loaded = False
    # the span the phase fell in owes the rest of its time (``_Span.
    # _book``); the spans round it, what was booked inside them
    _book(stack, now, us)
    top._timed = (top._timed[0] if top._timed else None, FIRST_CALL_REST)
    if phase == "trace" and now - start < TRACE_ENTRY_NS:
        return
    rec._append(("compile", top.name, start, now,
                 {"phase": phase, "fun_name": fun_name,
                  **_step_of(stack, rec)}))


def _on_cache_event(event: str, **kw) -> None:
    counter = CACHE_EVENTS.get(event)
    if counter is not None:
        stack = _stack()
        if stack:
            stack[-1].rec.count(counter)


def _listen() -> None:
    """Register the two compile listeners once a process (jax.monitoring
    keeps listeners for the life of the process, so not a pair an engine)."""
    global _listening
    with _listen_lock:
        if not _listening:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_compile)
            monitoring.register_event_listener(_on_cache_event)
            _listening = True


def recent() -> List["Recorder"]:
    """Recorders of the last :data:`KEPT` engines, oldest first."""
    return list(_recent)


class _Span:
    __slots__ = ("rec", "name", "attrs", "parent", "start_ns", "_ann",
                 "_before", "_stack", "_timed")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any],
                 step: bool):
        self.rec, self.name, self.attrs = rec, name, attrs
        ann = _annotations or _load_annotations()
        # with no profiler session the annotation is left out altogether
        # (is_enabled is a tenth of making, entering and leaving one)
        self._ann = (ann["step_num" in attrs](PREFIX + name, **attrs)
                     if ann[0].is_enabled() else None)
        # a step span reports what each counter gained inside it
        self._before = dict(rec.counters) if step else None
        # (whole, own) where its time goes to a counter; a compile phase
        # booked in it makes it one that books its own time
        self._timed = TIMED.get(name)

    def __enter__(self) -> "_Span":
        stack = self._stack = _stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        timed = self._timed
        if timed is not None:
            whole, own = timed
            us = end_ns // 1000 - self.start_ns // 1000
            if whole is not None:
                counters = self.rec.counters
                counters[whole] = counters.get(whole, 0) + us
            if own is not None:
                self._book_own(end_ns, us, own)
        self._stack.pop()
        if self._before is not None:
            before = self._before
            self.attrs["d"] = {k: v - before.get(k, 0)
                               for k, v in self.rec.counters.items()
                               if v != before.get(k, 0)}
        self.rec._append((self.name, self.parent, self.start_ns, end_ns,
                          self.attrs))

    def _book_own(self, end_ns: int, us: int, own: str) -> None:
        """A wait span, or one a compile phase was booked in: its ``us``
        microseconds less what was booked on this thread since it began go
        to the counter ``own``, and are booked in turn."""
        stack, start = self._stack, self.start_ns
        booked = stack.booked
        i = len(booked) - 1
        while i >= 0 and booked[i][0] >= start:
            us -= booked[i][1]
            i -= 1
        us = max(us, 0)
        _book(stack, end_ns, us)
        self.rec.count(own, us)
        if own is FIRST_CALL_REST:
            self.rec.count("compile.first_calls")
            if stack.pending is not None:       # traced, never compiled
                _close_program(stack)
            span, row = stack.last
            if span is self and row is not None:
                row["rest_us"] += us


class Recorder:
    """Spans, counters and gauges of one engine (module docstring)."""

    def __init__(self, kind: str, ring_size: int = RING_SIZE,
                 keep: bool = True):
        self.kind = kind
        #: when it was made (``time.monotonic_ns()``): an engine makes it
        #: first, so this is the start of the constructor
        self.t0_ns = time.monotonic_ns()
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, Any] = {}
        #: (fun_name, shape) -> what that program cost (module docstring)
        self.programs: Dict[tuple, Dict[str, Any]] = {}
        self.ring: "collections.deque[Entry]" = collections.deque(
            maxlen=int(ring_size))
        self.dropped = 0
        if keep:
            _recent.append(self)
            _listen()

    # ------------------------------------------------------------- recording

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs, False)

    def step_span(self, name: str, **attrs) -> _Span:
        """A span of one step, whose ring entry carries the counters'
        gains inside it under ``attrs["d"]``. With ``step_num`` among the
        attributes it is a ``StepTraceAnnotation`` and XProf groups the
        trace by step."""
        return _Span(self, name, attrs, True)

    def event(self, name: str, **attrs) -> None:
        """A zero-length span: a transition, under the span it fell in."""
        with _Span(self, name, attrs, False):
            pass

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value) -> None:
        self.gauges[name] = value

    def _append(self, entry: Entry) -> None:
        if len(self.ring) == self.ring.maxlen:
            self.dropped += 1
        self.ring.append(entry)

    # --------------------------------------------------------------- reading

    def span_times(self, since_ns: int = 0) -> Dict[str, Dict[str, float]]:
        """Per span name over the ring (entries that started at or after
        ``since_ns``): ``count``, ``total_ms`` and ``self_ms``, a span's
        duration less what its children cover."""
        return span_times(list(self.ring), since_ns)

    def snapshot(self) -> Dict[str, Any]:
        return {"kind": self.kind, "t0_ns": self.t0_ns,
                "counters": dict(self.counters),
                "gauges": dict(self.gauges), "spans": self.span_times(),
                "programs": [dict(row) for row in list(self.programs.values())],
                "ring_entries": len(self.ring), "ring_dropped": self.dropped}

    def dump(self, path: str) -> str:
        """The ring as JSON lines, oldest first: ``name``, ``parent``,
        ``start_ns``, ``end_ns``, ``attrs``."""
        with open(path, "w") as f:
            for name, parent, start, end, attrs in list(self.ring):
                f.write(json.dumps({"name": name, "parent": parent,
                                    "start_ns": start, "end_ns": end,
                                    "attrs": attrs}) + "\n")
        return path


def span_times(entries: List[Entry], since_ns: int = 0
               ) -> Dict[str, Dict[str, float]]:
    """:meth:`Recorder.span_times` of any list of ring entries. Children
    name their parent, so self time by name is the name's total less the
    total of the entries whose parent it is."""
    out: Dict[str, Dict[str, float]] = {}
    child_ns: Dict[str, int] = collections.defaultdict(int)
    for name, parent, start, end, _ in entries:
        if start < since_ns:
            continue
        row = out.setdefault(name, {"count": 0, "total_ms": 0.0})
        row["count"] += 1
        row["total_ms"] += (end - start) / 1e6
        if parent is not None:
            child_ns[parent] += end - start
    for name, row in out.items():
        row["self_ms"] = row["total_ms"] - child_ns.get(name, 0) / 1e6
    return out


# -------------------------------------------------------------- device scopes

#: the ``jax.named_scope`` names the program puts on its device work, at
#: layer boundaries only (``models/transformer.py``, ``serving/
#: model_runner.py``, the step functions of ``runtime/engine.py``)
SCOPES = frozenset({
    "embed", "layers", "block.attn", "qkv", "kv_write", "index",
    "index_write", "select", "attend", "out",
    "latent_q", "latent_write", "absorb",
    "block.mlp", "route", "dispatch", "experts", "combine", "shared",
    "head", "loss", "grad_reduce", "sample", "grad_accum", "optimizer",
    "zero.gather", "zero.scatter"})

_SEGMENT = re.compile(r"[^/()]+")


def scope_of(op_name: str) -> str:
    """The scope path of one ``op_name``: its segments that are
    :data:`SCOPES`, joined by ``.`` (JAX writes ``jit(step)/transpose(jvp(
    block.attn))/qkv/dot_general``; flax adds its module names between).
    Prefixed ``recompute:`` for an op of a rematerialised forward and
    ``backward:`` for any other op of the backward pass (JAX writes
    ``rematted_computation`` / ``transpose(`` into those). ``layers``
    alone names the layer loop's own work (slicing stacked parameters,
    stacking what the loop carries out). ``""`` where the op lies under no
    scope of the program."""
    segments = [s for s in _SEGMENT.findall(op_name) if s in SCOPES]
    if len(segments) > 1 and segments[0] == "layers":
        # "layers" alone is the layer loop's own work; a block's scope
        # inside it stands for itself
        segments = segments[1:]
    path = ".".join(segments)
    if "rematted_computation" in op_name:
        return "recompute:" + path
    if "transpose(" in op_name:
        return "backward:" + path
    return path
