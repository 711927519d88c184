"""The program's one recorder: host spans, counters, compiles by phase.

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
  ``TraceMe.is_enabled()`` are the whole cost (~1.2 us a span on a 2026
  server core).
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
* One ``jax.monitoring`` listener books what JAX spends on a program
  while a span of a recorder is open on the compiling thread, by phase
  (:data:`COMPILE_PHASES`): counters ``compile.trace_us``,
  ``compile.lower_us``, ``compile.backend_us``, ``compile.cache_load_us``
  (cumulative microseconds) beside ``compiles`` (programs compiled by the
  backend or loaded from the persistent cache), and a ring entry
  ``compile`` with ``phase``, ``fun_name`` and, inside a step span, the
  step's number under ``step``; its parent is the span it fell in. The
  listener runs only when JAX compiles: nothing on the hot path.
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


def _stack() -> list:
    """Open spans of this thread, innermost last."""
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
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
#: a program's trace reports every jitted helper traced inside it (thousands
#: of sub-millisecond events a serving engine): the counter takes them all,
#: the ring those at least this long
TRACE_ENTRY_NS = 1_000_000


def _on_compile(event: str, secs: float, **kw) -> None:
    phase_counter = COMPILE_PHASES.get(event)
    stack = _stack()
    if phase_counter is None or not stack:
        return
    state = vars(_tls)
    if phase_counter is _CACHE_LOAD:
        # JAX reports a persistent-cache hit INSIDE the backend event of
        # the same program, which follows at once and holds this time
        state["loaded"] = True
        return
    phase, counter = phase_counter
    now = time.monotonic_ns()
    start = now - int(secs * 1e9)
    self_ns = now - start
    top = stack[-1]
    rec = top.rec
    if phase == "trace":
        # a jitted function traced inside another's trace reports first:
        # the outer one's counter takes its own part only
        inner = state.setdefault("traces", [])
        while inner and inner[-1][0] >= start:
            a, b = inner.pop()
            self_ns -= b - a
        inner.append((start, now))
    elif phase == "backend":
        state.pop("traces", None)
        if state.pop("loaded", False):
            phase, counter = _CACHE_LOAD
        rec.count("compiles")
    rec.count(counter, max(self_ns, 0) // 1000)
    if phase == "trace" and now - start < TRACE_ENTRY_NS:
        return
    attrs = {"phase": phase, "fun_name": str(kw.get("fun_name", ""))}
    for span in reversed(stack):
        if span.rec is rec and span._before is not None:
            attrs["step"] = span.attrs.get("step",
                                           span.attrs.get("step_num"))
            break
    rec._append(("compile", top.name, start, now, attrs))


def _listen() -> None:
    """Register the compile listener once a process (jax.monitoring keeps
    listeners for the life of the process, so not one per engine)."""
    global _listening
    with _listen_lock:
        if not _listening:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_compile)
            _listening = True


def recent() -> List["Recorder"]:
    """Recorders of the last :data:`KEPT` engines, oldest first."""
    return list(_recent)


class _Span:
    __slots__ = ("rec", "name", "attrs", "parent", "start_ns", "_ann",
                 "_before", "_stack")

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
        self._stack.pop()
        if self._before is not None:
            before = self._before
            self.attrs["d"] = {k: v - before.get(k, 0)
                               for k, v in self.rec.counters.items()
                               if v != before.get(k, 0)}
        self.rec._append((self.name, self.parent, self.start_ns, end_ns,
                          self.attrs))


class Recorder:
    """Spans, counters and gauges of one engine (module docstring)."""

    def __init__(self, kind: str, ring_size: int = RING_SIZE,
                 keep: bool = True):
        self.kind = kind
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, Any] = {}
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
        return {"kind": self.kind, "counters": dict(self.counters),
                "gauges": dict(self.gauges), "spans": self.span_times(),
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
