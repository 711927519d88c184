"""The program's one recorder: host spans, counters, and scope paths.

Both engines (``runtime/engine.py``, ``serving/engine.py``) hold one
:class:`Recorder` and record through it; there is no switch.

* :meth:`Recorder.span` is a context manager. It enters a
  ``jax.profiler.TraceAnnotation("ds/" + name, **attrs)``, so that whenever
  a profiler session is active (``engine.profile_trace``, any
  ``jax.profiler.trace``) the span lies in the ``/host:CPU`` plane on the
  DEVICE TRACE'S CLOCK, and it appends ``(name, parent, start_ns, end_ns,
  attrs)`` to a bounded ring (oldest dropped, the drop counted). With no
  session the ring append and one ``TraceMe.is_enabled()`` are the whole
  cost (~1.2 us a span on a 2026 server core).
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
* A ``jax.monitoring`` listener counts the programs handed to the backend
  compiler while a span of a recorder is open on the compiling thread:
  counter ``compiles``, and a ring entry ``compile`` whose parent is the
  span it fell in.
* The module keeps the recorders of the last few engines
  (:func:`recent`): rings and counters, not the engines, for a reader that
  never held the engine.
* :func:`scope_paths` reads ``{HLO instruction: jax.named_scope path}``
  out of a compiled program's text, for a trace reader to group device
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
    """Open spans of this thread, innermost last: (recorder, name)."""
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


def _on_compile(event: str, secs: float, **kw) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    stack = _stack()
    if stack:
        rec, parent = stack[-1]
        rec.count("compiles")
        now = time.monotonic_ns()
        rec._append(("compile", parent, now - int(secs * 1e9), now, {}))


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
        self.parent = stack[-1][1] if stack else None
        stack.append((self.rec, self.name))
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


# --------------------------------------------------------------- scope paths

#: the ``jax.named_scope`` names the program puts on its device work, at
#: layer boundaries only (``models/transformer.py``, ``serving/
#: model_runner.py``, the step functions of ``runtime/engine.py``)
SCOPES = frozenset({
    "embed", "layers", "block.attn", "qkv", "kv_write", "attend", "out",
    "block.mlp", "route", "dispatch", "experts", "combine",
    "head", "loss", "grad_reduce", "sample", "grad_accum", "optimizer",
    "zero.gather", "zero.scatter"})

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)
_SEGMENT = re.compile(r"[^/()]+")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of an HLO module's text. A fusion
    carries the ``op_name`` the compiler wrote on the fusion instruction
    itself (that of its root)."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


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


def scope_paths(jitted_fn, *args, **kwargs) -> Dict[str, str]:
    """``{HLO instruction name: scope path}`` (:func:`scope_of`) of the
    program ``jitted_fn`` compiles for these arguments (arrays or
    ``ShapeDtypeStruct``s), read from the optimized module's
    ``metadata={op_name=...}``: for a trace reader whose trace does not
    carry ``op_name`` itself."""
    text = jitted_fn.lower(*args, **kwargs).compile().as_text()
    return {instr: scope_of(op_name)
            for instr, op_name in hlo_op_names(text).items()}
