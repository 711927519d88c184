"""Taking a profiler trace and reading it back as plain events.

``capture`` wraps ``jax.profiler`` (the Python tracer off: it would fill the
trace with interpreter frames); ``read`` turns the ``.xplane.pb`` into a
``Trace`` of plain tuples with ``jax.profiler.ProfileData`` and nothing but
JAX. All reduction to numbers is in ``reduce.py`` and works on a ``Trace``, so
it can be checked on a recorded one (``tests/data/``).

What a TPU trace looks like (looked at by hand, PR 23, jax 0.9.0): one plane
per chip named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per
executed HLO instruction, and the event's name is the instruction's whole
text: ``%copy.39.remat = bf16[16,32,12288,128]{3,2,1,0:T(8,128)(2,1)}
copy(...)``. The line nests: a ``%while`` spans the instructions of its body.
A Pallas kernel is a ``custom-call`` named after the kernel
(``%flash_attention_fwd.12``, ``%paged_attention.5``); the events carry no
``jax.named_scope`` path, so an instruction is found by its name alone.
``XLA Modules`` holds one event per program run (``jit_train_step(...)``),
``Async XLA Ops`` the start-done pairs of asynchronous copies (they overlap
compute and are not counted as busy time here). Host threads are lines of the
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans are events there
under the name given. All planes share one clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
from typing import Callable, Dict, List, Tuple, TypeVar

T = TypeVar("T")

#: (name, result, start_ns, duration_ns): the HLO instruction's name without
#: its ``%`` (``copy.39.remat``) and the shape it produces (``bf16[16,32,
#: 12288,128]``), both cut out of the event's name
DeviceOp = Tuple[str, str, float, float]
#: (name, start_ns, duration_ns)
Span = Tuple[str, float, float]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: prefix of the benchmark's own host spans
ANNOTATION = "bench/"
#: the host span that :func:`record` puts round the traced part of a window
WINDOW_SPAN = "window"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[DeviceOp]]      # plane name -> its XLA Ops
    host: List[Span]                        # the benchmark's annotations
    #: what ``reduce`` has worked out of this trace already (self times)
    cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                    compare=False)

    def to_json(self):
        return {"devices": self.devices, "host": self.host}

    @staticmethod
    def from_json(d) -> "Trace":
        tup = lambda rows: [tuple(r) for r in rows]
        return Trace({k: tup(v) for k, v in d["devices"].items()},
                     tup(d["host"]))


@contextlib.contextmanager
def capture(trace_dir: str):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def record(trace_dir: str, part: Callable[[], T]) -> Tuple[T, "Trace"]:
    """Run ``part()`` traced, under the ``window`` span; (its result, the
    trace read back)."""
    with capture(trace_dir):
        with annotate(WINDOW_SPAN):
            out = part()
    return out, read(trace_dir)


def annotate(name: str):
    """A host span of the benchmark's own, on the device trace's clock."""
    import jax
    return jax.profiler.TraceAnnotation(ANNOTATION + name)


def split_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, result shape) of an ``XLA Ops`` event name, which
    is ``%name = shape{layout} opcode(operands), attributes``. A tuple
    result gives its first shape; a name that is no HLO text is kept whole."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), ""
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return head.lstrip("%"), shape.rstrip(",")


def read(trace_dir: str, device_plane: str = DEVICE_PLANE) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (*split_hlo(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                host.extend((e.name[len(ANNOTATION):], float(e.start_ns),
                             float(e.duration_ns)) for e in line.events
                            if e.name.startswith(ANNOTATION))
    host.sort(key=lambda s: s[1])
    return Trace(devices, host)
