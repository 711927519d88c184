"""What every cell shares: finding a cell's files, taking the device, the
compile cache, counting compiles, and the one result line.

Everything that belongs to ONE configuration, traffic mix, cell or per-layer
metric is a data file found by name (``configs/``, ``traffic/``,
``workloads/``, ``layer_metrics/``); this module and the drivers hold no
name of any of them.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: a --trace 1 run traces this much of its window (or half of a shorter one)
#: and takes clocks and counters over the rest
TRACE_SECONDS = 5.0
#: where a --trace 1 run keeps its profile (wiped first)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads``, with its files read."""
    name: str
    kind: str                    # the driver: drivers/<kind>.py
    chips: int
    why: str
    config: Dict[str, Any]       # configs/<config>.json
    traffic: Dict[str, Any]      # traffic/<traffic>.json
    system: Dict[str, Any]       # how the program is set up for this cell
    expect_kernels: tuple        # kernel scopes a traced run has to find


def load_cell(name: str, base: str = HERE) -> Cell:
    w = load_json(os.path.join(base, "workloads", f"{name}.json"))
    if w["name"] != name:
        raise ValueError(f"workloads/{name}.json names itself {w['name']!r}")
    if w["chips"] not in (1, 4):
        raise ValueError(f"{name}: chips is {w['chips']!r}, not 1 or 4")
    config = load_json(os.path.join(base, "configs", f"{w['config']}.json"))
    traffic = load_json(os.path.join(base, "traffic", f"{w['traffic']}.json"))
    return Cell(name=name, kind=w["kind"], chips=int(w["chips"]),
                why=w["why"], config=config, traffic=traffic,
                system=w["system"],
                expect_kernels=tuple(w.get("expect_kernels", ())))


def load_layer_metrics(kind: str, base: str = HERE,
                       cell: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every ``layer_metrics/*.json`` that cell ``cell`` of kind ``kind``
    reports. A file lists either ``kinds`` (every cell of those kinds, the
    ones later PRs add too) or ``workloads`` (the cells it names and no
    other: the metric of a mechanism that only they have, as a mixture's
    experts)."""
    d = os.path.join(base, "layer_metrics")
    out = []
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            m = load_json(os.path.join(d, fn))
            if m["name"] + ".json" != fn:
                raise ValueError(f"layer_metrics/{fn} names itself "
                                 f"{m['name']!r}")
            if ("kinds" in m) == ("workloads" in m):
                raise ValueError(f"layer_metrics/{fn} lists either kinds or "
                                 f"workloads, not both and not neither")
            reports = cell in m["workloads"] if "workloads" in m \
                else kind in m["kinds"]
            if reports:
                out.append(m)
    return out


def load_family(name: str):
    """``families/<name>.py``: what one model family needs (the mapping from
    its published config keys to the program's, its sizes, its reference)."""
    return importlib.import_module(f"benchmark.families.{name}")


def load_driver(kind: str):
    return importlib.import_module(f"benchmark.drivers.{kind}")


def peaks_for(device_kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}: "
                       f"add it to benchmark/peaks.json with its source "
                       f"(known: {sorted(k for k in table if k[0] != '_')})")
    return table[device_kind]


def context(cell: "Cell", family, devices, rehearsal: bool) -> Dict[str, Any]:
    """What the reducers may need besides clocks, counters and the trace."""
    return {"dims": family.dims(cell.config), "traffic": cell.traffic,
            "peaks": None if rehearsal else peaks_for(devices[0].device_kind)}


# ------------------------------------------------------------------ device


def take_devices(chips: int, rehearsal: bool = False):
    """First touch of JAX. Without a TPU, or with another number of chips
    than the cell asks for, the run ends here: non-zero, no result line."""
    import jax
    devs = jax.devices()
    print(f"[bench] jax {jax.__version__}; platform {devs[0].platform}, "
          f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s)",
          flush=True)
    if rehearsal:
        return devs[:chips]
    if devs[0].platform != "tpu":
        print("benchmark: JAX found no TPU; nothing is measured on any other "
              "device", file=sys.stderr, flush=True)
        raise SystemExit(3)
    if len(devs) != chips:
        print(f"benchmark: the cell asks for {chips} chip(s), JAX reports "
              f"{len(devs)}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    return devs


def configure_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says, else
    the fixed ``<checkout>/.jax_cache`` (the path is part of the key). Every
    program is cached, however quick its compile, so that a second run of a
    cell finds them all."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    print(f"[bench] compile cache: {path}", flush=True)
    return path


class CompileWatch:
    """Counts programs handed to the backend compiler (a persistent-cache hit
    counts too: it is still a program the window had not seen), through
    ``jax.monitoring``. Copied from ``chip_smoke.CompileWatch``."""

    def __init__(self, t0: float):
        import jax
        self.t0 = t0
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.secs += secs
            self.compiles += 1

    def _ev(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def report(self, label: str) -> None:
        print(f"[bench] {time.perf_counter() - self.t0:7.2f}s {label}: "
              f"{self.compiles} programs compiled or loaded so far "
              f"({self.secs:.1f}s; persistent cache {self.hits} hits, "
              f"{self.misses} misses)", flush=True)


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip; 0 where the backend keeps no counter."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def jax_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; a PRNG key takes 32 signed bits."""
    return int(seed) % (2 ** 31 - 1)


# ------------------------------------------------------------------ result


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], devices,
                memory_peak: int, busy_s: Optional[float] = None,
                window_s: Optional[float] = None,
                breakdown: Optional[Dict[str, Any]] = None,
                compared: Optional[Dict[str, Any]] = None) -> str:
    """``compared``: every number the check held against a limit, under a
    short name, ``{"value", "limit"}``; the line's last key."""
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    if busy_s is not None:
        device["busy_s"] = busy_s
        device["window_s"] = window_s
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if compared is not None:
        out["compared"] = compared
    return json.dumps(out)
