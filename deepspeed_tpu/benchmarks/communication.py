"""Collective benchmarks — latency / algorithm BW / bus BW sweeps.

Capability parity with the reference's ``benchmarks/communication/*`` +
``bin/ds_bench`` (all_reduce/all_gather/all_to_all/broadcast/pt2pt sweeps
with algbw/busbw accounting). TPU edition: collectives run inside shard_map
over the full device mesh; busbw factors follow the standard ring-algorithm
accounting the reference uses (all_reduce busbw = 2(n-1)/n * algbw, etc.).

Round 10 additions (the comm-plan subsystem's measurement source):

* ``--algos`` sweeps WIRE FORMATS per op — ``exact`` plus the quantized
  implementations (``int8`` for all_reduce / reduce_scatter / all_to_all
  via ``runtime/comm``, ``onebit`` for all_reduce) — so the selector has
  real measurements to choose from;
* every row is ALSO printed as a machine-readable ``comm_bench: {json}``
  line (the format ``comm_plan.selector.parse_bench_lines`` ingests);
* ``--record PATH`` writes the sweep as JSON, and each run compares its
  rows against the newest recorded sweep next to it with the same >2x
  loud-regression convention as the dryrun timing gate
  (``DSTPU_COMM_BENCH_GATE=1`` makes a regression fatal).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


#: wire formats each op can sweep (exact always; quantized/overlap where
#: an implementation exists in runtime/comm). The overlap family times a
#: PIPELINE (chunked collective interleaved with a matmul payload) and
#: records its EXPOSED comm time as latency_us — wall minus compute —
#: so the selector compares it against exact's pure-wire latency on
#: equal terms; the raw wall/compute/comm split and the overlap_ratio
#: (wall / sum-of-parts; < 1 means the schedule actually hid wire time)
#: ride the row for the humans.
OP_ALGOS = {
    "all_reduce": ("exact", "int8", "onebit"),
    "all_gather": ("exact", "overlap", "overlap_int8"),
    "reduce_scatter": ("exact", "int8", "overlap", "overlap_int8"),
    "all_to_all": ("exact", "int8"),
    "pt2pt": ("exact",),
}

OVERLAP_ALGOS = ("overlap", "overlap_int8")

#: chunk count the benchmark's overlap cells use (the engine's is
#: comm_plan.overlap_chunks; rows record theirs in the "chunks" field)
OVERLAP_CHUNKS = 4

#: a row slower than this factor vs the newest recorded sweep is loud
SWEEP_REGRESSION_FACTOR = 2.0


def _mesh_all():
    devs = jax.devices()
    return Mesh(np.asarray(devs), ("all",))


def build_mesh(spec: str):
    """``'data=2,model=4'`` -> a named mesh over the first prod(sizes)
    devices (the per-axis sweep's substrate: one row per mesh axis, so
    hierarchical ICI/DCN selection has real per-axis measurements);
    ``''`` -> the flat ``('all',)`` mesh."""
    if not spec:
        return _mesh_all()
    names, sizes = [], []
    for part in spec.split(","):
        name, _, size = part.strip().partition("=")
        if not name or not size:
            raise ValueError(f"--mesh entry {part!r}: expected name=size")
        names.append(name)
        sizes.append(int(size))
    total = int(np.prod(sizes))
    devs = jax.devices()
    if total > len(devs):
        raise ValueError(f"--mesh {spec!r} needs {total} devices; "
                         f"host has {len(devs)}")
    return Mesh(np.asarray(devs[:total]).reshape(sizes), tuple(names))


def sweep_axes(mesh) -> List[str]:
    """The axes a sweep records rows for: every mesh axis of size > 1
    (a single-member axis has no wire to measure)."""
    return [a for a in mesh.axis_names if mesh.shape[a] > 1] or \
        [mesh.axis_names[0]]


def _timed(fn, arg, iters: int, warmups: int = 2) -> float:
    for _ in range(warmups):
        out = fn(arg)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(arg)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _collective_fn(op: str, mesh, axis: str = "all") -> Callable:
    n = mesh.shape[axis]
    manual = {axis}

    if op == "all_reduce":
        return jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, axis),
            mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            axis_names=manual, check_vma=False))
    if op == "all_gather":
        return jax.jit(jax.shard_map(
            lambda x: jax.lax.all_gather(x, axis, tiled=True),
            mesh=mesh, in_specs=P(axis), out_specs=P(),
            axis_names=manual, check_vma=False))
    if op == "reduce_scatter":
        return jax.jit(jax.shard_map(
            lambda x: jax.lax.psum_scatter(x, axis, tiled=True),
            mesh=mesh, in_specs=P(), out_specs=P(axis),
            axis_names=manual, check_vma=False))
    if op == "all_to_all":
        return jax.jit(jax.shard_map(
            lambda x: jax.lax.all_to_all(
                x.reshape(n, -1), axis, split_axis=0, concat_axis=0,
                tiled=True).reshape(-1),
            mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            axis_names=manual, check_vma=False))
    if op == "pt2pt":
        perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.jit(jax.shard_map(
            lambda x: jax.lax.ppermute(x, axis, perm),
            mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            axis_names=manual, check_vma=False))
    raise ValueError(f"unknown op {op}")


def _quantized_setup(op: str, algo: str, mesh, numel: int, dtype,
                     axis: str = "all") -> Tuple[Callable, jnp.ndarray]:
    """(fn, input) for a quantized wire format. ``numel`` is the same
    total element count the exact cell ran; each op maps it onto the
    stacked per-rank layout its runtime/comm collective consumes so the
    PER-RANK payload matches the exact variant's (allreduce family:
    per-rank value numel/n like the exact shard; reduce_scatter: each
    rank contributes a FULL numel buffer like the exact replicated
    input; all_to_all: numel/n sent per rank like the exact local
    (n, numel/n^2) chunking) — latency rows stay apples-to-apples."""
    from ..runtime.comm.compressed import (chunk_elems, compressed_allreduce,
                                           quantized_allreduce)
    from ..runtime.comm.quantized import (quantized_all_to_all,
                                          quantized_reduce_scatter)
    n = mesh.shape[axis]
    sh = NamedSharding(mesh, P(axis))
    per_rank = numel // n
    # one OUTER jit per cell so the timing loop hits the compile cache
    # (the runtime/comm collectives build their shard_map per trace —
    # correct under a caller's jit, a retrace per call when timed bare)
    if op == "all_reduce" and algo == "int8":
        x = jax.device_put(jnp.ones((n, per_rank), dtype), sh)
        err = jax.device_put(jnp.zeros((n, per_rank), jnp.float32), sh)
        return (jax.jit(lambda v: quantized_allreduce(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
            v, err, mesh=mesh, axis=axis)[0]), x)
    if op == "all_reduce" and algo == "onebit":
        x = jax.device_put(jnp.ones((n, per_rank), dtype), sh)
        werr = jax.device_put(jnp.zeros((n, per_rank), jnp.float32), sh)
        serr = jax.device_put(
            jnp.zeros((n, chunk_elems(per_rank, n)), jnp.float32), sh)
        return (jax.jit(lambda v: compressed_allreduce(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
            v, werr, serr, mesh=mesh, axis=axis)[0]), x)
    if op == "reduce_scatter" and algo == "int8":
        # each rank contributes a FULL buffer, like the exact replicated input
        x = jax.device_put(jnp.ones((n, numel), dtype), sh)
        return (jax.jit(lambda v: quantized_reduce_scatter(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
            v, mesh=mesh, axis=axis)), x)
    if op == "all_to_all" and algo == "int8":
        rows = n * n
        # logical [n*n, numel/n^2]: numel/n sent per rank, matching the
        # exact cell's local (n, numel/n^2) chunking
        x = jax.device_put(jnp.ones((rows, max(numel // rows, 1)), dtype),
                           sh)
        return (jax.jit(lambda v: quantized_all_to_all(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
            v, mesh=mesh, axis=axis)), x)
    raise ValueError(f"no {algo!r} implementation for op {op!r}")


def _overlap_setup(op: str, algo: str, mesh, numel: int, dtype,
                   axis: str = "all", chunks: int = OVERLAP_CHUNKS):
    """(wall_fn, wall_arg, comm_fn, comm_arg, compute_fn, compute_arg)
    for an overlap cell: the fused chunked pipeline, its comm-only half
    (same chunked collectives, compute precomputed) and its compute-only
    half (same matmul payload, wire precomputed). ``latency_us`` is the
    EXPOSED comm (wall - compute); per-rank wire payload matches the
    exact cell (all_gather: the shard each rank contributes;
    reduce_scatter: a full per-rank buffer)."""
    from ..runtime.comm.overlap import (chunked_ag_matmul, chunked_matmul_rs,
                                        chunked_rs, make_overlap_gather)
    n = mesh.shape[axis]
    sh = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    B = 64                                   # matmul payload's free dim
    if op == "all_gather":
        # w [R, C] sharded on dim 0 (each rank contributes numel/n, like
        # the exact cell's shard), consumed chunk-by-chunk by x @ w
        C = max(min(512, numel // (n * chunks)), 1)
        R = max(numel // C // (n * chunks), 1) * n * chunks
        w = jax.device_put(jnp.ones((R, C), dtype),
                           NamedSharding(mesh, P(axis)))
        x = jax.device_put(jnp.ones((B, R), dtype), rep)
        wfull = jax.device_put(jnp.ones((R, C), dtype), rep)
        gather = make_overlap_gather(mesh, axis, 0, chunks=chunks,
                                     algo=algo)
        return (jax.jit(lambda v: chunked_ag_matmul(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                    x, v, mesh=mesh, axis=axis, chunks=chunks, algo=algo)),
                w,
                jax.jit(gather), w,  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                jax.jit(lambda wf: x.astype(jnp.float32)  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                        @ wf.astype(jnp.float32)), wfull)
    if op == "reduce_scatter":
        # each rank PRODUCES a full numel buffer chunk-by-chunk (u @ v
        # segments) and reduce-scatters each chunk as it appears
        u = jax.device_put(jnp.ones((n, B), dtype), sh)
        v = jax.device_put(jnp.ones((B, numel), dtype), rep)
        g = jax.device_put(jnp.ones((n, numel), dtype), sh)
        return (jax.jit(lambda vv: chunked_matmul_rs(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                    u, vv, mesh=mesh, axis=axis, chunks=chunks, algo=algo)),
                v,
                jax.jit(lambda gg: chunked_rs(  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                    gg, mesh=mesh, axis=axis, chunks=chunks, algo=algo)),
                g,
                jax.jit(lambda vv: u[:1].astype(jnp.float32)  # graftlint: disable=TPU002 (one jit per sweep cell, reused across timed iters)
                        @ vv.astype(jnp.float32)), v)
    raise ValueError(f"no {algo!r} implementation for op {op!r}")


def busbw_factor(op: str, n: int) -> float:
    """Ring-algorithm bus bandwidth factors (reference: communication/utils.py)."""
    if n <= 1:
        return 1.0
    return {
        "all_reduce": 2.0 * (n - 1) / n,
        "all_gather": (n - 1) / n,
        "reduce_scatter": (n - 1) / n,
        "all_to_all": (n - 1) / n,
        "pt2pt": 1.0,
    }[op]


def run_op_sweep(op: str, sizes_mb: List[float], dtype=jnp.bfloat16,
                 iters: int = 10, algo: str = "exact",
                 emit: bool = False, mesh=None,
                 axis: Optional[str] = None) -> List[Dict]:
    mesh = _mesh_all() if mesh is None else mesh
    axis = axis or mesh.axis_names[0]
    n = mesh.shape[axis]
    itemsize = jnp.dtype(dtype).itemsize
    rows = []
    # reduce_scatter consumes a per-rank FULL buffer (in_specs=P()), so place
    # the input replicated; sharding it over the swept axis would fold an
    # implicit all-gather into the timed region and corrupt the measurement
    in_spec = P() if op == "reduce_scatter" else P(axis)
    fn = _collective_fn(op, mesh, axis) if algo == "exact" else None
    for mb in sizes_mb:
        base = max(int(mb * 2 ** 20 / itemsize) // n * n, n)
        numel = -(-base // (n * n)) * n * n      # divisible for every layout
        size_bytes = numel * itemsize
        row = {"op": op, "algo": algo, "axis": axis, "n": n,
               "size_mb": round(size_bytes / 2 ** 20, 3),
               "size_bytes": size_bytes}
        if algo in OVERLAP_ALGOS:
            (wall_fn, wall_x, comm_fn, comm_x,
             compute_fn, compute_x) = _overlap_setup(op, algo, mesh, numel,
                                                     dtype, axis)
            wall = _timed(wall_fn, wall_x, iters)
            comm = _timed(comm_fn, comm_x, iters)
            compute = _timed(compute_fn, compute_x, iters)
            dt = max(wall - compute, 1e-7)       # exposed comm time
            row.update({
                "latency_us": round(dt * 1e6, 1),
                "wall_us": round(wall * 1e6, 1),
                "comm_us": round(comm * 1e6, 1),
                "compute_us": round(compute * 1e6, 1),
                "overlap_ratio": round(wall / max(comm + compute, 1e-12),
                                       3),
                "chunks": OVERLAP_CHUNKS,
            })
        else:
            if algo == "exact":
                x = jax.device_put(jnp.ones((numel,), dtype),
                                   NamedSharding(mesh, in_spec))
                timed_fn = fn
            else:
                timed_fn, x = _quantized_setup(op, algo, mesh, numel,
                                               dtype, axis)
            dt = _timed(timed_fn, x, iters)
            row["latency_us"] = round(dt * 1e6, 1)
        algbw = size_bytes / dt / 1e9
        row["algbw_gbps"] = round(algbw, 3)
        row["busbw_gbps"] = round(algbw * busbw_factor(op, n), 3)
        rows.append(row)
        if emit:
            print("comm_bench: " + json.dumps(row))
    return rows


def print_table(rows: List[Dict]):
    if not rows:
        return
    cols = []                       # union of keys, first-seen order
    for r in rows:                  # (overlap rows carry extra columns)
        for c in r:
            if c not in cols:
                cols.append(c)
    widths = [max(len(c), max(len(str(r.get(c, ""))) for r in rows))
              for c in cols]
    line = "  ".join(c.ljust(w) for c, w in zip(cols, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(w)
                        for c, w in zip(cols, widths)))


# ---------------------------------------------------------------------------
# recorded sweeps + regression compare (dryrun timing-gate convention)
# ---------------------------------------------------------------------------

def record_sweep(rows: List[Dict], path: str) -> str:
    doc = {"n": rows[0]["n"] if rows else len(jax.devices()), "rows": rows}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, path)
    return path


def latest_comm_sweep(baseline_dir: str, n_devices: Optional[int] = None
                      ) -> Tuple[Optional[str], List[Dict]]:
    """(name, rows) of the newest recorded sweep in ``baseline_dir``
    (``COMMBENCH_r*.json`` reports or ``comm_sweep*.json`` recordings);
    sweeps from a different device count are skipped — their latencies
    aren't comparable."""
    from .sweeps import latest_recorded_sweep
    return latest_recorded_sweep(
        baseline_dir, ("COMMBENCH_r*.json", "comm_sweep*.json"), n_devices)


def check_sweep_regression(current: List[Dict], baseline: List[Dict],
                           factor: float = SWEEP_REGRESSION_FACTOR
                           ) -> List[str]:
    """Rows > ``factor`` x their recorded latency, keyed by
    (op, algo, axis, size_mb). Missing rows are NOT flagged (a narrower
    re-run is legitimate; the dryrun gate owns leg-coverage)."""
    def key(r):
        return (r.get("op"), r.get("algo", "exact"), r.get("axis", "all"),
                r.get("size_mb"))

    base = {key(r): float(r["latency_us"]) for r in baseline
            if "latency_us" in r}
    problems = []
    for r in current:
        b = base.get(key(r))
        if b is None or b <= 0 or "latency_us" not in r:
            continue
        now = float(r["latency_us"])
        if now > factor * b:
            problems.append(
                f"{r['op']}/{r.get('algo', 'exact')}@{r.get('size_mb')}MB: "
                f"{now:.1f}us vs recorded {b:.1f}us "
                f"({now / b:.1f}x > {factor:g}x budget)")
    return problems


def main(argv=None):
    p = argparse.ArgumentParser(prog="ds_bench",
                                description="collective benchmark sweeps")
    p.add_argument("--ops", default="all_reduce,all_gather,reduce_scatter,"
                                    "all_to_all,pt2pt")
    p.add_argument("--algos", default="exact",
                   help="comma list of wire formats per op "
                        "(exact,int8,onebit); unsupported (op, algo) "
                        "pairs are skipped")
    p.add_argument("--sizes-mb", default="1,16,64")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--mesh", default="",
                   help="named mesh spec 'data=2,model=4': one sweep row "
                        "per >1-member axis (per-axis plans for "
                        "hierarchical meshes); empty = the flat 'all' "
                        "mesh")
    p.add_argument("--record", default="",
                   help="write the sweep rows to this JSON path (the "
                        "comm-plan selector's input)")
    p.add_argument("--baseline-dir", default=".",
                   help="directory searched for the newest recorded "
                        "sweep to compare against (>2x = loud "
                        "regression; DSTPU_COMM_BENCH_GATE=1 makes it "
                        "fatal)")
    args = p.parse_args(argv)
    from ..utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
             "float16": jnp.float16}[args.dtype]
    sizes = [float(s) for s in args.sizes_mb.split(",")]
    algos = [a.strip() for a in args.algos.split(",") if a.strip()]
    mesh = build_mesh(args.mesh)
    all_rows = []
    for op in args.ops.split(","):
        op = op.strip()
        for algo in algos:
            if algo not in OP_ALGOS.get(op, ()):
                continue
            for axis in sweep_axes(mesh):
                all_rows += run_op_sweep(op, sizes, dtype, args.iters,
                                         algo=algo, emit=True,
                                         mesh=mesh, axis=axis)
    print_table(all_rows)
    base_name, baseline = latest_comm_sweep(args.baseline_dir,
                                            len(jax.devices()))
    if baseline:
        problems = check_sweep_regression(all_rows, baseline)
        for prob in problems:
            print(f"comm_bench REGRESSION vs {base_name}: {prob}")
        if not problems:
            print(f"comm_bench within {SWEEP_REGRESSION_FACTOR:g}x of "
                  f"{base_name}")
        elif os.environ.get("DSTPU_COMM_BENCH_GATE") == "1":
            raise SystemExit("comm_bench regression:\n" +
                             "\n".join(problems))
    if args.record:
        print(f"comm_bench recorded: {record_sweep(all_rows, args.record)}")


if __name__ == "__main__":
    main()
