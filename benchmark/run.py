#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds ``workloads/<cell>.json``, its configuration and traffic mix, and the
driver of the cell's ``kind``; runs it in THIS process (a chip belongs to one
process; no child is started, nothing is left behind) and prints, as the last
line of stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, traced ``breakdown``, and last ``compared`` (every
number the check held against a limit, beside it). ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
(``layer_metrics/``). Without a TPU, or with another number of chips than
the cell asks for, it exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()            # set-up is counted from here

import argparse                     # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, reduce  # noqa: E402


def finish(cell, out, trace: bool) -> str:
    """The result line of one driver run."""
    checks = dict(out["checks"])
    obs = out["obs"]
    kw = {}
    if trace:
        metrics = reduce.layer_metrics(
            harness.load_layer_metrics(cell.kind, cell=cell.name), obs)
        busy = reduce.busy_and_window_s(obs["trace"])
        checks["the trace holds device operations"] = bool(busy and busy[0] > 0)
        for kernel in cell.expect_kernels:
            checks[f"the trace holds the {kernel} kernel"] = \
                reduce.scope_seconds(obs["trace"], [kernel]) is not None
        if busy:
            kw = {"busy_s": busy[0], "window_s": busy[1]}
        kw["breakdown"] = {"device_ops": reduce.top_ops(obs["trace"]),
                           "idle_gaps": reduce.idle_gaps(obs["trace"])}
    else:
        metrics = out["end_to_end"]
    for what, ok in checks.items():
        print(f"[bench] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    for what, c in out["compared"].items():     # stderr's last lines
        print(f"[bench] compared {what}: {c['value']!r} (limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    return harness.result_line(
        compared=out["compared"],
        correct=all(checks.values()), attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, devices=out["devices"],
        memory_peak=out["memory_peak"], **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0, trace_dir=harness.TRACE_DIR)
    print(finish(cell, out, bool(args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
