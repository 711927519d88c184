#!/usr/bin/env python3
"""Where a benchmark cell's ``setup_s`` went, from the program's own counters.

    python scripts/setup_report.py --workload <cell> --seed <n> --seconds <s>

Runs what ``benchmark/program.py`` runs (the cell's driver, traced, the
reader attached, the same result line printed with the metrics of
``benchmark/program_metrics/``), then prints one table from the recorder the
run left (``deepspeed_tpu.utils.telemetry``) and the driver's own ``[bench]``
phase lines:

* before the engine's constructor: the driver's ``devices taken``, imports,
  weights (serving) or model (training), and what is left up to the instant
  the recorder was made;
* from there to the end of the last step before the window, the nine parts of
  the recorder's identity: init (the constructor's span, with what compiled
  in it said beside the sum), and inside the steps tracing, lowering, backend
  compile, cache load, rest of first calls, waiting for the device and the
  host's own work; outside steps;
* what the driver spent behind that step before it stamped ``setup_s``;

with their sum against ``setup_s``, the persistent cache's counts, the
programs compiled at every start (a backend compile whose entry JAX never
writes) and the five most expensive rows of ``programs``. ``time.
perf_counter`` and ``time.monotonic`` are one clock on Linux, so the driver's
seconds and the recorder's stamps are set side by side as they are.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, program, spans  # noqa: E402

PHASES = ("compile.trace_us", "compile.lower_us", "compile.backend_us",
          "compile.cache_load_us", "compile.first_call_rest_us")
COUNTS = ("compiles", "compile.cache_requests", "compile.cache_hits",
          "compile.cache_misses", "compile.backend_compiles",
          "compile.first_calls", "compile.programs_dropped")
_PHASE_LINE = re.compile(r"^\[bench\]\s+([0-9.]+)s ([^:]+):")


class Tee(io.StringIO):
    """Keeps what is printed and prints it."""

    def write(self, s):
        sys.__stdout__.write(s)
        return super().write(s)

    def flush(self):
        sys.__stdout__.flush()


def cost(row) -> int:
    return sum(row[k] for k in ("trace_us", "lower_us", "backend_us",
                                "cache_load_us", "rest_us"))


def report(prog, printed: str, setup_s: float) -> list:
    """The table's lines; ``prog`` is ``obs["program"]``."""
    kind, snap = prog["kind"], prog["snapshot"]
    at = program.setup_counters(prog)
    if at is None or f"{kind}.step_us" not in at:
        return ["setup_report: the ring does not hold the window's first "
                "step, or the recorder has no step counters: no table"]
    said = [(float(m.group(1)), m.group(2)) for m in map(
        _PHASE_LINE.match, printed.splitlines()) if m]
    steps = spans.steps_of(prog["ring"], kind)
    first = min(int(s[3][spans.STEP[kind][1]])
                for s in spans.window_spans(prog["trace"])
                if s[0] == spans.STEP[kind][0])
    before = [s for s in steps if s["n"] < first]
    t0 = snap["t0_ns"] / 1e9 - program.T0           # the constructor begins
    end = before[-1]["entry"][3] / 1e9 - program.T0  # the last set-up step ends
    in_steps = {k: sum(s["entry"][4]["d"].get(k, 0) for s in before)
                for k in PHASES + (f"{kind}.wait_us",)}
    # what the constructor's span booked itself is part of init, below
    in_init = {k: at.get(k, 0) - in_steps[k] for k in PHASES}
    wait = in_steps[f"{kind}.wait_us"]
    step_us, init_us = at[f"{kind}.step_us"], at[f"{kind}.init_us"]
    host = step_us - wait - sum(in_steps[k] for k in PHASES)
    outside = (end - t0) * 1e6 - init_us - step_us
    rows, last = [], 0.0
    for secs, label in said:
        if secs > t0:
            break
        rows.append((f"driver: {label}", secs - last))
        last = secs
    rows.append(("driver: the rest before the constructor", t0 - last))
    rows.append(("init (the constructor's span)", init_us / 1e6))
    rows += [(f"{label}, in steps", in_steps[k] / 1e6) for label, k in zip(
        ("tracing", "lowering", "backend compile", "cache load",
         "rest of first calls"), PHASES)]
    rows += [("waiting for the device, in steps", wait / 1e6),
             ("host inside steps", host / 1e6),
             ("outside steps (the driver)", outside / 1e6),
             ("driver: behind the last step", setup_s - end)]
    out = [f"setup_report: {label:<42s} {secs:9.3f} s" for label, secs in rows]
    total = sum(secs for _, secs in rows)
    out.append(f"setup_report: {'sum':<42s} {total:9.3f} s of setup_s "
               f"{setup_s:.3f} ({100 * (total / setup_s - 1):+.2f}%); "
               f"{len(before)} steps before the window; of init: " + ", ".join(
                   f"{k[len('compile.'):-3]} {v / 1e6:.3f}"
                   for k, v in in_init.items()))
    c = snap["counters"]
    out.append("setup_report: " + ", ".join(
        f"{k} {c.get(k, 0)}" for k in COUNTS)
        + f"; saved {at.get('compile.saved_us', 0) / 1e6:.1f} s + backend "
        f"{at.get('compile.backend_us', 0) / 1e6:.1f} s = this start's "
        "estimate of a cold start's backend compiles")
    never = sorted(r["fun_name"] + (f"[{r['shape']}]" if r["shape"] is not None
                                    else "")
                   for r in snap["programs"] if r["compiles"] > r["hit"])
    out.append(f"setup_report: compiled by the backend this start "
               f"({len(never)} rows): {', '.join(never) or 'none'}")
    # the loop's own reading of its device share against the trace's: both
    # are sums over the same spans, one over the window, one over the traced
    # seconds before it
    traced = [s[2] for s in spans.window_spans(prog["trace"])
              if s[0] == spans.STEP[kind][0]]
    host_ns = sum(v for k, v in spans.span_self_ns(prog["trace"]).items()
                  if k.startswith(kind + ".") and k != "serve.submit"
                  and not k.endswith((".fetch", ".sync")))
    window = [s["entry"][4]["d"] for s in steps
              if s["n"] >= first + len(traced)]
    if traced and window:
        step_us = sum(d.get(f"{kind}.step_us", 0) for d in window)
        wait_us = sum(d.get(f"{kind}.wait_us", 0) for d in window)
        out.append(
            f"setup_report: wait share: {100 * wait_us / step_us:.2f}% of "
            f"{len(window)} steps of {step_us / len(window) / 1e3:.3f} ms "
            f"behind the traced ones; by the trace 100 x (1 - host "
            f"{host_ns / len(traced) / 1e6:.4f} ms / step "
            f"{sum(traced) / len(traced) / 1e6:.3f} ms) = "
            f"{100 * (1 - host_ns / sum(traced)):.2f}% over {len(traced)} "
            "traced steps")
    by_cost = sorted(snap["programs"], key=cost, reverse=True)
    out.append("setup_report: programs " + json.dumps(by_cost))
    for r in by_cost[:5]:
        out.append(
            f"setup_report: {r['fun_name']}[{r['shape']}] in {r['span']} "
            f"(step {r['step']}): {cost(r) / 1e6:.3f} s = trace "
            f"{r['trace_us'] / 1e6:.3f} + lower {r['lower_us'] / 1e6:.3f} + "
            f"backend {r['backend_us'] / 1e6:.3f} + load "
            f"{r['cache_load_us'] / 1e6:.3f} + rest {r['rest_us'] / 1e6:.3f}"
            f"; {r['hit']} of {r['compiles']} from the cache, saved "
            f"{r['saved_us'] / 1e6:.0f} s")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    tee = Tee()
    with contextlib.redirect_stdout(tee):
        out = harness.load_driver(cell.kind).run(
            cell, seed=args.seed, seconds=args.seconds, trace=True,
            t0=program.T0, trace_dir=harness.TRACE_DIR)
    program.attach(cell, out, harness.TRACE_DIR)
    line = program.finish(cell, out)
    print(line, flush=True)
    prog = out["obs"].get("program")
    if prog is None or prog.get("trace") is None:
        print("setup_report: the reader read nothing: no table", flush=True)
        return 0
    # (a traced line holds the per-layer metrics only)
    setup_s = out["end_to_end"]["setup_s"]["value"]
    print("\n".join(report(prog, tee.getvalue(), setup_s)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
