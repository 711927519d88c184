#!/usr/bin/env python3
"""The grouped expert matmuls' share of the chip's roofline in a traced run
of a serving cell, for its decode-only steps and its steps with a prefill
chunk apart.

    python3 benchmark/moe_roofline.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's driver traced, as ``spans.py`` does (and prints ``spans.py``'s
line first: one chip run gives both), then joins two records of the traced
steps by their step number:

* the device trace: the time of the ``gmm`` custom calls (the megablox kernel
  of ``deepspeed_tpu/moe/dropless.py``) that ran inside each ``ds/serve.step``;
* the program's ring: the step's gains of ``moe.assignments`` (real rows
  routed, summed over layers), ``moe.layer_steps`` and
  ``moe.experts_idle_sum`` (so ``experts x layer_steps - idle`` experts had a
  row), and whether the step ran a prefill chunk.

``moe_cost.needed`` turns the counters into needed operations and bytes,
``moe_cost.roofline`` into a share of ``peaks.json``. The last line is one
JSON object: ``moe_experts_roofline_pct.decode`` / ``.chunk``, each with the
bound, the steps and the milliseconds a step. A model with no mixture, or a
program without these counters (the parent of the PR that brought them),
prints an object with no metric and exits 0. It joins ``run.py`` with ROADMAP
B1, like ``spans.py``'s metrics.
"""

import time

T0 = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Dict, List  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, moe_cost, spans  # noqa: E402

#: name stem of the kernel's device operations, as the trace prints them
KERNEL = "gmm"


def kernel_seconds_by_step(pt: spans.ProgramTrace, kernel: str = KERNEL
                           ) -> Dict[int, float]:
    """Seconds of the first device's ``kernel`` operations that started
    inside each traced ``serve.step`` span, by the step's number."""
    step_name, attr = spans.STEP["serve"]
    steps = sorted((s[1], s[1] + s[2], int(s[3][attr]))
                   for s in spans.window_spans(pt) if s[0] == step_name)
    out = {n: 0.0 for _, _, n in steps}
    if not steps or not pt.trace.devices:
        return out
    ops = sorted((o[2], o[3]) for o in
                 pt.trace.devices[sorted(pt.trace.devices)[0]]
                 if kernel in o[0])
    i = 0
    for start, dur in ops:
        while i < len(steps) and steps[i][1] < start:
            i += 1
        if i < len(steps) and steps[i][0] <= start:
            out[steps[i][2]] += dur / 1e9
    return out


def roofline_by_kind(pt: spans.ProgramTrace, ring, dims: Dict[str, Any],
                     peaks: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """``{"decode": ..., "chunk": ...}`` over the traced steps (a kind with
    no step, or a program with no ``moe.*`` counter, is left out)."""
    kernel_s = kernel_seconds_by_step(pt)
    sums: Dict[str, List[float]] = {}
    for s in spans.steps_of(ring, "serve"):
        d = s["entry"][4].get("d", {})
        rows = d.get("moe.assignments", 0)
        if s["n"] not in kernel_s or not rows or not kernel_s[s["n"]]:
            continue
        active = dims["experts"] * d["moe.layer_steps"] \
            - d.get("moe.experts_idle_sum", 0)
        flops, moved = moe_cost.needed(
            rows, active, hidden=dims["hidden"], width=dims["mlp_dim"],
            matrices=dims["mlp_matrices"])
        kind = "chunk" if any(e[0] == "serve.prefill" for e in s["inside"]) \
            else "decode"
        acc = sums.setdefault(kind, [0.0, 0.0, 0.0, 0])
        acc[0] += flops
        acc[1] += moved
        acc[2] += kernel_s[s["n"]]
        acc[3] += 1
    out = {}
    for kind, (flops, moved, secs, n) in sums.items():
        r = moe_cost.roofline(flops, moved, secs, peaks)
        out[kind] = {"value": r["pct"], "unit": "%", "bound": r["bound"],
                     "steps": n, "kernel_ms_per_step": r["measured_ms"] / n,
                     "least_ms_per_step": r["least_ms"] / n}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.kind != "serve":
        raise SystemExit("moe_roofline reads serving cells")
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=True, t0=T0,
        trace_dir=harness.TRACE_DIR)
    obs = spans.program_obs(cell, out, harness.TRACE_DIR)
    print(spans.finish(cell, out, obs), flush=True)
    ctx = obs["context"]
    metrics = {}
    if "experts" in ctx["dims"] and ctx["peaks"] is not None:
        by_kind = roofline_by_kind(obs["program"]["trace"],
                                   obs["program"]["ring"], ctx["dims"],
                                   ctx["peaks"])
        metrics = {f"moe_experts_roofline_pct.{k}": v
                   for k, v in sorted(by_kind.items())}
    print(json.dumps({"workload": cell.name, "metrics": metrics,
                      "device": out["devices"][0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
