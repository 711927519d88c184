#!/usr/bin/env python3
"""The paged kernel's share of the chip's memory roofline in a traced run of
a serving cell, from what the program counted.

    chiprun -- python3 scripts/paged_roofline.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's driver traced, as ``benchmark/spans.py`` does (and prints
``spans.py``'s line first: one chip run gives both), then joins two records of
the traced steps by their step number, as ``benchmark/moe_roofline.py`` does
for the experts:

* the device trace: the time of the ``paged_attention`` custom calls that
  ran inside each ``ds/serve.step``;
* the program's ring: the step's gains of ``paged.live_pages_sum`` (the pages
  the kernel walks) and ``paged.table_pages_sum`` (what the tables could
  hold), counted in ``serving/engine.py:_decode_lanes``.

A live page is ``block_size x stored heads x head_dim`` elements of K and of
V, a layer; the kernel is bound by reading them (eight query rows a head: the
FLOPs are nothing). The last line is one JSON object: ``paged_kernel_
roofline_pct`` with the kernel's and the least milliseconds a step,
``paged_live_page_share`` over the traced steps and over the untraced window
after them. A program without the counters (the parent of the PR that
brought them) prints an object with no metric and exits 0. Lives outside
``benchmark/`` until a ``benchmark`` PR folds it in (ROADMAP B1).
"""

import time

T0 = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, moe_roofline, spans  # noqa: E402

KERNEL = "paged_attention"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def paged_metrics(cell, obs):
    """The metrics of this file from a traced run's observations
    (``spans.program_obs``); empty where the program has no such counters
    or the trace no such kernel."""
    dims, peaks = obs["context"]["dims"], obs["context"]["peaks"]
    serving = cell.system["serving"]
    # the pool stores every query head (GQA is expanded before the write)
    page = (serving["block_size"] * dims["heads"] * dims["head_dim"] * 2
            * ITEMSIZE[cell.system["dtype"]] * dims["layers"])
    kernel_s = moe_roofline.kernel_seconds_by_step(obs["program"]["trace"],
                                                   KERNEL)
    live = table = secs = n = 0
    for s in spans.steps_of(obs["program"]["ring"], "serve"):
        d = s["entry"][4].get("d", {})
        if kernel_s.get(s["n"]) and d.get("paged.live_pages_sum"):
            live += d["paged.live_pages_sum"]
            table += d["paged.table_pages_sum"]
            secs += kernel_s[s["n"]]
            n += 1
    if not n or peaks is None:
        return {}
    least = live * page / (peaks["hbm_gb_per_s"] * 1e9)
    metrics = {
        "paged_kernel_roofline_pct": {
            "value": 100.0 * least / secs, "unit": "%", "bound": "memory",
            "steps": n, "kernel_ms_per_step": 1e3 * secs / n,
            "least_ms_per_step": 1e3 * least / n,
            "needed_gb_per_step": live * page / n / 1e9},
        "paged_live_page_share": {
            "value": live / table, "unit": "share", "steps": n,
            "live_pages_per_step": live / n}}
    win_table = spans.window_counter(obs, "paged.table_pages_sum")
    if win_table:
        metrics["paged_live_page_share.window"] = {
            "value": spans.window_counter(obs, "paged.live_pages_sum")
            / win_table,
            "unit": "share", "steps": len(spans.window_steps(obs))}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.kind != "serve":
        raise SystemExit("paged_roofline reads serving cells")
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=True, t0=T0,
        trace_dir=harness.TRACE_DIR)
    obs = spans.program_obs(cell, out, harness.TRACE_DIR)
    print(spans.finish(cell, out, obs), flush=True)
    print(json.dumps({"workload": cell.name,
                      "metrics": paged_metrics(cell, obs),
                      "device": out["devices"][0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
