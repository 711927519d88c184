#!/usr/bin/env python3
"""The shares of the chip's roofline of the three kernels of DeepSeek Sparse
Attention over a latent cache (``sparse_index_scores`` and ``sparse_topk`` at
the indexer's widths, ``paged_attention_latent`` under the selection) in a
traced run of a serving cell, for its decode-only steps and its steps with a
prefill chunk apart.

    python3 benchmark/dsa_roofline.py --workload <cell> --seed <n> --seconds <s>

By hand, on the chip, as ``mla_roofline.py`` and ``sparse_roofline.py`` are
(whose join of the device trace with the program's ring by step number,
``moe_roofline``'s, this reuses): the time of each kernel's custom calls
inside every ``ds/serve.step``, against the step's gains of the ``sparse.*``
and ``mla.*`` counters turned into needed operations and bytes by
``dsa_cost`` a step at a time (attention over the SELECTED keys only). Prints
``spans.py``'s line first, then one JSON object: ``<kernel>_roofline.decode``
/ ``.chunk`` in %, each with its bound, the steps and the milliseconds a
step. A program without the counters (no latent model with an indexer; the
parent of the PR that brought them) prints an object with no metric and
exits 0. Joining ``run.py`` is ROADMAP B2's.
"""

import time

T0 = time.perf_counter()

import argparse                     # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from typing import Any, Dict, List  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import dsa_cost, harness, moe_cost, moe_roofline  # noqa: E402
from benchmark import spans                                     # noqa: E402


def roofline_by_kind(pt: spans.ProgramTrace, ring, dims: Dict[str, Any],
                     peaks: Dict[str, Any], block_size: int
                     ) -> Dict[str, Dict[str, Any]]:
    """``{"<kernel>_roofline.<decode|chunk>": ...}`` over the traced steps."""
    out = {}
    for kernel, needed in dsa_cost.KERNELS.items():
        kernel_s = moe_roofline.kernel_seconds_by_step(pt, kernel)
        sums: Dict[str, List[Any]] = {}
        for s in spans.steps_of(ring, "serve"):
            d = s["entry"][4].get("d", {})
            if not d.get("mla.selected_keys_sum") \
                    or not kernel_s.get(s["n"]):
                continue
            kind = "chunk" if any(e[0] == "serve.prefill"
                                  for e in s["inside"]) else "decode"
            acc = sums.setdefault(kind, [dict.fromkeys(dsa_cost.COUNTERS, 0),
                                         0.0, 0.0, 0.0, 0])
            step = {name: d.get(name, 0) for name in dsa_cost.COUNTERS}
            for name, v in step.items():
                acc[0][name] += v
            flops, moved = needed(step, dims, block_size)
            acc[1] += flops
            acc[2] += moved
            acc[3] += kernel_s[s["n"]]
            acc[4] += 1
        for kind, (c, flops, moved, secs, n) in sums.items():
            r = moe_cost.roofline(flops, moved, secs, peaks)
            out[f"{kernel}_roofline.{kind}"] = {
                "value": r["pct"], "unit": "%", "bound": r["bound"],
                "steps": n, "kernel_ms_per_step": r["measured_ms"] / n,
                "least_ms_per_step": r["least_ms"] / n,
                "counters_per_step": {k: v / n for k, v in c.items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.kind != "serve":
        raise SystemExit("dsa_roofline reads serving cells")
    out = harness.load_driver(cell.kind).run(
        cell, seed=args.seed, seconds=args.seconds, trace=True, t0=T0,
        trace_dir=harness.TRACE_DIR)
    obs = spans.program_obs(cell, out, harness.TRACE_DIR)
    print(spans.finish(cell, out, obs), flush=True)
    ctx = obs["context"]
    metrics = {}
    if {"index_heads", "kv_lora_rank"} <= set(ctx["dims"]) \
            and ctx["peaks"] is not None:
        metrics = roofline_by_kind(
            obs["program"]["trace"], obs["program"]["ring"], ctx["dims"],
            ctx["peaks"], int(cell.system["serving"]["block_size"]))
    print(json.dumps({"workload": cell.name,
                      "metrics": dict(sorted(metrics.items())),
                      "device": out["devices"][0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
