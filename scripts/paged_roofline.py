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
  hold), counted in ``serving/engine.py:_launch``.

A live page is ``block_size x stored heads x head_dim`` elements of K and of
V, a layer; the kernel is bound by reading them (eight query rows a stored
head: the FLOPs are nothing). The stored heads are the program's own gauge
``kv.stored_heads`` (PR 44: a grouped-query model's pool holds its KV heads;
a program from before the gauge stored every query head, the config's
``heads``), and the line says which it read. The last line is one JSON object: ``paged_kernel_
roofline_pct`` with the kernel's and the least milliseconds a step,
``paged_live_page_share`` over the traced steps and over the untraced window
after them.

Since PR 37 a prefill chunk's attention is the same kernel at T > 1 query
rows (its custom call makes ``[1, programs, query heads a program, rows of
whole 256-row tiles, head_dim]``, a decode call ``[lanes, programs, stored
heads, 8 or 16, head_dim]``: the trace tells them apart by the rows): ``paged_chunk_kernel_roofline_pct`` holds the chunk calls' time in
the steps that carry one against the bytes of the pages they walk
(``paged.chunk_live_pages_sum``, counted in ``engine.py:_prefill_inputs``),
beside the decode calls' share, and ``paged_chunk_live_page_share`` those
pages over the table's width, which the gather reference read until then.
The line before it lists the device's operations by the kind of step they
started in (:func:`ops_by_step_kind`). A program without the counters (the parent of the PR that
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
    # what the pool stores a token and layer, from the program itself
    stored = obs["program"]["snapshot"].get("gauges", {}).get(
        "kv.stored_heads", dims["heads"])
    page = (serving["block_size"] * stored * dims["head_dim"] * 2
            * ITEMSIZE[cell.system["dtype"]] * dims["layers"])
    pt = obs["program"]["trace"]
    # a chunk call's result has its query rows where a decode call's has
    # the eight a token is broadcast to
    chunk_calls = _chunk_calls(pt)
    all_s = moe_roofline.kernel_seconds_by_step(pt, KERNEL)
    chunk_s = moe_roofline.kernel_seconds_by_step(chunk_calls, KERNEL)
    # layers with a sliding window (a family's ``dims`` says how many) walk
    # the pages of their window only: the program counts those apart
    # (``paged.window_pages_sum``, summed over those layers), and the other
    # layers walk every live page
    windowed = dims.get("window_layers", 0)
    live = table = win = secs = n = 0
    c_live = c_table = c_secs = c_n = 0
    for s in spans.steps_of(obs["program"]["ring"], "serve"):
        d = s["entry"][4].get("d", {})
        decode_s = all_s.get(s["n"], 0.0) - chunk_s.get(s["n"], 0.0)
        if decode_s and d.get("paged.live_pages_sum"):
            live += d["paged.live_pages_sum"]
            table += d["paged.table_pages_sum"]
            win += d.get("paged.window_pages_sum", 0)
            secs += decode_s
            n += 1
        if chunk_s.get(s["n"]) and d.get("paged.chunk_live_pages_sum"):
            c_live += d["paged.chunk_live_pages_sum"]
            c_table += d["paged.chunk_table_pages_sum"]
            c_secs += chunk_s[s["n"]]
            c_n += 1
    if not n or peaks is None:
        return {}
    needed = live * page
    if windowed and win:
        needed = page // dims["layers"] * (
            live * (dims["layers"] - windowed) + win)
    least = needed / (peaks["hbm_gb_per_s"] * 1e9)
    chunk = {}
    if c_n:
        c_least = c_live * page / (peaks["hbm_gb_per_s"] * 1e9)
        chunk = {
            "paged_chunk_kernel_roofline_pct": {
                "value": 100.0 * c_least / c_secs, "unit": "%",
                "bound": "memory", "steps": c_n,
                "kernel_ms_per_chunk_step": 1e3 * c_secs / c_n,
                "least_ms_per_chunk_step": 1e3 * c_least / c_n,
                "needed_gb_per_chunk_step": c_live * page / c_n / 1e9},
            "paged_chunk_live_page_share": {
                "value": c_live / c_table, "unit": "share", "steps": c_n,
                "live_pages_per_call": c_live / c_n}}
    metrics = {
        **chunk,
        "paged_kernel_roofline_pct": {
            "value": 100.0 * least / secs, "unit": "%", "bound": "memory",
            "stored_heads": stored,
            "steps": n, "kernel_ms_per_step": 1e3 * secs / n,
            "least_ms_per_step": 1e3 * least / n,
            "needed_gb_per_step": needed / n / 1e9},
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


def _chunk_calls(pt):
    """``pt`` with only the ``paged_attention`` calls of a prefill chunk on
    its device lines: the calls whose result has whole 256-row tiles of
    query rows (``_CHUNK_TILE``), where a decode call's has the 8 or 16 of
    one token a query head."""
    import dataclasses

    def rows(result):
        dims = result[result.find("[") + 1:result.find("]")].split(",")
        return int(dims[-2]) if len(dims) >= 2 and dims[-2].isdigit() else 8

    devices = {name: [o for o in ops
                      if KERNEL in o[0] and rows(o[1]) % 256 == 0]
               for name, ops in pt.trace.devices.items()}
    return dataclasses.replace(pt, trace=dataclasses.replace(
        pt.trace, devices=devices, cache={}))


def ops_by_step_kind(obs, top: int = 40):
    """``{"decode" | "chunk": {"steps": n, "device_ms_per_step": ...,
    "ops": [[label, self ms a step, instances a step], ...]}}``: the first
    device's operations of the traced window by the ``serve.step`` they
    started in, a step that ran a ``serve.prefill`` being a chunk step. The
    label is the instruction's name less its number and the shape it
    makes, so a layer loop's instances add up."""
    import re
    from collections import defaultdict
    from benchmark import reduce
    pt = obs["program"]["trace"]
    step_name, attr = spans.STEP["serve"]
    steps = sorted((s[1], s[1] + s[2], int(s[3][attr]))
                   for s in spans.window_spans(pt) if s[0] == step_name)
    kinds = {s["n"]: ("chunk" if any(e[0] == "serve.prefill"
                                     for e in s["inside"]) else "decode")
             for s in spans.steps_of(obs["program"]["ring"], "serve")}
    if not steps or not pt.trace.devices:
        return {}
    ops = pt.trace.devices[sorted(pt.trace.devices)[0]]
    selfs = reduce.self_times(ops)
    rows = sorted((o[2], f"{re.sub(r'[.][0-9]+', '', o[0])} {o[1]}", s[2])
                  for o, s in zip(ops, selfs))
    total = {k: defaultdict(lambda: [0.0, 0]) for k in ("decode", "chunk")}
    seen = {k: set() for k in total}
    i = 0
    for start, label, ns in rows:
        while i < len(steps) and steps[i][1] < start:
            i += 1
        if i < len(steps) and steps[i][0] <= start \
                and steps[i][2] in kinds:
            kind = kinds[steps[i][2]]
            seen[kind].add(steps[i][2])
            acc = total[kind][label]
            acc[0] += ns
            acc[1] += 1
    out = {}
    for kind, table in total.items():
        n = len(seen[kind])
        if n:
            ranked = sorted(table.items(), key=lambda kv: -kv[1][0])
            out[kind] = {
                "steps": n,
                "device_ms_per_step": sum(v[0] for v in table.values())
                / n / 1e6,
                "ops": [[label, ns / n / 1e6, cnt / n]
                        for label, (ns, cnt) in ranked[:top]]}
    return out


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
                      "ops_by_step_kind": ops_by_step_kind(obs)}),
          flush=True)
    print(json.dumps({"workload": cell.name,
                      "metrics": paged_metrics(cell, obs),
                      "device": out["devices"][0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
