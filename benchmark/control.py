#!/usr/bin/env python3
"""The control of a serving cell's check: what ``correct`` has to refuse.

    python3 benchmark/control.py --workload <serving cell> --seed <n> --seeds 3

By hand, on the chip, at the cell's own size; ``benchmark/run.py`` never runs
it. For each seed the program serves the cell's checked requests (the
driver's weights, prompts and engine: ``drivers/serve.py::submit_checked``).
With the engine closed and its pool freed, the driver's own check
(``check_served`` and ``judge``: the numbers a run compares, against the
limits a run holds them to) reads them twice. Once as a run does: the
``honest`` reading, which has to pass. Once with the CONTROL in the program's
place: the plain reference with every weight matrix rounded to float8 (e4m3,
one scale an output channel), the nearest precision below the bfloat16 the
cells serve in and the step that would tempt a later PR. The control need not
decode: at each served position of the same prompts and tokens it puts one
token first, and that token is judged where the served one was
(``reference.served_token_gaps(..., emitted=)``): as many positions as one
run compares, no more. Exit 0 only where every seed's control comes out NOT
correct and every honest reading correct; the limits in ``reference.py``
stand between the two sets of numbers this prints (``PERF.md``, section 6).

A mixture routes by its own scores on both sides here: the float8 router's
picks are the control's own, as its tokens are. ``--dump DIR`` keeps every
position's two gaps (``<seed>.npz``: ``honest`` and ``control``, a row a
request), for sizing a limit or a cell's ``check``.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
from typing import Any, Dict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import harness, reference  # noqa: E402
from benchmark.drivers import serve  # noqa: E402


def float8_weights(params):
    """Every matrix of the tree as float8 (e4m3) weights would serve it: one
    scale an output channel (the largest magnitude over the input axis onto
    the format's largest, 448), rounded, and back to the type it is stored
    in. Leaf by leaf, the leaf donated, so no second copy of the weights is
    held; in TWO calls with the float8 array between them, because inside one
    program the compiler may keep the excess precision and round nothing
    (read on the chip, PR 42: bf16 -> float8 -> bf16 in one jit moved no
    logit)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def pack(a):
        scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 448.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (a.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn), scale

    @functools.partial(jax.jit, static_argnums=2)
    def unpack(q, scale, dtype):
        return (q.astype(jnp.float32) * scale).astype(dtype)

    return jax.tree.map(
        lambda a: unpack(*pack(a), a.dtype) if a.ndim >= 2 else a, params)


def read(cell: harness.Cell, seed: int, rehearsal: bool = False,
         dump: str = "") -> Dict[str, Any]:
    """One seed: the driver's check over the served tokens (``honest``) and
    over the float8 reference's first choices at the same positions
    (``control``): each the numbers compared, beside their limits, and
    whether they come out correct."""
    import jax.numpy as jnp

    from deepspeed_tpu.serving.scheduler import FINISHED

    family = harness.load_family(cell.config["family"])
    model, mcfg, dtype = serve.cell_model(cell, family)
    params = serve.make_params(model, mcfg, seed, dtype)
    srv = serve.start_engine(cell, model, params, rehearsal)
    check = cell.system["check"]
    reqs = serve.submit_checked(srv, check, mcfg.vocab_size, seed)
    srv.run_until_idle()
    srv.close()
    if not all(r.state == FINISHED for r in reqs):
        raise RuntimeError("a checked request did not finish")
    served = serve.served_of(reqs)
    del srv, reqs
    gc.collect()

    picks_needed = bool(mcfg.moe_experts and mcfg.moe_norm_topk)
    honest = serve.check_served(family, cell, params, served)
    # the control's tokens: what the float8 reference puts first at each
    # served position of the same prompts and tokens (it routes by itself).
    # The weights are rounded in place (two sets do not fit beside a float32
    # layer) and drawn anew from the seed for the float32 reference after
    logits_fn = lambda p, ids: family.reference_logits(cell.config, p, ids)
    longest = max(check["prompt_lens"]) + int(check["new_tokens"])
    low = float8_weights(params)
    del params
    first = [np.asarray(jnp.argmax(reference.served_logits(
        logits_fn, low, prompt, tokens,
        reference.padded_len(len(prompt) + len(tokens), longest))[0], -1))
        for prompt, tokens, _ in served]
    del low
    params = serve.make_params(model, mcfg, seed, dtype)
    control = serve.check_served(
        family, cell, params, [(p, t, None) for p, t, _ in served],
        emitted=first)
    if dump:
        os.makedirs(dump, exist_ok=True)
        np.savez(os.path.join(dump, f"{seed}.npz"),
                 honest=np.stack(honest["gaps"]),
                 control=np.stack(control["gaps"]),
                 prompt_lens=np.asarray([len(p) for p, _, _ in served]))
    row = {"seed": seed}
    for name, got in (("honest", honest), ("control", control)):
        checks, compared = serve.judge(got, picks_needed and name == "honest")
        gaps = np.concatenate(got["gaps"])
        row[name] = {"correct": all(checks.values()), "compared": compared,
                     "positions": int(gaps.size),
                     "moved": int((gaps > 0).sum())}
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.kind != "serve":
        raise SystemExit(f"{cell.name}: a {cell.kind} cell has no such check")
    harness.take_devices(cell.chips)
    harness.configure_compile_cache()
    rows = []
    for n in range(args.seeds):
        rows.append(read(cell, args.seed + n, dump=args.dump))
        print("[control]", json.dumps(rows[-1]), flush=True)
    ok = all(r["honest"]["correct"] and not r["control"]["correct"]
             for r in rows)
    numbers = {name: {
        "honest_largest": max(r["honest"]["compared"][name]["value"]
                              for r in rows),
        "control_smallest": min(r["control"]["compared"][name]["value"]
                                for r in rows),
        "limit": rows[0]["honest"]["compared"][name]["limit"]}
        for name in rows[0]["control"]["compared"]}
    print(json.dumps({
        "workload": cell.name, "seeds": len(rows), "numbers": numbers,
        "controls_that_passed": [r["seed"] for r in rows
                                 if r["control"]["correct"]],
        "honest_that_failed": [r["seed"] for r in rows
                               if not r["honest"]["correct"]],
        "every_control_fails_and_every_honest_passes": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
