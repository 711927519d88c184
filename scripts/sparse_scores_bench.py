#!/usr/bin/env python3
"""``sparse_index_scores`` alone, on the chip, at the Keye cell's decode
call: 16 lanes of one query row each against a 25 600-key table of 32-token
pages (the ``ki`` pool of eight layers, 8 192 blocks), eight layers a step as
the decode program calls it. ``scripts/sparse_topk_bench.py``'s sibling.

    chiprun -- python scripts/sparse_scores_bench.py [--old FILE]

One JSON line a set of lane contexts and module: ms a step (eight calls) by
the DEVICE's clock (the ``sparse_index_scores`` custom calls of a traced
repeat, and every device op of the step beside them: a module that pads the
rows pays a gathered copy of its result outside the kernel), the key tiles
the kernel's own rule walks (``score_tiles``, where the module has it) beside
the old grid's lanes x tiles, and whether the scores are
``index_scores_reference``'s (``-inf`` in the same places, finite values
within ``--tol`` of the largest score). The contexts: the cell's mix (a third of the lanes live,
2k-24k keys), every lane idle (a decode call's idle lane holds its one null
key), every lane at 24 000 keys, and one long lane: together they tell a dead
tile's cost from a live one's. ``--old FILE``: another version of
``ops/pallas/sparse_select.py`` (``git show <commit>:<path> > FILE``) on the
same operands.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from benchmark import trace     # noqa: E402
from sparse_topk_bench import load  # noqa: E402  (the sibling's ``--old``)

LAYERS, HEADS, WIDTH, LANES, BS, NBK, BLOCKS = 8, 16, 64, 128, 32, 800, 8192
#: name -> the tokens each lane holds before the call's own (0: idle)
CONTEXTS = {
    "cell": [2300, 0, 9100, 0, 0, 24000, 0, 3000,
             0, 0, 7000, 0, 0, 0, 0, 8800],
    "idle": [0] * 16,
    "full": [24000] * 16,
    "one": [0] * 7 + [24575] + [0] * 8,
}


def draw(seed, lanes):
    """A step's operands: ``qi [L, B, heads, 1, lanes]``, ``w [L, B, 1,
    heads]``, the pool ``[L, 1, blocks, bs, lanes]`` (zeros past the
    indexer's width, as ``PagedCache.write_index`` leaves them) and a
    block table ``[B, nbk]`` of scattered pages."""
    kq, kw, kp = jax.random.split(jax.random.PRNGKey(seed), 3)
    wide = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                             + [(0, LANES - WIDTH)]).astype(jnp.bfloat16)
    qi = wide(jax.random.normal(kq, (LAYERS, lanes, HEADS, 1, WIDTH)))
    w = jax.random.normal(kw, (LAYERS, lanes, 1, HEADS), jnp.float32)
    pool = wide(jax.random.normal(kp, (LAYERS, 1, BLOCKS, BS, WIDTH)))
    # drawn page by page: sixteen full tables are more pages than the pool
    # has, and a page two lanes share is read like any other
    table = np.random.default_rng(seed).integers(1, BLOCKS, (lanes, NBK))
    return qi, w, pool, jnp.asarray(table, jnp.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--old")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tol", type=float, default=1e-4)
    ap.add_argument("--out", default="chiprun_out/sparse_scores_bench.jsonl")
    ap.add_argument("--trace-dir", default=".bench_trace/sparse_scores")
    args = ap.parse_args()
    from deepspeed_tpu.ops.pallas import sparse_select as new
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("sparse_scores_bench needs a TPU")
    modules = [("new", new)] + ([("old", load(args.old))] if args.old else [])
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    qi, w, pool, bt = draw(args.seed, len(CONTEXTS["cell"]))
    Kp = new.padded_keys(NBK * BS)
    layers = jnp.arange(LAYERS)
    with open(args.out, "a") as log:
        for name, held in CONTEXTS.items():
            q0 = jnp.asarray(held, jnp.int32)
            ctx = q0 + 1
            want = jax.jit(lambda pool: jax.lax.map(
                lambda l: new.index_scores_reference(
                    qi[l], w[l], pool[l, 0][bt].reshape(len(held), -1, LANES),
                    q0, ctx), layers))(pool)
            for label, mod in modules:
                step = jax.jit(lambda pool, mod=mod: jax.lax.map(
                    lambda l: mod.index_scores(qi[l], w[l], pool, bt, l, q0,
                                               ctx), layers))
                got = jax.block_until_ready(step(pool))
                with trace.capture(args.trace_dir):
                    for _ in range(args.reps):
                        out = step(pool)
                    jax.block_until_ready(out)
                ops = next(iter(trace.read(args.trace_dir).devices.values()))
                # the map's ``while`` spans its body's ops: not counted twice
                ms = lambda keep: sum(ns for op, _, _, ns in ops if keep(op)
                                      ) / 1e6 / args.reps
                fin = jnp.isfinite(want)
                row = {"contexts": name, "module": label,
                       "device_kind": dev.device_kind,
                       "kernel_ms_per_step": ms(
                           lambda op: "sparse_index_scores" in op),
                       "device_ms_per_step": ms(
                           lambda op: not op.startswith("while")),
                       "result": list(got.shape[1:]),
                       "table_tiles": len(held) * (Kp // new._key_tile(Kp)),
                       "same_inf": bool(jnp.array_equal(jnp.isfinite(got),
                                                        fin)),
                       "largest_gap": float(jnp.max(jnp.where(
                           fin, jnp.abs(got - want), 0.0))),
                       "largest_score": float(jnp.max(jnp.where(
                           fin, jnp.abs(want), 0.0)))}
                row["as_reference"] = row["same_inf"] and row[
                    "largest_gap"] <= args.tol * max(1.0, row["largest_score"])
                if hasattr(mod, "score_tiles"):
                    first, end = mod.score_tiles(np.asarray(held),
                                                 np.asarray(held) + 1, 0, Kp,
                                                 np)
                    row["tiles_walked"] = int((end - first).sum())
                print(json.dumps(row), flush=True)
                log.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
