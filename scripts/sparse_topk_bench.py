#!/usr/bin/env python3
"""``sparse_topk`` alone, on the chip, at the Keye cell's shapes: a prefill
chunk's 256 consecutive rows at several positions of a 25 600-key table and
a decode call's 16 lanes of different contexts, eight layers a step as the
serving programs call it.

    chiprun -- python scripts/sparse_topk_bench.py [--old FILE] [--sweep]

Scores are drawn as the indexer draws them (``sum_j w_j relu(qI_j . kI)``
over normal ``qI``, ``kI``, ``w``; 16 heads of 64), ``-inf`` where a row does
not see a key. One JSON line a shape and setting: ms a step (eight calls) by
the DEVICE's clock (the ``sparse_topk`` custom calls of a traced repeat: the
host's clock cannot read under its own 0.4 ms a dispatch), the value and
position passes its tiles made, the share of the table's columns they passed
over, and whether the selected sets are
``topk_threshold_reference``'s. ``--old FILE``: another version of
``ops/pallas/sparse_select.py`` (``git show <commit>:<path> > FILE``) on the
same scores. ``--sweep``: the module's tile rows, column step and passes a
group varied (what the constants in the module were chosen from).
"""

import argparse
import importlib.util
import inspect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from benchmark import trace     # noqa: E402

K, KP, LAYERS, HEADS, WIDTH = 2048, 25600, 8, 16, 64
#: name -> (q_start [B], rows a lane): a chunk's first row, a decode
#: call's lanes (0: idle)
SHAPES = {
    "chunk@0": ([0], 256), "chunk@1920": ([1920], 256),
    "chunk@2048": ([2048], 256), "chunk@8192": ([8192], 256),
    "chunk@16384": ([16384], 256), "chunk@24320": ([24320], 256),
    "decode16": ([2300, 0, 9100, 5000, 0, 24000, 0, 3000,
                  0, 12000, 7000, 0, 0, 16000, 0, 8800], 1),
}


def load(path):
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._sparse_other", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def draw(seed, q0, T):
    """``[LAYERS, B * T, KP]`` scores and the rows' positions."""
    B = len(q0)
    pos = (np.asarray(q0)[:, None] + np.arange(T)[None]).reshape(-1)

    @jax.jit
    def one(key):
        kq, kk, kw = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B * T, HEADS, WIDTH), jnp.float32)
        k = jax.random.normal(kk, (KP, WIDTH), jnp.float32)
        w = jax.random.normal(kw, (B * T, HEADS), jnp.float32)
        s = jnp.einsum("th,thk->tk", w, jax.nn.relu(
            jnp.einsum("thd,kd->thk", q, k)))
        return jnp.where(jnp.arange(KP)[None] <= pos[:, None], s, -jnp.inf)

    keys = jax.random.split(jax.random.PRNGKey(seed), LAYERS)
    return jnp.stack([one(k) for k in keys]), pos


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--old")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/sparse_topk_bench.jsonl")
    ap.add_argument("--trace-dir", default=".bench_trace/sparse_topk")
    args = ap.parse_args()
    from deepspeed_tpu.ops.pallas import sparse_select as new
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("sparse_topk_bench needs a TPU")
    settings = [("new", new, {})]
    if args.sweep:
        settings += [(f"new rows={r} cols={c} group={g}", new,
                      {"_TOPK_ROWS": r, "_TOPK_GROUP": g,
                       "_key_tile": (lambda Kp, c=c: c)})
                     for r, c, g in ((8, 1024, 4), (32, 1024, 4),
                                     (16, 1024, 1), (16, 1024, 8),
                                     (16, 512, 4), (16, 2560, 4))]
    if args.old:
        settings.append(("old", load(args.old), {}))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as log:
        for name, (q0, T) in SHAPES.items():
            scores, pos = draw(args.seed, q0, T)
            seen = jnp.asarray(pos + 1, jnp.int32)
            want = jax.jit(jax.vmap(lambda s: sets(new, s, *(
                new.topk_threshold_reference(s, K)))))(scores)
            for label, mod, consts in settings:
                kept = {c: getattr(mod, c) for c in consts}
                for c, v in consts.items():
                    setattr(mod, c, v)
                extent = "extent" in inspect.signature(
                    mod.topk_threshold).parameters
                call = (lambda s: mod.topk_threshold(
                    s, K, seen, seen, return_passes=True)) if extent \
                    else (lambda s: mod.topk_threshold(s, K, seen))
                step = jax.jit(lambda sc: jax.lax.map(call, sc))
                thr, tie, *passes = jax.block_until_ready(step(scores))
                with trace.capture(args.trace_dir):
                    for _ in range(args.reps):
                        out = step(scores)
                    jax.block_until_ready(out)
                ops = next(iter(trace.read(args.trace_dir).devices.values()))
                row = {"shape": name, "setting": label,
                       "device_kind": dev.device_kind,
                       "device_ms_per_step": sum(
                           ns for op, _, _, ns in ops
                           if "sparse_topk" in op) / 1e6 / args.reps,
                       "exact": bool(jnp.array_equal(jax.jit(jax.vmap(
                           lambda s, a, b: sets(new, s, a, b)))(
                               scores, thr, tie), want))}
                if extent:
                    passes = np.asarray(passes[0])
                    tiles = np.asarray(mod.topk_tiles(pos + 1, pos + 1, K, KP,
                                                      np))
                    busy = tiles > 0
                    row.update(
                        tiles_a_layer=int(tiles.size),
                        tiles_busy=int(busy.sum()),
                        column_share=float(mod.topk_columns(
                            tiles[busy], KP).sum() / max(1, busy.sum() * KP)),
                        value_passes_mean=float(passes[:, busy, 0].mean())
                        if busy.any() else 0.0,
                        value_passes_max=int(passes[..., 0].max()),
                        position_passes_sum=int(passes[..., 1].sum()))
                for c, v in kept.items():
                    setattr(mod, c, v)
                print(json.dumps(row), flush=True)
                log.write(json.dumps(row) + "\n")


def sets(mod, scores, thr, tie):
    return mod.selected(scores, thr[:, None], tie[:, None],
                        jnp.arange(scores.shape[1])) & (scores > -jnp.inf)


if __name__ == "__main__":
    main()
