#!/usr/bin/env python3
"""The latent-attention kernel alone, on the chip, at the DeepSeek-V2 cell's
widths (128 heads over a 640-lane row of 512 + 64, pages of 32 tokens): a
decode call of 32 lanes, and one lane's prefill chunk of 256, 512 and 1 024
rows (absorbed: ``attn_kv_b`` folded into the query and the output beside
the kernel), at the contexts the cell's prompts reach.

    chiprun -- python scripts/latent_attention_bench.py

One JSON line a case: milliseconds a call of ONE layer (host clock around
``block_until_ready`` over ``--reps`` repeats of a jitted call that holds the
absorb matmuls too), the FLOPs the absorbed form does by
``benchmark/mla_cost.pair_flops``, and how far the result lies from
``latent_attention_reference`` on the same operands. PR 49 read an expanded
mode of the kernel beside it with this script (PERF.md, section 4) and
removed it.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from benchmark import mla_cost                                  # noqa: E402
from deepspeed_tpu.models.generation import (absorb_output,      # noqa: E402
                                             absorb_query)
from deepspeed_tpu.ops.pallas import latent_attention as la      # noqa: E402

HEADS, RANK, ROPE, NOPE, V, LANES, BS, NBK, BLOCKS = \
    128, 512, 64, 128, 128, 640, 32, 800, 2048
DIMS = dict(heads=HEADS, kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
            qk_nope_head_dim=NOPE, v_head_dim=V)
SCALE = 0.11472


def operands(seed, lanes, T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = jnp.bfloat16
    pool = jax.random.normal(ks[0], (1, 1, BLOCKS, BS, LANES), bf).at[
        ..., RANK + ROPE:].set(0)
    qn = jax.random.normal(ks[1], (lanes, HEADS, T, NOPE), bf)
    qp = jax.random.normal(ks[2], (lanes, HEADS, T, ROPE), bf)
    wk = (jax.random.normal(ks[3], (HEADS, RANK, NOPE)) * RANK ** -.5
          ).astype(bf)
    wv = (jax.random.normal(ks[4], (HEADS, RANK, V)) * RANK ** -.5).astype(bf)
    rng = np.random.default_rng(seed)
    bt = np.stack([rng.permutation(BLOCKS - 1)[:NBK] + 1
                   for _ in range(lanes)]).astype(np.int32)
    return pool, qn, qp, wk, wv, jnp.asarray(bt)


def absorbed(attend):
    def call(pool, qn, qp, wk, wv, bt, ctx, q0):
        q = absorb_query(qn, qp, wk, LANES)
        o = attend(q, pool, bt, ctx, value=RANK, sm_scale=SCALE,
                   layer_idx=jnp.int32(0), q_start=q0)
        return absorb_output(o, wv)
    return jax.jit(call)


def timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / reps, out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    kernel, twin = absorbed(la.latent_attention), absorbed(
        la.latent_attention_reference)
    pair_f = mla_cost.pair_flops(DIMS)[0]
    cases = [("decode", 32, 1, ctx) for ctx in (2048, 8192, 24576)] + [
        ("chunk", 1, T, ctx) for T in (256, 512, 1024)
        for ctx in (T, 8192, 24576)]
    for kind, lanes, T, ctx in cases:
        ops = operands(args.seed, lanes, T)
        lens = jnp.full((lanes,), ctx, jnp.int32)
        q0 = lens - T
        pairs = lanes * sum(range(ctx - T + 1, ctx + 1))
        ms, out = timed(kernel, (*ops, lens, q0), args.reps)
        flops = pair_f * pairs
        line = {"case": kind, "lanes": lanes, "rows": T, "ctx": ctx,
                "ms": ms, "tflop": flops / 1e12,
                "tflops_per_s": flops / ms / 1e9,
                "bytes_gb_per_s": lanes * ctx * 1152 / ms / 1e6,
                "device": dev.device_kind}
        if ctx <= 8192 and (T == 1 or ctx == T or T == 256):
            want = twin(*ops, lens, q0)
            line["max_abs_off_reference"] = float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - want.astype(jnp.float32))))
            line["reference_abs_max"] = float(jnp.max(jnp.abs(
                want.astype(jnp.float32))))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
