#!/usr/bin/env python3
"""The latent-attention kernel alone, on the chip, at the DeepSeek-V2 cell's
widths (128 heads over a 640-lane row of 512 + 64, pages of 32 tokens), in
the form each call shape takes (``latent_attention.form``): a decode call of
32 lanes ABSORBED (``attn_kv_b`` folded into the query and the output beside
the kernel), and one lane's prefill chunk of 256, 512, 1 024 and 1 536 rows
EXPANDED inside the kernel (each key tile through ``attn_kv_b`` once for all
of the chunk's rows), as a first chunk and behind 8 192 and 24 576 cached
tokens; then calls of MORE rows than a chunk program holds (3 072 rows, two
row tiles of 1 536 on the grid, each expanding what it sees; a whole prompt
of 24 576 rows in one call, sixteen: what ``serving.prefill_chunk_tokens`` 0
hands the kernel), each held to the same rows handed in a tile a call
(``max_abs_off_row_tiles``).

    chiprun -- python scripts/latent_attention_bench.py

One JSON line a case: milliseconds a call of ONE layer (host clock around
``block_until_ready`` over ``--reps`` repeats of a jitted call; a decode
call's holds the absorb matmuls too), the operations the form does by its
own count (``tflop_own``) and the operations the MATHEMATICS needs by
``benchmark/mla_cost.pair_flops`` (``tflop_need``: a decode row the absorbed
count, a chunk the smaller form's with every cached token expanded once a
call, which is what ``mla_roofline.py``'s shares divide by), and how far the
result lies from the absorbed ``latent_attention_reference`` on the same
operands. PR 49 read an expanded mode that re-expanded a 256-row tile at a
time beside the absorbed chunk with this script and removed it; PR 50 read
the chunk-shared expansion beside the absorbed chunk of the parent commit
(PERF.md, section 6, PR 50 has the table) and removed the absorbed chunk.
``tflop_own`` of a call of several row tiles counts every tile's own
expansion (``latent_attention.chunk_expanded_keys``).
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
    """A call in the absorbed form, its absorb matmuls beside the kernel."""
    def call(pool, qn, qp, wk, wv, bt, ctx, q0):
        q = absorb_query(qn, qp, wk, LANES)
        o = attend(q, pool, bt, ctx, value=RANK, sm_scale=SCALE,
                   layer_idx=jnp.int32(0), **(
                       {} if q0 is None else {"q_start": q0}))
        return absorb_output(o, wv)
    return jax.jit(call)


@jax.jit
def expanded(pool, qn, qp, wk, wv, bt, ctx, q0):
    """A chunk's call: the expansion is inside the kernel."""
    return la.latent_chunk_attention(qn, qp, wk, wv, pool, bt, ctx,
                                     sm_scale=SCALE, layer_idx=jnp.int32(0),
                                     q_start=q0)


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
    twin = absorbed(la.latent_attention_reference)
    calls = {"absorbed": absorbed(la.latent_attention), "expanded": expanded}
    own, pair_x, expand_x = mla_cost.pair_flops(DIMS)
    cases = [("decode", 32, 1, ctx) for ctx in (2048, 8192, 24576)] + [
        ("chunk", 1, T, ctx) for T in (256, 512, 1024, 1536, 3072)
        for ctx in (T, 8192, 24576)] + [("chunk", 1, 24576, 24576)]
    for kind, lanes, T, ctx in cases:
        ops = operands(args.seed, lanes, T)
        lens = jnp.full((lanes,), ctx, jnp.int32)
        q0 = None if T == 1 else lens - T
        pairs = lanes * sum(range(ctx - T + 1, ctx + 1))
        # what the mathematics needs (``mla_cost.attention``'s rule): a
        # decode row the absorbed count, a chunk the smaller form's with
        # each cached token expanded once a call
        need = own * pairs if T == 1 else min(
            own * pairs, pair_x * pairs + expand_x * lanes * ctx)
        form = la.form(T)
        ms, out = timed(calls[form], (*ops, lens, q0), args.reps)
        done = own * pairs if form == "absorbed" else pair_x * pairs + \
            expand_x * lanes * la.chunk_expanded_keys(T, ctx - T, ctx)
        line = {"case": kind, "form": form, "lanes": lanes, "rows": T,
                "ctx": ctx, "ms": ms, "tflop_own": done / 1e12,
                "tflops_per_s_own": done / ms / 1e9,
                "tflop_need": need / 1e12,
                "tflops_per_s_need": need / ms / 1e9,
                "bytes_gb_per_s": lanes * ctx * 1152 / ms / 1e6,
                "device": dev.device_kind}
        tiles, per = la.chunk_tiles(T)
        if tiles > 1:
            pool, qn, qp, *rest = ops
            parts = [expanded(pool, qn[:, :, r:r + per], qp[:, :, r:r + per],
                              *rest, jnp.minimum(lens, q0 + r + per), q0 + r)
                     for r in range(0, T, per)]
            line["row_tiles"] = tiles
            line["max_abs_off_row_tiles"] = float(jnp.max(jnp.abs(
                out.astype(jnp.float32)
                - jnp.concatenate(parts, axis=2).astype(jnp.float32))))
        elif ctx <= 8192 and (T == 1 or ctx == T or T == 256):
            want = twin(*ops, lens, q0)
            line["max_abs_off_reference"] = float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - want.astype(jnp.float32))))
            line["reference_abs_max"] = float(jnp.max(jnp.abs(
                want.astype(jnp.float32))))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
