"""Long-sequence benchmark: dense flash vs block-sparse layout-skip kernel.

The reference's block-sparse claim (10x longer sequences,
docs/_pages/training.md:108) rests on attention cost scaling with layout
density. This sweep measures wall-clock per forward at growing seq length for
dense flash_attention vs block_sparse_flash_attention with a sliding-window +
global layout, on the real chip: `python -m
deepspeed_tpu.benchmarks.sparse_attention_bench [--seqs 4096,8192,16384]`.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.block_sparse_attention import block_sparse_flash_attention
from ..ops.pallas.flash_attention import flash_attention
from ..ops.sparse_attention import BSLongformerSparsityConfig


def _timed(attn_fn, q, k, v, iters=20):
    """Mean per-call latency of the jitted forward: warm once, then time
    ``iters`` calls ended by ``block_until_ready``."""
    f = jax.jit(attn_fn)
    jax.block_until_ready(f(q, k, v))       # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(q, k, v)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(seqs, heads=8, head_dim=128, block=128, window_blocks=5):
    rows = []
    for S in seqs:
        rng = np.random.default_rng(0)
        shape = (1, heads, S, head_dim)
        q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                   for _ in range(3))
        cfg = BSLongformerSparsityConfig(
            num_heads=heads, block=block,
            num_sliding_window_blocks=window_blocks)
        layout = cfg.make_layout(S)
        density = float(layout.mean())

        t_d = _timed(lambda q, k, v: flash_attention(q, k, v, causal=False),
                     q, k, v)
        t_s = _timed(lambda q, k, v: block_sparse_flash_attention(
            q, k, v, layout, block, causal=False), q, k, v)
        rows.append({"seq": S, "density": round(density, 4),
                     "dense_ms": round(t_d * 1e3, 3),
                     "sparse_ms": round(t_s * 1e3, 3),
                     "speedup": round(t_d / t_s, 2)})
        print(rows[-1])
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="4096,8192,16384")
    args = p.parse_args(argv)
    from ..utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    run([int(s) for s in args.seqs.split(",")])


if __name__ == "__main__":
    main()
