#!/usr/bin/env python3
"""Step-for-step replay of a serving cell's closed loop on the CPU, for whoever
designs a traffic mix: no chip time, no device number.

    python3 benchmark/mixsim.py --workload serve-mistral-7b-l16-chat \\
        --decode-ms 197 --prefill-ms 243 [--seconds 45] [--orders 12]
        [--scales 0.8,0.9,1,1.1,1.2] [--clients 32]

The serving loop never looks at the clock: which request is admitted, prefilled
and finished in which step follows from the sizes and their order alone. So the
program's own ``ServingEngine`` (scheduler, block pool, prefix cache) is run
with the cell's ``serving`` settings over a model of no size, its one device
call replaced by zeros, and a clock that adds ``--decode-ms`` for a step
without a prefill chunk and ``--prefill-ms`` for one with (both as a traced
chip run reports them: ``serve_decode_step_ms``, ``serve_prefill_step_ms``).
On PR 23's chip runs the replay gave the window's steps, requests and tokens
exactly and tokens/s and the TTFT percentiles within 0.2%.

It answers what a chip run cannot afford to: how far tokens/s and the TTFT
percentiles move with the ORDER of the same requests (``--orders``: that many
values of ``mix_seed``), with the window's length and with the step time
(``--scales``; times are printed divided by the scale, rates multiplied, so a
metric that does not hang on which requests the window holds reads the same in
every row). Every number it prints is simulated and named so.
"""

import argparse
import logging
import os
import statistics
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, reduce  # noqa: E402
from benchmark.traffic import request_sizes  # noqa: E402


def replay(mix, serving, *, seconds, decode_s, prefill_s, scale=1.0):
    """One window of the closed loop after the driver's ramp; simulated
    tokens/s, TTFT median / p95, ITL p95 and the requests submitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import TransformerConfig, build_model
    from deepspeed_tpu.serving.engine import ServingEngine

    longest = serving["max_blocks_per_seq"] * serving["block_size"]
    model, mcfg = build_model(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=longest, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    srv = ServingEngine(mcfg, params, serving=dict(serving))
    srv._run_device = lambda fn, *a: np.zeros(
        (1 if fn is srv._prefill_fn else srv.max_batch,), np.int32)

    sizes, rng, now = request_sizes(mix), np.random.default_rng(0), 0.0
    start = end = None
    first_tokens = tokens = 0
    ttft, itl = [], []

    def submit():
        p, o = next(sizes)
        return [srv.submit(rng.integers(1, 64, p).tolist(), max_new_tokens=o),
                now, 0, None]              # request, submitted, seen, last

    def inside(t):
        return start is not None and t >= start and (end is None or t <= end)

    clients = [submit() for _ in range(int(mix["clients"]))]
    while True:
        if start is None and first_tokens >= len(clients):
            start = now                                   # the ramp is over
        if end is None and start is not None and now - start >= seconds:
            end = now
        if end is not None and all(c[2] or not inside(c[1]) for c in clients):
            break
        before = srv.stats["prefill_tokens"]
        srv.step()
        now += scale * (prefill_s if srv.stats["prefill_tokens"] > before
                        else decode_s)
        for i, c in enumerate(clients):
            n = len(c[0].output_tokens)
            if n > c[2]:
                if c[2] == 0:
                    first_tokens += 1
                    if inside(c[1]):
                        ttft.append(now - c[1])
                elif inside(c[3]) and inside(now):
                    itl.append(now - c[3])
                tokens += (n - c[2]) if inside(now) else 0
                c[2], c[3] = n, now
            if c[0].done and end is None:
                clients[i] = submit()
    return {"requests": len(ttft),
            "sim_tokens_per_s": scale * tokens / (end - start),
            "sim_ttft_p50_ms": 1e3 * statistics.median(ttft) / scale,
            "sim_ttft_p95_ms": 1e3 * reduce.p95(ttft) / scale,
            "sim_itl_p95_ms": 1e3 * reduce.p95(itl) / scale}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--decode-ms", type=float, required=True)
    ap.add_argument("--prefill-ms", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--orders", type=int, default=1)
    ap.add_argument("--scales", default="1")
    ap.add_argument("--clients", type=int, help="in place of the mix's")
    args = ap.parse_args()
    logging.disable(logging.CRITICAL)
    cell = harness.load_cell(args.workload)
    mix = dict(cell.traffic)
    if args.clients:
        mix["clients"] = args.clients
    rows = []
    for k in range(args.orders):
        for scale in map(float, args.scales.split(",")):
            m = dict(mix, mix_seed=int(mix["mix_seed"]) + k)
            r = replay(m, cell.system["serving"], seconds=args.seconds,
                       decode_s=args.decode_ms / 1e3,
                       prefill_s=args.prefill_ms / 1e3, scale=scale)
            rows.append(r)
            print(f"mix_seed {m['mix_seed']:3d} step time x{scale:<5g} "
                  + "  ".join(f"{k} {v:.1f}" if isinstance(v, float)
                              else f"{k} {v}" for k, v in r.items()),
                  flush=True)
    if len(rows) >= 4:
        for k in rows[0]:
            xs = [r[k] for r in rows]
            q = statistics.quantiles(xs, n=4)
            print(f"{k}: {min(xs):.1f} to {max(xs):.1f}; interquartile range "
                  f"over median {100 * (q[2] - q[0]) / statistics.median(xs):.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
