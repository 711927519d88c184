#!/usr/bin/env python3
"""CPU rehearsal of the benchmark's drivers at a tiny size: no chip time.

    python3 benchmark/rehearse.py                 both kinds, one device
    python3 benchmark/rehearse.py --kind train --chips 4   four virtual devices

Drives the SAME drivers, reducers and result assembly as ``run.py`` on the
real cells' own ``system`` settings, with the sizes below in place of the
configuration and the traffic mix. It finds wrong paths, arguments and
control flow, and nothing else: Pallas kernels run interpreted (serving) or
give way to the jnp attention (training), so no number here is a device
number, and none is printed: only the names of the metrics that were filled
and the outcome of every check. It never prints a result line.
"""

import argparse
import dataclasses
import os
import sys
import tempfile
import time

T0 = time.perf_counter()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--kind", choices=("train", "serve", "all"), default="all")
ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
ap.add_argument("--seconds", type=float, default=4.0)
ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
ARGS = ap.parse_args()
if ARGS.chips > 1:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ARGS.chips}")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, reduce  # noqa: E402

TINY = {
    "train": {
        "cell": "train-gpt2-1.3b-z3",
        "config": {"name": "gpt2-tiny", "family": "gpt2", "num_layers": 2,
                   "hidden_size": 128, "num_attention_heads": 2,
                   "ffn_hidden_size": 512, "max_position_embeddings": 128,
                   "vocab_size": 512, "layernorm_epsilon": 1e-5},
        "traffic": {"kind": "token_batches", "seq_len": 128,
                    "micro_batch_per_chip": 2, "grad_accum_steps": 2},
        "system": {},
    },
    "serve": {
        "cell": "serve-mistral-7b-l16-chat",
        "config": {"name": "mistral-tiny", "family": "mistral",
                   "hidden_act": "silu", "hidden_size": 128,
                   "intermediate_size": 256, "max_position_embeddings": 512,
                   "num_attention_heads": 4, "num_hidden_layers": 2,
                   "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
                   "rope_theta": 10000.0, "sliding_window": 64,
                   "tie_word_embeddings": False, "vocab_size": 512},
        "traffic": {"kind": "closed_loop", "clients": 4,
                    "prompt_len": {"dist": "lognormal", "median": 24,
                                   "sigma": 0.8, "min": 8, "max": 64},
                    "output_len": {"dist": "lognormal", "median": 8,
                                   "sigma": 0.6, "min": 4, "max": 16},
                    "cycle": 8, "mix_seed": 1},
        # float32: with random weights bf16 ties would flip tokens, and the
        # CPU rehearsal has no use for the serving type
        "system": {"dtype": "float32",
                   "serving": {"block_size": 16, "pool_blocks": 24,
                               "max_batch": 4, "max_blocks_per_seq": 8,
                               "prefill_chunk_tokens": 32,
                               "prefix_cache": True},
                   "check": {"prompt_lens": [12, 40], "new_tokens": 6}},
    },
}


def rehearse(kind: str, trace: bool) -> bool:
    tiny = TINY[kind]
    real = harness.load_cell(tiny["cell"])
    cell = dataclasses.replace(
        real, chips=ARGS.chips, config=tiny["config"],
        traffic=tiny["traffic"], system={**real.system, **tiny["system"]})
    print(f"== rehearsal of {real.name} ({kind}, trace {int(trace)}, "
          f"{ARGS.chips} device(s)) at a tiny size ==", flush=True)
    with tempfile.TemporaryDirectory(prefix="bench_rehearsal_") as tdir:
        out = harness.load_driver(kind).run(
            cell, seed=ARGS.seed, seconds=ARGS.seconds, trace=trace, t0=T0,
            trace_dir=tdir, rehearsal=True)
    layer = reduce.layer_metrics(
        harness.load_layer_metrics(kind, cell=real.name), out["obs"])
    print(f"   end-to-end metrics filled: {sorted(out['end_to_end'])}")
    print(f"   per-layer metrics filled (trace- and peak-sourced ones need "
          f"the chip): {sorted(layer)}")
    print(f"   attempted {out['attempted']}, failed {out['failed']}")
    for what, ok in out["checks"].items():
        print(f"   {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    return all(out["checks"].values())


def main() -> int:
    import jax
    if jax.default_backend() != "cpu":
        print("rehearse.py runs on the CPU (JAX_PLATFORMS=cpu); the chip run "
              "is benchmark/run.py", file=sys.stderr)
        return 2
    kinds = ("train", "serve") if ARGS.kind == "all" else (ARGS.kind,)
    ok = True
    for kind in kinds:
        if ARGS.chips > 1 and kind == "serve":
            continue                # one engine, one device: nothing to shard
        for trace in (False, True):
            ok = rehearse(kind, trace) and ok
    print("rehearsal " + ("passed" if ok else "FAILED")
          + " (no device number was taken)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
