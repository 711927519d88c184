"""Long-context + MoE training throughput legs (single chip).

Fills the two perf-evidence gaps left after the pipeline/serving tables:
  - long-context training: the Pallas flash-attention path at seq 4k/8k,
    where the reference's answer was block-sparse attention (its dense
    kernels stop at ~1-2k; docs/_pages/training.md:108 claims 10x longer
    sequences via sparsity). Flash attention holds dense-exact math at
    those lengths; the reference-impl comparison leg quantifies what the
    kernel buys.
  - MoE training: GShard top-1 dispatch at 350m scale, TFLOPs accounted
    on ACTIVE params (6N with N = params a token actually touches), so
    the number is comparable to the dense 350m leg.

Usage: python scripts/longctx_moe_bench.py [--steps N]
Prints one JSON line per leg (same schema as bench.py) and a markdown
table for docs/BENCHMARKS.md.
"""

import argparse
import gc
import json
import sys

sys.path.insert(0, ".")  # run from the repo root


def run(legs=None, steps=6):
    import jax
    from deepspeed_tpu.benchmarks.training_bench import run_training_bench

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print("WARNING: not on TPU; numbers are smoke only", file=sys.stderr)

    all_legs = {
        # seq, micro, gas, extra model kwargs
        "350m-seq4k-flash": dict(preset="gpt2-350m", seq=4096, micro=2,
                                 gas=8, attention_impl="flash"),
        "350m-seq4k-reference": dict(preset="gpt2-350m", seq=4096, micro=2,
                                     gas=8, attention_impl="reference"),
        "350m-seq8k-flash": dict(preset="gpt2-350m", seq=8192, micro=1,
                                 gas=8, attention_impl="flash"),
        # 4 experts turn the 350m trunk into ~0.96B total params: pure-bf16
        # state (6 bytes/param) is what fits them on one 16 GB chip. 8
        # experts (~1.8B) reproducibly kill this environment's remote AOT
        # compile helper (HTTP 500, subprocess exit 1) — the same-size dense
        # 1.3B program compiles, so the limit is the helper's memory on the
        # grouped-dispatch MoE graph, not the model code.
        "350m-moe4": dict(preset="gpt2-350m", seq=1024, micro=8, gas=4,
                          moe_experts=4, moe_capacity_factor=1.25,
                          pure_bf16=True, grad_accum_dtype="bf16"),
    }
    rows = []
    for name, kw in all_legs.items():
        if legs and name not in legs:
            continue
        kw = dict(kw)
        preset = kw.pop("preset")
        try:
            r = run_training_bench(
                preset, seq=kw.pop("seq"), micro=kw.pop("micro"),
                gas=kw.pop("gas"), steps=steps, zero_stage=1, remat=True,
                remat_policy="dots", fused_loss=True, verbose=False,
                pure_bf16=kw.pop("pure_bf16", False),
                grad_accum_dtype=kw.pop("grad_accum_dtype", None), **kw)
        except Exception as e:  # OOM legs are data, not failures
            print(json.dumps({"leg": name, "error": repr(e)[:300]}),
                  flush=True)
            continue
        r["leg"] = name
        print(json.dumps(r), flush=True)
        d = r["detail"]
        rows.append((name, d["seq"], d["micro"] * d["gas"], r["value"],
                     d["tflops_incl_attention"], d.get("mfu_incl_attention"),
                     d["step_time_s"], d["samples_per_s"]))
        gc.collect()
        jax.clear_caches()

    print("\n| leg | seq | batch | TF/chip (6N) | TF incl attn | MFU | "
          "step s | samples/s |")
    print("|---|---|---|---|---|---|---|---|")
    for name, seq, batch, tf, tfa, mfu, dt, sps in rows:
        mfu_s = f"{mfu:.0%}" if mfu else "—"
        print(f"| {name} | {seq} | {batch} | {tf:.1f} | {tfa:.1f} | "
              f"{mfu_s} | {dt:.2f} | {sps:.2f} |")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("legs", nargs="*", help="subset of leg names")
    a = p.parse_args()
    run(legs=a.legs or None, steps=a.steps)
