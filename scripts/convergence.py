"""Convergence sanity harness (reference analogue:
tests/model/Megatron_GPT2/run_sanity_check.py — loss-curve agreement
across configs, not unit-step equality).

Two legs:
  1. CHIP: GPT-2-125M, a few hundred REAL optimizer steps under ZeRO
     stages 0/1/2/3 with identical seed + data order; the four loss
     curves must overlap within tolerance (the stages are layout
     transforms of the same math, so curve divergence = sharding bug).
  2. CPU MESH (8 virtual devices, re-exec'd subprocess like the dryrun):
     a small model trained to convergence under dense DP vs GPipe(pp=2)
     vs 1F1B(pp=2) — the pipeline schedules must track the dense curve.

Data is synthetic but LEARNABLE: per-sample arithmetic token sequences
(next = prev + delta mod V, delta inferable in-context) with 5% noise, so
the loss falls far below the uniform floor and a broken optimizer or
schedule shows up as a flat/diverging curve, which pure-random tokens
would mask.

Usage:  python scripts/convergence.py [--steps 250]
        (run from the repo root; needs the TPU chip for leg 1)
"""
import argparse
import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_batches(vocab, steps, batch, seq, seed=0):
    """[steps, batch, seq] int32: arithmetic sequences mod vocab + 5% noise."""
    rng = np.random.default_rng(seed)
    deltas = rng.integers(1, 17, size=(steps, batch, 1))
    start = rng.integers(0, vocab, size=(steps, batch, 1))
    pos = np.arange(seq)[None, None, :]
    ids = (start + deltas * pos) % vocab
    noise = rng.random((steps, batch, seq)) < 0.05
    ids = np.where(noise, rng.integers(0, vocab, size=ids.shape), ids)
    return ids.astype(np.int32)


def run_stage(stage, ids, preset="gpt2-125m", seq=512, micro=8,
              pure_bf16=False, log_every=50):
    import gc

    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, fused_loss_passthrough

    steps = ids.shape[0]
    model, cfg = build_model(preset, max_seq_len=seq, remat=True,
                             remat_policy="dots", fused_loss=True,
                             loss_chunk=256)
    config = {
        "train_batch_size": micro,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4,
                                                  "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_num_steps": 20}},
        "bf16": {"enabled": True, "master_weights": not pure_bf16},
        "zero_optimization": {"stage": stage},
        "gradient_clipping": 1.0,
        "seed": 1234,
    }
    engine, *_ = ds.initialize(
        model=model, config=config, loss_fn=fused_loss_passthrough,
        example_batch={"input_ids": ids[0]})
    losses = []
    for i in range(steps):
        m = engine.train_batch({"input_ids": ids[i]})
        losses.append(float(m["loss"]))
        if log_every and (i + 1) % log_every == 0:
            print(f"    stage {stage} step {i+1}: {losses[-1]:.4f}",
                  flush=True)
    del engine, model
    gc.collect()
    jax.clear_caches()
    return losses


def chip_leg(steps):
    import jax
    assert jax.default_backend() == "tpu", (
        "leg 1 needs the chip; found " + jax.default_backend())
    from deepspeed_tpu.models import build_model
    _, cfg = build_model("gpt2-125m")
    ids = make_batches(cfg.vocab_size, steps, batch=8, seq=512, seed=0)
    curves = {}
    for stage in (0, 1, 2, 3):
        print(f"  ZeRO-{stage} x {steps} steps on the chip", flush=True)
        curves[f"zero{stage}"] = run_stage(stage, ids)
    return curves


CPU_LEG = r"""
import os, sys, json
sys.path.insert(0, os.environ["DSTPU_CONV_REPO"])
import numpy as np
import jax
import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, causal_lm_loss
from deepspeed_tpu.models.pipeline import build_pipelined_model
sys.path.insert(0, os.path.join(os.environ["DSTPU_CONV_REPO"], "scripts"))
from convergence import make_batches

steps = int(os.environ["DSTPU_CONV_STEPS"])
V, SEQ, B = 256, 64, 16
ids = make_batches(V, steps, batch=B, seq=SEQ, seed=1)
kw = dict(hidden_size=128, num_layers=4, num_heads=4, vocab_size=V,
          max_seq_len=SEQ, attention_impl="reference")
base_cfg = {
    # same GLOBAL batch (16) in every config so the curves are comparable;
    # micro/gas/dp split differs by topology: dense dp=8 -> 2x1x8,
    # pipelined pp=2 => dp=4 -> 2x2x4
    "train_batch_size": B,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 1},
    "gradient_clipping": 1.0,
    "seed": 99,
}
curves = {}
for label in ("dense", "gpipe", "1f1b"):
    config = dict(base_cfg,
                  train_micro_batch_size_per_gpu=2,
                  gradient_accumulation_steps=1 if label == "dense" else 2)
    if label == "dense":
        model, cfg = build_model("gpt2-tiny", **kw)
    else:
        model, cfg = build_pipelined_model("gpt2-tiny", pp=2, n_micro=2,
                                           **kw)
        config["pipeline"] = ({"stages": 2} if label == "gpipe"
                              else {"stages": 2, "schedule": "1f1b"})
    eng, *_ = ds.initialize(model=model, config=config,
                            loss_fn=causal_lm_loss,
                            example_batch={"input_ids": ids[0]})
    ls = [float(eng.train_batch({"input_ids": ids[i]})["loss"])
          for i in range(steps)]
    curves[label] = ls
    print(f"  {label}: start {ls[0]:.4f} final {ls[-1]:.4f}", flush=True)
with open(os.environ["DSTPU_CONV_OUT"], "w") as f:
    json.dump(curves, f)
"""


def cpu_leg(steps, out_path):
    from deepspeed_tpu.utils.respawn import clean_cpu_env
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = clean_cpu_env(8)
    # no PYTHONPATH: CPU_LEG sys.path.inserts the repo itself
    env.update(DSTPU_CONV_REPO=repo, DSTPU_CONV_STEPS=str(steps),
               DSTPU_CONV_OUT=out_path)
    proc = subprocess.run([sys.executable, "-u", "-c", CPU_LEG], env=env,
                          cwd=repo, timeout=3600)
    assert proc.returncode == 0, f"cpu leg rc={proc.returncode}"
    with open(out_path) as f:
        return json.load(f)


def summarize(curves, ref_key, tol_final, tol_max, skip=20):
    """Max pointwise gap vs the reference curve after warmup + final gap."""
    ref = np.asarray(curves[ref_key])
    skip = min(skip, max(len(ref) - 1, 0))   # short runs: compare the tail
    rows = []
    ok = True
    for k, v in curves.items():
        v = np.asarray(v)
        gap = np.abs(v[skip:] - ref[skip:])
        row = {"config": k, "start": round(float(v[0]), 4),
               "final": round(float(v[-1]), 4),
               "max_gap": round(float(gap.max()), 4),
               "final_gap": round(float(abs(v[-1] - ref[-1])), 4)}
        row["pass"] = bool(row["max_gap"] <= tol_max
                           and row["final_gap"] <= tol_final)
        ok &= row["pass"]
        rows.append(row)
    return rows, ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=250)
    ap.add_argument("--cpu-steps", type=int, default=200)
    ap.add_argument("--out", default="docs/convergence_r05.json")
    ap.add_argument("--skip-chip", action="store_true")
    ap.add_argument("--skip-cpu", action="store_true")
    args = ap.parse_args()

    result = {"steps_chip": args.steps, "steps_cpu": args.cpu_steps}
    if not args.skip_chip:
        print("leg 1: ZeRO-0/1/2/3 @ gpt2-125m on the chip", flush=True)
        chip = chip_leg(args.steps)
        rows, ok = summarize(chip, "zero0", tol_final=0.05, tol_max=0.25)
        result["chip"] = {"curves": chip, "summary": rows, "ok": ok}
        for r in rows:
            print("  ", r, flush=True)
    if not args.skip_cpu:
        print("leg 2: dense vs gpipe vs 1f1b @ tiny on the 8-dev CPU mesh",
              flush=True)
        cpu = cpu_leg(args.cpu_steps, "/tmp/conv_cpu.json")
        rows, ok = summarize(cpu, "dense", tol_final=0.05, tol_max=0.25)
        result["cpu"] = {"curves": cpu, "summary": rows, "ok": ok}
        for r in rows:
            print("  ", r, flush=True)

    with open(args.out, "w") as f:
        json.dump(result, f)
    legs = [k for k in ("chip", "cpu") if k in result]
    all_ok = bool(legs) and all(result[k]["ok"] for k in legs)
    print(f"convergence: {'OK' if all_ok else 'DIVERGED'} -> {args.out}",
          flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
