"""Training-throughput benchmark: model-flops TFLOPs/chip for any preset.

Covers the reference's headline training benchmarks (BASELINE.md):
  - BERT-large seq128: 64 TFLOPS/GPU (docs/_posts/2020-05-28-fastest-bert
    -training.md:36) and seq512: 53 TFLOPS/GPU
  - GPT-2 sustained training throughput: 50 TFLOPS/GPU
    (docs/_posts/2021-03-08-zero3-offload.md:65)

The repo-root ``bench.py`` (the driver's entry) is the GPT-2 instance of this
loop; this module generalizes it so ``ds_bench --training bert-large`` can
reproduce every headline row on TPU.
"""

import json
import time

import numpy as np

#: Per-chip dense bf16 peak, keyed by the EXACT ``device_kind`` string the
#: chip reports (``python chip_smoke.py`` prints it). A device that is not
#: in the table is an error, never a default: a wrong MFU is worse than none.
BF16_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (16 GB HBM at 819 GB/s); reported as "TPU v5 lite" (chip run, PR 21)
    "TPU v5 lite": 197.0,
}


def _bf16_peak_tflops() -> float:
    """This device's bf16 peak; raises for a device the table does not know
    (the CPU among them — a utilization is a chip number)."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in BF16_PEAK_TFLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device_kind {kind!r}: add it to "
            "training_bench.BF16_PEAK_TFLOPS with its source (known: "
            f"{sorted(BF16_PEAK_TFLOPS)})")
    return BF16_PEAK_TFLOPS[kind]


# reference headline numbers to report "vs" (V100, see BASELINE.md)
REFERENCE_TFLOPS = {
    ("bert-large", 128): 64.0,
    ("bert-large", 512): 53.0,
    ("gpt2-350m", 1024): 50.0,
    ("gpt2-1.3b", 1024): 50.0,
}


def run_training_bench(preset: str = "bert-large", seq: int = 128,
                       micro: int = 64, gas: int = 1, steps: int = 4,
                       zero_stage: int = 1, remat: bool = False,
                       remat_policy: str = "dots", fused_loss=None,
                       pure_bf16: bool = False,
                       grad_accum_dtype=None,
                       masked=None,
                       low_precision=None,
                       verbose: bool = True,
                       **model_kw):
    """Measure sustained train-step model TFLOPs/chip for a preset.

    Extra keyword args flow into ``build_model`` (``attention_impl``,
    ``moe_experts``, ``moe_k``, …) so long-context and MoE variants run
    through the same timing loop. Returns the result dict (also printed as
    one JSON line when verbose).

    ``masked`` (default: True for BERT presets): batches carry a ragged
    attention_mask — sample lengths uniform in [seq/4, seq], the layout real
    padded-batch training sees. The mask rides the Pallas flash kernel
    in-kernel, so this leg times the representative path instead of the
    maskless upper bound (a maskless encoder leg never exercises the mask
    plumbing the reference's fused softmax kernels exist for).
    """
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import (build_model, fused_loss_passthrough,
                                      make_moe_loss)
    from deepspeed_tpu.models.transformer import causal_lm_loss, cross_entropy

    peak = _bf16_peak_tflops()          # an unknown device fails up front
    n_chips = len(jax.devices())
    causal = not preset.startswith("bert")
    if fused_loss is None:
        # measured on v5e: the chunked fused CE wins for causal seq>=1024
        # (avoids [B,S,50257] fp32 logits) but LOSES ~20% for BERT seq128
        # (logits fit; the checkpoint-recompute costs more than it saves)
        fused_loss = causal
    kw = dict(max_seq_len=max(seq, 512), remat=remat,
              remat_policy=remat_policy, fused_loss=fused_loss,
              loss_chunk=256)
    if low_precision:
        # round-17 experiment: int8/fp8 fake-quant on every block matmul
        # input (quant_format.fake_quant_act, STE) — sentinel-gated below
        kw["activation_quant"] = low_precision
    kw.update(model_kw)
    model, cfg = build_model(preset, **kw)
    batch_size = micro * gas * max(n_chips, 1)
    config = {
        "train_batch_size": batch_size,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        # pure_bf16: params-are-master + bf16 moments (BF16Config.
        # master_weights) — the device-resident route to 1.3B on one 16GB
        # chip
        "bf16": ({"enabled": True, "master_weights": False} if pure_bf16
                 else {"enabled": True}),
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10_000,
    }
    if grad_accum_dtype:
        config["data_types"] = {"grad_accum_dtype": grad_accum_dtype}
    if low_precision:
        # the experiment ships with its guardrail: the integrity sentinel's
        # skip/rollback ladder (the engine refuses the flag without it)
        config["integrity"] = {"enabled": True}
    rng = np.random.default_rng(0)
    if masked is None:
        masked = not causal

    def make_batch():
        b = {"input_ids": rng.integers(0, cfg.vocab_size,
                                       size=(batch_size, seq))}
        if masked:
            lens = rng.integers(max(seq // 4, 1), seq + 1, size=(batch_size,))
            b["attention_mask"] = (np.arange(seq)[None, :]
                                   < lens[:, None]).astype(np.int32)
        return b

    # fused_loss models return the scalar loss (BERT variant predicts in
    # place — same cost profile as the reference's MLM objective); plain
    # models emit [B,S,V] logits scored with token-level CE
    loss_fn = (fused_loss_passthrough if fused_loss
               else (causal_lm_loss if causal else
                     lambda out, b: cross_entropy(
                         out, b.get("labels", b["input_ids"]))))
    if cfg.moe_experts > 0:
        # MoE models emit (task_output, aux); fold the aux term in the same
        # way training does so the timed step is the real thing
        loss_fn = make_moe_loss(cfg.moe_aux_weight, base_loss=loss_fn)
    engine, *_ = ds.initialize(model=model, config=config, loss_fn=loss_fn,
                               example_batch=make_batch())

    def fenced_step():
        metrics = engine.train_batch(make_batch())
        jax.block_until_ready((engine.state, metrics))

    fenced_step()                                     # compile
    fenced_step()                                     # steady state

    # per-step timings, each ended by block_until_ready on the step's
    # outputs — median + spread instead of a single mean over a window
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        fenced_step()
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times))
    spread = (max(times) - min(times)) / dt if dt else 0.0

    # FLOPs accounting: the 6N basis is what the reference's TFLOPS/GPU
    # numbers use (attention-free); the attention matmul term (12*L*H*S per
    # token fwd+bwd) is reported separately so MFU is honest
    n_params = cfg.num_active_params()
    tokens = batch_size * seq
    model_flops = 6.0 * n_params * tokens
    attn_flops = 12.0 * cfg.num_layers * cfg.hidden_size * seq * tokens
    tflops = model_flops / dt / max(n_chips, 1) / 1e12
    tflops_attn = (model_flops + attn_flops) / dt / max(n_chips, 1) / 1e12
    ref = REFERENCE_TFLOPS.get((preset, seq))
    out = {
        "metric": f"{preset}_seq{seq}_train_tflops_per_chip",
        "value": round(tflops, 3),
        "unit": "TFLOPs/chip",
        "vs_baseline": round(tflops / ref, 4) if ref else None,
        "detail": {"preset": preset, "seq": seq, "micro": micro, "gas": gas,
                   "batch": batch_size, "chips": n_chips,
                   **({"moe_experts": cfg.moe_experts, "moe_k": cfg.moe_k,
                       "params_total": cfg.num_params(),
                       "params_active": n_params}
                      if cfg.moe_experts > 0 else {}),
                   **({"attention_impl": cfg.attention_impl}
                      if cfg.attention_impl != "auto" else {}),
                   "masked": bool(masked),
                   "zero_stage": zero_stage, "remat": remat,
                   "remat_policy": remat_policy if remat else None,
                   "pure_bf16": pure_bf16,
                   **({"low_precision": low_precision} if low_precision
                      else {}),
                   "grad_accum_dtype": grad_accum_dtype or "fp32",
                   "step_time_s": round(dt, 4),
                   "step_time_spread": round(spread, 4),
                   "steps_timed": steps,
                   "step_times_s": [round(t, 4) for t in times],
                   "tflops_incl_attention": round(tflops_attn, 3),
                   "mfu_incl_attention": round(tflops_attn / peak, 4),
                   "samples_per_s": round(batch_size / dt, 2),
                   "platform": jax.devices()[0].platform,
                   "device_kind": jax.devices()[0].device_kind},
    }
    if verbose:
        print(json.dumps(out))
    return out


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="bert-large")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--micro", type=int, default=64)
    p.add_argument("--gas", type=int, default=1)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--zero", type=int, default=1)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", default="dots",
                   help="full | dots | offload (see TransformerConfig)")
    fl = p.add_mutually_exclusive_group()
    fl.add_argument("--fused-loss", dest="fused_loss", default=None,
                    action="store_true",
                    help="force the chunked fused CE (default: causal only)")
    fl.add_argument("--no-fused-loss", dest="fused_loss",
                    action="store_false",
                    help="force the plain [B,S,V]-logits loss")
    mk = p.add_mutually_exclusive_group()
    mk.add_argument("--masked", dest="masked", default=None,
                    action="store_true",
                    help="ragged attention_mask batches (default for BERT)")
    mk.add_argument("--no-masked", dest="masked", action="store_false",
                    help="maskless batches (the pre-round-6 upper bound)")
    p.add_argument("--low-precision", choices=("int8", "fp8"), default=None,
                   help="round-17 experiment: fake-quant block matmul "
                        "inputs (sentinel-gated; e.g. --preset gpt2-350m)")
    a = p.parse_args(argv)
    from ..utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    run_training_bench(a.preset, a.seq, a.micro, a.gas, a.steps, a.zero,
                       a.remat, remat_policy=a.remat_policy,
                       fused_loss=a.fused_loss, masked=a.masked,
                       low_precision=a.low_precision)


if __name__ == "__main__":
    main()
