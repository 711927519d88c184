"""Training cells: ``deepspeed_tpu.initialize`` + ``engine.train_batch``, the
way a user's script drives them (copied from ``chip_smoke.train_phase``).

Set-up: build the model from the configuration, write ``ds_config.json``,
``initialize`` on the mesh of every chip the cell has, the reference's loss
on the first batch (before any step: the step donates the weights), then two
warm-up steps, the first of which compiles and is held against the reference.
Window: a fresh seeded batch every step, each step ended by a sync, until
``--seconds`` have passed; it ends with the step that crosses the mark.
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from typing import Any, Dict

from benchmark import harness, reference, trace as tracing
from benchmark.traffic import token_batches

WARMUP_STEPS = 2


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t0: float, trace_dir: str, rehearsal: bool = False
        ) -> Dict[str, Any]:
    devices = harness.take_devices(cell.chips, rehearsal)
    import jax

    if not rehearsal:
        harness.configure_compile_cache()
    watch = harness.CompileWatch(t0)
    watch.report("devices taken")

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import (TransformerConfig, build_model,
                                      fused_loss_passthrough)
    from deepspeed_tpu.parallel.mesh import MeshManager

    watch.report("program imported")
    family = harness.load_family(cell.config["family"])
    mix, n = cell.traffic, len(devices)
    micro, gas = int(mix["micro_batch_per_chip"]), int(mix["grad_accum_steps"])
    rows, seq = micro * gas * n, int(mix["seq_len"])
    model, mcfg = build_model(TransformerConfig(
        **family.model_kwargs(cell.config), **cell.system["model"]))
    print(f"[train] {cell.config['name']}: {mcfg.num_params() / 1e9:.3f}B "
          f"params; {n} chip(s), micro {micro} x gas {gas} x dp {n} = {rows} "
          f"rows x {seq} tokens a step", flush=True)

    ds_config = dict(cell.system["ds_config"],
                     train_batch_size=rows,
                     train_micro_batch_size_per_gpu=micro,
                     gradient_accumulation_steps=gas,
                     seed=harness.jax_seed(seed), steps_per_print=10 ** 9)
    batches = token_batches(mix, mcfg.vocab_size, seed, n)
    first = next(batches)
    with tempfile.TemporaryDirectory(prefix="bench_train_") as workdir:
        cfg_path = os.path.join(workdir, "ds_config.json")
        with open(cfg_path, "w") as f:
            json.dump(ds_config, f, indent=1)
        # the engine's default mesh is every chip JAX reports; only a
        # rehearsal on fewer virtual devices than JAX has hands it a mesh
        kw = {} if len(devices) == len(jax.devices()) else {
            "mesh_manager": MeshManager(devices=list(devices))}
        engine, *_ = ds.initialize(
            model=model, config=cfg_path, loss_fn=fused_loss_passthrough,
            example_batch={"input_ids": first}, **kw)
    jax.block_until_ready(engine.state)
    watch.report("initialize")

    ref_loss = reference.batch_loss(
        lambda p, ids: family.reference_logits(cell.config, p, ids),
        engine.state.params, first)
    watch.report("reference loss")

    def step(batch):
        t = time.perf_counter()
        metrics = engine.train_batch({"input_ids": batch})
        jax.block_until_ready((engine.state, metrics))
        return time.perf_counter() - t, float(metrics["loss"])

    _, loss0 = step(first)
    for _ in range(WARMUP_STEPS - 1):
        step(next(batches))
    watch.report("warm-up")
    loss_ok = abs(loss0 - ref_loss) <= reference.TRAIN_LOSS_TOL
    print(f"[train] first-step loss {loss0:.5f}, reference {ref_loss:.5f}: "
          f"|diff| {abs(loss0 - ref_loss):.5f} "
          f"{'<=' if loss_ok else '>'} {reference.TRAIN_LOSS_TOL}",
          flush=True)

    def window(length: float):
        """Steps until ``length`` seconds have passed; (step seconds,
        losses, wall seconds)."""
        times, losses = [], []
        start = time.perf_counter()
        while time.perf_counter() - start < length:
            with tracing.annotate("make_batch"):
                batch = next(batches)
            with tracing.annotate("train_batch"):
                dt, loss = step(batch)
            times.append(dt)
            losses.append(loss)
        return times, losses, time.perf_counter() - start

    counters: Dict[str, float] = {}
    the_trace = None
    traced_losses = []
    compiles_before = watch.compiles
    setup_s = time.perf_counter() - t0
    if trace:
        trace_s = min(harness.TRACE_SECONDS, seconds / 2)
        (t_times, traced_losses, _), the_trace = tracing.record(
            trace_dir, lambda: window(trace_s))
        counters["traced_steps"] = len(t_times)
        seconds = max(seconds - trace_s, 1.0)
    times, losses, wall = window(seconds)
    compiles_in_window = watch.compiles - compiles_before
    watch.report("window")

    all_losses = traced_losses + losses
    finite = [math.isfinite(x) for x in all_losses]
    tokens_per_s_per_chip = len(times) * rows * seq / wall / n
    counters["tokens_per_s_per_chip"] = tokens_per_s_per_chip
    memory_peak = harness.memory_peak_bytes(devices)
    if memory_peak:                     # the CPU client keeps no such counter
        counters["peak_hbm_bytes"] = memory_peak
    fell = all_losses[-1] < all_losses[0]
    print(f"[train] {len(times)} steps in {wall:.2f}s, losses "
          f"{all_losses[0]:.4f} -> {all_losses[-1]:.4f}; compiles inside the "
          f"window: {compiles_in_window}", flush=True)

    checks = {"first-step loss matches the reference": loss_ok,
              "every loss finite": all(finite),
              "last loss below the first": fell,
              "no compile inside the window": compiles_in_window == 0}
    obs = {"clocks": {"train_step": times}, "counters": counters,
           "trace": the_trace,
           "context": harness.context(cell, family, devices, rehearsal)}
    end_to_end = {
        "train_tokens_per_s_per_chip": {"value": tokens_per_s_per_chip,
                                        "unit": "tokens/s/chip"},
        "setup_s": {"value": setup_s, "unit": "s"}}
    compared = {"first_step_loss_diff": {"value": abs(loss0 - ref_loss),
                                         "limit": reference.TRAIN_LOSS_TOL}}
    return {"checks": checks, "compared": compared,
            "attempted": len(all_losses),
            "failed": finite.count(False), "end_to_end": end_to_end,
            "obs": obs, "devices": devices, "memory_peak": memory_peak}
