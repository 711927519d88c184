#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the trainer and the server still
start on the chip, through the entry points a user calls.

    python chip_smoke.py              one TPU chip: train phase, then serve phase
    python chip_smoke.py --multichip  four chips: the ZeRO-3 dp=4 train phase and
                                      its one-chip twin, and no other phase

Train phase (the README contract): write a ``ds_config.json``, call
``deepspeed_tpu.initialize(model=..., config="ds_config.json",
example_batch=...)`` on gpt2-1.3b at its published width AND depth (H 2048,
24 layers, 16 heads, vocab 50257; seq 1024, remat "dots", fused loss), ZeRO
stage 3, pure-bf16 state, and take a few ``engine.train_batch`` steps on one
repeated seeded batch. Serve phase, same widths:
``init_inference(model, cfg, model_parameters=params).serve()`` answers
eight greedy requests of mixed length submitted while the loop steps, half
of them sharing a two-block prefix; then a short int8-KV + int8-weight
pass. Weights are random (the engine's seeded init, four train steps old).

Every check that fails raises: there is no ``try/except`` round a phase, so
the exit code is non-zero and the final line is not printed. Without a TPU
the script exits non-zero before any phase. It is ONE process (a chip
belongs to one process at a time) and starts no other.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

The phase functions take their sizes as an argument and this file holds
one set of them, the full width; no check here can be turned off.
``scripts/smoke_rehearsal.py`` drives the same functions on the CPU with
sizes of its own and never prints the ``ok`` line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import statistics
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One run's sizes; the defaults are the full width this script runs."""
    preset: str = "gpt2-1.3b"
    model_kw: Tuple[Tuple[str, object], ...] = ()
    seq: int = 1024
    micro: int = 2                      # rows per chip per micro-step
    rows: int = 4                       # global batch rows, one chip (gas 2)
    rows_multichip: int = 8             # --multichip: dp 4 x micro 2 x gas 1,
    #                                     and the one-chip twin at gas 4
    steps: int = 4
    lr: float = 1e-4
    block_size: int = 32
    pool_blocks: int = 1024             # ~6.4 GB of bf16 KV beside the weights
    max_batch: int = 8
    # (prompt length, shares the two-block prefix?) — suffix buckets stay
    # few so prefill compiles stay few
    prompts: Tuple[Tuple[int, bool], ...] = (
        (128, True), (64, False), (192, True), (256, False),
        (320, True), (512, False), (128, True), (128, False))
    new_tokens: Tuple[int, ...] = (32, 16, 24, 32, 16, 24, 32, 16)
    int8_prompts: Tuple[int, ...] = (64, 128, 64, 128)
    int8_new_tokens: int = 8


FULL = Sizes()

#: --multichip: |loss(4 chips) - loss(1 chip)| per step, same seeded batch.
#: Both runs hold bf16 params and accumulate bf16 grads; the reduction order
#: differs (reduce-scatter over 4 vs 4 sequential micro-steps)
MULTICHIP_LOSS_TOL = 0.05
#: --multichip: the compute params ZeRO-3 keeps whole BY RULE besides those
#: under stage3_param_persistence_threshold. Every other parameter, and
#: every optimizer-state leaf, must be split over all the chips.
WHOLE_BY_RULE = {
    "['wte']['embedding']":
        "vocab 50257 has no factor 4, and the policy never splits a compute "
        "param's feature dim (runtime/zero/stages.py insert_zero_axes, "
        "avoid_last); its Adam moments ARE split",
}


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: non-zero exit, no ``ok`` line."""
    print(("  ok   " if ok else "  FAIL ") + what, flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def kernel_calls(hlo_text: str) -> List[str]:
    """op_name of every Pallas (Mosaic) custom call in a compiled program —
    the ``jax.named_scope`` each ``pallas_call`` sits in names the kernel."""
    out = []
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line and "custom-call(" in line:
            m = re.search(r'op_name="([^"]+)"', line)
            out.append(m.group(1) if m else "?")
    return out


class CompileWatch:
    """Counts backend compiles and persistent-cache hits through
    ``jax.monitoring`` (the listeners stay registered; one per process)."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            self.secs += secs
            self.compiles += 1

    def _ev(self, event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snapshot(self):
        return (self.secs, self.compiles, self.hits, self.misses)

    def since(self, snap) -> Dict[str, float]:
        s, c, h, m = snap
        return {"compile_s": round(self.secs - s, 2),
                "compiles": self.compiles - c,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


def peak_bytes(dev) -> Optional[int]:
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ train


def train_phase(sz: Sizes, workdir: str, watch: CompileWatch, *,
                rows: int, devices: Optional[Sequence] = None,
                label: str = "train"):
    """A few ZeRO-3 steps through ``deepspeed_tpu.initialize`` on the mesh of
    ``devices`` (None: the engine's default, every chip JAX reports).
    Returns (engine, result dict); the caller frees the engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, fused_loss_passthrough
    from deepspeed_tpu.parallel.mesh import BATCH_AXES, MeshManager

    n_dev = len(devices) if devices is not None else len(jax.devices())
    gas = rows // (sz.micro * n_dev)
    check(gas >= 1 and gas * sz.micro * n_dev == rows,
          f"[{label}] batch triple: rows {rows} = micro {sz.micro} x gas "
          f"{gas} x dp {n_dev}")
    model, cfg = build_model(sz.preset, max_seq_len=sz.seq, remat=True,
                             remat_policy="dots", fused_loss=True,
                             **dict(sz.model_kw))
    print(f"[{label}] model {sz.preset}: hidden {cfg.hidden_size}, layers "
          f"{cfg.num_layers}, heads {cfg.num_heads}, vocab {cfg.vocab_size}, "
          f"seq {sz.seq}; {cfg.num_params() / 1e9:.3f}B params; ZeRO-3 over "
          f"dp={n_dev}, micro {sz.micro} x gas {gas}", flush=True)
    cfg_path = os.path.join(workdir, f"ds_config_{label}.json")
    with open(cfg_path, "w") as f:
        json.dump({
            "train_batch_size": rows,
            "train_micro_batch_size_per_gpu": sz.micro,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": sz.lr}},
            "bf16": {"enabled": True, "master_weights": False},
            "data_types": {"grad_accum_dtype": "bf16"},
            "zero_optimization": {"stage": 3},
            "steps_per_print": 10_000,
        }, f, indent=1)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(rows, sz.seq), dtype=np.int32)}
    kw = {}
    if devices is not None:
        kw["mesh_manager"] = MeshManager(devices=list(devices))
    snap = watch.snapshot()
    t0 = time.perf_counter()
    engine, *_ = ds.initialize(model=model, config=cfg_path,
                               loss_fn=fused_loss_passthrough,
                               example_batch=batch, **kw)
    jax.block_until_ready(engine.state)
    init_s = time.perf_counter() - t0
    init_compile = watch.since(snap)

    losses, times = [], []
    snap = watch.snapshot()
    for _ in range(sz.steps):
        t0 = time.perf_counter()
        metrics = engine.train_batch(batch)
        jax.block_until_ready((engine.state, metrics))
        times.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    step_compile = watch.since(snap)
    n_step_compiles = engine._train_step._cache_size()
    steady = statistics.median(times[1:])
    print(f"[{label}] initialize {init_s:.1f}s (compile "
          f"{init_compile['compile_s']}s); first train_batch "
          f"{times[0]:.2f}s of which step compile "
          f"{step_compile['compile_s']}s ({step_compile}); later steps "
          f"{[round(t, 3) for t in times[1:]]}s, median {steady:.3f}s = "
          f"{rows * sz.seq / steady:.0f} tokens/s", flush=True)
    print(f"[{label}] losses {[round(x, 4) for x in losses]}", flush=True)
    check(all(np.isfinite(losses)), f"[{label}] every loss finite")
    check(losses[-1] < losses[0],
          f"[{label}] loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(n_step_compiles == 1,
          f"[{label}] exactly one compile of the step "
          f"(_cache_size {n_step_compiles})")

    # the compiled step's text: the SAME program through the AOT door (the
    # tests' own audit idiom) — with the persistent cache on, a cache hit
    gas_sh = NamedSharding(engine.mesh, P(None, BATCH_AXES))
    micros = jax.tree.map(
        lambda x: jax.device_put(
            jnp.asarray(x).reshape((gas, x.shape[0] // gas) + x.shape[1:]),
            gas_sh), batch)
    snap = watch.snapshot()
    t0 = time.perf_counter()
    text = engine._train_step.lower(
        engine.state, micros, engine.next_rng(),
        engine._current_lr()).compile().as_text()
    print(f"[{label}] step compiled again for its text in "
          f"{time.perf_counter() - t0:.1f}s: {watch.since(snap)}", flush=True)
    calls = kernel_calls(text)
    print(f"[{label}] Pallas custom calls in the compiled step: "
          f"{sorted(set(calls))}", flush=True)
    for scope in ("flash_attention_fwd", "flash_attention_bwd_dq",
                  "flash_attention_bwd_dkv"):
        check(any(scope in c for c in calls),
              f"[{label}] compiled step holds the {scope} kernel")
    peaks = [peak_bytes(d) for d in engine.mesh.devices.flat]
    print(f"[{label}] peak_bytes_in_use per device: {peaks}", flush=True)
    check(all(peaks), f"[{label}] every device reports its peak memory")
    return engine, {"losses": losses, "step_s": steady, "text": text,
                    "peaks": peaks}


def free_engine(engine) -> None:
    """Drop every device buffer a train engine holds (the next phase needs
    the HBM)."""
    import jax
    for leaf in jax.tree.leaves(engine.state):
        if hasattr(leaf, "delete"):
            leaf.delete()
    engine.state = None
    engine._train_step = None
    gc.collect()
    jax.clear_caches()


# ------------------------------------------------------------------ serve


def _make_requests(sz: Sizes, vocab: int):
    import numpy as np
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, vocab, size=2 * sz.block_size).tolist()
    prompts = []
    for n, shared in sz.prompts:
        body = rng.integers(1, vocab, size=n).tolist()
        prompts.append(prefix + body[len(prefix):] if shared else body)
    return prompts


def _serve(srv, prompts, new_tokens, label: str, timeout_s: float = 600.0):
    """Submit from a second thread WHILE the main thread steps the loop."""
    from deepspeed_tpu.serving.scheduler import FINISHED
    reqs: List = [None] * len(prompts)

    def submitter():
        for i, (p, n) in enumerate(zip(prompts, new_tokens)):
            reqs[i] = srv.submit(p, max_new_tokens=n)
            time.sleep(0.02)

    th = threading.Thread(target=submitter, name="smoke-submit", daemon=True)
    t0 = time.perf_counter()
    th.start()
    decode_only = []            # loop steps that ran no prefill
    while th.is_alive() or not srv.idle:
        if time.perf_counter() - t0 > timeout_s:
            raise SystemExit(f"chip_smoke: [{label}] serving loop did not "
                             f"drain in {timeout_s}s")
        if srv.idle:
            time.sleep(0.005)
            continue
        pre, t1 = srv.stats["prefill_tokens"], time.perf_counter()
        srv.step()              # ends in a device-to-host fetch of the tokens
        if srv.stats["prefill_tokens"] == pre:
            decode_only.append(time.perf_counter() - t1)
    th.join()
    wall = time.perf_counter() - t0
    done = sum(r.state == FINISHED for r in reqs)
    toks = sum(len(r.output_tokens) for r in reqs)
    med = statistics.median(decode_only) if decode_only else float("nan")
    print(f"[{label}] {done}/{len(reqs)} requests finished, {toks} tokens "
          f"in {wall:.1f}s wall (prefill compiles included), "
          f"{srv.steps} loop steps; {len(decode_only)} decode-only steps, "
          f"median {med * 1e3:.1f} ms (the first holds the decode compile);"
          f" stats {srv.stats}", flush=True)
    check(done == len(reqs), f"[{label}] all {len(reqs)} requests finished")
    check(all(len(r.output_tokens) == n for r, n in zip(reqs, new_tokens)),
          f"[{label}] every request produced its max_new_tokens")
    return [list(r.output_tokens) for r in reqs]


def _decode_text(srv) -> str:
    """Compiled text of the engine's ONE decode step (the one int32 buffer
    ``ServingEngine._decode_step`` passes: the lanes' state as it stands)."""
    return srv._decode_fn.lower(srv.params, srv.pools, srv._lanes.buf,
                                srv._dec_out, srv._pre_out
                                ).compile().as_text()


def _free_server(srv) -> None:
    import jax
    srv.close()
    for leaf in jax.tree.leaves(srv.pools):
        leaf.delete()
    gc.collect()


def _logit_gap(ie, prefix: List[int], tok_a: int, tok_b: int):
    """Logits of the two disputed tokens after ``prefix``, by the model's own
    full forward (a third program: flash prefill, no cache)."""
    import numpy as np
    logits = np.asarray(ie.forward({"input_ids": np.asarray([prefix],
                                                            np.int32)}),
                        np.float32)[0, -1]
    return float(logits[tok_a]), float(logits[tok_b])


def _kernel_parity(cfg, sz: Sizes) -> None:
    """Op-level parity ON THE DEVICE of the two int8 kernels against the
    repo's jnp oracles, at the serving widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops.attention import paged_attention
    from deepspeed_tpu.ops.pallas.quant_matmul import (
        pack_kernel, quant_matmul, quant_matmul_reference)
    from deepspeed_tpu.quant_format import kv_quantize

    rng = np.random.default_rng(2)
    nh, hd, bs = cfg.num_heads, cfg.head_dim, sz.block_size
    B, nbk, nb = sz.max_batch, 8, 8 * sz.max_batch + 1
    kp = jnp.asarray(rng.standard_normal((nh, nb, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nh, nb, bs, hd)), jnp.float32)
    (kq, ks), (vq, vs) = kv_quantize(kp), kv_quantize(vp)
    bt = jnp.asarray((rng.permutation(nb - 1)[:B * nbk] + 1)
                     .reshape(B, nbk).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, nbk * bs + 1, size=B), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, nh, 1, hd)), jnp.bfloat16)
    run = lambda impl: jax.jit(lambda *a: paged_attention(
        *a[:3], bt, lens, k_scale=a[3], v_scale=a[4], impl=impl))(
            q, kq, vq, ks, vs)
    got, want = (np.asarray(run(i), np.float32) for i in ("auto",
                                                          "reference"))
    err = float(np.max(np.abs(got - want)))
    check(err <= 3e-2, f"[int8] paged int8 kernel == gather reference on "
          f"the device (max abs err {err:.4f}, outputs O(1))")

    H = cfg.hidden_size
    for K, N in ((H, 3 * H), (H, 4 * H), (4 * H, H)):
        w = jnp.asarray(rng.standard_normal((K, N)) * 0.02, jnp.float32)
        x = jnp.asarray(rng.standard_normal((B, 1, K)), jnp.bfloat16)
        wq, sc = pack_kernel(w)
        got = np.asarray(jax.jit(quant_matmul)(x, wq, sc), np.float32)
        want = np.asarray(quant_matmul_reference(x, wq, sc), np.float32)
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        check(err <= 2e-2 * scale,
              f"[int8] quant_matmul {K}x{N} == jnp reference on the device "
              f"(max abs err {err:.4f} of {scale:.2f})")


def serve_phase(sz: Sizes, workdir: str, params, watch: CompileWatch) -> None:
    """Serve through ``init_inference(...).serve()``: the kernel-routed
    server, its reference-routed twin, then the int8 tier."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    model_kw = dict(sz.model_kw)
    model, cfg = build_model(sz.preset, max_seq_len=sz.seq, **model_kw)
    twin_model, _ = build_model(sz.preset, max_seq_len=sz.seq,
                                attention_impl="reference", **model_kw)
    serving = {"block_size": sz.block_size, "pool_blocks": sz.pool_blocks,
               "max_batch": sz.max_batch}
    cfg_path = os.path.join(workdir, "inference_config.json")
    with open(cfg_path, "w") as f:
        json.dump({"dtype": "bfloat16", "serving": serving}, f, indent=1)
    prompts = _make_requests(sz, cfg.vocab_size)
    pool_gb = (2 * cfg.num_layers * cfg.kv_heads * cfg.head_dim
               * sz.pool_blocks * sz.block_size * 2) / 1e9
    print(f"[serve] {len(prompts)} greedy requests, prompt lengths "
          f"{[len(p) for p in prompts]}, new tokens {list(sz.new_tokens)}; "
          f"pool {sz.pool_blocks} x {sz.block_size} tokens = {pool_gb:.2f} "
          f"GB bf16, max_batch {sz.max_batch}", flush=True)

    snap = watch.snapshot()
    ie = ds.init_inference(model, cfg_path, model_parameters=params)
    srv = ie.serve()
    outs = _serve(srv, prompts, sz.new_tokens, "serve")
    print(f"[serve] compiles so far: {watch.since(snap)}", flush=True)
    check(srv._decode_fn._cache_size() == 1,
          "[serve] one decode compile (_decode_fn._cache_size() == 1)")
    check(srv.stats["prefix_hit_tokens"] > 0,
          f"[serve] prefix_hit_tokens {srv.stats['prefix_hit_tokens']} > 0")
    calls = kernel_calls(_decode_text(srv))
    print(f"[serve] Pallas custom calls in the decode step: "
          f"{sorted(set(calls))}", flush=True)
    check(any("paged_attention" in c for c in calls),
          "[serve] compiled decode step holds the paged kernel")
    peak = peak_bytes(jax.devices()[0])
    print(f"[serve] peak_bytes_in_use {peak} (the process's peak so far, "
          "train phase included)", flush=True)
    _free_server(srv)

    # the twin: same dtype, same weights, same requests in the same order —
    # only the decode attention differs (gather reference, no kernel)
    twin_ie = ds.init_inference(twin_model, cfg_path, model_parameters=params)
    twin = twin_ie.serve()
    twin_outs = _serve(twin, prompts, sz.new_tokens, "serve-twin")
    check(not kernel_calls(_decode_text(twin)),
          "[serve-twin] the twin's compiled decode step holds NO Pallas "
          "kernel")
    _free_server(twin)
    parted = [i for i, (a, b) in enumerate(zip(outs, twin_outs)) if a != b]
    for i in parted:
        # the diagnostic of a failure: where the two part, and how far
        # apart the model's own full forward puts the two tokens
        a, b = outs[i], twin_outs[i]
        pos = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        la, lb = _logit_gap(ie, prompts[i] + a[:pos], a[pos], b[pos])
        print(f"[serve] request {i}: kernel and twin part at generated "
              f"position {pos}: token {a[pos]} (logit {la:.4f}) vs "
              f"{b[pos]} (logit {lb:.4f}); gap {abs(la - lb):.4f}",
              flush=True)
    check(not parted,
          f"[serve] outputs equal the reference-routed twin, token for "
          f"token: {len(outs) - len(parted)}/{len(outs)} requests")

    # ---- the int8 tier: int8 KV pool + blockwise-int8 weights ------------
    _kernel_parity(cfg, sz)
    q_path = os.path.join(workdir, "inference_config_int8.json")
    with open(q_path, "w") as f:
        json.dump({"dtype": "bfloat16",
                   "serving": dict(serving, kv_cache_dtype="int8",
                                   weight_dtype="int8")}, f, indent=1)
    rng = np.random.default_rng(3)
    q_prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                 for n in sz.int8_prompts]
    q_new = [sz.int8_new_tokens] * len(q_prompts)
    qsrv = ds.init_inference(model, q_path, model_parameters=params).serve()
    check(str(qsrv.pools["k"].dtype) == "int8"
          and str(qsrv.params["blocks"]["attn_qkv"]["kernel"].dtype)
          == "int8", "[int8] pool and packed weights are int8")
    q_outs = _serve(qsrv, q_prompts, q_new, "int8")
    check(all(0 <= t < cfg.vocab_size for o in q_outs for t in o),
          "[int8] every token is in the vocabulary")
    check(qsrv._decode_fn._cache_size() == 1, "[int8] one decode compile")
    calls = kernel_calls(_decode_text(qsrv))
    print(f"[int8] Pallas custom calls in the decode step: "
          f"{sorted(set(calls))}", flush=True)
    check(any("paged_attention" in c for c in calls),
          "[int8] compiled decode step holds the int8 paged kernel")
    check(any("quant_matmul" in c for c in calls),
          "[int8] compiled decode step holds the int8 matmul kernel")
    _free_server(qsrv)


# -------------------------------------------------------------- multichip


def _shard_report(tree, devices, label: str,
                  whole_ok=lambda name, leaf: False) -> None:
    """Every array leaf lies on len(devices) distinct devices with
    1/len(devices) of its bytes on each, except those ``whole_ok`` names —
    the leaves the ZeRO-3 policy keeps whole by a stated rule, which are
    listed."""
    import jax
    n = len(devices)
    split_b = 0
    whole, wrong = [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not hasattr(leaf, "addressable_shards") or leaf.ndim == 0:
            continue
        name = jax.tree_util.keystr(path)
        shards = leaf.addressable_shards
        if {s.device for s in shards} == set(devices) and len(shards) == n \
                and all(s.data.nbytes * n == leaf.nbytes for s in shards):
            split_b += leaf.nbytes
        elif whole_ok(name, leaf):
            whole.append((name, leaf.shape))
        else:
            wrong.append((name, leaf.shape,
                          [(str(s.device), s.data.shape) for s in shards]))
    print(f"[{label}] {split_b / 1e9:.3f} GB in leaves with a quarter of "
          f"their bytes on each of {n} distinct devices; whole by rule: "
          f"{whole}", flush=True)
    check(split_b > 0 and not wrong,
          f"[{label}] every leaf not whole by rule is split over {n} "
          f"distinct devices, 1/{n} of its bytes each"
          + (f" — NOT: {wrong}" if wrong else ""))


def multichip_phase(sz: Sizes, workdir: str, watch: CompileWatch) -> None:
    """ZeRO-3 over dp=4 and its comparison: the SAME seeded batch of
    ``rows_multichip`` rows, split four ways (micro 2 x dp 4 x gas 1) on the
    mesh of all four chips, against micro 2 x gas 4 on a mesh of one."""
    import jax
    devs = jax.devices()
    check(len(devs) == 4, f"--multichip needs 4 devices (JAX reports "
          f"{len(devs)})")
    engine, four = train_phase(sz, workdir, watch, rows=sz.rows_multichip,
                               devices=devs, label="dp4")
    thr = engine.config.zero_optimization.param_persistence_threshold
    print(f"[dp4] whole by rule: params under the persistence threshold "
          f"({thr} elements), and {WHOLE_BY_RULE}", flush=True)
    _shard_report(engine.state.params, devs, "dp4 params",
                  lambda name, leaf: leaf.size < thr or name in WHOLE_BY_RULE)
    _shard_report(engine.state.opt_state, devs, "dp4 optimizer state")
    n_ag = len(re.findall(r"\sall-gather(-start)?\(", four["text"]))
    # the TPU compiler writes a reduce-scatter either as the op or as a
    # kCustom fusion named all-reduce-scatter (all-reduce + slice, fused)
    n_rs = len(re.findall(r"\sreduce-scatter(-start)?\(", four["text"])) \
        + len(re.findall(r"calls=%all-reduce-scatter", four["text"]))
    n_ar = len(re.findall(r"\sall-reduce(-start)?\(", four["text"]))
    n_a2a = len(re.findall(r"\sall-to-all(-start)?\(", four["text"]))
    print(f"[dp4] collectives in the compiled step: all-gather {n_ag}, "
          f"reduce-scatter (op or all-reduce-scatter fusion) {n_rs}, "
          f"all-reduce {n_ar}, all-to-all {n_a2a}", flush=True)
    check(n_ag > 0, "[dp4] compiled step holds all-gather")
    check(n_rs > 0, "[dp4] compiled step holds reduce-scatter")
    free_engine(engine)

    engine, one = train_phase(sz, workdir, watch, rows=sz.rows_multichip,
                              devices=devs[:1], label="dp1")
    free_engine(engine)
    diffs = [abs(a - b) for a, b in zip(four["losses"], one["losses"])]
    print(f"[multichip] |loss dp4 - loss dp1| per step: "
          f"{[round(d, 4) for d in diffs]}", flush=True)
    check(max(diffs) <= MULTICHIP_LOSS_TOL,
          f"[multichip] losses agree within {MULTICHIP_LOSS_TOL} (bf16 "
          "state, different reduction order)")
    check(max(four["peaks"]) < one["peaks"][0],
          f"[multichip] per-device peak on four chips {max(four['peaks'])} "
          f"< one-chip peak {one['peaks'][0]}")
    print(f"[multichip] step median: dp4 {four['step_s']:.3f}s, dp1 "
          f"{one['step_s']:.3f}s (same {sz.rows_multichip}-row batch)",
          flush=True)


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: ZeRO-3 dp=4 train phase and its "
                         "one-chip twin, no other phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()                # first touch of JAX: takes the chip
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "unknown"
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__} / libtpu "
          f"{libtpu}; platform {devs[0].platform}, device_kind "
          f"{devs[0].device_kind!r}, {len(devs)} device(s)", flush=True)
    if devs[0].platform != "tpu":
        print("chip_smoke: JAX found no TPU; this script measures nothing "
              "on any other device", file=sys.stderr, flush=True)
        return 1
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    watch = CompileWatch()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.multichip:
            multichip_phase(FULL, workdir, watch)
        else:
            check(len(devs) == 1, f"the default run needs exactly one chip "
                  f"(JAX reports {len(devs)}); four chips: --multichip")
            engine, _ = train_phase(FULL, workdir, watch, rows=FULL.rows)
            params = engine.state.params
            engine.state = engine.state.replace(params=None)
            free_engine(engine)
            serve_phase(FULL, workdir, params, watch)
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.0f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
