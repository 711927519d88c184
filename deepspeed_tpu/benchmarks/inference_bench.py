"""Inference latency benchmarks — prefill/forward + generation sweeps,
plus a Poisson-arrival serving-load leg.

Capability parity with the reference's ``benchmarks/inference`` (bert/gpt
latency scripts): measures forward latency over batch/seq and per-token
decode latency with the KV-cache generate loop, on the current backend.
``--poisson`` drives the round-8 continuous-batching serving loop
(deepspeed_tpu/serving/) with open-loop Poisson arrivals at fixed request
rates, reporting tokens/s/chip and p50/p99 request latency — the
serving-SLO counterpart of the closed-loop sweeps above, with a
machine-readable ``inference_bench poisson: {json}`` line in the PR-7
dryrun-timings style. ``--poisson --fleet N`` (round 11) drives the
supervised N-replica fleet instead and injects a replica kill mid-run,
printing a ``poisson_fleet`` row with tokens/s before/during/after the
loss — the serving tier's resilience number.

Round 12 adds the newest-recorded-sweep regression convention (the
COMMBENCH / dryrun-timings pattern): ``--record PATH`` writes the
serving rows as JSON (commit as ``SERVEBENCH_rNN.json``), every
``--poisson`` run compares its rows against the newest recorded sweep in
``--baseline-dir`` (same device count), >2x p50 latency or <1/2 the
recorded tokens/s prints a LOUD regression, and
``DSTPU_SERVE_BENCH_GATE=1`` makes it fatal. ``--chunk N`` arms chunked
prefill for the serving rows (mode column records it).

Round 18 adds the process-placement leg: ``--fleet N --placement
process`` drives the process-per-replica fleet (serving/procfleet.py —
worker processes over the transfer fabric) and SIGKILLs a replica
PROCESS at 1/3 completion, printing a ``poisson_fleet_proc`` row with
tokens/s before/during/after the real process death; the row's
``heartbeat_dir`` is live for ``dstpu health`` (per-process replica
rows with pid/queue/pool gauges).

Round 17 adds the quantized-compute legs: ``--kv-dtype int8`` serves
from the int8 KV pool (in-kernel dequant) and ``--weight-dtype int8``
from blockwise weight-only int8 matmuls; the rows carry ``kv_dtype`` /
``weight_dtype`` columns and the regression key includes them, so the
bf16 and int8 tiers baseline independently.

    python -m deepspeed_tpu.benchmarks.inference_bench \
        [--preset gpt2-125m] [--batches 1,8] [--seqs 128,1024] [--new 64]
    python -m deepspeed_tpu.benchmarks.inference_bench --poisson \
        [--rates 2,8] [--requests 64] [--prompt 128] [--new 64] \
        [--fleet 3] [--no-fail-replica] [--slow-replica [--slow-ms 250]] \
        [--chunk 0] [--record PATH]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: >2x recorded p50 (or < recorded tokens/s / 2) = loud regression
SERVE_REGRESSION_FACTOR = 2.0


def _timed(fn, iters=5):
    jax.block_until_ready(fn())          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def run(preset: str, batches: List[int], seqs: List[int], new_tokens: int):
    from ..models import build_model
    from ..models.generation import generate
    rows = []
    for B in batches:
        for S in seqs:
            model, cfg = build_model(preset, max_seq_len=S + new_tokens)
            ids = jnp.asarray(np.random.default_rng(0).integers(
                0, cfg.vocab_size, (B, S)))
            # per-(B,S) sweep point builds a new model: a fresh trace per
            # point is inherent to the sweep
            # graftlint: disable=TPU002
            params = jax.jit(lambda r: model.init(r, {"input_ids": ids})
                             ["params"])(jax.random.PRNGKey(0))
            # graftlint: disable=TPU002
            fwd = jax.jit(lambda p, i: model.apply({"params": p},
                                                   {"input_ids": i}))
            t_fwd = _timed(lambda: fwd(params, ids))
            t_gen = _timed(
                lambda: generate(cfg, params, ids, new_tokens), iters=3)
            per_tok = (t_gen - t_fwd) / new_tokens
            rows.append({
                "preset": preset, "batch": B, "seq": S,
                "forward_ms": round(t_fwd * 1e3, 2),
                "generate_ms": round(t_gen * 1e3, 2),
                "ms_per_token": round(per_tok * 1e3, 3),
                "tokens_per_sec": round(B / max(per_tok, 1e-9), 1)})
            print(rows[-1])
    return rows


def run_ragged(preset: str, batch: int, max_seq: int, new_tokens: int):
    """Batched serving with MIXED context lengths: one left-padded ragged
    batch (per-sample positions/masks) vs the sum of per-sample runs —
    the batching win the round-3 decode bench (B=1 only) never measured."""
    from ..models import build_model
    from ..models.generation import generate
    model, cfg = build_model(preset, max_seq_len=max_seq + new_tokens)
    rng = np.random.default_rng(0)
    lens = [int(x) for x in
            rng.integers(max_seq // 4, max_seq + 1, size=batch)]
    ids = np.zeros((batch, max_seq), np.int64)
    mask = np.zeros((batch, max_seq), np.int64)
    for i, L in enumerate(lens):
        ids[i, max_seq - L:] = rng.integers(1, cfg.vocab_size, size=L)
        mask[i, max_seq - L:] = 1
    ids_j, mask_j = jnp.asarray(ids), jnp.asarray(mask)
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: model.init(r, {"input_ids": ids_j})
                     ["params"])(jax.random.PRNGKey(0))
    t_batch = _timed(lambda: generate(cfg, params, ids_j, new_tokens,
                                      attention_mask=mask_j), iters=3)
    t_seq = 0.0
    probe = lens[:4]                       # sample of per-sample runs
    for i, L in enumerate(probe):
        one = jnp.asarray(ids[i, max_seq - L:][None])
        t_seq += _timed(lambda: generate(cfg, params, one, new_tokens),
                        iters=3)
    t_seq *= batch / len(probe)            # extrapolate to full batch
    row = {"preset": preset, "batch": batch, "ctx_lens": lens,
           "new_tokens": new_tokens,
           "ragged_batch_s": round(t_batch, 3),
           "sequential_est_s": round(t_seq, 3),
           "batching_speedup": round(t_seq / max(t_batch, 1e-9), 2),
           "tokens_per_sec": round(batch * new_tokens / t_batch, 1)}
    print(row)
    return row


def run_poisson(preset: str, rate: float, num_requests: int,
                prompt_len: int, new_tokens: int,
                serving: Optional[dict] = None, seed: int = 0,
                model_kwargs: Optional[dict] = None) -> dict:
    """Open-loop Poisson load against the continuous-batching serving loop.

    Requests arrive at exponential inter-arrival times (rate = requests/s)
    regardless of server progress — the open-loop regime where queueing
    delay shows up honestly (a closed loop would self-throttle). Reports
    per-request latency (arrival -> completion, so queue wait counts)
    p50/p99 and steady-state tokens/s/chip, plus the machine-readable
    line the regression tooling greps::

        inference_bench poisson: {"rate": 8.0, "p50_s": ..., ...}
    """
    from ..models import build_model
    from ..serving.engine import ServingEngine
    model, cfg = build_model(preset, max_seq_len=prompt_len + new_tokens,
                             **(model_kwargs or {}))
    rng = np.random.default_rng(seed)
    ids0 = rng.integers(0, cfg.vocab_size, (1, prompt_len))
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: model.init(r, {"input_ids": ids0})
                     ["params"])(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, serving=serving)

    # a shared "system prompt" prefix (2 blocks) exercises the prefix
    # cache the way production traffic does; suffixes vary per request
    shared = 2 * eng.block_size
    sys_prompt = rng.integers(1, cfg.vocab_size, size=min(shared,
                                                          prompt_len // 2))
    prompts = []
    for _ in range(num_requests):
        suffix_len = max(1, prompt_len - len(sys_prompt))
        prompts.append(list(sys_prompt)
                       + list(rng.integers(1, cfg.vocab_size,
                                           size=suffix_len)))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=num_requests))

    # warm the compile caches outside the timed window (serving latency,
    # not XLA latency, is measured): warm A compiles the FULL-prompt
    # prefill bucket and seeds the prefix cache; warm B, sharing the
    # system prompt, takes the prefix hit and compiles the SUFFIX bucket
    # every timed request will actually use — plus the one decode step
    def _mk_prompt():
        suffix_len = max(1, prompt_len - len(sys_prompt))
        return (list(sys_prompt)
                + list(rng.integers(1, cfg.vocab_size, size=suffix_len)))
    for _ in range(2):
        warm = eng.submit(_mk_prompt(), 2)
        eng.run_until_idle()
        assert warm.done

    reqs = []
    lat: List[float] = []
    t0 = time.perf_counter()
    next_i = 0
    while len(lat) < num_requests:
        now = time.perf_counter() - t0
        while next_i < num_requests and arrivals[next_i] <= now:
            i = next_i
            reqs.append((eng.submit(prompts[i], new_tokens), arrivals[i]))
            next_i += 1
        if eng.idle:
            if next_i < num_requests:
                time.sleep(max(arrivals[next_i] - (time.perf_counter() - t0),
                               0.0))
            continue
        eng.step()
        done_now = time.perf_counter() - t0
        still = []
        for req, arr in reqs:
            if req.done:
                lat.append(done_now - arr)
            else:
                still.append((req, arr))
        reqs = still
    wall = time.perf_counter() - t0
    n_chips = jax.device_count()
    gen_tokens = num_requests * new_tokens
    row = {
        "mode": "poisson",
        "preset": preset, "rate": float(rate), "requests": num_requests,
        "prompt": prompt_len, "new_tokens": new_tokens,
        "chunk": int((serving or {}).get("prefill_chunk_tokens", 0)),
        "kv_dtype": (serving or {}).get("kv_cache_dtype"),
        "weight_dtype": (serving or {}).get("weight_dtype"),
        "wall_s": round(wall, 3),
        "p50_s": round(float(np.percentile(lat, 50)), 4),
        "p99_s": round(float(np.percentile(lat, 99)), 4),
        "mean_s": round(float(np.mean(lat)), 4),
        "tokens_per_s": round(gen_tokens / wall, 1),
        "tokens_per_s_per_chip": round(gen_tokens / wall / n_chips, 1),
        "prefix_hit_tokens": eng.stats["prefix_hit_tokens"],
        "n_chips": n_chips,
    }
    eng.close()      # loop exit stamps EXIT if a heartbeat is attached
    print("inference_bench poisson: " + json.dumps(row))
    return row


def run_poisson_fleet(preset: str, rate: float, num_requests: int,
                      prompt_len: int, new_tokens: int, replicas: int = 2,
                      serving: Optional[dict] = None,
                      fail_replica: bool = True, seed: int = 0,
                      slow_replica: bool = False, slow_ms: int = 250,
                      model_kwargs: Optional[dict] = None) -> dict:
    """Poisson load against the supervised multi-replica fleet
    (serving/fleet.py), with an optional failure-injection leg: once a
    third of the requests have completed, ``serve.replica_kill`` takes
    out the last replica mid-decode, and the row records tokens/s
    BEFORE / DURING / AFTER the loss — the resilience number ROADMAP
    item 1(c) asks the first serving BENCH entry to carry. "during"
    spans kill -> requeue-complete (detection + teardown + requeue +
    replay); "after" is the recovered fleet. Machine-readable row::

        inference_bench poisson_fleet: {"rate": ..., "replicas": ...,
            "tps_before": ..., "tps_during": ..., "tps_after": ...,
            "requeues": ..., "deaths": ..., ...}

    ``slow_replica`` (round 15, the straggler defense) injects a
    DEGRADED replica instead of a dead one: the keyed
    ``serve.replica_slow`` failpoint sleeps ``slow_ms`` per worker
    iteration (times=0, forever) so the victim keeps serving — slowly —
    until the FleetSupervisor's relative-slowness detector DRAINS it
    (requeue + warmed restart). The row's mode is
    ``poisson_fleet_slow``; ``drained_at_s`` is the detection instant
    and ``recovered_at_s`` the warmed restart, so the degraded window
    tokens/s is directly readable."""
    from ..models import build_model
    from ..serving.fleet import ServingFleet
    from ..testing import chaos
    model, cfg = build_model(preset, max_seq_len=prompt_len + new_tokens,
                             **(model_kwargs or {}))
    rng = np.random.default_rng(seed)
    ids0 = rng.integers(0, cfg.vocab_size, (1, prompt_len))
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: model.init(r, {"input_ids": ids0})
                     ["params"])(jax.random.PRNGKey(0))
    scfg = dict(serving or {})
    fleet_cfg = dict(scfg.pop("fleet", {}))
    fleet_cfg.setdefault("replicas", replicas)
    # snappy recovery for the bench window (production defaults are lazier)
    fleet_cfg.setdefault("poll_interval", 0.05)
    fleet_cfg.setdefault("heartbeat_interval", 0.05)
    if slow_replica:
        # the drain needs the detector on. Windows run at poll cadence,
        # so consecutive windows are CORRELATED samples of the same
        # rolling gauge — strike_window must be wide enough to span a
        # gauge turnover, and rel_threshold generous: in-process
        # replicas on a shared host are anti-correlated by construction
        # (one replica's step starves the other), which is noise a
        # chip-per-replica deployment doesn't have
        fleet_cfg.setdefault("straggler", {
            "enabled": True, "warmup": 3, "strike_window": 4,
            "cooldown": 20, "rel_threshold": 2.5})
        # the SILENCE detector must not race the straggler drain: a
        # degraded replica still stamps (slowly), and on a starved bench
        # host the default 10s would flap healthy replicas long before
        # the relative detector earns its verdict
        fleet_cfg.setdefault("heartbeat_timeout", 300.0)
        # both replicas must actually CARRY work for relative detection
        # to mean anything: with the default 8 lanes one replica can
        # swallow a whole small bench run at admission
        scfg.setdefault("max_batch", 2)
    scfg["fleet"] = fleet_cfg
    flt = ServingFleet(cfg, params, serving=scfg)
    flt.start()

    # warm EVERY replica's compile caches outside the timed window (each
    # engine has its own jit closures; a cold replica would bill XLA
    # latency to the serving numbers)
    flt.warmup(prompt=list(rng.integers(1, cfg.vocab_size,
                                        size=prompt_len)))
    base = dict(flt.stats)              # row reports the timed window only

    prompts = [list(rng.integers(1, cfg.vocab_size, size=prompt_len))
               for _ in range(num_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=num_requests))
    t0 = time.perf_counter()
    t0_mono = time.monotonic()
    reqs: List = []
    next_i = 0
    killed_at = None
    kill_target = str(int(fleet_cfg["replicas"]) - 1)
    timeline: List[tuple] = []          # (t, tokens_emitted) samples
    while True:
        now = time.perf_counter() - t0
        while next_i < num_requests and arrivals[next_i] <= now:
            reqs.append(flt.submit(prompts[next_i], new_tokens))
            next_i += 1
        done = sum(1 for r in reqs if r.done)
        timeline.append((now, flt.stats["tokens_emitted"]))
        # the slow leg additionally waits for the victim to HOLD lanes:
        # slowing an idle replica degrades nothing and detects nothing
        victim_busy = (not slow_replica
                       or bool(flt._replicas[int(kill_target)].inflight))
        if ((fail_replica or slow_replica) and killed_at is None
                and victim_busy
                and done >= max(num_requests // 3, 1)):
            if slow_replica:
                # degraded, not dead: the victim keeps serving at
                # sleep-inflated step times until the straggler drain
                chaos.arm("serve.replica_slow", "sleep", ms=int(slow_ms),
                          times=0, match=kill_target)
            else:
                chaos.arm("serve.replica_kill", "raise", match=kill_target)
            killed_at = now
        if (slow_replica and killed_at is not None
                and flt.stats["deaths"] > base["deaths"]
                and chaos.armed()):
            # drained: lift the injection so the warmed replacement
            # rejoins at full speed (the recovery the row measures)
            chaos.disarm("serve.replica_slow")
        if next_i >= num_requests and done >= num_requests:
            break
        time.sleep(0.005)
    wall = time.perf_counter() - t0
    if killed_at is not None:
        # the victim may have died with no in-flight work, in which case
        # the drain above never waited on detection — give the supervisor
        # its poll so the row's death/attribution columns are stable
        t_wait = time.perf_counter()
        while (flt.stats["deaths"] == base["deaths"]
               and time.perf_counter() - t_wait < 10.0):
            time.sleep(0.01)
    chaos.disarm("serve.replica_kill")
    chaos.disarm("serve.replica_slow")

    def _tps(t_lo, t_hi):
        if t_hi - t_lo <= 0:
            return None
        lo = min((s for s in timeline if s[0] >= t_lo),
                 default=timeline[-1])
        hi = max((s for s in timeline if s[0] <= t_hi),
                 default=timeline[-1])
        if hi[0] - lo[0] <= 0:
            return None
        return round((hi[1] - lo[1]) / (hi[0] - lo[0]), 1)

    # recovery instant: the death ledger's restart stamp, in bench time
    # (for the slow leg also the DRAIN instant — detection, before the
    # warmed restart — so the degraded window is directly readable)
    t_rec = t_drain = None
    if flt.deaths:
        rts = flt.deaths[-1]["restarted_ts"] or flt.deaths[-1]["detected_ts"]
        t_rec = rts - t0_mono
        t_drain = flt.deaths[-1]["detected_ts"] - t0_mono
    lat = sorted(r.finish_ts - (t0_mono + arr)
                 for r, arr in zip(reqs, arrivals) if r.finish_ts)
    n_chips = jax.device_count()
    mode = "poisson_fleet_slow" if slow_replica else "poisson_fleet"
    row = {
        "mode": mode,
        "preset": preset, "rate": float(rate), "replicas":
            int(fleet_cfg["replicas"]), "requests": num_requests,
        "prompt": prompt_len, "new_tokens": new_tokens,
        "chunk": int(scfg.get("prefill_chunk_tokens", 0)),
        "kv_dtype": scfg.get("kv_cache_dtype"),
        "weight_dtype": scfg.get("weight_dtype"),
        "wall_s": round(wall, 3),
        "p50_s": round(float(np.percentile(lat, 50)), 4),
        "p99_s": round(float(np.percentile(lat, 99)), 4),
        "tokens_per_s": round(num_requests * new_tokens / wall, 1),
        "tokens_per_s_per_chip": round(
            num_requests * new_tokens / wall / n_chips, 1),
        "tps_before": _tps(0.0, killed_at) if killed_at else None,
        "tps_during": (_tps(killed_at, t_rec)
                       if killed_at and t_rec else None),
        "tps_after": _tps(t_rec, wall) if t_rec else None,
        "kill_at_s": (round(killed_at, 3)
                      if killed_at and not slow_replica else None),
        "slow_at_s": (round(killed_at, 3)
                      if killed_at and slow_replica else None),
        "drained_at_s": (round(t_drain, 3)
                         if slow_replica and t_drain else None),
        "recovered_at_s": round(t_rec, 3) if t_rec else None,
        "deaths": flt.stats["deaths"] - base["deaths"],
        "requeues": flt.stats["requeues"] - base["requeues"],
        "completed": flt.stats["completed"] - base["completed"],
        "failed": flt.stats["failed"] - base["failed"],
        "timeout": flt.stats["timeout"] - base["timeout"],
        "n_chips": n_chips,
    }
    flt.close()
    print(f"inference_bench {mode}: " + json.dumps(row))
    return row


def run_poisson_fleet_proc(preset: str, rate: float, num_requests: int,
                           prompt_len: int, new_tokens: int,
                           replicas: int = 2,
                           serving: Optional[dict] = None,
                           fail_replica: bool = True, seed: int = 0,
                           model_kwargs: Optional[dict] = None) -> dict:
    """Poisson load against the PROCESS-placement fleet (round 18,
    serving/procfleet.py): each replica engine in a supervised OS
    process, request/token streams over the transfer fabric's TCP star.
    Once a third of the requests have completed, the last replica's
    PROCESS takes a real ``SIGKILL`` — actual process death, not a
    failpoint — and the row records tokens/s BEFORE / DURING / AFTER
    the loss plus the death-ledger columns, the process-placement
    counterpart of the ``poisson_fleet`` resilience number. The
    heartbeat channel is a real directory (``heartbeat_dir`` column):
    ``dstpu health <dir>`` shows the per-process replica rows —
    pid/queue/pool gauges per worker — mid-run and after. Row::

        inference_bench poisson_fleet_proc: {"rate": ..., "replicas":
            ..., "tps_before": ..., "tps_during": ..., "tps_after": ...,
            "requeues": ..., "deaths": ..., ...}
    """
    import signal as _signal

    from ..models import build_model
    from ..serving.procfleet import ProcessFleet
    model, cfg = build_model(preset, max_seq_len=prompt_len + new_tokens,
                             **(model_kwargs or {}))
    rng = np.random.default_rng(seed)
    ids0 = rng.integers(0, cfg.vocab_size, (1, prompt_len))
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: model.init(r, {"input_ids": ids0})
                     ["params"])(jax.random.PRNGKey(0))
    scfg = dict(serving or {})
    fleet_cfg = dict(scfg.pop("fleet", {}))
    fleet_cfg.setdefault("replicas", replicas)
    fleet_cfg["placement"] = "process"
    # snappy recovery for the bench window (production defaults are lazier)
    fleet_cfg.setdefault("poll_interval", 0.05)
    fleet_cfg.setdefault("heartbeat_interval", 0.05)
    scfg["fleet"] = fleet_cfg
    flt = ProcessFleet(cfg, params, serving=scfg)
    flt.start()
    # workers warm THEMSELVES at spawn (weights + compile off the
    # serving path); this is the ready barrier, not the trigger
    flt.warmup(timeout=600.0)
    base = dict(flt.stats)              # row reports the timed window only

    prompts = [list(rng.integers(1, cfg.vocab_size, size=prompt_len))
               for _ in range(num_requests)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=num_requests))
    t0 = time.perf_counter()
    t0_mono = time.monotonic()
    reqs: List = []
    next_i = 0
    killed_at = None
    victim = int(fleet_cfg["replicas"]) - 1
    timeline: List[tuple] = []          # (t, tokens_emitted) samples
    while True:
        now = time.perf_counter() - t0
        while next_i < num_requests and arrivals[next_i] <= now:
            reqs.append(flt.submit(prompts[next_i], new_tokens))
            next_i += 1
        done = sum(1 for r in reqs if r.done)
        timeline.append((now, flt.stats["tokens_emitted"]))
        if (fail_replica and killed_at is None
                and done >= max(num_requests // 3, 1)):
            pid = flt.pids().get(victim)
            if pid is not None:
                os.kill(pid, _signal.SIGKILL)   # a real process death
                killed_at = now
        if next_i >= num_requests and done >= num_requests:
            break
        time.sleep(0.005)
    wall = time.perf_counter() - t0
    if killed_at is not None:
        # the victim may have died idle — give the supervisor its poll
        # so the row's death/attribution columns are stable
        t_wait = time.perf_counter()
        while (flt.stats["deaths"] == base["deaths"]
               and time.perf_counter() - t_wait < 10.0):
            time.sleep(0.01)

    def _tps(t_lo, t_hi):
        if t_hi - t_lo <= 0:
            return None
        lo = min((s for s in timeline if s[0] >= t_lo),
                 default=timeline[-1])
        hi = max((s for s in timeline if s[0] <= t_hi),
                 default=timeline[-1])
        if hi[0] - lo[0] <= 0:
            return None
        return round((hi[1] - lo[1]) / (hi[0] - lo[0]), 1)

    t_rec = None
    if flt.deaths:
        rts = (flt.deaths[-1]["restarted_ts"]
               or flt.deaths[-1]["detected_ts"])
        t_rec = rts - t0_mono
    lat = sorted(r.finish_ts - (t0_mono + arr)
                 for r, arr in zip(reqs, arrivals) if r.finish_ts)
    n_chips = jax.device_count()
    row = {
        "mode": "poisson_fleet_proc",
        "preset": preset, "rate": float(rate),
        "replicas": int(fleet_cfg["replicas"]), "requests": num_requests,
        "prompt": prompt_len, "new_tokens": new_tokens,
        "chunk": int(scfg.get("prefill_chunk_tokens", 0)),
        "kv_dtype": scfg.get("kv_cache_dtype"),
        "weight_dtype": scfg.get("weight_dtype"),
        "wall_s": round(wall, 3),
        "p50_s": round(float(np.percentile(lat, 50)), 4),
        "p99_s": round(float(np.percentile(lat, 99)), 4),
        "tokens_per_s": round(num_requests * new_tokens / wall, 1),
        "tokens_per_s_per_chip": round(
            num_requests * new_tokens / wall / n_chips, 1),
        "tps_before": _tps(0.0, killed_at) if killed_at else None,
        "tps_during": (_tps(killed_at, t_rec)
                       if killed_at and t_rec else None),
        "tps_after": _tps(t_rec, wall) if t_rec else None,
        "kill_at_s": round(killed_at, 3) if killed_at else None,
        "recovered_at_s": round(t_rec, 3) if t_rec else None,
        "deaths": flt.stats["deaths"] - base["deaths"],
        "requeues": flt.stats["requeues"] - base["requeues"],
        "completed": flt.stats["completed"] - base["completed"],
        "failed": flt.stats["failed"] - base["failed"],
        "timeout": flt.stats["timeout"] - base["timeout"],
        "heartbeat_dir": flt.heartbeat_dir,
        "n_chips": n_chips,
    }
    flt.close()
    print("inference_bench poisson_fleet_proc: " + json.dumps(row))
    return row


def parse_trace(spec: str) -> List[Tuple[float, float]]:
    """``--trace`` spec -> [(rate_req_per_s, duration_s), ...]. The
    format is comma-separated ``rate@seconds`` segments, e.g.
    ``0.5@10,1.5@10,0.5@10`` — a 3x burst framed by the base rate —
    driven open-loop as piecewise-Poisson arrivals."""
    segs = []
    for part in spec.split(","):
        rate, dur = part.split("@")
        segs.append((float(rate), float(dur)))
    if not segs:
        raise ValueError(f"--trace {spec!r}: no segments")
    return segs


def trace_arrivals(segs: List[Tuple[float, float]], rng) -> List[float]:
    """Piecewise-Poisson arrival times over the trace segments."""
    arrivals, start = [], 0.0
    for rate, dur in segs:
        t, end = start, start + dur
        while True:
            t += float(rng.exponential(1.0 / rate))
            if t >= end:
                break
            arrivals.append(t)
        start = end
    return arrivals


#: deterministic tier mix for the autoscale leg: mostly standard, a
#: latency request (tight-deadline SLO traffic) and a batch request
#: (deferrable backfill) interleaved — enough of each for per-tier p99
_TIER_CYCLE = ("standard", "latency", "standard", "batch", "standard")


def run_poisson_autoscale(preset: str, trace: List[Tuple[float, float]],
                          prompt_len: int, new_tokens: int,
                          serving: Optional[dict] = None, seed: int = 0,
                          max_replicas: int = 3,
                          model_kwargs: Optional[dict] = None) -> dict:
    """Bursty piecewise-Poisson load against the AUTOSCALING fleet
    (round 19): the fleet starts at ``min_replicas=1``, the trace's
    burst segment pushes queue depth over the scale-up trigger, the
    supervisor spawns warmed replicas up to ``max_replicas``, and the
    post-burst idle trough drains them back down. Requests carry mixed
    priority tiers (``_TIER_CYCLE``), so the row reports per-tier p99 —
    the traffic-shaping number: latency-tier p99 should survive the
    burst that batch-tier p99 absorbs. Machine-readable row::

        inference_bench poisson_autoscale: {"trace": "...", "scale_ups":
            ..., "scale_downs": ..., "p99_by_tier": {...}, ...}

    ``clean_drain`` asserts the conclusion: every request concluded,
    every scale-down's drain completed (``drained_ts`` stamped), and
    the fleet ended back at its floor."""
    from ..models import build_model
    from ..serving.fleet import ServingFleet
    model, cfg = build_model(preset, max_seq_len=prompt_len + new_tokens,
                             **(model_kwargs or {}))
    rng = np.random.default_rng(seed)
    ids0 = rng.integers(0, cfg.vocab_size, (1, prompt_len))
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: model.init(r, {"input_ids": ids0})
                     ["params"])(jax.random.PRNGKey(0))
    scfg = dict(serving or {})
    fleet_cfg = dict(scfg.pop("fleet", {}))
    fleet_cfg.setdefault("replicas", 1)
    fleet_cfg.setdefault("poll_interval", 0.05)
    fleet_cfg.setdefault("heartbeat_interval", 0.05)
    # a warm scale-up compile on CPU can starve sibling heartbeats for
    # tens of seconds (GIL-bound tracing) — the bench measures traffic
    # shaping, not silence detection (run_poisson_fleet's convention)
    fleet_cfg.setdefault("heartbeat_timeout", 300.0)
    # aging short enough that a queued batch request can still promote
    # within the bench window (the starvation floor, observable)
    fleet_cfg.setdefault("priority_aging_s", 30.0)
    fleet_cfg.setdefault("autoscale", {
        "enabled": True, "min_replicas": 1, "max_replicas": max_replicas,
        "up_queue_per_replica": 2, "up_after": 2,
        "down_idle_s": 1.0, "cooldown_s": 2.0})
    scfg["fleet"] = fleet_cfg
    flt = ServingFleet(cfg, params, serving=scfg)
    flt.start()
    flt.warmup(prompt=list(rng.integers(1, cfg.vocab_size,
                                        size=prompt_len)))
    base = dict(flt.stats)

    arrivals = trace_arrivals(trace, rng)
    n = len(arrivals)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=prompt_len))
               for _ in range(n)]
    tiers = [_TIER_CYCLE[i % len(_TIER_CYCLE)] for i in range(n)]
    trace_end = sum(d for _, d in trace)
    t0 = time.perf_counter()
    t0_mono = time.monotonic()
    reqs: List = []
    next_i = 0
    max_live = len(flt.live_replicas())
    while True:
        now = time.perf_counter() - t0
        while next_i < n and arrivals[next_i] <= now:
            reqs.append(flt.submit(
                prompts[next_i], new_tokens, priority=tiers[next_i]))
            next_i += 1
        max_live = max(max_live, len(flt.live_replicas()))
        if next_i >= n and all(r.done for r in reqs):
            break
        time.sleep(0.005)
    wall = time.perf_counter() - t0
    # the idle tail: give the trough trigger its down_idle_s + cooldown
    # so the row records the drain-down, not just the spawn-up
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        ups = sum(1 for e in flt.scale_events if e.action == "up")
        downs = [e for e in flt.scale_events if e.action == "down"]
        if ups and downs and all(e.drained_ts for e in downs) \
                and len(flt.live_replicas()) <= max(
                    1, int(fleet_cfg["autoscale"]["min_replicas"])):
            break
        time.sleep(0.05)

    lat_by_tier: Dict[str, List[float]] = {}
    for r, arr in zip(reqs, arrivals):
        if r.finish_ts:
            lat_by_tier.setdefault(r.priority, []).append(
                r.finish_ts - (t0_mono + arr))
    p99 = {t: round(float(np.percentile(v, 99)), 4)
           for t, v in sorted(lat_by_tier.items())}
    downs = [e for e in flt.scale_events if e.action == "down"]
    clean_drain = (all(r.done for r in reqs)
                   and all(e.drained_ts is not None for e in downs))
    n_chips = jax.device_count()
    row = {
        "mode": "poisson_autoscale",
        "preset": preset,
        "trace": ",".join(f"{r:g}@{d:g}" for r, d in trace),
        "rate": trace[0][0],            # regression key: the base rate
        "burst_rate": max(r for r, _ in trace),
        "requests": n, "prompt": prompt_len, "new_tokens": new_tokens,
        "trace_s": round(trace_end, 1), "wall_s": round(wall, 3),
        "p50_s": round(float(np.percentile(
            [v for vs in lat_by_tier.values() for v in vs], 50)), 4),
        "p99_s": round(float(np.percentile(
            [v for vs in lat_by_tier.values() for v in vs], 99)), 4),
        "p99_by_tier": p99,
        "tokens_per_s": round(n * new_tokens / wall, 1),
        "replicas_floor": int(fleet_cfg["replicas"]),
        "max_replicas": max_replicas, "max_live": max_live,
        "scale_ups": flt.stats["scale_ups"] - base["scale_ups"],
        "scale_downs": flt.stats["scale_downs"] - base["scale_downs"],
        "scale_events": [
            {"action": e.action, "replica": e.replica,
             "reason": e.reason, "t_s": round(e.ts - t0_mono, 3),
             "drained_t_s": (round(e.drained_ts - t0_mono, 3)
                             if e.drained_ts else None)}
            for e in flt.scale_events],
        "shed": flt.stats["shed"] - base["shed"],
        "preempted": flt.stats["preempted"] - base["preempted"],
        "completed": flt.stats["completed"] - base["completed"],
        "failed": flt.stats["failed"] - base["failed"],
        "timeout": flt.stats["timeout"] - base["timeout"],
        "clean_drain": bool(clean_drain),
        "n_chips": n_chips,
    }
    flt.close()
    print("inference_bench poisson_autoscale: " + json.dumps(row))
    return row


def record_serve_bench(rows: List[Dict], path: str) -> str:
    """Write serving-bench rows in the SERVEBENCH report shape (the
    comm-sweep convention: ``{"n": device_count, "rows": [...]}`` so
    baselines from a different topology are skipped)."""
    doc = {"n": jax.device_count(), "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"inference_bench: recorded {len(rows)} serving rows -> {path}")
    return path


def latest_serve_bench(baseline_dir: str, n_devices: Optional[int] = None
                       ) -> Tuple[Optional[str], List[Dict]]:
    """(name, rows) of the newest recorded serving sweep in
    ``baseline_dir`` (``SERVEBENCH_r*.json`` reports or
    ``serve_bench*.json`` recordings); sweeps from a different device
    count are skipped — their throughputs aren't comparable."""
    from .sweeps import latest_recorded_sweep
    return latest_recorded_sweep(
        baseline_dir, ("SERVEBENCH_r*.json", "serve_bench*.json"),
        n_devices)


def check_serve_regression(current: List[Dict], baseline: List[Dict],
                           factor: float = SERVE_REGRESSION_FACTOR
                           ) -> List[str]:
    """Rows whose p50 latency exceeds ``factor`` x the recorded one, or
    whose tokens/s fell below recorded / ``factor`` — keyed by
    (mode, preset, rate, prompt, new_tokens, replicas, chunk, kv_dtype,
    weight_dtype) so the round-17 quantized legs never gate the bf16 row
    (or vice versa). Missing rows are NOT flagged (a narrower re-run is
    legitimate)."""
    def key(r):
        return (r.get("mode", "poisson"), r.get("preset"),
                r.get("rate"), r.get("prompt"), r.get("new_tokens"),
                r.get("replicas"), r.get("chunk", 0),
                r.get("kv_dtype"), r.get("weight_dtype"))

    base = {key(r): r for r in baseline}
    problems = []
    for r in current:
        b = base.get(key(r))
        if b is None:
            continue
        p50, bp50 = r.get("p50_s"), b.get("p50_s")
        if p50 and bp50 and float(p50) > factor * float(bp50):
            problems.append(
                f"{r.get('mode')}@rate={r.get('rate')}: p50 {p50:.3f}s vs "
                f"recorded {bp50:.3f}s ({p50 / bp50:.1f}x > {factor:g}x)")
        tps, btps = r.get("tokens_per_s"), b.get("tokens_per_s")
        if tps and btps and float(tps) < float(btps) / factor:
            problems.append(
                f"{r.get('mode')}@rate={r.get('rate')}: tokens/s {tps:.1f} "
                f"vs recorded {btps:.1f} (<1/{factor:g})")
    return problems


def run_spatial(size: int, batch: int, channels: int = 64,
                context_len: int = 77):
    """Conditional-UNet forward latency (the diffusion serving hot loop —
    the reference's diffusers injection slot)."""
    from ..inference import InferenceEngine
    from ..inference.spatial import UNet2DCondition
    unet = UNet2DCondition(block_channels=(channels, 2 * channels),
                           num_heads=8, out_channels=4, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, size, size, 4)), jnp.bfloat16)
    t = jnp.ones((batch,), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(batch, context_len, 2 * channels)),
                      jnp.bfloat16)
    # one-shot bench setup: init compiles once before the timed region
    # graftlint: disable=TPU002
    params = jax.jit(lambda r: unet.init(r, x, t, ctx)["params"])(
        jax.random.PRNGKey(0))
    eng = InferenceEngine(model=unet, model_parameters=params,
                          config={"dtype": "bfloat16"})
    dt = _timed(lambda: eng.forward(x, t, ctx))
    row = {"model": "unet2d-cond", "latent": size, "batch": batch,
           "channels": channels, "forward_ms": round(dt * 1e3, 2),
           "images_per_s": round(batch / dt, 2)}
    print(row)
    return row


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="gpt2-125m")
    p.add_argument("--batches", default="1,8")
    p.add_argument("--seqs", default="128,1024")
    p.add_argument("--new", type=int, default=64)
    p.add_argument("--ragged", action="store_true",
                   help="mixed-context left-padded batch bench")
    p.add_argument("--ragged-batch", type=int, default=8)
    p.add_argument("--ragged-seq", type=int, default=512)
    p.add_argument("--spatial", action="store_true",
                   help="conditional-UNet forward latency")
    p.add_argument("--latent", type=int, default=64)
    p.add_argument("--poisson", action="store_true",
                   help="Poisson-arrival load vs the serving loop")
    p.add_argument("--rates", default="2,8",
                   help="request rates (req/s), comma-separated")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--fleet", type=int, default=0,
                   help="with --poisson: drive a supervised N-replica "
                        "fleet instead of one engine; prints the "
                        "poisson_fleet degraded-throughput row")
    p.add_argument("--placement", choices=("thread", "process"),
                   default="thread",
                   help="fleet leg replica placement: 'process' (round "
                        "18) runs each replica in a supervised OS "
                        "process over the transfer fabric and SIGKILLs "
                        "a replica PROCESS at 1/3 completion — the "
                        "poisson_fleet_proc degraded-throughput row")
    p.add_argument("--no-fail-replica", action="store_true",
                   help="fleet leg: skip the replica-kill injection "
                        "(steady-state fleet throughput only)")
    p.add_argument("--slow-replica", action="store_true",
                   help="fleet leg: inject a DEGRADED (not dead) replica "
                        "via the keyed serve.replica_slow sleep failpoint "
                        "at 1/3 completion; the straggler detector drains "
                        "it and the poisson_fleet_slow row records "
                        "tps_before/during/after + drain/recovery stamps")
    p.add_argument("--slow-ms", type=int, default=250,
                   help="--slow-replica: injected per-iteration delay")
    p.add_argument("--trace", default="",
                   help="with --poisson: bursty piecewise-Poisson trace "
                        "as rate@seconds segments (e.g. 0.5@10,1.5@10,"
                        "0.5@10 — a 3x burst) against the AUTOSCALING "
                        "fleet with mixed priority tiers; prints the "
                        "poisson_autoscale row (scale events, per-tier "
                        "p99, clean drain)")
    p.add_argument("--max-replicas", type=int, default=3,
                   help="--trace: autoscaler ceiling (floor is 1)")
    p.add_argument("--chunk", type=int, default=0,
                   help="serving.prefill_chunk_tokens for the poisson "
                        "legs (0 = whole prefill)")
    p.add_argument("--kv-dtype", choices=("int8", "bf16", "f32"),
                   default=None,
                   help="serving.kv_cache_dtype for the poisson legs "
                        "(int8 = quantized pool, in-kernel dequant; "
                        "default: model dtype)")
    p.add_argument("--weight-dtype", choices=("int8",), default=None,
                   help="serving.weight_dtype for the poisson legs "
                        "(int8 = blockwise weight-only quant, packed "
                        "once at engine build)")
    p.add_argument("--record", default="",
                   help="write the poisson rows to this JSON path "
                        "(commit as SERVEBENCH_rNN.json)")
    p.add_argument("--baseline-dir", default=".",
                   help="directory searched for the newest recorded "
                        "serving sweep to compare against (>2x p50 or "
                        "<1/2 tokens/s = loud regression; "
                        "DSTPU_SERVE_BENCH_GATE=1 makes it fatal)")
    args = p.parse_args(argv)
    from ..utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.spatial:
        run_spatial(args.latent, int(args.batches.split(",")[0]))
        return
    if args.ragged:
        run_ragged(args.preset, args.ragged_batch, args.ragged_seq, args.new)
        return
    if args.poisson:
        serving = {}
        if args.chunk > 0:
            serving["prefill_chunk_tokens"] = args.chunk
        if args.kv_dtype:
            serving["kv_cache_dtype"] = args.kv_dtype
        if args.weight_dtype:
            serving["weight_dtype"] = args.weight_dtype
        serving = serving or None
        rows = []
        if args.trace:
            rows.append(run_poisson_autoscale(
                args.preset, parse_trace(args.trace), args.prompt,
                args.new, serving=serving,
                max_replicas=args.max_replicas))
        for rate in ((float(x) for x in args.rates.split(","))
                     if not args.trace else ()):
            if args.fleet > 1 and args.placement == "process":
                rows.append(run_poisson_fleet_proc(
                    args.preset, rate, args.requests, args.prompt,
                    args.new, replicas=args.fleet, serving=serving,
                    fail_replica=not args.no_fail_replica))
            elif args.fleet > 1:
                rows.append(run_poisson_fleet(
                    args.preset, rate, args.requests, args.prompt,
                    args.new, replicas=args.fleet, serving=serving,
                    fail_replica=(not args.no_fail_replica
                                  and not args.slow_replica),
                    slow_replica=args.slow_replica, slow_ms=args.slow_ms))
            else:
                rows.append(run_poisson(args.preset, rate, args.requests,
                                        args.prompt, args.new,
                                        serving=serving))
        base_name, baseline = latest_serve_bench(args.baseline_dir,
                                                 jax.device_count())
        problems = (check_serve_regression(rows, baseline)
                    if baseline else [])
        if problems:
            msg = (f"SERVING REGRESSION vs {base_name}:\n  "
                   + "\n  ".join(problems))
            if os.environ.get("DSTPU_SERVE_BENCH_GATE") == "1":
                raise SystemExit(msg)
            print(msg)
        elif base_name:
            print(f"inference_bench: no serving regression vs {base_name}")
        if args.record:
            record_serve_bench(rows, args.record)
        return
    run(args.preset, [int(x) for x in args.batches.split(",")],
        [int(x) for x in args.seqs.split(",")], args.new)


if __name__ == "__main__":
    main()
