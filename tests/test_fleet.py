"""Serving-fleet resilience: supervised multi-replica decode, request
requeue with exactly-once token emission, retry budgets, blacklist /
parole, deadlines, and the chaos failure matrix (serve.replica_kill /
serve.replica_hang / serve.requeue).

The oracle everywhere is sequential ``models.generation.generate()`` —
under greedy decode a killed-and-requeued request must produce final
token sequences IDENTICAL to an uninjected run, and the per-token
``on_token`` ledger must contain each token exactly once.

Determinism notes: requests are submitted BEFORE ``start()`` so the
chaos ``skip`` counter lands while the victim replica provably has
in-flight work; hang legs ``warmup()`` first and only then tighten
``heartbeat_timeout``, so an XLA compile can never read as silence.
"""

import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.runtime import heartbeat as hb
from deepspeed_tpu.serving.fleet import (BLACKLISTED, LIVE, FleetSupervisor,
                                         ServingFleet, _Replica)
from deepspeed_tpu.serving.scheduler import FAILED, FINISHED, TIMEOUT
from deepspeed_tpu.testing import chaos


@pytest.fixture(scope="module")
def tiny():
    # f32: the exactly-once contract is proven via greedy token-exactness
    # against sequential generate(); see test_serving.py's fixture note
    model, cfg = build_model(
        "gpt2-tiny", hidden_size=32, num_layers=2, num_heads=2,
        vocab_size=64, max_seq_len=256, attention_impl="reference",
        dtype=jnp.float32)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, params


def _oracle_tokens(cfg, params, prompt, n):
    out = generate(cfg, params, jnp.asarray([list(prompt)]), n)
    return [int(x) for x in np.asarray(out)[0][len(prompt):]]


def _serving(replicas, **fleet_kw):
    fleet = {"replicas": replicas, "poll_interval": 0.05,
             "heartbeat_interval": 0.02, "heartbeat_timeout": 60.0}
    fleet.update(fleet_kw)
    return {"block_size": 16, "pool_blocks": 64, "max_batch": 2,
            "max_blocks_per_seq": 8, "fleet": fleet}


def _wait_inflight(flt, idx, timeout=30.0):
    """Block until replica ``idx`` holds in-flight work — the straggler
    legs arm slowness only once the victim PROVABLY has lanes (the tiny
    model serves whole requests in milliseconds; armed too early, the
    pre-dispatch sleep lets the fast replica drain the queue and the
    victim never works at all)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if flt._replicas[idx].inflight:
            return
        time.sleep(0.001)
    raise AssertionError(f"replica {idx} never got in-flight work")


# ---------------------------------------------------------------------------
# tier-1: kill -> requeue (with a requeue crash folded in), exactly-once
# ---------------------------------------------------------------------------

# tier-2 (round-17 budget sweep, ~13s): the cheaper tier-1 cousins are
# test_fleet_retry_budget_exhaustion_fails_cleanly (death -> retry path),
# test_fleet_supervisor_verdict_units and
# test_init_inference_serve_returns_started_fleet; scripts/chaos.sh and
# scripts/tier2.sh run this leg and the 3-replica kill matrix
@pytest.mark.slow
def test_fleet_kill_requeues_exactly_once_token_exact(tiny):
    """serve.replica_kill mid-decode: the dead replica's in-flight
    requests requeue onto survivors and finish token-exact vs sequential
    generate(), with the on_token ledger emitting each token exactly
    once. A serve.requeue crash during the requeue orphans the request
    for the next supervisor poll instead of losing it."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, 64, size=n))
               for n in (5, 11, 17, 9, 13, 7)]
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(2))
    reqs = [flt.submit(
        p, 12, on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in prompts]
    # replica 1 dispatches up to 2 lanes on its first iterations and
    # each request needs >= 12 decode steps, so hit 6 is mid-decode
    chaos.arm("serve.replica_kill", "raise", match="1", skip=5)
    chaos.arm("serve.requeue", "raise", times=1)
    try:
        flt.start()
        assert flt.drain(timeout=180)
        assert chaos.fired("serve.replica_kill")
        assert flt.stats["deaths"] == 1 and flt.stats["restarts"] == 1
        assert flt.stats["requeues"] >= 1          # work actually moved
        death = flt.deaths[0]
        assert death["replica"] == 1 and death["reason"] == "crash"
        # attribution via heartbeat evidence: the replica's last word
        assert death["evidence"]["phase"] == hb.PHASE_SERVE
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 12)
            assert r.state == FINISHED
            assert r.output_tokens == oracle, \
                f"request {r.rid} diverged after requeue"
            assert emitted[r.rid] == oracle, \
                f"request {r.rid} re-fired or dropped a token"
    finally:
        flt.close()


def test_fleet_retry_budget_exhaustion_fails_cleanly(tiny):
    """Past retry_budget requeues the request concludes FAILED (callback
    fires, error names the budget) instead of looping forever."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    seen = []
    flt = ServingFleet(cfg, params, serving=_serving(1, retry_budget=0))
    req = flt.submit(list(rng.integers(1, 64, size=10)), 10,
                     on_finish=lambda r: seen.append(r.state))
    chaos.arm("serve.replica_kill", "raise", match="0", skip=3)
    try:
        flt.start()
        assert req.wait(timeout=120)
        assert req.state == FAILED and "retry budget" in req.error
        assert seen == [FAILED]
        assert flt.stats["failed"] == 1 and flt.stats["requeues"] == 0
        # the fleet itself recovered: a fresh request serves
        ok = flt.submit(list(rng.integers(1, 64, size=8)), 4)
        assert ok.wait(timeout=120) and ok.state == FINISHED
    finally:
        flt.close()


def test_fleet_deadline_sheds_queued_request_with_timeout(tiny):
    """A queued request past its TTL is shed with TIMEOUT while admitted
    work runs to completion — graceful admission backpressure."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    serving = {"block_size": 16, "pool_blocks": 4, "max_batch": 1,
               "max_blocks_per_seq": 3, "prefix_cache": False,
               "fleet": {"replicas": 1, "poll_interval": 0.02,
                         "heartbeat_interval": 0.02}}
    flt = ServingFleet(cfg, params, serving=serving)
    # the head occupies the single lane; the follower cannot be
    # dispatched and expires while queued (the strict-FIFO edge). The
    # deadline-less tail BEHIND it must survive the shed (the queue is
    # rebuilt mid-traffic) and still be dispatched and served
    head = flt.submit(list(rng.integers(1, 64, size=30)), 16)
    late = flt.submit(list(rng.integers(1, 64, size=30)), 16,
                      deadline_s=0.05)
    tail = flt.submit(list(rng.integers(1, 64, size=20)), 4)
    try:
        flt.start()
        assert late.wait(timeout=120)
        assert late.state == TIMEOUT and "deadline" in late.error
        assert head.wait(timeout=120) and head.state == FINISHED
        assert tail.wait(timeout=120) and tail.state == FINISHED
        assert flt.stats["timeout"] == 1 and flt.stats["completed"] == 2
    finally:
        flt.close()


def test_fleet_submit_validation_is_synchronous(tiny):
    """Inadmissible requests fail at submit() — a request no replica
    could ever admit must not be discovered asynchronously."""
    cfg, params = tiny
    serving = {"block_size": 16, "pool_blocks": 3, "max_batch": 2,
               "max_blocks_per_seq": 8,
               "fleet": {"replicas": 2, "max_queue": 1}}
    flt = ServingFleet(cfg, params, serving=serving)   # NOT started
    with pytest.raises(ValueError, match="empty prompt"):
        flt.submit([], 4)
    with pytest.raises(ValueError, match="max_model_len"):
        flt.submit(list(range(1, 120)), 32)
    with pytest.raises(ValueError, match="pool has 2"):
        flt.submit(list(range(1, 40)), 16)
    flt.submit([1, 2, 3], 2)
    with pytest.raises(RuntimeError, match="queue full"):
        flt.submit([4, 5, 6], 2)


def test_fleet_supervisor_verdict_units():
    """Detection predicate, model-free: thread death is a crash; a stale
    non-terminal record (or never writing at all) is silence; a terminal
    record is a conclusion, not silence; fresh records are healthy."""
    sup = FleetSupervisor(SimpleNamespace(
        fcfg=SimpleNamespace(heartbeat_timeout=1.0)))
    now = time.monotonic()

    rep = _Replica(0)
    rep.thread = SimpleNamespace(is_alive=lambda: False)
    assert sup._verdict(rep, {"phase": "SERVE", "ts": time.time()},
                        now) == "crash"

    rep = _Replica(1)                       # thread None -> liveness skipped
    fresh = {"phase": "SERVE", "ts": time.time()}
    stale = {"phase": "SERVE", "ts": time.time() - 5.0}
    stalled = {"phase": "STALLED", "ts": time.time() - 5.0}
    assert sup._verdict(rep, fresh, now) is None
    assert sup._verdict(rep, stale, now) == "silence"
    assert sup._verdict(rep, stalled, now) is None    # conclusion
    rep.started_ts = now - 0.2
    assert sup._verdict(rep, None, now) is None       # launch grace
    rep.started_ts = now - 5.0
    assert sup._verdict(rep, None, now) == "silence"  # never wrote
    # timeout 0 disables silence (thread liveness still applies)
    sup0 = FleetSupervisor(SimpleNamespace(
        fcfg=SimpleNamespace(heartbeat_timeout=0.0)))
    assert sup0._verdict(rep, stale, now) is None


@pytest.mark.slow
def test_fleet_straggler_drain_requeues_token_exact(tiny):
    """Acceptance (round 15): a serve.replica_slow-DEGRADED replica —
    alive, stepping, just slow — is detected by the FleetSupervisor's
    relative-slowness detector and DRAINED through the death path:
    admission stops, its lanes requeue exactly-once, the replacement
    restarts warmed, and greedy outputs stay token-identical to an
    uninjected twin. No dead/wrong check could have fired: the replica
    never crashes and never goes silent.

    slow-marked per the tier-1 budget guardrail (~8s of serving);
    cheaper tier-1 cousins: the detector/FP-guard + flag-consumption
    units in test_straggler.py, test_fleet_straggler_detection_off_by_
    default, and the chaos jitter semantics in test_chaos.py —
    scripts/chaos.sh and scripts/tier2.sh run this leg."""
    cfg, params = tiny
    rng = np.random.default_rng(23)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 11, 9, 13)]
    emitted = {}
    serving = _serving(2, straggler={"enabled": True, "warmup": 2,
                                     "strike_window": 2, "cooldown": 5})
    flt = ServingFleet(cfg, params, serving=serving)
    try:
        flt.start()
        flt.warmup()       # compile off-path: a compile is not a straggle
        reqs = [flt.submit(
            p, 48, on_token=lambda r, t: emitted.setdefault(r.rid, [])
            .append(t)) for p in prompts]
        _wait_inflight(flt, 1)
        chaos.arm("serve.replica_slow", "sleep", ms=150, times=0,
                  match="1")
        deadline = time.monotonic() + 60
        while flt.stats["deaths"] == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        chaos.disarm("serve.replica_slow")
        assert flt.drain(timeout=180)
        assert flt.stats["deaths"] == 1 and flt.stats["restarts"] == 1
        death = flt.deaths[0]
        assert death["replica"] == 1 and death["reason"] == "straggler"
        assert death["action"] == "restart"
        # the verdict's evidence carries the inflated gauge
        assert death["evidence"]["gauges"]["step_ms"] > 100.0
        # the healthy replica was never touched
        assert flt._replicas[0].generation == 0
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 48)
            assert r.state == FINISHED and r.output_tokens == oracle
            assert emitted[r.rid] == oracle       # exactly-once emission
    finally:
        flt.close()


def test_fleet_straggler_detection_off_by_default(tiny):
    """Without fleet.straggler.enabled the supervisor builds no
    detector — slowness is never a death verdict (evidence-only is the
    package default posture)."""
    cfg, params = tiny
    flt = ServingFleet(cfg, params, serving=_serving(2))
    assert flt.supervisor._straggler is None


def test_init_inference_serve_returns_started_fleet(tiny):
    """init_inference(...).serve() with fleet.replicas > 1 returns a
    STARTED ServingFleet; generate_batch round-trips token-exact."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    cfg, params = tiny
    eng = deepspeed_tpu.init_inference(
        Transformer(cfg),
        {"dtype": "float32",
         "serving": {"block_size": 16, "pool_blocks": 32, "max_batch": 2,
                     "max_blocks_per_seq": 8,
                     "fleet": {"replicas": 2, "poll_interval": 0.05}}},
        model_parameters=params)
    srv = eng.serve()
    assert isinstance(srv, ServingFleet)
    try:
        out = srv.generate_batch([[3, 1, 4, 1, 5], [2, 7, 2]],
                                 max_new_tokens=4)
        assert out[0] == _oracle_tokens(cfg, params, [3, 1, 4, 1, 5], 4)
        assert out[1] == _oracle_tokens(cfg, params, [2, 7, 2], 4)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# slow: the 3-replica acceptance matrix + hang/blacklist/parole + fleet oom
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_acceptance_3replica_kill_matrix(tiny):
    """Acceptance criterion: 3 replicas, serve.replica_kill mid-decode —
    every admitted request completes with final token sequences identical
    to an uninjected run, the loss is attributed via heartbeat evidence,
    and throughput recovers WITHOUT restarting surviving replicas."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 64, size=n))
               for n in (5, 11, 17, 23, 9, 13, 7, 21, 8)]
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(3))
    reqs = [flt.submit(
        p, 12, on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in prompts]
    chaos.arm("serve.replica_kill", "raise", match="1", skip=6)
    try:
        flt.start()
        survivors = {rep.idx: rep.engine for rep in flt._replicas
                     if rep.idx != 1}
        assert flt.drain(timeout=240)
        # one death, attributed; requeued work completed elsewhere
        assert flt.stats["deaths"] == 1 and flt.stats["requeues"] >= 1
        death = flt.deaths[0]
        assert death["replica"] == 1 and death["reason"] == "crash"
        assert death["evidence"]["phase"] == hb.PHASE_SERVE
        assert death["action"] == "restart"
        # survivors were never torn down: same engine objects, same
        # generation — throughput recovered without touching them
        for idx, engine in survivors.items():
            assert flt._replicas[idx].engine is engine
            assert flt._replicas[idx].generation == 0
        assert flt.stats["completed"] == len(prompts)
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 12)
            assert r.state == FINISHED and r.output_tokens == oracle
            assert emitted[r.rid] == oracle     # exactly-once emission
    finally:
        flt.close()
    # after close, every live replica concluded with an EXIT record —
    # `dstpu health` on the fleet dir reads conclusions, not silence
    records = hb.read_heartbeats(flt.heartbeat_dir)
    for rep in flt._replicas:
        if rep.state == LIVE:
            assert records[rep.idx]["phase"] == hb.PHASE_EXIT


@pytest.mark.slow
def test_fleet_hang_silence_detected_and_blacklisted(tiny):
    """serve.replica_hang: a wedged loop goes heartbeat-silent, the
    supervisor declares it via the rc-117 silence contract, requeues its
    work, and blacklist_after strikes quarantine it — the fleet keeps
    serving on the survivor at reduced capacity."""
    cfg, params = tiny
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 9, 13, 7)]
    serving = _serving(2, blacklist_after=1, poll_interval=0.1)
    flt = ServingFleet(cfg, params, serving=serving)
    try:
        flt.start()
        flt.warmup()           # compile off-path: a compile is not a wedge
        flt.fcfg.heartbeat_timeout = 1.0    # now silence means silence
        reqs = [flt.submit(p, 10) for p in prompts]
        chaos.arm("serve.replica_hang", "hang", match="1", skip=3)
        assert flt.drain(timeout=240)
        assert flt.stats["deaths"] == 1
        death = flt.deaths[0]
        assert death["replica"] == 1 and death["reason"] == "silence"
        assert death["action"] == "blacklist"
        assert flt._replicas[1].state == BLACKLISTED
        assert flt._replicas[0].state == LIVE      # reduced, still serving
        for p, r in zip(prompts, reqs):
            assert r.state == FINISHED
            assert r.output_tokens == _oracle_tokens(cfg, params, p, 10)
        # the quarantined replica's STALLED verdict is health-visible
        assert hb.read_heartbeats(flt.heartbeat_dir)[1]["phase"] == \
            hb.PHASE_STALLED
    finally:
        flt.close()


@pytest.mark.slow
def test_fleet_parole_restores_min_replicas(tiny):
    """With live replicas below min_replicas, the least-struck
    blacklisted replica is paroled back instead of starving the fleet."""
    cfg, params = tiny
    rng = np.random.default_rng(17)
    serving = _serving(2, blacklist_after=1, min_replicas=2,
                       poll_interval=0.1)
    flt = ServingFleet(cfg, params, serving=serving)
    try:
        flt.start()
        flt.warmup()
        flt.fcfg.heartbeat_timeout = 1.0
        reqs = [flt.submit(list(rng.integers(1, 64, size=9)), 8)
                for _ in range(4)]
        chaos.arm("serve.replica_hang", "hang", match="1", skip=3)
        assert flt.drain(timeout=240)
        assert flt.stats["deaths"] == 1 and flt.stats["paroles"] == 1
        assert flt.deaths[0]["action"] == "blacklist"
        # paroled back: replica 1 is LIVE again on a fresh generation,
        # strikes standing (it can be re-blacklisted)
        rep1 = flt._replicas[1]
        assert rep1.state == LIVE and rep1.generation >= 1
        assert rep1.strikes == 1
        assert all(r.state == FINISHED for r in reqs)
    finally:
        flt.close()


@pytest.mark.slow
def test_fleet_straggler_blacklist_flag_health_visible(tiny):
    """Repeated drains blacklist the chronically-slow replica, and its
    final record — STALLED, STRAGGLER-flagged — stays health-visible
    (the restart path overwrites the rank file; the blacklist path is
    the durable verdict)."""
    cfg, params = tiny
    rng = np.random.default_rng(29)
    serving = _serving(2, blacklist_after=1,
                       straggler={"enabled": True, "warmup": 2,
                                  "strike_window": 2, "cooldown": 5})
    flt = ServingFleet(cfg, params, serving=serving)
    try:
        flt.start()
        flt.warmup()
        # submit BEFORE arming: the victim dispatches at full speed and
        # provably holds in-flight lanes when the slowness lands
        reqs = [flt.submit(list(rng.integers(1, 64, size=9)), 48)
                for _ in range(6)]
        _wait_inflight(flt, 1)
        chaos.arm("serve.replica_slow", "sleep", ms=150, times=0,
                  match="1")
        deadline = time.monotonic() + 60
        while flt.stats["deaths"] == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        chaos.disarm("serve.replica_slow")
        assert flt.drain(timeout=180)
        assert flt.stats["deaths"] == 1 and flt.stats["blacklisted"] == 1
        assert flt.deaths[0]["reason"] == "straggler"
        assert flt.deaths[0]["action"] == "blacklist"
        assert flt._replicas[1].state == BLACKLISTED
        assert flt._replicas[0].state == LIVE     # reduced, still serving
        for r in reqs:
            assert r.state == FINISHED
        rec = hb.read_heartbeats(flt.heartbeat_dir)[1]
        assert rec["phase"] == hb.PHASE_STALLED
        assert "STRAGGLER" in rec["flags"]
    finally:
        flt.close()


@pytest.mark.slow
def test_fleet_serve_oom_keeps_other_replicas_serving(tiny):
    """serve.oom under the fleet: an injected allocation failure defers
    one replica's admission (request stays queued, PR-8 contract) while
    the rest of the fleet keeps serving — no death, no requeue storm."""
    cfg, params = tiny
    rng = np.random.default_rng(19)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 9, 13, 7, 11)]
    flt = ServingFleet(cfg, params, serving=_serving(2))
    reqs = [flt.submit(p, 8) for p in prompts]
    chaos.arm("serve.oom", "raise", times=2)
    try:
        flt.start()
        assert flt.drain(timeout=240)
        assert chaos.fired("serve.oom")
        assert flt.stats["deaths"] == 0 and flt.stats["failed"] == 0
        for p, r in zip(prompts, reqs):
            assert r.state == FINISHED
            assert r.output_tokens == _oracle_tokens(cfg, params, p, 8)
    finally:
        flt.close()
