"""Traffic-shaped serving (round 19): autoscaling fleet, priority lanes
with preemption, and overload-graceful admission.

Three layers, mirroring the code split:

* **Policy units** (serving/autoscale.py) — the AutoscalePolicy is pure
  and clock-injectable, so the false-flap guards are fake-clock unit
  tests: a single burst under cooldown causes at most ONE scale event,
  a warming replica's silence never triggers a scale-down, steady state
  produces zero events.
* **Queue/ladder units** (serving/scheduler.py) — TieredQueue ordering
  (highest tier first, FIFO within, aging floor, all-standard == exact
  FIFO) and the admit_or_shed overload ladder (batch highwater
  rejection, hard-full tier shedding, machine-readable
  AdmissionRejected — never a hang, never a silent drop).
* **Fleet end-to-end** (serving/fleet.py, thread placement in tier-1;
  the process placement rides tier-2) — scale-up under a burst and
  drain-down in the idle trough with greedy outputs token-exact vs
  sequential generate(), deadline-pressured preemption through the
  exactly-once requeue, and the crash matrix: serve.scale_up /
  serve.preempt failpoints, scale-down-during-kill, and
  preempt-during-replica-death never double-emit or lose a request.

Determinism notes follow tests/test_fleet.py: requests are submitted
BEFORE ``start()`` where dispatch timing matters, and the preemption
legs use ``max_batch=1`` so "no free lane" is a constructed fact, not a
race.
"""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config.config import AutoscaleConfig
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.runtime import heartbeat as hb
from deepspeed_tpu.serving.autoscale import (AUTOSCALER_RANK, SCALE_DOWN,
                                             SCALE_UP, AutoscalePolicy,
                                             Observation)
from deepspeed_tpu.serving.fleet import RETIRED, ServingFleet
from deepspeed_tpu.serving.scheduler import (BATCH, FINISHED, LATENCY, SHED,
                                             STANDARD, AdmissionRejected,
                                             Request, TieredQueue,
                                             admit_or_shed)
from deepspeed_tpu.testing import chaos


# ---------------------------------------------------------------------------
# policy units (fake clock — no fleet, no threads, no sleeps)
# ---------------------------------------------------------------------------

def _policy(**kw):
    kw.setdefault("enabled", True)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("up_queue_per_replica", 4)
    kw.setdefault("up_after", 2)
    kw.setdefault("down_idle_s", 10.0)
    kw.setdefault("cooldown_s", 15.0)
    return AutoscalePolicy(AutoscaleConfig(**kw))


def _obs(queue=0, live=1, warming=0, draining=0, active=0, pressured=0):
    return Observation(queue_depth=queue, pressured=pressured, live=live,
                       warming=warming, draining=draining,
                       active_lanes=active, total_lanes=live * 8)


def test_policy_single_burst_under_cooldown_at_most_one_event():
    """False-flap guard: a sustained burst produces exactly ONE scale-up
    until the cooldown expires, regardless of how many polls see it."""
    pol = _policy(cooldown_s=15.0, up_after=2)
    hot = _obs(queue=50, live=1)
    events = [pol.observe(hot, now=float(t)) for t in range(10)]
    assert events.count(SCALE_UP) == 1
    assert set(events) <= {SCALE_UP, None}
    # cooldown expiry: the STILL-hot fleet may scale again, exactly once
    events2 = [pol.observe(hot, now=20.0 + t) for t in range(10)]
    assert events2.count(SCALE_UP) == 1


def test_policy_warming_replica_silence_never_scales_down():
    """False-flap guard: while any replica warms (compiling off-path,
    gauges idle — compile is not idleness), NO verdict fires in either
    direction, and the idle/hot streaks reset so the warming window
    can't be double-counted once it lands."""
    pol = _policy(down_idle_s=1.0, cooldown_s=0.0)
    for t in range(100):                # 100s of "idle" while warming
        assert pol.observe(_obs(queue=0, live=1, warming=1,
                                active=0), now=float(t)) is None
    # warming also blocks scale-up (capacity already in flight)
    pol2 = _policy(cooldown_s=0.0)
    for t in range(10):
        assert pol2.observe(_obs(queue=99, live=1, warming=1),
                            now=float(t)) is None
    # once warmed, the idle trough must be UNBROKEN from here
    assert pol.observe(_obs(queue=0, live=2, active=0), now=100.0) is None
    assert pol.observe(_obs(queue=0, live=2, active=0),
                       now=101.5) == SCALE_DOWN


def test_policy_steady_state_zero_events():
    """Moderately loaded (below the trigger) and never idle: no events,
    ever — the autoscaler must not fidget under normal traffic."""
    pol = _policy(up_queue_per_replica=4, down_idle_s=5.0, cooldown_s=0.0)
    for t in range(200):
        obs = _obs(queue=3, live=2, active=4)  # 3 < 4*2, lanes busy
        assert pol.observe(obs, now=float(t) * 0.5) is None


def test_policy_hysteresis_and_bounds():
    pol = _policy(up_after=3, cooldown_s=0.0, max_replicas=2)
    hot = _obs(queue=50, live=1)
    assert pol.observe(hot, now=0.0) is None     # streak 1
    assert pol.observe(_obs(queue=0, live=1, active=1),
                       now=1.0) is None          # streak broken
    assert pol.observe(hot, now=2.0) is None
    assert pol.observe(hot, now=3.0) is None
    assert pol.observe(hot, now=4.0) == SCALE_UP
    # at max_replicas the verdict is withheld entirely
    assert pol.observe(_obs(queue=50, live=2), now=5.0) is None
    # at min_replicas the trough is ignored
    pol2 = _policy(min_replicas=1, down_idle_s=0.5, cooldown_s=0.0)
    for t in range(20):
        assert pol2.observe(_obs(queue=0, live=1, active=0),
                            now=float(t)) is None


def test_policy_deadline_pressure_triggers_without_queue_depth():
    pol = _policy(up_after=1, cooldown_s=0.0, up_queue_per_replica=100)
    assert pol.observe(_obs(queue=1, live=1, pressured=1),
                       now=0.0) == SCALE_UP


# ---------------------------------------------------------------------------
# tiered queue + overload ladder units
# ---------------------------------------------------------------------------

def _req(priority=STANDARD, arrival=None, deadline=None):
    r = Request(prompt=[1, 2], max_new_tokens=4, priority=priority)
    if arrival is not None:
        r.arrival_ts = arrival
    if deadline is not None:
        r.deadline_ts = deadline
    return r


def test_tiered_queue_orders_by_tier_then_fifo():
    tq = TieredQueue(aging_s=0)
    b = _req(BATCH, arrival=0.0)
    s1 = _req(STANDARD, arrival=1.0)
    s2 = _req(STANDARD, arrival=2.0)
    l1 = _req(LATENCY, arrival=3.0)
    for r in (b, s1, s2, l1):
        tq.append(r)
    assert [tq.popnext(now=4.0) for _ in range(4)] == [l1, s1, s2, b]


def test_tiered_queue_all_standard_is_exact_fifo():
    """The degeneration pin: single-tier traffic is the old deque — the
    strict-FIFO contract every round-8/11 test relies on."""
    tq = TieredQueue(aging_s=30.0)
    reqs = [_req(STANDARD, arrival=float(i)) for i in range(8)]
    for r in reqs:
        tq.append(r)
    assert list(tq) == reqs
    assert [tq.popnext(now=100.0) for _ in range(8)] == reqs


def test_tiered_queue_aging_floor_unstarves_batch():
    """A batch head older than aging_s competes at rank 0 — deferred,
    never starved."""
    tq = TieredQueue(aging_s=5.0)
    old_batch = _req(BATCH, arrival=0.0)
    young_lat = _req(LATENCY, arrival=8.0)
    tq.append(old_batch)
    tq.append(young_lat)
    # not yet aged: latency first
    assert tq.peeknext(now=4.0) is young_lat
    # aged past the floor: the batch head arrived first and now ties at
    # rank 0, so arrival order breaks the tie
    assert tq.peeknext(now=6.0) is old_batch


def test_tiered_queue_requeue_front_stays_in_own_tier():
    tq = TieredQueue(aging_s=0)
    s = _req(STANDARD, arrival=1.0)
    b1 = _req(BATCH, arrival=2.0)
    b2 = _req(BATCH, arrival=3.0)
    tq.append(s)
    tq.append(b2)
    tq.appendleft(b1)            # requeued batch: ahead of b2, behind s
    assert [tq.popnext(now=4.0) for _ in range(3)] == [s, b1, b2]


def test_admission_ladder_batch_highwater_and_hard_full():
    tq = TieredQueue(aging_s=0)
    for i in range(3):
        tq.append(_req(STANDARD, arrival=float(i)))
    # past the highwater fraction, NEW batch work is rejected
    # machine-readably while standard/latency still land
    with pytest.raises(AdmissionRejected) as ei:
        admit_or_shed(tq, _req(BATCH), max_queue=4, batch_highwater=0.5)
    assert ei.value.info["reason"] == "batch_highwater"
    assert "queue full" in str(ei.value)
    assert admit_or_shed(tq, _req(STANDARD, arrival=9.0),
                         max_queue=4, batch_highwater=0.5) is None
    # hard full + no lower tier to shed -> rejected, structured verdict
    with pytest.raises(AdmissionRejected) as ei:
        admit_or_shed(tq, _req(STANDARD), max_queue=4)
    info = ei.value.info
    assert info["error"] == "admission_rejected"
    assert info["reason"] == "queue_full" and info["max_queue"] == 4
    json.loads(str(ei.value).split(": ", 1)[1])   # message embeds JSON
    # hard full + a latency arrival: the YOUNGEST lowest-tier queued
    # request is shed to make room
    tq2 = TieredQueue(aging_s=0)
    b_old = _req(BATCH, arrival=0.0)
    b_young = _req(BATCH, arrival=5.0)
    for r in (b_old, _req(STANDARD, arrival=1.0), b_young,
              _req(STANDARD, arrival=2.0)):
        tq2.append(r)
    victim = admit_or_shed(tq2, _req(LATENCY), max_queue=4)
    assert victim is b_young
    assert len(tq2) == 4


# ---------------------------------------------------------------------------
# fleet end-to-end (thread placement; tiny model, token-exact oracles)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
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


def _serving(replicas=1, autoscale=None, max_batch=2, **fleet_kw):
    fleet = {"replicas": replicas, "poll_interval": 0.05,
             "heartbeat_interval": 0.02, "heartbeat_timeout": 60.0}
    if autoscale:
        fleet["autoscale"] = autoscale
    fleet.update(fleet_kw)
    return {"block_size": 16, "pool_blocks": 64, "max_batch": max_batch,
            "max_blocks_per_seq": 8, "fleet": fleet}


_SNAPPY_AS = {"enabled": True, "min_replicas": 1, "max_replicas": 2,
              "up_queue_per_replica": 1, "up_after": 2,
              "down_idle_s": 0.3, "cooldown_s": 0.2}


def test_fleet_autoscale_up_then_drain_down_token_exact(tiny):
    """The tentpole loop, end to end: a queue burst scales the fleet up
    (warmed — the new replica never serves cold), outputs stay
    token-exact vs sequential generate(), the idle trough drains the
    scaled-up replica back down through the straggler-drain path (EXIT
    terminal stamp, not STALLED), and every verdict lands in the
    capacity ledger and the autoscaler's heartbeat rank."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    # uniform length: one prefill + one oracle compile (tier-1 budget)
    prompts = [list(rng.integers(1, 64, size=8)) for _ in range(6)]
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(
        replicas=1, autoscale=_SNAPPY_AS))
    reqs = [flt.submit(
        p, 10, on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in prompts]
    try:
        flt.start()
        assert flt.drain(timeout=180)
        # drain() can return while the warm spawn is still compiling on
        # the supervisor thread; the event lands when the spawn finishes
        deadline = time.monotonic() + 60.0
        while flt.stats["scale_ups"] < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert flt.stats["scale_ups"] >= 1
        ups = [e for e in flt.scale_events if e.action == SCALE_UP]
        assert ups and ups[0].replica == 1 and "queue" in ups[0].reason
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 10)
            assert r.state == FINISHED and r.output_tokens == oracle
            assert emitted[r.rid] == oracle
        # idle trough: the scaled-up replica drains back down
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if flt.stats["scale_downs"] >= 1 \
                    and len(flt.live_replicas()) == 1:
                break
            time.sleep(0.02)
        assert flt.stats["scale_downs"] >= 1, flt.scale_events
        downs = [e for e in flt.scale_events if e.action == SCALE_DOWN]
        assert downs and downs[0].drained_ts is not None
        assert downs[0].error is None            # clean drain, not death
        assert flt._replicas[downs[0].replica].state == RETIRED
        assert flt.stats["deaths"] == 0 and flt.stats["restarts"] == 0
        # evidence: the retired replica concluded with EXIT (not
        # STALLED/silent) and the autoscaler rank carries the ledger
        recs = hb.read_heartbeats(flt.heartbeat_dir)
        assert recs[downs[0].replica]["phase"] == hb.PHASE_EXIT
        asr = recs[AUTOSCALER_RANK]
        assert asr["gauges"]["role"] == "AUTOSCALER"
        assert asr["gauges"]["events"] == len(flt.scale_events)
    finally:
        flt.close()


def test_fleet_autoscale_scale_up_crash_rolls_back(tiny):
    """serve.scale_up crash matrix: a failed warmed spawn rolls the slot
    back (no phantom replica), records an ``up_failed`` event, and the
    fleet keeps serving every request to conclusion."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, 64, size=7)) for _ in range(5)]
    flt = ServingFleet(cfg, params, serving=_serving(
        replicas=1, autoscale=_SNAPPY_AS))
    chaos.arm("serve.scale_up", "raise", times=1)
    reqs = [flt.submit(p, 10) for p in prompts]
    try:
        flt.start()
        assert flt.drain(timeout=180)
        assert chaos.fired("serve.scale_up")
        fails = [e for e in flt.scale_events if e.action == "up_failed"]
        assert fails and fails[0].error
        with flt._lock:
            assert all(r.idx == i for i, r in enumerate(flt._replicas))
        for p, r in zip(prompts, reqs):
            assert r.state == FINISHED
            assert r.output_tokens == _oracle_tokens(cfg, params, p, 10)
    finally:
        chaos.disarm()
        flt.close()


# tier-2 (round-19 budget, ~10s): the cheaper tier-1 cousins are
# test_fleet_autoscale_scale_up_crash_rolls_back (spawn-side crash)
# and test_fleet.test_fleet_kill_requeues_exactly_once_token_exact
# (the same requeue ledger, undrained); scripts/chaos.sh runs this leg
@pytest.mark.slow
def test_fleet_scale_down_during_kill_requeues_exactly_once(tiny):
    """Crash matrix: a DRAINING replica that dies mid-drain ends the
    drain by death — its lanes requeue through the exactly-once
    token-exact path, the death records action 'retired' (the
    autoscaler wanted the capacity gone: no strike, no replacement),
    and nothing double-emits."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 64, size=n))
               for n in (6, 10, 8, 12, 7, 9)]
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(replicas=2))
    reqs = [flt.submit(
        p, 16, on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in prompts]
    try:
        flt.start()
        deadline = time.monotonic() + 30.0
        while not flt._replicas[1].inflight:
            assert time.monotonic() < deadline, "replica 1 never dispatched"
            time.sleep(0.001)
        flt._replicas[1].draining = True         # scale-down in flight
        chaos.arm("serve.replica_kill", "raise", match="1", skip=2)
        assert flt.drain(timeout=180)
        assert chaos.fired("serve.replica_kill")
        assert flt.stats["deaths"] == 1
        assert flt.deaths[0]["action"] == "retired"
        assert flt.stats["restarts"] == 0        # capacity stays gone
        assert flt.live_replicas() == [0]
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 16)
            assert r.state == FINISHED and r.output_tokens == oracle
            assert emitted[r.rid] == oracle, \
                f"request {r.rid} re-fired or dropped a token"
    finally:
        chaos.disarm()
        flt.close()


def test_fleet_preemption_token_exact_no_retry_charge(tiny):
    """Deadline-pressured latency preempts the youngest RUNNING batch
    lane: the victim's emitted prefix is synced before eviction and it
    resumes token-exact (vs an uninjected sequential oracle) with NO
    retry-budget charge; the latency request takes the freed lane."""
    cfg, params = tiny
    rng = np.random.default_rng(17)
    bprompt = list(rng.integers(1, 64, size=9))
    lprompt = list(rng.integers(1, 64, size=6))
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(
        replicas=1, max_batch=1, preempt_pressure_s=30.0))
    batch_req = flt.submit(
        bprompt, 24, priority=BATCH,
        on_token=lambda r, t: emitted.setdefault(r.rid, []).append(t))
    try:
        flt.start()
        deadline = time.monotonic() + 30.0
        while not flt._replicas[0].inflight:
            assert time.monotonic() < deadline, "batch never dispatched"
            time.sleep(0.001)
        lat_req = flt.submit(lprompt, 8, priority=LATENCY, deadline_s=20.0)
        assert flt.drain(timeout=180)
        assert flt.stats["preempted"] == 1
        assert batch_req.preemptions == 1
        assert batch_req.retries == 0            # eviction is not failure
        for req, prompt, n in ((batch_req, bprompt, 24),
                               (lat_req, lprompt, 8)):
            oracle = _oracle_tokens(cfg, params, prompt, n)
            assert req.state == FINISHED and req.output_tokens == oracle
        assert emitted[batch_req.rid] == _oracle_tokens(
            cfg, params, bprompt, 24), "victim re-fired or lost a token"
    finally:
        flt.close()


# tier-2 (round-19 budget, ~9s): the cheaper tier-1 cousins are
# test_fleet_preemption_token_exact_no_retry_charge (clean preempt
# ledger) and the serve.preempt orphan economy asserted there; the
# death half rides test_fleet's kill legs; scripts/chaos.sh runs this
@pytest.mark.slow
def test_fleet_preempt_crash_then_replica_death_exactly_once(tiny):
    """Crash matrix: serve.preempt fires between eviction and requeue —
    the victim parks on the orphan list — and then the victim's OLD
    replica dies before the orphan retry lands. Nothing is lost and
    nothing double-emits: the orphan retry requeues the victim
    token-exactly (one retry charged, the documented orphan economy)
    and the death path requeues only what the dead replica still
    held."""
    cfg, params = tiny
    rng = np.random.default_rng(23)
    bprompts = [list(rng.integers(1, 64, size=n)) for n in (8, 10)]
    lprompt = list(rng.integers(1, 64, size=5))
    emitted = {}
    flt = ServingFleet(cfg, params, serving=_serving(
        replicas=2, max_batch=1, preempt_pressure_s=30.0))
    breqs = [flt.submit(
        p, 20, priority=BATCH,
        on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in bprompts]
    chaos.arm("serve.preempt", "raise", times=1)
    try:
        flt.start()
        deadline = time.monotonic() + 30.0
        while not (flt._replicas[0].inflight and flt._replicas[1].inflight):
            assert time.monotonic() < deadline, "lanes never filled"
            time.sleep(0.001)
        lat_req = flt.submit(lprompt, 8, priority=LATENCY, deadline_s=20.0)
        deadline = time.monotonic() + 30.0
        while not chaos.fired("serve.preempt"):
            assert time.monotonic() < deadline, "preemption never fired"
            time.sleep(0.001)
        # the victim (replica 0's batch lane — _maybe_preempt walks the
        # replicas in order) is orphan-parked; now its old replica dies
        # before/while the orphan retry lands
        victim = next(r for r in breqs if r.preemptions >= 1)
        chaos.arm("serve.replica_kill", "raise", match="0", times=1)
        assert flt.drain(timeout=180)
        assert flt.stats["preempted"] == 1
        assert victim.preemptions == 1
        assert victim.retries >= 1               # the orphan retry charges
        for req, prompt, n in ((breqs[0], bprompts[0], 20),
                               (breqs[1], bprompts[1], 20),
                               (lat_req, lprompt, 8)):
            oracle = _oracle_tokens(cfg, params, prompt, n)
            assert req.state == FINISHED and req.output_tokens == oracle
        for req, prompt in zip(breqs, bprompts):
            assert emitted[req.rid] == _oracle_tokens(
                cfg, params, prompt, 20), \
                f"request {req.rid} re-fired or dropped a token"
    finally:
        chaos.disarm()
        flt.close()


def test_fleet_overload_ladder_sheds_and_rejects_machine_readably(tiny):
    """Admission under overload, fleet-level: expired work sheds with
    TIMEOUT (existing), a hard-full queue rejects same-tier arrivals
    with the machine-readable AdmissionRejected, and a latency arrival
    at a hard-full queue sheds the youngest batch victim (concluded
    SHED, callback fired, structured error) — never a hang, never a
    silent drop."""
    cfg, params = tiny
    rng = np.random.default_rng(29)
    flt = ServingFleet(cfg, params, serving=_serving(
        replicas=1, max_queue=3, batch_highwater=0.99))
    shed = []
    p = list(rng.integers(1, 64, size=5))
    flt.submit(p, 4, priority=STANDARD)
    flt.submit(p, 4, priority=STANDARD)
    victim = flt.submit(p, 4, priority=BATCH,
                        on_finish=lambda r: shed.append(r))
    # hard full, batch arrival, nothing below batch: structured reject
    with pytest.raises(AdmissionRejected) as ei:
        flt.submit(p, 4, priority=BATCH)
    assert ei.value.info["reason"] == "queue_full"
    assert "queue full" in str(ei.value)
    # hard full, latency arrival: the batch victim is shed to make room
    kept = flt.submit(p, 4, priority=LATENCY)
    assert victim.state == SHED and shed == [victim]
    assert json.loads(victim.error)["reason"] == "displaced_by_tier"
    assert flt.stats["shed"] == 1
    assert kept.rid in flt._outstanding
    flt.close()


def test_fleet_submit_rejects_unknown_tier(tiny):
    cfg, params = tiny
    flt = ServingFleet(cfg, params, serving=_serving(replicas=1))
    with pytest.raises(ValueError, match="priority tier"):
        flt.submit([1, 2, 3], 4, priority="urgent")
    flt.close()


def test_autoscale_refuses_disagg(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="disagg"):
        ServingFleet(cfg, params, serving=_serving(
            replicas=1, autoscale=_SNAPPY_AS, prefill_replicas=1,
            decode_replicas=1))


def test_serve_entry_forces_fleet_for_floor1_autoscale(tiny):
    """replicas=1 + autoscale.enabled through init_inference().serve()
    must return a STARTED fleet — the single-engine path has no
    supervisor to grow capacity (the verify drive caught serve()
    falling through to a bare ServingEngine)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    cfg, params = tiny
    srv = deepspeed_tpu.init_inference(
        Transformer(cfg),
        {"dtype": "float32",
         "serving": {"block_size": 16, "pool_blocks": 32, "max_batch": 2,
                     "max_blocks_per_seq": 8,
                     "fleet": {"replicas": 1, "poll_interval": 0.05,
                               "heartbeat_interval": 0.02,
                               "autoscale": dict(_SNAPPY_AS)}}},
        model_parameters=params).serve()
    try:
        assert isinstance(srv, ServingFleet)
        assert srv.autoscale is not None and srv.autoscale.max_replicas == 2
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# tier-2: process placement + the bench trace row (slow — OS processes /
# full bench plumbing; the tier-1 cousins are the thread-placement legs
# above plus the policy/ladder units)
# ---------------------------------------------------------------------------

# tier-2 (round-19 budget): the cheaper tier-1 cousins are
# test_fleet_autoscale_up_then_drain_down_token_exact (same loop, thread
# placement) and the policy units; scripts/chaos.sh runs this leg
@pytest.mark.slow
def test_procfleet_autoscale_up_then_drain_down_token_exact(tiny, tmp_path):
    """The tentpole loop on the PROCESS placement: burst -> warmed
    worker-process spawn -> token-exact outputs -> idle trough ->
    drain, RETIRE, and a clean rc-0 worker exit (no death verdict)."""
    from deepspeed_tpu.serving.procfleet import ProcessFleet
    cfg, params = tiny
    rng = np.random.default_rng(31)
    prompts = [list(rng.integers(1, 64, size=n))
               for n in (5, 9, 7, 11, 6, 8)]
    scfg = _serving(replicas=1, autoscale=dict(_SNAPPY_AS, down_idle_s=0.5),
                    placement="process")
    flt = ProcessFleet(cfg, params, serving=scfg, log_dir=str(tmp_path))
    reqs = [flt.submit(p, 10) for p in prompts]
    try:
        flt.start()
        assert flt.drain(timeout=300)
        deadline = time.monotonic() + 60.0
        while flt.stats["scale_ups"] < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert flt.stats["scale_ups"] >= 1, flt.scale_events
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 10)
            assert r.state == FINISHED and r.output_tokens == oracle
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if flt.stats["scale_downs"] >= 1 \
                    and len(flt.live_replicas()) == 1:
                break
            time.sleep(0.05)
        assert flt.stats["scale_downs"] >= 1, flt.scale_events
        downs = [e for e in flt.scale_events if e.action == SCALE_DOWN]
        assert downs[0].drained_ts is not None and downs[0].error is None
        assert flt.stats["deaths"] == 0          # drain, not death
        rep = flt._replicas[downs[0].replica]
        assert rep.state == RETIRED
        deadline = time.monotonic() + 30.0
        while rep.proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rep.proc.poll() == 0              # clean stop, not a kill
    finally:
        flt.close()


# tier-2 (round-19 budget): the cheaper tier-1 cousin is
# test_fleet_preemption_token_exact_no_retry_charge (same contract,
# thread placement); scripts/chaos.sh runs this leg
@pytest.mark.slow
def test_procfleet_preempt_cancel_token_exact(tiny, tmp_path):
    """Preemption across the process boundary: the hub cancels the
    victim's lane in its worker, requeues it hub-side from the
    cumulative ledger, and both requests finish token-exact with no
    retry charge on the victim."""
    from deepspeed_tpu.serving.procfleet import ProcessFleet
    cfg, params = tiny
    rng = np.random.default_rng(37)
    bprompt = list(rng.integers(1, 64, size=9))
    lprompt = list(rng.integers(1, 64, size=6))
    scfg = _serving(replicas=1, max_batch=1, preempt_pressure_s=60.0,
                    placement="process")
    flt = ProcessFleet(cfg, params, serving=scfg, log_dir=str(tmp_path))
    try:
        flt.start()
        flt.warmup(timeout=240)
        batch_req = flt.submit(bprompt, 48, priority=BATCH)
        deadline = time.monotonic() + 60.0
        while not flt._replicas[0].inflight:
            assert time.monotonic() < deadline, "batch never dispatched"
            time.sleep(0.005)
        lat_req = flt.submit(lprompt, 8, priority=LATENCY, deadline_s=50.0)
        assert flt.drain(timeout=300)
        assert flt.stats["preempted"] == 1
        assert batch_req.preemptions == 1 and batch_req.retries == 0
        for req, prompt, n in ((batch_req, bprompt, 48),
                               (lat_req, lprompt, 8)):
            oracle = _oracle_tokens(cfg, params, prompt, n)
            assert req.state == FINISHED and req.output_tokens == oracle
    finally:
        flt.close()
