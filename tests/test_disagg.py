"""Disaggregated serving (round 12): chunked prefill + prefill/decode
split over the paged-KV block handoff (serving/disagg.py).

The acceptance contract: greedy outputs are TOKEN-IDENTICAL across all
three serving modes — whole prefill, chunked prefill, disaggregated
prefill->decode handoff — against the sequential ``generate()`` oracle,
the decode ROLE compiles its decode step exactly once, and a replica
kill at any of ``serve.chunk`` / ``serve.handoff`` /
``serve.handoff_drop`` ends with every request COMPLETED (token-exact)
or FAILED-within-retry-budget while the shared pool's free+refcounted
accounting balances after recovery.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.runtime import heartbeat as hb
from deepspeed_tpu.serving.disagg import (BlockHandoff, DisaggEngine,
                                          HandoffFull, HandoffItem)
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.fleet import ServingFleet
from deepspeed_tpu.serving.kv_cache import BlockPool
from deepspeed_tpu.serving.scheduler import FINISHED, Request, TIMEOUT
from deepspeed_tpu.testing import chaos


@pytest.fixture(scope="module")
def tiny():
    # f32: greedy token-exactness across differently-fused programs (see
    # test_serving.py's fixture note)
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


SERVE_CFG = {"block_size": 16, "pool_blocks": 64, "max_batch": 4,
             "max_blocks_per_seq": 8}


def _fleet_serving(prefill=1, decode=1, chunk=10, **fleet_kw):
    fleet = {"prefill_replicas": prefill, "decode_replicas": decode,
             "poll_interval": 0.05, "heartbeat_interval": 0.02,
             "heartbeat_timeout": 60.0}
    fleet.update(fleet_kw)
    return dict(SERVE_CFG, max_batch=2, prefill_chunk_tokens=chunk,
                fleet=fleet)


# ---------------------------------------------------------------------------
# the acceptance-criteria three-mode matrix
# ---------------------------------------------------------------------------

# tier-2 (round-17 budget sweep, ~14s): the cheaper tier-1 cousins are
# test_chunked_prefill_fairness_no_stall_beyond_one_chunk,
# test_disagg_handoff_chaos_refcount_exact and
# test_init_inference_serve_disagg_entry; scripts/chaos.sh and
# scripts/tier2.sh run this acceptance matrix
@pytest.mark.slow
def test_three_modes_staggered_token_exact(tiny):
    """Whole prefill, chunked prefill (non-block-aligned chunk) and the
    disaggregated pair produce IDENTICAL greedy outputs for a staggered
    multi-request load — and the disagg decode role compiles exactly one
    decode step while its prefill role never traces one."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    # 4 distinct lengths over 6 requests: mixed-length coverage while
    # the sequential-generate oracle compiles only 4 programs
    lens = [5, 11, 21, 33, 11, 5]
    prompts = [list(rng.integers(1, 64, size=n)) for n in lens]
    new = 6
    oracles = [_oracle_tokens(cfg, params, p, new) for p in prompts]

    def drive(eng):
        reqs = [eng.submit(p, new) for p in prompts[:3]]
        eng.step(); eng.step()
        reqs += [eng.submit(p, new) for p in prompts[3:]]
        for _ in range(2000):
            if eng.idle:
                break
            eng.step()
        return [r.output_tokens for r in reqs]

    whole = drive(ServingEngine(cfg, params, serving=SERVE_CFG))
    chunked = drive(ServingEngine(
        cfg, params, serving=dict(SERVE_CFG, prefill_chunk_tokens=10)))
    dis = DisaggEngine(cfg, params,
                       serving=dict(SERVE_CFG, prefill_chunk_tokens=10))
    disagg = drive(dis)
    for p, o, w, c, d in zip(prompts, oracles, whole, chunked, disagg):
        assert w == o, f"whole diverged for {p}"
        assert c == o, f"chunked diverged for {p}"
        assert d == o, f"disagg diverged for {p}"
    # fixed-shape discipline across the split: decode role compiles its
    # decode step ONCE and never traces a prefill; prefill role never
    # traces a decode
    cache_size = getattr(dis.decode._decode_fn, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1
        assert dis.decode._prefill_fn._cache_size() == 0
        assert dis.prefill._decode_fn._cache_size() == 0
    dis.close()


@pytest.mark.slow
def test_three_modes_arch_matrix_token_exact():
    """The acceptance arch matrix: ALiBi+softcap and GQA+rotary+RMSNorm
    serve token-identical across whole / chunked / disagg modes."""
    archs = [
        dict(pos_embed="alibi", attn_softcap=20.0, final_logit_softcap=15.0,
             norm="layernorm"),
        dict(pos_embed="rotary", norm="rmsnorm", gated_mlp=True,
             activation="silu", num_kv_heads=2, tie_embeddings=False),
    ]
    rng = np.random.default_rng(13)
    for kw in archs:
        model, cfg = build_model("gpt2-tiny", hidden_size=32, num_layers=2,
                                 num_heads=4, vocab_size=64, max_seq_len=128,
                                 attention_impl="reference",
                                 dtype=jnp.float32, **kw)
        ids = np.zeros((1, 8), np.int32)
        params = model.init(jax.random.PRNGKey(1),
                            {"input_ids": ids})["params"]
        prompts = [list(rng.integers(1, 64, size=n)) for n in (6, 21, 33)]
        oracles = [_oracle_tokens(cfg, params, p, 5) for p in prompts]
        scfg = {"block_size": 16, "pool_blocks": 32, "max_batch": 3,
                "max_blocks_per_seq": 8}
        for mode, eng in (
                ("whole", ServingEngine(cfg, params, serving=scfg)),
                ("chunked", ServingEngine(
                    cfg, params,
                    serving=dict(scfg, prefill_chunk_tokens=10))),
                ("disagg", DisaggEngine(
                    cfg, params,
                    serving=dict(scfg, prefill_chunk_tokens=10)))):
            outs = eng.generate_batch(prompts, max_new_tokens=5)
            for p, o, got in zip(prompts, oracles, outs):
                assert got == o, f"arch {kw} mode {mode} diverged"


# ---------------------------------------------------------------------------
# handoff queue units (host-side, no model)
# ---------------------------------------------------------------------------

def test_handoff_bounded_and_deadline_aware():
    pool = BlockPool(num_blocks=16, block_size=4)
    ho = BlockHandoff(pool, capacity=1)

    def item(req):
        blocks = pool.alloc(1)
        return HandoffItem(req=req, blocks=blocks,
                           table=np.asarray(blocks, np.int32), ctx=4,
                           last_tok=1)

    a = item(Request(prompt=[1], max_new_tokens=4))
    b = item(Request(prompt=[2], max_new_tokens=4))
    ho.push(a)
    with pytest.raises(HandoffFull):
        ho.push(b)                      # bounded: backpressure, no drop
    assert ho.pending == 1
    got = ho.pop()
    assert got is a and ho.pop() is None
    pool.release(got.blocks)
    # deadline-aware: an expired item is shed with TIMEOUT + release
    done = []
    expired_req = Request(prompt=[3], max_new_tokens=4,
                          deadline_ts=time.monotonic() - 1.0,
                          on_finish=lambda r: done.append(r.state))
    c = item(expired_req)
    ho.push(c)
    shed = ho.shed_expired()
    assert [it.req.rid for it in shed] == [expired_req.rid]
    assert expired_req.state == TIMEOUT and done == [TIMEOUT]
    pool.release(b.blocks)
    assert pool.used_count == 0         # every path returned its blocks


def test_handoff_push_failpoint_leaves_blocks_with_caller():
    """serve.handoff fires BEFORE the enqueue: the item is never
    half-queued, the blocks stay with the (dying) pusher — and a retry
    succeeds (the standalone prefill role's backpressure path)."""
    pool = BlockPool(num_blocks=8, block_size=4)
    ho = BlockHandoff(pool, capacity=4)
    blocks = pool.alloc(1)
    it = HandoffItem(req=Request(prompt=[1], max_new_tokens=2),
                     blocks=blocks, table=np.asarray(blocks, np.int32),
                     ctx=4, last_tok=0)
    chaos.arm("serve.handoff", "raise", times=1)
    try:
        with pytest.raises(chaos.ChaosError):
            ho.push(it)
        assert ho.pending == 0 and pool.refcount(blocks[0]) == 1
        ho.push(it)                     # retry lands
        assert ho.pending == 1
    finally:
        chaos.disarm()


# ---------------------------------------------------------------------------
# chunked-prefill + handoff refcount exactness under chaos (standalone)
# ---------------------------------------------------------------------------

def test_disagg_handoff_chaos_refcount_exact(tiny):
    """A serve.handoff crash mid-run: the pushed-but-failed item is
    retried, every request finishes token-exact, and afterwards the
    shared pool shows NO leak and NO double-free (release raises on
    double-free, so a clean used_count==0 after cache clear proves
    both)."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 21, 33)]
    eng = DisaggEngine(cfg, params,
                       serving=dict(SERVE_CFG, prefill_chunk_tokens=10))
    reqs = [eng.submit(p, 6) for p in prompts]
    chaos.arm("serve.handoff", "raise", times=1)
    try:
        raised = False
        for _ in range(2000):
            if eng.idle:
                break
            try:
                eng.step()
            except chaos.ChaosError:
                raised = True
        assert raised and chaos.fired("serve.handoff")
        for p, r in zip(prompts, reqs):
            assert r.state == FINISHED
            assert r.output_tokens == _oracle_tokens(cfg, params, p, 6)
        eng.shared.prefix_cache.clear()
        assert eng.pool.used_count == 0
    finally:
        chaos.disarm()


# ---------------------------------------------------------------------------
# chunked-prefill admission fairness
# ---------------------------------------------------------------------------

def test_chunked_prefill_fairness_no_stall_beyond_one_chunk(tiny):
    """A long prompt admitted mid-decode must not stall running lanes:
    with chunked prefill every loop iteration still runs the decode
    step, so the running request gains EXACTLY one token per iteration
    (max inter-token gap = 1 iteration) while the long prefill spans
    multiple iterations."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, prefill_chunk_tokens=16))
    rng = np.random.default_rng(5)
    short_prompt = list(rng.integers(1, 64, size=5))
    runner = eng.submit(short_prompt, 24)
    eng.step()                           # admitted + prefill started
    eng.step()                           # single chunk done + 1st decode
    assert runner.state == "RUNNING"
    long_prompt = list(rng.integers(1, 64, size=80))   # 5 chunks of 16
    eng.submit(long_prompt, 4)
    prefill_iters = 0
    while len(runner.output_tokens) < 24:
        before = len(runner.output_tokens)
        eng.step()
        if eng._prefilling is not None:
            prefill_iters += 1
        assert len(runner.output_tokens) == before + 1, \
            "running lane stalled behind the long prefill"
    assert prefill_iters >= 2, "long prompt should span several chunks"
    assert runner.output_tokens == _oracle_tokens(cfg, params,
                                                  short_prompt, 24)


# ---------------------------------------------------------------------------
# disagg fleet: kill matrix (tier-1 keeps one failpoint; slow runs all)
# ---------------------------------------------------------------------------

def _drive_fleet_kill(tiny, failpoint, skip=1):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 21, 33, 11)]
    emitted = {}
    flt = ServingFleet(cfg, params,
                       serving=_fleet_serving(retry_budget=3))
    reqs = [flt.submit(
        p, 8, on_token=lambda r, t: emitted.setdefault(r.rid, [])
        .append(t)) for p in prompts]
    chaos.arm(failpoint, "raise", times=1, skip=skip)
    try:
        flt.start()
        assert flt.drain(timeout=180), f"{failpoint}: drain failed"
        assert chaos.fired(failpoint)
        assert flt.stats["deaths"] == 1
        for p, r in zip(prompts, reqs):
            oracle = _oracle_tokens(cfg, params, p, 8)
            assert r.state == FINISHED, (failpoint, r.state, r.error)
            assert r.output_tokens == oracle, \
                f"{failpoint}: request {r.rid} diverged after recovery"
            assert emitted[r.rid] == oracle, \
                f"{failpoint}: token re-fired or dropped"
        flt.close()
        flt._drain_quarantine()
        # accounting balance: after release of the prefix cache's own
        # refs, free + refcounted must cover the whole pool (no leak; a
        # double-free would have raised inside release)
        flt._shared.prefix_cache.clear()
        assert flt._shared.pool.used_count == 0, \
            f"{failpoint}: leaked {flt._shared.pool.used_count} blocks"
        return flt
    finally:
        chaos.disarm()


@pytest.mark.slow
def test_disagg_fleet_kill_at_handoff_exactly_once(tiny):
    """Prefill replica killed AT the handoff push: blocks released via
    quarantine, half-done request requeued exactly-once, outputs
    token-exact, pool accounting balanced. (slow: the tier-1 cousins are
    the single-request serve.chunk fleet kill below and the standalone
    serve.handoff chaos leg; scripts/chaos.sh and tier2 run this and the
    full matrix.)"""
    flt = _drive_fleet_kill(tiny, "serve.handoff")
    death = flt.deaths[0]
    assert death["replica"] == 0 and death["reason"] == "crash"
    assert flt.stats["restarts"] == 1


@pytest.mark.slow
def test_disagg_fleet_crash_matrix_all_failpoints(tiny):
    """The full crash-at-every-failpoint matrix: serve.chunk (prefill
    mid-chunk), serve.handoff (push), serve.handoff_drop (pop->install
    window on the decode side)."""
    for fp in ("serve.chunk", "serve.handoff", "serve.handoff_drop"):
        _drive_fleet_kill(tiny, fp)


def test_disagg_fleet_requeue_carries_chunk_progress(tiny):
    """A prefill replica killed mid-chunk requeues its half-prefilled
    request with the chunk progress carried (observability contract) —
    and the retry still completes token-exact."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    long_prompt = list(rng.integers(1, 64, size=40))   # 4 chunks of 10
    flt = ServingFleet(cfg, params, serving=_fleet_serving(retry_budget=2))
    req = flt.submit(long_prompt, 6)
    # skip=2: the kill lands on a LATER chunk of the same prefill, so
    # progress is provably > 0 when the replica dies
    chaos.arm("serve.chunk", "raise", times=1, skip=2)
    try:
        flt.start()
        assert req.wait(timeout=120)
        assert req.state == FINISHED
        assert req.output_tokens == _oracle_tokens(cfg, params,
                                                   long_prompt, 6)
        assert req.retries == 1
        assert req.prefill_progress > 0, \
            "chunk progress of the dead leg should be carried"
        flt.close()
    finally:
        chaos.disarm()


# ---------------------------------------------------------------------------
# roles visible in dstpu health; init_inference entry
# ---------------------------------------------------------------------------

def test_health_shows_prefill_decode_roles(tmp_path, capsys):
    """PREFILL/DECODE role gauges ride the heartbeat records into
    ``dstpu health`` (round-12 acceptance: roles visible)."""
    from deepspeed_tpu.launcher.runner import health_main
    w0 = hb.HeartbeatWriter(str(tmp_path), rank=0, host="replica-0")
    w0.write(hb.PHASE_SERVE, 3, force=True,
             extra={"queue": 1, "active": 1, "lanes": 2,
                    "role": "PREFILL", "handoff": 0})
    w1 = hb.HeartbeatWriter(str(tmp_path), rank=1, host="replica-1")
    w1.write(hb.PHASE_SERVE, 9, force=True,
             extra={"queue": 0, "active": 2, "lanes": 2,
                    "role": "DECODE", "handoff": 0})
    rc = health_main([str(tmp_path)])
    out = capsys.readouterr().out
    assert "role=PREFILL" in out and "role=DECODE" in out
    assert rc == 0


@pytest.mark.slow
def test_disagg_fleet_stamps_role_gauges(tiny, tmp_path):
    """End-to-end: a running disagg fleet's heartbeat records carry the
    role gauge per replica. (slow: the tier-1 cousin is the record-level
    health rendering test above.)"""
    cfg, params = tiny
    flt = ServingFleet(cfg, params, serving=_fleet_serving(),
                       heartbeat_dir=str(tmp_path))
    try:
        flt.start()
        r = flt.submit([1, 2, 3, 4, 5], 4)
        assert r.wait(timeout=60) and r.state == FINISHED
        records = hb.read_heartbeats(str(tmp_path))
        roles = {rank: (rec.get("gauges") or {}).get("role")
                 for rank, rec in records.items()}
        assert roles.get(0) == "PREFILL" and roles.get(1) == "DECODE"
    finally:
        flt.close()


def test_init_inference_serve_disagg_entry(tiny):
    """serve() with fleet.prefill_replicas/decode_replicas returns a
    started disagg fleet even at replicas=1; output token-exact."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    cfg, params = tiny
    eng = deepspeed_tpu.init_inference(
        Transformer(cfg),
        {"dtype": "float32",
         "serving": dict(SERVE_CFG, max_batch=2, prefill_chunk_tokens=10,
                         fleet={"prefill_replicas": 1,
                                "decode_replicas": 1,
                                "poll_interval": 0.05})},
        model_parameters=params)
    srv = eng.serve()
    assert isinstance(srv, ServingFleet) and srv.disagg
    try:
        out = srv.generate_batch([[3, 1, 4, 1, 5], [2, 7, 2]],
                                 max_new_tokens=4)
        assert out[0] == _oracle_tokens(cfg, params, [3, 1, 4, 1, 5], 4)
        assert out[1] == _oracle_tokens(cfg, params, [2, 7, 2], 4)
    finally:
        srv.close()


def test_fleet_rejects_one_sided_disagg(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError):
        ServingFleet(cfg, params,
                     serving=dict(SERVE_CFG,
                                  fleet={"prefill_replicas": 1,
                                         "decode_replicas": 0}))
    # the serve() entry must also reject it — falling through to plain
    # single-engine serving would silently drop the operator's intent
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    eng = deepspeed_tpu.init_inference(
        Transformer(cfg),
        {"dtype": "float32",
         "serving": dict(SERVE_CFG, fleet={"prefill_replicas": 1})},
        model_parameters=params)
    with pytest.raises(ValueError):
        eng.serve()


# ---------------------------------------------------------------------------
# PR 31: the roles follow the loop's one signature and its lane state
# ---------------------------------------------------------------------------

def test_decode_role_warm_and_served_batch_share_one_program(tiny):
    """``DecodeEngine.warm()`` hands the decode program the loop's own kind
    of argument (one numpy int32 buffer, every lane idle), so warm-up and a
    served batch, greedy and sampled, leave ONE entry in the program's
    cache; the prefill role's warm leaves no counter behind."""
    cfg, params = tiny
    dis = DisaggEngine(cfg, params, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=10, sampling_filters=True))
    dis.decode.warm()
    dis.prefill.warm()
    assert dis.decode._decode_fn._cache_size() == 1
    assert dis.prefill.stats["step_inputs.transfers_sum"] == 0
    assert dis.pool.used_count == 0
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (7, 23, 12)]
    greedy = [dis.submit(p, 6) for p in prompts]
    sampled = dis.submit(prompts[0], 6, temperature=0.9, top_k=5)
    dis.run_until_idle()
    for p, r in zip(prompts, greedy):
        assert r.output_tokens == _oracle_tokens(cfg, params, p, 6)
    assert len(sampled.output_tokens) == 6
    assert dis.decode._decode_fn._cache_size() == 1
    assert dis.prefill._prefill_fn._cache_size() <= 2     # chunks of 16, 8
    dis.close()


def test_decode_role_launches_ahead_and_a_warm_retires_the_call(tiny):
    """The decode role launches its next call before it fetches the one in
    flight, over lanes that arrive by handoff with a token the host has read
    (they join the next call as they are: a free lane is none of the call in
    flight's); a ``warm()`` in mid-run retires the call in flight, its
    tokens booked, and leaves the loop's two device arrays alone, so the
    lanes go on token for token; ONE decode program through all of it."""
    cfg, params = tiny
    dis = DisaggEngine(cfg, params, serving=dict(SERVE_CFG,
                                                 prefill_chunk_tokens=10))
    dec = dis.decode
    rng = np.random.default_rng(12)
    sizes = ((9, 14), (25, 9), (14, 11), (31, 7), (5, 12), (18, 4))
    prompts = [rng.integers(1, 64, size=n).tolist() for n, _ in sizes]
    reqs = [dis.submit(p, m) for p, (_, m) in zip(prompts, sizes)]
    while dec.active < 2 or dec._flight is None:
        dis.step()
    booked = sum(len(r.output_tokens) for r in reqs)
    fed = dec._dec_out
    dec.warm()
    assert dec._flight is None and dec._dec_out is fed
    assert sum(len(r.output_tokens) for r in reqs) == booked + dec.active
    assert dec.stats["decode_ahead.retired_unread"] == 1
    dis.run_until_idle()
    for p, (_, m), r in zip(prompts, sizes, reqs):
        assert r.output_tokens == _oracle_tokens(cfg, params, p, m)
    calls = sum(1 for e in dec.rec.ring if e[0] == "serve.decode.dispatch")
    c = dec.stats
    # all but the first call, the one behind the warm, and one wherever the
    # lanes ran dry before the next handoff came
    assert calls - 4 <= c["decode_ahead.launched"] <= calls - 2
    assert c["decode_ahead.wasted_lane_tokens"] == 0
    # every lane's FIRST input is the handoff's host token, the rest come
    # off the device
    assert c["decode_ahead.device_lane_tokens_sum"] == \
        c["tokens_generated"] - len(reqs)
    assert dec._flight is None and dec._decode_fn._cache_size() == 1
    dis.close()


def test_handoff_writes_the_decode_roles_lane_rows(tiny):
    """Across the handoff the decode role's lane state is written where a
    popped item takes a lane and cleared where it finishes: every decode
    call's one buffer equals, field for field, the per-lane build from the
    requests and the lanes' block lists (tests/test_serving.py keeps the
    parent's build), and the held-block counts return to nothing."""
    from test_serving import _watch_device_calls
    cfg, params = tiny
    dis = DisaggEngine(cfg, params, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=10, sampling_filters=True))
    calls = _watch_device_calls(dis.decode)
    rng = np.random.default_rng(8)
    reqs = [dis.submit(rng.integers(1, 64, size=n).tolist(), m,
                       temperature=t, top_p=tp)
            for n, m, t, tp in ((9, 5, 0.0, None), (25, 9, 0.7, 0.9),
                                (14, 3, 0.0, None), (31, 7, 1.1, None),
                                (5, 12, 0.0, None), (18, 4, 0.3, 0.5))]
    dis.run_until_idle()
    assert all(len(r.output_tokens) == r.max_new_tokens for r in reqs)
    assert calls and set(calls) == {"decode"}
    dec = dis.decode
    assert dec.stats["step_inputs.lane_rows_written_sum"] == 2 * len(reqs)
    assert dec.stats["step_inputs.transfers_sum"] == len(calls)
    assert not dec._lanes.live.any() and not dec._lanes.tables.any()
    assert dec._held.reserved == 0 and dis.prefill._held.reserved == 0
    dis.close()
