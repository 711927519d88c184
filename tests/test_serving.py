"""Continuous-batching serving loop: token-exactness, fixed-shape compile
discipline, block-pool admission control, prefix-cache COW, FIFO fairness,
chaos failpoints, SERVE heartbeat supervision.

The oracle everywhere is sequential ``models.generation.generate()`` —
greedy serving output must be TOKEN-EXACT with one-at-a-time generation
(same layer math through serving/model_runner.py), across staggered
arrivals, mixed lengths, admissions and evictions.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import TransformerConfig, build_model
from deepspeed_tpu.models.generation import generate
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.kv_cache import (BlockPool, BlockPoolExhausted,
                                            PrefixCache)
from deepspeed_tpu.serving.scheduler import QUEUED, RUNNING
from deepspeed_tpu.testing import chaos


@pytest.fixture(scope="module")
def tiny():
    # f32: the token-exactness contract compares greedy argmaxes between
    # two mathematically-identical-but-differently-fused programs; bf16's
    # 8-bit mantissa makes 1-ulp near-ties on a random tiny model likely
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


# ---------------------------------------------------------------------------
# the acceptance-criteria integration leg
# ---------------------------------------------------------------------------

# tier-2 (round-19 budget sweep, ~6s): the cheaper tier-1 cousins are
# test_fifo_fairness_under_full_pool + test_admission_eviction_protects
# _heads_own_prefix (admission/eviction ledger) and the fleet suites'
# token-exact e2e legs (test_fleet.py, test_autoscale.py);
# scripts/tier2.sh runs this 9-request staggered matrix
@pytest.mark.slow
def test_serving_integration_staggered_token_exact(tiny):
    """>= 8 concurrent requests, staggered arrivals, mixed lengths, greedy:
    token-exact vs sequential generate(), with EXACTLY ONE decode-step
    compile across all admissions/evictions (fixed-shape discipline)."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=SERVE_CFG)
    rng = np.random.default_rng(7)
    # 9 requests (> max_batch lanes), 4 distinct prompt lengths and 2
    # distinct generation lengths: mixed-length coverage while the
    # sequential-generate oracle compiles only 4 (T, max_new) programs
    # (tier-1 budget — each distinct pair is one _generate trace)
    lens = [5, 11, 17, 23, 5, 17, 11, 23, 11]
    prompts = [list(rng.integers(1, 64, size=n)) for n in lens]
    new = [6, 6, 8, 8, 6, 8, 6, 8, 6]     # per-length, so 4 oracle pairs
    finished = []
    # staggered: 3 up front, 3 after a couple of loop iterations, 3 after
    # the first completions — admissions ride a live, partially-full loop
    reqs = [eng.submit(prompts[i], new[i],
                       on_finish=lambda r: finished.append(r.rid))
            for i in range(3)]
    eng.step(); eng.step()
    reqs += [eng.submit(prompts[i], new[i]) for i in range(3, 6)]
    while eng.stats["completed"] == 0:
        eng.step()
    reqs += [eng.submit(prompts[i], new[i]) for i in range(6, 9)]
    eng.run_until_idle()

    assert eng.stats["completed"] == 9
    for p, n, r in zip(prompts, new, reqs):
        assert r.output_tokens == _oracle_tokens(cfg, params, p, n), \
            f"request {r.rid} diverged from sequential generate()"
    # the fixed-shape decode step compiled exactly once
    cache_size = getattr(eng._decode_fn, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax build has no PjitFunction._cache_size")
    assert cache_size() == 1
    assert finished                      # completion callbacks fired


def test_serving_pool_released_after_drain(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, prefix_cache=False))
    rng = np.random.default_rng(3)
    eng.generate_batch([list(rng.integers(1, 64, size=12))] * 3,
                       max_new_tokens=5)
    assert eng.pool.used_count == 0      # every block returned


# ---------------------------------------------------------------------------
# block pool + prefix cache units
# ---------------------------------------------------------------------------

def test_block_pool_alloc_release_refcounts():
    pool = BlockPool(num_blocks=8, block_size=16)
    assert pool.free_count == 7          # block 0 reserved
    a = pool.alloc(3)
    assert 0 not in a and pool.free_count == 4
    shared = pool.fork(a[:2])
    assert pool.refcount(a[0]) == 2
    pool.release(a)                      # first holder gone
    assert pool.free_count == 5          # a[2] back; a[0], a[1] still held
    assert pool.refcount(a[0]) == 1
    pool.release(shared)
    assert pool.free_count == 7
    with pytest.raises(BlockPoolExhausted):
        pool.alloc(8)
    with pytest.raises(ValueError):
        pool.fork([0])                   # null block is never shareable


def test_prefix_cache_match_insert_evict():
    pool = BlockPool(num_blocks=16, block_size=4)
    cache = PrefixCache(pool)
    toks = list(range(10))               # 2 full blocks + 2 tokens
    blocks = pool.alloc(3)
    cache.insert(toks, blocks)
    assert len(cache) == 2               # k=1 and k=2 prefixes
    n, forked = cache.match(toks)
    assert n == 8 and forked == blocks[:2]
    # owner + one ref per covering cache entry (k=1, k=2) + the fork:
    # per-entry refs keep partial eviction safe (dropping the k=2 entry
    # must not free the block the k=1 entry still serves)
    assert pool.refcount(blocks[0]) == 4
    pool.release(forked)
    # an 8-token prompt (exactly 2 blocks) must leave >= 1 token to
    # prefill: only the 1-block prefix may be reused
    n8, forked8 = cache.match(toks[:8])
    assert n8 == 4
    pool.release(forked8)
    # eviction under pressure releases LRU entries (owner refs remain)
    pool.release(blocks)
    cache.evict(pool.num_blocks)
    assert pool.used_count == 0


def test_prefix_cache_hash_collision_guard():
    pool = BlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool)
    blocks = pool.alloc(1)
    cache.insert([1, 2, 3, 4], blocks)
    n, forked = cache.match([9, 9, 9, 9, 5])
    assert n == 0 and forked == []


def test_serving_prefix_cow_blocks_are_shared_readonly(tiny):
    """Forked prefix blocks are refcounted and READ-ONLY: the consumer
    writes only above its fork point, the donor's block contents are
    bit-identical after the consumer runs, and freeing the donor does not
    corrupt the consumer (token-exactness holds throughout)."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=SERVE_CFG)
    rng = np.random.default_rng(11)
    sys_prompt = list(rng.integers(1, 64, size=32))      # 2 full blocks
    p1 = sys_prompt + list(rng.integers(1, 64, size=5))
    p2 = sys_prompt + list(rng.integers(1, 64, size=9))

    r1 = eng.submit(p1, 4)
    eng.run_until_idle()
    assert r1.output_tokens == _oracle_tokens(cfg, params, p1, 4)
    # the shared blocks live on in the prefix cache after r1 drained
    n, forked = eng.prefix_cache.match(p2)
    assert n == 32
    shared = list(forked)
    eng.pool.release(forked)             # undo the probe's fork
    snapshot = np.asarray(
        eng.pools["k"][:, :, shared[0] * 16:(shared[0] + 1) * 16])

    r2 = eng.submit(p2, 4)
    eng.run_until_idle()
    assert r2.prefix_hit_tokens == 32    # reused, not recomputed
    assert r2.output_tokens == _oracle_tokens(cfg, params, p2, 4)
    after = np.asarray(
        eng.pools["k"][:, :, shared[0] * 16:(shared[0] + 1) * 16])
    np.testing.assert_array_equal(snapshot, after)   # copy-on-write honored


# ---------------------------------------------------------------------------
# admission control / FIFO / chaos
# ---------------------------------------------------------------------------

def test_pool_exhaustion_queues_not_crashes(tiny):
    """More lifetime blocks than the pool holds: the overflow requests
    WAIT (admission control) and complete as earlier ones free blocks."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving={"block_size": 16, "pool_blocks": 5,
                                 "max_batch": 4, "max_blocks_per_seq": 4,
                                 "prefix_cache": False})
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, 64, size=20)) for _ in range(4)]
    reqs = [eng.submit(p, 6) for p in prompts]          # 2 blocks each, 4 free
    eng.step()
    assert eng.active == 2 and eng.scheduler.pending == 2   # budget-limited
    eng.run_until_idle()
    assert eng.stats["completed"] == 4
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == _oracle_tokens(cfg, params, p, 6)


def test_fifo_fairness_under_full_pool(tiny):
    """Strict FIFO: a big head request that does not fit blocks the small
    ones behind it — small traffic cannot starve a large request."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving={"block_size": 16, "pool_blocks": 7,
                                 "max_batch": 4, "max_blocks_per_seq": 6,
                                 "prefix_cache": False})
    rng = np.random.default_rng(6)
    running = eng.submit(list(rng.integers(1, 64, size=40)), 6)   # 3 blocks
    eng.step()
    assert eng.active == 1
    big = eng.submit(list(rng.integers(1, 64, size=60)), 6)       # 4 blocks
    small = eng.submit(list(rng.integers(1, 64, size=8)), 4)      # 1 block
    eng.step()
    # 3 free blocks: big does not fit; small WOULD fit but must wait
    assert big.state == QUEUED and small.state == QUEUED
    eng.run_until_idle()
    assert running.done and big.done and small.done
    assert big.first_token_ts <= small.first_token_ts    # FIFO admission


def test_prefill_failure_marks_failed_and_releases_blocks(tiny):
    """A deterministic forward failure mid-prefill must not leak blocks:
    the request is FAILED (callback fires, stats count it), the pool is
    whole, and the loop keeps serving."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, prefix_cache=False))
    boom = eng._prefill_fn
    eng._prefill_fn = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected prefill failure"))
    seen = []
    req = eng.submit([1, 2, 3, 4], 4, on_finish=lambda r: seen.append(r))
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    assert req.state == "FAILED" and "injected" in req.error
    assert seen and eng.stats["failed"] == 1
    assert eng.pool.used_count == 0          # nothing leaked
    eng._prefill_fn = boom
    ok = eng.submit([1, 2, 3, 4], 3)
    eng.run_until_idle()
    assert ok.done and ok.state == "FINISHED"


def test_admission_eviction_protects_heads_own_prefix(tiny):
    """Make-room eviction nets the head's prefix hit out of the budget and
    never evicts the entry the head is about to reuse."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving={"block_size": 16, "pool_blocks": 6,
                                 "max_batch": 2, "max_blocks_per_seq": 5})
    rng = np.random.default_rng(17)
    shared = list(rng.integers(1, 64, size=32))          # 2 full blocks
    r1 = eng.submit(shared + [5, 6], 2)                  # 3 blocks lifetime
    eng.run_until_idle()
    # cache holds the 2 shared blocks; 3 blocks free. The follower needs
    # 3 total, nets to 1 with the hit — admissible WITHOUT eviction even
    # though the gross budget (3) equals free (3): the hit survives
    r2 = eng.submit(shared + [7, 8, 9], 2)
    eng.run_until_idle()
    assert r1.done and r2.done
    assert r2.prefix_hit_tokens == 32        # the entry was not evicted


def test_chaos_serve_oom_keeps_request_queued(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=SERVE_CFG)
    rng = np.random.default_rng(8)
    prompt = list(rng.integers(1, 64, size=10))
    chaos.arm("serve.oom", "raise", times=2)
    req = eng.submit(prompt, 4)
    eng.step()
    assert req.state == QUEUED and not req.done     # deferred, not failed
    assert chaos.fired("serve.oom")
    eng.step(); eng.step()                          # failpoint exhausted
    eng.run_until_idle()
    assert req.done and req.output_tokens == \
        _oracle_tokens(cfg, params, prompt, 4)


def test_chaos_serve_enqueue_surfaces_to_caller(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=SERVE_CFG)
    chaos.arm("serve.enqueue", "raise")
    with pytest.raises(chaos.ChaosError):
        eng.submit([1, 2, 3], 4)
    # the loop itself is unharmed
    eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    assert eng.stats["completed"] == 1


def test_scheduler_rejects_overlong_and_full_queue(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, max_queue=1))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.submit(list(range(1, 60)) * 3, 128)     # 177 + 128 > 128
    eng.submit([1, 2, 3], 2)
    with pytest.raises(RuntimeError, match="queue full"):
        eng.submit([4, 5, 6], 2)


def test_submit_rejects_request_bigger_than_whole_pool(tiny):
    """A lifetime budget beyond the pool could NEVER be admitted — under
    strict FIFO it would wedge the queue forever while the loop keeps
    heartbeating. submit() must reject it synchronously."""
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving={"block_size": 16, "pool_blocks": 3,
                                 "max_batch": 2, "max_blocks_per_seq": 8,
                                 "prefix_cache": False})
    with pytest.raises(ValueError, match="pool has 2"):
        eng.submit(list(range(1, 40)), 16)          # needs 4 > 2 blocks
    # a fitting request still serves
    r = eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    assert r.done


# ---------------------------------------------------------------------------
# supervision + sampling + entry points
# ---------------------------------------------------------------------------

def test_serving_stamps_serve_heartbeat(tmp_path, tiny):
    import json
    from deepspeed_tpu.runtime.heartbeat import (PHASE_EXIT, PHASE_SERVE,
                                                 HeartbeatWriter,
                                                 heartbeat_path,
                                                 read_heartbeats)
    cfg, params = tiny
    hb = HeartbeatWriter(str(tmp_path), rank=0, min_interval=0.0,
                         refresh_interval=0.0)
    eng = ServingEngine(cfg, params, serving=SERVE_CFG, heartbeat=hb)
    eng.submit([1, 2, 3, 4], 3)
    eng.run_until_idle()
    eng.close()
    with open(heartbeat_path(str(tmp_path), 0), encoding="utf-8") as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    phases = [r["phase"] for r in recs]
    assert PHASE_SERVE in phases         # the loop was supervised
    assert read_heartbeats(str(tmp_path))[0]["phase"] == PHASE_EXIT
    # SERVE records carry queue/active/lanes load gauges (round 11)
    serve = [r for r in recs if r["phase"] == PHASE_SERVE]
    assert all(set(r["gauges"]) == {"queue", "active", "lanes"}
               for r in serve)
    assert any(r["gauges"]["active"] > 0 for r in serve)


def test_serving_context_manager_stamps_exit_and_health_reads_gauges(
        tmp_path, tiny, capsys):
    """Loop exit through the context manager stamps the EXIT terminal
    heartbeat, and `dstpu health` surfaces the SERVE gauges — a finished
    serving loop must read as a conclusion, never as silence."""
    from deepspeed_tpu.launcher.runner import health_main
    from deepspeed_tpu.runtime.heartbeat import (PHASE_EXIT,
                                                 HeartbeatWriter,
                                                 read_heartbeats)
    cfg, params = tiny
    hb = HeartbeatWriter(str(tmp_path), rank=0, min_interval=0.0,
                         refresh_interval=0.0)
    with ServingEngine(cfg, params, serving=SERVE_CFG, heartbeat=hb) as eng:
        eng.submit([5, 6, 7], 3)
        eng.run_until_idle()
        # still serving inside the block: latest record is SERVE w/ gauges
        rec = read_heartbeats(str(tmp_path))[0]
        assert rec["phase"] == "SERVE" and "gauges" in rec
        assert health_main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "GAUGES" in out and "lanes=4" in out
    assert read_heartbeats(str(tmp_path))[0]["phase"] == PHASE_EXIT
    assert health_main([str(tmp_path)]) == 0
    assert "clean exit" in capsys.readouterr().out


def test_scheduler_deadline_sheds_queued_with_timeout(tiny):
    """Engine-level satellite: a queued request past its deadline is shed
    with TIMEOUT at the next admission pass instead of waiting forever
    behind a too-big head (the strict-FIFO unbounded-wait edge); admitted
    requests are never shed."""
    import time as _time
    cfg, params = tiny
    eng = ServingEngine(cfg, params,
                        serving={"block_size": 16, "pool_blocks": 4,
                                 "max_batch": 1, "max_blocks_per_seq": 3,
                                 "prefix_cache": False})
    rng = np.random.default_rng(23)
    shed = []
    # head takes the lane and nearly the pool; the deadlined follower
    # can never be admitted behind it and must be shed, not starved
    head = eng.submit(list(rng.integers(1, 64, size=30)), 16,
                      deadline_s=30.0)          # admitted -> never shed
    late = eng.submit(list(rng.integers(1, 64, size=30)), 16,
                      deadline_s=0.01, on_finish=lambda r: shed.append(r))
    eng.step()
    assert head.state in ("PREFILL", "RUNNING")
    _time.sleep(0.03)
    eng.step()                                   # admission pass sheds
    assert late.state == "TIMEOUT" and late.done
    assert "deadline" in late.error and shed == [late]
    assert eng.stats["timeout"] == 1
    eng.run_until_idle()
    assert head.state == "FINISHED"              # deadline was queue-wait only
    assert eng.scheduler.timed_out == 1


def test_serving_eos_and_temperature_lanes(tiny):
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=SERVE_CFG)
    greedy = _oracle_tokens(cfg, params, [5, 6, 7, 8], 6)
    # eos cut: force eos at the first greedy token -> finishes after 1
    r_eos = eng.submit([5, 6, 7, 8], 6, eos_token_id=greedy[0])
    # a temperature lane rides the same compiled step
    r_temp = eng.submit([9, 10, 11], 6, temperature=0.8)
    eng.run_until_idle()
    assert r_eos.output_tokens == [greedy[0]]
    assert len(r_temp.output_tokens) == 6
    with pytest.raises(NotImplementedError):
        eng.submit([1, 2], 4, top_k=5)


def test_init_inference_serve_entry(tiny):
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import Transformer
    cfg, params = tiny
    module = Transformer(cfg)
    eng = deepspeed_tpu.init_inference(
        module, {"dtype": "float32",
                 "serving": {"block_size": 16, "pool_blocks": 32,
                             "max_batch": 2, "max_blocks_per_seq": 8}},
        model_parameters=params)
    srv = eng.serve()
    out = srv.generate_batch([[3, 1, 4, 1, 5]], max_new_tokens=4)
    assert out[0] == _oracle_tokens(cfg, params, [3, 1, 4, 1, 5], 4)


@pytest.mark.slow
def test_serving_arch_matrix_token_exact():
    """Heavier matrix: ALiBi+softcap (Gemma/BLOOM-class), sliding window,
    GQA+rotary+RMSNorm — each serves token-exact vs sequential
    generate()."""
    archs = [
        dict(pos_embed="alibi", attn_softcap=20.0, final_logit_softcap=15.0,
             norm="layernorm"),
        dict(layer_windows=(32, 32), pos_embed="rotary"),
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
        eng = ServingEngine(cfg, params,
                            serving={"block_size": 16, "pool_blocks": 32,
                                     "max_batch": 3, "max_blocks_per_seq": 8})
        prompts = [list(rng.integers(1, 64, size=n)) for n in (6, 13, 21)]
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            assert r.output_tokens == _oracle_tokens(cfg, params, p, 5), \
                f"arch {kw} diverged"


# ---------------------------------------------------------------------------
# round 12: chunked prefill, per-lane top-k/top-p, int8 paged KV pool
# ---------------------------------------------------------------------------

# tier-2 (round-17 budget sweep, ~10s): the cheaper tier-1 cousins are
# test_disagg.test_chunked_prefill_fairness_no_stall_beyond_one_chunk and
# test_disagg.test_disagg_fleet_requeue_carries_chunk_progress (same
# chunk machinery under fault); scripts/tier2.sh runs this compile-bound pin
@pytest.mark.slow
def test_chunked_prefill_token_exact_and_compile_bound(tiny):
    """A non-block-aligned chunk size is token-exact vs whole prefill,
    and the chunk machinery adds at most ONE extra prefill bucket (all
    full chunks share the chunk's block-rounded width; the final partial
    chunk lands in an existing bucket here)."""
    cfg, params = tiny
    rng = np.random.default_rng(23)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (35, 50, 7)]
    whole = ServingEngine(cfg, params, serving=SERVE_CFG)
    outs_whole = whole.generate_batch(prompts, max_new_tokens=5)
    chunked = ServingEngine(cfg, params,
                            serving=dict(SERVE_CFG,
                                         prefill_chunk_tokens=10))
    outs_chunked = chunked.generate_batch(prompts, max_new_tokens=5)
    assert outs_chunked == outs_whole
    for p, o in zip(prompts, outs_whole):
        assert o == _oracle_tokens(cfg, params, p, 5)
    cache_size = getattr(chunked._prefill_fn, "_cache_size", None)
    if cache_size is not None:
        # chunks of 10 bucket to 16: every call (full chunks AND the
        # <=10-token finals) is the same [1, 16] program. The bound is
        # <= 2, not == 1: the very first prefill call can specialize
        # separately (fresh jnp.zeros pools vs donated committed pools —
        # e.g. when an earlier test left a global mesh set), which is a
        # one-time sharding entry, not a per-bucket retrace. Whole
        # prefill pays one bucket PER suffix width (48, 64, 16 here), so
        # chunking must strictly reduce specializations.
        assert cache_size() <= 2
        whole_size = getattr(whole._prefill_fn, "_cache_size")()
        assert cache_size() < whole_size


def test_lane_topk_topp_parity_with_generate_sample():
    """The vectorized per-lane filter + categorical at one key is
    token-identical to models.generation._sample at the same key, per
    lane, across greedy/top-k/top-p/combined lanes (the satellite's
    parity contract)."""
    from deepspeed_tpu.models.generation import _sample
    from deepspeed_tpu.serving.engine import lane_topk_topp
    rng = np.random.default_rng(0)
    lanes = [(0.7, 5, None), (1.0, None, 0.9), (0.5, 8, 0.5),
             (1.3, None, None), (0.9, 1, None), (0.8, 3, 0.95)]
    logits = jnp.asarray(rng.normal(size=(len(lanes), 64)),
                         jnp.float32)
    temps = jnp.asarray([t for t, _, _ in lanes], jnp.float32)
    tks = jnp.asarray([k or 0 for _, k, _ in lanes], jnp.int32)
    tps = jnp.asarray([p if p is not None else 1.0 for _, _, p in lanes],
                      jnp.float32)
    key = jax.random.PRNGKey(42)
    filtered = lane_topk_topp(logits / temps[:, None], tks, tps)
    for b, (t, k, p) in enumerate(lanes):
        ref = int(np.asarray(_sample(logits[b:b + 1], key, t, k, p))[0])
        got = int(np.asarray(jax.random.categorical(
            key, filtered[b:b + 1], axis=-1))[0])
        assert got == ref, f"lane {b} ({t}, {k}, {p}) diverged"


def test_sampling_filters_guard_and_greedy_invariance(tiny):
    """top_k/top_p raise without serving.sampling_filters (off by
    default: the nucleus filter puts a sort in the decode step); with the
    flag on, greedy lanes stay oracle-exact next to filtered lanes and
    the decode step still compiles once."""
    cfg, params = tiny
    eng_off = ServingEngine(cfg, params, serving=SERVE_CFG)
    with pytest.raises(NotImplementedError):
        eng_off.submit([1, 2, 3], 4, top_k=5)
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, sampling_filters=True))
    p = [5, 9, 2, 33, 7]
    r_greedy = eng.submit(p, 5)
    r_filt = eng.submit(p, 5, temperature=0.8, top_k=4, top_p=0.9)
    eng.run_until_idle()
    assert r_greedy.output_tokens == _oracle_tokens(cfg, params, p, 5)
    assert len(r_filt.output_tokens) == 5
    cache_size = getattr(eng._decode_fn, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() == 1


@pytest.fixture
def paged_kernel_traces(monkeypatch):
    """One entry per trace that reaches the Pallas paged kernel (True: with
    int8 scales) — a "kernel leg" whose list stays empty is comparing the
    reference with itself."""
    import deepspeed_tpu.ops.pallas.paged_attention as paged_mod
    traces = []
    real = paged_mod.paged_attention

    def spy(*args, **kw):
        traces.append(kw.get("k_scale") is not None)
        return real(*args, **kw)

    monkeypatch.setattr(paged_mod, "paged_attention", spy)
    return traces


@pytest.mark.parametrize("mode", ["chunked", "prefix_hit", "whole"])
def test_prefill_through_the_kernel_is_token_exact(tiny, monkeypatch, mode):
    """A prefill's attention rides the paged kernel (PR 37; interpreted
    here), its chunk's own keys read from the pool it has just written:
    greedy streams stay token-exact with sequential ``generate()`` under
    chunked prefill (chunks of 10 pad to one block and start and end in
    mid-block), after a prefix-cache hit (the suffix's first query sits at
    the hit's end) and under whole prefill; the kernel is traced at the
    prefill's rows, and the engine says so."""
    import deepspeed_tpu.ops.pallas.paged_attention as paged_mod
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_impl="auto")
    rows, real = set(), paged_mod.paged_attention

    def spy(q, *args, **kw):
        rows.add(q.shape[2])
        return real(q, *args, **kw)

    monkeypatch.setattr(paged_mod, "paged_attention", spy)
    chunk = 0 if mode == "whole" else 10 if mode == "chunked" else 32
    eng = ServingEngine(cfg, params, interpret=True, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=chunk))
    rng = np.random.default_rng(41)
    shared = list(rng.integers(1, 64, size=37))
    prompts = [shared + list(rng.integers(1, 64, size=n)) for n in (9, 4)] \
        if mode == "prefix_hit" else \
        [list(rng.integers(1, 64, size=n)) for n in (37, 23)]
    outs = [eng.generate_batch([p], max_new_tokens=5)[0] for p in prompts]
    for p, o in zip(prompts, outs):
        assert o == _oracle_tokens(cfg, params, p, 5)
    if mode == "prefix_hit":
        assert eng.stats["prefix_hit_tokens"] == 32     # two whole blocks
    want = {"chunked": {1, 16}, "prefix_hit": {1, 16, 32},
            "whole": {1, 32, 48}}[mode]
    assert rows == want, rows
    paths = eng.telemetry()["gauges"]["paged.prefill_path"]
    assert {k: sorted(v) for k, v in paths.items()} == \
        {"kernel": sorted(want - {1})}


#: grouped-query models at a tiny size: query heads, KV heads, head_dim.
#: Heads of 128 take the kernel that copies its own pages, narrower ones
#: come through the pipeline a page a grid step (decode; a chunk of theirs
#: rides the loop kernel under the interpreter only)
_GQA = {"llama-1.1b-narrow-heads": ("llama-1.1b", 4, 2, 16),
        "mistral-like-128-wide": ("llama-1.1b", 4, 1, 128),
        "multi-query": ("gpt2-tiny", 2, 1, 16)}


@functools.lru_cache(maxsize=None)
def _gqa_model(name):
    preset, nh, kvh, hd = _GQA[name]
    model, cfg = build_model(
        preset, hidden_size=nh * hd, num_layers=2, num_heads=nh,
        num_kv_heads=kvh, mlp_dim_override=64, vocab_size=64,
        max_seq_len=256, dtype=jnp.float32)
    assert cfg.attention_impl == "auto" and cfg.kv_heads == kvh
    params = model.init(jax.random.PRNGKey(2),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    return cfg, params


@pytest.mark.parametrize("model,mode,kv", [
    ("mistral-like-128-wide", "chunked", "model_dtype"),
    ("mistral-like-128-wide", "prefix_hit", "model_dtype"),
    ("mistral-like-128-wide", "whole", "model_dtype"),
    ("mistral-like-128-wide", "chunked", "int8"),
    ("llama-1.1b-narrow-heads", "chunked", "model_dtype"),
    ("multi-query", "prefix_hit", "model_dtype")])
def test_grouped_query_serve_is_token_exact_at_kv_heads(model, mode, kv):
    """A grouped-query model's pool holds its KV heads (PR 44), the paged
    kernel (interpreted here) reads one stored head for the query heads that
    share it, and greedy streams stay token-exact with sequential
    ``generate()``, whose dense cache still holds a row a query head: under
    chunked prefill (chunks of 10 start and end in mid-block), across a
    prefix-cache hit and under whole prefill. The int8 tier stores its
    scales at the KV heads too and agrees with its own gather reference."""
    cfg, params = _gqa_model(model)
    chunk = 0 if mode == "whole" else 10 if mode == "chunked" else 32
    serving = dict(SERVE_CFG, prefill_chunk_tokens=chunk,
                   **({"kv_cache_dtype": "int8"} if kv == "int8" else {}))
    eng = ServingEngine(cfg, params, interpret=True, serving=serving)
    L, slots = cfg.num_layers, SERVE_CFG["pool_blocks"] * 16
    assert eng.pools["k"].shape == (L, cfg.kv_heads, slots, cfg.head_dim)
    per_token = 2 * L * cfg.kv_heads * cfg.head_dim * 4
    if kv == "int8":
        assert eng.pools["k_scale"].shape == (L, cfg.kv_heads, slots, 1)
        per_token = 2 * L * cfg.kv_heads * (cfg.head_dim + 4)
    gauges = eng.telemetry()["gauges"]
    assert gauges["kv.stored_heads"] == cfg.kv_heads < cfg.num_heads
    assert gauges["kv.bytes_per_token"] == per_token
    rng = np.random.default_rng(43)
    shared = list(rng.integers(1, 64, size=37))
    prompts = [shared + list(rng.integers(1, 64, size=n)) for n in (9, 4)] \
        if mode == "prefix_hit" else \
        [list(rng.integers(1, 64, size=n)) for n in (37, 23)]
    outs = [eng.generate_batch([p], max_new_tokens=5)[0] for p in prompts]
    if mode == "prefix_hit":
        assert eng.stats["prefix_hit_tokens"] == 32     # two whole blocks
    paths = eng.telemetry()["gauges"]["paged.prefill_path"]
    assert list(paths) == ["kernel"], paths
    if kv == "int8":
        twin = ServingEngine(
            dataclasses.replace(cfg, attention_impl="reference"), params,
            serving=serving)
        assert outs == [twin.generate_batch([p], max_new_tokens=5)[0]
                        for p in prompts]
        return
    for p, o in zip(prompts, outs):
        assert o == _oracle_tokens(cfg, params, p, 5)


def test_int8_kv_pool_parity_jnp_and_kernel(tiny, paged_kernel_traces):
    """The quantized pool tier (serving.kv_cache_dtype='int8'):
    quantize-on-write, dequantize IN-kernel (round 17 — the round-12
    construction guard is gone). Greedy outputs match the f32 oracle
    within the int8 error bound (token-equal on this fixture — f32
    compute, real logit gaps) on BOTH decode paths: the jnp
    gather-then-dequant reference AND the Pallas kernel's int8 tier
    (interpret=True forces it on CPU), which must also agree with each
    other token-for-token."""
    cfg, params = tiny
    rng = np.random.default_rng(29)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 21)]
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, kv_cache_dtype="int8"))
    assert eng.pools["k"].dtype == jnp.int8
    assert eng.pools["k_scale"].dtype == jnp.float32
    outs = eng.generate_batch(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        assert o == _oracle_tokens(cfg, params, p, 6), \
            "int8 pool beyond the quantization error bound"
    assert not paged_kernel_traces, "the jnp leg reached the kernel"
    # the Pallas int8 tier: same pools, dequant in-kernel (the fixture's
    # attention_impl="reference" would serve on the gather oracle)
    eng_k = ServingEngine(dataclasses.replace(cfg, attention_impl="auto"),
                          params,
                          serving=dict(SERVE_CFG, kv_cache_dtype="int8"),
                          interpret=True)
    outs_k = eng_k.generate_batch(prompts, max_new_tokens=6)
    assert paged_kernel_traces and all(paged_kernel_traces), \
        "the kernel leg never reached the int8 paged kernel"
    assert outs_k == outs, "in-kernel dequant diverged from the jnp path"


def test_int8_weight_only_decode_parity(tiny, paged_kernel_traces):
    """serving.weight_dtype='int8' (round 17): dense kernels pack ONCE to
    blockwise int8 + per-256-element f32 scales and every decode matmul
    rides the quant path. Greedy outputs are token-equal with the
    unquantized oracle on this fixture (f32 compute, real logit gaps
    exceed the <=absmax/127 weight error), the packed leaves are
    genuinely int8, and the kernel (interpret) and jnp reference paths
    agree token-for-token."""
    cfg, params = tiny
    rng = np.random.default_rng(31)
    prompts = [list(rng.integers(1, 64, size=n)) for n in (4, 18)]
    eng = ServingEngine(cfg, params,
                        serving=dict(SERVE_CFG, weight_dtype="int8"))
    blk = eng.params["blocks"]
    assert blk["attn_qkv"]["kernel"].dtype == jnp.int8
    assert blk["attn_qkv"]["kernel_qscale"].dtype == jnp.float32
    outs = eng.generate_batch(prompts, max_new_tokens=6)
    for p, o in zip(prompts, outs):
        assert o == _oracle_tokens(cfg, params, p, 6), \
            "int8 weight-only decode beyond the quantization error bound"
    eng_k = ServingEngine(dataclasses.replace(cfg, attention_impl="auto"),
                          params,
                          serving=dict(SERVE_CFG, weight_dtype="int8",
                                       kv_cache_dtype="int8"),
                          interpret=True)
    outs_k = eng_k.generate_batch(prompts, max_new_tokens=6)
    assert paged_kernel_traces and all(paged_kernel_traces), \
        "the kernel leg never reached the int8 paged kernel"
    assert outs_k == outs, "quantized kernels diverged from the jnp path"
    with pytest.raises(ValueError):
        ServingEngine(cfg, params,
                      serving=dict(SERVE_CFG, weight_dtype="int4"))


# ---------------------------------------------------------------------------
# the K/V write (PR 25): in-place updates, held to the scatter they replaced
# ---------------------------------------------------------------------------

W_BS, W_NBK, W_BLOCKS = 16, 8, 32

#: name -> (T, lanes as (q_start, ctx, block table)); ctx counts the real
#: queries of the call, positions past it are padding
_WRITE_CASES = {
    # a decode step with two live lanes and two idle ones (an all-null
    # table: they land in the null block), and a lane whose one position is
    # padding (q_start == ctx): its own block 20 must not take the row
    "decode_idle_lanes": (1, [(4, 5, [3, 9]), (0, 1, []), (37, 38, [5, 6, 7]),
                              (0, 1, []), (20, 20, [19, 20])]),
    # a bucket-padded prompt: positions 21..31 are padding
    "prefill_padded_tail": (32, [(0, 21, [4, 8])]),
    # the chunk after a prefix hit of one block (block 11, shared) and a
    # first chunk of 10: it starts at slot 10 of the second block
    "q_start_mid_block": (16, [(26, 36, [11, 12, 13])]),
    # a prompt's last chunk, 21 real tokens of 32: ends in mid-block
    "last_chunk_mid_block": (32, [(32, 53, [11, 2, 14, 15])]),
    # everything before q_start came from the prefix cache: its blocks are
    # another sequence's too and must come out as they went in
    "shared_prefix_unwritten": (16, [(32, 48, [11, 21, 17])]),
}


def _scatter_write_kv(bt, q_start, ctx, T):
    """The write as it was up to PR 24, in ``_write_kv``'s signature: ONE
    scatter a layer into flat slots, padded positions into the null block
    at their offset."""
    pos = q_start[:, None] + np.arange(T)[None, :]
    phys = np.take_along_axis(bt, np.clip(pos // W_BS, 0, W_NBK - 1), axis=1)
    slots = jnp.asarray(np.where(pos < ctx[:, None],
                                 phys * W_BS + pos % W_BS,
                                 pos % W_BS).reshape(-1))

    def write(pool, li, new, ax, plan):
        L, nh, nb = pool.shape[:3]
        B, w = new.shape[0], pool.shape[7 - ax]      # hd (ax 3) or 1 (ax 4)
        rows = jnp.moveaxis(new.reshape(B, nh, T, w) if ax == 3
                            else new.reshape(B, nh, T, 1), 1, 2)
        # a scale pool (ax 4) holds a block's slots on the first lanes of a
        # row of whole 128-lane tiles: the lanes past them are not slots
        held = pool if ax == 3 else pool[..., :W_BS]
        flat = held.reshape(L, nh, nb * W_BS, w)
        out = flat.at[li, :, slots].set(
            rows.reshape(B * T, nh, w)).reshape(held.shape)
        return out if ax == 3 else jnp.concatenate(
            [out, pool[..., W_BS:]], axis=-1)
    return write


@pytest.mark.parametrize("case", sorted(_WRITE_CASES))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_kv_write_equals_the_scatter_bit_for_bit(tiny, monkeypatch, kv, case):
    """One ``paged_forward`` call over a pool full of other sequences' K/V:
    every slot outside the null block (a sink nothing reads) comes out bit
    for bit as the scatter left it, and no block but the call's own
    targets changed at all — idle lanes, padding, a shared prefix block."""
    from deepspeed_tpu.serving import model_runner
    from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
    cfg, params = tiny
    T, lanes = _WRITE_CASES[case]
    B = len(lanes)
    bt = np.full((B, W_NBK), NULL_BLOCK, np.int32)
    for b, (_, _, blocks) in enumerate(lanes):
        bt[b, :len(blocks)] = blocks
    q_start = np.asarray([q for q, _, _ in lanes], np.int32)
    ctx = np.asarray([c for _, c, _ in lanes], np.int32)
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 64, size=(B, T)).astype(np.int32)

    def filled(name, z):
        if z.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, z.shape), jnp.int8)
        r = rng.standard_normal(z.shape).astype(np.float32)
        return jnp.asarray(np.abs(r) + 0.1 if name.endswith("_scale") else r,
                           z.dtype)

    before = {name: filled(name, z) for name, z in init_pool(
        cfg, W_BLOCKS, W_BS,
        jnp.int8 if kv == "int8" else jnp.bfloat16).items()}

    def run():
        return jax.jit(lambda pools: model_runner.paged_forward(
            cfg, params, jnp.asarray(ids), pools, jnp.asarray(bt),
            jnp.asarray(q_start), jnp.asarray(ctx), W_BS))(before)

    logits, after = run()
    monkeypatch.setattr(model_runner, "_write_kv",
                        _scatter_write_kv(bt, q_start, ctx, T))
    logits_ref, scattered = run()

    def blocks_of(name, pools):
        a = np.asarray(pools[name])
        return a.reshape(a.shape[:2] + (W_BLOCKS, W_BS, -1)).view(np.uint8)

    pos = q_start[:, None] + np.arange(T)[None, :]
    targets = {int(bt[b, p // W_BS]) for b in range(B)
               for p in pos[b] if p < ctx[b]} - {NULL_BLOCK}
    assert targets, "the case writes nothing"
    others = [i for i in range(1, W_BLOCKS) if i not in targets]
    for name in before:
        got, want, was = (blocks_of(name, p)
                          for p in (after, scattered, before))
        assert np.array_equal(got[:, :, 1:], want[:, :, 1:]), name
        assert np.array_equal(got[:, :, others], was[:, :, others]), name
        assert not np.array_equal(got[:, :, sorted(targets)],
                                  was[:, :, sorted(targets)]), name
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv", ["model_dtype", "int8"])
def test_step_programs_hold_no_pool_scatter_on_the_cpu(tiny, kv):
    """The CPU twin of tests/test_chip_compile.py's guard, for where no TPU
    topology can be described: the engine's own two compiled programs hold
    no ``scatter`` that makes a whole pool (the write this PR replaced),
    and the decode step no pool-shaped ``copy`` either. The prefill program
    is not held to the second: the CPU compiler fuses a touched block's
    read into the update before it and then copies the pool to keep both
    versions, which the chip's compiler does not (the guard for that is the
    described-chip test)."""
    import math
    import re
    cfg, params = tiny
    eng = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, **({"kv_cache_dtype": "int8"} if kv == "int8" else {})))
    programs = {
        "decode": eng._decode_fn.lower(eng.params, eng.pools,
                                       eng._lanes.buf, eng._dec_out,
                                       eng._pre_out),
        "prefill": eng._prefill_fn.lower(
            eng.params, eng.pools,
            np.zeros((eng._layout.prefill_words(32),), np.int32)),
    }
    pool_sizes = {math.prod(p.shape) for p in eng.pools.values()}
    for name, lowered in programs.items():
        made = [(m.group(2), m.group(1)) for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* (scatter|copy)\(",
            lowered.compile().as_text())
            if math.prod(int(d) for d in m.group(1).split(",") if d)
            in pool_sizes]
        banned = {"scatter"} | ({"copy"} if name == "decode" else set())
        assert not [m for m in made if m[0] in banned], (name, made)


# ---------------------------------------------------------------------------
# PR 31: lane state kept between steps, one transfer a device call
# ---------------------------------------------------------------------------

LANE_CFG = dict(SERVE_CFG, sampling_filters=True, seed=5)


def _lane_ctx(s):
    """A lane's context as the outside sees it: the prompt and every token
    emitted but the last, which the next step writes."""
    return len(s.req.prompt) + len(s.req.output_tokens) - 1


def _per_lane_decode_build(srv, fed):
    """The parent's build of a decode step's inputs (engine.py before PR 31,
    ``_decode_lanes``): fresh arrays, a Python loop over the lanes, every
    fact read from the requests and the lanes' block lists. Since PR 40 the
    call is launched before the one in flight (``srv._flight`` at that
    moment) is booked: a lane of that call stands one token further than its
    request's outputs say, a lane staged by a prompt's last chunk has no
    output yet, a lane whose last token either of them brings is left out,
    and a lane reads its token from the device if the host never held it:
    ``fed`` are the ``(lane, rid)`` of the previous decode call."""
    B, layout = srv.max_batch, srv._layout
    toks, ctx = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    temps, tks = np.zeros((B,), np.float32), np.zeros((B,), np.int32)
    tps = np.ones((B,), np.float32)
    tables = np.zeros((B, srv.nbk), np.int32)            # NULL_BLOCK
    flying = srv._flight.go if srv._flight is not None else np.zeros(B, bool)
    for i, s in enumerate(srv._slots):
        if s is None:
            continue
        staged = not s.req.output_tokens
        unbooked = int(staged) + int(flying[i])
        if s.req.max_new_tokens - len(s.req.output_tokens) - unbooked <= 0:
            continue                       # its last token is on its way
        toks[i] = (layout.FROM_DECODE if (i, s.req.rid) in fed
                   else layout.FROM_PREFILL if staged
                   else s.req.output_tokens[-1])
        ctx[i] = _lane_ctx(s) + unbooked
        temps[i] = s.req.temperature
        tks[i] = s.req.top_k or 0
        tps[i] = s.req.top_p if s.req.top_p is not None else 1.0
        tables[i, :len(s.blocks)] = s.blocks
    return toks, ctx, tks, tables, temps, tps


def _per_request_prefill_build(srv, Tb):
    """The parent's build of a chunk's inputs (``_prefill_chunk``), from the
    prompt in prefill as it stands before the call."""
    pf = srv._prefilling
    n = min(srv._chunk, pf.total - pf.done)
    ids = np.zeros((1, Tb), np.int32)
    ids[0, :n] = pf.req.prompt[pf.done:pf.done + n]
    table = np.zeros((1, srv.nbk), np.int32)
    table[0, :len(pf.blocks)] = pf.blocks
    req = pf.req
    return (ids, table, [pf.done], [pf.done + n], [n - 1],
            [req.top_k or 0], np.float32([req.temperature]),
            np.float32([req.top_p if req.top_p is not None else 1.0]))


def _watch_device_calls(srv, check=True, keep=None):
    """Every ``_run_device`` call of ``srv`` recorded as 'decode' /
    'prefill' / 'mixed' (its buffer's fields copied into ``keep``, if given:
    a mixed call's prefill fields, then its decode fields); with
    ``check`` the buffer is held, field for field, to the per-lane build
    above (a mixed call's two halves to the two builds, each with its own
    key)."""
    calls, real = [], srv._run_device

    fed = set()                # (lane, rid) of the previous decode call

    def spy(fn, *args):
        assert type(args[0]) is np.ndarray \
            and args[0].dtype == np.int32 and args[0].ndim == 1
        kind = "decode" if fn is srv._decode_fn else \
            "mixed" if fn is srv._mixed_fn else "prefill"
        # ONE host array a call; the decode program (and the mixed one, a
        # decode call with a chunk in it) reads beside it the two token
        # vectors the engine keeps on the device
        assert len(args) == (1 if kind == "prefill" else 3)
        assert kind == "prefill" or (args[1] is srv._dec_out
                                     and args[2] is srv._pre_out)
        calls.append(kind)
        layout = srv._layout
        halves = {"prefill": args[0], "decode": args[0]}
        if kind == "mixed":
            halves["prefill"], halves["decode"] = layout.mixed(
                args[0], srv.max_batch)
        fields = {k: getattr(layout, k)(halves[k]) for k in halves
                  if kind in (k, "mixed")}
        if keep is not None:
            keep.append([np.array(f) for got in fields.values() for f in got])
        if check:
            # each half's own key: calls are numbered 1, 2, ..., and a mixed
            # call's chunk samples under a key of its own
            if "prefill" in fields:
                *got, key = fields["prefill"]
                want = _per_request_prefill_build(srv, got[0].shape[1])
                for g, w in zip(got, want, strict=True):
                    np.testing.assert_array_equal(g, w)
                np.testing.assert_array_equal(key, srv._call_key(
                    len(calls), *([1] if kind == "mixed" else [])))
            if "decode" in fields:
                *got, key = fields["decode"]
                want = _per_lane_decode_build(srv, fed)
                for g, w in zip(got, want, strict=True):
                    np.testing.assert_array_equal(g, w)
                np.testing.assert_array_equal(key, srv._call_key(len(calls)))
        if "decode" in fields:
            ctx = fields["decode"][1]
            fed.clear()
            fed.update((i, s.req.rid) for i, s in enumerate(srv._slots)
                       if s is not None and ctx[i] > 0)
        return real(fn, *args)

    srv._run_device = spy
    return calls


def _drive_lane_scenario(srv, before_step=lambda: None):
    """A seeded run that changes the lanes in every way the engine has:
    installs and finishes of mixed lengths (idle lanes in between, 4 lanes),
    chunked prompts that end in mid-block (chunk 24, blocks of 16), a
    prefix-cache hit, greedy, temperature and filtered lanes, a preempted
    lane, a cancelled queued request and a cancelled running one."""
    rng = np.random.default_rng(31)
    prompt = lambda n: rng.integers(1, 64, size=n).tolist()

    def step(n=1):
        for _ in range(n):
            before_step()
            srv.step()

    shared = prompt(40)
    first = [srv.submit(prompt(37), 9),
             srv.submit(shared, 25, temperature=0.8),
             srv.submit(prompt(21), 12, temperature=1.1, top_k=7, top_p=0.9)]
    step(6)
    hit = srv.submit(shared[:32] + prompt(9), 6)         # two cached blocks
    victim = srv.submit(prompt(50), 30, temperature=0.5, top_p=0.8)
    step(7)
    queued = srv.submit(prompt(18), 8)
    while victim.state != RUNNING:
        step()
    step(2)
    assert srv.preempt_request(victim) and victim.state == QUEUED
    assert srv.cancel_request(srv.submit(prompt(26), 4))  # still in the queue
    step(3)
    runner = srv.submit(prompt(33), 40)
    while runner.state != RUNNING:
        step()
    step(3)
    assert srv.cancel_request(runner)                    # holds a lane
    late = srv.submit(prompt(16), 3)                     # a whole block
    while not srv.idle:
        step()
    assert hit.prefix_hit_tokens == 32
    done = first + [hit, queued, late]
    assert all(r.done and len(r.output_tokens) == r.max_new_tokens
               for r in done)
    srv.prefix_cache.clear()
    assert srv.pool.used_count == 0       # every path returned its blocks
    return done + [victim, runner]


def test_step_inputs_equal_the_per_lane_build_field_for_field(tiny):
    """Every device call of the scenario gets ONE numpy int32 buffer whose
    fields are what the parent's per-lane build gives at that moment and
    the call's own key; and the two counters say what was
    reused: one transfer a call, a lane row written per install and per
    freed lane and none per step."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        LANE_CFG, prefill_chunk_tokens=24))
    calls = _watch_device_calls(srv)
    reqs = _drive_lane_scenario(srv)
    assert calls.count("decode") > 12 and calls.count("mixed") > 12 \
        and "prefill" not in calls
    c = srv.telemetry()["counters"]
    assert c["step_inputs.transfers_sum"] == len(calls)
    installs = len(reqs)                  # each took a lane once
    assert c["step_inputs.lane_rows_written_sum"] == 2 * installs
    assert c["step_inputs.lane_rows_written_sum"] < c["steps"] \
        < c["steps"] * srv.max_batch      # the parent wrote every row a step
    assert not srv._lanes.live.any() and not srv._lanes.ctx.any() \
        and not srv._lanes.tables.any()   # every lane reads idle again


def test_whole_prefill_inputs_ride_one_buffer(tiny):
    """Whole (unchunked) prefill builds its call the same way: the suffix
    the prefix cache does not hold, padded to a block multiple, at its
    offset in the request's table."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=LANE_CFG)
    seen = []
    calls = _watch_device_calls(srv, check=False, keep=seen)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 64, size=40).tolist()
    a = srv.submit(shared, 4)
    srv.step()
    b = srv.submit(shared[:32] + [7, 8, 9], 4, temperature=0.7, top_k=3)
    srv.run_until_idle()
    assert calls.count("prefill") == 2 and len(a.output_tokens) == 4
    seen = [f for kind, f in zip(calls, seen) if kind == "prefill"]
    ids, table, q0, ctx, last_idx, tk, temp, tp, key = seen[1]
    assert ids.shape == (1, 16) and ids[0, :3].tolist() == [7, 8, 9] \
        and not ids[0, 3:].any()
    assert (q0[0], ctx[0], last_idx[0], tk[0]) == (32, 35, 2, 3)
    assert temp[0] == np.float32(0.7) and tp[0] == 1.0
    np.testing.assert_array_equal(table[0, :2], seen[0][1][0, :2])  # forked
    assert np.count_nonzero(table) == srv.pool.blocks_for_tokens(35 + 3)
    assert b.prefix_hit_tokens == 32 and len(b.output_tokens) == 4


def test_no_eager_dispatch_between_steps(tiny, monkeypatch):
    """After warm-up a step makes exactly the device calls it is for, one
    decode and at most one prefill chunk, and touches jax nowhere else: the
    engine's, the pool's and the scheduler's modules see a ``jax`` and a
    ``jnp`` that raise on any use (no ``jnp.asarray`` round an input, no
    ``jax.random`` on the host), while the jitted programs, compiled in the
    warm-up, run from their caches."""
    from deepspeed_tpu.serving import engine, kv_cache, scheduler
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        LANE_CFG, prefill_chunk_tokens=24))
    rng = np.random.default_rng(11)
    prompt = lambda n: rng.integers(1, 64, size=n).tolist()
    # warm-up: every shape the run below uses (chunks of 32 and 16 padded
    # tokens, the decode step), greedy and sampled
    srv.submit(prompt(40), 3, temperature=0.9, top_k=5)
    srv.submit(prompt(30), 3)
    srv.run_until_idle()

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"the host path touched jax: .{name}")

    for mod in (engine, kv_cache, scheduler):
        for name in ("jax", "jnp"):
            if hasattr(mod, name):
                monkeypatch.setattr(mod, name, Untouchable())
    calls = _watch_device_calls(srv, check=False)
    reqs = [srv.submit(prompt(40), 6, temperature=0.9, top_k=5),
            srv.submit(prompt(30), 5), srv.submit(prompt(13), 7,
                                                  temperature=1.2)]
    while not srv.idle:
        before, owed = len(calls), srv._lanes.next_call().any()
        srv.step()
        made = sorted(calls[before:])
        # (a step that advances a chunk: the chunk and the lanes in one)
        assert made in ([], ["decode"], ["mixed"])
        # a lane still owed a token no launched call brings gets its call
        assert made or not owed
    assert all(len(r.output_tokens) == r.max_new_tokens for r in reqs)
    assert calls.count("decode") >= 5 and calls.count("mixed") == 5
    assert srv._decode_fn._cache_size() == 1


def test_seeded_temperature_mix_gives_the_same_tokens_twice(tiny):
    """Same seed, same requests, same tokens, run to run: a call's key is
    a hash of the base key and the call's number, made on the host, and the
    calls are a function of the admissions alone. Another seed gives other
    tokens; a greedy lane never reads the key."""
    cfg, params = tiny

    def run(seed):
        srv = ServingEngine(cfg, params, serving=dict(
            LANE_CFG, seed=seed, prefill_chunk_tokens=24))
        return [list(r.output_tokens) for r in _drive_lane_scenario(srv)]

    one, two, other = run(5), run(5), run(6)
    assert one == two
    assert one != other
    assert one[0] == other[0]             # the greedy request of the mix


def test_held_block_counters_equal_a_recount_at_every_step(tiny):
    """``kv.held_blocks_sum``, ``kv.blocks_reserved_sum``,
    ``kv.tokens_written_sum``, ``lane_sum`` and the ``kv.held_blocks_peak``
    gauge, kept as running counts where blocks are reserved and released,
    gain at every step of the scenario what a recount over the lanes' and
    the prefilling prompt's own lists reads on entry to that step (the
    parent's ``_step_span``, and the benchmark driver's ``held_blocks``)."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        LANE_CFG, prefill_chunk_tokens=24))
    names = ("kv.held_blocks_sum", "kv.blocks_reserved_sum",
             "kv.tokens_written_sum", "lane_sum")
    want, peak, forked = dict.fromkeys(names, 0), [0], [0]

    def recount():
        assert {k: srv.stats[k] for k in names} == want
        holders = [s for s in srv._slots if s is not None]
        want["lane_sum"] += len(holders)
        # (a lane staged behind its prompt's last chunk, its first token not
        # booked yet, holds the whole prompt)
        written = sum(_lane_ctx(s) + (not s.req.output_tokens)
                      for s in holders)
        if srv._prefilling is not None:
            holders.append(srv._prefilling)
            written += srv._prefilling.done
        held = len(set().union(*(h.blocks for h in holders)))
        reserved = sum(len(h.blocks) for h in holders)
        want["kv.held_blocks_sum"] += held
        want["kv.blocks_reserved_sum"] += reserved
        want["kv.tokens_written_sum"] += written
        peak[0] = max(peak[0], held)
        forked[0] += reserved - held

    _drive_lane_scenario(srv, before_step=recount)
    recount()
    assert srv.telemetry()["gauges"]["kv.held_blocks_peak"] == peak[0] > 8
    assert forked[0] > 0                  # a shared prefix was held twice
    assert srv._held.distinct == srv._held.reserved == 0 \
        and not srv._held.refs.any()


def test_mixsim_replays_a_window_against_the_engine(monkeypatch):
    """``benchmark/mixsim.py`` drives the engine's scheduler and pool with
    the device stubbed: ``srv._run_device(fn, *args)`` answered by a numpy
    vector of ``max_batch`` tokens (one for ``srv._prefill_fn``). A short
    window of the chat mix replays, step for step, against the loop as it
    is now. (Since PR 57 a chunk step is one mixed call that answers with
    the lanes' vector AND the chunk's; mixsim's stub knows the two old
    programs only and is a ``benchmark`` PR's to edit, PERF.md section 7:
    until then the pair is made here from the stub's two answers.)"""
    from benchmark import harness, mixsim
    call_mixed = ServingEngine._call_mixed

    def with_both_answers(srv, step_in, chunk_words):
        stub = srv._run_device
        srv._run_device = lambda fn, *a: (stub(fn, *a), stub(srv._prefill_fn))
        try:
            return call_mixed(srv, step_in, chunk_words)
        finally:
            srv._run_device = stub

    monkeypatch.setattr(ServingEngine, "_call_mixed", with_both_answers)
    cell = harness.load_cell("serve-mistral-7b-l16-chat")
    out = mixsim.replay(dict(cell.traffic), cell.system["serving"],
                        seconds=1.5, decode_s=0.016, prefill_s=0.035)
    assert out["requests"] >= 10
    assert 600 < out["sim_tokens_per_s"] < 1600
    assert 35 <= out["sim_itl_p95_ms"] <= 36


# ---------------------------------------------------------------------------
# PR 40: the next decode call is launched before the one in flight is fetched
# ---------------------------------------------------------------------------

AHEAD_MIX = [(37, 9), (21, 5), (50, 12), (18, 3), (33, 7), (26, 4), (37, 9)]


def _decode_calls(srv):
    """Calls that carry the decode lanes: the decode program's, and the
    mixed program's (a chunk and the lanes in one call)."""
    return sum(1 for e in srv.rec.ring if e[0] == "serve.decode.dispatch") \
        + srv.stats["mixed.calls"]


def _steps_with(srv, *names):
    """How many ``serve.step`` spans of the ring hold a span of every name
    (a pair ``(name, attrs)`` also asks for those attributes)."""
    steps = [e for e in srv.rec.ring if e[0] == "serve.step"]
    n = 0
    for _, _, start, end, _ in steps:
        inside = [e for e in srv.rec.ring if start <= e[2] and e[3] <= end]
        n += all(any(e[0] == (want if isinstance(want, str) else want[0])
                     and (isinstance(want, str)
                          or want[1].items() <= e[4].items())
                     for e in inside) for want in names)
    return n


@pytest.mark.parametrize("mode", ["chunked", "whole", "disagg"])
def test_launch_ahead_is_token_exact_in_every_mode(tiny, mode):
    """Greedy output is token for token ``generate()``'s with the decode
    call launched one step ahead, in the three modes (chunked prefill, whole
    prefill, the disaggregated pair): more requests than lanes, prompts
    whose last chunk lands in a step in which other lanes finish, a warm-up,
    a ramp and a drain; ONE decode specialization through all of it, nothing
    in flight at the end, and no lane computed after its end (no EOS)."""
    from deepspeed_tpu.serving.disagg import DisaggEngine
    cfg, params = tiny
    rng = np.random.default_rng(40)
    prompts = [rng.integers(1, 64, size=n).tolist() for n, _ in AHEAD_MIX]
    if mode == "disagg":
        srv = DisaggEngine(cfg, params, serving=dict(
            SERVE_CFG, prefill_chunk_tokens=16))
        loop, dec = srv, srv.decode
    else:
        srv = loop = dec = ServingEngine(cfg, params, serving=dict(
            SERVE_CFG, prefill_chunk_tokens=16 if mode == "chunked" else 0))
    srv.generate_batch([prompts[0][:20]], max_new_tokens=3)     # warm-up
    reqs = [srv.submit(p, m) for p, (_, m) in zip(prompts[:4], AHEAD_MIX)]
    for _ in range(5):
        loop.step()
    reqs += [srv.submit(p, m) for p, (_, m) in zip(prompts[4:],
                                                   AHEAD_MIX[4:])]
    loop.run_until_idle()
    for p, (_, m), r in zip(prompts, AHEAD_MIX, reqs):
        assert r.output_tokens == _oracle_tokens(cfg, params, p, m), r.rid
    assert dec._flight is None and dec._chunk_out is None
    assert dec._decode_fn._cache_size() == 1
    c = dec.stats
    assert c["decode_ahead.wasted_lane_tokens"] == 0
    assert c["decode_ahead.retired_unread"] == 0
    assert 0 < c["decode_ahead.launched"] < _decode_calls(dec)
    if mode == "chunked":
        # the case the mix was made for: a prompt's last chunk in a step
        # that also retires the last token of another lane
        assert _steps_with(srv, ("serve.prefill", {"final": 1}),
                           "serve.req.finished") >= 1
        # every lane input but none comes off the device: the first token
        # from the prefill call, the others from the previous decode call
        assert c["decode_ahead.device_lane_tokens_sum"] == \
            c["tokens_generated"] - c["completed"]


def test_decode_ahead_counters_of_a_steady_run(tiny):
    """While a lane goes on, every decode call but the first is launched
    before the call in flight is fetched (``decode_ahead.launched`` = decode
    calls - 1 - retirements), a step returns with one call in flight, the
    tokens booked are the calls' live lanes, and a retirement from outside
    (here ``preempt_request`` on a lane) costs one launch ahead."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=16, prefix_cache=False))
    rng = np.random.default_rng(41)
    long = srv.submit(rng.integers(1, 64, size=20).tolist(), 40)
    others = [srv.submit(rng.integers(1, 64, size=n).tolist(), m)
              for n, m in ((30, 6), (12, 9), (25, 4))]
    while long.state != RUNNING:
        srv.step()
    victim = srv.submit(rng.integers(1, 64, size=9).tolist(), 30)
    for _ in range(12):
        srv.step()
        assert srv._flight is not None        # a call in flight between steps
        assert not srv.idle and srv.has_work
    assert victim.state == RUNNING and srv.preempt_request(victim)
    assert srv._flight is None and victim.state == QUEUED
    srv.run_until_idle()
    c = srv.stats
    assert c["decode_ahead.retired_unread"] == 1
    assert c["decode_ahead.launched"] == _decode_calls(srv) - 1 - 1
    assert c["decode_ahead.wasted_lane_tokens"] == 0
    assert long.output_tokens == _oracle_tokens(cfg, params, long.prompt, 40)
    assert all(len(r.output_tokens) == r.max_new_tokens for r in others)
    # the preempted request kept what was booked and resumes from it exactly
    kept = list(victim.output_tokens)
    assert 0 < len(kept) < 30
    rest = srv.generate_batch([victim.prompt + kept], 30 - len(kept))[0]
    assert kept + rest == _oracle_tokens(cfg, params, victim.prompt, 30)
    assert srv.pool.used_count == 0


@pytest.mark.parametrize("at", [0, 3], ids=["first_token", "later_token"])
def test_an_eos_finish_is_seen_one_call_late_and_costs_one_lane_token(
        tiny, at):
    """A lane that ends by EOS (its first token, or a later one) is in the
    call launched ahead once more: exactly one computed token is dropped,
    its blocks go back at once, the request holds what ``generate()`` gives
    up to the EOS, and the next owner of those blocks (a request that could
    not be admitted before: the pool holds one of them at a time) is exact."""
    cfg, params = tiny
    rng = np.random.default_rng(42 + at)
    prompt = rng.integers(1, 64, size=40).tolist()
    want = _oracle_tokens(cfg, params, prompt, 12)
    assert want[at] not in want[:at]           # the EOS is its first sighting
    srv = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, pool_blocks=6, prefill_chunk_tokens=16,
        prefix_cache=False))
    ends = srv.submit(prompt, 12, eos_token_id=want[at])
    nxt_prompt = rng.integers(1, 64, size=40).tolist()
    nxt = srv.submit(nxt_prompt, 12)           # 4 of the 5 blocks: must wait
    while not ends.done:
        srv.step()
        assert nxt.state == QUEUED or ends.done
    assert ends.output_tokens == want[:at + 1]
    assert srv.stats["decode_ahead.wasted_lane_tokens"] == 1
    assert srv.pool.used_count == 0 or nxt.state != QUEUED
    srv.run_until_idle()
    assert srv._flight is None                 # the dropped call was read too
    assert nxt.output_tokens == _oracle_tokens(cfg, params, nxt_prompt, 12)
    assert srv.stats["decode_ahead.wasted_lane_tokens"] == 1
    assert srv.stats["tokens_generated"] == at + 1 + 12
    assert srv.pool.used_count == 0


@pytest.mark.parametrize("how", ["cancel", "preempt", "held_state", "close"])
def test_lane_state_is_moved_only_behind_the_call_in_flight(tiny, how):
    """``cancel_request``, ``preempt_request``, ``held_state`` and ``close``
    meet a call in flight (there is one between any two steps of a busy
    loop) and retire it first: the first two book its tokens, the last two
    drop them; none leaves a call in flight or a block unaccounted for, and
    what the requests hold is a prefix of what ``generate()`` gives."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=16, prefix_cache=False))
    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (22, 35)]
    a, b = (srv.submit(p, 20) for p in prompts)
    while b.state != RUNNING or len(b.output_tokens) < 3:
        srv.step()
    assert srv._flight is not None and srv._flight.go.sum() == 2
    booked = (len(a.output_tokens), len(b.output_tokens))
    if how in ("cancel", "preempt"):
        act = srv.cancel_request if how == "cancel" else srv.preempt_request
        assert act(b) and b.state == QUEUED
        # the call in flight was booked before the lane was given up
        assert (len(a.output_tokens), len(b.output_tokens)) == \
            (booked[0] + 1, booked[1] + 1)
        srv.run_until_idle()
        assert len(a.output_tokens) == 20
    elif how == "held_state":
        blocks, reqs = srv.held_state()
        assert {r.rid for r in reqs} == {a.rid, b.rid} and len(blocks) == 2
        assert (len(a.output_tokens), len(b.output_tokens)) == booked
        assert not srv._lanes.live.any() and srv.idle
        for held in blocks:
            srv.pool.release(held)
    else:
        srv.close()
        assert (len(a.output_tokens), len(b.output_tokens)) == booked
        for s in filter(None, srv._slots):
            srv.pool.release(s.blocks)
    assert srv._flight is None
    assert srv.stats["decode_ahead.retired_unread"] == 1
    assert srv.stats["decode_ahead.wasted_lane_tokens"] == 0
    assert srv.pool.used_count == 0
    for p, r in zip(prompts, (a, b)):
        want = _oracle_tokens(cfg, params, p, 20)
        assert r.output_tokens == want[:len(r.output_tokens)]


def test_run_until_idle_reads_a_call_that_holds_dropped_lanes_only(tiny):
    """The last lane of a batch ends by EOS: the call launched ahead for it
    holds no lane anyone waits for, and the loop is not idle until a step
    has read it (``idle`` counts the call in flight)."""
    cfg, params = tiny
    rng = np.random.default_rng(44)
    prompt = rng.integers(1, 64, size=19).tolist()
    want = _oracle_tokens(cfg, params, prompt, 9)
    at = next(i for i in range(1, 8) if want[i] not in want[:i])
    srv = ServingEngine(cfg, params, serving=dict(SERVE_CFG,
                                                  prefill_chunk_tokens=16))
    req = srv.submit(prompt, 9, eos_token_id=want[at])
    while not req.done:
        srv.step()
    assert srv._flight is not None and not srv._flight.go.any()
    assert srv.active == 0 and not srv.idle and srv.has_work
    srv.run_until_idle()
    assert srv._flight is None and srv.idle
    assert req.output_tokens == want[:at + 1]
    assert srv.generate_batch([prompt], 9)[0] == want      # and again, whole
    assert srv._flight is None


def _latent_tiny():
    """A latent (MLA) model at the smallest widths: one ``ckv`` leaf."""
    model, cfg = build_model(TransformerConfig(
        vocab_size=64, max_seq_len=256, hidden_size=32, num_layers=2,
        num_heads=2, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, norm="rmsnorm", pos_embed="rotary",
        rotary_interleaved=True, use_bias=False, tie_embeddings=False,
        attention_impl="reference", dtype=jnp.float32))
    ids = np.zeros((1, 8), np.int32)
    return cfg, model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]


@pytest.mark.parametrize("kind", ["kv", "int8", "latent"])
def test_the_pools_start_committed_and_the_first_shape_compiles_once(
        tiny, kind):
    """Beside committed weights (as ``init_inference`` hands them over) the
    pools are committed, on the weights' device, when the constructor
    returns: the first call hands them back as it got them, so the second
    call of that shape is the same signature and compiles nothing. (Made
    uncommitted they came back committed, and the engine's first program,
    its largest in a long-document cell, was compiled twice.)"""
    cfg, params = _latent_tiny() if kind == "latent" else tiny
    device = jax.devices()[0]
    params = jax.device_put(params, device)
    srv = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, kv_cache_dtype="int8" if kind == "int8" else None))
    assert set(srv.pools) == {"kv": {"k", "v"}, "latent": {"ckv"},
                              "int8": {"k", "v", "k_scale", "v_scale"}}[kind]
    before = {k: (p.committed, p.sharding) for k, p in srv.pools.items()}
    assert all(c and s.device_set == {device} for c, s in before.values())
    for seed in (1, 2):                  # two prompts, one shape: two calls
        srv.generate_batch(
            [np.random.default_rng(seed).integers(1, 64, 12).tolist()], 2)
        assert srv._prefill_fn._cache_size() == 1
        assert {k: (p.committed, p.sharding)
                for k, p in srv.pools.items()} == before
    assert srv._decode_fn._cache_size() == 1
    assert srv.stats["prefill_tokens"] == 24
    srv.close()


@pytest.mark.parametrize("chunk, calls", [(0, [37, 5]), (16, [16, 16, 5, 5])])
def test_prefill_rows_count_the_calls_padding(tiny, chunk, calls):
    """``prefill_rows`` beside ``prefill_tokens``: the rows the prefill
    calls brought, their padding to whole blocks (of 16 here) with them."""
    cfg, params = tiny
    srv = ServingEngine(cfg, params, serving=dict(
        SERVE_CFG, prefill_chunk_tokens=chunk, prefix_cache=False))
    rng = np.random.default_rng(3)
    srv.generate_batch([rng.integers(1, 64, n).tolist() for n in (37, 5)], 3)
    rows = sum(-(-n // 16) * 16 for n in calls)
    assert srv.stats["prefill_tokens"] == sum(calls) == 42
    assert srv.stats["prefill_rows"] == rows
    assert rows - 42 == sum(-n % 16 for n in calls)
    srv.close()
