"""A step with a prefill chunk is ONE device program wherever the cache is
not latent (PR 57): the chunk's rows and the decode lanes' rows go through
``decoder_forward`` together (``serving.model_runner.MixedCache``), and what
comes out is what the prefill call and the decode call behind it gave.

Three things are held here, one case a feature the two kinds of row differ
in (grouped-query K/V, a dropless mixture's counts and picks, windows three
to one behind a dense layer, an indexer's selection, the int8 KV tier):

* the program: on the same pools, ``mixed_forward``'s logits, pools, expert
  counts and picks are the two ``paged_forward`` calls';
* the loop: a scripted run (staggered arrivals, a prompt that ends in
  mid-run, a preemption, a cancelled request, the ``serve.chunk`` failpoint)
  yields the greedy tokens of the two-program path, kept as the reference by
  an engine whose mixed program is taken away;
* the rule: ONE program a prefill shape and ``mixed.calls`` equal to the
  steps that advanced a chunk where the cache is not latent; a latent engine
  builds no mixed program and compiles the parent's programs, row for row;
  whole prefill and the disagg prefill role keep theirs.

float32 on the CPU at tiny widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import build_model
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.disagg import DisaggEngine
from deepspeed_tpu.serving.engine import (ServingEngine, StepLayout,
                                          step_programs, token_words)
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import mixed_forward, paged_forward
from deepspeed_tpu.serving.scheduler import QUEUED, RUNNING
from deepspeed_tpu.testing import chaos

BS, NBK, BLOCKS, VOCAB = 8, 6, 24, 64

_ROTARY = dict(pos_embed="rotary", rotary_interleaved=False)
_SWIGLU = dict(norm="rmsnorm", gated_mlp=True, activation="silu",
               use_bias=False, mlp_dim_override=48)
FEATURES = {
    "dense_gqa": dict(**_ROTARY, **_SWIGLU, num_kv_heads=2,
                      tie_embeddings=False),
    "dropless_moe": dict(**_ROTARY, **_SWIGLU, moe_experts=16, moe_k=8,
                         moe_dropless=True, moe_norm_topk=False),
    # K-EXAONE's layer: windows three to one behind a leading dense layer,
    # a share of a sigmoid mixture with a shared expert
    "windows_3_to_1": dict(
        **_ROTARY, **dict(_SWIGLU, mlp_dim_override=24), num_layers=5,
        num_kv_heads=2, tie_embeddings=False, qk_norm=True, pre_norm=False,
        post_block_norms=True, layer_windows=(6, 6, 6, 0, 6),
        layer_rope=(True, True, True, False, True), dense_layers=1,
        dense_mlp_dim=80, moe_experts=16, moe_k=4, moe_held=(4, 2),
        moe_dropless=True, moe_scores="sigmoid", moe_select_bias=True,
        moe_routed_scale=2.5, moe_shared_dim=24),
    # Keye-VL-2.0's layer: an indexer's selection beside a window, in a
    # dropless mixture (its picks carry the selection's bits)
    "indexer": dict(**_ROTARY, **_SWIGLU, num_kv_heads=2, qk_norm=True,
                    layer_windows=(0, 9), index_heads=3, index_head_dim=4,
                    index_topk=5, moe_experts=8, moe_k=2, moe_dropless=True,
                    moe_norm_topk=True),
    "int8_kv": dict(**_ROTARY, num_kv_heads=2),
}


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    """A dropless mixture's constraints resolve against the global mesh,
    which is whatever the worker's last test left."""
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


@pytest.fixture(scope="module", params=sorted(FEATURES))
def built(request):
    feature = request.param
    model, cfg = build_model("gpt2-tiny", **dict(dict(
        hidden_size=32, num_layers=2, num_heads=4, vocab_size=VOCAB,
        max_seq_len=64, attention_impl="reference", dtype=jnp.float32),
        **FEATURES[feature]))
    params = model.init(jax.random.PRNGKey(3),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    if cfg.moe_select_bias:         # drawn zero: give it something to select
        gate = params["blocks"]["moe"]["gate"]
        gate["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                               gate["bias"].shape)
    return feature, cfg, params


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def test_mixed_forward_is_a_prefill_call_then_a_decode_call(built):
    """Three lanes of 11, 17 and 3 cached tokens beside an idle one, and a
    prompt whose first 13 tokens are in (so its chunk starts in mid-block
    and, 9 real rows of 16, ends in one): the mixed call's logits at the
    rows the head reads, the pools it leaves, and a mixture's counts (a kind
    apart) and picks are what the prefill call and then the decode call
    give on the same pools."""
    feature, cfg, params = built
    kv_dtype = jnp.int8 if feature == "int8_kv" else jnp.float32
    counting = bool(cfg.moe_is_dropless)
    rng = np.random.default_rng(7)
    T, B, done, n = 16, 4, 13, 9
    flags = dict(expert_counts=counting, expert_picks=counting)
    paged = jax.jit(lambda ids, pools, bt, q0, ctx: paged_forward(
        cfg, params, ids, pools, bt, q0, ctx, BS, **flags))
    pools = init_pool(cfg, BLOCKS, BS, kv_dtype)
    # what is cached before the step: the lanes' contexts and the prompt's
    # first chunk, a sequence a call
    held = [11, 17, 3, 0]
    tables = np.full((B + 1, NBK), NULL_BLOCK, np.int32)
    tables[0, :2], tables[1, :3], tables[2, :1] = (3, 9), (5, 1, 14), (7,)
    tables[B, :4] = (2, 11, 6, 20)                       # the prompt's
    for row, length in ((0, 11), (1, 17), (2, 3), (B, done)):
        ids = np.zeros((1, 24), np.int32)
        ids[0, :length] = rng.integers(1, VOCAB, size=length)
        pools = paged(ids, pools, tables[row:row + 1], jnp.zeros((1,), jnp.int32),
                      jnp.asarray([length], jnp.int32))[1]
    chunk_ids = np.zeros((1, T), np.int32)
    chunk_ids[0, :n] = rng.integers(1, VOCAB, size=n)
    lane_ids = rng.integers(1, VOCAB, size=B).astype(np.int32)
    lane_ids[3] = 0
    q0, ctx = jnp.asarray([done], jnp.int32), jnp.asarray([done + n], jnp.int32)
    lane_ctx = jnp.asarray(held, jnp.int32)

    # ---- the two calls
    want_chunk = paged(chunk_ids, pools, tables[B:], q0, ctx)
    want_lanes = paged(lane_ids[:, None], want_chunk[1], tables[:B], lane_ctx,
                       lane_ctx + 1)
    # ---- the one
    head_rows = jnp.concatenate([jnp.asarray([n - 1]), T + jnp.arange(B)])
    got = jax.jit(lambda pools: mixed_forward(
        cfg, params, chunk_ids, lane_ids, pools, (tables[B:], q0, ctx),
        (tables[:B], lane_ctx, lane_ctx + 1), BS, head_rows, **flags))(pools)

    logits = np.asarray(got[0])
    assert logits.shape == (1, 1 + B, VOCAB)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(logits[0, 0], np.asarray(want_chunk[0])[0, n - 1],
                               **tol)
    np.testing.assert_allclose(logits[0, 1:], np.asarray(want_lanes[0])[:, 0],
                               **tol)
    assert set(got[1]) == set(want_lanes[1])
    for name, pool in got[1].items():
        want = np.asarray(want_lanes[1][name])
        if pool.dtype == jnp.int8:
            # a value on a rounding boundary may fall either way
            assert np.abs(np.asarray(pool).astype(np.int32) - want).max() <= 1
            assert (np.asarray(pool) != want).mean() < 1e-3
        else:
            np.testing.assert_allclose(np.asarray(pool), want, **tol)
    if counting:
        counts, picks = np.asarray(got[2]), np.asarray(got[3])
        L, E = cfg.sparse_layers, cfg.moe_experts
        assert counts.shape == (L, 2, E)
        np.testing.assert_array_equal(counts[:, 0], np.asarray(want_chunk[2]))
        np.testing.assert_array_equal(counts[:, 1], np.asarray(want_lanes[2]))
        # real rows only: the chunk's 9, the three live lanes
        assert (counts[:, 0].sum(axis=1) == n * cfg.moe_k).all()
        assert (counts[:, 1].sum(axis=1) == 3 * cfg.moe_k).all()
        assert picks.shape[:2] == (cfg.routed_layers, T + B)
        np.testing.assert_array_equal(picks[:, :T], np.asarray(want_chunk[3]))
        np.testing.assert_array_equal(picks[:, T:], np.asarray(want_lanes[3]))
        if cfg.index_heads:
            # behind a row's experts its selection, the kind's own
            assert picks.shape[2] > cfg.moe_k


def test_the_mixed_program_returns_what_the_two_programs_return(built):
    """``step_programs``' third program over one buffer of two halves: its
    two outputs have the shapes, and under greedy sampling the tokens, of
    the prefill program's and the decode program's on the same pools. With
    a temperature each kind is drawn under its own key: the lanes' tokens
    are the decode program's under that key, and the chunk's token follows
    the chunk's key and not the lanes'."""
    feature, cfg, params = built
    kv_dtype = jnp.int8 if feature == "int8_kv" else jnp.float32
    decode, prefill, mixed = (jax.jit(f) for f in step_programs(
        cfg, BS, NBK, mixed=True))
    layout, B, T, n = StepLayout(NBK), 3, 16, 16
    pools = init_pool(cfg, BLOCKS, BS, kv_dtype)
    fed = [jnp.zeros((token_words(cfg, k),), jnp.int32) for k in (B, 1)]

    def inputs(temp, chunk_key, lanes_key):
        chunk_in = np.zeros((layout.prefill_words(T),), np.int32)
        ids, bt, q0, ctx, last_idx, _, temps, tp, key = layout.prefill(
            chunk_in)
        ids[0, :n] = np.random.default_rng(5).integers(1, VOCAB, size=n)
        bt[0, :3], ctx[0], last_idx[0], tp[0] = (4, 8, 2), n, n - 1, 1.0
        temps[0], key[:] = temp, chunk_key
        lanes_in = np.zeros((layout.decode_words(B),), np.int32)
        toks, _, _, tables, temps, tps, key = layout.decode(lanes_in)
        toks[:2], tables[0, 0], tables[1, 0], tps[:] = (5, 9), 6, 10, 1.0
        temps[:2], key[:] = temp, lanes_key
        return chunk_in, lanes_in

    def two(chunk_in, lanes_in):
        first, pools_a = prefill(params, pools, chunk_in)
        lanes, pools_a = decode(params, pools_a, lanes_in, *fed)
        return (lanes, first), pools_a

    def one(chunk_in, lanes_in):
        return mixed(params, pools, np.concatenate([chunk_in, lanes_in]),
                     *fed)

    want, pools_a = two(*inputs(0.0, (1, 2), (3, 4)))
    got, pools_b = one(*inputs(0.0, (1, 2), (3, 4)))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert jax.tree.structure(pools_a) == jax.tree.structure(pools_b)

    tokens = lambda out: [np.asarray(jax.tree.leaves(o)[0])[:k]
                          for o, k in zip(out, (B, 1))]
    hot = tokens(one(*inputs(5.0, (1, 2), (3, 4)))[0])
    again = tokens(one(*inputs(5.0, (1, 2), (3, 4)))[0])
    np.testing.assert_array_equal(hot[0], again[0])
    np.testing.assert_array_equal(hot[1], again[1])
    # the lanes' draw is the decode program's under the lanes' key
    np.testing.assert_array_equal(
        hot[0], tokens(two(*inputs(5.0, (1, 2), (3, 4)))[0])[0])
    assert not np.array_equal(hot[0], tokens(got)[0])       # not greedy
    # another chunk key: the lanes' tokens stay, the chunk's is drawn anew
    # (over a few keys: one draw of 64 may repeat)
    others = [tokens(one(*inputs(5.0, (k, 2), (3, 4)))[0]) for k in (7, 8, 9)]
    assert all(np.array_equal(o[0], hot[0]) for o in others)
    assert any(o[1][0] != hot[1][0] for o in others)
    # another lanes key: the chunk's token stays
    moved = tokens(one(*inputs(5.0, (1, 2), (5, 6)))[0])
    assert moved[1][0] == hot[1][0] and not np.array_equal(moved[0], hot[0])


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

SERVING = {"block_size": BS, "pool_blocks": 64, "max_batch": 4,
           "max_blocks_per_seq": 8, "prefill_chunk_tokens": 16}


def _engine(cfg, params, feature, mixed=True, **serving):
    srv = ServingEngine(cfg, params, serving=dict(
        SERVING, **({"kv_cache_dtype": "int8"} if feature == "int8_kv"
                    else {}), **serving))
    if not mixed:
        # the parent's path, kept as the reference: a chunk's program, then
        # the decode call's
        assert srv._mixed_fn is not None
        srv._mixed_fn = None
    return srv


def _scripted_run(srv):
    """Staggered arrivals over more requests than lanes, a prompt whose last
    chunk lands while others decode, a preempted lane resumed from what it
    had emitted, a cancelled lane, and a chunk that fails once (the
    ``serve.chunk`` failpoint) and is served again. Returns ``(every
    finished request's tokens, steps in which prefill_tokens grew)``."""
    rng = np.random.default_rng(57)
    prompt = lambda n: rng.integers(1, VOCAB, size=n).tolist()
    grew = [0]

    def step(n=1):
        for _ in range(n):
            before = srv.stats["prefill_tokens"]
            srv.step()
            grew[0] += srv.stats["prefill_tokens"] > before

    first = [srv.submit(prompt(37), 9), srv.submit(prompt(21), 12)]
    step(4)
    later = [srv.submit(prompt(50), 7), srv.submit(prompt(5), 14)]
    victim = srv.submit(prompt(26), 20)
    while victim.state != RUNNING:
        step()
    step(3)
    assert srv.preempt_request(victim) and victim.state == QUEUED
    kept = list(victim.output_tokens)
    assert 0 < len(kept) < 20
    resumed = srv.submit(victim.prompt + kept, 20 - len(kept))
    runner = srv.submit(prompt(18), 30)
    while runner.state != RUNNING:
        step()
    step(2)
    assert srv.cancel_request(runner)
    # a chunk that fails once: the prompt's blocks go back, the request is
    # left QUEUED for whoever requeues it (the fleet; here the test)
    failed = srv.submit(prompt(40), 6)
    chaos.arm("serve.chunk", "raise", times=1, skip=1)
    try:
        with pytest.raises(chaos.ChaosError):
            step(8)
    finally:
        chaos.disarm()
    assert failed.state == QUEUED and srv._prefilling is None
    srv.scheduler.requeue_front(failed)
    while not srv.idle:
        step()
    done = first + later + [resumed, failed]
    assert all(r.done and len(r.output_tokens) == r.max_new_tokens
               for r in done)
    srv.prefix_cache.clear()
    assert srv.pool.used_count == 0       # every path returned its blocks
    # (how many tokens the victim had when it was preempted is a matter of
    # which step books them: its stream is what it kept and then resumed)
    return [list(r.output_tokens) for r in first + later + [failed]] \
        + [kept + resumed.output_tokens], grew[0]


def test_a_scripted_run_gives_the_two_program_paths_tokens(built):
    feature, cfg, params = built
    one = _engine(cfg, params, feature)
    two = _engine(cfg, params, feature, mixed=False)
    got, grew = _scripted_run(one)
    want, grew_two = _scripted_run(two)
    assert got == want
    # the rule's counters: every step that advanced a chunk was ONE call ...
    assert one.stats["mixed.calls"] == grew > 10
    assert 0 < one.stats["mixed.lane_rows_sum"] \
        <= one.stats["mixed.calls"] * one.max_batch
    # ... of ONE program a prefill shape (16 rows, 8 rows), in place of the
    # prefill program; the reference ran the two programs and no mixed call
    assert one._mixed_fn._cache_size() == 2
    assert one._prefill_fn._cache_size() == 0
    assert one._decode_fn._cache_size() == 1
    assert two.stats["mixed.calls"] == two.stats["mixed.lane_rows_sum"] == 0
    assert two._prefill_fn._cache_size() == 2
    assert one._flight is None and one._chunk_out is None
    one.close()
    two.close()


def test_keep_routing_hands_out_each_kinds_own_picks(built):
    """A request that asks for its routing gets, from mixed calls, the rows
    the two programs hand out: its prompt's picks from the chunks' half, its
    generated tokens' from the lanes' half of later calls."""
    feature, cfg, params = built
    if not cfg.moe_is_dropless:
        pytest.skip("no picks to hand out")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (29, 12, 20)]

    def run(mixed):
        srv = _engine(cfg, params, feature, mixed=mixed)
        reqs = [srv.submit(p, 6, keep_routing=True) for p in prompts]
        srv.run_until_idle()
        srv.close()
        return [(r.output_tokens, r.routed_experts) for r in reqs]

    for (toks, routed), (want_toks, want) in zip(run(True), run(False),
                                                 strict=True):
        assert toks == want_toks
        np.testing.assert_array_equal(routed, want)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


def _program_rows(srv):
    return sorted((r["fun_name"], r["shape"], r["compiles"])
                  for r in srv.telemetry()["programs"]
                  if r["fun_name"] in ("jit(_decode)", "jit(_prefill)",
                                       "jit(_mixed)"))


#: ``_program_rows`` of the latent script below AT THE PARENT of PR 57 (commit
#: 8c4e2ad, run there by that PR's builder): a latent engine compiles these
#: and no other, whatever that PR added. Since PR 60 a latent cache without an
#: indexer brings whole 256-row tiles too: its chunks of 16 and 5 tokens are
#: ONE program (named by the tokens of its first call), where whole blocks
#: made two
PARENT_LATENT_ROWS = {
    "deepseek_v2": [("jit(_decode)", None, 1), ("jit(_prefill)", 16, 1)],
    "deepseek_v32": [("jit(_decode)", None, 1), ("jit(_prefill)", 16, 1)],
}


@pytest.mark.parametrize("family", sorted(PARENT_LATENT_ROWS))
def test_a_latent_engine_keeps_the_parents_programs(family):
    """Where the cache is latent (with and without an indexer) the mixed
    program is never built, no step rides, and a fixed script of requests
    compiles the rows it compiled at the parent: names, shapes, compiles."""
    module = __import__("test_" + family)
    cfg, params = module.built()
    assert cfg.kv_lora_rank and bool(cfg.index_heads) == (
        family == "deepseek_v32")
    srv = module._engine(cfg, params)
    assert srv._mixed_fn is None and srv._mixed_program is None
    rng = np.random.default_rng(2)
    for n, new in ((37, 5), (21, 3), (16, 4)):
        srv.submit(rng.integers(1, 64, size=n).tolist(), max_new_tokens=new)
        srv.step()
    srv.run_until_idle()
    assert srv.stats["mixed.calls"] == srv.stats["mixed.lane_rows_sum"] == 0
    assert srv.stats["completed"] == 3
    assert _program_rows(srv) == PARENT_LATENT_ROWS[family]
    srv.close()


def test_whole_prefill_and_the_disagg_prefill_role_keep_their_programs(built):
    feature, cfg, params = built
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (29, 12)]
    whole = _engine(cfg, params, feature, prefill_chunk_tokens=0)
    assert whole._mixed_fn is None
    want = whole.generate_batch(prompts, max_new_tokens=5)
    assert whole.stats["mixed.calls"] == 0
    assert whole._prefill_fn._cache_size() == 2
    whole.close()
    pair = DisaggEngine(cfg, params, serving=dict(
        SERVING, **({"kv_cache_dtype": "int8"} if feature == "int8_kv"
                    else {})))
    assert pair.generate_batch(prompts, max_new_tokens=5) == want
    for role in (pair.prefill, pair.decode):
        assert role.stats["mixed.calls"] == 0
        assert role._mixed_fn is None or role._mixed_fn._cache_size() == 0
    # 29 in chunks of 16 and 13, 12 in one: rows of 16 every time
    assert pair.prefill._prefill_fn._cache_size() == 1
    assert pair.decode._decode_fn._cache_size() == 1
    pair.close()
    # and the chunked engine beside them serves the same tokens
    chunked = _engine(cfg, params, feature)
    assert chunked.generate_batch(prompts, max_new_tokens=5) == want
    assert chunked.stats["mixed.calls"] == 3
    assert chunked._mixed_fn._cache_size() == 1
    chunked.close()
