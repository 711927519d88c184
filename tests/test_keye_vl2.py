"""Keye-VL-2.0's layer at a tiny size on the CPU (float32, seeded weights)
against its plain reference (``benchmark/families/keye_vl2.py``): grouped
attention over the keys a learned indexer picks (top-k 16 here, contexts to
96, blocks of 8), every layer a chip's share of a softmax mixture. The
indexer's keys live in the pool's blocks beside K and V; the selection
reaches the paged kernel as the index scores with a threshold and a tie
position a row (``ops/pallas/sparse_select.py``)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers.serve import make_params
from deepspeed_tpu.models import TransformerConfig, build_model
from deepspeed_tpu.models.generation import forward_with_cache, init_cache
from deepspeed_tpu.ops.pallas import sparse_select as ss
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference)
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import paged_forward
from deepspeed_tpu.serving.scheduler import RUNNING

FAM = harness.load_family("keye_vl2")
TOPK = 16
#: 16 experts ranked, 2 held (a chip of eight), top-4; a 4 x 8 indexer
TINY = dict(
    family="keye_vl2", attention_bias=False, decoder_sparse_step=1,
    head_dim=8, hidden_act="silu", hidden_size=32, intermediate_size=96,
    max_position_embeddings=256, max_window_layers=3, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=24, norm_topk_prob=True,
    num_attention_heads=4, num_experts=2, num_experts_per_tok=4,
    num_hidden_layers=3, num_key_value_heads=2, num_local_experts=2,
    rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [1, 1, 2], "rope_type": "default",
                  "type": "default"},
    rope_theta=1e7,
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": TOPK},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=64, deployment={"router_outputs": 16, "experts_held": [4, 2]})
BS, NBK, BLOCKS = 8, 12, 30
PROMPT, CHUNK, STEPS = 69, 13, 4


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


def built(config=TINY, seed=11, **knobs):
    """``(model config, parameters with a drawn LayerNorm on the indexer's
    key)``."""
    model, cfg = build_model(TransformerConfig(**{
        **FAM.model_kwargs(config), "dtype": jnp.float32,
        "attention_impl": "reference", **knobs}))
    params = make_params(model, cfg, seed, jnp.float32)
    norm = params["blocks"]["index_k_norm"]
    key = jax.random.PRNGKey(seed)
    norm["bias"] = 0.2 * jax.random.normal(key, norm["bias"].shape)
    norm["scale"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.fold_in(key, 1), norm["scale"].shape)
    return cfg, params


@pytest.fixture(scope="module")
def tiny():
    return built()


@pytest.fixture(scope="module")
def sequence(tiny):
    ids = np.random.default_rng(7).integers(
        1, 64, size=(1, PROMPT + STEPS)).astype(np.int32)
    return ids, np.asarray(FAM.reference_logits(TINY, tiny[1],
                                                jnp.asarray(ids[0])))


def _dense_cache(cfg, params, ids):
    """``generate()``'s cache: the prompt at once, then a token a call (the
    dense-masked form of the selection)."""
    cache = init_cache(cfg, 1, 80, jnp.float32)
    logits, cache = forward_with_cache(cfg, params, ids[:, :PROMPT], cache)
    out = [np.asarray(logits)]
    for t in range(PROMPT, PROMPT + STEPS):
        logits, cache = forward_with_cache(cfg, params, ids[:, t:t + 1],
                                           cache)
        out.append(np.asarray(logits))
    return np.concatenate(out, axis=1)[0]


def _paged(cfg, params, ids, interpret=False):
    """The serving loop's calls: chunks that end in mid-block, then decode
    steps beside an idle lane."""
    table = np.full((1, NBK), NULL_BLOCK, np.int32)
    table[0, :10] = (7, 2, 9, 4, 21, 13, 5, 17, 11, 3)
    pools, got = init_pool(cfg, BLOCKS, BS, jnp.float32), []
    assert not cfg.index_heads or \
        pools["ki"].shape == (3, 1, BLOCKS * BS, 128)

    forward = jax.jit(lambda *a: paged_forward(cfg, *a, BS,
                                               interpret=interpret))

    def call(tokens, bt, q0, ctx, real):
        nonlocal pools
        logits, pools = forward(
            params, jnp.asarray(tokens), pools, jnp.asarray(bt),
            jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32))
        got.append(np.asarray(logits)[0, :real])

    for q0 in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - q0)
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = ids[0, q0:q0 + n]
        call(chunk, table, [q0], [q0 + n], n)
    lanes = np.concatenate([table, np.full((1, NBK), NULL_BLOCK, np.int32)])
    for t in range(PROMPT, PROMPT + STEPS):
        call(np.asarray([[ids[0, t]], [0]], np.int32), lanes, [t, 0],
             [t + 1, 1], 1)
    return np.concatenate(got, axis=0)


def _paged_kernels(cfg, params, ids):
    """The same calls on the three kernels, interpreted."""
    return _paged(dataclasses.replace(cfg, attention_impl="auto"), params,
                  ids, interpret=True)


@pytest.mark.parametrize("path", [_dense_cache, _paged, _paged_kernels],
                         ids=["dense_cache", "paged", "paged_kernels"])
def test_the_program_matches_the_plain_reference(tiny, sequence, path):
    ids, want = sequence
    np.testing.assert_allclose(path(*tiny, ids), want, rtol=2e-5, atol=2e-5)


def test_the_selection_selects(tiny, sequence):
    """The cases above prove nothing unless leaving the indexer out moves
    the logits behind position ``TOPK``; and up to there it moves none: a
    row that sees no more than top-k keys runs the dense arithmetic."""
    cfg, params = tiny
    ids, want = sequence
    dense = dataclasses.replace(cfg, index_heads=0, index_head_dim=0,
                                index_topk=0)
    got = _paged(dense, params, ids)
    np.testing.assert_allclose(got[:TOPK], want[:TOPK], rtol=2e-5, atol=2e-5)
    assert np.abs(got[TOPK + 8:] - want[TOPK + 8:]).max() > 0.05


def test_ties_go_to_the_lower_position_on_both_sides():
    """Scores on a coarse grid (ties in every row, -0.0 among them): the
    bisection kernel, its ``top_k`` twin and the reference's own selection
    keep the same keys."""
    rng = np.random.default_rng(3)
    scores = np.round(rng.standard_normal((24, 256)) * 2) / 2
    scores[scores == 0] = -0.0
    rows = np.arange(24)[:, None] * 9 + 20
    scores = jnp.asarray(np.where(np.arange(256)[None] <= rows, scores,
                                  -np.inf), jnp.float32)
    want, _ = FAM.own_selection(scores, TOPK)
    assert int(jnp.sum(want, axis=1).max()) == TOPK
    for kernel in (True, False):
        sel = ss.select(scores[None], TOPK, kernel=kernel, interpret=True)
        got = ss.selected(sel.scores, sel.thr[..., None], sel.tie[..., None],
                          jnp.arange(256)) & (sel.scores > -jnp.inf)
        assert np.array_equal(got[0], want), kernel
        positions = ss.bits_to_positions(
            np.asarray(ss.selection_bits(sel))[0], TOPK)
        for r in range(24):
            assert list(positions[r][positions[r] >= 0]) == \
                list(np.nonzero(np.asarray(want[r]))[0])


@pytest.mark.parametrize("shape", ["decode", "chunk", "two_lane_chunk"])
@pytest.mark.parametrize("window", [None, 24])
def test_the_kernel_masks_what_the_reference_masks(shape, window):
    """The paged kernel interpreted against ``paged_attention_reference``
    under a selection that leaves whole pages empty, alone and beside a
    window."""
    B, T, starts = {"decode": (3, 1, [95, 40, 7]), "chunk": (1, 24, [60]),
                    "two_lane_chunk": (2, 16, [80, 3])}[shape]
    rng = np.random.default_rng(0)
    L, kvh, nh, hd, bs, blocks, nbk = 2, 2, 8, 128, 8, 40, 12
    pool = lambda: jnp.asarray(rng.standard_normal((L, kvh, blocks, bs, hd)),
                               jnp.float32)
    kp, vp = pool(), pool()
    bt = jnp.asarray(rng.permutation(np.arange(1, blocks))[:B * nbk].reshape(
        B, nbk), jnp.int32)
    q0 = jnp.asarray(starts, jnp.int32)
    ctx = q0 + T
    q = jnp.asarray(rng.standard_normal((B, nh, T, hd)), jnp.float32)
    Kp = ss.padded_keys(nbk * bs)
    pos = jnp.arange(Kp)[None, None, :]
    seen = (pos <= q0[:, None, None] + jnp.arange(T)[None, :, None]) \
        & (pos < ctx[:, None, None])
    sc = jnp.asarray(np.round(rng.standard_normal((B, T, Kp)) * 2) / 2,
                     jnp.float32)
    sc = sc.at[:, :, 16:32].add(-10.0)              # pages 2 and 3: empty
    sel = ss.select(jnp.where(seen, sc, -jnp.inf), TOPK, kernel=False)
    kw = dict(layer_idx=jnp.int32(1), q_start=q0 if T > 1 else None,
              window=window)
    got = paged_attention(q, kp, vp, bt, ctx, select=sel, interpret=True,
                          **kw)
    want = paged_attention_reference(q, kp, vp, bt, ctx, select=sel, **kw)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    dense = paged_attention_reference(q, kp, vp, bt, ctx, **kw)
    assert float(jnp.max(jnp.abs(want - dense))) > 0.1


#: a lane's tokens before a decode call's own, over a table of 25 pages of 8
#: (``Kp`` 256: two key tiles of 128); the last lane is idle (no key at all)
_HELD = {"mid_page": 100, "mid_tile": 52, "on_a_tile": 127,
         "first_of_a_tile": 128, "one_key": 0, "fills_the_table": 199,
         "idle": 0}
#: call -> (index heads, rows a lane, the lanes' first positions, contexts)
_SCORE_CALLS = {
    "chunk": (4, 12, [100, 30], [112, 37]),
    # one row a lane: the decode-shaped program, 16 heads the rows of one
    # matmul, a lane a row of ``[B, Kp]``, its own key tiles only
    "a_row_a_lane": (16, 1, list(_HELD.values()),
                     [h + 1 for h in _HELD.values()][:-1] + [0]),
    # 2 to 8 rows a lane keep the chunk kernel's 8-row tiles
    "five_rows_a_lane": (16, 5, [max(h - 4, 0) for h in _HELD.values()],
                         [h + 1 for h in _HELD.values()][:-1] + [0]),
}


@pytest.mark.parametrize("window", [None, 20, 150])
@pytest.mark.parametrize("call", list(_SCORE_CALLS))
def test_the_score_kernel_reads_its_pages_through_the_table(call, window):
    """Scores through a permuted block table, ``-inf`` exactly where the
    reference has it (an idle lane and a padding lane everywhere), whatever
    program the call's shape picks."""
    rng = np.random.default_rng(1)
    H, T, q0, ctx = _SCORE_CALLS[call]
    B, D, bs, nbk, blocks, L = len(q0), 128, 8, 25, 200, 3
    qi = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32)
    pool = jnp.asarray(rng.standard_normal((L, 1, blocks, bs, D)),
                       jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, blocks))[:B * nbk].reshape(
        B, nbk), jnp.int32)
    q0, ctx = jnp.asarray(q0), jnp.asarray(ctx)
    got = ss.index_scores(qi, w, pool, bt, 2, q0, ctx, window=window,
                          interpret=True)
    keys = pool.reshape(L * blocks, bs, D)[2 * blocks + bt].reshape(B, -1, D)
    want = ss.index_scores_reference(qi, w, keys, q0, ctx, window)
    Kp = ss.padded_keys(nbk * bs)
    assert got.shape == want.shape == (B, T, Kp)
    # each lane's first row sees the keys up to its own, inside the window
    seen = np.minimum(np.minimum(q0 + 1, ctx), window or Kp)
    assert np.array_equal(np.isfinite(want[:, 0]).sum(-1), seen)
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    np.testing.assert_allclose(np.where(np.isfinite(want), got, 0),
                               np.where(np.isfinite(want), want, 0),
                               rtol=1e-4, atol=1e-4)
    if T == 1:
        # the rule the host counts by: a lane's tiles hold every key it
        # sees and no tile more than its context and window need
        first, end = ss.score_tiles(np.asarray(q0), np.asarray(ctx),
                                    window or 0, Kp, np)
        tk = ss._key_tile(Kp)
        cols = np.arange(Kp)[None]
        inside = (cols >= first[:, None] * tk) & (cols < end[:, None] * tk)
        assert not (np.isfinite(got[:, 0]) & ~inside).any()
        assert (end - first <= -(-seen // tk) + 1).all() \
            and end[-1] == first[-1]


def _engine(cfg, params, **serving):
    return ServingEngine(cfg, params, interpret=True, serving=dict(dict(
        block_size=8, pool_blocks=60, max_batch=4, max_blocks_per_seq=12,
        prefill_chunk_tokens=16, prefix_cache=True), **serving))


def test_the_hand_out_follows_the_reference(tiny):
    """``keep_routing``: ``[fed tokens, layers, k + top-k]``, a token's
    experts and then the positions of the keys it attended, rising, -1
    behind a short row's count; the reference routed and selecting by them
    reads no deficit and puts the served tokens first; the counters count
    what the rows saw."""
    cfg, params = tiny
    srv = _engine(cfg, params)
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(1, 64, size=n).tolist(),
                       max_new_tokens=m, keep_routing=True)
            for n, m in ((70, 6), (11, 9))]
    srv.run_until_idle()
    for r in reqs:
        fed = r.prompt + r.output_tokens[:-1]
        got = r.routed_experts
        assert got.shape == (len(fed), 3, 4 + TOPK) and got.dtype == np.int32
        keys = got[:, :, 4:]
        for t in range(len(fed)):
            n = min(t + 1, TOPK)
            assert (keys[t, :, :n] >= 0).all() and (keys[t, :, n:] == -1).all()
            assert (np.diff(keys[t, :, :n], axis=-1) > 0).all()
            assert keys[t].max() <= t
        assert got[:, :, :4].max() > 5          # ids beyond the held 4, 5
        logits, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(got))
        assert deficits.shape == got.shape and float(deficits.max()) < 1e-3
        served = np.asarray(logits)[len(r.prompt) - 1:]
        assert (served.argmax(-1) == np.asarray(r.output_tokens)).all()
    c = srv.telemetry()["counters"]
    fed = c["prefill_tokens"] + c["tokens_generated"] - 2
    assert c["sparse.rows_sum"] == 3 * fed
    assert c["sparse.keys_selected_sum"] < c["sparse.keys_scored_sum"]
    assert c["sparse.keys_selected_sum"] <= TOPK * c["sparse.rows_sum"]
    assert c["sparse.pages_walked_sum"] > 0
    g = srv.telemetry()["gauges"]
    assert g["kv.bytes_per_token"] == 3 * (2 * 2 * 8 + 128) * 4
    srv.close()


@pytest.mark.parametrize("table_blocks", [12, 48])
def test_the_topk_counters_follow_the_tiles(table_blocks):
    """``sparse.topk_*``: the row tiles the top-k kernel is handed, those
    that make no pass, and the columns the others pass over beside the whole
    table's: a prompt under top-k keys leaves only idle tiles, and a table
    sized beyond what the requests use costs the kernel nothing (the share
    of its columns read falls with its length)."""
    cfg, params = built({**TINY, "max_position_embeddings": 512})
    srv = _engine(cfg, params, max_blocks_per_seq=table_blocks,
                  pool_blocks=120)
    rng = np.random.default_rng(9)
    counters = lambda: dict(srv.telemetry()["counters"])
    tiles = lambda rows: -(-rows // ss._TOPK_ROWS)
    srv.submit(rng.integers(1, 64, size=TOPK - 4).tolist(), max_new_tokens=2)
    srv.run_until_idle()
    c = counters()
    # a chunk's 16 rows beside the 4 (idle) lanes of its own call, and a
    # decode call's 4 lanes, in each of 3 layers
    assert c["sparse.topk_tiles_sum"] == c["sparse.topk_tiles_idle_sum"] \
        == 3 * (tiles(16) + 2 * tiles(4))
    assert c["sparse.topk_columns_sum"] == 0 \
        == c["sparse.topk_columns_table_sum"]
    srv.submit(rng.integers(1, 64, size=70).tolist(), max_new_tokens=6)
    srv.run_until_idle()
    c = counters()
    Kp = ss.padded_keys(table_blocks * 8)
    busy = (c["sparse.topk_tiles_sum"] - c["sparse.topk_tiles_idle_sum"]) // 3
    # chunks 2 to 4 (rows 16-63: the first tile holds row 16, which sees 17
    # keys), the last chunk's 6 rows on one tile, and five decode calls
    assert busy == 3 * tiles(16) + 1 + 5
    assert c["sparse.topk_columns_table_sum"] == 3 * busy * Kp
    # contexts of at most 76 keys lie inside the first column step of 128
    assert c["sparse.topk_columns_sum"] == 3 * busy * 128
    assert (c["sparse.topk_columns_sum"] < c["sparse.topk_columns_table_sum"]
            ) == (table_blocks > 16)
    srv.close()


def test_the_score_counters_follow_the_lanes_tiles():
    """``sparse.score_tiles_*``: the key tiles a decode call's score
    programs copy and multiply, by the kernel's own rule, beside lanes x
    the table's tiles; a model without an indexer counts neither."""
    cfg, params = built({**TINY, "max_position_embeddings": 512})
    srv = _engine(cfg, params, max_blocks_per_seq=48, pool_blocks=120)
    Kp, prompt, new = ss.padded_keys(48 * 8), 120, 12    # three tiles of 128
    srv.submit(np.random.default_rng(5).integers(1, 64, size=prompt).tolist(),
               max_new_tokens=new)
    srv.run_until_idle()
    c = dict(srv.telemetry()["counters"])
    srv.close()
    # the prefill's eight chunks ride a call with four idle lanes each; its
    # last gives the first token, a decode call each of the others: one lane
    # of 120 to 130 tokens beside three idle ones
    assert c["mixed.calls"] == 8 and c["mixed.lane_rows_sum"] == 0
    held = np.zeros((8 + new - 1, 4), np.int64)
    held[8:, 0] = np.arange(prompt, prompt + new - 1)
    first, end = ss.score_tiles(held, held + 1, 0, Kp, np)
    assert not first.any() and np.array_equal(
        end[:, 0], 1 + (held[:, 0] >= 128)) and (end[:, 1:] == 1).all()
    assert c["sparse.score_tiles_sum"] == 3 * int((end - first).sum())
    assert c["sparse.score_tiles_table_sum"] == 3 * held.size * 3
    model, plain = build_model(TransformerConfig(**{
        **FAM.model_kwargs(TINY), "dtype": jnp.float32,
        "attention_impl": "reference", "index_heads": 0, "index_head_dim": 0,
        "index_topk": 0}))
    srv = _engine(plain, make_params(model, plain, 11, jnp.float32))
    srv.submit([3, 4, 5], max_new_tokens=3)
    srv.run_until_idle()
    assert not [k for k in srv.telemetry()["counters"]
                if k.startswith("sparse.")]
    srv.close()


def test_the_indexers_keys_travel_with_the_blocks(tiny):
    """A prefix-cache hit and a lane preempted and resumed give a cold run's
    tokens and selection: the ``ki`` leaf is in the blocks the prefix cache
    and the allocator hand on."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 64, size=72).tolist()
    cold = _engine(cfg, params, prefix_cache=False)
    want = cold.submit(prompt, max_new_tokens=10, keep_routing=True)
    cold.run_until_idle()
    cold.close()

    srv = _engine(cfg, params)
    first = srv.submit(prompt, max_new_tokens=10)
    srv.run_until_idle()
    hit = srv.submit(prompt, max_new_tokens=10, keep_routing=True)
    srv.run_until_idle()
    assert hit.prefix_hit_tokens == 64
    assert first.output_tokens == hit.output_tokens == want.output_tokens
    assert (hit.routed_experts[:64] == -1).all()
    assert np.array_equal(hit.routed_experts[64:], want.routed_experts[64:])

    victim = srv.submit(prompt[:50] + [1, 2, 3], max_new_tokens=10)
    while victim.state != RUNNING or len(victim.output_tokens) < 4:
        srv.step()
    assert srv.preempt_request(victim)
    emitted = list(victim.output_tokens)
    resumed = srv.submit(victim.prompt + emitted,
                         max_new_tokens=10 - len(emitted))
    srv.run_until_idle()
    whole = cold_tokens(cfg, params, victim.prompt, 10)
    assert emitted + resumed.output_tokens == whole
    srv.close()


def cold_tokens(cfg, params, prompt, n):
    srv = _engine(cfg, params, prefix_cache=False)
    r = srv.submit(prompt, max_new_tokens=n)
    srv.run_until_idle()
    srv.close()
    return r.output_tokens


def test_a_training_block_and_a_half_set_indexer_are_refused(tiny):
    cfg, params = tiny
    model, _ = build_model(cfg)
    with pytest.raises(NotImplementedError, match="ROADMAP M7"):
        model.apply({"params": params},
                    {"input_ids": np.ones((1, 8), np.int32)})
    with pytest.raises(ValueError, match="all three"):
        TransformerConfig(num_layers=2, index_heads=4)
    with pytest.raises(ValueError, match="causal decoder"):
        TransformerConfig(num_layers=2, index_heads=4, index_head_dim=8,
                          index_topk=4, causal=False)
    dense = dataclasses.replace(cfg, index_heads=0, index_head_dim=0,
                                index_topk=0)
    assert cfg.num_params() - dense.num_params() == sum(
        a.size for name, leaf in params["blocks"].items()
        if name.startswith("index_") for a in jax.tree_util.tree_leaves(leaf))


def test_the_prefix_cache_evicts_in_order_of_last_use_reading_one_entry():
    """Long prompts make an entry a block; an eviction reads the first
    entry of a dict kept in order of last use (two where the first is the
    protected one), never every entry."""
    from deepspeed_tpu.serving.kv_cache import BlockPool, PrefixCache
    pool = BlockPool(40, 4)
    cache = PrefixCache(pool)
    prompts = {name: [ord(name)] * 12 for name in "abc"}
    for toks in prompts.values():
        blocks = pool.alloc(3)
        cache.insert(toks, blocks)
        pool.release(blocks)
    assert len(cache) == 9 and pool.used_count == 9
    assert cache.match(prompts["a"] + [1])[0] == 12       # a: used last
    key_b1 = cache.peek(prompts["b"][:5])[1]
    before = pool.counters["prefix.evict_scanned_entries"]
    cache.evict(pool.free_count + 1, protect=key_b1)
    # oldest first: a's two shorter prefixes (its longest was used last and
    # holds its blocks still), then b's, whose first entry is protected and
    # read past twice; b's last entry frees blocks and the pass ends
    assert cache.peek(prompts["b"] + [1])[0] == 4
    assert cache.peek(prompts["c"] + [1])[0] == 12
    assert cache.peek(prompts["a"] + [1])[0] == 12
    assert len(cache) == 5
    assert pool.counters["prefix.evict_scanned_entries"] - before == 6
