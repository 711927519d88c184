"""DeepSeek-V3.2-Exp's layer at a tiny size on the CPU (float32, seeded
weights) against its plain reference (``benchmark/families/deepseek_v32.py``,
the EXPANDED form): latent attention over ONE cached row a token whose keys
a learned indexer picks (4 index heads of 8, fed by the normed QUERY latent,
their first 4 lanes turned under the model's YaRN table; top-16 of contexts
of up to 73), and behind a leading dense layer (which selects too) a chip's
share of a ``noaux_tc`` sigmoid mixture (32 outputs in 8 groups of which a
token keeps 4 by the sum of their two best BIASED scores, top-8 renormalised
and scaled 2.5, one shared expert; this chip holds HALF of group 0)."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers.serve import make_params
from deepspeed_tpu.models import TransformerConfig, build_model
from deepspeed_tpu.models.generation import (absorb_output, absorb_query,
                                             forward_with_cache, generate,
                                             init_cache)
from deepspeed_tpu.moe.dropless import kept_groups, route_sigmoid_topk
from deepspeed_tpu.ops.pallas import latent_attention as la
from deepspeed_tpu.ops.pallas import sparse_select as ss
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import paged_forward

FAM = harness.load_family("deepseek_v32")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
TINY = dict(
    family="deepseek_v32", attention_bias=False, first_k_dense_replace=1,
    hidden_act="silu", hidden_size=32, intermediate_size=80, kv_lora_rank=16,
    max_position_embeddings=256, model_type="deepseek_v32",
    moe_intermediate_size=24, moe_layer_freq=1, n_group=8,
    n_routed_experts=2, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=8, num_hidden_layers=3,
    num_key_value_heads=4, num_nextn_predict_layers=0, q_lora_rank=24,
    qk_nope_head_dim=8, qk_rope_head_dim=4, rms_norm_eps=1e-6,
    rope_scaling=YARN, rope_theta=10000, routed_scaling_factor=2.5,
    scoring_func="sigmoid", tie_word_embeddings=False, topk_group=4,
    topk_method="noaux_tc", v_head_dim=8, vocab_size=64, index_n_heads=4,
    index_head_dim=8, index_topk=16,
    deployment={"router_outputs": 32, "experts_held": [0, 2]})
UNCUT = dict({k: v for k, v in TINY.items() if k != "deployment"},
             n_routed_experts=32)
BS, NBK, BLOCKS = 8, 12, 30
PROMPT, CHUNK, STEPS = 69, 13, 4


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


def built(config=TINY, seed=11, **knobs):
    """``(model config, parameters with a drawn LayerNorm bias on the
    indexer's key and a drawn selection bias on the router)``."""
    model, cfg = build_model(TransformerConfig(**{
        **FAM.model_kwargs(config), "dtype": jnp.float32,
        "attention_impl": "reference", **knobs}))
    params = make_params(model, cfg, seed, jnp.float32)
    key = jax.random.PRNGKey(seed)
    for i, stack in enumerate(("dense_blocks", "blocks")):
        norm = params[stack]["index_k_norm"]
        norm["bias"] = 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), norm["bias"].shape)
    gate = params["blocks"]["moe"]["gate"]
    gate["bias"] = 0.2 * jax.random.normal(jax.random.fold_in(key, 9),
                                           gate["bias"].shape)
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
    """``generate()``'s cache: the prompt at once, then a token a call."""
    cache = init_cache(cfg, 1, 80, jnp.float32)
    assert set(cache) == {"ckv", "ki", "pos"} and \
        cache["ckv"].shape == (3, 1, 1, 80, 20) and \
        cache["ki"].shape == (3, 1, 1, 80, 8)
    logits, cache = forward_with_cache(cfg, params, ids[:, :PROMPT], cache)
    out = [np.asarray(logits)]
    for t in range(PROMPT, PROMPT + STEPS):
        logits, cache = forward_with_cache(cfg, params, ids[:, t:t + 1],
                                           cache)
        out.append(np.asarray(logits))
    return np.concatenate(out, axis=1)[0]


def _paged(cfg, params, ids, interpret=False, whole=False):
    """The serving loop's calls: the prompt whole or in chunks that end in
    mid-block, then decode steps beside an idle lane."""
    table = np.full((1, NBK), NULL_BLOCK, np.int32)
    table[0, :10] = (7, 2, 9, 4, 21, 13, 5, 17, 11, 3)
    pools, got = init_pool(cfg, BLOCKS, BS, jnp.float32), []
    # TWO leaves, no k / v: the 20-wide row and the 8-wide indexer key, each
    # on a whole 128-lane tile
    assert {n: a.shape for n, a in pools.items()} == {
        "ckv": (3, 1, BLOCKS * BS, 128), "ki": (3, 1, BLOCKS * BS, 128)}
    forward = jax.jit(lambda *a: paged_forward(cfg, *a, BS,
                                               interpret=interpret))

    def call(tokens, bt, q0, ctx, real):
        nonlocal pools
        logits, pools = forward(
            params, jnp.asarray(tokens), pools, jnp.asarray(bt),
            jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32))
        got.append(np.asarray(logits)[0, :real])

    step = PROMPT if whole else CHUNK
    for q0 in range(0, PROMPT, step):
        n = min(step, PROMPT - q0)
        chunk = np.zeros((1, -(-step // BS) * BS), np.int32)
        chunk[0, :n] = ids[0, q0:q0 + n]
        call(chunk, table, [q0], [q0 + n], n)
    lanes = np.concatenate([table, np.full((1, NBK), NULL_BLOCK, np.int32)])
    for t in range(PROMPT, PROMPT + STEPS):
        call(np.asarray([[ids[0, t]], [0]], np.int32), lanes, [t, 0],
             [t + 1, 1], 1)
    return np.concatenate(got, axis=0)


def _paged_whole(cfg, params, ids):
    return _paged(cfg, params, ids, whole=True)


def _paged_kernel(cfg, params, ids):
    """The same chunked calls on the three kernels (index scores, top-k,
    the latent kernel under the selection), interpreted."""
    return _paged(dataclasses.replace(cfg, attention_impl="auto"), params,
                  ids, interpret=True)


@pytest.mark.parametrize("path", [_dense_cache, _paged_whole, _paged,
                                  _paged_kernel],
                         ids=["dense_cache", "paged_whole", "paged_chunks",
                              "paged_kernel"])
def test_the_program_matches_the_plain_reference(tiny, sequence, path):
    """The program against the expanded float32 reference, logits at every
    position: the prompt's (whole or in chunks) and the decode steps'. The
    selection selects: the same model attending every visible key is
    several logits away."""
    cfg, params = tiny
    ids, want = sequence
    got = path(cfg, params, ids)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    dense = np.asarray(FAM.reference_logits(
        TINY, params, jnp.asarray(ids[0]), ignore_selection=True))
    np.testing.assert_allclose(dense[:16], want[:16], atol=2e-4)
    assert np.abs(dense[17:] - want[17:]).max() > 1.0


def test_generate_follows_the_reference(tiny):
    """``generate()`` (prefill and its decode scan in one program, the
    dense-masked form) emits the reference's greedy tokens."""
    cfg, params = tiny
    prompt = np.random.default_rng(3).integers(1, 64, size=(1, 21))
    out = np.asarray(generate(cfg, params, jnp.asarray(prompt, jnp.int32), 5))
    for t in range(21, 26):
        logits = FAM.reference_logits(TINY, params, jnp.asarray(out[0, :t]))
        assert int(np.asarray(logits)[-1].argmax()) == out[0, t]


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The sixteen shares of a sparse layer (two experts each: half a
    routing group of four), the shared expert counted once, add up to the
    uncut reference's layer on the same tokens; in the program
    (``dropless_moe(held=)`` inside a group) and in the reference."""
    from deepspeed_tpu.models.generation import _moe_mlp
    cfg, params = built(UNCUT)
    moe = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    g = jax.random.normal(jax.random.PRNGKey(5), (40, 32), jnp.float32)
    route = FAM._router_step(8, 8, 4, True, 2.5, 1e-6)
    one = {"scale": jnp.ones((32,))}
    with jax.default_matmul_precision("highest"):
        gn, weights, own, _ = route(moe["gate"], one, g)
        whole = FAM.reference_moe(moe, gn, weights, own)
        shared = FAM.reference_moe(
            {**moe, "experts": jax.tree.map(lambda a: a[:0], moe["experts"])},
            gn, weights, own)
        parts, program = [], []
        for first in range(0, 32, 2):
            held = {**moe, "experts": jax.tree.map(
                lambda a: a[first:first + 2], moe["experts"])}
            parts.append(FAM.reference_moe(held, gn, weights, own, first,
                                           shared=False))
            share = dataclasses.replace(cfg, moe_held=(first, 2))
            y, routing = _moe_mlp(share, held, gn[None])
            assert np.array_equal(np.asarray(routing.experts),
                                  np.asarray(own))
            program.append(y[0] - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sum(program) + shared),
                               np.asarray(whole), atol=2e-5)


def test_noaux_tc_ranks_a_group_by_its_two_best_biased_scores():
    """``route_sigmoid_topk(groups=)`` on seeded logits with a non-zero
    bias: the eight picks lie in the 4 groups whose two best BIASED scores
    add up to most; their weights are the UNBIASED sigmoids, renormalised
    and scaled; and, by hand, one row whose groups rank differently by their
    maximum: group 0 holds the best single score and a poor second, group 2
    two good ones."""
    z = jax.random.normal(jax.random.PRNGKey(1), (200, 32))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    r = route_sigmoid_topk(z, 8, True, bias, 2.5, (8, 4))
    s = np.asarray(jax.nn.sigmoid(z))
    biased = s + np.asarray(bias)
    two = np.sort(biased.reshape(200, 8, 4), -1)[..., -2:].sum(-1)
    top4 = np.argsort(-two, axis=1)[:, :4]
    assert np.array_equal(np.asarray(r.groups), np.asarray(
        kept_groups(jnp.asarray(biased), (8, 4), best=2)))
    picks = np.asarray(r.experts)
    by_max = 0
    for t in range(200):
        assert set(picks[t] // 4) <= set(top4[t])
        allowed = np.where(np.isin(np.arange(32) // 4, top4[t]), biased[t], 0)
        assert set(picks[t]) == set(np.argsort(-allowed)[:8])
        by_max += set(np.argsort(-biased[t].reshape(8, 4).max(-1))[:4]) \
            != set(top4[t])
    assert by_max > 20          # the two rules differ on many rows
    w = np.take_along_axis(s, picks, 1)
    np.testing.assert_allclose(np.asarray(r.weights),
                               2.5 * w / w.sum(1, keepdims=True), rtol=1e-5)
    # the bias moves picks, and never a weight
    assert (np.sort(picks, 1) != np.sort(np.asarray(route_sigmoid_topk(
        z, 8, True, None, 2.5, (8, 4)).experts), 1)).any()
    # by hand: 2 groups of 2, keep 1, k 1. Group 0 = (3.0, -3.0): sigmoids
    # 0.953 + 0.047 = 1.0; group 1 = (1.0, 1.0): 0.731 x 2 = 1.462
    hand = route_sigmoid_topk(jnp.asarray([[3.0, -3.0, 1.0, 1.0]]), 1, False,
                              None, 1.0, (2, 1))
    assert np.asarray(hand.experts).tolist() == [[2]]
    assert np.asarray(kept_groups(jax.nn.sigmoid(jnp.asarray(
        [[3.0, -3.0, 1.0, 1.0]])), (2, 1))).tolist() == [[True, False]]


#: a call's rows, then lane 0's and lane 1's (first position, context);
#: lane 2 is idle; the top-k is 64
CALLS = {
    "decode_under_topk": (1, (40, 41), (8, 9)),
    "decode_over_topk": (1, (700, 701), (76, 77)),
    "chunk_under_topk": (20, (30, 50), (3, 21)),
    "chunk_over_topk": (300, (440, 740), (301, 601)),
    "first_chunk_over_topk": (300, (0, 300), (0, 280)),
    "padded_last_chunk": (512, (600, 637), (40, 45)),
}


@pytest.mark.parametrize("shape", list(CALLS))
def test_both_kernel_forms_attend_the_selected_rows(shape):
    """One pool of latent rows and of indexer keys, one block table: the
    decode form (absorbed) and the chunk form (expanded) under ``select=``,
    interpreted, and the absorbed jnp twin give what the dense-masked form
    gives: full attention with every key a row did not select masked, worked
    plainly here from the selection's own bits. Under the top-k a row
    attends all it sees; over it exactly 64 keys."""
    from tests.test_deepseek_v2 import _expanded
    rng = np.random.default_rng(0)
    L, bs, W, R, ROPE, NH, NOPE, V, K = 2, 8, 256, 128, 16, 8, 32, 32, 64
    T, *lanes = CALLS[shape]
    NB, nbk = 128, 100
    pool = jnp.asarray(rng.normal(size=(L, 1, NB, bs, W)), jnp.float32
                       ).at[..., R + ROPE:].set(0)
    bt = jnp.asarray(rng.permutation(NB - 1)[:nbk].reshape(1, nbk) + 1,
                     jnp.int32)
    bt = jnp.concatenate([bt, bt[:, ::-1], jnp.zeros_like(bt)])
    q0 = np.asarray([lane[0] for lane in lanes] + [0])
    ctx = np.asarray([lane[1] for lane in lanes] + [0])
    wk = jnp.asarray(rng.normal(size=(NH, R, NOPE)) * R ** -.5, jnp.float32)
    wv = jnp.asarray(rng.normal(size=(NH, R, V)) * R ** -.5, jnp.float32)
    qn = jnp.asarray(rng.normal(size=(3, NH, T, NOPE)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(3, NH, T, ROPE)), jnp.float32)
    lens, first = jnp.asarray(ctx, jnp.int32), jnp.asarray(q0, jnp.int32)
    # index scores as an indexer leaves them: -inf where a row does not see
    Kp = ss.padded_keys(nbk * bs)
    at = q0[:, None] + np.arange(T)[None]
    seen = (np.arange(Kp)[None, None] <= at[..., None]) \
        & (np.arange(Kp)[None, None] < ctx[:, None, None])
    scores = jnp.asarray(np.where(seen, rng.normal(size=(3, T, Kp)),
                                  -np.inf), jnp.float32)
    sel = ss.select(scores, K, kernel=False)
    on = np.asarray(ss.selected(scores, sel.thr[..., None],
                                sel.tie[..., None], jnp.arange(Kp))) & seen
    counts = on.sum(-1)
    real = ctx[:2, None] - q0[:2, None] > np.arange(T)[None]
    assert (counts[:2][real] == np.minimum(at[:2][real] + 1, K)).all()
    kw = dict(sm_scale=0.11, layer_idx=jnp.int32(1))
    want = _expanded_selected(pool, bt, ctx, q0, qn, qp, wk, wv, 1, R, 0.11,
                              on)
    if "under" in shape:        # every visible key: the dense form's numbers
        dense = _expanded(pool, bt, ctx, q0, qn, qp, wk, wv, 1, R, 0.11)
        for lane in range(2):
            np.testing.assert_allclose(want[lane][:, real[lane]],
                                       dense[lane][:, real[lane]], atol=1e-9)
    qa = absorb_query(qn, qp, wk, W)
    twin = absorb_output(la.latent_attention_reference(
        qa, pool, bt, lens, value=R, q_start=first, select=sel, **kw), wv)
    if T == 1:
        kernel = absorb_output(la.latent_attention(
            qa, pool, bt, lens, value=R, interpret=True, select=sel, **kw),
            wv)
    else:
        kernel = la.latent_chunk_attention(
            qn, qp, wk, wv, pool, bt, lens, q_start=first, interpret=True,
            select=sel, **kw)
    for got in (twin, kernel):
        assert got.shape == (3, NH, T, V)
        for lane in range(2):
            np.testing.assert_allclose(
                np.asarray(got)[lane][:, real[lane]],
                want[lane][:, real[lane]], atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(kernel)).all()    # the padding rows
    assert not np.asarray(kernel)[2].any()          # the idle lane


def _expanded_selected(pool, bt, ctx, q0, qn, qp, wk, wv, layer, rank, scale,
                       on):
    """``test_deepseek_v2._expanded`` with each row over the keys ``on [B,
    T, Kp]`` gives it (numpy, float64)."""
    pool, wk, wv = (np.asarray(a, np.float64) for a in (pool, wk, wv))
    B, nh, T, _ = qn.shape
    out = np.zeros((B, nh, T, wv.shape[-1]))
    for b in range(B):
        if not ctx[b]:
            continue
        rows = pool[layer, 0][np.asarray(bt)[b]].reshape(-1, pool.shape[-1])
        c, k_pe = rows[:, :rank], rows[:, rank:rank + qp.shape[-1]]
        k = np.einsum("kc,hcd->hkd", c, wk)
        v = np.einsum("kc,hcd->hkd", c, wv)
        s = scale * (np.einsum("htd,hkd->htk", np.asarray(qn[b], np.float64),
                               k)
                     + np.einsum("htd,kd->htk", np.asarray(qp[b], np.float64),
                                 k_pe))
        keep = on[b][:, :len(rows)]
        keep = np.where(keep.any(-1, keepdims=True), keep, True)  # padding
        s = np.where(keep[None], s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("htk,hkd->htd", a / a.sum(-1, keepdims=True), v)
    return out


def test_the_host_counts_the_chunk_forms_tile_pairs_by_the_kernels_own_rule():
    """``chunk_key_tiles``: the (256-row tile, 512-key turn) pairs of the
    turns a chunk's programs walk, and those ``chunk_row_tiles`` lets them
    compute (a tile whose last row stands before a turn's first key skips
    it)."""
    # 1 536 rows behind 12 288 cached tokens: 27 turns x 6 tiles; the last
    # three turns lie on the chunk's own triangle: 6, 4 and 2 tiles
    assert la.chunk_key_tiles(1536, 12288, 13824, 32, 800) == (
        27 * 6, 24 * 6 + 6 + 4 + 2)
    # a first chunk: its triangle alone
    assert la.chunk_key_tiles(1536, 0, 1536, 32, 800) == (18, 6 + 4 + 2)
    # a padded last chunk: 37 real rows of 512, one row tile has them
    assert la.chunk_key_tiles(512, 600, 637, 32, 800) == (4, 2)
    lo, full, hi = la.chunk_row_tiles(12288, 13824, 1536, 12800, 512, np)
    assert (int(lo), int(full), int(hi)) == (2, 4, 6)


def _engine(cfg, params, **serving):
    return ServingEngine(cfg, params, interpret=True, serving={**dict(
        block_size=8, pool_blocks=60, max_batch=4, max_blocks_per_seq=12,
        prefill_chunk_tokens=16, prefix_cache=True), **serving})


def cold_tokens(cfg, params, prompt, n):
    srv = _engine(cfg, params, prefix_cache=False)
    r = srv.submit(prompt, max_new_tokens=n)
    srv.run_until_idle()
    srv.close()
    return r.output_tokens


def test_the_hand_out_has_a_row_a_layer_that_picks_or_selects(tiny):
    """``keep_routing``: ``[fed tokens, 3 layers, 8 + 16]``: the DENSE
    layer's row has no picks (-1) and its selection; a sparse layer's row
    ids over the router's 32 in at most 4 groups, then the positions of the
    keys the token attended. The reference routed and attending by them
    reads no deficit and puts the served tokens first; the routing of the
    token before reads deficits over the tolerance. ``generate()`` emits
    ``serve()``'s tokens. The counters follow the rows."""
    cfg, params = tiny
    srv = _engine(cfg, params)
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(1, 64, size=n).tolist(),
                       max_new_tokens=6, keep_routing=True)
            for n in (70, 19)]
    srv.run_until_idle()
    for r in reqs:
        fed = list(r.prompt) + list(r.output_tokens[:-1])
        got = r.routed_experts
        assert got.shape == (len(fed), 3, 8 + 16) and got.dtype == np.int32
        assert (got[:, 0, :8] == -1).all() and (got[:, 1:, :8] >= 0).all()
        assert got[:, 1:, :8].max() > 1           # ids beyond the held 0-1
        assert all(len(set(row // 4)) <= 4
                   for row in got[:, 1:, :8].reshape(-1, 8))
        for li in range(3):                     # the dense layer's too
            keys = got[:, li, 8:]
            n = (keys >= 0).sum(-1)
            assert (n == np.minimum(np.arange(len(fed)) + 1, 16)).all()
            assert (keys.max(-1) <= np.arange(len(fed))).all()
        # past the top-k the layers select differently
        assert len(fed) < 40 or (got[40:, 0, 8:] != got[40:, 1, 8:]).any()
        logits, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(got))
        assert deficits.shape == got.shape and float(deficits.max()) < 1e-3
        served = np.asarray(logits)[len(r.prompt) - 1:]
        assert (served.argmax(-1) == np.asarray(r.output_tokens)).all()
        wrong = np.concatenate([got[:1], got[:-1]])
        _, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(wrong))
        assert float(np.median(deficits[:, 1:, :8].max(-1))) > 0.1
        out = np.asarray(generate(cfg, params, jnp.asarray(
            [r.prompt], jnp.int32), 6))[0, len(r.prompt):]
        assert out.tolist() == r.output_tokens
    t = srv.telemetry()
    c, g = t["counters"], t["gauges"]
    fed = c["prefill_tokens"] + c["tokens_generated"] - 2
    ctx = sum(n * (n + 1) // 2 for n in (70 + 5, 19 + 5))
    sel = sum(min(t, 16) for n in (70 + 5, 19 + 5) for t in range(1, n + 1))
    # sparse.* and mla.* both count in this model
    assert c["mla.rows_sum"] == c["sparse.rows_sum"] == 3 * fed
    assert c["mla.ctx_tokens_sum"] == c["sparse.keys_scored_sum"] == 3 * ctx
    assert c["mla.selected_keys_sum"] == c["sparse.keys_selected_sum"] \
        == 3 * sel
    assert c["routing.fetches"] > 0
    # this chip lies in group 0: the rows that kept it
    assert 0 < c["moe.group_rows_sum"] < 2 * fed
    assert c["moe.held_assignments"] <= 2 * c["moe.group_rows_sum"]
    assert 0 < c["moe.held_assignments"] < c["moe.assignments"] == 2 * 8 * fed
    assert g["kv.bytes_per_token"] == 3 * (128 + 128) * 4 and \
        g["kv.latent_lanes"] == 128 and g["kv.stored_heads"] == 1
    srv.close()


def test_a_served_chunk_counts_its_tile_pairs(tiny):
    """On the kernels (interpreted) a prompt's chunks attend EXPANDED under
    the selection; the engine counts the pairs by the kernel's rule and its
    tokens are the jnp twins' engine's."""
    cfg, params = tiny
    prompt = np.random.default_rng(5).integers(1, 64, size=45).tolist()
    want = cold_tokens(cfg, params, prompt, 4)
    srv = _engine(dataclasses.replace(cfg, attention_impl="auto"), params)
    r = srv.submit(prompt, max_new_tokens=4)
    srv.run_until_idle()
    assert r.output_tokens == want
    c = srv.telemetry()["counters"]
    # three chunks of one 256-row tile, one turn each, in 3 layers
    assert c["mla.chunk_key_tiles_sum"] == \
        c["mla.chunk_key_tiles_live_sum"] == 3 * 3
    # ... of 16, 16 and 13 tokens, through ONE program of a whole row tile
    assert srv.telemetry()["gauges"]["paged.prefill_path"] == \
        {"kernel (expanded)": [256]}
    assert srv._prefill_fn._cache_size() == 1
    srv.close()


@pytest.mark.parametrize("tokens, rows", [(1, 256), (256, 256), (257, 512),
                                          (1536, 1536), (1537, 2048)])
def test_a_prefill_call_brings_whole_row_tiles(tiny, tokens, rows):
    """Over a latent cache a prefill call's rows are the whole 256-row tiles
    its chunk kernel computes anyway (a program a tile count, not a program
    a block count), with an indexer or without; a K/V model keeps whole
    blocks."""
    cfg, params = tiny
    srv = _engine(cfg, params)
    assert srv._prefill_rows(tokens) == rows
    srv.cfg = types.SimpleNamespace(kv_lora_rank=cfg.kv_lora_rank,
                                    index_heads=0)
    assert srv._prefill_rows(tokens) == rows
    srv.cfg = types.SimpleNamespace(kv_lora_rank=0, index_heads=2)
    assert srv._prefill_rows(tokens) == -(-tokens // 8) * 8
    srv.close()


def test_both_leaves_travel_with_the_blocks(tiny):
    """A prefix-cache hit (the hit FORKS the cached blocks: the latent rows
    AND the indexer keys) gives a cold run's tokens, and its hand-out reads
    -1 where nothing was computed; with the indexer keys of the hit's blocks
    zeroed underneath, it does not."""
    cfg, params = tiny
    prompt = np.random.default_rng(5).integers(1, 64, size=72).tolist()
    want = cold_tokens(cfg, params, prompt, 10)
    srv = _engine(cfg, params)
    first = srv.submit(prompt, max_new_tokens=10)
    srv.run_until_idle()
    hit = srv.submit(prompt, max_new_tokens=10, keep_routing=True)
    srv.run_until_idle()
    assert hit.prefix_hit_tokens == 64
    assert first.output_tokens == hit.output_tokens == want
    assert (hit.routed_experts[:64] == -1).all()
    assert (hit.routed_experts[64:, :, 8] >= 0).all()
    # the hit reads the cached indexer keys: without them it goes elsewhere
    srv._shared.pools = {**srv.pools, "ki": jnp.zeros_like(srv.pools["ki"])}
    blind = srv.submit(prompt, max_new_tokens=10)
    srv.run_until_idle()
    assert blind.prefix_hit_tokens == 64 and blind.output_tokens != want
    srv.close()


def test_what_stays_refused_is_refused_in_words(tiny):
    cfg, params = tiny
    model, _ = build_model(cfg)
    with pytest.raises(NotImplementedError, match="ROADMAP M4"):
        model.apply({"params": params},
                    {"input_ids": np.ones((1, 8), np.int32)})
    kw = FAM.model_kwargs(TINY)
    for knobs, words in (
            (dict(layer_windows=(4, 4, 4)), "no sliding window"),
            (dict(index_head_dim=2), "at least as wide as the rotated"),
            (dict(index_topk=0), "an indexer has all three"),
            (dict(moe_held=(1, 2)), "an equal part of one group"),
            (dict(moe_held=(0, 3)), "an equal part of one group"),
            (dict(moe_scores="softmax"), "group_limited_greedy"),
            (dict(moe_groups=32, moe_topk_groups=16), "TWO best scores"),
            (dict(rope_mscale=0.5), "cos and sin")):
        with pytest.raises(ValueError, match=words):
            TransformerConfig(**{**kw, **knobs})
    # an indexer under a scaled table without a latent beside it
    with pytest.raises(ValueError, match="unless it stands beside latent"):
        TransformerConfig(num_layers=2, pos_embed="rotary", index_heads=2,
                          index_head_dim=4, index_topk=4,
                          rope_scaling_type="yarn", rope_scaling_factor=4.0)
    with pytest.raises(ValueError, match="no quantized format"):
        init_pool(cfg, 8, 8, jnp.int8)
    with pytest.raises(ValueError, match="no quantized format"):
        init_cache(cfg, 1, 16, jnp.int8)
    # half a group is a share, and so are whole groups
    for held in ((2, 2), (0, 4), (8, 8)):
        TransformerConfig(**{**kw, "moe_held": held})
    # the leaves are counted: seven of the latent, four of the indexer
    plain = sum(a.size for name, leaf in params["blocks"].items()
                if name.startswith(("attn_", "q_a_norm", "kv_a_norm",
                                    "index_"))
                for a in jax.tree_util.tree_leaves(leaf)) // 2
    assert cfg._attn_params() == plain
    assert (cfg.index_rope_dim, cfg.routed_layers, cfg.sparse_layers) == (
        4, 3, 2)
