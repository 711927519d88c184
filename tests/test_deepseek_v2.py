"""DeepSeek-V2's layer at a tiny size on the CPU (float32, seeded weights)
against its plain reference (``benchmark/families/deepseek_v2.py``, the
EXPANDED form): latent attention over ONE cached row a token (latent 16 +
a rotated key of 4, narrower than the 4 heads x 12 it stands for; rope 4 of
a 12-wide head as 64 of 192), YaRN rotary, and behind a leading dense layer
a chip's share of a group-limited softmax mixture (32 outputs in 8 groups of
which a token keeps 3, top-6, scaled 16, not renormalised, two shared
experts; this chip holds group 0). The program's decode calls and its jnp
twin attend ABSORBED, the kernel's chunk calls EXPANDED."""
import dataclasses
import functools

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
from deepspeed_tpu.moe.dropless import kept_groups, route_topk
from deepspeed_tpu.ops.pallas import latent_attention as la
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import paged_forward
from deepspeed_tpu.serving.scheduler import RUNNING

FAM = harness.load_family("deepseek_v2")
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
        "type": "yarn"}
TINY = dict(
    family="deepseek_v2", attention_bias=False, first_k_dense_replace=1,
    hidden_act="silu", hidden_size=32, intermediate_size=80, kv_lora_rank=16,
    max_position_embeddings=256, model_type="deepseek_v2",
    moe_intermediate_size=24, moe_layer_freq=1, n_group=8,
    n_routed_experts=4, n_shared_experts=2, norm_topk_prob=False,
    num_attention_heads=4, num_experts_per_tok=6, num_hidden_layers=3,
    num_key_value_heads=4, q_lora_rank=24, qk_nope_head_dim=8,
    qk_rope_head_dim=4, rms_norm_eps=1e-6, rope_scaling=YARN,
    rope_theta=10000, routed_scaling_factor=16, scoring_func="softmax",
    seq_aux=True, tie_word_embeddings=False, topk_group=3,
    topk_method="group_limited_greedy", v_head_dim=8, vocab_size=64,
    deployment={"router_outputs": 32, "experts_held": [0, 4]})
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
    """``(model config, parameters with drawn scales on the two latent
    norms)``."""
    model, cfg = build_model(TransformerConfig(**{
        **FAM.model_kwargs(config), "dtype": jnp.float32,
        "attention_impl": "reference", **knobs}))
    params = make_params(model, cfg, seed, jnp.float32)
    key = jax.random.PRNGKey(seed)
    for i, stack in enumerate(("dense_blocks", "blocks")):
        for j, name in enumerate(("q_a_norm", "kv_a_norm")):
            norm = params[stack][name]
            norm["scale"] = 1.0 + 0.2 * jax.random.normal(
                jax.random.fold_in(key, 2 * i + j), norm["scale"].shape)
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
    assert set(cache) == {"ckv", "pos"} and \
        cache["ckv"].shape == (3, 1, 1, 80, 20)
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
    # ONE leaf, no k / v: the 20-wide row on a whole 128-lane tile
    assert set(pools) == {"ckv"} and \
        pools["ckv"].shape == (3, 1, BLOCKS * BS, 128)
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
    """The same chunked calls on the latent kernel, interpreted."""
    return _paged(dataclasses.replace(cfg, attention_impl="auto"), params,
                  ids, interpret=True)


@pytest.mark.parametrize("path", [_dense_cache, _paged_whole, _paged,
                                  _paged_kernel],
                         ids=["dense_cache", "paged_whole", "paged_chunks",
                              "paged_kernel"])
def test_the_program_matches_the_plain_reference(tiny, sequence, path):
    """The absorbed program against the expanded float32 reference, at every
    position: the prompt's (whole or in chunks) and the decode steps'."""
    cfg, params = tiny
    ids, want = sequence
    got = path(cfg, params, ids)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_generate_follows_the_reference(tiny):
    """``generate()`` (prefill and its decode scan in one program) emits the
    reference's greedy tokens."""
    cfg, params = tiny
    prompt = np.random.default_rng(3).integers(1, 64, size=(1, 21))
    out = np.asarray(generate(cfg, params, jnp.asarray(prompt, jnp.int32), 5))
    for t in range(21, 26):
        logits = FAM.reference_logits(TINY, params, jnp.asarray(out[0, :t]))
        assert int(np.asarray(logits)[-1].argmax()) == out[0, t]


def _expanded(pool, bt, ctx, q0, qn, qp, wk, wv, layer, rank, scale):
    """Latent attention in the EXPANDED form, plainly (numpy, float64):
    every cached latent of a lane through ``wk`` / ``wv`` to a head's own
    key and value, attention at the model's head widths. ``[B, heads, T,
    v]``; an idle lane reads zeros."""
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
        at = q0[b] + np.arange(T)                       # a row's position
        s = np.where(np.arange(len(rows))[None, None] <= at[None, :, None],
                     s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("htk,hkd->htd", a / a.sum(-1, keepdims=True), v)
    return out


#: a call's rows, then lane 0's and lane 1's (first position, context);
#: lane 2 is idle. The kernel's chunk tile is 256 rows, its key group the
#: table's first 512 tokens, then the rest
CALLS = {
    "decode": (1, (76, 77), (8, 9)),
    "chunk": (20, (50, 70), (3, 21)),
    "two_row_tiles": (300, (50, 350), (3, 301)),
    "first_chunk": (40, (0, 40), (0, 38)),
    # behind a cached context that ends inside the second key group, the
    # first row tile's diagonal in one group and the second's in the next
    "behind_a_context": (300, (440, 740), (301, 601)),
    # a prompt's last chunk: 37 and 5 real rows of a 512-row bucket
    "padded_last_chunk": (512, (600, 637), (40, 45)),
    # a prefix hit's: first positions that are whole blocks and no row tiles
    "prefix_hit": (264, (520, 784), (8, 272)),
    # a whole prompt in one call (``prefill_chunk_tokens`` 0): more rows
    # than a program holds, so two row tiles of 1 024 on the grid, each
    # expanding what it sees; lane 1's second tile is padding rows only
    "whole_prompt": (1600, (0, 1600), (200, 1100)),
}


@pytest.mark.parametrize("shape", list(CALLS))
def test_absorbed_equals_expanded_on_one_cache(shape):
    """One pool of latent rows, one block table: the kernel and the absorbed
    jnp twin give what the EXPANDED form gives on the same rows (every
    latent through ``attn_kv_b`` to a head's key and value, worked plainly
    here). A decode call's heads are rows of one absorbed tile; a chunk's
    call is expanded inside the kernel, a key group once for all of its row
    tiles, of which an earlier one meets fewer groups (:data:`CALLS`);
    padding rows are finite and an idle lane reads zeros."""
    rng = np.random.default_rng(0)
    L, bs, W, R, ROPE, NH, NOPE, V = 2, 8, 256, 128, 16, 8, 32, 32
    T, *lanes = CALLS[shape]
    NB, nbk = (128, 100) if max(ctx for _, ctx in lanes) <= 800 else (256, 200)
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
    kw = dict(sm_scale=0.11, layer_idx=jnp.int32(1))
    want = _expanded(pool, bt, ctx, q0, qn, qp, wk, wv, 1, R, 0.11)
    real = ctx[:2, None] - q0[:2, None] > np.arange(T)[None]
    qa = absorb_query(qn, qp, wk, W)
    twin = absorb_output(la.latent_attention_reference(
        qa, pool, bt, lens, value=R, q_start=first, **kw), wv)
    assert la.form(T) == ("absorbed" if T == 1 else "expanded")
    if T == 1:
        kernel = absorb_output(la.latent_attention(
            qa, pool, bt, lens, value=R, interpret=True, **kw), wv)
    else:
        kernel = la.latent_chunk_attention(
            qn, qp, wk, wv, pool, bt, lens, q_start=first, interpret=True,
            **kw)
    for got in (twin, kernel):
        assert got.shape == (3, NH, T, V)
        for lane in range(2):
            np.testing.assert_allclose(
                np.asarray(got)[lane][:, real[lane]],
                want[lane][:, real[lane]], atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(kernel)).all()    # the padding rows
    assert not np.asarray(kernel)[2].any()          # the idle lane


@pytest.mark.parametrize("rows, tiles", [
    (1, (1, 256)), (256, (1, 256)), (300, (1, 512)), (1536, (1, 1536)),
    (1537, (2, 1024)), (3072, (2, 1536)), (4000, (3, 1536)),
    (24576, (16, 1536)), (24577, (17, 1536))])
def test_a_chunk_program_holds_a_bounded_number_of_rows(rows, tiles):
    """A call's rows go to the fewest even row tiles of at most 1 536 rows
    (what a program's VMEM is sized for), whole 256-row tiles each."""
    assert la.chunk_tiles(rows) == tiles
    n, per = tiles
    assert per <= la._CHUNK_ROWS and per % 256 == 0 and n * per >= rows


def test_the_host_counts_what_a_call_expands_by_the_kernels_own_grid():
    """``chunk_expanded_keys``: a head of a one-tile call expands the
    lane's live tokens once; of a longer call every row tile the keys up to
    its own last row, none where a tile is padding only."""
    assert la.chunk_expanded_keys(1536, 12288, 13824) == 13824
    assert la.chunk_expanded_keys(512, 600, 637) == 637
    # a whole prompt of 24 576 rows: sixteen tiles, 1 536 x (1 + ... + 16)
    assert la.chunk_expanded_keys(24576, 0, 24576) == 1536 * 136
    # two tiles of 1 024; 1 100 live tokens from 200: the second tile
    # (from 1 224) is padding only
    assert la.chunk_expanded_keys(1600, 200, 1100) == 1100
    assert la.chunk_expanded_keys(1600, 0, 1600) == 1024 + 1600


def test_a_chunk_is_no_call_of_the_absorbed_kernel():
    """ONE chunk form: the absorbed kernel takes a decode call's one row a
    lane and says whose a chunk is."""
    pool = jnp.zeros((1, 1, 4, 8, 128))
    with pytest.raises(ValueError, match="latent_chunk_attention"):
        la.latent_attention(jnp.zeros((1, 8, 2, 128)), pool,
                            jnp.zeros((1, 2), jnp.int32), jnp.asarray([9]),
                            value=64, sm_scale=1.0, layer_idx=0,
                            interpret=True)


def test_yarn_frequencies_and_scale_against_numbers_worked_by_hand():
    """At the published numbers (dim 64, base 10000, factor 40 over 4096,
    beta 32 / 1): the correction range is dimensions 10 to 23; below it a
    frequency is the model's own, above it a fortieth, between a linear
    ramp; mscale = 0.1 x 0.707 x ln 40 + 1 = 1.26080, and the softmax scale
    192^-0.5 x mscale^2 = 0.114721."""
    cfg = TransformerConfig(**FAM.model_kwargs(harness.load_json(
        harness.HERE + "/configs/deepseek-v2-ep8-l5.json")))
    inv = cfg.rope_inv_freq(4096)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,)
    # 64 ln(4096 / (32 x 2 pi)) / (2 ln 10000) = 10.47 -> 10; at beta 1: 22.5
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    # dimension 16: ramp (16 - 10) / 13 = 0.461538
    np.testing.assert_allclose(
        inv[16], plain[16] * (0.461538 / 40 + 1 - 0.461538), rtol=1e-5)
    np.testing.assert_allclose(plain[16], 1e-2, rtol=1e-6)  # 10000^(-32/64)
    np.testing.assert_allclose(inv[16], 5.50000e-3, rtol=1e-4)
    np.testing.assert_allclose(cfg.softmax_scale, 0.114721, rtol=1e-5)
    np.testing.assert_allclose(FAM.softmax_scale(TINY) * 12 ** .5, 1.26080 ** 2,
                               rtol=1e-5)
    # the reference's own table is the same one
    np.testing.assert_allclose(inv, FAM.yarn_inv_freq(harness.load_json(
        harness.HERE + "/configs/deepseek-v2-ep8-l5.json")), rtol=1e-6)
    assert (cfg.head_dim, cfg.latent_width, cfg.latent_lanes) == (192, 576,
                                                                  640)


def test_group_limited_picks_keep_three_groups_of_eight():
    """The six picks lie in at most 3 groups, which are the 3 of largest
    group maximum; a better expert of a fourth group is passed over; the
    weights are the raw softmax scores times 16."""
    z = jax.random.normal(jax.random.PRNGKey(1), (200, 32))
    r = route_topk(z, 6, False, (8, 3), 16.0)
    probs = np.asarray(jax.nn.softmax(z, axis=-1))
    gmax = probs.reshape(200, 8, 4).max(-1)
    top3 = np.argsort(-gmax, axis=1)[:, :3]
    kept = np.asarray(kept_groups(jnp.asarray(probs), (8, 3)))
    assert (kept.sum(1) == 3).all() and np.array_equal(kept, np.asarray(
        r.groups))
    picks = np.asarray(r.experts)
    for t in range(200):
        assert set(picks[t] // 4) <= set(top3[t])
        allowed = np.where(np.isin(np.arange(32) // 4, top3[t]), probs[t], 0)
        assert set(picks[t]) == set(np.argsort(-allowed)[:6])
    np.testing.assert_allclose(
        np.asarray(r.weights), 16 * np.take_along_axis(probs, picks, 1),
        rtol=1e-6)
    # the limit binds: an ungrouped top-6 picks elsewhere in some rows
    assert (np.sort(np.asarray(route_topk(z, 6, False).experts), 1)
            != np.sort(picks, 1)).any()


def _engine(cfg, params, **serving):
    return ServingEngine(cfg, params, interpret=True, serving={**dict(
        block_size=8, pool_blocks=60, max_batch=4, max_blocks_per_seq=12,
        prefill_chunk_tokens=16, prefix_cache=True), **serving})


def test_the_hand_out_follows_the_reference_and_a_wrong_router_reads_a_deficit(
        tiny):
    """``keep_routing``: ``[fed tokens, 2 sparse layers, 6]`` ids over the
    router's 32, in at most 3 groups a row; the reference routed by them
    reads no deficit and puts the served tokens first; the picks of the token
    before (another router's, as far as this token goes) read deficits over
    the tolerance (a quarter of a logit) at the median token-layer. The
    counters follow the rows."""
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
        assert got.shape == (len(fed), 2, 6) and got.dtype == np.int32
        assert got.max() > 3                    # ids beyond the held 0-3
        assert all(len(set(row // 4)) <= 3 for row in got.reshape(-1, 6))
        logits, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(got))
        assert deficits.shape == got.shape and float(deficits.max()) < 1e-3
        served = np.asarray(logits)[len(r.prompt) - 1:]
        assert (served.argmax(-1) == np.asarray(r.output_tokens)).all()
        wrong = np.concatenate([got[:1], got[:-1]])
        _, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(wrong))
        assert float(np.median(np.asarray(deficits).max(-1))) > 0.1
    t = srv.telemetry()
    c, g = t["counters"], t["gauges"]
    fed = c["prefill_tokens"] + c["tokens_generated"] - 2
    assert c["mla.rows_sum"] == 3 * fed
    # a row attends every cached token up to itself, in each of 3 layers
    assert c["mla.ctx_tokens_sum"] == 3 * sum(
        n * (n + 1) // 2 for n in (70 + 5, 19 + 5))
    assert c["mla.pages_walked_sum"] > 0
    # under even routing 3 rows in 8 keep the held group; every held
    # assignment is of such a row
    assert 0 < c["moe.group_rows_sum"] < 2 * fed
    assert c["moe.held_assignments"] <= 6 * c["moe.group_rows_sum"]
    assert 0 < c["moe.held_assignments"] < c["moe.assignments"] == 2 * 6 * fed
    assert g["kv.bytes_per_token"] == 3 * 128 * 4 and \
        g["kv.latent_lanes"] == 128 and g["kv.stored_heads"] == 1
    # (this engine was built on the jnp twins; chunks of 16 and a tail of 6
    # or 3 tokens all come in one whole row tile)
    assert g["paged.prefill_path"] == {"reference": [256]}
    # the absorbed twin expands nothing
    assert c["mla.chunk_keys_sum"] > 0 == c["mla.chunk_expanded_keys_sum"]
    srv.close()


def test_a_served_chunk_expands_each_cached_token_once(tiny):
    """On the kernel (interpreted) a prompt's chunks attend EXPANDED: the
    cached tokens a head puts through ``attn_kv_b`` are the cached tokens
    the calls see, once a call, and the gauge names the form; the tokens are
    the absorbed twins' engine's."""
    cfg, params = tiny
    prompt = np.random.default_rng(5).integers(1, 64, size=45).tolist()
    want = cold_tokens(cfg, params, prompt, 4)
    srv = _engine(dataclasses.replace(cfg, attention_impl="auto"), params)
    assert srv._latent_prefill_path(16) == ("kernel", None)
    r = srv.submit(prompt, max_new_tokens=4)
    srv.run_until_idle()
    assert r.output_tokens == want
    t = srv.telemetry()
    c = t["counters"]
    # chunks of 16, 16 and 13 rows see 16, 32 and 45 cached tokens, 3 layers
    assert c["mla.chunk_expanded_keys_sum"] == c["mla.chunk_keys_sum"] == \
        3 * (16 + 32 + 45)
    assert t["gauges"]["paged.prefill_path"] == {"kernel (expanded)": [256]}
    srv.close()


def test_three_chunks_ride_one_program_with_the_unpadded_tokens(tiny):
    """Chunks of 16, 16 and 13 tokens go through ONE prefill program of a
    whole 256-row tile (on the kernel, interpreted); the padding rows write
    to the null block and are counted nowhere but in ``prefill_rows``: the
    tokens are ``generate()``'s, which pads nothing."""
    cfg, params = tiny
    prompt = np.random.default_rng(5).integers(1, 64, size=45).tolist()
    want = np.asarray(generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                               4))[0, len(prompt):].tolist()
    srv = _engine(dataclasses.replace(cfg, attention_impl="auto"), params)
    r = srv.submit(prompt, max_new_tokens=4)
    srv.run_until_idle()
    assert r.output_tokens == want
    assert srv._prefill_fn._cache_size() == 1
    assert srv.telemetry()["gauges"]["paged.prefill_path"] == \
        {"kernel (expanded)": [256]}
    c = srv.stats
    assert (c["prefill_tokens"], c["prefill_rows"]) == (45, 3 * 256)
    srv.close()


@pytest.mark.parametrize("tokens, rows", [(1, 256), (256, 256), (257, 512),
                                          (1536, 1536), (1537, 2048)])
def test_a_prefill_call_brings_whole_row_tiles(tiny, tokens, rows):
    """A latent cache needs no indexer for it: a prefill call's rows are the
    whole 256-row tiles its chunk kernel computes anyway (a program a tile
    count, not a program a block count)."""
    cfg, params = tiny
    assert cfg.kv_lora_rank and not cfg.index_heads
    srv = _engine(cfg, params)
    assert srv._prefill_rows(tokens) == rows
    srv.close()


def cold_tokens(cfg, params, prompt, n):
    srv = _engine(cfg, params, prefix_cache=False)
    r = srv.submit(prompt, max_new_tokens=n)
    srv.run_until_idle()
    srv.close()
    return r.output_tokens


def test_the_latent_rows_travel_with_the_blocks(tiny):
    """A prefix-cache hit (the hit FORKS the cached blocks: two holders of
    the same latent rows), a second request forked off the same prefix while
    the first still decodes, and a lane preempted and resumed all give a
    cold run's tokens: the one leaf is in the blocks the prefix cache and
    the allocator hand on."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 64, size=72).tolist()
    want = cold_tokens(cfg, params, prompt, 10)

    srv = _engine(cfg, params)
    first = srv.submit(prompt, max_new_tokens=10)
    srv.run_until_idle()
    hit = srv.submit(prompt, max_new_tokens=10, keep_routing=True)
    srv.run_until_idle()
    assert hit.prefix_hit_tokens == 64
    assert first.output_tokens == hit.output_tokens == want
    assert (hit.routed_experts[:64] == -1).all()

    # two live holders of the prefix's blocks at once, different tails
    tails = [prompt[:64] + [5, 6, 7], prompt[:64] + [9]]
    pair = [srv.submit(p, max_new_tokens=8) for p in tails]
    srv.run_until_idle()
    assert [r.prefix_hit_tokens for r in pair] == [64, 64]
    for r, p in zip(pair, tails):
        assert r.output_tokens == cold_tokens(cfg, params, p, 8)

    victim = srv.submit(prompt[:50] + [1, 2, 3], max_new_tokens=10)
    while victim.state != RUNNING or len(victim.output_tokens) < 4:
        srv.step()
    assert srv.preempt_request(victim)
    emitted = list(victim.output_tokens)
    resumed = srv.submit(victim.prompt + emitted,
                         max_new_tokens=10 - len(emitted))
    srv.run_until_idle()
    assert emitted + resumed.output_tokens == cold_tokens(
        cfg, params, victim.prompt, 10)
    srv.close()


def test_what_no_path_carries_is_refused_in_words(tiny):
    cfg, params = tiny
    model, _ = build_model(cfg)
    with pytest.raises(NotImplementedError, match="ROADMAP M4"):
        model.apply({"params": params},
                    {"input_ids": np.ones((1, 8), np.int32)})
    kw = FAM.model_kwargs(TINY)
    with pytest.raises(ValueError, match="all five sizes"):
        TransformerConfig(num_layers=2, kv_lora_rank=16)
    for knobs, words in (
            (dict(layer_windows=(4, 4, 4)), "no sliding window"),
            (dict(pos_embed="alibi"), "rotary positions"),
            # (an indexer beside a latent is DeepSeek-V3.2's, tests/
            # test_deepseek_v32.py; its head is as wide as the rotated key)
            (dict(index_heads=2, index_head_dim=2, index_topk=4),
             "at least as wide as the rotated shared key"),
            (dict(num_kv_heads=2), "no KV heads"),
            (dict(moe_held=(2, 4)), "whole groups of 4"),
            (dict(moe_held=(0, 6)), "whole groups of 4"),
            (dict(moe_topk_groups=1), "enough to hold its 6 picks"),
            (dict(moe_select_bias=True), "group_limited_greedy"),
            (dict(rope_mscale=1.0), "cos and sin")):
        with pytest.raises(ValueError, match=words):
            TransformerConfig(**{**kw, **knobs})
    with pytest.raises(ValueError, match="only the dropless mixture"):
        TransformerConfig(num_layers=2, moe_experts=8, moe_k=2, moe_groups=4,
                          moe_topk_groups=2)
    with pytest.raises(ValueError, match="no quantized format"):
        init_pool(cfg, 8, 8, jnp.int8)
    with pytest.raises(ValueError, match="no quantized format"):
        init_cache(cfg, 1, 16, jnp.int8)
    with pytest.raises(NotImplementedError, match="longrope"):
        TransformerConfig(num_layers=2, pos_embed="rotary",
                          rope_scaling_type="longrope").rope_inv_freq(64)
    # the seven leaves are counted
    plain = sum(a.size for name, leaf in params["blocks"].items()
                if name.startswith(("attn_", "q_a_norm", "kv_a_norm"))
                for a in jax.tree_util.tree_leaves(leaf)) // 2
    assert cfg._attn_params() == plain
