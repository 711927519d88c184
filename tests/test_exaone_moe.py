"""K-EXAONE's layer at a tiny size on the CPU (float32, seeded weights, a
NON-ZERO selection bias) against its plain reference
(``benchmark/families/exaone_moe.py``): a chip's share of a sigmoid mixture
behind a leading dense layer, a shared expert, window layers three to one
full, rotary on the window layers only, no input norms. The reference is
given the same share; one test ties the shares to the uncut layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers.serve import make_params
from deepspeed_tpu.models import TransformerConfig, build_model
from deepspeed_tpu.models.generation import (DenseCache, _moe_mlp,
                                             decoder_forward,
                                             forward_with_cache, init_cache)
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
from deepspeed_tpu.serving.model_runner import paged_forward

FAM = harness.load_family("exaone_moe")
_S, _F = "sliding_attention", "full_attention"
#: 16 experts ranked, 2 held (a chip of eight), top-4; window 6 on layers
#: 0-2 and 4, layer 3 full; layer 0 dense
TINY = dict(
    family="exaone_moe", first_k_dense_replace=1, head_dim=8,
    hidden_act="silu", hidden_size=32, intermediate_size=80,
    layer_types=[_S, _S, _S, _F, _S], max_position_embeddings=256,
    mlp_layer_types=["dense"] + ["sparse"] * 4, moe_intermediate_size=24,
    n_group=1, norm_topk_prob=True, num_attention_heads=4, num_experts=2,
    num_experts_per_tok=4, num_hidden_layers=5, num_key_value_heads=2,
    num_nextn_predict_layers=0, num_shared_experts=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=6,
    sliding_windows=[6, 6, 6, 0, 6], tie_word_embeddings=False, topk_group=1,
    vocab_size=64,
    deployment={"router_outputs": 16, "experts_held": [4, 2]})
UNCUT = dict({k: v for k, v in TINY.items() if k != "deployment"},
             num_experts=16)
BS, NBK, BLOCKS = 8, 5, 12
PROMPT, CHUNK, STEPS = 21, 13, 3


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


def built(config, seed=11):
    """``(model, its config, parameters with a drawn selection bias)``."""
    model, cfg = build_model(TransformerConfig(
        **FAM.model_kwargs(config), dtype=jnp.float32,
        attention_impl="reference"))
    params = make_params(model, cfg, seed, jnp.float32)
    gate = params["blocks"]["moe"]["gate"]
    gate["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed),
                                           gate["bias"].shape)
    return model, cfg, params


@pytest.fixture(scope="module")
def tiny():
    return built(TINY)


@pytest.fixture(scope="module")
def sequence(tiny):
    ids = np.random.default_rng(7).integers(
        1, 64, size=(1, PROMPT + STEPS)).astype(np.int32)
    return ids, np.asarray(FAM.reference_logits(TINY, tiny[2],
                                                jnp.asarray(ids[0])))


def _block_forward(model, cfg, params, ids):
    return np.asarray(model.apply({"params": params},
                                  {"input_ids": ids})[0])[0]


def _dense_cache(model, cfg, params, ids):
    """``generate()``'s cache: the prompt at once, then a token a call."""
    cache = init_cache(cfg, 1, 32, jnp.float32)
    logits, cache = forward_with_cache(cfg, params, ids[:, :PROMPT], cache)
    out = [np.asarray(logits)]
    for t in range(PROMPT, PROMPT + STEPS):
        logits, cache = forward_with_cache(cfg, params, ids[:, t:t + 1],
                                           cache)
        out.append(np.asarray(logits))
    return np.concatenate(out, axis=1)[0]


def _paged(model, cfg, params, ids):
    """The serving loop's calls: two chunks that end in mid-block, then
    decode steps beside an idle lane."""
    table = np.full((1, NBK), NULL_BLOCK, np.int32)
    table[0, :4] = (7, 2, 9, 4)
    pools, got = init_pool(cfg, BLOCKS, BS, jnp.float32), []

    def call(tokens, bt, q0, ctx, real):
        nonlocal pools
        logits, pools = paged_forward(
            cfg, params, jnp.asarray(tokens), pools, jnp.asarray(bt),
            jnp.asarray(q0, jnp.int32), jnp.asarray(ctx, jnp.int32), BS)
        got.append(np.asarray(logits)[0, :real])

    call(ids[:, :CHUNK], table, [0], [CHUNK], CHUNK)
    second = np.zeros((1, CHUNK), np.int32)
    second[0, :PROMPT - CHUNK] = ids[0, CHUNK:PROMPT]
    call(second, table, [CHUNK], [PROMPT], PROMPT - CHUNK)
    lanes = np.concatenate([table, np.full((1, NBK), NULL_BLOCK, np.int32)])
    for t in range(PROMPT, PROMPT + STEPS):
        call(np.asarray([[ids[0, t]], [0]], np.int32), lanes, [t, 0],
             [t + 1, 1], 1)
    return np.concatenate(got, axis=0)


@pytest.mark.parametrize("path", [_block_forward, _dense_cache, _paged],
                         ids=["block_forward", "dense_cache", "paged"])
def test_the_program_matches_the_plain_reference(tiny, sequence, path):
    ids, want = sequence
    np.testing.assert_allclose(path(*tiny, ids), want, rtol=1e-5, atol=1e-5)


def test_the_bias_selects_and_never_weighs(tiny):
    """The drawn bias moves picks (else the cases above prove nothing of
    it), and the reference's weights are its scores', not score + bias."""
    _, _, params = tiny
    gate = jax.tree.map(lambda a: a[0], params["blocks"]["moe"]["gate"])
    h = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    s, w, picks, _ = FAM.reference_router(gate, h, 4, True, 2.5)
    plain = jax.lax.top_k(s, 4)[1]
    assert not np.array_equal(np.sort(picks, -1), np.sort(plain, -1))
    at = jnp.take_along_axis(s, picks, axis=-1)
    np.testing.assert_allclose(
        w, 2.5 * at / at.sum(-1, keepdims=True), rtol=1e-6)


def _keye_uncut():
    """Keye-VL-2.0's mixture (softmax top-4 renormalised, no shared expert)
    at the sizes of ``tests/test_keye_vl2.py``, uncut: 16 experts held."""
    from test_keye_vl2 import TINY as cut
    return harness.load_family("keye_vl2"), dict(
        {k: v for k, v in cut.items() if k != "deployment"},
        num_experts=16, num_local_experts=16), cut


def _deepseek_uncut():
    """DeepSeek-V2's mixture (group-limited softmax top-6 of 32 in 8 groups
    keep 3, scaled 16, two shared experts) at the sizes of
    ``tests/test_deepseek_v2.py``, uncut: 32 experts held."""
    from test_deepseek_v2 import TINY as cut, UNCUT as uncut
    return harness.load_family("deepseek_v2"), uncut, cut


@pytest.mark.parametrize("family", ["exaone_moe", "keye_vl2", "deepseek_v2"])
def test_the_shares_add_up_to_the_uncut_layer(family):
    """Eight chips' routed parts, with what every chip computes alike (a
    shared expert, where the family has one) counted once, are the uncut
    reference's mixture. DeepSeek-V2's shares are its routing groups, one a
    chip: a token keeps 3 of the 8, so five chips add nothing for it."""
    each, router = (4, 32) if family == "deepseek_v2" else (2, 16)
    if family == "exaone_moe":
        fam, uncut, cut = FAM, UNCUT, TINY
        mixture = lambda moe, h, **kw: fam.reference_moe(
            moe, h, k=4, renorm=True, scale=2.5, **kw)[0]
        routed_only = dict(shared=False)
    elif family == "deepseek_v2":
        fam, uncut, cut = _deepseek_uncut()
        mixture = lambda moe, h, **kw: fam.reference_moe(
            moe, h, k=6, groups=8, keep=3, renorm=False, scale=16.0, **kw)[0]
        routed_only = dict(shared=False)
    else:
        fam, uncut, cut = _keye_uncut()
        mixture = lambda moe, h, **kw: fam.held_mixture(
            moe, h, k=4, renorm=True, **kw)[0]
        routed_only = {}
    if family == "exaone_moe":
        _, cfg_all, params = built(uncut)       # with its selection bias
    else:
        model, cfg_all = build_model(TransformerConfig(
            **fam.model_kwargs(uncut), dtype=jnp.float32))
        params = make_params(model, cfg_all, 11, jnp.float32)
    moe = jax.tree.map(lambda a: a[1], params["blocks"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 32))
    want = mixture(moe, h[0])
    total = 0.0
    idle = 0
    for chip in range(8):
        config = dict(cut, deployment={"router_outputs": router,
                                       "experts_held": [each * chip, each]})
        cfg = TransformerConfig(**fam.model_kwargs(config),
                                dtype=jnp.float32)
        assert cfg.moe_held == (each * chip, each)
        held = dict(moe, experts=jax.tree.map(
            lambda a: a[each * chip:each * chip + each], moe["experts"]))
        y, routing = _moe_mlp(cfg, held, h)
        shared, _ = _moe_mlp(cfg, dict(held, experts=jax.tree.map(
            jnp.zeros_like, held["experts"])), h)
        # this chip's routed part alone; the reference's share is the same
        mine = mixture(held, h[0], first=each * chip, **routed_only)
        tol = 1e-5 if family != "deepseek_v2" else 5e-5     # weights x 16
        np.testing.assert_allclose((y - shared)[0], mine, atol=tol)
        total = total + (y - shared)[0]
        idle += int((np.abs(np.asarray(y - shared)[0]).max(-1) == 0).sum())
    assert (family != "keye_vl2") == bool(np.abs(shared).max() > 0)
    # a grouped router's token computes on 3 chips of 8 at most
    assert (idle >= 5 * h.shape[1]) == (family == "deepseek_v2")
    np.testing.assert_allclose(total + shared[0], want, atol=tol)
    # and the uncut program is the uncut reference
    y, _ = _moe_mlp(cfg_all, moe, h)
    np.testing.assert_allclose(y[0], want, atol=tol)


def _engine(cfg, params):
    return ServingEngine(cfg, params, interpret=True, serving=dict(
        block_size=8, pool_blocks=40, max_batch=4, max_blocks_per_seq=8,
        prefill_chunk_tokens=16, prefix_cache=True))


def test_routed_experts_cover_the_sparse_layers_and_follow_the_reference(tiny):
    """``keep_routing``: ``[fed tokens, SPARSE layers, k]`` ids over the
    router's 16, equal to ``Routing.experts`` of the same tokens; the
    reference routed by them reads no deficit and puts the served tokens
    first."""
    _, cfg, params = tiny
    srv = _engine(cfg, params)
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(1, 64, size=n).tolist(),
                       max_new_tokens=m, keep_routing=True)
            for n, m in ((37, 6), (11, 9))]
    srv.run_until_idle()
    for r in reqs:
        fed = r.prompt + r.output_tokens[:-1]
        cache = init_cache(cfg, 1, len(fed))
        _, _, _, picks = decoder_forward(
            cfg, params, jnp.asarray(fed, jnp.int32)[None],
            DenseCache(cfg, cache, False), expert_picks=True)
        assert r.routed_experts.shape == (len(fed), 4, 4)
        assert np.array_equal(r.routed_experts,
                              np.asarray(picks).transpose(1, 0, 2))
        assert r.routed_experts.max() > 5       # ids beyond the held 4, 5
        logits, deficits = FAM.reference_logits(
            TINY, params, jnp.asarray(fed), jnp.asarray(r.routed_experts))
        assert float(deficits.max()) < 1e-3
        served = np.asarray(logits)[len(r.prompt) - 1:]
        assert (served.argmax(-1) == np.asarray(r.output_tokens)).all()
    c = srv.telemetry()["counters"]
    # every real token reaches 4 experts in 4 sparse layers; a share of them
    # is held here, and the window layers walk fewer pages than the full one
    assert c["moe.assignments"] == 16 * (c["prefill_tokens"]
                                         + c["tokens_generated"] - 2)
    assert 0 < c["moe.held_assignments"] < c["moe.assignments"] // 2
    assert 0 < c["paged.window_pages_sum"] < 4 * c["paged.live_pages_sum"]
    srv.close()


def test_a_wrong_routers_picks_read_a_deficit(tiny):
    """The check's control: picks taken from the token before lie whole
    logits under the reference's own scores."""
    _, cfg, params = tiny
    ids = jnp.asarray(np.random.default_rng(9).integers(1, 64, size=40))
    _, routing = FAM.reference_logits_and_routing(TINY, params, ids)
    own = jnp.stack([r[1] for r in routing], axis=1)          # [S, 4, 4]
    _, honest = FAM.reference_logits(TINY, params, ids, own)
    _, wrong = FAM.reference_logits(TINY, params, ids,
                                    jnp.roll(own, 1, axis=0))
    assert float(honest.max()) < 1e-3 and float(wrong.max()) > 0.5


@pytest.mark.parametrize("knobs, words", [
    (dict(moe_experts=4, moe_k=2, moe_shared_dim=8), "GShard"),
    (dict(moe_experts=4, moe_k=2, moe_scores="sigmoid"), "GShard"),
    (dict(moe_experts=8, moe_k=4, moe_held=(6, 4)), "inside the router"),
    (dict(dense_layers=1), "leading dense layers of a mixture"),
    (dict(pos_embed="rotary", layer_rope=(True,)), "one flag a layer"),
    (dict(pre_norm=False), "its output must be"),
], ids=["shared_gshard", "sigmoid_gshard", "held_outside", "dense_no_moe",
        "rope_flags_short", "no_norm_at_all"])
def test_what_a_path_cannot_carry_is_refused_in_words(knobs, words):
    with pytest.raises(ValueError, match=words):
        TransformerConfig(num_layers=2, **knobs)


def test_the_pipelined_model_and_an_expert_axis_refuse_it(tiny):
    from deepspeed_tpu.models.pipeline import PipelinedTransformer
    model, cfg, params = tiny
    with pytest.raises(NotImplementedError, match="layer_windows"):
        PipelinedTransformer(cfg, pp=1, n_micro=1)
    with pytest.raises(NotImplementedError, match="layer_rope"):
        PipelinedTransformer(TransformerConfig(
            num_layers=2, pos_embed="rotary", layer_rope=(True, False)),
            pp=1, n_micro=1)
    with pytest.raises(NotImplementedError, match="dense_layers"):
        PipelinedTransformer(TransformerConfig(
            num_layers=2, moe_experts=4, moe_k=2, dense_layers=1,
            dense_mlp_dim=64), pp=1, n_micro=1)
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(ep_size=2,
                                         devices=jax.devices()[:2]))
    try:
        with pytest.raises(NotImplementedError, match="expert axis"):
            model.apply({"params": params},
                        {"input_ids": np.ones((2, 8), np.int32)})
    finally:
        mesh_mod.set_global_mesh(before)
