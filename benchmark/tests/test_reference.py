"""The plain reference against ``models/transformer.py`` at a tiny size, for
both families, on seeded random weights in float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, reference
from benchmark.drivers.serve import make_params
from deepspeed_tpu.models import TransformerConfig, build_model

TINY = {
    "gpt2": {"family": "gpt2", "num_layers": 3, "hidden_size": 64,
             "num_attention_heads": 4, "ffn_hidden_size": 256,
             "max_position_embeddings": 64, "vocab_size": 97,
             "layernorm_epsilon": 1e-5},
    "mistral": {"family": "mistral", "hidden_act": "silu", "hidden_size": 64,
                "intermediate_size": 160, "max_position_embeddings": 256,
                "num_attention_heads": 4, "num_hidden_layers": 3,
                "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
                "rope_theta": 10000.0, "sliding_window": 8,
                "tie_word_embeddings": False, "vocab_size": 97},
}


def build(name, **kw):
    fam = harness.load_family(name)
    model, cfg = build_model(TransformerConfig(
        **fam.model_kwargs(TINY[name]), dtype=jnp.float32,
        attention_impl="reference", **kw))
    params = make_params(model, cfg, seed=2 ** 31 + 3, dtype=jnp.float32)
    # biases are drawn too: a reference that dropped one must not pass
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(str(p))), a.shape)
        if getattr(p[-1], "key", "") == "bias" else a, params)
    return fam, model, params


@pytest.mark.parametrize("name", ["gpt2", "mistral"])
def test_reference_logits_equal_the_models(name):
    fam, model, params = build(name)
    ids = np.random.default_rng(0).integers(0, 97, size=(24,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, {"input_ids": ids[None]})[0]
    got = fam.reference_logits(TINY[name], params, jnp.asarray(ids))
    assert got.shape == (24, 97) and got.dtype == jnp.float32
    # both in float32: they differ by the order of summation only (the
    # window of 8 is shorter than the sequence, so a reference that ignored
    # Mistral's sliding window would be off by tenths)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4


def test_reference_loss_equals_the_fused_loss():
    fam, model, params = build("gpt2", fused_loss=True)
    batch = np.random.default_rng(1).integers(0, 97, size=(3, 32),
                                              dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = float(model.apply({"params": params}, {"input_ids": batch}))
    got = reference.batch_loss(
        lambda p, ids: fam.reference_logits(TINY["gpt2"], p, ids), params,
        batch)
    assert abs(got - want) < 1e-4


def test_served_token_gaps_find_a_wrong_token():
    fam, model, params = build("mistral")
    logits_fn = lambda p, ids: fam.reference_logits(TINY["mistral"], p, ids)
    prompt = list(range(1, 11))
    served = []
    for _ in range(5):                      # the reference's own greedy path
        ids = jnp.asarray(prompt + served, jnp.int32)
        served.append(int(jnp.argmax(logits_fn(params, ids)[-1])))
    gaps = reference.served_token_gaps(logits_fn, params, prompt, served, 32)
    assert gaps.shape == (5,) and float(gaps.max()) == 0.0
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % 97
    gaps = reference.served_token_gaps(logits_fn, params, prompt, wrong, 32)
    assert gaps[2] > 0 and gaps[0] == gaps[1] == 0


def test_served_token_gaps_judge_the_emitted_tokens_of_a_control():
    """``emitted``: another model's first choices are judged at the served
    positions, while the reference is still fed the served tokens."""
    fam, model, params = build("mistral")
    logits_fn = lambda p, ids: fam.reference_logits(TINY["mistral"], p, ids)
    prompt, served = list(range(1, 11)), []
    for _ in range(5):
        ids = jnp.asarray(prompt + served, jnp.int32)
        served.append(int(jnp.argmax(logits_fn(params, ids)[-1])))
    at, deficits = reference.served_logits(logits_fn, params, prompt, served,
                                           32)
    assert at.shape == (5, 97) and deficits is None
    assert [int(t) for t in jnp.argmax(at, -1)] == served
    runner_up = [int(t) for t in jnp.argsort(at, axis=-1)[:, -2]]
    gaps = reference.served_token_gaps(logits_fn, params, prompt, served, 32,
                                       emitted=runner_up)
    want = np.sort(np.asarray(at), axis=-1)
    np.testing.assert_allclose(gaps, want[:, -1] - want[:, -2], rtol=1e-6)
    assert gaps.min() > 0
    with pytest.raises(ValueError, match="tokens to judge"):
        reference.served_token_gaps(logits_fn, params, prompt, served, 32,
                                    emitted=runner_up[:-1])


@pytest.mark.parametrize("n, longest, want", [
    (40, 1516, 128), (128, 1516, 128), (129, 1516, 256), (536, 1516, 1024),
    (1516, 1516, 1536), (1024, 1024, 1024), (1048, 1024 + 24, 1152)])
def test_padded_len_is_the_next_doubling_never_over_the_longest(n, longest,
                                                                want):
    assert reference.padded_len(n, longest) == want


# ---- a mixture: the reference under the program's picks -------------------

OLMOE = {"family": "olmoe", "attention_bias": False, "clip_qkv": None,
         "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 48,
         "max_position_embeddings": 256, "norm_topk_prob": True,
         "num_attention_heads": 4, "num_experts": 8,
         "num_experts_per_tok": 2, "num_hidden_layers": 2,
         "num_key_value_heads": 4, "rms_norm_eps": 1e-5,
         "rope_scaling": None, "rope_theta": 10000,
         "router_aux_loss_coef": 0.01, "tie_word_embeddings": False,
         "vocab_size": 97}
A, B = 2, 5                     # the two experts of the forced near tie


@pytest.fixture(scope="module")
def mixture():
    """A tiny renormalised mixture in float32, and the same with layer 0's
    router columns A and B made equal but for +-1e-6: ``plus`` ranks B just
    over A for every token, ``minus`` just under."""
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshManager
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    fam = harness.load_family("olmoe")
    model, cfg = build_model(TransformerConfig(
        **fam.model_kwargs(OLMOE), dtype=jnp.float32,
        attention_impl="reference"))
    params = make_params(model, cfg, seed=2 ** 31 + 7, dtype=jnp.float32)
    mesh_mod.set_global_mesh(before)

    def tied(sign):
        g = params["blocks"]["moe"]["gate"]["kernel"]
        # h is positive along no fixed direction, so the offset is a copy of
        # the column itself: logit B = (1 + sign 1e-6) x logit A
        g = g.at[0, :, B].set(g[0, :, A] * (1.0 + sign * 1e-6))
        return jax.tree_util.tree_map_with_path(
            lambda p, a: g if "gate" in str(p) and "experts" not in str(p)
            and a.shape == g.shape else a, params)

    ids = jnp.asarray(np.random.default_rng(3).integers(
        1, 97, size=(48,), dtype=np.int32))
    return fam, params, tied(+1), tied(-1), ids


def routed(fam, params, ids, picks=None):
    logits, routing = fam.reference_logits_and_routing(OLMOE, params, ids,
                                                       picks)
    return (np.asarray(logits), np.stack([np.asarray(r[1]) for r in routing],
                                         axis=1),
            np.stack([np.asarray(r[2]) for r in routing], axis=1))


def test_pinned_by_its_own_picks_the_reference_is_the_drawn_one(mixture):
    fam, params, _, _, ids = mixture
    drawn, picks, none = routed(fam, params, ids)
    assert picks.shape == (48, 2, 2) and not none.any()
    pinned, deficits = fam.reference_logits(OLMOE, params, ids,
                                            jnp.asarray(picks))
    assert np.array_equal(np.asarray(pinned), drawn)
    assert np.array_equal(np.asarray(pinned), np.asarray(
        fam.reference_logits(OLMOE, params, ids)))
    assert deficits.shape == (48, 2, 2) and not np.asarray(deficits).any()
    # rows marked -1 (never computed by the program) route by themselves
    some = picks.copy()
    some[5:9] = -1
    again, d = fam.reference_logits(OLMOE, params, ids, jnp.asarray(some))
    assert np.array_equal(np.asarray(again), drawn) and not np.asarray(d).any()


def test_pinned_reference_follows_a_pick_that_flipped_on_a_near_tie(mixture):
    fam, _, plus, minus, ids = mixture
    up, picks_up, _ = routed(fam, plus, ids)
    down, picks_down, _ = routed(fam, minus, ids)
    flipped = (picks_up[:, 0] != picks_down[:, 0]).any(axis=-1)
    assert 2 <= flipped.sum() < 24          # the tie straddles k for a few
    # each side routing by its own scores: far apart, from the first flip on
    first = int(np.argmax(flipped))
    assert np.abs(up - down)[first:].max() > reference.SERVE_LOGIT_MARGIN
    assert np.abs(up - down)[:first].max() < 1e-4
    # the reference of ``plus`` given the picks ``minus`` made: it computes
    # what ``minus`` computed, and holds every pick a near tie
    pinned, _, deficits = routed(fam, plus, ids, jnp.asarray(picks_down))
    assert np.abs(pinned - down).max() < 1e-3
    assert 0 < deficits.max() < 1e-4 < reference.ROUTE_TIE_TOL
    # ... and only a flipped one (a float32 tie outright reads 0)
    assert not deficits[~flipped].any() and not deficits[:, 1].any()


def test_a_pick_that_is_no_near_tie_fails_the_tolerance(mixture):
    fam, params, _, _, ids = mixture
    _, picks, _ = routed(fam, params, ids)
    scores = jnp.asarray(np.random.default_rng(0).normal(size=(6, 8)),
                         jnp.float32)
    own = np.asarray(jax.lax.top_k(scores, 3)[1])
    assert not np.asarray(reference.pick_deficit(scores, own)).any()
    worst = np.asarray(jnp.argmin(scores, axis=-1))
    wrong = own.copy()
    wrong[:, 2] = worst
    d = np.asarray(reference.pick_deficit(scores, wrong))
    assert (d[:, 2] > reference.ROUTE_TIE_TOL).all() and not d[:, :2].any()
    s = np.sort(np.asarray(scores), axis=-1)
    assert np.allclose(d[:, 2], s[:, -3] - s[:, 0])
    gone = np.full_like(own, -1)
    assert not np.asarray(reference.pick_deficit(scores, gone)).any()
    # in the model: every token's weakest pick swapped for its last choice
    _, routing = fam.reference_logits_and_routing(OLMOE, params, ids)
    bad = picks.copy()
    bad[:, 1, 1] = np.asarray(jnp.argmin(routing[1][0], axis=-1))
    _, _, deficits = routed(fam, params, ids, jnp.asarray(bad))
    assert deficits[:, 1, 1].min() > reference.ROUTE_TIE_TOL
    assert not deficits[:, 0].any()


def test_served_token_gaps_with_and_without_picks(mixture):
    fam, _, plus, minus, ids = mixture
    fn = lambda p, *a: fam.reference_logits(OLMOE, p, *a)
    prompt = [int(t) for t in ids[:40]]
    served = []
    for _ in range(6):          # ``minus`` is the server: greedy, its picks
        seq = jnp.asarray(prompt + served, jnp.int32)
        logits, picks, _ = routed(fam, minus, seq)
        served.append(int(np.argmax(logits[-1])))
    fed = picks                                   # [45, layers, k]
    assert fed.shape[0] == len(prompt) + len(served) - 1
    plain = reference.served_token_gaps(fn, minus, prompt, served, 64)
    assert plain.shape == (6,) and float(plain.max()) == 0.0
    # the reference ``plus`` ranks the tied pair the other way: by its own
    # scores it computes another model; pinned, the served one
    own = reference.served_token_gaps(fn, plus, prompt, served, 64)
    gaps, deficits = reference.served_token_gaps(fn, plus, prompt, served,
                                                 64, picks=fed)
    assert gaps.shape == (6,) and float(gaps.max()) < 1e-3 < float(own.max())
    assert deficits.shape == fed.shape
    assert 0 < float(deficits.max()) < reference.ROUTE_TIE_TOL
    with pytest.raises(ValueError, match="the model was fed"):
        reference.served_token_gaps(fn, plus, prompt, served, 64,
                                    picks=fed[:-1])
