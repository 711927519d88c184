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
