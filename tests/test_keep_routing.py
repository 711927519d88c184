"""``submit(keep_routing=True)`` -> ``Request.routed_experts``: the picks of
a tiny mixture served through chunked prefill and paged decode, with
decode-ahead, equal ``decoder_forward``'s on the same tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers.serve import make_params
from deepspeed_tpu.models import TransformerConfig, build_model
from deepspeed_tpu.models.generation import (DenseCache, decoder_forward,
                                             init_cache)
from deepspeed_tpu.parallel.mesh import MeshManager
from deepspeed_tpu.serving.engine import ServingEngine, step_programs

TINY = {"family": "olmoe", "attention_bias": False, "clip_qkv": None,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 48,
        "max_position_embeddings": 256, "model_type": "olmoe",
        "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 10000, "router_aux_loss_coef": 0.01,
        "tie_word_embeddings": False, "vocab_size": 97}
FAM = harness.load_family("olmoe")


@pytest.fixture(scope="module", autouse=True)
def one_device_mesh():
    from deepspeed_tpu.parallel import mesh as mesh_mod
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


@pytest.fixture(scope="module")
def tiny():
    model, cfg = build_model(TransformerConfig(
        **FAM.model_kwargs(TINY), dtype=jnp.float32,
        attention_impl="reference"))
    return cfg, make_params(model, cfg, seed=2 ** 31 + 5, dtype=jnp.float32)


def engine(cfg, params, **kw):
    serving = dict(block_size=8, pool_blocks=40, max_batch=4,
                   max_blocks_per_seq=8, prefill_chunk_tokens=16,
                   prefix_cache=True)
    serving.update(kw)
    return ServingEngine(cfg, params, serving=serving, interpret=True)


def dense_picks(cfg, params, tokens):
    """``decoder_forward`` over a dense cache on the whole sequence."""
    ids = jnp.asarray(tokens, jnp.int32)[None]
    cache = init_cache(cfg, 1, len(tokens))
    _, _, _, picks = decoder_forward(cfg, params, ids,
                                     DenseCache(cfg, cache, False),
                                     expert_picks=True)
    return np.asarray(picks).transpose(1, 0, 2)        # [S, L, k]


def toks(n, seed):
    return np.random.default_rng(seed).integers(1, 97, size=(n,)).tolist()


def test_routed_experts_are_the_decoders_picks(tiny):
    cfg, params = tiny
    srv = engine(cfg, params)
    a = srv.submit(toks(37, 1), max_new_tokens=6, keep_routing=True)
    b = srv.submit(toks(11, 2), max_new_tokens=9, keep_routing=True)
    c = srv.submit(toks(20, 3), max_new_tokens=5)
    srv.run_until_idle()
    assert c.routed_experts is None
    for r in (a, b):
        fed = r.prompt + r.output_tokens[:-1]
        assert r.routed_experts.shape == (len(fed), 2, 4)
        assert r.routed_experts.dtype == np.int32
        assert np.array_equal(r.routed_experts, dense_picks(cfg, params, fed))
    assert srv.stats["decode_ahead.launched"] > 0
    srv.close()


def test_a_request_that_did_not_ask_causes_no_fetch(tiny):
    cfg, params = tiny
    srv = engine(cfg, params)
    r = srv.submit(toks(20, 4), max_new_tokens=5)
    srv.run_until_idle()
    assert r.routed_experts is None and "routing.fetches" not in srv.stats
    q = srv.submit(toks(20, 5), max_new_tokens=5, keep_routing=True)
    srv.run_until_idle()
    # two chunks of its prompt and four decode calls
    assert srv.stats["routing.fetches"] == 2 + 4
    assert q.routed_experts.shape == (24, 2, 4)
    srv.close()


def test_a_prefix_cache_hit_reads_minus_one(tiny):
    cfg, params = tiny
    srv = engine(cfg, params)
    shared = toks(24, 6)
    first = srv.submit(shared + toks(5, 7), max_new_tokens=3,
                       keep_routing=True)
    srv.run_until_idle()
    second = srv.submit(shared + toks(6, 8), max_new_tokens=3,
                        keep_routing=True)
    srv.run_until_idle()
    assert (first.routed_experts >= 0).all()
    hit = second.prefix_hit_tokens
    assert hit >= 16
    assert (second.routed_experts[:hit] == -1).all()
    fed = second.prompt + second.output_tokens[:-1]
    assert np.array_equal(second.routed_experts[hit:],
                          dense_picks(cfg, params, fed)[hit:])
    srv.close()


def test_a_dense_models_programs_have_one_output():
    model, cfg = build_model(TransformerConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=128, dtype=jnp.float32, attention_impl="reference"))
    params = make_params(model, cfg, seed=3, dtype=jnp.float32)
    srv = ServingEngine(cfg, params, interpret=True, serving=dict(
        block_size=8, pool_blocks=16, max_batch=2, max_blocks_per_seq=8))
    r = srv.submit(toks(10, 9), max_new_tokens=4, keep_routing=True)
    srv.run_until_idle()
    assert r.routed_experts is None and not r.keep_routing
    assert not hasattr(srv, "_picks_out")
    srv.close()
