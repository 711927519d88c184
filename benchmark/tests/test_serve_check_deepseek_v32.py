"""The serving check on sparse latent attention behind a ``noaux_tc`` router
(the ``deepseek_v32`` family at a tiny size, float32, on the CPU; the driver
whole, as ``test_serve_check.py`` drives it, with a drawn selection bias on
the router where the driver's own draw leaves it zero): the program's own
routing (``submit(keep_routing=True)`` -> ``Request.routed_experts``: every
layer's row, the dense layer's selection among them) comes out correct with
every deficit read; with the path broken underneath three ways (the
selection ignored in the decode kernel; a group ranked by its maximum; the
bias left out of the selection) ``correct`` is false; and the float8
control comes out not correct."""
import dataclasses

import jax
import pytest

import deepspeed_tpu.moe.dropless as dropless
import deepspeed_tpu.ops.pallas.latent_attention as latent
from benchmark import control, harness, reference
from benchmark.drivers import serve
from benchmark.tests.test_serve_check import (SYSTEM, TRAFFIC, drive,
                                              one_device_mesh)  # noqa: F401

TOPK = 16
CONFIG = dict(
    name="deepseek-v32-tiny", family="deepseek_v32", attention_bias=False,
    first_k_dense_replace=1, hidden_act="silu", hidden_size=64,
    intermediate_size=160, kv_lora_rank=32, max_position_embeddings=256,
    model_type="deepseek_v32", moe_intermediate_size=48, moe_layer_freq=1,
    n_group=8, n_routed_experts=2, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=8, num_hidden_layers=3,
    num_key_value_heads=4, num_nextn_predict_layers=0, q_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, rms_norm_eps=1e-6,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16, "type": "yarn"},
    rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
    v_head_dim=16, vocab_size=97, index_n_heads=4, index_head_dim=16,
    index_topk=TOPK, deployment={"router_outputs": 32, "experts_held": [0, 2]})


def tiny_cell(**system):
    real = harness.load_cell("serve-deepseek-v32-exp-ep16-l5-longdoc")
    return dataclasses.replace(real, config=CONFIG, traffic=TRAFFIC,
                               system=dict(SYSTEM, **system),
                               expect_kernels=())


@pytest.fixture(autouse=True)
def a_drawn_selection_bias(monkeypatch):
    """The driver draws every bias 0; the router's selection bias here is
    N(0, 0.3) from the seed, so that leaving it out is a fault."""
    plain = serve.make_params

    def make(model, mcfg, seed, dtype):
        params = plain(model, mcfg, seed, dtype)
        gate = params["blocks"]["moe"]["gate"]
        gate["bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(harness.jax_seed(seed) + 1),
            gate["bias"].shape, dtype)
        return params
    monkeypatch.setattr(serve, "make_params", make)


def the_selection_ignored_in_the_decode_kernel(monkeypatch):
    plain = latent.latent_attention
    monkeypatch.setattr(latent, "latent_attention",
                        lambda *a, select=None, **kw: plain(*a, **kw))


def a_group_ranked_by_its_maximum(monkeypatch):
    plain = dropless.kept_groups
    monkeypatch.setattr(dropless, "kept_groups",
                        lambda scores, groups, best=1: plain(scores, groups))


def the_bias_left_out_of_the_selection(monkeypatch):
    plain = dropless.route_sigmoid_topk
    monkeypatch.setattr(
        dropless, "route_sigmoid_topk",
        lambda logits, k, renorm, bias=None, scale=1.0, groups=None: plain(
            logits, k, renorm, None, scale, groups))


@pytest.mark.parametrize("case", [
    "own_routing", "the_selection_ignored_in_the_decode_kernel",
    "a_group_ranked_by_its_maximum", "the_bias_left_out_of_the_selection"])
def test_the_serving_check_on_sparse_latent_attention(case, monkeypatch,
                                                      one_device_mesh,
                                                      capsys, tmp_path):
    if case != "own_routing":
        globals()[case](monkeypatch)
    line, printed = drive(tiny_cell(), tmp_path, capsys)
    compared = line["compared"]
    if case == "own_routing":
        assert line["correct"] and line["failed"] == 0
        # (12 + 5 and 40 + 5 tokens fed) x (2 sparse layers x top-8 + 3
        # layers x the keys each row attends: all it sees up to 16)
        fed = [12 + 5, 40 + 5]
        keys = sum(min(t + 1, TOPK) for n in fed for t in range(n))
        assert f"of {2 * 8 * sum(fed) + 3 * keys} the reference's own, " \
            "largest deficit 0.000" in printed.out
        assert compared["pick_deficit"]["value"] < 1e-3
        assert compared["served_logit_gap"]["value"] < 1e-3
    elif case == "the_selection_ignored_in_the_decode_kernel":
        # the hand-out still says what the indexer selected; the kernel
        # attended everything: the served logits are another model's
        assert not line["correct"]
        assert compared["served_logit_gap"]["value"] > \
            reference.SERVE_LOGIT_MARGIN
        assert "FAIL served tokens within the margin" in printed.out
    else:
        assert not line["correct"]
        assert compared["pick_deficit"]["value"] > reference.ROUTE_TIE_TOL
        assert "FAIL every pick within the tie tolerance" in printed.out


@pytest.mark.parametrize("seed", [2 ** 31 + 42, 2 ** 31 + 43])
def test_the_float8_control_comes_out_not_correct(seed, one_device_mesh):
    got = control.read(tiny_cell(check={"prompt_lens": [12, 40, 25, 33],
                                        "new_tokens": 24}), seed,
                       rehearsal=True)
    assert got["honest"]["positions"] == got["control"]["positions"] == 96
    assert got["honest"]["correct"]
    assert "pick_deficit" in got["honest"]["compared"]
    assert not got["control"]["correct"]
