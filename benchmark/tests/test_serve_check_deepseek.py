"""The serving check on latent attention behind a grouped router (the
``deepseek_v2`` family at a tiny size, float32, on the CPU; the driver whole,
as ``test_serve_check.py`` drives it): the program's own picks
(``submit(keep_routing=True)`` -> ``Request.routed_experts``) come out
correct with every deficit read, judged by a reference in the EXPANDED form
while the program attends absorbed; picks no router made for that token come
out not correct by the tolerance; with the latent path broken underneath (the
rotated shared key left out of the absorbed query; a latent row stored
without its norm) ``correct`` is false by the margin; and the float8 control
comes out not correct."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.models.generation as generation
import deepspeed_tpu.serving.model_runner as model_runner
from benchmark import control, harness, reference
from benchmark.tests.test_serve_check import (SYSTEM, TRAFFIC, drive,
                                              one_device_mesh)  # noqa: F401
from deepspeed_tpu.serving.engine import ServingEngine

CONFIG = dict(
    name="deepseek-v2-tiny", family="deepseek_v2", attention_bias=False,
    first_k_dense_replace=1, hidden_act="silu", hidden_size=64,
    intermediate_size=160, kv_lora_rank=32, max_position_embeddings=256,
    model_type="deepseek_v2", moe_intermediate_size=48, moe_layer_freq=1,
    n_group=8, n_routed_experts=4, n_shared_experts=2, norm_topk_prob=False,
    num_attention_heads=4, num_experts_per_tok=6, num_hidden_layers=3,
    num_key_value_heads=4, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rms_norm_eps=1e-6,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 16, "type": "yarn"},
    rope_theta=10000, routed_scaling_factor=16, scoring_func="softmax",
    seq_aux=True, tie_word_embeddings=False, topk_group=3,
    topk_method="group_limited_greedy", v_head_dim=16, vocab_size=97,
    deployment={"router_outputs": 32, "experts_held": [0, 4]})


def tiny_cell(**system):
    real = harness.load_cell("serve-deepseek-v2-ep8-l5-longdoc")
    return dataclasses.replace(real, config=CONFIG, traffic=TRAFFIC,
                               system=dict(SYSTEM, **system),
                               expect_kernels=())


def picks_of_the_token_before(monkeypatch):
    plain = ServingEngine._gather_routing

    def gather(self, req):
        plain(self, req)
        req.routed_experts = np.roll(req.routed_experts, 1, axis=0)
    monkeypatch.setattr(ServingEngine, "_gather_routing", gather)


def the_shared_key_left_out_of_the_score(monkeypatch):
    plain = generation.absorb_query
    monkeypatch.setattr(model_runner, "absorb_query",
                        lambda q_nope, q_pe, wk, lanes: plain(
                            q_nope, jnp.zeros_like(q_pe), wk, lanes))


def a_latent_stored_a_slot_late(monkeypatch):
    """Every row goes to the slot after its own: the pool holds the right
    numbers at wrong positions."""
    plain = model_runner.PagedCache.write_latent
    monkeypatch.setattr(
        model_runner.PagedCache, "write_latent",
        lambda self, kv, li, row: plain(self, kv, li, jnp.roll(row, 1, 1)))


@pytest.mark.parametrize("case", ["own_picks", "picks_of_the_token_before",
                                  "the_shared_key_left_out_of_the_score",
                                  "a_latent_stored_a_slot_late"])
def test_the_serving_check_on_latent_attention(case, monkeypatch,
                                               one_device_mesh, capsys,
                                               tmp_path):
    if case != "own_picks":
        globals()[case](monkeypatch)
    line, printed = drive(tiny_cell(), tmp_path, capsys)
    compared = line["compared"]
    if case == "own_picks":
        assert line["correct"] and line["failed"] == 0
        # 2 sparse layers x top-6 x (12 + 5 and 40 + 5 tokens fed)
        assert "of 744 the reference's own, largest deficit 0.000" \
            in printed.out
        assert compared["pick_deficit"]["value"] < 1e-3
        assert compared["served_logit_gap"]["value"] < 1e-3
    elif case == "picks_of_the_token_before":
        assert not line["correct"]
        assert compared["pick_deficit"]["value"] > reference.ROUTE_TIE_TOL
        assert "FAIL every pick within the tie tolerance" in printed.out
    else:
        assert not line["correct"]
        assert compared["served_logit_gap"]["value"] > \
            reference.SERVE_LOGIT_MARGIN
        assert "FAIL served tokens within the margin" in printed.out


@pytest.mark.parametrize("seed", [2 ** 31 + 42, 2 ** 31 + 43])
def test_the_float8_control_comes_out_not_correct(seed, one_device_mesh):
    got = control.read(tiny_cell(check={"prompt_lens": [12, 40, 25, 33],
                                        "new_tokens": 24}), seed,
                       rehearsal=True)
    assert got["honest"]["positions"] == got["control"]["positions"] == 96
    assert got["honest"]["correct"]
    assert "pick_deficit" in got["honest"]["compared"]
    assert not got["control"]["correct"]
