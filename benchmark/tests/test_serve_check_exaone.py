"""The serving check on a chip's share of a sigmoid mixture (the
``exaone_moe`` family at a tiny size, float32, on the CPU; the driver whole,
as ``test_serve_check.py`` drives it): the program's own picks
(``submit(keep_routing=True)`` -> ``Request.routed_experts``, the real ones)
come out correct with every deficit read; a program that hands none out is
refused, because the mixture renormalises; picks no router made and the
float8 control come out not correct."""
import dataclasses

import numpy as np
import pytest

from benchmark import control, harness
from benchmark.tests.conftest import submit_without_keep_routing
from benchmark.tests.test_serve_check import (SYSTEM, TRAFFIC, drive,
                                              one_device_mesh)  # noqa: F401
from deepspeed_tpu.serving.engine import ServingEngine

_S, _F = "sliding_attention", "full_attention"
CONFIG = dict(
    name="exaone-moe-tiny", family="exaone_moe", first_k_dense_replace=1,
    head_dim=16, hidden_act="silu", hidden_size=64, intermediate_size=160,
    layer_types=[_S, _S, _S, _F, _S], max_position_embeddings=256,
    mlp_layer_types=["dense"] + ["sparse"] * 4, moe_intermediate_size=48,
    n_group=1, norm_topk_prob=True, num_attention_heads=4, num_experts=4,
    num_experts_per_tok=4, num_hidden_layers=5, num_key_value_heads=2,
    num_nextn_predict_layers=0, num_shared_experts=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=8,
    sliding_windows=[8, 8, 8, 0, 8], tie_word_embeddings=False, topk_group=1,
    vocab_size=97, deployment={"router_outputs": 16, "experts_held": [8, 4]})


def tiny_cell(**system):
    real = harness.load_cell("serve-k-exaone-236b-ep8-l5-mixed")
    return dataclasses.replace(real, config=CONFIG, traffic=TRAFFIC,
                               system=dict(SYSTEM, **system),
                               expect_kernels=())


def picks_of_the_token_before(monkeypatch):
    plain = ServingEngine._gather_routing

    def gather(self, req):
        plain(self, req)
        req.routed_experts = np.roll(req.routed_experts, 1, axis=0)
    monkeypatch.setattr(ServingEngine, "_gather_routing", gather)


@pytest.mark.parametrize("case", ["own_picks", "no_picks_handed_out",
                                  "picks_of_the_token_before"])
def test_the_serving_check_on_a_share(case, monkeypatch, one_device_mesh,
                                      capsys, tmp_path):
    if case == "no_picks_handed_out":
        submit_without_keep_routing(monkeypatch)
    elif case == "picks_of_the_token_before":
        picks_of_the_token_before(monkeypatch)
    line, printed = drive(tiny_cell(), tmp_path, capsys)
    compared = line["compared"]
    if case == "own_picks":
        assert line["correct"] and line["failed"] == 0
        # 4 sparse layers x top-4 x (12 + 5 and 40 + 5 tokens fed)
        assert "of 992 the reference's own, largest deficit 0.000" \
            in printed.out
        assert compared["pick_deficit"]["value"] < 1e-3
        assert compared["served_logit_gap"]["value"] < 1e-3
    if case == "no_picks_handed_out":
        assert not line["correct"] and "pick_deficit" not in compared
        assert "FAIL a renormalised mixture's program hands out its picks" \
            in printed.out
    if case == "picks_of_the_token_before":
        assert not line["correct"]
        assert compared["pick_deficit"]["value"] > \
            compared["pick_deficit"]["limit"]


@pytest.mark.parametrize("seed", [2 ** 31 + 42, 2 ** 31 + 43])
def test_the_float8_control_comes_out_not_correct(seed, one_device_mesh):
    got = control.read(tiny_cell(check={"prompt_lens": [12, 40, 25, 33],
                                        "new_tokens": 24}), seed,
                       rehearsal=True)
    assert got["honest"]["positions"] == got["control"]["positions"] == 96
    assert got["honest"]["correct"]
    assert "pick_deficit" in got["honest"]["compared"]
    assert not got["control"]["correct"]
