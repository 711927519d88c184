"""The serving check on a model whose indexer picks each query's keys (the
``keye_vl2`` family at a tiny size, float32, on the CPU; the driver whole, as
``test_serve_check.py`` drives it): the program's own routing
(``submit(keep_routing=True)`` -> ``Request.routed_experts``: a token's
experts, then the keys it attended) comes out correct with every deficit
read; a program that hands none out is refused; the selection of the token
before, and a reference whose indexer has lost its ReLU, read deficits over
the tolerance; the float8 control comes out not correct."""
import dataclasses
import functools

import numpy as np
import pytest

from benchmark import control, harness, reference
from benchmark.tests.conftest import submit_without_keep_routing
from benchmark.tests.test_serve_check import (SYSTEM, TRAFFIC, drive,
                                              one_device_mesh)  # noqa: F401
from deepspeed_tpu.serving.engine import ServingEngine

TOPK = 16
CONFIG = dict(
    name="keye-vl2-tiny", family="keye_vl2", attention_bias=False,
    decoder_sparse_step=1, head_dim=16, hidden_act="silu", hidden_size=64,
    intermediate_size=192, max_position_embeddings=256, max_window_layers=3,
    mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=48,
    norm_topk_prob=True, num_attention_heads=4, num_experts=4,
    num_experts_per_tok=4, num_hidden_layers=3, num_key_value_heads=2,
    num_local_experts=4, rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    rope_theta=1e7,
    sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": TOPK},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=97, deployment={"router_outputs": 16, "experts_held": [8, 4]})
#: the keys' columns of ``routed_experts``
KEYS = slice(CONFIG["num_experts_per_tok"], None)


def tiny_cell(**system):
    real = harness.load_cell("serve-keye-vl2-30b-ep8-l8-longdoc")
    return dataclasses.replace(real, config=CONFIG, traffic=TRAFFIC,
                               system=dict(SYSTEM, **system),
                               expect_kernels=())


def selection_of_the_token_before(monkeypatch):
    """Every token attends what the token before it selected (all of it
    visible to it): its experts stay its own."""
    plain = ServingEngine._gather_routing

    def gather(self, req):
        plain(self, req)
        req.routed_experts[1:, :, KEYS] = req.routed_experts[:-1, :, KEYS]
    monkeypatch.setattr(ServingEngine, "_gather_routing", gather)


def an_indexer_without_its_relu(monkeypatch):
    family = harness.load_family("keye_vl2")
    monkeypatch.setattr(family, "reference_logits", functools.partial(
        family.reference_logits, relu=False))


@pytest.mark.parametrize("case", ["own_selection", "no_hand_out",
                                  "selection_of_the_token_before",
                                  "an_indexer_without_its_relu"])
def test_the_serving_check_follows_the_selection(case, monkeypatch,
                                                 one_device_mesh, capsys,
                                                 tmp_path):
    if case == "no_hand_out":
        submit_without_keep_routing(monkeypatch)
    elif case == "selection_of_the_token_before":
        selection_of_the_token_before(monkeypatch)
    elif case == "an_indexer_without_its_relu":
        an_indexer_without_its_relu(monkeypatch)
    line, printed = drive(tiny_cell(), tmp_path, capsys)
    compared = line["compared"]
    if case == "own_selection":
        assert line["correct"] and line["failed"] == 0
        # 3 layers x (12 + 5 and 40 + 5 tokens fed) x (top-4 experts + the
        # keys each row attends: all it sees up to 16)
        fed = [12 + 5, 40 + 5]
        keys = sum(min(t + 1, TOPK) for n in fed for t in range(n))
        assert f"of {3 * (4 * sum(fed) + keys)} the reference's own, " \
            "largest deficit 0.000" in printed.out
        assert compared["pick_deficit"]["value"] < 1e-3
        assert compared["served_logit_gap"]["value"] < 1e-3
    if case == "no_hand_out":
        assert not line["correct"] and "pick_deficit" not in compared
        assert "FAIL a renormalised mixture's program hands out its picks" \
            in printed.out
    if case in ("selection_of_the_token_before",
                "an_indexer_without_its_relu"):
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
