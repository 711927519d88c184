"""The serving driver's check, driven whole on the CPU at a tiny size (the
harness's look for a chip skipped, as ``rehearse.py`` skips it) on a mixture
in float32: as published and with the program as it is; renormalised, where
a program that hands out no picks is refused; with a stand-in for the picks a
program hands out (``submit(keep_routing=True)`` -> ``Request.
routed_experts``), which are the PROGRAM's own (``Routing.experts`` of its
``decoder_forward``, layer by layer); and with the path broken underneath:
``correct`` has to come out false. Last, the check's control (``benchmark/
control.py``: the reference with float8 weights in the program's place,
through the same check) at that size: it has to come out not correct."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.models.generation as generation
from benchmark import control, harness, run
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.scheduler import Request

CONFIG = {"name": "olmoe-tiny", "family": "olmoe", "attention_bias": False,
          "clip_qkv": None, "hidden_act": "silu", "hidden_size": 64,
          "intermediate_size": 48, "max_position_embeddings": 256,
          "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
          "num_experts_per_tok": 2, "num_hidden_layers": 2,
          "num_key_value_heads": 4, "rms_norm_eps": 1e-5,
          "rope_scaling": None, "rope_theta": 10000,
          "router_aux_loss_coef": 0.01, "tie_word_embeddings": False,
          "vocab_size": 97}
TRAFFIC = {"kind": "closed_loop", "clients": 3,
           "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                          "min": 8, "max": 48},
           "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                          "min": 4, "max": 10},
           "cycle": 8, "mix_seed": 1}
SYSTEM = {"dtype": "float32",
          "serving": {"block_size": 16, "pool_blocks": 24, "max_batch": 4,
                      "max_blocks_per_seq": 8, "prefill_chunk_tokens": 32,
                      "prefix_cache": True},
          "check": {"prompt_lens": [12, 40], "new_tokens": 6}}


@pytest.fixture
def one_device_mesh():
    import jax
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.parallel.mesh import MeshManager
    before = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(MeshManager(devices=jax.devices()[:1]))
    yield
    mesh_mod.set_global_mesh(before)


def program_picks(monkeypatch, cfg, params, ids) -> np.ndarray:
    """``[len(ids), layers, k]``: the experts the PROGRAM picks for every
    token of ``ids`` in every mixture layer, read off ``Routing.experts``
    inside its ``decoder_forward`` (one ordered callback a layer)."""
    seen = {}
    plain = generation._moe_mlp

    def hooked(cfg, p_moe, h, interpret=False, layer=None):
        y, routing = plain(cfg, p_moe, h, interpret, layer=layer)
        jax.debug.callback(
            lambda li, e: seen.__setitem__(int(li), np.asarray(e)),
            layer, routing.experts, ordered=True)
        return y, routing

    with monkeypatch.context() as m:
        m.setattr(generation, "_moe_mlp", hooked)
        logits, _ = generation.forward_with_cache(
            cfg, params, jnp.asarray(ids, jnp.int32)[None],
            generation.init_cache(cfg, 1, len(ids)))
        jax.block_until_ready(logits)
        jax.effects_barrier()
    assert sorted(seen) == list(range(cfg.num_layers))
    return np.stack([seen[li] for li in range(cfg.num_layers)], axis=1)


def hand_out_picks(monkeypatch, spoil=False):
    """What the program will do itself: ``submit`` takes ``keep_routing`` and
    the request then carries the experts every layer picked for every token
    it was fed, best first, ``[tokens, layers, k]``. The stand-in reads them
    off the program's own decoder (:func:`program_picks`), not off the
    reference; ``spoil`` gives five tokens' second pick in layer 1 to an
    expert the program did not pick there."""
    state = {}
    plain = ServingEngine.submit

    def submit(self, prompt, max_new_tokens=32, keep_routing=False, **kw):
        req = plain(self, prompt, max_new_tokens=max_new_tokens, **kw)
        req._keep_routing = keep_routing
        state["engine"] = self
        return req

    def routed_experts(req):
        if not getattr(req, "_keep_routing", False):
            return None
        engine = state["engine"]
        fed = req.prompt + req.output_tokens[:-1]
        picks = program_picks(monkeypatch, engine.cfg, engine.params, fed)
        if spoil:
            for t in range(2, 7):
                rest = set(range(CONFIG["num_experts"])) - set(picks[t, 1])
                picks[t, 1, 1] = min(rest)
        return picks

    monkeypatch.setattr(ServingEngine, "submit", submit)
    monkeypatch.setattr(Request, "routed_experts", property(routed_experts),
                        raising=False)


def alter_a_served_token(monkeypatch):
    plain = ServingEngine.submit

    def submit(self, prompt, max_new_tokens=32, **kw):
        def on_finish(r):
            r.output_tokens[2] = (r.output_tokens[2] + 1) % 97
        return plain(self, prompt, max_new_tokens=max_new_tokens,
                     on_finish=on_finish, **kw)
    monkeypatch.setattr(ServingEngine, "submit", submit)


def drive(cell, tmp_path, capsys):
    """The driver and ``run.finish`` on ``cell``: the result line, and what
    was printed."""
    out = harness.load_driver("serve").run(
        cell, seed=2 ** 31 + 42, seconds=1.0, trace=False,
        t0=time.perf_counter(), trace_dir=str(tmp_path), rehearsal=True)
    out["devices"] = [type("D", (), {"platform": "cpu",
                                     "device_kind": "cpu"})()]
    line = json.loads(run.finish(cell, out, trace=False))
    return line, capsys.readouterr()


@pytest.mark.parametrize("case", ["published_as_the_program_is",
                                  "renormalised_and_no_picks_handed_out",
                                  "picks_handed_out",
                                  "a_pick_no_router_made",
                                  "a_served_token_altered"])
def test_the_serving_check(case, monkeypatch, one_device_mesh, capsys,
                           tmp_path):
    config = CONFIG
    if case == "picks_handed_out":
        hand_out_picks(monkeypatch)
    elif case == "a_pick_no_router_made":
        hand_out_picks(monkeypatch, spoil=True)
    elif case == "a_served_token_altered":
        config = dict(CONFIG, norm_topk_prob=False)
        alter_a_served_token(monkeypatch)
    elif case == "published_as_the_program_is":
        config = dict(CONFIG, norm_topk_prob=False)
    real = harness.load_cell("serve-olmoe-1b-7b-l8-gen")
    cell = dataclasses.replace(real, config=config, traffic=TRAFFIC,
                               system=SYSTEM, expect_kernels=())
    line, printed = drive(cell, tmp_path, capsys)
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    assert compared["served_logit_gap"]["limit"] == 0.15
    for name, c in compared.items():       # each number beside its limit
        assert f"compared {name}: {c['value']!r}" in printed.err
    handed = case in ("picks_handed_out", "a_pick_no_router_made")
    assert ("pick_deficit" in compared) == handed
    if case in ("published_as_the_program_is", "picks_handed_out"):
        assert line["correct"] and line["failed"] == 0
        assert compared["served_logit_gap"]["value"] < 1e-3
    if case == "renormalised_and_no_picks_handed_out":
        # float32 everywhere, so the tokens agree; refused all the same
        assert not line["correct"]
        assert compared["served_logit_gap"]["value"] < 1e-3
        assert "the program hands none out" in printed.out
        assert "FAIL a renormalised mixture's program hands out its picks" \
            in printed.out
    if case == "picks_handed_out":
        # the program's own picks, in its own layout: all the reference's
        assert "of 248 the reference's own, largest deficit 0.0000" \
            in printed.out
        assert compared["pick_deficit"]["value"] < 1e-4
    if case == "a_pick_no_router_made":
        assert not line["correct"]
        assert compared["pick_deficit"]["value"] > \
            compared["pick_deficit"]["limit"]
        assert "FAIL every pick within the tie tolerance" in printed.out
    if case == "a_served_token_altered":
        assert not line["correct"]
        assert compared["served_logit_gap"]["value"] > 0.15


@pytest.mark.parametrize("seed", [2 ** 31 + 42, 2 ** 31 + 43, 2 ** 31 + 44])
def test_the_float8_control_comes_out_not_correct(seed, one_device_mesh,
                                                  capsys):
    real = harness.load_cell("serve-olmoe-1b-7b-l8-gen")
    system = dict(SYSTEM, check={"prompt_lens": [12, 40, 25, 33],
                                 "new_tokens": 24})
    cell = dataclasses.replace(real, config=dict(CONFIG, norm_topk_prob=False),
                               traffic=TRAFFIC, system=system,
                               expect_kernels=())
    got = control.read(cell, seed, rehearsal=True)
    # both through the driver's own check, at the served positions
    assert got["honest"]["positions"] == got["control"]["positions"] == 96
    assert got["honest"]["correct"]
    assert got["honest"]["compared"]["served_logit_gap"]["value"] < 1e-3
    assert not got["control"]["correct"]
    gap = got["control"]["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert "FAIL" not in capsys.readouterr().out   # judge prints no verdicts
