"""HF architecture import policies: logits parity vs torch for GPT-Neo,
GPT-J, OPT, BLOOM, BERT (the GPT-2 policy test lives in test_inference.py).

Mirrors the reference's replace_policy.py per-arch coverage
(module_inject/replace_policy.py:18-32) with tiny randomly-initialized HF
models as oracles.
"""

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeed_tpu.models.hf import load_hf
from deepspeed_tpu.models.transformer import Transformer


def _ours_from(hf_model, ids, batch_extra=None):
    params, cfg = load_hf(hf_model)
    model = Transformer(cfg.__class__(**{**cfg.__dict__,
                                         "dtype": jnp.float32,
                                         "attention_impl": "reference"}))
    batch = {"input_ids": jnp.asarray(ids)}
    if batch_extra:
        batch.update(batch_extra)
    return np.asarray(model.apply({"params": params}, batch))


@pytest.mark.slow
def test_hf_gpt_neo_parity():
    """Alternating global/local attention + unscaled attn + unbiased qkv."""
    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=32, hidden_size=32,
        num_layers=4, num_heads=4, intermediate_size=64,
        attention_types=[[["global", "local"], 2]], window_size=8)
    hf = transformers.GPTNeoForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


# tier-2 (round 8 budget): the fattest per-arch HF parity leg; the other
# arch parities (falcon/mixtral/qwen3/...) keep gating tier-1
@pytest.mark.slow
def test_hf_gptj_parity():
    """Rotary positions + parallel residual + untied biased lm head."""
    hf_cfg = transformers.GPTJConfig(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        rotary_dim=4)
    hf = transformers.GPTJForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(1).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


# tier-2 (round 10 budget): fattest passing legs demoted per the standing
# guardrail — tier-1 crept past ~80% of the 870s budget once the comm-plan
# legs landed; cheaper cousins still gate tier-1
@pytest.mark.slow
def test_hf_opt_parity():
    """ReLU MLP + learned positions at +2 offset."""
    hf_cfg = transformers.OPTConfig(
        vocab_size=96, max_position_embeddings=32, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, ffn_dim=64,
        word_embed_proj_dim=32, do_layer_norm_before=True)
    hf = transformers.OPTForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(2).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_hf_bloom_parity():
    """ALiBi attention + embedding LayerNorm + head-major fused qkv.

    slow (round-14 budget sweep, 11s): the cheaper tier-1 cousins are
    the other arch parities in this file (gpt2/llama/...) and the ALiBi
    kernel parity in test_flash_attention.py / routing in
    test_attention_routing.py."""
    hf_cfg = transformers.BloomConfig(
        vocab_size=96, hidden_size=32, n_layer=2, n_head=4)
    hf = transformers.BloomForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(3).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


# tier-2 (round-19 budget sweep, ~7s): the cheaper tier-1 cousins are
# test_hf_roberta_parity + test_hf_distilbert_parity (same encoder
# loader family) and test_attention_routing's
# test_masked_bert_trains_through_kernel; scripts/tier2.sh runs this
# MLM-head leg
@pytest.mark.slow
def test_hf_bert_parity():
    """Post-LN encoder + token types + MLM transform head."""
    hf_cfg = transformers.BertConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, type_vocab_size=2)
    hf = transformers.BertForMaskedLM(hf_cfg).eval()
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 96, (2, 16))
    tt = rng.integers(0, 2, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids), token_type_ids=torch.tensor(tt)).logits.numpy()
    ours = _ours_from(hf, ids, {"token_type_ids": jnp.asarray(tt)})
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_unknown_arch_raises():
    with pytest.raises(NotImplementedError, match="policy"):
        load_hf(object(), arch="T5ForConditionalGeneration")


# -- KV-cache decode parity for the policy architectures ----------------------

import dataclasses

import jax
from deepspeed_tpu.models.generation import forward_with_cache, init_cache


def _decode_vs_full(hf_model, ids, rtol=2e-3):
    """Last-token logits from the cached decode path must match the full
    forward (which is itself HF-parity-tested above)."""
    from deepspeed_tpu.models.hf import load_hf
    params, cfg = load_hf(hf_model)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              attention_impl="reference")
    model = Transformer(cfg)
    full = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    cache = init_cache(cfg, ids.shape[0], ids.shape[1])
    # feed the prompt in two chunks to exercise pos-offset handling
    half = ids.shape[1] // 2
    _, cache = forward_with_cache(cfg, params, jnp.asarray(ids[:, :half]),
                                  cache)
    logits, _ = forward_with_cache(cfg, params, jnp.asarray(ids[:, half:]),
                                   cache)
    np.testing.assert_allclose(np.asarray(logits[:, -1]), full[:, -1],
                               rtol=rtol, atol=rtol)


def test_gptj_decode_parity():
    hf_cfg = transformers.GPTJConfig(vocab_size=96, n_positions=32, n_embd=32,
                                     n_layer=2, n_head=4, rotary_dim=4)
    hf = transformers.GPTJForCausalLM(hf_cfg).eval()
    _decode_vs_full(hf, np.random.default_rng(5).integers(0, 96, (2, 16)))


def test_gpt_neo_decode_parity():
    hf_cfg = transformers.GPTNeoConfig(
        vocab_size=96, max_position_embeddings=32, hidden_size=32,
        num_layers=4, num_heads=4, intermediate_size=64,
        attention_types=[[["global", "local"], 2]], window_size=8)
    hf = transformers.GPTNeoForCausalLM(hf_cfg).eval()
    _decode_vs_full(hf, np.random.default_rng(6).integers(0, 96, (2, 16)))


def test_opt_decode_parity():
    hf_cfg = transformers.OPTConfig(
        vocab_size=96, max_position_embeddings=32, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, ffn_dim=64,
        word_embed_proj_dim=32, do_layer_norm_before=True)
    hf = transformers.OPTForCausalLM(hf_cfg).eval()
    _decode_vs_full(hf, np.random.default_rng(7).integers(0, 96, (2, 16)))


def test_bloom_decode_parity():
    hf_cfg = transformers.BloomConfig(vocab_size=96, hidden_size=32,
                                      n_layer=2, n_head=4)
    hf = transformers.BloomForCausalLM(hf_cfg).eval()
    _decode_vs_full(hf, np.random.default_rng(8).integers(0, 96, (2, 16)))


@pytest.mark.slow
def test_moe_decode_parity():
    """MoE models decode (round-1 gap: generation.py raised); with a no-drop
    capacity factor the cached decode matches the full forward."""
    from deepspeed_tpu.models import build_model
    model, cfg = build_model("gpt2-tiny", moe_experts=4,
                             moe_capacity_factor=4.0, dtype=jnp.float32,
                             attention_impl="reference")
    ids = np.random.default_rng(9).integers(0, cfg.vocab_size, (2, 16))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.asarray(ids)})["params"]
    logits_full, _aux = model.apply({"params": params},
                                    {"input_ids": jnp.asarray(ids)})
    cache = init_cache(cfg, 2, 16)
    _, cache = forward_with_cache(cfg, params, jnp.asarray(ids[:, :8]), cache)
    logits, _ = forward_with_cache(cfg, params, jnp.asarray(ids[:, 8:]), cache)
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(logits_full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_hf_roberta_parity():
    """RoBERTa: BERT encoder + position offset + lm_head transform."""
    hf_cfg = transformers.RobertaConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=34, type_vocab_size=1, pad_token_id=1)
    hf = transformers.RobertaForMaskedLM(hf_cfg).eval()
    ids = np.random.default_rng(10).integers(2, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_hf_distilbert_parity():
    hf_cfg = transformers.DistilBertConfig(
        vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
        max_position_embeddings=32)
    hf = transformers.DistilBertForMaskedLM(hf_cfg).eval()
    ids = np.random.default_rng(11).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_hf_gpt_neox_parity():
    """GPT-NeoX/Pythia: dual-LN parallel residual + rotate_half rotary over
    rotary_pct of head_dim + per-head-interleaved fused qkv."""
    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, rotary_pct=0.25,
        use_parallel_residual=True)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(12).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_hf_gpt_neox_sequential_parity():
    """use_parallel_residual=False NeoX variants reduce to the standard
    sequential pre-LN block."""
    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, rotary_pct=1.0,
        use_parallel_residual=False)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(13).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_hf_gpt_neox_decode_parity():
    # rotary_pct=0.5 of head_dim 8 gives rotary_dim 4: at rd=2 the rotate_half
    # and interleaved layouts coincide and the test would be vacuous. Likewise
    # perturb the LayerNorms away from fresh-init identity so the dual-LN
    # parallel residual (ln1 != ln2) is actually observable in decode.
    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, rotary_pct=0.5)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in hf.named_parameters():
            if "layernorm" in name:
                p.add_(torch.randn_like(p) * 0.2)
    ids = np.random.default_rng(14).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(_ours_from(hf, ids), ref, rtol=2e-3, atol=2e-3)
    _decode_vs_full(hf, ids)


def test_hf_clip_text_parity():
    """CLIP text encoder: causal pre-LN + quick_gelu; output = final hidden
    states (no LM head)."""
    hf_cfg = transformers.CLIPTextConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32)
    hf = transformers.CLIPTextModel(hf_cfg).eval()
    ids = np.random.default_rng(15).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).last_hidden_state.numpy()
    ours = _ours_from(hf, ids)
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_megatron_gpt_load():
    """Megatron-LM GPT checkpoint layout: the v2 per-head-interleaved fused
    qkv de-interleaves to exactly the column-chunked v0 layout."""
    from deepspeed_tpu.models.hf import load_megatron_gpt
    rng = np.random.default_rng(16)
    L, H, nh, V, S = 2, 32, 4, 96, 32
    hd = H // nh

    def mk(shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.05

    qw = [mk((H, H)) for _ in range(L)]      # rows = output (q) dim
    kw = [mk((H, H)) for _ in range(L)]
    vw = [mk((H, H)) for _ in range(L)]
    qb = [mk((H,)) for _ in range(L)]
    kb = [mk((H,)) for _ in range(L)]
    vb = [mk((H,)) for _ in range(L)]

    def interleave_w(i):
        # [nh, 3, hd, H] row layout of megatron v2 fused qkv
        per = np.stack([qw[i].reshape(nh, hd, H), kw[i].reshape(nh, hd, H),
                        vw[i].reshape(nh, hd, H)], axis=1)
        return per.reshape(3 * H, H)

    def interleave_b(i):
        per = np.stack([qb[i].reshape(nh, hd), kb[i].reshape(nh, hd),
                        vb[i].reshape(nh, hd)], axis=1)
        return per.reshape(3 * H)

    sd = {"language_model.embedding.word_embeddings.weight": mk((V, H)),
          "language_model.embedding.position_embeddings.weight": mk((S, H)),
          "language_model.encoder.final_layernorm.weight": mk((H,)),
          "language_model.encoder.final_layernorm.bias": mk((H,))}
    for i in range(L):
        p = f"language_model.encoder.layers.{i}."
        sd[p + "input_layernorm.weight"] = mk((H,))
        sd[p + "input_layernorm.bias"] = mk((H,))
        sd[p + "attention.query_key_value.weight"] = interleave_w(i)
        sd[p + "attention.query_key_value.bias"] = interleave_b(i)
        sd[p + "attention.dense.weight"] = mk((H, H))
        sd[p + "attention.dense.bias"] = mk((H,))
        sd[p + "post_attention_layernorm.weight"] = mk((H,))
        sd[p + "post_attention_layernorm.bias"] = mk((H,))
        sd[p + "mlp.dense_h_to_4h.weight"] = mk((2 * H, H))
        sd[p + "mlp.dense_h_to_4h.bias"] = mk((2 * H,))
        sd[p + "mlp.dense_4h_to_h.weight"] = mk((H, 2 * H))
        sd[p + "mlp.dense_4h_to_h.bias"] = mk((H,))

    meta = {"num_layers": L, "hidden_size": H, "num_heads": nh,
            "vocab_size": V, "max_seq_len": S, "mlp_ratio": 2}
    params, cfg = load_megatron_gpt(sd, meta, version=2)
    # oracle: the de-interleaved kernel must equal the hand-concatenated one
    expect = np.concatenate([qw[0].T, kw[0].T, vw[0].T], axis=1)
    np.testing.assert_allclose(
        np.asarray(params["blocks"]["attn_qkv"]["kernel"][0]), expect,
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(params["blocks"]["attn_qkv"]["bias"][0]),
        np.concatenate([qb[0], kb[0], vb[0]]), rtol=1e-6, atol=1e-6)
    # and the loaded model must run
    model = Transformer(cfg.__class__(**{**cfg.__dict__,
                                         "dtype": jnp.float32,
                                         "attention_impl": "reference"}))
    ids = rng.integers(0, V, (2, 16))
    out = model.apply({"params": params}, {"input_ids": jnp.asarray(ids)})
    assert np.asarray(out).shape == (2, 16, V)


def test_replace_and_revert_transformer_layer_api():
    """Reference export names (deepspeed/__init__.py:24-35): replace maps an
    HF model functionally onto the TPU-native Transformer (logits parity);
    revert returns the untouched original."""
    import deepspeed_tpu as ds

    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=4)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    module, params, cfg = ds.replace_transformer_layer(
        hf, dtype=jnp.float32)
    assert module.cfg.dtype == jnp.float32      # dtype override applied
    ids = np.random.default_rng(2).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    import dataclasses
    from deepspeed_tpu.models.transformer import Transformer
    # parity through the RETURNED module's cfg (only the attention impl is
    # swapped — the Pallas kernel needs a TPU)
    module = Transformer(dataclasses.replace(
        module.cfg, attention_impl="reference"))
    ours = np.asarray(module.apply({"params": params},
                                   {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)
    assert ds.revert_transformer_layer(hf) is hf


def test_deepspeed_transformer_layer_module():
    """DeepSpeedTransformerLayer: one block over [B, S, H] hidden states
    (the reference's fused-layer export, ops/transformer/transformer.py:459)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(hidden_size=32, num_heads=4, num_layers=1,
                            dtype=jnp.float32, attention_impl="reference")
    layer = ds.DeepSpeedTransformerLayer(cfg)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 8, 32)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    y = layer.apply(params, x)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
    assert ds.DeepSpeedTransformerConfig is TransformerConfig
    assert "dtype" in ds.default_inference_config()


def test_replace_transformer_layer_raw_state_dict():
    """The shim threads an explicit HF config through to the policy (the
    raw-state-dict path load_hf's live-model dispatch can't carry)."""
    import deepspeed_tpu as ds

    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=4)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    sd = hf.state_dict()
    module, params, cfg = ds.replace_transformer_layer(
        sd, config=hf_cfg, arch="gpt2")
    assert cfg.num_layers == 2 and cfg.hidden_size == 32
    with pytest.raises(NotImplementedError, match="no import policy"):
        ds.replace_transformer_layer(sd, config=hf_cfg, arch="not-an-arch")


def test_deepspeed_transformer_layer_mask_contract():
    """The shim validates the mask: boolean/int True=attend (HF [B,S]
    accepted and expanded); the reference's ADDITIVE float mask is rejected
    loudly (silently passing it would attend the inverted positions)."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(hidden_size=32, num_heads=4, num_layers=1,
                            dtype=jnp.float32, causal=False,
                            attention_impl="reference")
    layer = ds.DeepSpeedTransformerLayer(cfg)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 8, 32)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    mask = np.ones((2, 8), np.int32)
    mask[:, -3:] = 0
    y_masked = layer.apply(params, x, jnp.asarray(mask))
    assert np.isfinite(np.asarray(y_masked)).all()
    # masking the tail must change the visible positions' outputs
    y_full = layer.apply(params, x)
    assert not np.allclose(np.asarray(y_masked)[:, :5],
                           np.asarray(y_full)[:, :5])
    with pytest.raises(ValueError, match="additive"):
        layer.apply(params, x, (1.0 - mask) * -10000.0)
    with pytest.raises(ValueError, match="MoE"):
        moe_layer = ds.DeepSpeedTransformerLayer(
            TransformerConfig(hidden_size=32, num_heads=4, num_layers=1,
                              moe_experts=4, dtype=jnp.float32,
                              attention_impl="reference"))
        moe_layer.init(jax.random.PRNGKey(0), x)


def _llama_tiny(**over):
    kw = dict(vocab_size=96, hidden_size=32, intermediate_size=56,
              num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=2, max_position_embeddings=64)
    kw.update(over)
    # seeded weights: the token-exact greedy checks are knife-edge argmaxes
    # over near-random logits — unseeded torch init made them flaky
    torch.manual_seed(7)
    return transformers.LlamaForCausalLM(transformers.LlamaConfig(**kw)).eval()


def test_hf_llama_parity():
    """Llama family (EXCEEDS the reference's replace_policy list — v0.8.1
    pre-dates Llama): RMSNorm, SwiGLU, grouped-query attention, rotate_half
    rotary with config rope_theta."""
    import dataclasses
    hf = _llama_tiny(rope_theta=500000.0)
    ids = np.random.default_rng(0).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.norm == "rmsnorm" and cfg.gated_mlp and cfg.num_kv_heads == 2
    assert cfg.rope_theta == 500000.0
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_hf_mistral_parity():
    """Mistral: the Llama block family + a uniform sliding window on every
    layer (window smaller than the test seq so it actually binds)."""
    import dataclasses
    hf = transformers.MistralForCausalLM(transformers.MistralConfig(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8)).eval()
    ids = np.random.default_rng(1).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.layer_windows == (8, 8, 8)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_hf_llama_greedy_generate_matches():
    """KV-cache decode (RMSNorm + GQA + SwiGLU through the scan loop) is
    token-exact vs HF greedy generate."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    hf = _llama_tiny()
    params, cfg = load_hf(hf)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              attention_impl="reference")
    ids = np.random.default_rng(2).integers(0, 96, (2, 10))
    with torch.no_grad():
        ref = hf.generate(torch.tensor(ids), max_new_tokens=8,
                          do_sample=False).numpy()
    ours = np.asarray(generate(cfg, params, jnp.asarray(ids), 8))
    np.testing.assert_array_equal(ours, ref)


# tier-2 (round-19 budget sweep, ~4s): the cheaper tier-1 cousins are
# test_hf_llama_parity + test_hf_mistral_parity (real GQA ratios vs
# HF); scripts/tier2.sh runs this degenerate-ratio pin
@pytest.mark.slow
def test_gqa_matches_mha_when_kv_heads_equal():
    """num_kv_heads == num_heads must be numerically identical to the MHA
    path (the GQA split/repeat degenerates away)."""
    from deepspeed_tpu.models import build_model
    kw = dict(hidden_size=64, num_layers=2, num_heads=4, vocab_size=128,
              max_seq_len=32, dtype=jnp.float32, attention_impl="reference")
    m1, _ = build_model("gpt2-tiny", **kw)
    m2, _ = build_model("gpt2-tiny", num_kv_heads=4, **kw)
    import jax
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32)}
    p = m1.init(jax.random.PRNGKey(0), batch)["params"]
    np.testing.assert_array_equal(
        np.asarray(m1.apply({"params": p}, batch)),
        np.asarray(m2.apply({"params": p}, batch)))


def test_hf_llama_attention_bias_parity():
    """Qwen-style attention_bias=True: biased q/k/v/o projections map and
    match HF; genuinely unsupported RoPE geometry (yarn) is still REJECTED
    at load instead of decoding garbage."""
    import dataclasses
    hf = _llama_tiny(attention_bias=True)
    ids = np.random.default_rng(3).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert "bias" in params["blocks"]["attn_qkv"]
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)

    with pytest.raises(NotImplementedError, match="yarn"):
        load_hf(_llama_tiny(num_hidden_layers=1,
                            rope_scaling={"rope_type": "yarn",
                                          "factor": 2.0}))


def test_hf_llama3_rope_scaling_parity():
    """Llama-3.1-style rope_scaling (per-frequency remap): logits parity
    and token-exact greedy decode vs HF. The original window (16) is far
    below max (64) so all three frequency bands (high kept, low divided,
    medium smoothed) are exercised. Round 4 refused these checkpoints;
    the table now mirrors HF modeling_rope_utils._compute_llama3_parameters."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    hf = _llama_tiny(rope_scaling={
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 16})
    ids = np.random.default_rng(6).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.rope_scaling_type == "llama3"
    assert cfg.rope_original_max_position == 16
    # the static table itself matches HF's llama3 remap
    from transformers.modeling_rope_utils import _compute_llama3_parameters
    ref_inv, _ = _compute_llama3_parameters(hf.config, device="cpu")
    np.testing.assert_allclose(cfg.rope_inv_freq(), ref_inv.numpy(),
                               rtol=1e-6)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    # token-exact greedy through the KV-cache decode path
    pids = np.random.default_rng(7).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)


def test_hf_llama_linear_and_dynamic_rope_parity():
    """Linear position-interpolation scaling: logits parity vs HF. Dynamic
    NTK: the static table equals HF's _compute_dynamic_ntk_parameters at
    every target length (beyond the original window the base stretches;
    within it the table is the default one — checked both ways)."""
    import dataclasses
    hf = _llama_tiny(rope_scaling={"rope_type": "linear", "factor": 2.0})
    ids = np.random.default_rng(8).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.rope_scaling_type == "linear"
    assert cfg.rope_scaling_factor == 2.0
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)

    from transformers.modeling_rope_utils import \
        _compute_dynamic_ntk_parameters
    from deepspeed_tpu.models.transformer import TransformerConfig
    hcfg = transformers.LlamaConfig(
        hidden_size=32, num_attention_heads=4, max_position_embeddings=32,
        rope_theta=10000.0,
        rope_scaling={"rope_type": "dynamic", "factor": 2.0})
    for S in (16, 32, 64, 128):
        ref_inv, _ = _compute_dynamic_ntk_parameters(hcfg, seq_len=S)
        mine = TransformerConfig(
            hidden_size=32, num_heads=4, max_seq_len=32, pos_embed="rotary",
            rope_scaling_type="dynamic", rope_scaling_factor=2.0,
            rope_original_max_position=32).rope_inv_freq(S)
        np.testing.assert_allclose(mine, ref_inv.numpy(), rtol=1e-6)

    # HF's dynamic path IGNORES the dict's original_max_position_embeddings
    # (explicit TODO in modeling_rope_utils) and stretches relative to
    # config.max_position_embeddings — the loader must mirror that, not
    # trust the dict key
    _, cfg_d = load_hf(_llama_tiny(
        num_hidden_layers=1, max_position_embeddings=64,
        rope_scaling={"rope_type": "dynamic", "factor": 2.0,
                      "original_max_position_embeddings": 16}))
    assert cfg_d.rope_original_max_position == 64
    # a scaled config without the mandatory "factor" must fail loudly,
    # not load as an unscaled table
    with pytest.raises(KeyError, match="factor"):
        sd = _llama_tiny(num_hidden_layers=1).state_dict()
        bad = transformers.LlamaConfig(
            vocab_size=96, hidden_size=32, intermediate_size=56,
            num_hidden_layers=1, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64)
        bad.rope_scaling = {"rope_type": "linear"}
        load_hf(sd, arch="llama", config=bad)

    # end-to-end dynamic at S=64 BEYOND the original 32-window: HF's
    # forward recomputes the stretched base from max(position)+1, and the
    # block passes the trace-time S so the tables agree — this is the
    # branch a static-table loader would silently get wrong
    hf3 = _llama_tiny(max_position_embeddings=32,
                      rope_scaling={"rope_type": "dynamic", "factor": 2.0})
    ids3 = np.random.default_rng(15).integers(0, 96, (2, 64))
    with torch.no_grad():
        ref3 = hf3(torch.tensor(ids3)).logits.numpy()
    params3, cfg3 = load_hf(hf3)
    assert cfg3.rope_scaling_type == "dynamic"
    model3 = Transformer(dataclasses.replace(cfg3, dtype=jnp.float32,
                                             attention_impl="reference"))
    ours3 = np.asarray(model3.apply({"params": params3},
                                    {"input_ids": jnp.asarray(ids3)}))
    np.testing.assert_allclose(ours3, ref3, rtol=4e-3, atol=4e-3)


def test_hf_llama_decoupled_head_dim_parity():
    """Mistral-Nemo-style decoupled head_dim (16 vs hidden/heads = 8):
    qkv projects to (nh + 2*kv) * 16 and attn_proj maps 64 -> 32. Logits
    parity and token-exact greedy decode vs HF."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    hf = _llama_tiny(head_dim=16)
    ids = np.random.default_rng(9).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.head_dim == 16 and cfg.head_dim_override == 16
    # [L, H, (nh + 2*kv) * hd] = [2, 32, (4 + 4) * 16]
    assert params["blocks"]["attn_qkv"]["kernel"].shape == (2, 32, 128)
    assert params["blocks"]["attn_proj"]["kernel"].shape == (2, 64, 32)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    pids = np.random.default_rng(10).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)


def test_hf_qwen3_parity_qk_norm_and_head_dim():
    """Qwen3 (policy 15): per-head q/k RMSNorm before rotary + decoupled
    head_dim (16 vs hidden/heads = 8) + layer_types sliding windows.
    Logits parity and token-exact greedy decode vs HF. q/k norm scales are
    forced away from 1.0 first (ones-init would pass even if dropped)."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(12)
    hf = transformers.Qwen3ForCausalLM(transformers.Qwen3Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, head_dim=16,
        tie_word_embeddings=False)).eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            layer.self_attn.q_norm.weight.normal_(mean=1.0, std=0.2)
            layer.self_attn.k_norm.weight.normal_(mean=1.0, std=0.2)
    ids = np.random.default_rng(12).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.qk_norm and cfg.head_dim == 16
    assert params["blocks"]["q_norm"]["scale"].shape == (2, 16)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    # token-exact greedy through the KV-cache decode path
    pids = np.random.default_rng(13).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)
    # layer_types -> per-layer windows (sliding engages only where typed)
    torch.manual_seed(13)
    hfw = transformers.Qwen3ForCausalLM(transformers.Qwen3Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, head_dim=16, use_sliding_window=True,
        sliding_window=8, max_window_layers=1,
        layer_types=["full_attention", "sliding_attention"])).eval()
    idsw = np.random.default_rng(14).integers(0, 96, (2, 24))
    with torch.no_grad():
        refw = hfw(torch.tensor(idsw)).logits.numpy()
    paramsw, cfgw = load_hf(hfw)
    assert cfgw.layer_windows == (0, 8)
    modelw = Transformer(dataclasses.replace(cfgw, dtype=jnp.float32,
                                             attention_impl="reference"))
    oursw = np.asarray(modelw.apply({"params": paramsw},
                                    {"input_ids": jnp.asarray(idsw)}))
    np.testing.assert_allclose(oursw, refw, rtol=4e-3, atol=4e-3)


def test_hf_mixtral_parity_and_greedy():
    """Mixtral (policy 16): Mistral attention + SwiGLU EXPERTS behind a
    top-2 router (HF block_sparse_moe gate/w1/w3/w2 -> moe.experts
    gate/fc/proj). Logits parity and token-exact greedy decode vs HF —
    the capacity factor E/k makes the GShard queues drop-free, so the
    routing matches HF's capacity-less top-2 exactly at eval."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(21)
    hf = transformers.MixtralForCausalLM(transformers.MixtralConfig(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64)).eval()
    ids = np.random.default_rng(21).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.moe_experts == 4 and cfg.moe_k == 2 and cfg.gated_mlp
    assert cfg.moe_capacity_factor == 2.0          # E/k -> drop-free
    # [L, E, H, I] expert-stacked SwiGLU kernels
    assert params["blocks"]["moe"]["experts"]["gate"]["kernel"].shape == \
        (2, 4, 32, 56)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours, aux = model.apply({"params": params},
                            {"input_ids": jnp.asarray(ids)})
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=4e-3, atol=4e-3)
    assert np.isfinite(float(aux))
    # token-exact greedy through the KV-cache decode path (_moe_mlp)
    pids = np.random.default_rng(22).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)


def test_hf_gemma_parity_and_greedy():
    """Gemma (policy 17): (1+w) RMSNorm scales folded at load, sqrt(H)
    embedding scaling in the compute dtype, tanh-GELU gated MLP, decoupled
    head_dim, tied embeddings. Norm scales are forced away from 0 first
    (fresh HF zero-inits w, making 1+w == 1 — a loader that dropped the
    +1 fold would still pass random-init parity). Logits parity and
    token-exact greedy decode vs HF."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(31)
    hf = transformers.GemmaForCausalLM(transformers.GemmaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64)).eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            layer.input_layernorm.weight.normal_(std=0.3)
            layer.post_attention_layernorm.weight.normal_(std=0.3)
        hf.model.norm.weight.normal_(std=0.3)
    ids = np.random.default_rng(31).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.embed_scale == float(32) ** 0.5
    assert cfg.activation == "gelu" and cfg.head_dim == 16
    assert cfg.tie_embeddings
    # the +1 fold really happened (HF stores w ~ N(0, 0.3); ours = 1 + w)
    assert abs(float(np.mean(params["ln_f"]["scale"])) - 1.0) < 0.5
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    pids = np.random.default_rng(32).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)


def test_hf_phi_parity_and_greedy():
    """Phi (policy 18): parallel residual with a single shared LayerNorm,
    partial rotate_half rotary (0.5 * head_dim), biased projections and
    biased untied lm_head. Logits parity and token-exact greedy decode vs
    HF; qk_layernorm configs are refused loudly."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(41)
    hf = transformers.PhiForCausalLM(transformers.PhiConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64)).eval()
    ids = np.random.default_rng(41).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.parallel_residual and not cfg.parallel_residual_dual_ln
    assert cfg.rotary_dim == 4 and not cfg.rotary_interleaved
    assert cfg.lm_head_bias and not cfg.tie_embeddings
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    pids = np.random.default_rng(42).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)
    with pytest.raises(NotImplementedError, match="qk_layernorm"):
        torch.manual_seed(42)
        load_hf(transformers.PhiForCausalLM(transformers.PhiConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=4,
            qk_layernorm=True)))


def test_hf_gpt_bigcode_mqa_parity_and_greedy():
    """GPT-BigCode / StarCoder (policy 19): multi-query attention — the
    fused c_attn [H + 2*head_dim, H] maps onto our GQA qkv kernel at
    num_kv_heads=1. Logits parity and token-exact greedy decode vs HF;
    the MHA (multi_query=False) layout is refused loudly."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(51)
    hf = transformers.GPTBigCodeForCausalLM(transformers.GPTBigCodeConfig(
        vocab_size=96, n_embd=32, n_head=4, n_layer=2, n_positions=64,
        n_inner=64)).eval()
    ids = np.random.default_rng(51).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.num_kv_heads == 1 and cfg.head_dim == 8
    # [L, H, (nh + 2) * hd] = [2, 32, 48]
    assert params["blocks"]["attn_qkv"]["kernel"].shape == (2, 32, 48)
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    pids = np.random.default_rng(52).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)
    with pytest.raises(NotImplementedError, match="multi_query"):
        torch.manual_seed(52)
        load_hf(transformers.GPTBigCodeForCausalLM(
            transformers.GPTBigCodeConfig(
                vocab_size=96, n_embd=32, n_head=4, n_layer=1,
                n_positions=64, multi_query=False)))


# tier-2 (round-19 budget sweep, ~7s): the cheaper tier-1 cousins are
# test_hf_gpt_neox_parity (parallel residual), test_hf_llama_parity
# (GQA de-interleave) and test_hf_gpt_bigcode_mqa_parity_and_greedy
# (fused qkv + token-exact greedy); scripts/tier2.sh runs this
# two-variant falcon leg
@pytest.mark.slow
def test_hf_falcon_parity_and_greedy():
    """Falcon (policy 20), both supported variants. 7B-style: shared-LN
    parallel residual + MQA. 40B-style: dual-LN parallel residual + GQA
    with the per-kv-group interleaved fused qkv de-interleaved at load.
    Logits parity and token-exact greedy decode vs HF each; legacy
    alibi/sequential falcon-rw configs are refused loudly."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate

    def check(hfcfg, seed, kv_expect):
        torch.manual_seed(seed)
        hf = transformers.FalconForCausalLM(hfcfg).eval()
        ids = np.random.default_rng(seed).integers(0, 96, (2, 20))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.numpy()
        params, cfg = load_hf(hf)
        assert cfg.kv_heads == kv_expect and cfg.parallel_residual
        model = Transformer(dataclasses.replace(
            cfg, dtype=jnp.float32, attention_impl="reference"))
        ours = np.asarray(model.apply({"params": params},
                                      {"input_ids": jnp.asarray(ids)}))
        np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
        pids = np.random.default_rng(seed + 1).integers(0, 96, (2, 10))
        with torch.no_grad():
            gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                               do_sample=False).numpy()
        gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                   attention_impl="reference")
        np.testing.assert_array_equal(
            np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)
        return cfg

    cfg7 = check(transformers.FalconConfig(
        vocab_size=96, hidden_size=32, num_attention_heads=4,
        num_hidden_layers=2, new_decoder_architecture=False,
        multi_query=True, parallel_attn=True, bias=False), 61, 1)
    assert not cfg7.parallel_residual_dual_ln
    cfg40 = check(transformers.FalconConfig(
        vocab_size=96, hidden_size=32, num_attention_heads=4,
        num_hidden_layers=2, new_decoder_architecture=True,
        num_kv_heads=2), 63, 2)
    assert cfg40.parallel_residual_dual_ln
    # Falcon2-11B style: new_decoder_architecture with ONE shared LN
    # (num_ln_in_parallel_attn=1) — detected from the state dict
    cfg11 = check(transformers.FalconConfig(
        vocab_size=96, hidden_size=32, num_attention_heads=4,
        num_hidden_layers=2, new_decoder_architecture=True,
        num_kv_heads=2, num_ln_in_parallel_attn=1,
        parallel_attn=True), 67, 2)
    assert not cfg11.parallel_residual_dual_ln

    with pytest.raises(NotImplementedError, match="alibi"):
        torch.manual_seed(65)
        load_hf(transformers.FalconForCausalLM(transformers.FalconConfig(
            vocab_size=96, hidden_size=32, num_attention_heads=4,
            num_hidden_layers=1, new_decoder_architecture=False,
            multi_query=False, parallel_attn=False, alibi=True)))
    with pytest.raises(NotImplementedError, match="bias"):
        torch.manual_seed(66)
        load_hf(transformers.FalconForCausalLM(transformers.FalconConfig(
            vocab_size=96, hidden_size=32, num_attention_heads=4,
            num_hidden_layers=1, new_decoder_architecture=False,
            multi_query=True, parallel_attn=True, bias=True)))


def test_hf_gemma2_parity_and_greedy():
    """Gemma-2 (policy 21): sandwich norms (post-attn/post-MLP branch norms
    + pre-MLP norm in the ln2 slot), tanh softcapping on attention scores
    and final logits, query_pre_attn_scalar scaling, alternating
    sliding/full layers. The attention cap is small (5.0) so its tanh
    saturation bites hard; the final cap keeps Gemma-2's real 30.0 — still
    a >1% logit shift if dropped, without compressing argmax margins to
    the ulp level that flips greedy tokens spuriously.
    Logits parity and token-exact greedy decode vs HF."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(71)
    hf = transformers.Gemma2ForCausalLM(transformers.Gemma2Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, query_pre_attn_scalar=32,
        attn_logit_softcapping=5.0, final_logit_softcapping=30.0,
        sliding_window=8,
        layer_types=["sliding_attention", "full_attention"])).eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            for norm in (layer.input_layernorm,
                         layer.post_attention_layernorm,
                         layer.pre_feedforward_layernorm,
                         layer.post_feedforward_layernorm):
                norm.weight.normal_(std=0.3)
        hf.model.norm.weight.normal_(std=0.3)
    ids = np.random.default_rng(71).integers(0, 96, (2, 24))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.post_block_norms and cfg.attn_softcap == 5.0
    assert cfg.final_logit_softcap == 30.0
    assert cfg.attn_scale == float(32) ** -0.5
    assert cfg.layer_windows == (8, 0)
    assert "post_attn_norm" in params["blocks"]
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)
    # Token-exact greedy needs a window the generation never overflows:
    # once HF's rolling HybridCache drops positions, HF generate DIVERGES
    # FROM HF's OWN full forward (verified: at context 12 > window 8 the
    # full forward's top-1 is not what HF generate emits), while our
    # decode stays consistent with the forward both parity-match above.
    torch.manual_seed(72)
    hfg = transformers.Gemma2ForCausalLM(transformers.Gemma2Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, query_pre_attn_scalar=32,
        attn_logit_softcapping=5.0, final_logit_softcapping=30.0,
        sliding_window=32,
        layer_types=["sliding_attention", "full_attention"])).eval()
    with torch.no_grad():
        for layer in hfg.model.layers:
            layer.input_layernorm.weight.normal_(std=0.3)
            layer.post_feedforward_layernorm.weight.normal_(std=0.3)
    gparams, gcfg = load_hf(hfg)
    pids = np.random.default_rng(72).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hfg.generate(torch.tensor(pids), max_new_tokens=8,
                            do_sample=False).numpy()
    gcfg = dataclasses.replace(gcfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, gparams, jnp.asarray(pids), 8)), gref)


def test_hf_llama_mlp_bias_parity():
    """mlp_bias=True: biased gate/up/down projections map and match HF.
    Biases forced NONZERO first (fresh HF zero-inits them — a loader that
    dropped them would still pass random-init parity)."""
    import dataclasses
    hf = _llama_tiny(mlp_bias=True)
    torch.manual_seed(1)
    with torch.no_grad():
        for layer in hf.model.layers:
            for proj in (layer.mlp.gate_proj, layer.mlp.up_proj,
                         layer.mlp.down_proj):
                proj.bias.normal_(std=0.2)
    ids = np.random.default_rng(11).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.mlp_bias is True
    for name in ("mlp_gate", "mlp_fc", "mlp_proj"):
        assert "bias" in params["blocks"][name], name
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_hf_gptneox_nonstandard_rotary_base_parity():
    """NeoX checkpoints with rotary_emb_base != 10000 load with the right
    angles now that apply_rotary takes theta (the old guard refused them)."""
    import dataclasses
    hf = transformers.GPTNeoXForCausalLM(transformers.GPTNeoXConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, rotary_emb_base=50000,
        rotary_pct=0.5)).eval()
    ids = np.random.default_rng(4).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert cfg.rope_theta == 50000.0
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_llama_untied_without_head_rejected_and_gated_moe_params():
    """Fail-loud guard: a bare decoder state dict (no lm_head.weight,
    untied) must not fabricate a tied head. gated_mlp + MoE (the Mixtral
    family, supported since round 5) must count the 3-matmul experts in
    the FLOPs model."""
    hf = _llama_tiny(num_hidden_layers=1)
    sd = {k: v for k, v in hf.state_dict().items() if k != "lm_head.weight"}
    with pytest.raises(KeyError, match="lm_head.weight"):
        load_hf(sd, arch="llama", config=hf.config)

    from deepspeed_tpu.models.transformer import get_config
    gated = get_config("gpt2-tiny", gated_mlp=True, moe_experts=4)
    plain = get_config("gpt2-tiny", gated_mlp=False, moe_experts=4)
    per_layer_mlp = 4 * gated.mlp_dim * gated.hidden_size
    assert gated.num_params() - plain.num_params() == \
        gated.num_layers * per_layer_mlp


def test_hf_qwen2_parity_nonzero_biases():
    """Qwen2 (policy 14): Llama family with q/k/v biases but NO o bias —
    mapping is presence-driven from the state dict. Biases are forced
    NONZERO first: a fresh HF model zero-inits them, so a loader that
    dropped them would still pass random-init parity (the trap this test
    exists to close)."""
    import dataclasses
    hf = transformers.Qwen2ForCausalLM(transformers.Qwen2Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True)).eval()
    torch.manual_seed(0)            # unseeded normal_ made this flaky
    with torch.no_grad():
        for layer in hf.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(std=0.2)
    ids = np.random.default_rng(5).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, cfg = load_hf(hf)
    assert "bias" in params["blocks"]["attn_qkv"]
    assert "bias" not in params["blocks"]["attn_proj"]
    assert cfg.tie_embeddings
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_hf_qwen2_sliding_window_gating():
    """Qwen2's window only engages when use_sliding_window=True, and the
    first max_window_layers stay on full attention."""
    mk = lambda **kw: transformers.Qwen2ForCausalLM(transformers.Qwen2Config(
        vocab_size=96, hidden_size=32, intermediate_size=56,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, **kw)).eval()
    _, cfg_off = load_hf(mk(sliding_window=8, use_sliding_window=False))
    assert cfg_off.layer_windows is None
    hf = mk(sliding_window=8, use_sliding_window=True, max_window_layers=1)
    _, cfg_on = load_hf(hf)
    assert cfg_on.layer_windows == (0, 8, 8)
    # and parity holds with the window binding (seq 20 > window 8)
    import dataclasses
    ids = np.random.default_rng(6).integers(0, 96, (2, 20))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    params, _ = load_hf(hf)
    model = Transformer(dataclasses.replace(cfg_on, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours = np.asarray(model.apply({"params": params},
                                  {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(ours, ref, rtol=4e-3, atol=4e-3)


def test_hf_olmoe_parity_and_greedy():
    """OLMoE: MHA with an RMSNorm over the WHOLE projected q and k vectors,
    64-class routing in small (8 SwiGLU experts, top-4 of a softmax, the
    weights NOT renormalised, nothing dropped): HF ``mlp.gate`` +
    ``mlp.experts.{j}.{gate,up,down}_proj`` -> ``moe.gate`` +
    ``moe.experts.{gate,fc,proj}`` stacked [L, E, in, out], served by the
    sorted-token dispatch of moe/dropless.py. Norm scales are forced away
    from 1 first (a loader that dropped q_norm would still pass fresh-init
    parity). Logits, the load-balancing loss and token-exact greedy decode
    vs HF."""
    import dataclasses
    from deepspeed_tpu.models.generation import generate
    torch.manual_seed(41)
    hf = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(
        vocab_size=96, hidden_size=32, intermediate_size=24,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=4, norm_topk_prob=False,
        max_position_embeddings=64, router_aux_loss_coef=0.01)).eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm,
                         layer.input_layernorm,
                         layer.post_attention_layernorm):
                norm.weight.normal_(mean=1.0, std=0.3)
    ids = np.random.default_rng(41).integers(0, 96, (2, 20))
    with torch.no_grad():
        out = hf(torch.tensor(ids), output_router_logits=True,
                 labels=torch.tensor(ids))
    params, cfg = load_hf(hf)
    assert cfg.moe_experts == 8 and cfg.moe_k == 4 and cfg.gated_mlp
    assert cfg.moe_is_dropless and not cfg.moe_norm_topk
    assert cfg.qk_norm == "projection" and cfg.kv_heads == 4
    sd = hf.state_dict()
    blocks = params["blocks"]
    # names, transposes, [L, E, in, out] stacking, both norms at full width
    assert blocks["moe"]["gate"]["kernel"].shape == (2, 32, 8)
    assert blocks["moe"]["experts"]["gate"]["kernel"].shape == (2, 8, 32, 24)
    assert blocks["moe"]["experts"]["proj"]["kernel"].shape == (2, 8, 24, 32)
    np.testing.assert_array_equal(
        np.asarray(blocks["moe"]["experts"]["fc"]["kernel"][1, 5]),
        sd["model.layers.1.mlp.experts.5.up_proj.weight"].numpy().T)
    np.testing.assert_array_equal(
        np.asarray(blocks["moe"]["gate"]["kernel"][0]),
        sd["model.layers.0.mlp.gate.weight"].numpy().T)
    assert blocks["q_norm"]["scale"].shape == (2, 32) == \
        blocks["k_norm"]["scale"].shape
    np.testing.assert_array_equal(
        np.asarray(blocks["k_norm"]["scale"][1]),
        sd["model.layers.1.self_attn.k_norm.weight"].numpy())
    model = Transformer(dataclasses.replace(cfg, dtype=jnp.float32,
                                            attention_impl="reference"))
    ours, aux = model.apply({"params": params},
                            {"input_ids": jnp.asarray(ids)})
    np.testing.assert_allclose(np.asarray(ours), out.logits.numpy(),
                               rtol=4e-3, atol=4e-3)
    # HF's load_balancing_loss_func over both layers' tokens together
    np.testing.assert_allclose(float(aux), float(out.aux_loss), rtol=1e-4)
    pids = np.random.default_rng(42).integers(0, 96, (2, 10))
    with torch.no_grad():
        gref = hf.generate(torch.tensor(pids), max_new_tokens=8,
                           do_sample=False).numpy()
    gcfg = dataclasses.replace(cfg, dtype=jnp.float32,
                               attention_impl="reference")
    np.testing.assert_array_equal(
        np.asarray(generate(gcfg, params, jnp.asarray(pids), 8)), gref)
