"""Inference: KV-cache decode parity, generation, HF GPT-2 import parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _precise_matmuls():
    """Parity tolerances assume fp32 math; on real TPUs jnp matmuls default
    to bf16 internally, so pin the precision for these tests."""
    import jax as _jax
    with _jax.default_matmul_precision("highest"):
        yield


from util import require_devices

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.generation import (ensure_scan_layout,
                                             forward_with_cache, generate,
                                             init_cache)


def _model_and_params(seed=0, **kw):
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("attention_impl", "reference")
    model, cfg = build_model("gpt2-tiny", **kw)
    batch = {"input_ids": np.zeros((1, 8), np.int32)}
    params = model.init(jax.random.PRNGKey(seed), batch)["params"]
    return model, cfg, params


def test_cache_forward_matches_full_forward():
    """Prefill-through-cache logits == plain forward logits."""
    model, cfg, params = _model_and_params()
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 16)))
    full = model.apply({"params": params}, {"input_ids": ids})
    cache = init_cache(cfg, 2, 32, jnp.float32)
    cached, cache = forward_with_cache(cfg, params, ids, cache)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
    assert int(cache["pos"]) == 16


@pytest.mark.slow
def test_cache_forward_matches_full_forward_default_dtype():
    """The same at the preset's own dtype, from a restacked per-layer tree:
    the carried-cache scan (in-place KV update) against a fresh full
    forward."""
    model, cfg = build_model("gpt2-tiny", hidden_size=32, num_layers=2,
                             num_heads=2, vocab_size=64, max_seq_len=64,
                             attention_impl="reference")
    ids = np.random.default_rng(0).integers(0, 64, size=(2, 10)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    full_logits = model.apply({"params": params}, {"input_ids": ids})
    sparams = ensure_scan_layout(params, cfg.num_layers)
    cache = init_cache(cfg, 2, 16)
    logits, cache = forward_with_cache(cfg, sparams, jnp.asarray(ids), cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits),
                               rtol=2e-2, atol=2e-2)
    assert int(cache["pos"]) == 10


# tier-2 (round 8 budget): test_cache_forward_matches_full_forward gates
# the same cache numerics in tier-1; the serving integration test pins the
# decode loop token-exactly
@pytest.mark.slow
def test_incremental_decode_matches_full():
    """Token-by-token decode == full forward on the whole sequence."""
    model, cfg, params = _model_and_params(seed=1)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 128, (1, 12)))
    full = model.apply({"params": params}, {"input_ids": ids})

    cache = init_cache(cfg, 1, 16, jnp.float32)
    logits, cache = forward_with_cache(cfg, params, ids[:, :4], cache)
    outs = [logits]
    for t in range(4, 12):
        logits, cache = forward_with_cache(cfg, params, ids[:, t:t + 1], cache)
        outs.append(logits)
    stitched = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stitched), np.asarray(full),
                               rtol=3e-4, atol=3e-4)


def test_generate_greedy_deterministic():
    model, cfg, params = _model_and_params(seed=2)
    prompt = jnp.asarray([[5, 17, 3]])
    out1 = generate(cfg, params, prompt, 10)
    out2 = generate(cfg, params, prompt, 10)
    assert out1.shape == (1, 13)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :3]), np.asarray(prompt))


@pytest.mark.slow
def test_generate_greedy_matches_naive_loop():
    """Cached greedy decode == argmax over repeated full forwards."""
    model, cfg, params = _model_and_params(seed=3)
    prompt = jnp.asarray([[7, 2, 9, 4]])
    out = generate(cfg, params, prompt, 6)
    ids = prompt
    for _ in range(6):
        logits = model.apply({"params": params}, {"input_ids": ids})
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ids))


def test_generate_sampling_reproducible():
    model, cfg, params = _model_and_params(seed=4)
    prompt = jnp.asarray([[1, 2]])
    r = jax.random.PRNGKey(42)
    a = generate(cfg, params, prompt, 8, 0.8, r, 16)
    b = generate(cfg, params, prompt, 8, 0.8, r, 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_inference_engine_end_to_end():
    model, cfg, params = _model_and_params(seed=5)
    eng = ds.init_inference(model=model,
                            config={"dtype": "float32"},
                            model_parameters=params)
    prompt = np.asarray([[3, 1, 4]])
    out = eng.generate(prompt, max_new_tokens=5)
    assert out.shape == (1, 8)
    logits = eng({"input_ids": jnp.asarray(prompt)})
    assert logits.shape == (1, 3, cfg.vocab_size)


# tier-2 (round 8 budget): the fattest HF-parity leg; per-component torch
# mirrors + test_hf_policies config parity keep gating tier-1
@pytest.mark.slow
def test_hf_gpt2_import_parity():
    """HF GPT2LMHeadModel -> our params: logits match torch within tolerance."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2, n_head=4)
    hf_model = transformers.GPT2LMHeadModel(hf_cfg).eval()

    from deepspeed_tpu.models.hf import load_hf
    from deepspeed_tpu.models.transformer import Transformer
    params, cfg = load_hf(hf_model)
    model = Transformer(cfg.__class__(**{**cfg.__dict__,
                                         "dtype": jnp.float32,
                                         "attention_impl": "reference"}))
    ids = np.random.default_rng(6).integers(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    ours = model.apply({"params": params}, {"input_ids": jnp.asarray(ids)})
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-3, atol=2e-3)


def test_tp2_generate_with_resharded_checkpoint(tmp_path):
    require_devices(2)
    """TP-degree resharding at load (reference: state_dict_factory.py:214):
    a checkpoint written topology-free loads into a tp=2 engine and greedy
    generation matches the tp=1 engine token for token."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.runtime.checkpointing import save_tree

    model, cfg = build_model("gpt2-tiny", dtype=jnp.float32,
                             attention_impl="reference")
    ids = np.random.default_rng(11).integers(0, cfg.vocab_size, (2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.asarray(ids)})["params"]
    path = str(tmp_path / "model_states.npz")
    save_tree(params, path)

    def make(tp):
        return InferenceEngine(
            model=model, model_parameters=params,
            config={"dtype": "float32",
                    "tensor_parallel": {"tp_size": tp}},
            sharding_rules=cfg.tp_rules())

    e1 = make(1).load_checkpoint(path)
    e2 = make(2).load_checkpoint(path)
    # tp=2 weights really are sharded over the model axis
    qkv = e2.params["blocks"]["attn_qkv"]["kernel"]
    assert not qkv.sharding.is_fully_replicated
    t1 = np.asarray(e1.generate(ids, max_new_tokens=8))
    t2 = np.asarray(e2.generate(ids, max_new_tokens=8))
    np.testing.assert_array_equal(t1, t2)


def test_spatial_attention_inference():
    """Spatial (image-model) attention blocks run through the framework's
    attention path + InferenceEngine (reference: diffusers spatial
    injection). Numerics vs a plain softmax attention over the token grid."""
    from deepspeed_tpu.inference.spatial import (SpatialSelfAttention,
                                                 spatial_attention)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 32)), jnp.float32)

    out = spatial_attention(x, num_heads=4, impl="reference")
    # oracle: dense softmax over the 64-token grid
    t = np.asarray(x).reshape(2, 64, 4, 8).transpose(0, 2, 1, 3)
    s = t @ t.transpose(0, 1, 3, 2) / np.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = (p @ t).transpose(0, 2, 1, 3).reshape(2, 8, 8, 32)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)

    # the block hosts in InferenceEngine like any module
    from deepspeed_tpu.inference import InferenceEngine
    block = SpatialSelfAttention(num_heads=4, num_groups=8,
                                 attention_impl="reference")
    params = block.init(jax.random.PRNGKey(0), x)["params"]
    eng = InferenceEngine(model=block, model_parameters=params,
                          config={"dtype": "float32"})
    y = eng.forward(x)
    assert np.asarray(y).shape == (2, 8, 8, 32)
    assert np.all(np.isfinite(np.asarray(y)))


# ----------------------------------------------------------------- int8

def test_int8_quantization_error_bound():
    """Dequantized int8 weights reconstruct within scale/2 elementwise (the
    symmetric per-output-channel bound)."""
    from deepspeed_tpu.inference.engine import quantize_weights_int8
    rng = np.random.default_rng(0)
    params = {"attn": {"kernel": rng.standard_normal((32, 16)).astype(np.float32),
                       "bias": np.zeros(16, np.float32)},
              "gate": {"kernel": rng.standard_normal((32, 4)).astype(np.float32)},
              "ln": {"scale": np.ones(32, np.float32)}}
    q = quantize_weights_int8(params)
    assert q["attn"]["kernel"].dtype == jnp.int8
    deq = np.asarray(q["attn"]["kernel"], np.float32) * np.asarray(q["attn"]["kernel_scale"])
    bound = np.asarray(q["attn"]["kernel_scale"]) / 2 + 1e-7
    assert (np.abs(deq - params["attn"]["kernel"]) <= bound).all()
    # the router and non-kernel leaves are untouched
    assert q["gate"]["kernel"].dtype == np.float32
    assert "kernel_scale" not in q["gate"]
    assert q["ln"]["scale"].dtype == np.float32


# tier-2 (round-17 budget sweep, ~10s): the cheaper tier-1 cousins are
# test_serving.test_int8_weight_only_decode_parity and
# test_serving.test_int8_kv_pool_parity_jnp_and_kernel (the round-17
# blockwise int8 tier, token-exact end to end); tier2.sh runs this leg
@pytest.mark.slow
def test_int8_engine_logits_close_and_generates():
    """dtype:int8 builds a weight-only-quantized engine whose logits track
    the bf16 engine within int8 noise and whose generate() runs end to end
    (round-2 Weak #7: int8 used to silently mean bf16)."""
    model, cfg, params = _model_and_params()
    ids = np.random.default_rng(1).integers(0, 128, (2, 16)).astype(np.int32)

    e_bf = ds.init_inference(model=model, model_parameters=params,
                             config={"dtype": "bf16"})
    e_q = ds.init_inference(model=model, model_parameters=params,
                            config={"dtype": "int8"})
    assert e_q.quantized
    l_bf = np.asarray(e_bf.forward({"input_ids": jnp.asarray(ids)}), np.float32)
    l_q = np.asarray(e_q.forward({"input_ids": jnp.asarray(ids)}), np.float32)
    # int8 weight noise perturbs logits but must keep them close; top-1
    # predictions should overwhelmingly agree
    agree = (l_bf.argmax(-1) == l_q.argmax(-1)).mean()
    assert agree > 0.9, agree
    assert np.abs(l_q - l_bf).mean() < 0.15 * (np.abs(l_bf).mean() + 1.0)

    out = e_q.generate(jnp.asarray(ids), max_new_tokens=4)
    assert out.shape == (2, 20)

    # checkpoint load re-quantizes from full precision
    import tempfile, os
    from deepspeed_tpu.runtime import checkpointing as ckpt_lib
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "m.npz")
        ckpt_lib.save_tree(params, path)
        e_q.load_checkpoint(path)
        l_q2 = np.asarray(e_q.forward({"input_ids": jnp.asarray(ids)}), np.float32)
        np.testing.assert_allclose(l_q2, l_q, rtol=1e-4, atol=1e-4)


def test_int8_engine_rejects_arbitrary_module():
    import flax.linen as nn

    class Plain(nn.Module):
        @nn.compact
        def __call__(self, batch):
            return nn.Dense(4)(batch["x"])

    with pytest.raises(ValueError, match="int8"):
        ds.init_inference(model=Plain(),
                          model_parameters=Plain().init(
                              jax.random.PRNGKey(0),
                              {"x": np.zeros((1, 8), np.float32)})["params"],
                          config={"dtype": "int8"})


# -- serving depth: top-p, repetition penalty, ragged prefill (round-3 #9) ----


def test_top_p_matches_hf_warper():
    """apply_top_p == transformers' TopPLogitsWarper on the same logits."""
    import torch
    from transformers.generation.logits_process import TopPLogitsWarper
    from deepspeed_tpu.models.generation import apply_top_p
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 64)).astype(np.float32) * 3
    for p in (0.3, 0.7, 0.95):
        ours = np.asarray(apply_top_p(jnp.asarray(logits), p))
        hf = TopPLogitsWarper(top_p=p, filter_value=-1e30)(
            None, torch.tensor(logits)).numpy()
        kept_o = ours > -1e29
        kept_h = hf > -1e29
        np.testing.assert_array_equal(kept_o, kept_h)
        np.testing.assert_allclose(np.where(kept_o, ours, 0),
                                   np.where(kept_h, hf, 0), rtol=1e-6)


def test_repetition_penalty_matches_hf_processor():
    """apply_repetition_penalty == HF RepetitionPenaltyLogitsProcessor."""
    import torch
    from transformers.generation.logits_process import (
        RepetitionPenaltyLogitsProcessor)
    from deepspeed_tpu.models.generation import apply_repetition_penalty
    rng = np.random.default_rng(1)
    V = 64
    logits = rng.normal(size=(2, V)).astype(np.float32) * 2
    prompt = rng.integers(0, V, size=(2, 10))
    seen = np.zeros((2, V), bool)
    for b in range(2):
        seen[b, prompt[b]] = True
    ours = np.asarray(apply_repetition_penalty(
        jnp.asarray(logits), jnp.asarray(seen), 1.3))
    hf = RepetitionPenaltyLogitsProcessor(penalty=1.3)(
        torch.tensor(prompt), torch.tensor(logits)).numpy()
    np.testing.assert_allclose(ours, hf, rtol=1e-6)


# tier-2 (round 8 budget): test_generate_sampling_reproducible is the
# cheaper tier-1 cousin; the top-p/penalty unit math keeps its HF-parity
# pins above (test_top_p_matches_hf_warper / repetition_penalty)
@pytest.mark.slow
def test_generate_with_top_p_and_penalty_reproducible():
    model, cfg, params = _model_and_params(seed=3)
    prompt = jnp.asarray(np.random.default_rng(2).integers(0, 128, (2, 8)))
    r = jax.random.PRNGKey(5)
    a = generate(cfg, params, prompt, 8, 0.9, r, 40, 0.9, 1.2)
    b = generate(cfg, params, prompt, 8, 0.9, r, 40, 0.9, 1.2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # penalty visibly discourages repeats vs no penalty under greedy
    g_plain = generate(cfg, params, prompt, 12)
    g_pen = generate(cfg, params, prompt, 12, 0.0, None, None, None, 4.0)
    assert not np.array_equal(np.asarray(g_plain), np.asarray(g_pen))


@pytest.mark.slow
def test_ragged_batched_prefill_matches_per_sample():
    """LEFT-padded ragged batch: each sample's greedy continuation equals
    its own unpadded single-sample generation (positions and masks are
    pad-corrected per sample)."""
    for pos_embed in ("learned", "rotary"):
        model, cfg, params = _model_and_params(seed=4, pos_embed=pos_embed)
        rng = np.random.default_rng(3)
        lens = [5, 8, 3, 8]
        T = max(lens)
        prompts = [rng.integers(1, 128, size=(L,)) for L in lens]
        ids = np.zeros((len(lens), T), np.int64)
        mask = np.zeros((len(lens), T), np.int64)
        for i, p in enumerate(prompts):
            ids[i, T - len(p):] = p          # left-padded
            mask[i, T - len(p):] = 1
        out = generate(cfg, params, jnp.asarray(ids), 6,
                       attention_mask=jnp.asarray(mask))
        new = np.asarray(out)[:, T:]
        for i, p in enumerate(prompts):
            solo = generate(cfg, params, jnp.asarray(p)[None], 6)
            np.testing.assert_array_equal(
                new[i], np.asarray(solo)[0, len(p):],
                err_msg=f"sample {i} (len {len(p)}, {pos_embed})")


def test_ragged_generate_matches_hf():
    """End-to-end parity with HF's left-padded batched greedy generate with
    repetition penalty, on a real (randomly initialized) HF architecture
    loaded through the policy mapper."""
    import torch
    import transformers
    from deepspeed_tpu.models.hf import load_hf_gpt2

    torch.manual_seed(0)
    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    params, cfg = load_hf_gpt2(hf)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.float32})
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)

    rng = np.random.default_rng(4)
    lens = [4, 7, 7, 3]
    T = max(lens)
    prompts = [rng.integers(1, 128, size=(L,)) for L in lens]
    ids = np.zeros((len(lens), T), np.int64)
    mask = np.zeros((len(lens), T), np.int64)
    for i, p in enumerate(prompts):
        ids[i, T - len(p):] = p
        mask[i, T - len(p):] = 1

    with torch.no_grad():
        hf_out = hf.generate(
            torch.tensor(ids), attention_mask=torch.tensor(mask),
            max_new_tokens=6, do_sample=False, repetition_penalty=1.3,
            pad_token_id=0)
    ours = generate(cfg, params, jnp.asarray(ids), 6,
                    repetition_penalty=1.3,
                    attention_mask=jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(ours)[:, T:],
                                  hf_out.numpy()[:, T:])


# -- diffusers-grade spatial path (round-3 Missing #4) ------------------------


def test_resnet_block_matches_torch_mirror():
    """ResnetBlock == a torch mirror of diffusers' ResnetBlock2D ops
    (GroupNorm/SiLU/Conv3x3 + time-emb injection + shortcut)."""
    import torch
    import torch.nn as tnn
    from deepspeed_tpu.inference.spatial import (ResnetBlock,
                                                 load_torch_conv,
                                                 load_torch_linear)

    torch.manual_seed(0)
    Cin, Cout, G = 8, 16, 4

    class TorchRes(tnn.Module):
        def __init__(self):
            super().__init__()
            self.norm1 = tnn.GroupNorm(G, Cin)
            self.conv1 = tnn.Conv2d(Cin, Cout, 3, padding=1)
            self.time_emb_proj = tnn.Linear(12, Cout)
            self.norm2 = tnn.GroupNorm(G, Cout)
            self.conv2 = tnn.Conv2d(Cout, Cout, 3, padding=1)
            self.shortcut = tnn.Conv2d(Cin, Cout, 1)

        def forward(self, x, temb):
            h = self.conv1(tnn.functional.silu(self.norm1(x)))
            h = h + self.time_emb_proj(
                tnn.functional.silu(temb))[:, :, None, None]
            h = self.conv2(tnn.functional.silu(self.norm2(h)))
            return self.shortcut(x) + h

    tm = TorchRes().eval()
    x = torch.randn(2, Cin, 8, 8)
    temb = torch.randn(2, 12)
    with torch.no_grad():
        ref = tm(x, temb).permute(0, 2, 3, 1).numpy()

    params = {
        "norm1": {"scale": jnp.asarray(tm.norm1.weight.detach().numpy()),
                  "bias": jnp.asarray(tm.norm1.bias.detach().numpy())},
        "conv1": load_torch_conv(tm.conv1.weight.detach(),
                                 tm.conv1.bias.detach()),
        "time_emb_proj": load_torch_linear(
            tm.time_emb_proj.weight.detach(),
            tm.time_emb_proj.bias.detach()),
        "norm2": {"scale": jnp.asarray(tm.norm2.weight.detach().numpy()),
                  "bias": jnp.asarray(tm.norm2.bias.detach().numpy())},
        "conv2": load_torch_conv(tm.conv2.weight.detach(),
                                 tm.conv2.bias.detach()),
        "conv_shortcut": load_torch_conv(tm.shortcut.weight.detach(),
                                         tm.shortcut.bias.detach()),
    }
    blk = ResnetBlock(Cout, num_groups=G)
    ours = blk.apply({"params": params},
                     jnp.asarray(x.permute(0, 2, 3, 1).numpy()),
                     jnp.asarray(temb.numpy()))
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-5)


def test_transformer_block_matches_torch_mirror():
    """TransformerBlock (self-attn + cross-attn + geglu FF) == a torch
    mirror of diffusers' BasicTransformerBlock."""
    import torch
    import torch.nn as tnn
    from deepspeed_tpu.inference.spatial import (TransformerBlock,
                                                 load_torch_linear)

    torch.manual_seed(1)
    C, H, Tq, Tc, Cc = 16, 2, 12, 5, 16

    class TorchAttn(tnn.Module):
        def __init__(self, kdim):
            super().__init__()
            self.to_q = tnn.Linear(C, C, bias=False)
            self.to_k = tnn.Linear(kdim, C, bias=False)
            self.to_v = tnn.Linear(kdim, C, bias=False)
            self.to_out = tnn.Linear(C, C)

        def forward(self, x, ctx=None):
            ctx = x if ctx is None else ctx
            B, T, _ = x.shape
            hd = C // H
            sh = lambda t: t.reshape(B, -1, H, hd).transpose(1, 2)
            q, k, v = sh(self.to_q(x)), sh(self.to_k(ctx)), sh(self.to_v(ctx))
            o = tnn.functional.scaled_dot_product_attention(q, k, v)
            return self.to_out(o.transpose(1, 2).reshape(B, T, C))

    class TorchBlock(tnn.Module):
        def __init__(self):
            super().__init__()
            self.norm1, self.norm2, self.norm3 = (tnn.LayerNorm(C)
                                                  for _ in range(3))
            self.attn1 = TorchAttn(C)
            self.attn2 = TorchAttn(Cc)
            self.geglu = tnn.Linear(C, 8 * C)
            self.ff_out = tnn.Linear(4 * C, C)

        def forward(self, x, ctx):
            x = x + self.attn1(self.norm1(x))
            x = x + self.attn2(self.norm2(x), ctx)
            h = self.geglu(self.norm3(x))
            a, g = h.chunk(2, dim=-1)
            return x + self.ff_out(a * tnn.functional.gelu(g))

    tm = TorchBlock().eval()
    x = torch.randn(2, Tq, C)
    ctx = torch.randn(2, Tc, Cc)
    with torch.no_grad():
        ref = tm(x, ctx).numpy()

    def attn_params(ta):
        return {"to_q": load_torch_linear(ta.to_q.weight.detach()),
                "to_k": load_torch_linear(ta.to_k.weight.detach()),
                "to_v": load_torch_linear(ta.to_v.weight.detach()),
                "to_out": load_torch_linear(ta.to_out.weight.detach(),
                                            ta.to_out.bias.detach())}

    ln = lambda m: {"scale": jnp.asarray(m.weight.detach().numpy()),
                    "bias": jnp.asarray(m.bias.detach().numpy())}
    params = {
        "norm1": ln(tm.norm1), "norm2": ln(tm.norm2), "norm3": ln(tm.norm3),
        "attn1": attn_params(tm.attn1), "attn2": attn_params(tm.attn2),
        "ff_geglu": {"proj": load_torch_linear(tm.geglu.weight.detach(),
                                               tm.geglu.bias.detach())},
        "ff_out": load_torch_linear(tm.ff_out.weight.detach(),
                                    tm.ff_out.bias.detach()),
    }
    blk = TransformerBlock(H, attention_impl="reference")
    ours = blk.apply({"params": params}, jnp.asarray(x.numpy()),
                     jnp.asarray(ctx.numpy()))
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_unet_serves_through_inference_engine():
    """The assembled conditional UNet hosts in InferenceEngine like any
    module (the reference's generic_injection capability slot) and is
    jit-stable end to end."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.spatial import UNet2DCondition
    unet = UNet2DCondition(block_channels=(16, 32), num_heads=2,
                           out_channels=4, attention_impl="reference")
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 16, 16, 4)), jnp.float32)
    t = jnp.asarray([1.0, 17.0])
    ctx = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, 6, 16)), jnp.float32)
    params = unet.init(jax.random.PRNGKey(0), x, t, ctx)["params"]
    eng = InferenceEngine(model=unet, model_parameters=params,
                          config={"dtype": "float32"})
    y1 = eng.forward(x, t, ctx)
    y2 = eng.forward(x, t, ctx)
    assert np.asarray(y1).shape == (2, 16, 16, 4)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert np.all(np.isfinite(np.asarray(y1)))


def test_timestep_embedding_matches_torch_mirror():
    import torch
    from deepspeed_tpu.inference.spatial import timestep_embedding
    t = np.asarray([0.0, 1.0, 999.0], np.float32)
    dim = 32
    half = dim // 2
    freqs = torch.exp(-torch.log(torch.tensor(10000.0)) *
                      torch.arange(half) / half)
    ang = torch.tensor(t)[:, None] * freqs[None]
    ref = torch.cat([torch.cos(ang), torch.sin(ang)], dim=-1).numpy()
    ours = np.asarray(timestep_embedding(jnp.asarray(t), dim))
    np.testing.assert_allclose(ours, ref, rtol=1e-5, atol=1e-6)


# tier-2 (round 8 budget; round-17 re-homed the gating cousins to
# test_serving.test_int8_kv_pool_parity_jnp_and_kernel +
# test_int8_weight_only_decode_parity, which keep the int8 tier tier-1)
@pytest.mark.slow
def test_int8_kv_cache_parity_and_capacity():
    """kv_cache_dtype='int8': greedy generations match the bf16-cache path
    (int8 KV error is far below greedy decision margins on a trained-free
    random model), prefill logits stay close, and the cache's k/v HBM bytes
    halve (+small scale overhead) — 2x context/batch capacity."""
    model, cfg, params = _model_and_params(seed=6)
    ids = jnp.asarray(np.random.default_rng(7).integers(0, 128, (2, 12)))

    # prefill logits tolerance through the quantized cache
    cache16 = init_cache(cfg, 2, 32, jnp.float32)
    cache8 = init_cache(cfg, 2, 32, jnp.int8)
    l16, _ = forward_with_cache(cfg, params, ids, cache16)
    l8, c8 = forward_with_cache(cfg, params, ids, cache8)
    assert c8["k"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(l8), np.asarray(l16),
                               rtol=0.1, atol=0.05)

    # greedy decode parity end to end
    g16 = generate(cfg, params, ids, 8)
    g8 = generate(cfg, params, ids, 8, kv_cache_dtype="int8")
    np.testing.assert_array_equal(np.asarray(g16), np.asarray(g8))

    # capacity: int8 k/v bytes = half the f32... compare against the
    # compute-dtype cache the same config would build
    bytes16 = cache16["k"].nbytes + cache16["v"].nbytes
    bytes8 = (cache8["k"].nbytes + cache8["v"].nbytes
              + cache8["k_scale"].nbytes + cache8["v_scale"].nbytes)
    assert bytes8 < 0.32 * bytes16, (bytes8, bytes16)   # f32 ref: ~0.28x


def test_generate_rejects_right_padded_mask():
    """The left-pad guard lives in models.generation.generate itself (the
    shared entry point), not only in the InferenceEngine wrapper — a direct
    caller with an HF-default right-padded mask must fail loudly, not
    silently decode garbage."""
    model, cfg, params = _model_and_params(seed=5)
    rng = np.random.default_rng(6)
    ids = np.zeros((2, 8), np.int64)
    mask = np.zeros((2, 8), np.int64)
    ids[0], mask[0] = rng.integers(1, 128, size=8), 1
    ids[1, :5] = rng.integers(1, 128, size=5)
    mask[1, :5] = 1                              # right-padded (HF default)
    with pytest.raises(ValueError, match="LEFT-padded"):
        generate(cfg, params, jnp.asarray(ids), 4,
                 attention_mask=jnp.asarray(mask))
    # bool masks must hit the same guard (np.diff on bool is XOR — a raw
    # diff check would wave a bool right-padded mask through)
    with pytest.raises(ValueError, match="LEFT-padded"):
        generate(cfg, params, jnp.asarray(ids), 4,
                 attention_mask=jnp.asarray(mask.astype(bool)))
    # an all-ones mask is accepted and equals the maskless call
    ids2 = rng.integers(1, 128, size=(2, 8))
    a = generate(cfg, params, jnp.asarray(ids2), 4,
                 attention_mask=jnp.ones((2, 8), np.int64))
    b = generate(cfg, params, jnp.asarray(ids2), 4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# tier-2 (round 8 budget): test_tp2_generate_with_resharded_checkpoint
# keeps TP2 generate gating tier-1
@pytest.mark.slow
def test_llama_tp2_generate_matches_tp1():
    """GQA + SwiGLU + RMSNorm under tensor parallelism: a Llama-family
    model's greedy generation on a tp=2 mesh matches tp=1 token for token
    (the GQA qkv concat reshards correctly under the model-axis rules)."""
    require_devices(2)
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models import build_model

    model, cfg = build_model(
        "gpt2-tiny", hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, norm="rmsnorm", gated_mlp=True, activation="silu",
        pos_embed="rotary", rotary_interleaved=False, use_bias=False,
        tie_embeddings=False, mlp_dim_override=96, vocab_size=128,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference")
    ids = np.random.default_rng(12).integers(0, 128, (2, 8))
    params = model.init(jax.random.PRNGKey(1),
                        {"input_ids": jnp.asarray(ids)})["params"]

    def make(tp):
        return InferenceEngine(
            model=model, model_parameters=params,
            config={"dtype": "float32",
                    "tensor_parallel": {"tp_size": tp}},
            sharding_rules=cfg.tp_rules())

    t1 = np.asarray(make(1).generate(ids, max_new_tokens=8))
    t2 = np.asarray(make(2).generate(ids, max_new_tokens=8))
    np.testing.assert_array_equal(t1, t2)
