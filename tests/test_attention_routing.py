"""Routing tests: each attention regime must reach the Pallas flash path.

The round-5 verdict's top gap was real-model regimes (padding masks, alibi,
softcap, sliding windows) silently reroutes to the O(S²) jnp path. These
tests pin the dispatch: a spy on the flash kernel entry asserts the kernel
is invoked (CPU-interpreted Pallas — the same kernel runs compiled on TPU),
and parity against the reference impl pins the numerics. Plus the engine
wiring of the previously parsed-but-dead ``sparse_attention`` and
``sequence_parallel.mode`` config sections.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _precise_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


import deepspeed_tpu.ops.pallas.flash_attention as flash_mod
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.transformer import alibi_slopes
from deepspeed_tpu.ops.attention import (alibi_bias_from_slopes, attention,
                                         mha_reference)


@pytest.fixture
def flash_spy(monkeypatch):
    """Spy on the flash kernel entry; forces interpret mode so the REAL
    Pallas kernel runs (interpreted) on CPU. calls[] records the kwargs of
    every flash_attention invocation; kernel_calls[] records invocations
    that reached the pallas_call path (not the internal dense fallback)."""
    calls = []
    kernel_calls = []
    real_fa = flash_mod.flash_attention
    real_flash = flash_mod._flash

    def spy_fa(q, k, v, **kw):
        kw["interpret"] = True
        calls.append(kw)
        return real_fa(q, k, v, **kw)

    def spy_flash(*args):
        kernel_calls.append(args)
        return real_flash(*args)

    monkeypatch.setattr(flash_mod, "flash_attention", spy_fa)
    monkeypatch.setattr(flash_mod, "_flash", spy_flash)
    spy_fa.calls = calls
    spy_fa.kernel_calls = kernel_calls
    return spy_fa


def qkv(rng, shape):
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for _ in range(3))


# ---------------------------------------------------------------------------
# attention() entry-point routing
# ---------------------------------------------------------------------------

def test_padding_mask_routes_to_kernel(flash_spy):
    rng = np.random.default_rng(0)
    q, k, v = qkv(rng, (2, 2, 128, 32))
    mask = jnp.asarray(np.arange(128)[None, :] < 70)[None, None]
    mask = jnp.broadcast_to(mask, (2, 1, 1, 128))
    out = attention(q, k, v, causal=False, mask=mask, impl="flash")
    assert len(flash_spy.kernel_calls) == 1, "mask did not reach the kernel"
    ref = mha_reference(q, k, v, causal=False, mask=mask)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_alibi_slopes_route_to_kernel(flash_spy):
    rng = np.random.default_rng(1)
    q, k, v = qkv(rng, (1, 4, 128, 32))
    sl = alibi_slopes(4)
    out = attention(q, k, v, causal=True, alibi_slopes=sl, impl="flash")
    assert len(flash_spy.kernel_calls) == 1, "alibi did not reach the kernel"
    ref = mha_reference(q, k, v, causal=True,
                        bias=alibi_bias_from_slopes(sl, 128, 128))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_sliding_window_routes_to_kernel(flash_spy):
    rng = np.random.default_rng(2)
    q, k, v = qkv(rng, (1, 2, 128, 32))
    out = attention(q, k, v, causal=True, window=48, impl="flash")
    assert len(flash_spy.kernel_calls) == 1, "window did not reach the kernel"
    qp, kp = np.arange(128)[:, None], np.arange(128)[None, :]
    ref = mha_reference(q, k, v, causal=True,
                        mask=jnp.asarray(qp - kp < 48)[None, None])
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_softcap_routes_to_kernel(flash_spy):
    rng = np.random.default_rng(3)
    q, k, v = qkv(rng, (1, 2, 128, 32))
    out = attention(q, k, v, causal=True, softcap=30.0, impl="flash")
    assert len(flash_spy.kernel_calls) == 1, "softcap did not reach the kernel"
    ref = mha_reference(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_dropout_stays_on_reference(flash_spy):
    """Attention dropout is the documented fallback: no kernel call."""
    rng = np.random.default_rng(4)
    q, k, v = qkv(rng, (1, 2, 128, 32))
    attention(q, k, v, causal=True, dropout_rate=0.1,
              dropout_rng=jax.random.PRNGKey(0), impl="flash")
    assert not flash_spy.kernel_calls


@pytest.mark.parametrize("batch,split", [(4, True), (3, False)],
                         ids=["split", "batch_stays_whole"])
def test_flash_on_a_mesh_runs_per_shard_and_says_what_repeats(
        flash_spy, monkeypatch, batch, split):
    """Operands laid out on a multi-device mesh: the dispatch layer runs the
    kernel per shard under shard_map (batch over the batch axes, heads over
    the TP axis) and the result is exact. A batch the axes do not divide
    stays whole — every chip along them repeats the work — and on a TPU
    that is said once with the reason, never silently."""
    import deepspeed_tpu.utils.logging as ds_logging
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import BATCH_AXES, MESH_AXES, TP_AXIS
    said = []
    monkeypatch.setattr(ds_logging, "warning_once", said.append)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 1, 1, 2),
                MESH_AXES)
    rng = np.random.default_rng(5)
    q, k, v = qkv(rng, (batch, 4, 128, 32))
    ref = mha_reference(q, k, v, causal=True)
    sh = NamedSharding(mesh, P(BATCH_AXES if split else None, TP_AXIS))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    fn = lambda q, k, v: attention(q, k, v, causal=True, impl="flash")
    assert "shard_map" in str(jax.make_jaxpr(fn)(qs, ks, vs))
    out = jax.jit(fn)(qs, ks, vs)
    assert flash_spy.kernel_calls, "the kernel was not reached on the mesh"
    # per-shard shapes at the kernel entry: heads always split over "model"
    assert flash_spy.kernel_calls[-1][0].shape == (
        batch // 2 if split else batch, 2, 128, 32)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
    if split:
        assert not said, said
    else:
        assert len(set(said)) == 1 and "batch 3 is not divisible by 2" in \
            said[0] and "'data'" in said[0], said


# ---------------------------------------------------------------------------
# model-level routing: the HF-zoo regimes ride the kernel through Block
# ---------------------------------------------------------------------------

def _forward(model, params, batch):
    return model.apply({"params": params}, batch)


def _parity_vs_reference(cfg_kw, batch, flash_spy, seed=0):
    """Build the same arch twice (flash vs reference impl), share params,
    assert the flash forward used the kernel and matches the reference."""
    m_flash, _ = build_model("gpt2-tiny", attention_impl="flash",
                             dtype=jnp.float32, **cfg_kw)
    m_ref, _ = build_model("gpt2-tiny", attention_impl="reference",
                           dtype=jnp.float32, **cfg_kw)
    params = m_ref.init(jax.random.PRNGKey(seed), batch)["params"]
    out_ref = _forward(m_ref, params, batch)
    n_before = len(flash_spy.kernel_calls)
    out_flash = _forward(m_flash, params, batch)
    assert len(flash_spy.kernel_calls) > n_before, \
        "model forward did not dispatch to the Pallas kernel"
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               rtol=5e-4, atol=5e-4)
    return flash_spy.calls[-1]


# tier-2 (round-19 budget sweep, ~5s): the cheaper tier-1 cousins are
# test_padding_mask_routes_to_kernel (the routing verdict itself) and
# test_softcap_gemma2_rides_kernel /
# test_uniform_window_mistral_rides_kernel_under_scan (same
# model-level ride, other features); scripts/tier2.sh runs this
@pytest.mark.slow
def test_masked_bert_rides_kernel(flash_spy):
    """BERT with real padding — the verdict's headline example."""
    rng = np.random.default_rng(10)
    ids = rng.integers(0, 512, size=(2, 64))
    lens = np.array([40, 64])
    batch = {"input_ids": jnp.asarray(ids),
             "attention_mask": jnp.asarray(
                 np.arange(64)[None, :] < lens[:, None])}
    kw = _parity_vs_reference(
        dict(causal=False, vocab_size=512, max_seq_len=64, hidden_size=64,
             num_layers=2, num_heads=2), batch, flash_spy)
    assert kw["mask"] is not None


# tier-2 (round-19 budget sweep, ~7s): the cheaper tier-1 cousins are
# test_alibi_slopes_route_to_kernel (the routing verdict) and
# test_hf_policies.test_bloom_decode_parity (alibi model math);
# scripts/tier2.sh runs this model-level ride
@pytest.mark.slow
def test_alibi_bloom_rides_kernel(flash_spy):
    """BLOOM-style alibi positions ride as slopes (no [B,H,S,S] bias)."""
    rng = np.random.default_rng(11)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 512, size=(2, 64)))}
    kw = _parity_vs_reference(
        dict(vocab_size=512, max_seq_len=64, hidden_size=64, num_layers=2,
             num_heads=2, pos_embed="alibi", embed_ln=True), batch, flash_spy)
    assert kw["alibi_slopes"] is not None


def test_softcap_gemma2_rides_kernel(flash_spy):
    """Gemma-2-class attn softcap runs in-kernel."""
    rng = np.random.default_rng(12)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 512, size=(1, 64)))}
    kw = _parity_vs_reference(
        dict(vocab_size=512, max_seq_len=64, hidden_size=64, num_layers=2,
             num_heads=2, attn_softcap=50.0, final_logit_softcap=30.0),
        batch, flash_spy)
    assert kw["softcap"] == 50.0


def test_uniform_window_mistral_rides_kernel_under_scan(flash_spy):
    """Mistral-class UNIFORM layer windows stay a static int through the
    scanned-layers path, so attention() gets a kernel-routable window."""
    rng = np.random.default_rng(13)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 512, size=(1, 64)))}
    kw = _parity_vs_reference(
        dict(vocab_size=512, max_seq_len=64, hidden_size=64, num_layers=2,
             num_heads=2, layer_windows=(32, 32), scan_layers=True),
        batch, flash_spy)
    assert kw["window"] == 32


@pytest.mark.slow
def test_masked_bert_trains_through_kernel(flash_spy):
    """fwd+bwd: grads of a masked encoder step flow through the kernel's
    custom VJP and match the reference-impl grads."""
    from deepspeed_tpu.models.transformer import masked_lm_loss
    rng = np.random.default_rng(14)
    ids = rng.integers(0, 256, size=(2, 32))
    batch = {"input_ids": jnp.asarray(ids),
             "attention_mask": jnp.asarray(
                 np.arange(32)[None, :] < np.array([20, 32])[:, None]),
             "labels": jnp.asarray(ids)}
    kw = dict(causal=False, vocab_size=256, max_seq_len=32, hidden_size=32,
              num_layers=2, num_heads=2)
    m_flash, _ = build_model("gpt2-tiny", attention_impl="flash",
                             dtype=jnp.float32, **kw)
    m_ref, _ = build_model("gpt2-tiny", attention_impl="reference",
                           dtype=jnp.float32, **kw)
    params = m_ref.init(jax.random.PRNGKey(0), batch)["params"]

    def loss(model, p):
        return masked_lm_loss(model.apply({"params": p}, batch), batch)

    g_ref = jax.grad(functools.partial(loss, m_ref))(params)
    n_before = len(flash_spy.kernel_calls)
    g_flash = jax.grad(functools.partial(loss, m_flash))(params)
    assert len(flash_spy.kernel_calls) > n_before
    for (path_f, leaf_f), (_, leaf_r) in zip(
            jax.tree_util.tree_leaves_with_path(g_flash),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        np.testing.assert_allclose(np.asarray(leaf_f), np.asarray(leaf_r),
                                   rtol=1e-3, atol=1e-3,
                                   err_msg=str(path_f))


@pytest.mark.slow
def test_prefill_rides_flash_kernel(flash_spy):
    """Generation prefill (empty cache) runs the flash kernel and matches
    the jnp cache path token-for-token."""
    from deepspeed_tpu.models.generation import forward_with_cache, init_cache
    rng = np.random.default_rng(15)
    model, cfg = build_model("gpt2-tiny", vocab_size=256, max_seq_len=64,
                             hidden_size=64, num_layers=2, num_heads=2,
                             dtype=jnp.float32, attn_softcap=30.0)
    ids = jnp.asarray(rng.integers(0, 256, size=(2, 16)))
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    cache = init_cache(cfg, 2, 64, dtype=jnp.float32)
    logits_jnp, _ = forward_with_cache(cfg, params, ids, cache)
    assert not flash_spy.kernel_calls
    cache = init_cache(cfg, 2, 64, dtype=jnp.float32)
    logits_flash, cache2 = forward_with_cache(cfg, params, ids, cache,
                                              prefill_flash="interpret")
    assert flash_spy.kernel_calls, "prefill did not use the flash kernel"
    np.testing.assert_allclose(np.asarray(logits_flash),
                               np.asarray(logits_jnp), rtol=2e-4, atol=2e-4)
    # the cache written during the flash prefill must decode identically
    tok = jnp.argmax(logits_flash[:, -1:], axis=-1)
    l1, _ = forward_with_cache(cfg, params, tok, cache2)
    cache3 = init_cache(cfg, 2, 64, dtype=jnp.float32)
    _, cache_jnp = forward_with_cache(cfg, params, ids, cache3)
    l2, _ = forward_with_cache(cfg, params, tok, cache_jnp)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# engine wiring: sparse_attention + sequence_parallel.mode config sections
# ---------------------------------------------------------------------------

from deepspeed_tpu.config import load_config
from deepspeed_tpu.runtime.engine import wire_attention_config


def _tiny_model(**kw):
    model, _ = build_model("gpt2-tiny", vocab_size=128, max_seq_len=32,
                           hidden_size=32, num_layers=2, num_heads=2,
                           dtype=jnp.float32, **kw)
    return model


def test_sparse_attention_config_wires_attention_impl():
    model = _tiny_model()
    cfg = load_config({"sparse_attention": {"mode": "fixed", "block": 16,
                                            "num_local_blocks": 2}})
    wired = wire_attention_config(model, cfg)
    assert wired.cfg.attention_impl == "sparse"
    items = dict(wired.cfg.sparse_attention)
    assert items["mode"] == "fixed" and items["num_local_blocks"] == 2
    # config is hashable (jit-static requirement)
    hash(wired.cfg)


def test_sparse_attention_unknown_mode_raises():
    with pytest.raises(ValueError, match="unknown sparse attention mode"):
        wire_attention_config(
            _tiny_model(), load_config({"sparse_attention":
                                        {"mode": "banded"}}))


def test_sparse_attention_requires_in_tree_model():
    with pytest.raises(ValueError, match="in-tree"):
        wire_attention_config(
            object(), load_config({"sparse_attention": {"mode": "fixed"}}))


def test_sparse_attention_conflicting_impl_raises():
    with pytest.raises(ValueError, match="conflicts"):
        wire_attention_config(
            _tiny_model(attention_impl="flash"),
            load_config({"sparse_attention": {"mode": "fixed"}}))


def test_sequence_parallel_mode_selects_impl():
    cfg = load_config({"sequence_parallel": {"sp_size": 2,
                                             "mode": "ulysses"}})
    wired = wire_attention_config(_tiny_model(), cfg)
    assert wired.cfg.attention_impl == "ulysses"
    # hand-set matching impl is left alone
    wired = wire_attention_config(_tiny_model(attention_impl="ulysses"), cfg)
    assert wired.cfg.attention_impl == "ulysses"


def test_sequence_parallel_unknown_mode_raises():
    with pytest.raises(ValueError, match="sequence_parallel.mode"):
        wire_attention_config(
            _tiny_model(), load_config({"sequence_parallel":
                                        {"sp_size": 2, "mode": "zigzag"}}))


def test_sequence_parallel_conflicting_impl_raises():
    with pytest.raises(ValueError, match="conflicts"):
        wire_attention_config(
            _tiny_model(attention_impl="ring"),
            load_config({"sequence_parallel": {"sp_size": 2,
                                               "mode": "ulysses"}}))


# tier-2 (round 10 budget): fattest passing legs demoted per the standing
# guardrail — tier-1 crept past ~80% of the 870s budget once the comm-plan
# legs landed; cheaper cousins still gate tier-1
@pytest.mark.slow
def test_sparse_model_forward_matches_layout_mask():
    """attention_impl='sparse' (as the engine wires it): 'dense' mode must
    equal the plain reference exactly, and a genuinely-masking fixed layout
    must change the logits (the section is consumed, not decorative)."""
    from deepspeed_tpu.ops.sparse_attention import (FixedSparsityConfig,
                                                    layout_to_dense_mask)
    rng = np.random.default_rng(20)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 128, size=(2, 32)))}
    sa_items = (("block", 4), ("mode", "fixed"), ("num_local_blocks", 2),
                ("num_global_blocks", 1), ("attention", "unidirectional"))
    m_sparse = _tiny_model(attention_impl="sparse", sparse_attention=sa_items)
    m_ref = _tiny_model(attention_impl="reference")
    params = m_ref.init(jax.random.PRNGKey(1), batch)["params"]
    out_sparse = m_sparse.apply({"params": params}, batch)
    out_ref = m_ref.apply({"params": params}, batch)
    # the layout must mask real causal pairs, or the comparison is vacuous
    sp = FixedSparsityConfig(num_heads=2, block=4, num_local_blocks=2,
                             num_global_blocks=1, attention="unidirectional")
    lmask = np.asarray(layout_to_dense_mask(sp.make_layout(32), 4))
    causal = np.tril(np.ones((32, 32), bool))
    assert (lmask[0] & causal).sum() < causal.sum(), "layout masks nothing"
    assert not np.allclose(np.asarray(out_sparse), np.asarray(out_ref),
                           atol=1e-3)
    # dense mode == plain reference bit-for-bit
    m_dense = _tiny_model(attention_impl="sparse",
                          sparse_attention=(("mode", "dense"), ("block", 16)))
    out_dense = m_dense.apply({"params": params}, batch)
    np.testing.assert_allclose(np.asarray(out_dense), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)


def test_sparse_model_unknown_mode_raises_at_forward():
    rng = np.random.default_rng(21)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 128, size=(1, 32)))}
    model = _tiny_model(attention_impl="sparse",
                        sparse_attention=(("mode", "banded"),))
    with pytest.raises(ValueError, match="unknown sparse attention mode"):
        model.init(jax.random.PRNGKey(0), batch)


@pytest.mark.slow
def test_engine_initializes_with_sparse_attention():
    """End-to-end: ds.initialize consumes the sparse_attention section —
    the knob is no longer parsed-but-dead."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.transformer import causal_lm_loss
    rng = np.random.default_rng(22)
    model = _tiny_model()
    mk = lambda: {"input_ids": rng.integers(0, 128, size=(8, 32))}
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "sparse_attention": {"mode": "fixed", "block": 16,
                                     "num_local_blocks": 2}},
        loss_fn=causal_lm_loss, example_batch=mk())
    assert engine.module.cfg.attention_impl == "sparse"
    assert float(engine.train_batch(mk())["loss"]) > 0.0


# ---------------------------------------------------------------------------
# pipelined engine: final_logit_softcap is applied (not silently dropped)
# ---------------------------------------------------------------------------

# tier-2 (round-19 budget sweep, ~5s): the cheaper tier-1 cousins are
# test_softcap_routes_to_kernel and test_softcap_gemma2_rides_kernel
# (the softcap feature itself); scripts/tier2.sh runs this
# pipelined-head plumbing pin
@pytest.mark.slow
def test_pipelined_head_applies_final_logit_softcap():
    from deepspeed_tpu.models.pipeline import PipelinedTransformer
    from deepspeed_tpu.models.transformer import (Transformer,
                                                  TransformerConfig)
    cfg = TransformerConfig(vocab_size=128, max_seq_len=32, hidden_size=32,
                            num_layers=2, num_heads=2, dtype=jnp.float32,
                            final_logit_softcap=5.0, scan_layers=True)
    rng = np.random.default_rng(30)
    batch = {"input_ids": jnp.asarray(rng.integers(0, 128, size=(2, 32)))}
    ref_model = Transformer(cfg)
    params = ref_model.init(jax.random.PRNGKey(0), batch)["params"]
    ref_logits = ref_model.apply({"params": params}, batch)
    assert float(jnp.max(jnp.abs(ref_logits))) <= 5.0
    pipe = PipelinedTransformer(cfg, pp=1, n_micro=1)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1,),
                             ("pipe",))
    pipe_logits = pipe.apply({"params": params}, batch, mesh=mesh)
    assert float(jnp.max(jnp.abs(pipe_logits))) <= 5.0
    np.testing.assert_allclose(np.asarray(pipe_logits),
                               np.asarray(ref_logits), rtol=1e-4, atol=1e-4)
