"""Flagship transformer: shapes, loss descent through the engine, TP rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import (Transformer, build_model, causal_lm_loss,
                                  get_config)


def tiny_batch(rng, cfg, batch=8, seq=32):
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq))
    return {"input_ids": ids}


def test_forward_shapes():
    model, cfg = build_model("gpt2-tiny", attention_impl="reference")
    batch = tiny_batch(np.random.default_rng(0), cfg)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    logits = model.apply({"params": params}, batch)
    assert logits.shape == (8, 32, cfg.vocab_size)
    assert logits.dtype == jnp.float32


# tier-2 (round-19 budget sweep, ~8s): the scanned path gates tier-1
# end-to-end (test_engine_trains_transformer[0],
# test_fused_loss_encoder_no_shift); this pin of the unrolled-loop
# twin runs in scripts/tier2.sh
@pytest.mark.slow
def test_scan_and_loop_agree():
    """nn.scan over layers must match the unrolled loop numerically."""
    kw = dict(hidden_size=64, num_layers=3, num_heads=4, vocab_size=128,
              max_seq_len=64, dtype=jnp.float32, attention_impl="reference")
    m_scan, cfg = build_model("gpt2-tiny", scan_layers=True, **kw)
    m_loop, _ = build_model("gpt2-tiny", scan_layers=False, **kw)
    batch = tiny_batch(np.random.default_rng(1), cfg, batch=2, seq=16)
    p_scan = m_scan.init(jax.random.PRNGKey(7), batch)["params"]
    # map scanned params [L, ...] -> per-layer dicts for the loop model
    p_loop = {k: v for k, v in p_scan.items() if k != "blocks"}
    for i in range(cfg.num_layers):
        p_loop[f"blocks_{i}"] = jax.tree.map(lambda x: x[i], p_scan["blocks"])
    out_scan = m_scan.apply({"params": p_scan}, batch)
    out_loop = m_loop.apply({"params": p_loop}, batch)
    np.testing.assert_allclose(out_scan, out_loop, rtol=2e-5, atol=2e-5)


# stages 2/3 are tier-2 (round 8 budget): test_zero_stage_trains[2]/[3]
# keep per-stage engine training gating tier-1 at a third the cost
@pytest.mark.parametrize(
    "stage", [0, pytest.param(2, marks=pytest.mark.slow),
              pytest.param(3, marks=pytest.mark.slow)])
def test_engine_trains_transformer(stage):
    model, cfg = build_model("gpt2-tiny", hidden_size=64, num_layers=2,
                             num_heads=4, vocab_size=256, max_seq_len=64,
                             attention_impl="reference")
    config = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
    }
    rng = np.random.default_rng(2)
    batch = tiny_batch(rng, cfg, batch=16, seq=32)
    engine, *_ = ds.initialize(model=model, config=config,
                               loss_fn=causal_lm_loss, example_batch=batch)
    losses = [float(engine.train_batch(tiny_batch(rng, cfg, 16, 32))["loss"])
              for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_tp_rules_cover_params():
    model, cfg = build_model("gpt2-tiny", attention_impl="reference")
    rules = cfg.tp_rules()
    batch = tiny_batch(np.random.default_rng(0), cfg, batch=2, seq=16)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    from deepspeed_tpu.utils.partitioning import build_tp_specs
    specs = build_tp_specs(params, rules)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: x is None or hasattr(x, "index"))
    matched = [s for s in flat if s is not None]
    # qkv, qkv bias, proj, fc, fc bias, fc_proj, wte at minimum
    assert len(matched) >= 6


@pytest.mark.slow
def test_tp_sharded_engine_matches_unsharded():
    """2-way TP x 2-way DP on the 8-dev CPU mesh == single-device numerics."""
    kw = dict(hidden_size=64, num_layers=2, num_heads=4, vocab_size=256,
              max_seq_len=64, dtype=jnp.float32, attention_impl="reference")
    model, cfg = build_model("gpt2-tiny", **kw)
    rng = np.random.default_rng(3)
    batch = tiny_batch(rng, cfg, batch=16, seq=32)
    base = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
    }
    cfg_tp = dict(base, tensor_parallel={"tp_size": 2},
                  zero_optimization={"stage": 1})
    eng_plain, *_ = ds.initialize(model=model, config=base,
                                  loss_fn=causal_lm_loss, example_batch=batch,
                                  rng=jax.random.PRNGKey(11))
    eng_tp, *_ = ds.initialize(model=model, config=cfg_tp,
                               loss_fn=causal_lm_loss, example_batch=batch,
                               rng=jax.random.PRNGKey(11),
                               sharding_rules=cfg.tp_rules())
    m1 = eng_plain.train_batch(batch)
    m2 = eng_tp.train_batch(batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_fused_loss_matches_unfused():
    """fused_loss=True returns the same scalar + grads as logits->causal_lm_loss,
    including ignore_index=-100 masking, at a chunk size that forces padding."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, size=(2, 64))
    kw = dict(vocab_size=256, max_seq_len=64, dtype=jnp.float32,
              attention_impl="reference")
    m1, _ = build_model("gpt2-tiny", **kw)
    m2, _ = build_model("gpt2-tiny", fused_loss=True, loss_chunk=24, **kw)
    batch = {"input_ids": jnp.asarray(ids)}
    params = m1.init(jax.random.PRNGKey(0), batch)["params"]

    l1 = causal_lm_loss(m1.apply({"params": params}, batch), batch)
    l2 = m2.apply({"params": params}, batch)
    assert abs(float(l1 - l2)) < 1e-5

    g1 = jax.grad(lambda p: causal_lm_loss(m1.apply({"params": p}, batch),
                                           batch))(params)
    g2 = jax.grad(lambda p: m2.apply({"params": p}, batch))(params)
    errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)
    assert max(jax.tree.leaves(errs)) < 1e-4

    labels = ids.copy()
    labels[:, 10:20] = -100
    b2 = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}
    l1m = causal_lm_loss(m1.apply({"params": params}, b2), b2)
    l2m = m2.apply({"params": params}, b2)
    assert abs(float(l1m - l2m)) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shift", [1, 0], ids=["causal", "in_place"])
@pytest.mark.parametrize("head", ["tied", "untied"])
@pytest.mark.parametrize("layout", ["dp4", "dp2_tp2"])
def test_fused_loss_per_shard_matches_one_device_and_unfused(layout, head,
                                                             shift, dtype):
    """The fused loss on a mesh of four chips (the chunk loop per batch
    shard, the head gradient summed on each chip and reduced once behind
    the loop: dp4 by a reduce-scatter, dp2_tp2, the head vocab-parallel
    over "model", by an all-reduce) against one device (the plain loop) and
    against the unfused cross entropy over whole logits: loss, d x and the
    head's gradient, with -100 labels and a sequence that needs padding to
    the chunk. The untied head hands in its [H, V] kernel's transpose, as
    ``Transformer`` does."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu.models.transformer import (_fused_causal_lm_loss,
                                                  cross_entropy)
    from deepspeed_tpu.parallel.mesh import BATCH_AXES, MESH_AXES, TP_AXIS
    B, S, H, V, chunk = 8, 37, 16, 50, 8
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (B, S, H), dtype)
    w = jax.random.normal(k[1], (V, H) if head == "tied" else (H, V), dtype)
    labels = jax.random.randint(k[2], (B, S), 0, V).at[:, 5:9].set(-100)
    emb = (lambda w: w) if head == "tied" else (lambda w: w.T)

    def fused(x, w, labels):
        return _fused_causal_lm_loss(x, emb(w), labels, chunk, shift=shift)

    def unfused(x, w, labels):
        logits = jnp.einsum("bsh,vh->bsv", x, emb(w),
                            preferred_element_type=jnp.float32)
        if shift:
            return cross_entropy(logits[:, :-1], labels[:, 1:])
        return cross_entropy(logits, labels)

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    dp, tp = (4, 1) if layout == "dp4" else (2, 2)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, dp, 1, 1, tp),
                MESH_AXES)
    rows = NamedSharding(mesh, P(BATCH_AXES))
    vocab = P() if tp == 1 else \
        P(TP_AXIS) if head == "tied" else P(None, TP_AXIS)
    on_mesh = (jax.device_put(x, rows),
               jax.device_put(w, NamedSharding(mesh, vocab)),
               jax.device_put(labels, rows))
    assert "shard_map" in str(jax.make_jaxpr(fused)(*on_mesh))
    assert "shard_map" not in str(jax.make_jaxpr(fused)(x, w, labels))
    assert ("reduce_scatter" in str(jax.make_jaxpr(
        jax.grad(fused, argnums=1))(*on_mesh))) == (tp == 1)
    got = grad(fused)(*on_mesh)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for want in (grad(fused)(x, w, labels), grad(unfused)(x, w, labels)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            np.testing.assert_allclose(a, b, rtol=tol,
                                       atol=tol * np.abs(b).max())


@pytest.mark.parametrize("layout", ["dp4", "dp2_tp2"])
@pytest.mark.parametrize("head", ["tied", "untied"])
def test_fused_loss_loop_holds_no_collective_in_the_engines_step(head, layout):
    """The CPU twin of tests/test_chip_compile.py's pin: through
    ``initialize`` and the engine's own ZeRO-3 step on four virtual devices,
    the optimized program has no collective inside the loss's chunk loop
    that crosses the data-parallel chips (before PR 34 the partitioner
    carried the accumulated gradient's layout into the loop and reduced
    every chunk's [V, H] product over them), and the head gradient's one
    reduction over them lies behind it: a reduce-scatter on a mesh of
    data-parallel chips only."""
    from deepspeed_tpu.parallel.mesh import BATCH_AXES
    from util import collectives, crosses, in_loss_loop, zero3_engine_on_four
    tp = 2 if layout == "dp2_tp2" else 1
    model, cfg = build_model(
        "gpt2-tiny", hidden_size=32, num_layers=2, num_heads=2, vocab_size=66,
        max_seq_len=64, remat=True, remat_policy="dots", fused_loss=True,
        loss_chunk=8, tie_embeddings=head == "tied",
        attention_impl="reference")
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 66, size=(2 * 2 * (4 // tp), 32)).astype(np.int32)}
    with zero3_engine_on_four(model, cfg, batch, micro=2, gas=2, tp=tp,
                              stage3_param_persistence_threshold=0) as engine:
        text = engine._active_train_step()[0].lower(
            engine.state, engine._put_micro_batches(batch),
            engine.next_rng(), engine._current_lr()).compile().as_text()
        assert np.isfinite(float(engine.train_batch(batch)["loss"]))
    # tensor parallelism keeps its own sums over "model" in the loop (the
    # softmax over a split vocabulary, d x): those are not this test's
    over_chips = [c for c in collectives(text)
                  if crosses(c[3], engine.mesh, BATCH_AXES)]
    assert not [c for c in over_chips if in_loss_loop(c[2])], over_chips
    reductions = [c[0] for c in over_chips if "grad_reduce" in c[2]]
    # beside tensor parallelism it is an all-reduce, which the CPU's
    # compiler combines with others under one of their names
    assert reductions == ["reduce-scatter"] if tp == 1 else \
        "reduce-scatter" not in reductions, over_chips


@pytest.mark.slow
def test_remat_policies_agree():
    """dots/full remat and no remat give identical losses AND gradients
    (remat only changes what is saved for backward, so grads are where a
    broken checkpoint policy would show up)."""
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 256, size=(2, 32))
    batch = {"input_ids": jnp.asarray(ids)}
    results = []
    for remat, policy in [(False, "dots"), (True, "dots"), (True, "full"),
                          (True, "attn")]:
        m, _ = build_model("gpt2-tiny", vocab_size=256, max_seq_len=32,
                           dtype=jnp.float32, attention_impl="reference",
                           remat=remat, remat_policy=policy)
        params = m.init(jax.random.PRNGKey(0), batch)["params"]
        loss, grads = jax.value_and_grad(
            lambda p: causal_lm_loss(m.apply({"params": p}, batch), batch)
        )(params)
        results.append((float(loss), grads))
    base_loss, base_grads = results[0]
    for loss, grads in results[1:]:
        assert loss == pytest.approx(base_loss, abs=1e-6)
        errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                            base_grads, grads)
        assert max(jax.tree.leaves(errs)) < 1e-5


def test_fused_loss_encoder_no_shift():
    """causal=False (BERT-style) fused loss predicts in place: matches plain
    per-token cross_entropy on the logits with no shift."""
    from deepspeed_tpu.models.transformer import cross_entropy
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 256, size=(2, 48))
    kw = dict(vocab_size=256, max_seq_len=64, causal=False,
              dtype=jnp.float32, attention_impl="reference")
    m1, _ = build_model("gpt2-tiny", **kw)
    m2, _ = build_model("gpt2-tiny", fused_loss=True, loss_chunk=20, **kw)
    batch = {"input_ids": jnp.asarray(ids)}
    params = m1.init(jax.random.PRNGKey(0), batch)["params"]

    logits = m1.apply({"params": params}, batch)
    l1 = cross_entropy(logits, jnp.asarray(ids))
    l2 = m2.apply({"params": params}, batch)
    assert abs(float(l1 - l2)) < 1e-5

    labels = ids.copy()
    labels[:, :8] = -100            # masked-LM-style ignore positions
    b2 = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}
    l1m = cross_entropy(m1.apply({"params": params}, b2), jnp.asarray(labels))
    l2m = m2.apply({"params": params}, b2)
    assert abs(float(l1m - l2m)) < 1e-5


def test_adhoc_jit_off_mesh_runs_unconstrained():
    """With a multi-device session mesh installed, a plain-jit model call on
    data committed to ONE device must run unconstrained (the activation
    constraints would otherwise pin it to the full mesh and fail dispatch
    with an incompatible-devices error)."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    model, cfg = build_model("gpt2-tiny", hidden_size=64, num_layers=2,
                             num_heads=4, vocab_size=256, max_seq_len=64,
                             attention_impl="reference")
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 16,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1}},
        loss_fn=causal_lm_loss,
        example_batch={"input_ids": np.zeros((16, 32), np.int64)})
    # divisible batch, params + inputs committed to a non-default device
    params = jax.device_put(jax.device_get(engine.state.params),
                            jax.devices()[1])
    x = jax.device_put(jnp.zeros((8, 32), jnp.int32), jax.devices()[1])
    out = jax.jit(lambda p, b: model.apply({"params": p}, b))(
        params, {"input_ids": x})
    assert out.shape == (8, 32, 256)
    assert {d.id for d in out.devices()} == {1}
    # the session engine still steps (its program keeps the mesh layout)
    m = engine.train_batch({"input_ids": np.random.default_rng(1).integers(
        0, 256, size=(16, 32))})
    assert np.isfinite(float(m["loss"]))


# tier-2 (round 8 budget): test_fused_loss_encoder_no_shift keeps the
# fused-CE path gating tier-1; the untied-head variant rides tier2
@pytest.mark.slow
def test_fused_loss_untied_head_matches_dense_path():
    """fused_loss now supports untied lm_head models (Llama family): the
    param tree is IDENTICAL to the non-fused nn.Dense path (shared
    checkpoints/HF imports) and the loss matches token-level CE."""
    from deepspeed_tpu.models import fused_loss_passthrough
    kw = dict(hidden_size=64, num_layers=2, num_heads=4, vocab_size=128,
              max_seq_len=64, tie_embeddings=False, dtype=jnp.float32,
              attention_impl="reference")
    m1, _ = build_model("gpt2-tiny", fused_loss=False, **kw)
    m2, _ = build_model("gpt2-tiny", fused_loss=True, loss_chunk=16, **kw)
    ids = np.random.default_rng(0).integers(0, 128, (2, 32))
    batch = {"input_ids": jnp.asarray(ids)}
    p = m1.init(jax.random.PRNGKey(0), batch)["params"]
    p2 = m2.init(jax.random.PRNGKey(0), batch)["params"]
    assert jax.tree.structure(p) == jax.tree.structure(p2)
    l1 = float(causal_lm_loss(m1.apply({"params": p}, batch), batch))
    l2 = float(m2.apply({"params": p}, batch))
    assert abs(l1 - l2) < 1e-4, (l1, l2)
    # biased untied head has no fused path — must refuse, not drop the bias
    m3, _ = build_model("gpt2-tiny", fused_loss=True, lm_head_bias=True, **kw)
    with pytest.raises(ValueError, match="BIASED"):
        m3.init(jax.random.PRNGKey(0), batch)


# tier-2 (round-19 budget sweep, ~9s): the cheaper tier-1 cousins are
# test_engine_trains_transformer[0] (same training loop, gpt2 preset),
# test_hf_llama_parity (llama block math) and
# test_fused_loss_encoder_no_shift (fused CE); scripts/tier2.sh runs this
@pytest.mark.slow
def test_llama_preset_trains():
    """The llama-1.1b preset's block recipe (tiny-shaped here) trains
    through the engine with the fused untied-head CE."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import fused_loss_passthrough
    model, cfg = build_model("llama-1.1b", hidden_size=64, num_layers=2,
                             num_heads=4, num_kv_heads=2, vocab_size=256,
                             max_seq_len=64, mlp_dim_override=96,
                             fused_loss=True, loss_chunk=16,
                             attention_impl="reference")
    rng = np.random.default_rng(1)
    mk = lambda: {"input_ids": rng.integers(0, 256, size=(16, 32))}
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 16,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 2}},
        loss_fn=fused_loss_passthrough, example_batch=mk())
    losses = [float(engine.train_batch(mk())["loss"]) for _ in range(20)]
    # bf16 on random tokens descends noisily: compare window means
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05, losses
