"""Round-17 quantized-compute tier: the single-sourced quant format's
error bounds, the weight-only int8 matmul's analytic logit bound, the
sentinel-gated low-precision training experiment, and the decode
hot-path audit proving no bulk dequant survives outside the kernels.

Layers covered:

* ``quant_format`` — property tests pinning the documented error model
  (COMM.md: per-element roundtrip error <= block_absmax / 127) for the
  blockwise wire/weight format AND the per-row KV format, plus the
  straight-through ``fake_quant_act`` (int8 + fp8-e4m3 emulation).
* ``ops/pallas/quant_matmul`` — interpret-mode kernel vs jnp reference
  parity, and both vs the exact f32 matmul within the analytic bound
  ``sum_b ||x_block||_1 * block_absmax_b / 127`` per output element.
* per-architecture weight-only logit bounds (gpt2-ish learned+gelu,
  llama-ish rmsnorm+gated+rotary+GQA) through ``paged_forward``.
* ``wire_low_precision`` gates (the experiment REQUIRES the integrity
  sentinel) and the engine loss-parity twin; the chaos sentinel.spike
  leg on a low-precision engine is ``slow`` (scripts/chaos.sh).
* the acceptance audit: the traced decode step contains NO int8 ->
  float convert of pool-slice / packed-kernel size outside pallas_call
  — the round-12 full-pool dequant copy is structurally gone.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfig
from deepspeed_tpu.models import build_model, fused_loss_passthrough
from deepspeed_tpu.models.generation import ensure_scan_layout
from deepspeed_tpu.models.transformer import causal_lm_loss
from deepspeed_tpu.ops.pallas.quant_matmul import (pack_decode_weights,
                                                   pack_kernel, quant_matmul,
                                                   quant_matmul_reference)
from deepspeed_tpu.quant_format import (QUANT_BLOCK, block_dequant,
                                        block_quant, fake_quant_act,
                                        kv_quantize)
from deepspeed_tpu.runtime.engine import wire_low_precision
from deepspeed_tpu.serving.kv_cache import init_pool
from deepspeed_tpu.serving.model_runner import paged_forward
from deepspeed_tpu.testing import chaos
from tests.util import SimpleModel


# ------------------------------------------------------ quant_format bounds

@pytest.mark.parametrize("shape,block", [
    ((3, 256), 256),          # exact block multiple
    ((2, 300), 256),          # ragged tail -> one padded block
    ((4, 7, 96), 32),         # small blocks, leading dims
    ((1, 1), 256),            # single element
])
def test_block_quant_error_bound_property(shape, block):
    """THE documented error model (COMM.md / quant_format docstring):
    per-element roundtrip error <= block_absmax / 127."""
    rng = np.random.default_rng(hash(shape) % 2**32)
    x = (rng.standard_normal(shape) * 10 ** rng.uniform(-2, 2, shape)
         ).astype(np.float32)
    q, s, pad = block_quant(jnp.asarray(x), 8, block)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    deq = np.asarray(block_dequant(q, s, pad))[..., :shape[-1]]
    L = shape[-1]
    nb = -(-L // block)
    xp = np.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * block - L)])
    absmax = np.abs(xp.reshape(shape[:-1] + (nb, block))).max(-1)
    bound = np.repeat(absmax / 127.0, block, axis=-1)[..., :L]
    np.testing.assert_array_less(np.abs(deq - x), bound + 1e-7)


def test_block_quant_zero_blocks_exact_and_int4_bound():
    x = jnp.zeros((2, 512), jnp.float32)
    q, s, pad = block_quant(x)
    assert pad == 0
    np.testing.assert_array_equal(np.asarray(s), 1.0)   # zero block scale 1
    np.testing.assert_array_equal(np.asarray(block_dequant(q, s, pad)), 0.0)
    # 4-bit widens the step to absmax / 7 — the bits knob scales the bound
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 256)),
                    jnp.float32)
    q4, s4, _ = block_quant(x, bits=4)
    assert int(np.abs(np.asarray(q4)).max()) <= 7
    err = np.abs(np.asarray(block_dequant(q4, s4, 0)) - np.asarray(x))
    absmax = np.abs(np.asarray(x)).reshape(2, 1, 256).max(-1)
    assert (err <= np.repeat(absmax / 7.0, 256, -1) + 1e-7).all()


def test_kv_quantize_error_bound_per_row():
    """Per-row format: one scale per (layer, head, slot) vector; error
    <= row_absmax / 127; zero rows roundtrip exactly."""
    rng = np.random.default_rng(1)
    t = rng.standard_normal((3, 4, 5, 64)).astype(np.float32)
    t[0, 1, 2] = 0.0                                    # a zero row
    q, s = kv_quantize(jnp.asarray(t))
    assert q.dtype == jnp.int8 and s.shape == t.shape[:-1] + (1,)
    deq = np.asarray(q, np.float32) * np.asarray(s)
    bound = np.abs(t).max(-1, keepdims=True) / 127.0
    assert (np.abs(deq - t) <= bound + 1e-7).all()
    np.testing.assert_array_equal(deq[0, 1, 2], 0.0)


def test_fake_quant_act_bounds_and_ste_gradient():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 300)) * 3, jnp.float32)
    absmax = np.abs(np.asarray(x)).max()                # one padded block
    y8 = fake_quant_act(x, "int8")
    assert float(jnp.abs(y8 - x).max()) <= absmax / 127.0 + 1e-7
    yf = fake_quant_act(x, "fp8")
    # e4m3 normals carry a 3-bit mantissa: relative error <= 2^-4, plus a
    # subnormal floor from the absmax -> 448 block scale
    err = np.abs(np.asarray(yf) - np.asarray(x))
    assert (err <= np.abs(np.asarray(x)) * 0.0625 + absmax / 448.0).all()
    # straight-through: the gradient ignores the quantizer entirely
    for fmt in ("int8", "fp8"):
        g = jax.grad(lambda v: jnp.sum(fake_quant_act(v, fmt)))(x)
        np.testing.assert_array_equal(np.asarray(g), 1.0)
    with pytest.raises(ValueError, match="int8|fp8"):
        fake_quant_act(x, "int4")


# ------------------------------------------------------------- quant_matmul

@pytest.mark.parametrize("M,K,N", [(3, 300, 256), (9, 512, 128),
                                   (2, 32, 128)])
def test_quant_matmul_kernel_reference_parity_and_analytic_bound(M, K, N):
    """The interpret-mode Pallas kernel computes the reference's per-block
    identity; both sit within the analytic bound vs the exact product:
    |err[m, n]| <= sum_b ||x[m, block_b]||_1 * block_absmax_b[n] / 127."""
    rng = np.random.default_rng(M * 1000 + K)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.3, jnp.float32)
    q, s = pack_kernel(w)
    Kp = q.shape[0]
    nkb = s.shape[0]
    yk = np.asarray(quant_matmul(x, q, s, interpret=True))
    yr = np.asarray(quant_matmul_reference(x, q, s))
    np.testing.assert_allclose(yk, yr, atol=1e-4)
    y_true = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    # per-element weight error bound summed through the contraction
    wp = np.zeros((Kp, N), np.float32)
    wp[:K] = np.asarray(w)
    absmax = np.abs(wp.reshape(nkb, Kp // nkb, N)).max(1)      # [nkb, N]
    xp = np.zeros((M, Kp), np.float32)
    xp[:, :K] = np.abs(np.asarray(x))
    xnorm = xp.reshape(M, nkb, Kp // nkb).sum(-1)              # [M, nkb]
    bound = xnorm @ (absmax / 127.0)
    assert (np.abs(yr - y_true) <= bound + 1e-4).all()
    assert (np.abs(yk - y_true) <= bound + 1e-3).all()


def test_pack_decode_weights_selective_and_idempotent():
    rng = np.random.default_rng(3)
    mk = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    params = {
        "blocks": {
            "attn_qkv": {"kernel": mk(2, 64, 192), "bias": mk(2, 192)},
            "mlp_fc": {"kernel": mk(2, 64, 256)},
            "ln1": {"scale": mk(2, 64)},                # no kernel: untouched
            "moe": {"gate": {"kernel": mk(2, 64, 4)}},  # nested: untouched
        },
        "lm_head": {"kernel": mk(64, 100)},
        "wte": {"embedding": mk(100, 64)},
    }
    out = pack_decode_weights(params)
    for name in ("attn_qkv", "mlp_fc"):
        sub = out["blocks"][name]
        assert sub["kernel"].dtype == jnp.int8
        assert sub["kernel_qscale"].dtype == jnp.float32
        # stacked [L, K, N] leaves pack per-layer: leading dim preserved
        assert sub["kernel"].shape[0] == 2
    assert out["blocks"]["attn_qkv"]["bias"] is params["blocks"]["attn_qkv"]["bias"]
    assert out["blocks"]["ln1"] is params["blocks"]["ln1"]
    assert out["blocks"]["moe"]["gate"]["kernel"].dtype == jnp.float32
    assert out["lm_head"]["kernel"].dtype == jnp.int8
    assert out["wte"] is params["wte"]
    again = pack_decode_weights(out)                    # already packed: noop
    assert again["blocks"]["attn_qkv"]["kernel"] is \
        out["blocks"]["attn_qkv"]["kernel"]


# ------------------------------------- per-architecture weight-only bounds

_ARCHS = {
    "gpt2ish": dict(preset="gpt2-tiny", hidden_size=32, num_layers=2,
                    num_heads=2, vocab_size=64),
    "llamaish": dict(preset="llama-1.1b", hidden_size=32, num_layers=2,
                     num_heads=4, num_kv_heads=2, mlp_dim_override=64,
                     vocab_size=64),
}


# tier-2 (round-17 budget sweep, ~12s): the cheaper tier-1 cousins are
# test_quant_matmul_kernel_reference_parity_and_analytic_bound (per-matmul
# bound) and test_serving.test_int8_weight_only_decode_parity (end-to-end
# token-exactness); scripts/tier2.sh runs this per-arch magnitude pin
@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(_ARCHS))
def test_weight_only_int8_logit_bound_per_arch(arch):
    """Blockwise-int8 weights perturb prefill logits by a small bounded
    amount per architecture — and leave the greedy argmax intact on the
    tested prompt (the serving tier's token-exactness contract rides
    tests/test_serving.py's engine legs; this pins the magnitude)."""
    kw = dict(_ARCHS[arch])
    model, cfg = build_model(kw.pop("preset"), max_seq_len=64,
                             attention_impl="reference",
                             dtype=jnp.float32, **kw)
    ids = np.asarray([[5, 9, 2, 7, 11, 3, 1, 8]], np.int32)
    params = ensure_scan_layout(
        model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"],
        cfg.num_layers)
    packed = pack_decode_weights(params)
    bs, nbk = 16, 4
    pools = init_pool(cfg, 8, bs)
    bt = np.zeros((1, nbk), np.int32)
    bt[0] = [1, 2, 3, 4]
    run = lambda p, pl_: paged_forward(
        cfg, p, jnp.asarray(ids), pl_, jnp.asarray(bt),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), ids.shape[1], jnp.int32),
        bs)[0]
    base = np.asarray(run(params, pools))
    quant = np.asarray(run(packed, init_pool(cfg, 8, bs)))
    err = np.abs(quant - base).max()
    assert err < 0.15, f"{arch}: weight-only logit err {err}"
    assert np.array_equal(base[0, -1].argmax(), quant[0, -1].argmax())


# ------------------------------------------- the experiment's sentinel gate

def _lp_model(**kw):
    return build_model("gpt2-tiny", hidden_size=32, num_layers=2,
                       num_heads=2, vocab_size=64, max_seq_len=64,
                       attention_impl="reference", **kw)


def test_wire_low_precision_gates():
    """The low-precision step is a GATED experiment: both routes (config
    section and model knob) demand the integrity sentinel; unsupported
    schedules / bit widths / model families raise instead of silently
    training full precision."""
    act = {"shared_parameters": {"enabled": True},
           "different_groups": {"g": {"params": {"bits": 8}}}}
    ok = DeepSpeedConfig(
        compression_training={"activation_quantization": act},
        integrity={"enabled": True})
    model, _ = _lp_model()
    wired = wire_low_precision(model, ok)
    assert wired.cfg.activation_quant == "int8"
    # section enabled but sentinel off
    with pytest.raises(ValueError, match="integrity"):
        wire_low_precision(model, DeepSpeedConfig(
            compression_training={"activation_quantization": act}))
    # model knob without sentinel
    knob, _ = _lp_model(activation_quant="int8")
    with pytest.raises(ValueError, match="integrity"):
        wire_low_precision(knob, DeepSpeedConfig())
    # the knob + sentinel passes through untouched
    assert wire_low_precision(
        knob, DeepSpeedConfig(integrity={"enabled": True})
    ).cfg.activation_quant == "int8"
    # schedule offsets can't reach inside the model
    with pytest.raises(NotImplementedError, match="schedule_offset"):
        wire_low_precision(model, DeepSpeedConfig(
            compression_training={"activation_quantization": {
                "shared_parameters": {"enabled": True,
                                      "schedule_offset": 100}}},
            integrity={"enabled": True}))
    # only 8-bit activations
    with pytest.raises(ValueError, match="bits=4"):
        wire_low_precision(model, DeepSpeedConfig(
            compression_training={"activation_quantization": {
                "shared_parameters": {"enabled": True},
                "different_groups": {"g": {"params": {"bits": 4}}}}},
            integrity={"enabled": True}))
    # not a transformer: nothing to wire the knob into
    with pytest.raises(ValueError, match="TransformerConfig|transformer"):
        wire_low_precision(SimpleModel(), ok)
    # the knob itself validates its values at config construction
    with pytest.raises(ValueError, match="activation_quant"):
        _lp_model(activation_quant="int4")


# -------------------------------------------------- engine loss parity twin

def _lp_engine(activation_quant=None, integrity=True, batch=None):
    model, _ = _lp_model(fused_loss=True, loss_chunk=32,
                         activation_quant=activation_quant)
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "steps_per_print": 1000,
    }
    if integrity:
        cfg["integrity"] = {"enabled": True, "warmup_steps": 6,
                            "window": 16, "zmax": 6.0, "cooldown_steps": 0}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, loss_fn=fused_loss_passthrough,
        example_batch=batch)
    return engine


def _lm_batches(n, b=8, s=32, vocab=64, distinct=6):
    rng = np.random.default_rng(4)
    pool = [{"input_ids": rng.integers(0, vocab, size=(b, s))}
            for _ in range(distinct)]
    return [pool[i % distinct] for i in range(n)]


# tier-2 (round-17 budget sweep, 25s): the cheaper tier-1 cousins are
# test_wire_low_precision_gates (wiring + integrity refusal) and
# test_fake_quant_act_bounds_and_ste_gradient (quantizer math + STE);
# scripts/chaos.sh and scripts/tier2.sh run this 3-engine parity leg
@pytest.mark.slow
def test_low_precision_training_loss_parity():
    """The experiment's headline: int8/fp8 fake-quant training tracks the
    full-precision twin's loss trajectory on identical data; running the
    knob WITHOUT the sentinel is refused at engine construction."""
    batches = _lm_batches(9)
    with pytest.raises(ValueError, match="integrity"):
        _lp_engine("int8", integrity=False, batch=batches[0])
    losses = {}
    for fmt in (None, "int8", "fp8"):
        eng = _lp_engine(fmt, batch=batches[0])
        losses[fmt] = [float(jax.device_get(eng.train_batch(b)["loss"]))
                       for b in batches]
    assert losses[None][-1] < losses[None][0]           # it trains
    for fmt in ("int8", "fp8"):
        assert losses[fmt][-1] == pytest.approx(losses[None][-1], rel=0.05), \
            (fmt, losses[fmt][-1], losses[None][-1])


@pytest.mark.slow
def test_chaos_spike_on_low_precision_engine_skips_and_recovers():
    """scripts/chaos.sh low-precision leg: the guardrail the experiment is
    gated on actually fires under it. A chaos-poisoned step (sentinel.spike
    scales the batch's float features x1e4 -> loss and grads x1e4) is
    skipped in-jit by the quantized engine's sentinel, and the run trains
    through to loss parity with an uninjected low-precision twin."""
    steps = 24
    b = 8
    rng = np.random.default_rng(5)
    pool = [{"input_ids": rng.integers(0, 64, size=(b, 16)),
             "chaos_gain": np.ones((b,), np.float32)} for _ in range(6)]
    batches = [pool[i % 6] for i in range(steps)]
    # the float feature the engine-side spike can scale: a loss gain of 1
    gain_loss = lambda out, bt: causal_lm_loss(out, bt) * \
        jnp.mean(bt["chaos_gain"])

    def engine():
        model, _ = _lp_model(activation_quant="int8")
        return deepspeed_tpu.initialize(
            model=model, config={
                "train_batch_size": b,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "gradient_clipping": 1.0,
                "bf16": {"enabled": True},
                "steps_per_print": 1000,
                "integrity": {"enabled": True, "warmup_steps": 6,
                              "window": 16, "zmax": 6.0,
                              "cooldown_steps": 0},
            }, loss_fn=gain_loss, example_batch=batches[0])[0]

    clean = engine()
    clean_losses = [float(jax.device_get(clean.train_batch(bt)["loss"]))
                    for bt in batches]

    chaos.arm("sentinel.spike", "flag", skip=10, times=1, factor=10000)
    eng = engine()
    skipped_at, losses = [], []
    for i, bt in enumerate(batches):
        m = eng.train_batch(bt)
        losses.append(float(jax.device_get(m["loss"])))
        if "anomaly_skip" in m and bool(np.asarray(
                jax.device_get(m["anomaly_skip"]))):
            skipped_at.append(i + 1)
    assert skipped_at == [11], skipped_at
    assert int(jax.device_get(eng.state.skipped_steps)) == 1
    assert eng.sentinel.rollbacks_done == 0             # rung 1 was enough
    assert losses[-1] == pytest.approx(clean_losses[-1], rel=0.25)


# ------------------------------------------------- decode hot-path audit

def _collect_bulk_int8_converts(jaxpr, threshold, found, pallas=None):
    """Walk a jaxpr (recursing into sub-jaxprs in eqn params) collecting
    int8 -> float convert_element_type eqns with >= threshold elements,
    SKIPPING pallas_call bodies (in-kernel dequant is the design)."""
    pallas = pallas if pallas is not None else [0]
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            pallas[0] += 1
            continue
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (getattr(src, "dtype", None) == jnp.int8
                    and jnp.issubdtype(dst.dtype, jnp.floating)
                    and dst.size >= threshold):
                found.append((src.shape, dst.dtype, dst.size))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", None)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    _collect_bulk_int8_converts(sub, threshold, found, pallas)
                elif inner is not None and hasattr(inner, "eqns"):
                    _collect_bulk_int8_converts(inner, threshold, found,
                                                pallas)
    return pallas[0]


def test_decode_hot_path_has_no_bulk_dequant_outside_kernels():
    """Acceptance audit: trace one int8-KV + int8-weight decode step (the
    Pallas tier, interpret mode) and prove NO int8 -> float conversion of
    pool-slice or packed-kernel size happens outside a pallas_call — the
    round-12 O(pool) dequant copy and the _kernel_of full-weight
    materialization are structurally absent from the hot path."""
    # attention_impl stays "auto": a "reference" model serves on the gather
    # oracle (serving/model_runner.py), and this audit wants the kernels
    model, cfg = build_model("gpt2-tiny", max_seq_len=256, dtype=jnp.float32)
    ids = np.zeros((2, 1), np.int32)
    params = ensure_scan_layout(
        model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"],
        cfg.num_layers)
    packed = pack_decode_weights(params)
    bs, nbk, nblocks = 16, 4, 8
    pools = init_pool(cfg, nblocks, bs, dtype=jnp.int8)
    bt = np.asarray([[1, 2, 0, 0], [3, 4, 5, 0]], np.int32)
    ctx = np.asarray([5, 21], np.int32)

    def step(pools):
        return paged_forward(cfg, packed, jnp.asarray(ids), pools,
                             jnp.asarray(bt), jnp.asarray(ctx - 1),
                             jnp.asarray(ctx), bs, interpret=True)

    jaxpr = jax.make_jaxpr(step)(pools)
    # the smallest guarded object: one layer's pool slice (nh * slots * hd
    # = 4 * 128 * 32 = 16k elems); packed kernels are >= 32k. Anything
    # int8->float at >= 1/4 of that size outside a kernel is a bulk copy.
    threshold = cfg.num_heads * nblocks * bs * cfg.head_dim // 4
    found = []
    n_pallas = _collect_bulk_int8_converts(jaxpr.jaxpr, threshold, found)
    assert n_pallas >= 2, "expected paged-attention AND quant-matmul " \
        f"pallas_calls on the traced decode step, saw {n_pallas}"
    assert not found, (
        "bulk int8->float dequant outside Pallas kernels on the decode "
        f"hot path: {found}")
