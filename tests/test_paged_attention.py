"""Pallas paged-attention kernel: parity vs a dense numpy oracle.

The kernel gathers K/V blocks through a per-sequence block table inside
the pipeline (serving decode path); the oracle materializes each
sequence's logical K/V by following the table on the host and runs dense
masked attention. Interpret mode on CPU — the same kernel runs compiled
on TPU. Covers the acceptance regimes: padding (ragged context lengths,
dead table entries), ALiBi, softcap, sliding window, stacked layer pools.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_attention, paged_attention_reference)
from deepspeed_tpu.quant_format import kv_quantize


def _oracle(q, k_pool, v_pool, bt, lens, *, window=0, slopes=None,
            softcap=0.0):
    """Dense numpy oracle: gather via table, mask, f32 softmax."""
    B, nh, T, hd = q.shape
    bs = k_pool.shape[2]
    nbk = bt.shape[1]
    out = np.zeros((B, nh, T, hd), np.float32)
    for b in range(B):
        k = np.concatenate([k_pool[:, bt[b, j]] for j in range(nbk)],
                           axis=1)                     # [nh, nbk*bs, hd]
        v = np.concatenate([v_pool[:, bt[b, j]] for j in range(nbk)], axis=1)
        q_abs = np.arange(lens[b] - T, lens[b])        # [T]
        k_pos = np.arange(nbk * bs)
        s = np.einsum("htd,hkd->htk", q[b].astype(np.float32),
                      k.astype(np.float32)) / np.sqrt(hd)
        if softcap:
            s = np.tanh(s / softcap) * softcap
        if slopes is not None:
            s = s + slopes[:, None, None] * (
                k_pos[None, None, :] - q_abs[None, :, None])
        mask = k_pos[None, :] <= q_abs[:, None]
        if window > 0:
            mask &= q_abs[:, None] - k_pos[None, :] < window
        s = np.where(mask[None], s, -1e30)
        p = np.asarray(jax.nn.softmax(jnp.asarray(s), axis=-1))
        out[b] = np.einsum("htk,hkd->htd", p, v.astype(np.float32))
    return out


def _data(B=3, nh=4, hd=64, bs=16, num_blocks=32, nbk=8, seed=0):
    """Random pool + a random (valid, non-overlapping) block assignment."""
    rng = np.random.default_rng(seed)
    k_pool = rng.standard_normal((nh, num_blocks, bs, hd)).astype(np.float32)
    v_pool = rng.standard_normal((nh, num_blocks, bs, hd)).astype(np.float32)
    # distinct physical blocks per (b, j); block 0 reserved as null
    perm = rng.permutation(num_blocks - 1)[:B * nbk] + 1
    bt = perm.reshape(B, nbk).astype(np.int32)
    lens = rng.integers(1, nbk * bs + 1, size=B).astype(np.int32)
    q = rng.standard_normal((B, nh, 1, hd)).astype(np.float32)
    return q, k_pool, v_pool, bt, lens


@pytest.mark.parametrize("seed", [0, 1])
def test_paged_parity_ragged_lengths(seed):
    q, kp, vp, bt, lens = _data(seed=seed)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens), interpret=True)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)


def test_paged_parity_alibi():
    q, kp, vp, bt, lens = _data()
    slopes = np.asarray([2.0 ** -(i + 1) for i in range(4)], np.float32)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens),
                          alibi_slopes=jnp.asarray(slopes), interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, slopes=slopes),
        rtol=2e-5, atol=2e-5)


def test_paged_parity_softcap():
    q, kp, vp, bt, lens = _data(seed=2)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens), softcap=30.0,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, softcap=30.0),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 50])
def test_paged_parity_window(window):
    q, kp, vp, bt, lens = _data(seed=3)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(lens),
                          window=jnp.asarray(window, jnp.int32),
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _oracle(q, kp, vp, bt, lens, window=window),
        rtol=2e-5, atol=2e-5)


def test_paged_stacked_layer_pool():
    """layer_idx form: blocks picked straight out of the [L, ...] pool."""
    L = 3
    q, kp, vp, bt, lens = _data(B=2, nbk=4)
    kpl = np.stack([kp * (l + 1) for l in range(L)])
    vpl = np.stack([vp * 0.5 * (l + 1) for l in range(L)])
    for li in range(L):
        out = paged_attention(jnp.asarray(q), jnp.asarray(kpl),
                              jnp.asarray(vpl), jnp.asarray(bt),
                              jnp.asarray(lens),
                              layer_idx=jnp.asarray(li, jnp.int32),
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(q, kpl[li], vpl[li], bt, lens),
            rtol=2e-5, atol=2e-5)


def test_paged_reference_matches_kernel_and_serves_prefill():
    """The jnp reference (the CPU/serving fallback) agrees with the numpy
    oracle for T=1 AND for the prefill regime (T>1) the kernel refuses."""
    q, kp, vp, bt, lens = _data(seed=4)
    ref = paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(ref), _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)
    # prefill: 5 queries ending at lens[b]
    rng = np.random.default_rng(9)
    B, nh, _, hd = q.shape
    lens5 = np.maximum(lens, 5)
    q5 = rng.standard_normal((B, nh, 5, hd)).astype(np.float32)
    ref5 = paged_attention_reference(
        jnp.asarray(q5), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens5))
    np.testing.assert_allclose(
        np.asarray(ref5), _oracle(q5, kp, vp, bt, lens5), rtol=2e-5,
        atol=2e-5)
    with pytest.raises(ValueError, match="1 token"):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_attention as kern)
        kern(jnp.asarray(q5), jnp.asarray(kp), jnp.asarray(vp),
             jnp.asarray(bt), jnp.asarray(lens5), interpret=True)


def test_router_dispatch():
    """ops.attention.paged_attention: kernel for T=1 under interpret,
    reference for prefill — same numerics either way."""
    from deepspeed_tpu.ops.attention import paged_attention as router
    q, kp, vp, bt, lens = _data(seed=5)
    out = router(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(bt), jnp.asarray(lens), interpret=True)
    np.testing.assert_allclose(np.asarray(out), _oracle(q, kp, vp, bt, lens),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 KV tier (round 17): the kernel DMAs int8 blocks + per-row scales and
# dequantizes IN VMEM — parity vs the numpy oracle running on the
# dequantized pools must be as tight as the f32 tier's, in every routed
# regime, because the in-kernel dequant reconstructs the identical values.
# ---------------------------------------------------------------------------

def _int8_pools(kp, vp):
    """Quantize pools to the serving format: int8 values + one f32 scale
    per (head, block, slot) row; returns the exact dequantized floats the
    oracle attends over."""
    (kq, ks), (vq, vs) = kv_quantize(jnp.asarray(kp)), kv_quantize(
        jnp.asarray(vp))
    kd = np.asarray(kq, np.float32) * np.asarray(ks)
    vd = np.asarray(vq, np.float32) * np.asarray(vs)
    return kq, ks, vq, vs, kd, vd


@pytest.mark.parametrize("regime", ["plain", "alibi", "softcap", "window16",
                                    "window50"])
def test_paged_int8_parity_all_regimes(regime):
    q, kp, vp, bt, lens = _data(seed=6)
    kq, ks, vq, vs, kd, vd = _int8_pools(kp, vp)
    kw, okw = {}, {}
    if regime == "alibi":
        slopes = np.asarray([2.0 ** -(i + 1) for i in range(4)], np.float32)
        kw["alibi_slopes"] = jnp.asarray(slopes)
        okw["slopes"] = slopes
    elif regime == "softcap":
        kw["softcap"] = okw["softcap"] = 30.0
    elif regime.startswith("window"):
        w = int(regime[len("window"):])
        kw["window"] = jnp.asarray(w, jnp.int32)
        okw["window"] = w
    args = (jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(lens))
    out = paged_attention(*args, k_scale=ks, v_scale=vs, interpret=True,
                          **kw)
    ref = paged_attention_reference(*args, k_scale=ks, v_scale=vs, **kw)
    want = _oracle(q, kd, vd, bt, lens, **okw)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-5)


def test_paged_int8_stacked_layer_pool():
    """int8 + layer_idx: per-layer scale slices ride the SAME block-table
    index map as the values — each layer dequantizes with its own rows."""
    L = 3
    q, kp, vp, bt, lens = _data(B=2, nbk=4, seed=7)
    kpl = np.stack([kp * (l + 1) for l in range(L)])
    vpl = np.stack([vp * 0.5 * (l + 1) for l in range(L)])
    kq, ks, vq, vs, kd, vd = _int8_pools(kpl, vpl)
    for li in range(L):
        out = paged_attention(jnp.asarray(q), kq, vq, jnp.asarray(bt),
                              jnp.asarray(lens), k_scale=ks, v_scale=vs,
                              layer_idx=jnp.asarray(li, jnp.int32),
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), _oracle(q, kd[li], vd[li], bt, lens),
            rtol=2e-5, atol=2e-5)


def test_paged_int8_guards():
    """int8 pools without scales (and scales without int8 pools) raise —
    a silent garbage read is the failure mode these guard against."""
    q, kp, vp, bt, lens = _data(B=1, nbk=2, seed=8)
    kq, ks, vq, vs, _, _ = _int8_pools(kp, vp)
    with pytest.raises(ValueError, match="scale"):
        paged_attention(jnp.asarray(q), kq, vq, jnp.asarray(bt),
                        jnp.asarray(lens), interpret=True)
    with pytest.raises(ValueError, match="int8"):
        paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                        jnp.asarray(bt), jnp.asarray(lens), k_scale=ks,
                        v_scale=vs, interpret=True)


# tier-2 (round-17 budget sweep, ~9s): the cheaper tier-1 cousins are
# test_paged_int8_parity_all_regimes (kernel+reference vs dequant oracle)
# and test_serving.test_int8_kv_pool_parity_jnp_and_kernel (engine-level
# token parity); scripts/tier2.sh runs this full-plumbing GQA+rotary leg
@pytest.mark.slow
def test_paged_int8_gqa_rotary_decode_kernel_vs_reference():
    """GQA + rotary through the full decode plumbing: a llama-ish
    paged_forward prefill writes the int8 pool (kv heads repeated to full
    heads upstream, rotary applied before the write), then ONE decode step
    runs twice — interpret=True (Pallas int8 kernel, in-VMEM dequant) and
    interpret=False (jnp reference, post-gather dequant). Same pool bytes,
    same logits, same greedy token."""
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.models.generation import ensure_scan_layout
    from deepspeed_tpu.serving.kv_cache import init_pool
    from deepspeed_tpu.serving.model_runner import paged_forward
    model, cfg = build_model(
        "llama-1.1b", hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, mlp_dim_override=64, vocab_size=64, max_seq_len=64,
        dtype=jnp.float32)
    # attention_impl stays "auto": a "reference" model serves on the gather
    # oracle whatever ``interpret`` says (serving/model_runner.py), and the
    # kernel leg must really hold the kernel
    assert cfg.attention_impl == "auto"
    ids = np.asarray([[3, 1, 4, 1, 5, 9, 2], [6, 5, 3, 5, 8, 9, 7]],
                     np.int32)
    params = ensure_scan_layout(
        model.init(jax.random.PRNGKey(1), {"input_ids": ids})["params"],
        cfg.num_layers)
    bs, nbk = 16, 2
    bt = np.asarray([[1, 2], [3, 4]], np.int32)
    T = ids.shape[1]
    run = lambda interp: _gqa_decode(cfg, params, ids, bt, bs, nbk, interp)
    logits_k, n_kernels = run(True)
    logits_r, n_ref_kernels = run(False)
    # one paged_attention pallas_call in the scanned layer body of the
    # kernel leg, none in the reference leg — a routing change cannot
    # quietly turn this into reference-vs-reference
    assert n_kernels >= 1 and n_ref_kernels == 0, (n_kernels, n_ref_kernels)
    np.testing.assert_allclose(logits_k, logits_r, rtol=2e-5, atol=2e-5)
    assert np.array_equal(logits_k[:, -1].argmax(-1),
                          logits_r[:, -1].argmax(-1))


def _gqa_decode(cfg, params, ids, bt, bs, nbk, interpret):
    from deepspeed_tpu.serving.kv_cache import init_pool
    from deepspeed_tpu.serving.model_runner import paged_forward
    B, T = ids.shape
    pools = init_pool(cfg, 8, bs, dtype=jnp.int8)
    zeros = jnp.zeros((B,), jnp.int32)
    # prefill (reference attention path for T>1) populates the int8 pool
    _, pools = paged_forward(cfg, params, jnp.asarray(ids), pools,
                             jnp.asarray(bt), zeros,
                             jnp.full((B,), T, jnp.int32), bs,
                             interpret=interpret)
    nxt = jnp.asarray([[7], [2]], jnp.int32)
    decode = lambda pools: paged_forward(
        cfg, params, nxt, pools, jnp.asarray(bt),
        jnp.full((B,), T, jnp.int32), jnp.full((B,), T + 1, jnp.int32), bs,
        interpret=interpret)[0]
    n_kernels = str(jax.make_jaxpr(decode)(pools)).count("pallas_call")
    return np.asarray(decode(pools)), n_kernels
