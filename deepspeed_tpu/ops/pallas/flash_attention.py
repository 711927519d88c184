"""Flash attention (FlashAttention-2 schedule) as Pallas TPU kernels.

Fills the slot of the reference's attention kernels: the fused softmax/attention
CUDA path (csrc/transformer/softmax_kernels.cu, attn kernels) and the Triton
block-sparse attention (deepspeed/ops/sparse_attention/) — block-sparse masks
plug in via the same block-skip mechanism used for causal masking here
(see ops/sparse.py).

Layout: inputs [batch, heads, seq, head_dim] are flattened to [B*H, S, D];
grid = (B*H, q_blocks, k_blocks) with the k dimension innermost (sequential on
TPU), carrying the online-softmax running max/denominator in VMEM scratch.
Backward recomputes probabilities from the saved logsumexp (no S×S
materialization) in two kernels: dq (grid over q blocks) and dk/dv (grid over
k blocks).

In-kernel score features (the reference fuses the same set into its softmax
kernels — masking, alibi, and the inference softmax_context path):
  * boolean masks, in two forms: a key/padding mask [B, 1, 1, Sk] rides as an
    O(S) per-key row broadcast over queries; anything with a query dimension
    rides as per-(q-block, k-block) tiles. Fully-masked tiles skip the MXU
    work entirely (same ``@pl.when`` block-skip as causal).
  * ALiBi bias from per-head slopes: the bias term slope * (k_pos - q_pos) is
    rebuilt from block indices via iota — no [B, H, S, S] materialization
    anywhere, forward or backward.
  * causal sliding-window masking: KV blocks strictly outside
    (q - window, q] are skipped at block level; the boundary blocks apply the
    exact per-token window.
  * logit softcap (Gemma-2): cap * tanh(s / cap) pre-softmax; the backward
    threads the tanh derivative through dS.
Attention dropout has NO kernel path (the router falls back to the jnp
reference for it).

Numerics: logits and softmax statistics in fp32; the P·V / dP matmuls cast P to
the value dtype (bf16), matching standard flash implementations. Query rows
with zero active keys produce ZEROS (and zero grads) — the jnp reference's
softmax of an all-masked row degenerates to uniform weights instead, so parity
holds on rows that attend at least one key (any real padding layout).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _causal_block_mask(s, iq, ik, block_q, block_k, offset):
    """Apply the triangular mask inside a diagonal block. s: [block_q, block_k].

    ``offset = k_len - q_len`` matches mha_reference's causal semantics: the
    last query row attends all keys (used for decode where Sk > S)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


def _scores(s, iq, ik, *, block_q, block_k, offset, causal, window, softcap,
            slope, kvm, qkm):
    """Shared fwd/bwd score pipeline on one [block_q, block_k] tile.

    Order matches mha_reference: scaled logits -> softcap -> +alibi bias ->
    causal/window/boolean masks to NEG_INF. Returns (s, dsoft) where dsoft
    is d(capped)/d(raw) for the backward (None when softcap is off)."""
    dsoft = None
    if softcap:
        t = jnp.tanh(s / softcap)
        s = t * softcap
        dsoft = 1.0 - t * t
    if slope is None and not window and kvm is None and qkm is None:
        # pure causal: mask only the diagonal block (interior blocks are
        # either fully attended or skipped by the grid-level `run` gate)
        if causal:
            diagonal = ik * block_k + block_k > iq * block_q + offset
            s = jax.lax.cond(
                diagonal,
                lambda x: _causal_block_mask(x, iq, ik, block_q, block_k,
                                             offset),
                lambda x: x, s)
        return s, dsoft
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if slope is not None:
        s = s + slope * (k_pos - q_pos).astype(jnp.float32)
    keep = None
    if causal:
        keep = k_pos <= q_pos
    if window:
        w = q_pos - k_pos < window
        keep = w if keep is None else keep & w
    if kvm is not None:                       # [1, block_k] broadcasts over q
        keep = kvm if keep is None else keep & kvm
    if qkm is not None:                       # [block_q, block_k]
        keep = qkm if keep is None else keep & qkm
    if keep is not None:
        s = jnp.where(keep, s, NEG_INF)
    return s, dsoft


def _unpack(refs, n_fixed, has_kvm, has_qkm, has_alibi):
    """Split a kernel's ref list into (fixed inputs, kvm, qkm, slopes, rest)."""
    fixed = refs[:n_fixed]
    i = n_fixed
    kvm_ref = qkm_ref = slopes_ref = None
    if has_kvm:
        kvm_ref = refs[i]
        i += 1
    if has_qkm:
        qkm_ref = refs[i]
        i += 1
    if has_alibi:
        slopes_ref = refs[i]
        i += 1
    return fixed, kvm_ref, qkm_ref, slopes_ref, refs[i:]


def _run_gate(causal, window, offset, block_q, block_k, iq, ik,
              kvm_ref, qkm_ref):
    """Block-level skip predicate for the (iq, ik) tile: out-of-triangle /
    out-of-window blocks and fully-masked mask tiles contribute nothing."""
    conds = []
    if causal:
        conds.append(ik * block_k <= iq * block_q + block_q - 1 + offset)
    if window:
        conds.append(ik * block_k + block_k - 1
                     >= iq * block_q + offset - (window - 1))
    if kvm_ref is not None:
        conds.append(jnp.any(kvm_ref[0] != 0))
    if qkm_ref is not None:
        conds.append(jnp.any(qkm_ref[0] != 0))
    if not conds:
        return True
    return functools.reduce(jnp.logical_and, conds)


def _mask_operands(kvm_ref, qkm_ref, slopes_ref):
    kvm = (kvm_ref[0] != 0) if kvm_ref is not None else None
    qkm = (qkm_ref[0] != 0) if qkm_ref is not None else None
    slope = slopes_ref[0][0, 0] if slopes_ref is not None else None
    return kvm, qkm, slope


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, offset, window,
                softcap, has_kvm, has_qkm, has_alibi):
    (q_ref, k_ref, v_ref), kvm_ref, qkm_ref, slopes_ref, rest = _unpack(
        refs, 3, has_kvm, has_qkm, has_alibi)
    o_ref, lse_ref, acc, m_scr, l_scr = rest
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    run = _run_gate(causal, window, offset, block_q, block_k, iq, ik,
                    kvm_ref, qkm_ref)
    guarded = causal or bool(window) or has_kvm or has_qkm

    @pl.when(run)
    def _compute():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        kvm, qkm, slope = _mask_operands(kvm_ref, qkm_ref, slopes_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, _ = _scores(s, iq, ik, block_q=block_q, block_k=block_k,
                       offset=offset, causal=causal, window=window,
                       softcap=softcap, slope=slope, kvm=kvm, qkm=qkm)
        m_prev = m_scr[:, :1]                       # [block_q, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        if guarded:
            # a fully-masked ROW inside a live tile has m_cur == NEG_INF and
            # exp(s - m_cur) would degenerate to 1 per entry; zero it so
            # l stays 0, the output finalizes to zeros, and the backward's
            # identical guard makes the grads the true gradient of THIS fwd
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_cur), 0.0)
        else:
            p = jnp.exp(s - m_cur)                  # [block_q, block_k] f32
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_cur

    last = (jnp.clip((iq * block_q + block_q - 1 + offset) // block_k, 0, nk - 1)
            if causal else nk - 1)

    @pl.when(ik == last)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-37)))


def _extra_specs(kvm, qkm, slopes, H, mask_per_head, block_q, block_k,
                 qi, ki):
    """BlockSpecs + operands for the optional mask/slope inputs. ``qi``/``ki``
    pick the q- and k-block grid indices out of (b, *grid) so the same
    builder serves the fwd (b, iq, ik) and dkv (b, ik, iq) grids."""
    specs, operands = [], []
    if kvm is not None:
        specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, i, j: (b // H, 0, (i, j)[ki])))
        operands.append(kvm)
    if qkm is not None:
        div = 1 if mask_per_head else H
        specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, i, j: (b // div, (i, j)[qi], (i, j)[ki])))
        operands.append(qkm)
    if slopes is not None:
        specs.append(pl.BlockSpec((1, 1, 128), lambda b, i, j: (b, 0, 0)))
        operands.append(slopes)
    return specs, operands


def _fwd(q3, k3, v3, kvm, qkm, slopes, H, causal, sm_scale, block_q, block_k,
         window, softcap, mask_per_head, interpret):
    BH, S, D = q3.shape
    Sk = k3.shape[1]
    nq, nk = S // block_q, Sk // block_k
    grid = (BH, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, offset=Sk - S, window=window, softcap=softcap,
        has_kvm=kvm is not None, has_qkm=qkm is not None,
        has_alibi=slopes is not None)
    extra_specs, extra_ops = _extra_specs(kvm, qkm, slopes, H, mask_per_head,
                                          block_q, block_k, qi=0, ki=1)
    with jax.named_scope("flash_attention_fwd"):
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            ] + extra_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
                jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            interpret=interpret,
        )(q3, k3, v3, *extra_ops)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_p(s, lse, guarded):
    """Recover P from the saved logsumexp. ``guarded`` zeroes masked entries
    explicitly: a fully-masked row's lse is itself NEG_INF-sized, and the
    plain exp(s - lse) would resurrect p=1 there."""
    if guarded:
        return jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
    return jnp.exp(s - lse)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, offset, window,
                   softcap, has_kvm, has_qkm, has_alibi):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), kvm_ref, qkm_ref,
     slopes_ref, rest) = _unpack(refs, 6, has_kvm, has_qkm, has_alibi)
    dq_ref, dq_acc = rest
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _run_gate(causal, window, offset, block_q, block_k, iq, ik,
                    kvm_ref, qkm_ref)
    guarded = causal or bool(window) or has_kvm or has_qkm

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]                # [block_q, 1]
        delta = delta_ref[0, 0][:, None]
        kvm, qkm, slope = _mask_operands(kvm_ref, qkm_ref, slopes_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, dsoft = _scores(s, iq, ik, block_q=block_q, block_k=block_k,
                           offset=offset, causal=causal, window=window,
                           softcap=softcap, slope=slope, kvm=kvm, qkm=qkm)
        p = _bwd_p(s, lse, guarded)                 # [block_q, block_k]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if dsoft is not None:
            ds = ds * dsoft
        ds = ds * sm_scale
        dq_acc[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    last = (jnp.clip((iq * block_q + block_q - 1 + offset) // block_k, 0, nk - 1)
            if causal else nk - 1)

    @pl.when(ik == last)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, offset, window,
                    softcap, has_kvm, has_qkm, has_alibi):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), kvm_ref, qkm_ref,
     slopes_ref, rest) = _unpack(refs, 6, has_kvm, has_qkm, has_alibi)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # same predicate as the fwd/dq grids: causal (q blocks strictly before
    # this k block never attend it) and window (q blocks entirely past the
    # window never attend it) are symmetric in (iq, ik)
    run = _run_gate(causal, window, offset, block_q, block_k, iq, ik,
                    kvm_ref, qkm_ref)
    guarded = causal or bool(window) or has_kvm or has_qkm

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        kvm, qkm, slope = _mask_operands(kvm_ref, qkm_ref, slopes_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, dsoft = _scores(s, iq, ik, block_q=block_q, block_k=block_k,
                           offset=offset, causal=causal, window=window,
                           softcap=softcap, slope=slope, kvm=kvm, qkm=qkm)
        p = _bwd_p(s, lse, guarded)                 # [block_q, block_k]
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if dsoft is not None:
            ds = ds * dsoft
        ds = ds * sm_scale                          # [block_q, block_k]
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, do3, lse, kvm, qkm, slopes, H, causal, sm_scale,
         block_q, block_k, window, softcap, mask_per_head, interpret):
    BH, S, D = q3.shape
    Sk = k3.shape[1]
    nq, nk = S // block_q, Sk // block_k
    # delta_i = rowsum(dO * O) — small elementwise pass, XLA fuses it
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]            # [BH, 1, S]
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=Sk - S, window=window,
                  softcap=softcap, has_kvm=kvm is not None,
                  has_qkm=qkm is not None, has_alibi=slopes is not None)

    qspec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    kspec_for_dq = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    row_q = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    extra_specs, extra_ops = _extra_specs(kvm, qkm, slopes, H, mask_per_head,
                                          block_q, block_k, qi=0, ki=1)
    with jax.named_scope("flash_attention_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **static),
            grid=(BH, nq, nk),
            in_specs=[qspec, kspec_for_dq, kspec_for_dq, qspec, row_q, row_q]
            + extra_specs,
            out_specs=[qspec],
            out_shape=[jax.ShapeDtypeStruct((BH, S, D), q3.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta, *extra_ops)[0]

    # dkv: grid dim 1 = k block, dim 2 (innermost) = q block
    qspec2 = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    kspec2 = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    row_q2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
    extra_specs2, extra_ops2 = _extra_specs(kvm, qkm, slopes, H,
                                            mask_per_head, block_q, block_k,
                                            qi=1, ki=0)
    with jax.named_scope("flash_attention_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, **static),
            grid=(BH, nk, nq),
            in_specs=[qspec2, kspec2, kspec2, qspec2, row_q2, row_q2]
            + extra_specs2,
            out_specs=[kspec2, kspec2],
            out_shape=[jax.ShapeDtypeStruct((BH, Sk, D), k3.dtype),
                       jax.ShapeDtypeStruct((BH, Sk, D), v3.dtype)],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta, *extra_ops2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, extras, H, causal, sm_scale, block_q, block_k,
           block_q_bwd, block_k_bwd, window, softcap, mask_per_head,
           interpret):
    out, _ = _flash_fwd(q, k, v, extras, H, causal, sm_scale, block_q,
                        block_k, block_q_bwd, block_k_bwd, window, softcap,
                        mask_per_head, interpret)
    return out


def _flash_fwd(q, k, v, extras, H, causal, sm_scale, block_q, block_k,
               block_q_bwd, block_k_bwd, window, softcap, mask_per_head,
               interpret):
    kvm, qkm, slopes = extras
    B, Hq, S, D = q.shape
    Sk = k.shape[2]
    q3 = q.reshape(B * Hq, S, D)
    k3 = k.reshape(B * Hq, Sk, D)
    v3 = v.reshape(B * Hq, Sk, D)
    o3, lse = _fwd(q3, k3, v3, kvm, qkm, slopes, H, causal, sm_scale,
                   block_q, block_k, window, softcap, mask_per_head,
                   interpret)
    return o3.reshape(B, Hq, S, D), (q3, k3, v3, o3, lse, kvm, qkm, slopes,
                                     (B, Hq, S, D))


def _flash_bwd(H, causal, sm_scale, block_q, block_k, block_q_bwd,
               block_k_bwd, window, softcap, mask_per_head, interpret,
               res, g):
    q3, k3, v3, o3, lse, kvm, qkm, slopes, (B, Hq, S, D) = res
    do3 = g.reshape(B * Hq, S, D)
    dq, dk, dv = _bwd(q3, k3, v3, o3, do3, lse, kvm, qkm, slopes, H, causal,
                      sm_scale, block_q_bwd, block_k_bwd, window, softcap,
                      mask_per_head, interpret)
    Sk = k3.shape[1]
    return (dq.reshape(B, Hq, S, D), dk.reshape(B, Hq, Sk, D),
            dv.reshape(B, Hq, Sk, D), (None, None, None))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _normalize_mask(mask, B, H, S, Sk):
    """Classify a boolean mask (any mha_reference-broadcastable shape) into
    the kernel's two forms: a key mask [B, 1, Sk] (no query dim — the
    padding case, O(S) memory) or query-block tiles [B(*H), S, Sk].
    Returns (kvm, qkm, mask_per_head); int32 because Mosaic tiles i32/f32
    uniformly where bool memrefs are not portable."""
    mask = jnp.asarray(mask)
    if mask.ndim > 4:
        raise ValueError(f"attention mask has rank {mask.ndim} > 4")
    mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)
    mb, mh, mq, mk = mask.shape
    if mb not in (1, B) or mh not in (1, H) or mq not in (1, S) \
            or mk not in (1, Sk):
        raise ValueError(f"mask shape {mask.shape} does not broadcast to "
                         f"{(B, H, S, Sk)}")
    if mh == 1 and mq == 1:
        kvm = jnp.broadcast_to(mask, (B, 1, 1, Sk)).reshape(B, 1, Sk)
        return kvm.astype(jnp.int32), None, False
    per_head = mh == H and H > 1
    if per_head:
        qkm = jnp.broadcast_to(mask, (B, H, S, Sk)).reshape(B * H, S, Sk)
    else:
        qkm = jnp.broadcast_to(mask, (B, 1, S, Sk)).reshape(B, S, Sk)
    return None, qkm.astype(jnp.int32), per_head


def flash_attention(q: jnp.ndarray,
                    k: jnp.ndarray,
                    v: jnp.ndarray,
                    *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    mask: Optional[jnp.ndarray] = None,
                    alibi_slopes=None,
                    window: int = 0,
                    softcap: float = 0.0,
                    block_q: int = 1024,
                    block_k: int = 1024,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Flash attention. q,k,v: [batch, heads, seq, head_dim] -> same shape.

    ``mask``: boolean, True = attend, any shape broadcastable to
    [B, H, Sq, Sk] (padding masks [B, 1, 1, Sk] ride an O(S) kernel input).
    ``alibi_slopes``: [H] per-head slopes; the bias slope * (k - q) is built
    from block indices in-kernel. ``window`` > 0 (causal only): sliding
    window with block-level skip. ``softcap``: Gemma-2 tanh logit cap.
    All features compose and are differentiable (fwd + bwd in-kernel).

    Forward and backward take independent block sizes: measured on v5e
    (gpt2-350m, seq 1024, D=64) 1024x1024 blocks win for BOTH passes — at
    seq<=1024 the whole sequence sits in one tile (no online-softmax loop),
    and per-step MXU occupancy dominates VMEM pressure up to that size.

    Falls back to the jnp reference when shapes don't tile (short
    sequences), or for a non-causal window: kernels want seq % block == 0
    and head_dim lane-friendly.
    """
    B, H, S, D = q.shape
    Sk = k.shape[-2]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    window = int(window) if window and window > 0 else 0
    softcap = float(softcap) if softcap else 0.0

    # non-TPU backends can only run the kernel interpreted — they take the
    # exact reference instead of crashing in pallas_call
    on_tpu = jax.default_backend() == "tpu"
    runnable = interpret or on_tpu

    # classify the mask (shape work only; materialization happens after the
    # tiling check passes)
    mask4 = None
    if mask is not None:
        mask4 = jnp.asarray(mask)
        if mask4.ndim > 4:
            raise ValueError(f"attention mask has rank {mask4.ndim} > 4")
        mask4 = mask4.reshape((1,) * (4 - mask4.ndim) + mask4.shape)
    has_qk_mask = mask4 is not None and (mask4.shape[1] != 1
                                         or mask4.shape[2] != 1)
    if has_qk_mask:
        # per-(q,k) tiles live in VMEM next to the f32 score tile: cap the
        # tile footprint (1024² i32 mask + f32 scores alone would be 8MB) —
        # for the backward kernels too, which carry even more live tiles
        block_q = min(block_q, 512)
        block_k = min(block_k, 512)
        block_q_bwd = min(block_q_bwd, 512) if block_q_bwd else None
        block_k_bwd = min(block_k_bwd, 512) if block_k_bwd else None

    def snap(seq_len: int, want: int) -> int:
        """Largest 16-multiple divisor of seq_len <= want (keeps e.g.
        seq=1280 on the kernel at block 256 instead of falling back dense)."""
        b = min(want, seq_len)
        while b > 16 and (seq_len % b or b % 16):
            b -= 16
        return b

    block_q = snap(S, block_q)
    block_k = snap(Sk, block_k)
    block_q_bwd = snap(S, block_q_bwd or max(block_q, 512))
    block_k_bwd = snap(Sk, block_k_bwd or max(block_k, 512))
    # fall back unless blocks tile the sequences AND are TPU-tile aligned
    # (sublane multiple of 16 covers bf16; lane dim D padded by Mosaic)
    aligned = all(s % b == 0 and b % 16 == 0
                  for s, b in [(S, block_q), (Sk, block_k),
                               (S, block_q_bwd), (Sk, block_k_bwd)]) \
        and D % 8 == 0
    reason = (None if aligned else
              f"S={S}/Sk={Sk}/D={D} do not tile (16-multiple blocks that "
              "divide the sequences, head_dim % 8 == 0)")
    if reason is None and window and not causal:
        reason = "a non-causal sliding window has no kernel path"
    if reason is not None or not runnable:
        if reason is not None and on_tpu:
            # the CPU (tests) takes the reference in silence; on a TPU the
            # choice is said once, with the reason
            from ...utils.logging import warning_once
            warning_once("flash_attention on TPU takes the jnp reference: "
                         f"{reason}")
        return _reference_fallback(q, k, v, causal, sm_scale, mask,
                                   alibi_slopes, window, softcap)
    kvm = qkm = None
    mask_per_head = False
    if mask4 is not None:
        kvm, qkm, mask_per_head = _normalize_mask(mask4, B, H, S, Sk)
    slopes3 = None
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(H)
        # [B*H, 1, 128] so each program reads its head's slope from its own
        # (1, 1, 128) tile — no dynamic VMEM scalar indexing
        slopes3 = jnp.broadcast_to(jnp.tile(sl, B)[:, None, None],
                                   (B * H, 1, 128))
    return _flash(q, k, v, (kvm, qkm, slopes3), H, causal, sm_scale, block_q,
                  block_k, block_q_bwd, block_k_bwd, window, softcap,
                  mask_per_head, interpret)


def _reference_fallback(q, k, v, causal, sm_scale, mask, alibi_slopes,
                        window, softcap):
    """Exact jnp path for untileable shapes: same feature semantics, the
    O(S²) way (bias/window materialized)."""
    from ..attention import alibi_bias_from_slopes, mha_reference, window_mask
    S, Sk = q.shape[-2], k.shape[-2]
    bias = None
    if alibi_slopes is not None:
        bias = alibi_bias_from_slopes(alibi_slopes, S, Sk)
    if window:
        wmask = window_mask(S, Sk, window)
        mask = wmask if mask is None else jnp.asarray(mask).astype(bool) & wmask
    return mha_reference(q, k, v, causal=causal, bias=bias, mask=mask,
                         sm_scale=sm_scale, softcap=softcap)
