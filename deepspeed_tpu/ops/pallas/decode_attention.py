"""Pallas decode attention — KV-cache attention that skips the dead tail.

Capability slot of the reference's fused decode kernels
(``csrc/transformer/inference/csrc/pt_binding.cpp:1703-1779``
``softmax_context``: attention against the preallocated KV workspace at the
CURRENT sequence length).  The jnp decode path scores the query against the
ENTIRE max_len cache every token; this kernel visits only
``ceil(cur_len / block_k)`` K/V blocks — both the compute AND the HBM DMA of
the dead tail are skipped, so per-token cost scales with the tokens generated
so far, not the preallocated maximum.

Mechanics (same machinery as block_sparse_attention's block-skip):
  * ``cur_len`` rides in as a prefetched scalar; the K/V BlockSpec index_map
    clamps dead grid steps to the last active block — Pallas's pipeline sees
    a repeated block index and elides the copy.
  * ``@pl.when(j < cnt)`` skips the FLOPs of dead steps.
  * heads are folded into each program in groups (batched MXU dots), so the
    decode loop issues B * nh/hg programs per k-block instead of B * nh.
  * causal + current-length + optional sliding-window masking is exact
    per-token, all driven by scalars so one compiled kernel serves the whole
    generation loop (no recompile as the sequence grows).
  * ALiBi (per-head slopes, bias rebuilt from indices) and the Gemma-2 tanh
    softcap run in-kernel — BLOOM/MPT and Gemma-2-class models decode on the
    kernel instead of silently falling back to the jnp path.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

__all__ = ["decode_attention", "untileable"]


def _kernel(scal_ref, q_ref, k_ref, v_ref, slopes_ref, o_ref, acc, m_scr,
            l_scr, *, hg, Tp, block_k, nk, sm_scale, softcap, has_alibi,
            stacked):
    j = pl.program_id(1)
    cnt, qstart, window = scal_ref[0], scal_ref[1], scal_ref[2]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(j < cnt)
    def _compute():
        q = q_ref[0]                                        # [hg, Tp, hd]
        k = k_ref[0, 0] if stacked else k_ref[0]            # [hg, bk, hd]
        v = v_ref[0, 0] if stacked else v_ref[0]
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * sm_scale
        if softcap:
            # Gemma-2 logit cap, BEFORE bias/masks (the decode-path order)
            s = jnp.tanh(s / softcap) * softcap
        # rows t of the (padded) q block are absolute position qstart + t;
        # cols are cache positions j*block_k + c
        q_abs = qstart + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if has_alibi:
            # per-head slope * (k - q) distance, built from indices — the
            # same in-kernel term as the flash kernel's prefill bias
            slope = slopes_ref[0][:, :1][:, None, :]        # [hg, 1, 1]
            s = s + slope * (k_pos - q_abs).astype(jnp.float32)
        keep = k_pos <= q_abs                               # causal w/ cache
        keep &= (q_abs - k_pos < window) | (window <= 0)    # sliding window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[:, :, :1] = (l_scr[:, :, :1] * alpha
                           + jnp.sum(p, axis=2, keepdims=True))
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:, :, :1] = m_cur

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _head_group(nh: int, block_k: int, hd: int, itemsize: int) -> int:
    """Heads per program: target ~1MB K blocks, largest divisor of nh."""
    target = max(1, (1 << 20) // (block_k * hd * itemsize))
    hg = 1
    for d in range(1, nh + 1):
        if nh % d == 0 and d <= target:
            hg = d
    return hg


def _snap_block_k(max_len: int, block_k: int) -> int:
    return block_k if max_len % block_k == 0 else int(np.gcd(max_len, block_k))


def untileable(T: int, max_len: int, hd: int, block_k: int = 512, *,
               interpret: bool = False) -> Optional[str]:
    """The kernel's tiling rules as a test made BEFORE the call: the reason
    these shapes cannot ride the kernel, or None when they can (so a
    compiler refusal is never read as "shapes don't tile")."""
    if T > 64:
        # decode-regime kernel: per-program scratch scales with T, and a
        # large-T call is the PREFILL, which is an ordinary causal attention
        # the MXU-shaped flash/jnp paths already handle well
        return f"decode_attention is for small T (got {T})"
    if max_len % block_k != 0 and _snap_block_k(max_len, block_k) < 128:
        return f"max_len {max_len} has no >=128 block tiling"
    if hd % 8 != 0 and not interpret:
        # Mosaic pads sub-128 lane dims (64 measured fine on v5e); truly odd
        # head dims take the jnp path
        return f"head_dim {hd} does not tile"
    return None


def decode_attention(q: jnp.ndarray,
                     k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray,
                     cur_len: jnp.ndarray,
                     *,
                     window=None,
                     sm_scale: Optional[float] = None,
                     block_k: int = 512,
                     layer_idx=None,
                     alibi_slopes=None,
                     softcap: float = 0.0,
                     interpret: bool = False) -> jnp.ndarray:
    """Attention of T new tokens against a preallocated KV cache.

    q: [B, nh, T, hd] — queries for absolute positions
       [cur_len - T, cur_len).
    k_cache/v_cache: [B, nh, max_len, hd]; positions >= cur_len are dead.
       With ``layer_idx`` (traced i32 ok): [L, B, nh, max_len, hd] — the
       kernel's index_map picks layer blocks directly out of the stacked
       cache, so a scan-carried cache needs NO materialized per-layer slice.
    cur_len: i32 scalar (traced ok), total valid length INCLUDING the T new
       tokens.  window: python int or traced i32 scalar; <= 0 means global.
    alibi_slopes: [nh] per-head slopes — the bias slope * (k_pos - q_pos)
       is built from indices in-kernel (BLOOM/MPT decode stays on the
       kernel). softcap: Gemma-2 tanh logit cap, STATIC float (it changes
       the compiled math). Returns [B, nh, T, hd].

    Raises ValueError (the :func:`untileable` reason) when shapes can't
    tile (tiny head_dim / max_len) — callers ask :func:`untileable` FIRST
    and take the jnp path on a reason.
    """
    B, nh, T, hd = q.shape
    stacked = layer_idx is not None
    max_len = k_cache.shape[3 if stacked else 2]
    reason = untileable(T, max_len, hd, block_k, interpret=interpret)
    if reason is not None:
        raise ValueError(reason)
    block_k = _snap_block_k(max_len, block_k)
    nk = max_len // block_k
    Tp = max(8, -(-T // 8) * 8)                  # sublane-pad the q rows
    hg = _head_group(nh, block_k, hd, k_cache.dtype.itemsize)
    ng = nh // hg
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)

    qf = q.reshape(B * nh, T, hd)
    if Tp != T:
        qf = jnp.pad(qf, ((0, 0), (0, Tp - T), (0, 0)))
    qf = qf.reshape(B * ng, hg, Tp, hd)

    cur = jnp.asarray(cur_len, jnp.int32)
    cnt = (cur + block_k - 1) // block_k
    win = jnp.asarray(0 if window is None else window, jnp.int32)
    li = jnp.asarray(0 if layer_idx is None else layer_idx, jnp.int32)
    scal = jnp.stack([cnt, cur - T, win.reshape(()), li.reshape(())])
    softcap = float(softcap) if softcap else 0.0
    has_alibi = alibi_slopes is not None
    if has_alibi:
        # [B*ng, hg, 128]: program g reads its head group's slopes from its
        # own tile (no dynamic VMEM scalar indexing)
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(ng, hg)
        slopes = jnp.broadcast_to(sl[None, :, :, None],
                                  (B, ng, hg, 128)).reshape(B * ng, hg, 128)
    else:
        slopes = jnp.zeros((1, 1, 128), jnp.float32)    # placeholder

    # dead grid steps clamp to the last active block: a repeated index means
    # the pipeline skips the K/V copy (the DMA half of the block skip)
    if stacked:
        L = k_cache.shape[0]
        kf = k_cache.reshape(L, B * ng, hg, max_len, hd)
        vf = v_cache.reshape(L, B * ng, hg, max_len, hd)
        kv_spec = pl.BlockSpec(
            (1, 1, hg, block_k, hd),
            lambda g, j, s: (s[3], g, 0, jnp.minimum(j, s[0] - 1), 0))
    else:
        kf = k_cache.reshape(B * ng, hg, max_len, hd)
        vf = v_cache.reshape(B * ng, hg, max_len, hd)
        kv_spec = pl.BlockSpec(
            (1, hg, block_k, hd),
            lambda g, j, s: (g, 0, jnp.minimum(j, s[0] - 1), 0))
    slopes_spec = (pl.BlockSpec((1, hg, 128), lambda g, j, s: (g, 0, 0))
                   if has_alibi else
                   pl.BlockSpec((1, 1, 128), lambda g, j, s: (0, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * ng, nk),
        in_specs=[
            pl.BlockSpec((1, hg, Tp, hd), lambda g, j, s: (g, 0, 0, 0)),
            kv_spec,
            kv_spec,
            slopes_spec,
        ],
        out_specs=pl.BlockSpec((1, hg, Tp, hd), lambda g, j, s: (g, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hg, Tp, hd), jnp.float32),
            pltpu.VMEM((hg, Tp, 128), jnp.float32),
            pltpu.VMEM((hg, Tp, 128), jnp.float32),
        ],
    )
    with jax.named_scope("decode_attention"):
        out = pl.pallas_call(
            partial(_kernel, hg=hg, Tp=Tp, block_k=block_k, nk=nk,
                    sm_scale=scale, softcap=softcap, has_alibi=has_alibi,
                    stacked=stacked),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B * ng, hg, Tp, hd), q.dtype),
            interpret=interpret,
        )(scal, qf, kf, vf, slopes)
    return out.reshape(B, nh, Tp, hd)[:, :, :T]
