"""Pallas paged-attention decode kernel — KV blocks gathered via block table.

The serving subsystem (deepspeed_tpu/serving/) keeps the KV cache as a POOL
of fixed-size blocks shared by every in-flight sequence; a per-sequence
*block table* maps logical block j to a physical pool block. The decode
step then needs attention of one fresh query token per sequence against a
K/V that is physically scattered across the pool. This kernel performs the
gather INSIDE the pipeline: the K/V BlockSpec index_map reads the block
table (a prefetched scalar array) to pick the physical block for grid step
j, so the only HBM traffic is the ``ceil(ctx_len / block_size)`` live
blocks of each sequence — no materialized per-sequence contiguous copy,
and per-token cost scales with the tokens each sequence has generated, not
with the pool size.

Capability slot of the reference's fused ``softmax_context`` decode kernels
(csrc/transformer/inference/csrc/pt_binding.cpp:1703-1779) generalized to
the vLLM-style paged layout; the mechanics (clamped index_map elides dead
copies, ``@pl.when`` skips dead FLOPs, online-softmax scratch carries
m/l across blocks) are shared with ops/pallas/decode_attention.py.

In-kernel score features (parity with the flash/decode kernels): ALiBi via
per-head slopes, Gemma-2 tanh softcap, causal masking by per-sequence
context length, and a sliding window. The jnp oracle
:func:`paged_attention_reference` computes the identical math by dense
gather — the CPU fallback and the parity target for the interpret-mode
tests.

The pool operand is row-major ``[L?, nh, num_blocks, block_size, hd]`` and
the kernel reads it where it lies. Whoever writes the pool has to leave it
so: ``serving.model_runner`` updates it in place with dynamic-update-slices
because the layout the chip's compiler gives a scatter's operand (slots
major) had the whole pool copied to this one before every call.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _head_group
from .flash_attention import NEG_INF

__all__ = ["paged_attention", "paged_attention_reference", "untileable"]

#: query rows per program — a single decode token is broadcast to the
#: sublane minimum so every operand is a legal (>=8)x128 tile
_QROWS = 8


def _kernel(bt_ref, lens_ref, misc_ref, q_ref, k_ref, v_ref, *rest, hg, bs,
            nbk, sm_scale, softcap, has_alibi, stacked, quant):
    if quant:
        ks_ref, vs_ref, slopes_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        slopes_ref, o_ref, acc, m_scr, l_scr = rest
    b, j = pl.program_id(0), pl.program_id(2)
    ctx = lens_ref[b]
    window = misc_ref[0]
    cnt = (ctx + bs - 1) // bs                    # live blocks of seq b

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(j < cnt)
    def _compute():
        q = q_ref[0, 0]                                     # [hg, 8, hd]
        k = k_ref[0, :, 0] if stacked else k_ref[:, 0]      # [hg, bs, hd]
        v = v_ref[0, :, 0] if stacked else v_ref[:, 0]
        if quant:
            # int8 tier (round 17): the DMA moved int8 rows + one f32
            # scale per (head, slot); dequantize HERE, on the block
            # already in VMEM — only int8 crossed HBM. The scale rows
            # arrive [hg, 1, bs] (slots on the LANE axis, the layout the
            # chip's compiler takes), which is the score tile's own
            # layout: q.(k_i * s_i) == (q.k_i) * s_i, so the K scale
            # multiplies the scores and the V scale the probabilities —
            # no lane->sublane relayout, and the int8 -> q.dtype convert
            # is exact (|int8| <= 127)
            ks = ks_ref[0, :, 0] if stacked else ks_ref[:, 0]   # [hg, 1, bs]
            vs = vs_ref[0, :, 0] if stacked else vs_ref[:, 0]
            k = k.astype(jnp.float32).astype(q.dtype)
            v = v.astype(jnp.float32).astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        if quant:
            s = s * ks
        s = s * sm_scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        # one real query at absolute (logical) position ctx - 1, broadcast
        # over the 8 padded rows; keys of block j cover logical positions
        # [j*bs, (j+1)*bs) regardless of which PHYSICAL block the table
        # routed the DMA to
        q_abs = ctx - 1
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if has_alibi:
            slope = slopes_ref[0][:, :1][:, None, :]        # [hg, 1, 1]
            s = s + slope * (k_pos - q_abs).astype(jnp.float32)
        keep = k_pos <= q_abs                               # causal + dead tail
        keep &= (q_abs - k_pos < window) | (window <= 0)    # sliding window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[:, :, :1] = (l_scr[:, :, :1] * alpha
                           + jnp.sum(p, axis=2, keepdims=True))
        pv = p * vs if quant else p
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            pv.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:, :, :1] = m_cur

    @pl.when(j == nbk - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        o_ref[0, 0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def untileable(q_shape, pool_shape, *, stacked: bool, quant: bool,
               interpret: bool = False) -> Optional[str]:
    """The kernel's tiling rules as a test made BEFORE the call: the reason
    these shapes cannot ride the kernel, or None when they can. Dispatchers
    route on this instead of catching the kernel's errors, so a refusal by
    the chip's compiler can never be read as "shapes don't tile"."""
    T, hd = q_shape[2], q_shape[3]
    if T != 1:
        return (f"paged_attention decodes 1 token/seq (got T={T}); "
                "prefill rides the gather reference/flash paths")
    if interpret:
        return None
    bs = pool_shape[3 if stacked else 2]
    if bs % 8 != 0:
        return (f"block_size {bs} does not tile (sublane multiple of 8 "
                "required)")
    if hd % 8 != 0:
        return f"head_dim {hd} does not tile"
    if quant and bs % 32 != 0:
        return (f"block_size {bs} does not tile the int8 KV tier (int8 "
                "sublane multiple of 32 required)")
    return None


def paged_attention(q: jnp.ndarray,
                    k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray,
                    block_tables: jnp.ndarray,
                    context_lens: jnp.ndarray,
                    *,
                    sm_scale: Optional[float] = None,
                    alibi_slopes=None,
                    softcap: float = 0.0,
                    window=None,
                    layer_idx=None,
                    k_scale=None,
                    v_scale=None,
                    interpret: bool = False) -> jnp.ndarray:
    """One decode token per sequence against a paged KV pool.

    q: [B, nh, 1, hd] — each sequence's fresh query, at logical position
       ``context_lens[b] - 1`` (context_lens INCLUDES the new token).
    k_pool/v_pool: [nh, num_blocks, block_size, hd]; with ``layer_idx``
       (traced i32 ok) the stacked [L, nh, num_blocks, block_size, hd]
       layout — the index_map picks the layer straight out of the
       scan-carried pool, no materialized per-layer slice.
    k_scale/v_scale: the int8 tier (round 17) — pools are int8 in the
       ``quant_format.kv_quantize`` layout and these carry the f32
       per-(layer, head, slot) scales (any shape that reshapes to the
       pool's [..., num_blocks, block_size], e.g. init_pool's
       [L, nh, num_slots, 1]). The scale blocks ride the SAME block-table
       index_map as k/v and the dequant happens in-kernel, so the HBM
       read is int8 + 4 bytes/slot — no pool-slice f32 copy exists.
    block_tables: [B, max_blocks] i32 — logical block j of sequence b
       lives in physical pool block ``block_tables[b, j]``. Entries past
       the live count are never DMA'd (the index_map clamps them to the
       last live block, which the pipeline elides as a repeated index).
    context_lens: [B] i32. ``window``: python int or traced i32, <= 0
       means global. ``alibi_slopes``: [nh] per-head slopes (in-kernel
       bias slope * (k_pos - q_pos)). ``softcap``: Gemma-2 tanh cap
       (STATIC float — it changes the compiled math).

    Returns [B, nh, 1, hd]. Raises ValueError (the :func:`untileable`
    reason) when shapes can't tile — callers ask :func:`untileable` FIRST
    and route to :func:`paged_attention_reference` on a reason, so an
    error out of this function is never mistaken for one.
    """
    B, nh, T, hd = q.shape
    stacked = layer_idx is not None
    quant = k_scale is not None
    reason = untileable(q.shape, k_pool.shape, stacked=stacked, quant=quant,
                        interpret=interpret)
    if reason is not None:
        raise ValueError(reason)
    bs = k_pool.shape[3 if stacked else 2]
    if quant:
        if k_pool.dtype != jnp.int8:
            raise ValueError("k_scale/v_scale given but the pool dtype is "
                             f"{k_pool.dtype} — scales pair with int8 pools")
        # [..., num_blocks, 1, block_size]: the unit axis before the lane
        # axis makes the scale block's last two dims equal the array's —
        # the Mosaic block rule a (1, block_size) tile of a
        # (num_blocks, block_size) array breaks
        sc_shape = k_pool.shape[:-2] + (1, bs)
        ks_pool = jnp.asarray(k_scale, jnp.float32).reshape(sc_shape)
        vs_pool = jnp.asarray(v_scale, jnp.float32).reshape(sc_shape)
    elif k_pool.dtype == jnp.int8:
        raise ValueError("int8 KV pool needs k_scale/v_scale "
                         "(quant_format.kv_quantize layout)")
    nbk = block_tables.shape[1]
    hg = _head_group(nh, bs, hd, k_pool.dtype.itemsize)
    ng = nh // hg
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)
    softcap = float(softcap) if softcap else 0.0

    # broadcast the single query row to the sublane minimum (all 8 rows are
    # the real query; row 0 is read back)
    qf = jnp.broadcast_to(q.reshape(B, ng, hg, 1, hd), (B, ng, hg, _QROWS, hd))

    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(())
    li = jnp.asarray(0 if layer_idx is None else layer_idx,
                     jnp.int32).reshape(())
    misc = jnp.stack([win, li])

    # dead grid steps clamp to the sequence's last live block: a repeated
    # physical index means the pipeline skips the K/V copy
    def _phys(j, bt_s, lens_s, b):
        last = jnp.maximum((lens_s[b] + bs - 1) // bs - 1, 0)
        return bt_s[b, jnp.minimum(j, last)]

    if stacked:
        kv_spec = pl.BlockSpec(
            (1, hg, 1, bs, hd),
            lambda b, g, j, bt_s, lens_s, misc_s: (
                misc_s[1], g, _phys(j, bt_s, lens_s, b), 0, 0))
    else:
        kv_spec = pl.BlockSpec(
            (hg, 1, bs, hd),
            lambda b, g, j, bt_s, lens_s, misc_s: (
                g, _phys(j, bt_s, lens_s, b), 0, 0))
    q_spec = pl.BlockSpec((1, 1, hg, _QROWS, hd),
                          lambda b, g, j, *_: (b, g, 0, 0, 0))

    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qf, k_pool, v_pool]
    if quant:
        # scale blocks follow the K/V through the SAME clamped
        # block-table index_map (one f32 per slot, slots on the lane axis)
        if stacked:
            sc_spec = pl.BlockSpec(
                (1, hg, 1, 1, bs),
                lambda b, g, j, bt_s, lens_s, misc_s: (
                    misc_s[1], g, _phys(j, bt_s, lens_s, b), 0, 0))
        else:
            sc_spec = pl.BlockSpec(
                (hg, 1, 1, bs),
                lambda b, g, j, bt_s, lens_s, misc_s: (
                    g, _phys(j, bt_s, lens_s, b), 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [ks_pool, vs_pool]
    has_alibi = alibi_slopes is not None
    if has_alibi:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(ng, hg)
        slopes = jnp.broadcast_to(sl[:, :, None], (ng, hg, 128))
        in_specs.append(pl.BlockSpec((1, hg, 128),
                                     lambda b, g, j, *_: (g, 0, 0)))
        operands.append(slopes)
    else:
        # constant placeholder so the kernel arity is static
        in_specs.append(pl.BlockSpec((1, 1, 128), lambda b, g, j, *_: (0, 0, 0)))
        operands.append(jnp.zeros((1, 1, 128), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, ng, nbk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, hg, _QROWS, hd),
                               lambda b, g, j, *_: (b, g, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hg, _QROWS, hd), jnp.float32),
            pltpu.VMEM((hg, _QROWS, 128), jnp.float32),
            pltpu.VMEM((hg, _QROWS, 128), jnp.float32),
        ],
    )
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            partial(_kernel, hg=hg, bs=bs, nbk=nbk, sm_scale=scale,
                    softcap=softcap, has_alibi=has_alibi, stacked=stacked,
                    quant=quant),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, ng, hg, _QROWS, hd), q.dtype),
            interpret=interpret,
        )(bt, lens, misc, *operands)
    return out[:, :, :, :1].reshape(B, nh, 1, hd)


def paged_attention_reference(q: jnp.ndarray,
                              k_pool: jnp.ndarray,
                              v_pool: jnp.ndarray,
                              block_tables: jnp.ndarray,
                              context_lens: jnp.ndarray,
                              *,
                              sm_scale: Optional[float] = None,
                              alibi_slopes=None,
                              softcap: float = 0.0,
                              window=None,
                              layer_idx=None,
                              k_scale=None,
                              v_scale=None,
                              q_start=None) -> jnp.ndarray:
    """jnp oracle / CPU fallback: dense gather through the block table,
    then exactly the decode-path attention math (f32 scores, softcap
    before the ALiBi bias before the -1e30 masks, f32 softmax).

    Generalizes over the kernel: q may carry T > 1 query tokens (the
    PREFILL of a paged sequence — queries at logical positions
    [ctx - T, ctx), or [q_start, q_start + T) when ``q_start`` [B] is
    given: a bucket-PADDED prefill carries trailing garbage queries past
    ctx whose outputs the caller discards), so one definition serves
    prefill and decode.

    int8 tier (``k_scale``/``v_scale``, the kernel's layout): the gather
    moves int8 rows and their scales, and the dequant happens AFTER the
    gather — O(attended tokens), not O(pool). Gather-then-dequantize is
    elementwise identical to the round-12 dequantize-then-gather, so
    greedy decodes are token-for-token unchanged.
    """
    B, nh, T, hd = q.shape
    quant = k_scale is not None
    if quant:
        k_scale = jnp.asarray(k_scale, jnp.float32).reshape(
            k_pool.shape[:-1])
        v_scale = jnp.asarray(v_scale, jnp.float32).reshape(
            v_pool.shape[:-1])
    if layer_idx is not None:
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer_idx, 0,
                                              keepdims=False)
        v_pool = jax.lax.dynamic_index_in_dim(v_pool, layer_idx, 0,
                                              keepdims=False)
        if quant:
            k_scale = jax.lax.dynamic_index_in_dim(k_scale, layer_idx, 0,
                                                   keepdims=False)
            v_scale = jax.lax.dynamic_index_in_dim(v_scale, layer_idx, 0,
                                                   keepdims=False)
    bs = k_pool.shape[2]
    nbk = block_tables.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)

    # gather [nh, B, nbk, bs, hd] -> [B, nh, K, hd], K = nbk * bs logical
    k = jnp.transpose(k_pool[:, bt], (1, 0, 2, 3, 4)).reshape(
        B, nh, nbk * bs, hd)
    v = jnp.transpose(v_pool[:, bt], (1, 0, 2, 3, 4)).reshape(
        B, nh, nbk * bs, hd)
    if quant:
        ks = jnp.transpose(k_scale[:, bt], (1, 0, 2, 3)).reshape(
            B, nh, nbk * bs)
        vs = jnp.transpose(v_scale[:, bt], (1, 0, 2, 3)).reshape(
            B, nh, nbk * bs)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)

    if q_start is not None:
        q_abs = (jnp.asarray(q_start, jnp.int32).reshape(B)[:, None]
                 + jnp.arange(T))                          # [B, T]
    else:
        q_abs = (lens[:, None] - T + jnp.arange(T))        # [B, T]
    k_pos = jnp.arange(nbk * bs)                           # [K]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap:
        from ..attention import apply_softcap
        s = apply_softcap(s, softcap)
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(nh)
        dist = (k_pos[None, None, :] - q_abs[:, :, None]).astype(jnp.float32)
        s = s + sl[None, :, None, None] * dist[:, None]
    keep = k_pos[None, None, :] <= q_abs[:, :, None]       # [B, T, K]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        keep = keep & ((q_abs[:, :, None] - k_pos[None, None, :] < win)
                       | (win <= 0))
    s = jnp.where(keep[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", prob, v)
