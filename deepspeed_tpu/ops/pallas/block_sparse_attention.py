"""Block-sparse flash attention — layout-driven block skip in Pallas.

Executes the sparsity layouts from ops/sparse_attention.py (Fixed / BigBird /
BSLongformer / Variable / LocalSlidingWindow) the way the reference's Triton
sdd/dsd kernels do (deepspeed/ops/sparse_attention/matmul.py:6, softmax.py):
inactive blocks are never visited — attention cost scales with layout
density, which is the mechanism behind the reference's "10x longer sequences"
claim (docs/_pages/training.md:108).

The sparsity is realized at the GRID level, not by masking: per (head,
q-block) the host builds the list of active k-blocks, the innermost grid
dimension runs over that list (padded to the max count), and the k/v
BlockSpec index maps read the list from scalar-prefetch SMEM — so skipped
blocks cost neither MXU work NOR the K/V tile DMA (~128KB/block that
otherwise caps the win at memory bandwidth). This is the splash-attention
scheduling shape, rebuilt for the layout zoo.

Inside a visited block, the LAYOUT's fine granularity (SparsityConfig.block,
often 16) is applied element-exactly. TPU lowering constraints probed on v5e
(dynamic lane slices + dynamic VMEM scalar loads crash Mosaic; SMEM scalar
reads and BlockSpec-mapped fetches are fine) dictate the mechanics:
  * q selection rides the BlockSpec: the layout is host-expanded to exactly
    8 rows per kernel q block ([H, nq*8, nf] — tile-legal (1, 8, nf) blocks);
  * k selection is arithmetic: an iota-built selector
    W[f, c] = ((kb*block_k + c)//fine == f) turns the fine row into per-lane
    flags via one [8, nf] x [nf, block_k] matmul (~1% of block FLOPs).

Backward follows flash_attention.py's two-kernel split: dq reuses the
q->active-k lists; dk/dv uses the transposed k->active-q lists.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _causal_block_mask

__all__ = ["block_sparse_flash_attention"]


def _window_block_mask(s, iq, kb, block_q, block_k, window):
    """Exact per-token sliding window: keep logits with q_pos - k_pos <
    window (the causal side is _causal_block_mask's job)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos - k_pos < window, s, NEG_INF)


def _layout_mask(sub8, s, kb, fine, block_q, block_k):
    """Apply the fine layout to logits s [block_q, block_k]; kb is the
    (dynamic) k-block index, sub8 the q side's [8, nf] fine rows."""
    nf = sub8.shape[1]
    f_iota = jax.lax.broadcasted_iota(jnp.int32, (nf, block_k), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (nf, block_k), 1)
    sel = ((kb * block_k + c_iota) // fine == f_iota).astype(jnp.float32)
    mask8 = jax.lax.dot(sub8.astype(jnp.float32), sel,
                        preferred_element_type=jnp.float32)   # [8, block_k]
    mask = jnp.repeat(mask8 > 0.5, block_q // 8, axis=0)
    return jnp.where(mask, s, NEG_INF)


def _fwd_kernel(cnt_ref, idx_ref, lay_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, acc, m_scr, l_scr,
                *, H, nq, maxk, sm_scale, causal, block_q, block_k, fine,
                window, layout_exact):
    b, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h = b % H
    row = h * nq + iq

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    kb = idx_ref[row * maxk + j]
    run = j < cnt_ref[row]

    @pl.when(run)
    def _compute():
        sub8 = lay_ref[0]                               # [8, nf] i32, static
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if layout_exact:
            s = _layout_mask(sub8, s, kb, fine, block_q, block_k)
        if causal:
            s = _causal_block_mask(s, iq, kb, block_q, block_k, 0)
        if window:
            s = _window_block_mask(s, iq, kb, block_q, block_k, window)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows with nothing active so far keep m = NEG_INF; exp underflows to 0
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_cur

    @pl.when(j == maxk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-37)))


def _bwd_dq_kernel(cnt_ref, idx_ref, lay_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc,
                   *, H, nq, maxk, sm_scale, causal, block_q, block_k, fine,
                   window, layout_exact):
    b, iq, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row = (b % H) * nq + iq

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    kb = idx_ref[row * maxk + j]
    run = j < cnt_ref[row]

    @pl.when(run)
    def _compute():
        sub8 = lay_ref[0]
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if layout_exact:
            s = _layout_mask(sub8, s, kb, fine, block_q, block_k)
        if causal:
            s = _causal_block_mask(s, iq, kb, block_q, block_k, 0)
        if window:
            s = _window_block_mask(s, iq, kb, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(cnt_ref, idx_ref, lay_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, H, nk, maxq, sm_scale, causal, block_q, block_k, fine,
                    window, layout_exact):
    b, ik, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row = (b % H) * nk + ik

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qb = idx_ref[row * maxq + j]
    run = j < cnt_ref[row]

    @pl.when(run)
    def _compute():
        sub8 = lay_ref[0]                   # fine rows of ACTIVE q block qb
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if layout_exact:
            s = _layout_mask(sub8, s, ik, fine, block_q, block_k)
        if causal:
            s = _causal_block_mask(s, qb, ik, block_q, block_k, 0)
        if window:
            s = _window_block_mask(s, qb, ik, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side schedule building
# ---------------------------------------------------------------------------

def _expand_rows8(layout: np.ndarray, block_q: int, fine: int) -> np.ndarray:
    """[H, nf, nf] fine layout -> [H, nq*8, nf]: exactly 8 rows per kernel q
    block; exact when block_q//8 divides fine (enforced by the caller)."""
    H, nfq, nf = layout.shape
    S = nfq * fine
    nq = S // block_q
    step = block_q // 8
    rows = (np.arange(nq * 8) * step) // fine
    return np.ascontiguousarray(layout[:, rows, :])


def _active_lists(layout: np.ndarray, fine: int, block_q: int, block_k: int
                  ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Coarsen the fine layout to kernel blocks and build, per (head,
    q-block), the padded list of active k-block indices.
    Returns (counts [H*nq] i32, indices [H*nq*maxk] i32, maxk)."""
    H, nfq, nfk = layout.shape
    rq, rk = block_q // fine, block_k // fine
    nq, nk = nfq // rq, nfk // rk
    coarse = layout.reshape(H, nq, rq, nk, rk).any(axis=(2, 4))   # [H,nq,nk]
    counts = coarse.sum(axis=2).astype(np.int32)                  # [H, nq]
    maxk = max(int(counts.max()), 1)
    idx = np.zeros((H, nq, maxk), np.int32)
    for h in range(H):
        for i in range(nq):
            act = np.nonzero(coarse[h, i])[0]
            idx[h, i, :len(act)] = act
            if len(act):
                idx[h, i, len(act):] = act[-1]
    return counts.reshape(-1), idx.reshape(-1), maxk


def _fwd(q3, k3, v3, lay8, cnt, idx, maxk, H, causal, sm_scale, block_q,
         block_k, fine, window, layout_exact, interpret):
    BH, S, D = q3.shape
    nq = S // block_q
    nf = lay8.shape[2]
    kernel = functools.partial(
        _fwd_kernel, H=H, nq=nq, maxk=maxk, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, fine=fine, window=window,
        layout_exact=layout_exact)

    def kv_index(b, i, j, cnt_ref, idx_ref):
        return (b, idx_ref[((b % H) * nq + i) * maxk + j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, maxk),
        in_specs=[
            pl.BlockSpec((1, 8, nf), lambda b, i, j, c, x: (b % H, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, c, x: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    with jax.named_scope("block_sparse_attention_fwd"):
        o, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((BH, S, D), q3.dtype),
                jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
            ],
            interpret=interpret,
        )(cnt, idx, lay8, q3, k3, v3)
    return o, lse


def _bwd(q3, k3, v3, o3, do3, lse, lay8, sched, H, causal, sm_scale, block_q,
         block_k, fine, window, layout_exact, interpret):
    BH, S, D = q3.shape
    nq, nk = S // block_q, S // block_k
    nf = lay8.shape[2]
    cnt, idx, maxk, cnt_t, idx_t, maxq = sched
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]

    def kv_index(b, i, j, c, x):
        return (b, x[((b % H) * nq + i) * maxk + j], 0)

    grid_dq = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq, maxk),
        in_specs=[
            pl.BlockSpec((1, 8, nf), lambda b, i, j, c, x: (b % H, i, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_q, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, c, x: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, c, x: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j, c, x: (b, i, 0))],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
    )
    with jax.named_scope("block_sparse_attention_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, H=H, nq=nq, maxk=maxk,
                              sm_scale=sm_scale, causal=causal,
                              block_q=block_q, block_k=block_k, fine=fine,
                              window=window, layout_exact=layout_exact),
            grid_spec=grid_dq,
            out_shape=[jax.ShapeDtypeStruct((BH, S, D), q3.dtype)],
            interpret=interpret,
        )(cnt, idx, lay8, q3, k3, v3, do3, lse, delta)[0]

    # dkv: grid over k blocks x active q blocks (transposed lists); every
    # q-side tensor (q, do, lse, delta) and the layout rows are fetched via
    # the active-q index
    def q_index(b, i, j, c, x):
        return (b, x[((b % H) * nk + i) * maxq + j], 0)

    def row_index(b, i, j, c, x):
        return (b, 0, x[((b % H) * nk + i) * maxq + j])

    grid_dkv = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nk, maxq),
        in_specs=[
            pl.BlockSpec((1, 8, nf),
                         lambda b, i, j, c, x:
                         (b % H, x[((b % H) * nk + i) * maxq + j], 0)),
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_q), row_index),
            pl.BlockSpec((1, 1, block_q), row_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i, j, c, x: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j, c, x: (b, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
    )
    with jax.named_scope("block_sparse_attention_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, H=H, nk=nk, maxq=maxq,
                              sm_scale=sm_scale, causal=causal,
                              block_q=block_q, block_k=block_k, fine=fine,
                              window=window, layout_exact=layout_exact),
            grid_spec=grid_dkv,
            out_shape=[jax.ShapeDtypeStruct((BH, S, D), k3.dtype),
                       jax.ShapeDtypeStruct((BH, S, D), v3.dtype)],
            interpret=interpret,
        )(cnt_t, idx_t, lay8, q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _bs_flash(q, k, v, prefetch, sched_meta, H, causal, sm_scale, block_q,
              block_k, fine, window, layout_exact, interpret):
    out, _ = _bs_fwd(q, k, v, prefetch, sched_meta, H, causal, sm_scale,
                     block_q, block_k, fine, window, layout_exact, interpret)
    return out


def _bs_fwd(q, k, v, prefetch, sched_meta, H, causal, sm_scale, block_q,
            block_k, fine, window, layout_exact, interpret):
    maxk, maxq = sched_meta
    lay8, cnt, idx, cnt_t, idx_t = prefetch
    B, Hh, S, D = q.shape
    q3 = q.reshape(B * Hh, S, D)
    k3 = k.reshape(B * Hh, S, D)
    v3 = v.reshape(B * Hh, S, D)
    o3, lse = _fwd(q3, k3, v3, lay8, cnt, idx, maxk, Hh, causal, sm_scale,
                   block_q, block_k, fine, window, layout_exact, interpret)
    return o3.reshape(B, Hh, S, D), (q3, k3, v3, o3, lse, prefetch,
                                     (B, Hh, S, D))


def _bs_bwd(sched_meta, H, causal, sm_scale, block_q, block_k, fine, window,
            layout_exact, interpret, res, g):
    q3, k3, v3, o3, lse, prefetch, (B, Hh, S, D) = res
    maxk, maxq = sched_meta
    lay8, cnt, idx, cnt_t, idx_t = prefetch
    do3 = g.reshape(B * Hh, S, D)
    sched = (cnt, idx, maxk, cnt_t, idx_t, maxq)
    dq, dk, dv = _bwd(q3, k3, v3, o3, do3, lse, lay8, sched, Hh, causal,
                      sm_scale, block_q, block_k, fine, window, layout_exact,
                      interpret)
    return (dq.reshape(B, Hh, S, D), dk.reshape(B, Hh, S, D),
            dv.reshape(B, Hh, S, D), (None,) * 5)


_bs_flash.defvjp(_bs_fwd, _bs_bwd)


def tile_plan(S: int, D: int, fine_block: int, block_q: int = 256,
              block_k: int = 256) -> Tuple[int, int, Optional[str]]:
    """(block_q, block_k, reason): the kernel blocks an S x S, head-dim D
    problem with a ``fine_block`` layout runs at, and the reason it cannot
    tile (None when it can). Dispatchers ask this BEFORE the call and route
    on the reason, so an error out of the kernel — the chip's compiler
    refusing it, say — is never read as "shapes don't tile"."""
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if fine_block > block_q or fine_block > block_k:
        # a very coarse layout: the fine block IS the kernel block
        block_q = block_k = fine_block
    # the q side of the layout rides the BlockSpec at block_q//8 granularity —
    # that step must subdivide a fine block exactly
    while block_q > 8 and (block_q // 8 > fine_block
                           or fine_block % (block_q // 8)):
        block_q //= 2
    reason = None
    if (S % block_q or S % block_k or block_q % 8
            or block_k % fine_block or D % 8):
        reason = (
            f"block_sparse_flash_attention cannot tile S={S}, D={D} with "
            f"kernel blocks ({block_q},{block_k}) and fine block {fine_block}")
    return block_q, block_k, reason


def block_sparse_flash_attention(q: jnp.ndarray,
                                 k: jnp.ndarray,
                                 v: jnp.ndarray,
                                 layout: np.ndarray,
                                 fine_block: int,
                                 *,
                                 causal: bool = False,
                                 sm_scale: Optional[float] = None,
                                 block_q: int = 256,
                                 block_k: int = 256,
                                 window: int = 0,
                                 layout_exact: bool = True,
                                 interpret: bool = False) -> jnp.ndarray:
    """Layout-skipping attention. q,k,v: [B, H, S, D]; layout [H, nq, nk]
    bool at ``fine_block`` granularity (SparsityConfig.make_layout output).

    Returns exactly what the dense-mask oracle returns for the same layout
    (rows with no active keys produce zeros). Raises the :func:`tile_plan`
    reason when shapes can't tile — callers ask :func:`tile_plan` first and
    take the mask path on a reason (ops/sparse_attention.sparse_attention).
    """
    B, H, S, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    block_q, block_k, reason = tile_plan(S, D, fine_block, block_q, block_k)
    if reason is not None:
        raise ValueError(reason)
    nf = S // fine_block
    if layout.shape != (H, nf, nf):
        raise ValueError(f"layout shape {layout.shape} != {(H, nf, nf)} for "
                         f"S={S}, fine_block={fine_block}")
    lay_np = np.asarray(layout).astype(np.int32)
    lay8 = jnp.asarray(_expand_rows8(lay_np, block_q, fine_block))
    cnt, idx, maxk = _active_lists(lay_np, fine_block, block_q, block_k)
    cnt_t, idx_t, maxq = _active_lists(
        lay_np.transpose(0, 2, 1), fine_block, block_k, block_q)
    prefetch = (lay8, jnp.asarray(cnt), jnp.asarray(idx),
                jnp.asarray(cnt_t), jnp.asarray(idx_t))
    return _bs_flash(q, k, v, prefetch, (maxk, maxq), H, causal, sm_scale,
                     block_q, block_k, fine_block, window, layout_exact,
                     interpret)
