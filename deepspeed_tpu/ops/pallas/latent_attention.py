"""Paged attention over LATENT pages (multi-head latent attention, MLA).

A latent model (``TransformerConfig.kv_lora_rank``: DeepSeek-V2) caches one
row a token and layer and nothing a head: the normed latent ``c`` and the
rotated key ``k_pe`` all heads share, on the lanes ``[c | k_pe | zeros]`` of
a row of whole 128-lane tiles (``serving/kv_cache.init_pool``).
:func:`latent_attention` reads it ABSORBED, at every call shape:
``attn_kv_b`` is folded into the query and the output
(``models/generation.absorb_query`` / ``absorb_output``), so a head's query
is a row ``[q_nope Wk[h] | q_pe | zeros]`` as wide as the stored row, its
score the dot product with the stored row itself, and its value the row's
first ``kv_lora_rank`` lanes: ONE stored row serves every head, the heads
are the ROWS of the query tile (what PR 44 built for a grouped-query group,
at group = all heads), and a page is copied once and used twice, for scores
and for values. 2 x (width + value) FLOPs a (head, query, key) against
``width`` x 2 bytes a key: at DeepSeek-V2's 128 heads the decode call sits
on the v5e's ridge.

The EXPANDED form (each cached latent through ``attn_kv_b`` again, attention
at the model's own 192 / 128-wide heads: 320 against 2 176 FLOPs a (head,
query, key), plus the re-expansion) is the plain reference's arithmetic
(``benchmark/families/deepseek_v2.py``) and no path of the program: on the
chip it read slower than this form at chunks of 256, 512 and 1 024 rows
wherever a context was cached, and faster only on a first chunk, by
0.01-0.67 ms (PERF.md, section 4, PR 49). A second form is a selection on
the context length with a benchmark cell on each side (ROADMAP M4 e).

The kernel is ``paged_attention._loop_kernel``'s loop without what a latent
model cannot have (no second pool, no int8 scales, no ALiBi, no window, no
selection): grid ``(lanes, head programs, row tiles)``, the pool left in HBM,
a program walking its lane's live pages through the prefetched block table
``P`` pages a turn into one of two VMEM buffers, the next turn's (and at a
program's last turn the next program's first) in flight while one is
computed; ``paged_attention._attend`` does the online-softmax update. A
decode token's tile is ``[1, heads, width]``; a chunk's ``[gq heads, 256
rows, width]`` a program, row tile t at positions ``q_start + 256 t ..``, walking
the pages up to ITS last row only (a later tile of the same chunk sees
more). The custom call is named ``paged_attention_latent``.

:func:`latent_attention_reference` is the jnp oracle (a dense gather through
the table, float32 scores): the CPU fallback and the parity target of the
interpret-mode tests.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF
from .paged_attention import _CHUNK_TILE, _attend

__all__ = ["latent_attention", "latent_attention_reference", "untileable",
           "path", "KERNEL_NAME"]

#: the custom call's name: ``paged_attention`` (what every metric of paged
#: attention matches) and a suffix of its own
KERNEL_NAME = "paged_attention_latent"

#: keys one turn of the loop copies and computes: decode (a page is ``bs``
#: of them), and under a chunk's 256-row tiles, whose float32 scores
#: ``[heads, rows, keys]`` and their copies are the program's largest arrays
_DECODE_KEYS, _CHUNK_KEYS = 512, 256

#: query heads a chunk program takes: its rows (heads x 256) bound the
#: float32 accumulator, the query and output tiles (twice buffered) and the
#: scores: 6.5 MB at 2, past the chip's 16 MB of scoped VMEM at 4
_CHUNK_HEADS = 2


def untileable(q_shape, pool_shape, interpret: bool = False
               ) -> Optional[str]:
    """The reason these shapes cannot ride the kernel, or None: asked BEFORE
    the call (``paged_attention.untileable`` says why)."""
    if interpret:
        return None
    bs, width = pool_shape[-2], pool_shape[-1]
    if width % 128 or q_shape[-1] != width:
        return (f"a latent row of {width} lanes (queries of {q_shape[-1]}): "
                "whole 128-lane tiles, the queries padded to them")
    if bs % 8:
        return f"block_size {bs} does not tile (sublane multiple of 8)"
    if q_shape[2] == 1 and q_shape[1] % 8:
        return f"{q_shape[1]} heads are no whole sublane tiles of rows"
    return None


def path(q_shape, pool_shape, impl: str = "auto", interpret: bool = False
         ) -> Tuple[str, Optional[str]]:
    """``("kernel", None)`` or ``("reference", why)`` for a call of these
    shapes (``q_shape`` at the pool's width), as ``ops.attention.
    paged_attention_path`` answers for K/V pools: ``why`` is
    :func:`untileable`'s reason, None where the reference was asked for or
    no TPU is there to run the kernel."""
    if impl == "reference" or not (jax.default_backend() == "tpu"
                                   or bool(interpret)):
        return "reference", None
    why = untileable(q_shape, pool_shape, interpret)
    return ("kernel", None) if why is None else ("reference", why)


def _kernel(bt_ref, lens_ref, misc_ref, q_ref, pool, o_ref, buf, acc, m_scr,
            l_scr, state, sem, *, bs, P, nbk, rt, value, sm_scale, chunk):
    """One program: lane b, head program g, row tile t (module docstring).
    ``misc`` = (layer, -, q_start of every lane)."""
    b, g, t = (pl.program_id(i) for i in range(3))
    nb, ng, nt = (pl.num_programs(i) for i in range(3))
    layer = misc_ref[0]

    def span(b, t):
        """Groups of P pages tile t of lane b walks: up to its last row's
        page (a decode token: the lane's context), none for an idle lane or
        a tile of padding rows only."""
        ctx = lens_ref[b]
        if chunk:
            q0 = misc_ref[2 + b] + t * rt
            ctx = jnp.where(q0 < ctx, jnp.minimum(ctx, q0 + rt), 0)
        cnt = jnp.minimum((ctx + bs - 1) // bs, nbk)
        return cnt, (cnt + P - 1) // P

    def copies(act, b, cnt, i, slot):
        """Start or wait for group i of lane b into buffer ``slot``: a page
        a descriptor, out of the pool where the table says it lies."""
        def body(p, _):
            page = i * P + p

            @pl.when(page < cnt)
            def _():
                phys = bt_ref[b, jnp.minimum(page, nbk - 1)]
                getattr(pltpu.make_async_copy(
                    pool.at[layer, 0, phys],
                    buf.at[slot, 0, pl.ds(pl.multiple_of(p * bs, bs), bs)],
                    sem.at[slot]), act)()

        jax.lax.fori_loop(0, P, body, None)

    start, wait = partial(copies, "start"), partial(copies, "wait")
    cnt, g1 = span(b, t)
    # the program that runs next (row tiles innermost): its first group is
    # started from this one's last turn
    last_t, last_g = t + 1 == nt, g + 1 == ng
    t_nxt = jnp.where(last_t, 0, t + 1)
    b_nxt = jnp.minimum(jnp.where(last_t & last_g, b + 1, b), nb - 1)
    cnt_nxt, n1 = span(b_nxt, t_nxt)
    has_nxt = ~(last_t & last_g & (b + 1 == nb)) & (n1 > 0)

    @pl.when((b == 0) & (g == 0) & (t == 0))
    def _first_program():
        # a page the loop skips keeps what the buffer held: masked scores
        # give it probability 0, and 0 times a stale NaN is a NaN
        buf[...] = jnp.zeros_like(buf)
        state[0] = 0        # the slot this program's loop starts in
        state[1] = 0        # 1: the program before started its first group

    slot0 = state[0]

    @pl.when((g1 > 0) & (state[1] == 0))
    def _start_own():
        start(b, cnt, 0, slot0)

    state[1] = 0
    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    q = q_ref[0]

    def group(i, _):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < g1)
        def _next_group():
            start(b, cnt, i + 1, 1 - slot)

        @pl.when((i + 1 == g1) & has_nxt)
        def _next_program():
            start(b_nxt, cnt_nxt, 0, 1 - slot)
            state[1] = 1

        wait(b, cnt, i, slot)
        rows = buf[slot]                                # [1, P * bs, width]
        q0 = misc_ref[2 + b] + t * rt if chunk else None
        _attend(q, rows, rows[:, :, :value], None, None, i * (P * bs),
                lens_ref[b], 0, None, acc, m_scr, l_scr, sm_scale=sm_scale,
                softcap=0.0, q0=q0)

    jax.lax.fori_loop(0, g1, group, None)
    state[0] = (slot0 + g1) % 2
    l = l_scr[:, :, :1]
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def latent_attention(q: jnp.ndarray, pool: jnp.ndarray,
                     block_tables: jnp.ndarray, context_lens: jnp.ndarray, *,
                     value: int, sm_scale: float, layer_idx,
                     q_start=None, interpret: bool = False) -> jnp.ndarray:
    """T query tokens a lane against its latent pages.

    q: ``[B, heads, T, width]`` absorbed queries, zeros behind their
       ``kv_lora_rank + rope`` lanes as the stored rows have.
    pool: ``[L, 1, blocks, block_size, width]``, layer ``layer_idx`` (traced
       ok) read in place. ``value``: the lanes of a row that are its value
       (``kv_lora_rank``).
    Returns ``[B, heads, T, value]``, for ``absorb_output``.
    T == 1: every lane's fresh token at ``context_lens[b] - 1``. T > 1: rows
    at ``q_start[b] + r``, causal among themselves; rows at or past
    ``context_lens[b]`` are bucket padding (finite garbage).
    """
    B, nh, T, width = q.shape
    reason = untileable(q.shape, pool.shape, interpret)
    if reason is not None:
        raise ValueError(reason)
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)
    li = jnp.asarray(layer_idx, jnp.int32).reshape(())
    if T == 1:
        # the heads are the rows of the lane's one tile
        return _call(q.reshape(B, 1, nh, width), pool, block_tables, lens,
                     li, jnp.zeros((B,), jnp.int32), value=value,
                     sm_scale=float(sm_scale), chunk=False,
                     interpret=interpret).reshape(B, nh, 1, value)
    q0 = lens - T if q_start is None else jnp.asarray(
        q_start, jnp.int32).reshape(B)
    # a chunk: its rows padded to whole tiles, and the call shared by every
    # program of these shapes (``paged_attention._shared_chunk_call``; the
    # interpreter's parameter object is not hashable: called as it is)
    q = jnp.pad(q, [(0, 0), (0, 0), (0, -T % _CHUNK_TILE), (0, 0)])
    call = _shared_chunk_call if isinstance(interpret, bool) else _call
    return call(q, pool, block_tables, lens, li, q0, value=value,
                sm_scale=float(sm_scale), chunk=True,
                interpret=interpret)[:, :, :T]


def _call(qf, pool, block_tables, lens, li, q0, *, value, sm_scale, chunk,
          interpret):
    """:func:`latent_attention` on a query of whole tiles: a decode call's
    ``[B, 1, heads, width]``, a chunk's ``[B, heads, rows, width]``."""
    B, nh, Tp, qw = qf.shape
    bs, width = pool.shape[3], pool.shape[4]
    nbk = block_tables.shape[1]
    if chunk:
        rt, keys = _CHUNK_TILE, _CHUNK_KEYS
        gq = max(d for d in range(1, _CHUNK_HEADS + 1) if nh % d == 0)
    else:
        rt, gq, keys = Tp, 1, _DECODE_KEYS
    P = max(1, min(keys // bs, nbk))
    grid = (B, nh // gq, Tp // rt)
    misc = jnp.concatenate([jnp.stack([li, jnp.int32(0)]), q0])
    tile = lambda w: pl.BlockSpec((1, gq, rt, w),
                                  lambda b, g, t, *_: (b, g, t, 0))
    kernel = partial(_kernel, bs=bs, P=P, nbk=nbk, rt=rt, value=value,
                     sm_scale=sm_scale, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid,
        in_specs=[tile(qw), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(value),
        scratch_shapes=[pltpu.VMEM((2, 1, P * bs, width), pool.dtype),
                        pltpu.VMEM((gq, rt, value), jnp.float32),
                        pltpu.VMEM((gq, rt, 128), jnp.float32),
                        pltpu.VMEM((gq, rt, 128), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope("paged_attention"):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, name=KERNEL_NAME,
            out_shape=jax.ShapeDtypeStruct(qf.shape[:3] + (value,), qf.dtype),
            interpret=interpret,
        )(jnp.asarray(block_tables, jnp.int32), lens, misc, qf, pool)


#: a chunk's call under ``jax.jit``: traced once for every prefill program
#: of a serving loop (one a chunk shape) that makes it at these shapes
_shared_chunk_call = jax.jit(
    _call, static_argnames=("value", "sm_scale", "chunk", "interpret"))


def latent_attention_reference(q, pool, block_tables, context_lens, *,
                               value: int, sm_scale: float, layer_idx,
                               q_start=None) -> jnp.ndarray:
    """jnp oracle / CPU fallback of :func:`latent_attention`: the lane's
    rows gathered through the table, float32 scores, -1e30 masks
    (``paged_attention_reference``'s arithmetic)."""
    B, nh, T, _ = q.shape
    bs, nbk = pool.shape[3], block_tables.shape[1]
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)
    rows = jax.lax.dynamic_index_in_dim(pool, layer_idx, 0, keepdims=False)[0]
    rows = rows[jnp.asarray(block_tables, jnp.int32)].reshape(
        B, nbk * bs, pool.shape[-1])                      # [B, K, width]
    q_abs = (lens[:, None] - T if q_start is None else jnp.asarray(
        q_start, jnp.int32).reshape(B)[:, None]) + jnp.arange(T)
    s = jnp.einsum("bhtw,bkw->bhtk", q, rows).astype(jnp.float32) * sm_scale
    keep = jnp.arange(nbk * bs)[None, None, :] <= q_abs[:, :, None]
    s = jnp.where(keep[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhtk,bhkd->bhtd", prob, jnp.broadcast_to(
        rows[:, None, :, :value], (B, nh, nbk * bs, value)))
