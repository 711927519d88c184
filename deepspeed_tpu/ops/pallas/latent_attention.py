"""Paged attention over LATENT pages (multi-head latent attention, MLA).

A latent model (``TransformerConfig.kv_lora_rank``: DeepSeek-V2) caches one
row a token and layer and nothing a head: the normed latent ``c`` and the
rotated key ``k_pe`` all heads share, on the lanes ``[c | k_pe | zeros]`` of
a row of whole 128-lane tiles (``serving/kv_cache.init_pool``). The kernel
reads it in one of TWO forms, chosen by the call's static row count
(:func:`form`): two bodies that share the page walk (:func:`_pages`) and
nothing else, because what they need conflicts.

**A decode call (one row a lane) attends ABSORBED**
(:func:`latent_attention`): one row cannot pay for putting its keys through
``attn_kv_b``, so that matrix is folded into the query and the output beside
the kernel (``models/generation.absorb_query`` / ``absorb_output``): a
head's query is a row ``[q_nope Wk[h] | q_pe | zeros]`` as wide as the
stored row, its score the dot product with the stored row itself, and its
value the row's first ``kv_lora_rank`` lanes. ONE stored row serves every
head, the heads are the ROWS of the lane's one query tile (what PR 44 built
for a grouped-query group, at group = all heads), and a page is copied once
and used twice, for scores and for values. 2 x (width + value) FLOPs a
(head, key) against ``width`` x 2 bytes a key: at DeepSeek-V2's 128 heads
the call sits on the v5e's ridge. Grid ``(lanes, 1, 1)``;
``paged_attention._attend`` does the online-softmax update.

**A prefill chunk (T > 1 rows a lane) attends EXPANDED, each key once for
all of its rows** (:func:`latent_chunk_attention`): grid ``(lanes, heads /
4, row tiles)``, ONE row tile for a chunk of up to 1 536 rows (every chunk a
benchmark cell makes) and even tiles of at most that many for a longer call
(a whole prompt where ``serving.prefill_chunk_tokens`` is 0: what a program
holds in VMEM is bounded, :func:`chunk_tiles`). A program holds ALL of its
tile's rows of its four heads at the model's own widths (``q_nope`` 128, ``q_pe`` 64), their float32 accumulator,
running max and sum, and the heads' slices of ``attn_kv_b`` (``wk``,
``wv``). It walks the lane's pages once; each turn's keys go through ``wk``
and ``wv`` ONCE (a head's unrotated keys and values, rounded to the model's
dtype as the published ``kv_b_proj``'s output is) and are then met by every
256-row tile of the program's rows that sees them: float32 scores ``q_nope . k_nope
+ q_pe . k_pe``, the online softmax, ``p V`` into a 128-wide accumulator. A
row tile whose last row stands before a turn's first key skips it, and the
mask is worked out only on the tiles the chunk's own triangle or the
context's end cuts. At DeepSeek-V2's widths a (head, row, key) costs 640
operations and ``attn_kv_b`` 262 144 a (head, key) shared by the chunk's
rows: about 810 at 1 536 rows, where the absorbed form does 2 176 (2 304 on
the stored lanes) and two absorb matmuls besides. On the chip the call is
2.9 times faster than the absorbed chunk form it replaced at 1 536 rows
behind 8 192 or 24 576 cached tokens, and faster at every chunk shape from
256 rows and on a first chunk (PERF.md, section 6, PR 50). PR 49 had tried
an expanded mode that re-expanded a turn's keys for every 256-row tile (its
grid had the row tiles innermost); that paid 1 024 operations a pair for
the expansion at every chunk size and read slower wherever a context was
cached.

Both walk alike (``paged_attention._loop_kernel``'s loop without what a
latent model cannot have: no second pool, no int8 scales, no ALiBi, no
window, no selection): the pool left in HBM, a program walking its lane's
live pages through the prefetched block table ``P`` pages a turn, a page a
descriptor, into one of two VMEM buffers, the next turn's (and at a
program's last turn the next program's first) in flight while one is
computed. Both custom calls are named ``paged_attention_latent``.

:func:`latent_attention_reference` is the jnp oracle (a dense gather through
the table, float32 scores, ABSORBED at every call shape): the CPU fallback
and the parity target of the interpret-mode tests.
"""

from __future__ import annotations

from functools import partial, reduce
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF
from .paged_attention import _CHUNK_TILE, _attend
from .sparse_select import selected as _selected

__all__ = ["latent_attention", "latent_chunk_attention",
           "latent_attention_reference", "untileable", "path", "form",
           "chunk_tiles", "tile_keys", "chunk_expanded_keys",
           "chunk_row_tiles", "chunk_key_tiles", "KERNEL_NAME"]

#: the custom call's name: ``paged_attention`` (what every metric of paged
#: attention matches) and a suffix of its own
KERNEL_NAME = "paged_attention_latent"

#: keys one turn of the loop copies and computes: decode (a page is ``bs``
#: of them), and under a chunk, where a turn's keys are expanded once and a
#: 256-row tile's float32 scores ``[rows, keys]`` and their copies are the
#: largest arrays a head's update makes. On the chip (PR 50, a call of
#: 1 536 rows behind 24 576 tokens: 26.4 ms) 256 keys read 28% longer and
#: 1 024 2% shorter, with a first chunk of 256 rows 0.18 ms longer (0.48 for
#: 0.30): a first chunk pays a whole turn
_DECODE_KEYS, _CHUNK_KEYS = 512, 512

#: query heads a chunk program takes, each with ALL of its row tile's rows
#: (the chunk's, up to ``_CHUNK_ROWS``): at 4 heads x 1 536 rows the query tiles (nope, and rope padded to 128 lanes)
#: and the output tile, twice buffered, are 9 MB, the float32 accumulator,
#: running max and running sum 3 MB each; with the page buffers, the heads'
#: ``attn_kv_b`` slices, a turn's expanded keys and values and one update's
#: scores a program holds about 26 MB. Every program reads the lane's pages
#: again and a program's heads overlap one another's matmuls and softmax:
#: 2 heads read 9% longer at 24 576 tokens, 8 heads 4% shorter at twice the
#: VMEM
_CHUNK_HEADS = 4

#: what a chunk program may take of the chip's 128 MB of VMEM (the
#: compiler's own limit is 16 MB)
_CHUNK_VMEM = 48 << 20

#: rows a chunk program holds at most: the largest chunk read on the chip
#: (26 MB of ``_CHUNK_VMEM``; a program's VMEM grows by about 12 KB a row).
#: A call of more rows is cut into even row tiles on the grid
#: (:func:`chunk_tiles`), each expanding the keys it sees for itself: at
#: 1 536 rows the expansion is 170 of a pair's 810 operations
_CHUNK_ROWS = 1536


def chunk_tiles(rows: int) -> Tuple[int, int]:
    """``(tiles, rows a tile)`` of a chunk call of ``rows`` rows a lane: one
    tile of the rows padded to whole 256-row tiles up to ``_CHUNK_ROWS``,
    else the fewest even tiles of at most that many."""
    n = -(-rows // _CHUNK_ROWS)
    return n, -(-rows // (n * _CHUNK_TILE)) * _CHUNK_TILE


def tile_keys(ctx, q0, rows, xp=jnp):
    """The lane's first keys a chunk program of ``rows`` rows from position
    ``q0`` walks and expands: up to its last row's, of the ``ctx`` live
    ones; none where all its rows are padding. The kernel's own rule;
    ``xp``: numpy for a host that counts what the kernel will do."""
    return xp.where(q0 < ctx, xp.minimum(ctx, q0 + rows), 0)


def chunk_expanded_keys(rows: int, q_start: int, ctx: int) -> int:
    """Cached tokens a HEAD of a chunk call of ``rows`` (padded) rows from
    ``q_start`` puts through ``attn_kv_b``, of a lane of ``ctx`` live
    tokens: :func:`tile_keys` over the call's row tiles
    (:func:`chunk_tiles`), on the host. ``ctx`` itself for a call of one
    tile."""
    n, per = chunk_tiles(rows)
    return int(tile_keys(ctx, q_start + per * np.arange(n), per, np).sum())


def chunk_row_tiles(q0, ctx, rows: int, k0, keys: int, xp=jnp):
    """``(lo, full, hi)``: of a chunk program's ``rows // 256`` row tiles
    (rows from position ``q0``, a lane of ``ctx`` live tokens), those that
    meet the turn whose keys lie at ``[k0, k0 + keys)``: tiles ``[lo, hi)``,
    from the one whose last row stands at the turn's first key to the last
    with a real row; of them ``[full, hi)`` need no causal mask (their first
    row stands at or past the turn's last key, and the context does not end
    inside the turn). The kernel's own rule; ``xp``: numpy for a host that
    counts what the kernel will do (:func:`chunk_key_tiles`)."""
    rt = _CHUNK_TILE
    lo = xp.maximum(k0 - q0, 0) // rt
    hi = xp.minimum((ctx - q0 + rt - 1) // rt, rows // rt)
    full = xp.where(k0 + keys <= ctx, xp.clip(
        (xp.maximum(k0 + keys - 1 - q0, 0) + rt - 1) // rt, lo, hi), hi)
    return lo, full, hi


def chunk_key_tiles(rows: int, q_start: int, ctx: int, bs: int, nbk: int
                    ) -> Tuple[int, int]:
    """``(pairs, live pairs)`` of a chunk call of ``rows`` (padded) rows
    from ``q_start`` over a lane of ``ctx`` live tokens, a head group: the
    (256-row tile, key turn) pairs of the turns its programs walk, and those
    whose matmuls :func:`chunk_row_tiles` lets them make (under a selection
    a pair none of whose rows selected a key of the turn is skipped besides,
    which only the device knows). On the host."""
    n, per = chunk_tiles(rows)
    keys = max(1, min(_CHUNK_KEYS // bs, nbk)) * bs
    pairs = live = 0
    for t in range(n):
        q0 = q_start + t * per
        turns = -(-int(tile_keys(ctx, q0, per, np)) // keys)
        lo, _, hi = chunk_row_tiles(q0, ctx, per, np.arange(turns) * keys,
                                    keys, np)
        pairs += turns * (per // _CHUNK_TILE)
        live += int(np.maximum(hi - lo, 0).sum())
    return pairs, live


def untileable(q_shape, pool_shape, interpret: bool = False
               ) -> Optional[str]:
    """The reason these shapes cannot ride the kernel, or None: asked BEFORE
    the call (``paged_attention.untileable`` says why). ``q_shape`` at the
    pool's width (a chunk's heads and rows beside it)."""
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
    no TPU is there to run the kernel. The kernel's FORM follows from the
    call's rows alone (:func:`form`)."""
    if impl == "reference" or not (jax.default_backend() == "tpu"
                                   or bool(interpret)):
        return "reference", None
    why = untileable(q_shape, pool_shape, interpret)
    return ("kernel", None) if why is None else ("reference", why)


def form(rows: int) -> str:
    """How the kernel computes a call of ``rows`` query rows a lane: one
    row cannot pay for putting its keys through ``attn_kv_b`` and attends
    ``"absorbed"``; a chunk's rows share each key's expansion and attend
    ``"expanded"`` (module docstring)."""
    return "absorbed" if rows == 1 else "expanded"


def _pages(bt_ref, lens_ref, misc_ref, pool, buf, state, sem, *, bs, P, nbk,
           rows=None, seen=None, beside=None):
    """What a decode program and a chunk program share: the walk over lane
    b's live pages, ``P`` a group, through the two buffers of ``buf``, the
    group after (and behind a program's last group the next program's
    first) in flight while one is computed. Returns ``(b, loop)``: the
    program's lane, and ``loop(tile)``, which calls ``tile(i, slot)`` once
    group i lies in ``buf[slot]``. ``misc`` = (layer, -, q_start of every
    lane); ``rows``: what a chunk program holds (None: a decode token),
    ``seen``: :func:`tile_keys`, handed in (the serving loop calls it on the
    host, and the package's linter takes every function a traced body names
    for device work). ``beside(act, b, t, i, slot)``: one more copy a group,
    started and waited for with its pages (a selecting chunk's index
    scores)."""
    b, g, t = (pl.program_id(i) for i in range(3))
    nb, ng, nt = (pl.num_programs(i) for i in range(3))
    layer = misc_ref[0]

    def span(b, t):
        """Groups of P pages tile t of lane b walks: up to its last row's
        page (a decode token: the lane's context), none for an idle lane or
        a tile of padding rows only."""
        ctx = lens_ref[b]
        if rows is not None:
            ctx = seen(ctx, misc_ref[2 + b] + t * rows, rows)
        cnt = jnp.minimum((ctx + bs - 1) // bs, nbk)
        return cnt, (cnt + P - 1) // P

    def copies(act, b, cnt, i, slot, t=t):
        """Start or wait for group i of lane b (row tile t) into buffer
        ``slot``: a page a descriptor, out of the pool where the table says
        it lies."""
        if beside is not None:
            beside(act, b, t, i, slot)

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

    def loop(tile):
        def group(i, _):
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < g1)
            def _next_group():
                start(b, cnt, i + 1, 1 - slot)

            @pl.when((i + 1 == g1) & has_nxt)
            def _next_program():
                start(b_nxt, cnt_nxt, 0, 1 - slot, t_nxt)
                state[1] = 1

            wait(b, cnt, i, slot)
            tile(i, slot)

        jax.lax.fori_loop(0, g1, group, None)
        state[0] = (slot0 + g1) % 2

    return b, loop


def _kernel(bt_ref, lens_ref, misc_ref, q_ref, pool, *rest, bs, P, nbk,
            value, sm_scale, select=False):
    """A decode program, ABSORBED: lane b's one tile of ``heads`` rows
    against the lane's stored rows themselves (module docstring).
    ``select``: the lane's index scores ``[1, keys of the table]`` (a block
    in VMEM) and its row's threshold and tie position; the heads share the
    ONE mask row a turn they make."""
    rest = list(rest)
    sel_ref, thr_ref, tie_ref = (rest.pop(0), rest.pop(0), rest.pop(0)) \
        if select else (None, None, None)
    o_ref, buf, acc, m_scr, l_scr, state, sem = rest
    b, loop = _pages(bt_ref, lens_ref, misc_ref, pool, buf, state, sem,
                     bs=bs, P=P, nbk=nbk)
    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    q = q_ref[0]

    def tile(i, slot):
        rows = buf[slot]                                # [1, P * bs, width]
        sel = None
        if select:
            at = pl.ds(pl.multiple_of(i * (P * bs), P * bs), P * bs)
            sel = (sel_ref[0, :, at], thr_ref[0][:, :1], tie_ref[0][:, :1])
        _attend(q, rows, rows[:, :, :value], None, None, i * (P * bs),
                lens_ref[b], 0, None, acc, m_scr, l_scr, sm_scale=sm_scale,
                softcap=0.0, sel=sel)

    loop(tile)
    l = l_scr[:, :, :1]
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _chunk_kernel(bt_ref, lens_ref, misc_ref, qn_ref, qp_ref, wk_ref, wv_ref,
                  pool, *rest, bs, P, nbk, rank, sm_scale, seen, tiles,
                  select=False):
    """A chunk program, EXPANDED: ALL of a row tile's rows (the chunk's, up
    to ``_CHUNK_ROWS``) of ``gq`` heads against lane b's pages, each key group put through the heads' slices of
    ``attn_kv_b`` ONCE (``kx`` / ``vx``) and then met by every 256-row tile
    of the program that sees it (module docstring). ``select``: the index
    scores of the program's rows against a turn's keys ``[rows, keys]``,
    copied out of the scores where the top-k left them beside the turn's
    pages, and each row's threshold and tie position: a (row tile, turn) is
    masked ONCE for the program's heads, and one none of whose rows selected
    a key makes no matmul."""
    rest = list(rest)
    sel_hbm, thr_ref, tie_ref = (rest.pop(0), rest.pop(0), rest.pop(0)) \
        if select else (None, None, None)
    o_ref, buf, kx, vx, acc, m_scr, l_scr, state, sem = rest[:9]
    sel_buf, sel_sem = rest[9:] if select else (None, None)
    gq, rows, rt, keys = acc.shape[0], acc.shape[1], _CHUNK_TILE, P * bs
    rope, lanes = qp_ref.shape[-1], m_scr.shape[-1]
    # the lanes of a row's ``alpha`` that rescale its accumulator: all of
    # them where the value is as wide, else one, broadcast
    width = lanes if acc.shape[-1] == lanes else 1

    def scores_of(act, b, t, i, slot):
        """Start or wait for the index scores of row tile t of lane b
        against group i's keys."""
        getattr(pltpu.make_async_copy(
            sel_hbm.at[b, pl.ds(pl.multiple_of(t * rows, rows), rows),
                       pl.ds(pl.multiple_of(i * keys, keys), keys)],
            sel_buf.at[slot], sel_sem.at[slot]), act)()

    b, loop = _pages(bt_ref, lens_ref, misc_ref, pool, buf, state, sem,
                     bs=bs, P=P, nbk=nbk, rows=rows, seen=seen,
                     beside=scores_of if select else None)
    ctx, q0 = lens_ref[b], misc_ref[2 + b] + pl.program_id(2) * rows
    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    dot = partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
    nn, nt = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))

    def attend(h, at, s):
        """Head h's rows ``at`` against this group's keys, scores ``s``
        [rt, keys]: the online-softmax update. The running max lies alike
        on all ``lanes`` of its row and the running sum a lane apart (a
        lane its share of the keys, added up at the end), so a group's keys
        are taken a lane tile at a time and nothing but the row's max
        crosses lanes (with a max and a sum of ``[rows, 1]`` a group the
        call at 1 536 rows behind 24 576 tokens read 35.9 ms for 26.5 on
        the chip: PERF.md, PR 50)."""
        s = [s[:, j:j + lanes] for j in range(0, keys, lanes)]
        m_prev = m_scr[h, at]
        m_cur = jnp.maximum(m_prev, jnp.max(
            reduce(jnp.maximum, s), axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = [jnp.exp(t - m_cur) for t in s]
        l_scr[h, at] = l_scr[h, at] * alpha + sum(p[1:], p[0])
        pv = dot(jnp.concatenate(p, axis=1).astype(vx.dtype), vx[h], nn)
        acc[h, at] = acc[h, at] * alpha[:, :width] + pv
        m_scr[h, at] = m_cur

    def tile(i, slot):
        k0 = i * keys
        c = buf[slot, 0, :, :rank]                      # [keys, rank]
        for h in range(gq):
            kx[h] = dot(c, wk_ref[h], nn).astype(kx.dtype)
            vx[h] = dot(c, wv_ref[h], nn).astype(vx.dtype)
        k_pe = buf[slot, 0, :, rank:rank + rope]        # [keys, rope]

        def row_tile(masked, r, _):
            r0 = pl.multiple_of(r * rt, rt)
            at = pl.ds(r0, rt)
            if select:
                # the row's top-k keys by its index score, which reads -inf
                # where the row does not see the key: the causal mask and
                # the context's end are in it
                sc = sel_buf[slot, at]
                keep = _selected(
                    sc, thr_ref[0, at, :1], tie_ref[0, at, :1],
                    k0 + jax.lax.broadcasted_iota(jnp.int32, (rt, keys), 1)) \
                    & (sc > -jnp.inf)
            elif masked:
                # row r0 + j sees the keys up to its own position and,
                # a padding row, the live ones (finite, read by nobody)
                last = jnp.minimum(q0 + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (rt, 1), 0), ctx - 1) - k0
                keep = jax.lax.broadcasted_iota(
                    jnp.int32, (rt, keys), 1) <= last

            def heads():
                for h in range(gq):
                    s = (dot(qn_ref[0, h, at], kx[h], nt)
                         + dot(qp_ref[0, h, at], k_pe, nt)) * sm_scale
                    attend(h, at, jnp.where(keep, s, NEG_INF) if masked
                           else s)

            if select:
                pl.when(jnp.max(keep.astype(jnp.int32)) > 0)(heads)
            else:
                heads()

        # the program's 256-row tiles that see this group: from the one whose
        # last row stands at its first key to the last with a real row;
        # those whose FIRST row stands at or past its last key (and none
        # where the context ends inside the group) need no mask
        # (a selection masks every tile: one loop)
        lo, full, hi = tiles(q0, ctx, rows, k0, keys)
        jax.lax.fori_loop(lo, hi if select else full,
                          partial(row_tile, True), None)
        if not select:
            jax.lax.fori_loop(full, hi, partial(row_tile, False), None)

    loop(tile)
    l = jnp.sum(l_scr[...], axis=2, keepdims=True)
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _lens_layer(context_lens, layer_idx, B):
    """A call's context lengths ``[B]`` and layer index, as the kernels'
    prefetched int32 scalars."""
    return (jnp.asarray(context_lens, jnp.int32).reshape(B),
            jnp.asarray(layer_idx, jnp.int32).reshape(()))


def _selection_rows(select, rows: int, keys: int):
    """A call's :class:`sparse_select.Selection` as the kernels take it:
    the scores ``[B, rows, keys]`` (rows and keys past the call's own read
    ``-inf``: nothing of them is selected; a call of whole tiles over a
    table of whole turns is handed on as it is), and ``thr`` / ``tie`` ``[B,
    rows, 128]``, a row's on every lane."""
    B, T, Kp = select.scores.shape
    sc = select.scores.astype(jnp.float32)[:, :, :keys]
    more = (rows - T, keys - min(Kp, keys))
    if any(more):
        sc = jnp.pad(sc, [(0, 0), (0, more[0]), (0, more[1])],
                     constant_values=-jnp.inf)
    on_lanes = lambda a, fill: jnp.broadcast_to(jnp.pad(
        a, [(0, 0), (0, rows - T)], constant_values=fill)[:, :, None],
        (B, rows, 128))
    return [sc, on_lanes(select.thr.astype(jnp.float32), jnp.inf),
            on_lanes(select.tie.astype(jnp.int32), -1)]


def latent_attention(q: jnp.ndarray, pool: jnp.ndarray,
                     block_tables: jnp.ndarray, context_lens: jnp.ndarray, *,
                     value: int, sm_scale: float, layer_idx,
                     interpret: bool = False, select=None) -> jnp.ndarray:
    """A decode call, ABSORBED: every lane's fresh token, at
    ``context_lens[b] - 1``, against its latent pages.

    q: ``[B, heads, 1, width]`` absorbed queries, zeros behind their
       ``kv_lora_rank + rope`` lanes as the stored rows have.
    pool: ``[L, 1, blocks, block_size, width]``, layer ``layer_idx`` (traced
       ok) read in place. ``value``: the lanes of a row that are its value
       (``kv_lora_rank``).
    select: a layer with an indexer (``sparse_select.Selection`` of the
       call's one row a lane): a lane attends the keys ``selected`` keeps
       among those it sees; the loop still walks every live page.
    Returns ``[B, heads, 1, value]``, for ``absorb_output``.
    """
    B, nh, T, width = q.shape
    reason = untileable(q.shape, pool.shape, interpret)
    if reason is not None or T != 1:
        raise ValueError(reason or f"{T} rows a lane: a chunk is "
                         "latent_chunk_attention's")
    lens, li = _lens_layer(context_lens, layer_idx, B)
    bs, nbk = pool.shape[3], block_tables.shape[1]
    P = max(1, min(_DECODE_KEYS // bs, nbk))
    # the heads are the rows of the lane's one tile
    qf, no_start = q.reshape(B, 1, nh, width), jnp.zeros((B,), jnp.int32)
    misc = jnp.concatenate([jnp.stack([li, jnp.int32(0)]), no_start])
    tile = lambda w: pl.BlockSpec((1, 1, nh, w),
                                  lambda b, g, t, *_: (b, g, t, 0))
    kernel = partial(_kernel, bs=bs, P=P, nbk=nbk, value=value,
                     sm_scale=float(sm_scale), select=select is not None)
    sel_ops, sel_specs = [], []
    if select is not None:
        # a lane's scores of the table's keys, whole turns: its block
        keys = -(-nbk // P) * P * bs
        sel_ops = _selection_rows(select, 1, keys)
        of_lane = lambda w: pl.BlockSpec((1, 1, w),
                                         lambda b, g, t, *_: (b, 0, 0))
        sel_specs = [of_lane(keys), of_lane(128), of_lane(128)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, 1, 1),
        in_specs=[tile(width), pl.BlockSpec(memory_space=pl.ANY)] + sel_specs,
        out_specs=tile(value),
        scratch_shapes=[pltpu.VMEM((2, 1, P * bs, width), pool.dtype),
                        pltpu.VMEM((1, nh, value), jnp.float32),
                        pltpu.VMEM((1, nh, 128), jnp.float32),
                        pltpu.VMEM((1, nh, 128), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope("paged_attention"):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, name=KERNEL_NAME,
            out_shape=jax.ShapeDtypeStruct((B, 1, nh, value), q.dtype),
            interpret=interpret,
        )(jnp.asarray(block_tables, jnp.int32), lens, misc, qf, pool,
          *sel_ops).reshape(B, nh, 1, value)


def latent_chunk_attention(q_nope, q_pe, wk, wv, pool, block_tables,
                           context_lens, *, sm_scale: float, layer_idx,
                           q_start=None, interpret: bool = False,
                           select=None):
    """A prefill chunk's call, EXPANDED: T > 1 rows a lane at positions
    ``q_start[b] + r`` (``context_lens - T`` by default), causal among
    themselves, against the lane's latent pages; rows at or past
    ``context_lens[b]`` are bucket padding (finite garbage).

    q_nope ``[B, heads, T, nope]``, q_pe ``[B, heads, T, rope]`` (rotated):
       a head's query at the model's own widths.
    wk ``[heads, rank, nope]``, wv ``[heads, rank, v]``: ``attn_kv_b`` a
       head (``models/generation.latent_weights``).
    pool: as :func:`latent_attention`'s, a row ``[c (rank) | k_pe | zeros]``.
    select: a layer with an indexer (``sparse_select.Selection`` of the
       call's T rows): each row attends the keys ``selected`` keeps. The
       scores stay where the top-k left them (a last chunk of fewer rows
       than whole row tiles, or a table of no whole turns, pads them).
    Returns ``[B, heads, T, v]``: the heads' outputs themselves.
    """
    B, nh, T, _ = q_nope.shape
    reason = untileable(q_nope.shape[:3] + pool.shape[-1:], pool.shape,
                        interpret)
    if reason is not None:
        raise ValueError(reason)
    lens, li = _lens_layer(context_lens, layer_idx, B)
    q0 = lens - T if q_start is None else jnp.asarray(
        q_start, jnp.int32).reshape(B)
    # the rows padded to whole row tiles, and the call shared by every
    # program of these shapes (``paged_attention._shared_chunk_call``; the
    # interpreter's parameter object is not hashable: called as it is)
    n, per = chunk_tiles(T)
    pad = lambda q: jnp.pad(q, [(0, 0), (0, 0), (0, n * per - T), (0, 0)])
    call = _shared_chunk_call if isinstance(interpret, bool) else _chunk_call
    return call(pad(q_nope), pad(q_pe), wk, wv, pool, block_tables, lens, li,
                q0, rows=per, sm_scale=float(sm_scale), interpret=interpret,
                sel=None if select is None else tuple(select))[:, :, :T]


def _chunk_call(qn, qp, wk, wv, pool, block_tables, lens, li, q0, *,
                rows, sm_scale, interpret, sel=None):
    """:func:`latent_chunk_attention` on whole row tiles of ``rows`` rows
    (``sel``: the call's selection, ``(scores, thr, tie)``)."""
    B, nh, nope = qn.shape[0], qn.shape[1], qn.shape[3]
    nt = qn.shape[2] // rows
    rope, rank, vw = qp.shape[-1], wk.shape[1], wv.shape[-1]
    bs, width = pool.shape[3], pool.shape[4]
    nbk = block_tables.shape[1]
    gq = max(d for d in range(1, _CHUNK_HEADS + 1) if nh % d == 0)
    P = max(1, min(_CHUNK_KEYS // bs, nbk))
    misc = jnp.concatenate([jnp.stack([li, jnp.int32(0)]), q0])
    rows_of = lambda w: pl.BlockSpec((1, gq, rows, w),
                                     lambda b, g, t, *_: (b, g, t, 0))
    heads_of = lambda w: pl.BlockSpec((gq, rank, w),
                                      lambda b, g, t, *_: (g, 0, 0))
    kernel = partial(_chunk_kernel, bs=bs, P=P, nbk=nbk, rank=rank,
                     sm_scale=sm_scale, seen=tile_keys, tiles=chunk_row_tiles,
                     select=sel is not None)
    # the running max and sum a row: a lane tile of the group's keys wide
    lanes = 128 if P * bs % 128 == 0 else P * bs
    sel_ops, sel_specs, sel_scratch = [], [], []
    if sel is not None:
        from .sparse_select import Selection
        sel_ops = _selection_rows(Selection(*sel), nt * rows,
                                  -(-nbk // P) * P * bs)
        of_rows = pl.BlockSpec((1, rows, 128), lambda b, g, t, *_: (b, t, 0))
        sel_specs = [pl.BlockSpec(memory_space=pl.ANY), of_rows, of_rows]
        sel_scratch = [pltpu.VMEM((2, rows, P * bs), jnp.float32),
                       pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, nh // gq, nt),
        in_specs=[rows_of(nope), rows_of(rope), heads_of(nope), heads_of(vw),
                  pl.BlockSpec(memory_space=pl.ANY)] + sel_specs,
        out_specs=rows_of(vw),
        scratch_shapes=[pltpu.VMEM((2, 1, P * bs, width), pool.dtype),
                        pltpu.VMEM((gq, P * bs, nope), pool.dtype),
                        pltpu.VMEM((gq, P * bs, vw), pool.dtype),
                        pltpu.VMEM((gq, rows, vw), jnp.float32),
                        pltpu.VMEM((gq, rows, lanes), jnp.float32),
                        pltpu.VMEM((gq, rows, lanes), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))] + sel_scratch)
    with jax.named_scope("paged_attention"):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, name=KERNEL_NAME,
            out_shape=jax.ShapeDtypeStruct((B, nh, nt * rows, vw), qn.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_CHUNK_VMEM),
            interpret=interpret,
        )(jnp.asarray(block_tables, jnp.int32), lens, misc, qn, qp, wk, wv,
          pool, *sel_ops)


#: a chunk's call under ``jax.jit``: traced once for every prefill program
#: of a serving loop (one a chunk shape) that makes it at these shapes
_shared_chunk_call = jax.jit(
    _chunk_call, static_argnames=("rows", "sm_scale", "interpret"))


def latent_attention_reference(q, pool, block_tables, context_lens, *,
                               value: int, sm_scale: float, layer_idx,
                               q_start=None, select=None) -> jnp.ndarray:
    """jnp oracle / CPU fallback of :func:`latent_attention`: the lane's
    rows gathered through the table, float32 scores, -1e30 masks
    (``paged_attention_reference``'s arithmetic; ``select``: the dense mask
    of the rows' selected keys beside the causal one)."""
    B, nh, T, _ = q.shape
    bs, nbk = pool.shape[3], block_tables.shape[1]
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)
    rows = jax.lax.dynamic_index_in_dim(pool, layer_idx, 0, keepdims=False)[0]
    rows = rows[jnp.asarray(block_tables, jnp.int32)].reshape(
        B, nbk * bs, pool.shape[-1])                      # [B, K, width]
    q_abs = (lens[:, None] - T if q_start is None else jnp.asarray(
        q_start, jnp.int32).reshape(B)[:, None]) + jnp.arange(T)
    s = jnp.einsum("bhtw,bkw->bhtk", q, rows).astype(jnp.float32) * sm_scale
    k_pos = jnp.arange(nbk * bs)
    keep = k_pos[None, None, :] <= q_abs[:, :, None]
    if select is not None:
        sc = jnp.pad(select.scores[:, :, :nbk * bs], [(0, 0), (0, 0), (0, max(
            0, nbk * bs - select.scores.shape[2]))],
            constant_values=-jnp.inf)
        keep = keep & _selected(sc, select.thr[..., None],
                                select.tie[..., None], k_pos)
    s = jnp.where(keep[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhtk,bhkd->bhtd", prob, jnp.broadcast_to(
        rows[:, None, :, :value], (B, nh, nbk * bs, value)))
