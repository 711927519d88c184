"""Pallas paged-attention kernel — KV pages copied via block table.

The serving subsystem (deepspeed_tpu/serving/) keeps the KV cache as a POOL
of fixed-size blocks (pages) shared by every in-flight sequence; a
per-sequence *block table* maps logical page j to a physical pool block. The
decode step then needs attention of one fresh query token per sequence
against a K/V that is physically scattered across the pool, and a prefill
chunk the same for its T query rows (since PR 37: until then a chunk rode
the gather reference, which slices a whole layer out of the pool). This
kernel walks only the pages a sequence HOLDS: the grid is (sequences, head groups),
the pools stay in HBM, and a program loops over its sequence's
``ceil(ctx_len / block_size)`` live pages (from the window's first page when
a sliding window is set), ``P`` pages a turn. It copies each page's
``[stored heads, block_size, head_dim]`` out of the pool itself (the block
table is a prefetched scalar array), into one of two VMEM buffers: while a
group of ``P * block_size`` keys is computed the next group is in flight, and a
sequence's last turn starts the next sequence's first group, so only the
call's very first copy is waited for with nothing to do. No materialized
per-sequence contiguous copy, no step for a table entry no sequence uses
(until PR 28 the table's length was a grid axis: an idle lane and a short
sequence cost as many grid steps as a full one), and per-token cost scales
with the tokens each sequence has generated, not with the pool or the table.

``P`` is a function of the operand shapes alone (:func:`_pages_per_group`:
the largest power of two at which four group buffers of the program's QUERY
heads fit :data:`_VMEM_BUDGET`, at most the table's length; a chunk's, at
most :data:`_CHUNK_KEYS` keys).

**The tile is a stored head's query-head group** (PR 44). The pool holds
the model's KV heads, ``q`` comes at its ``nh`` query heads, and ``group =
nh // kvh`` is read from the two shapes: the ``group`` query heads that
share a stored head are ROWS of that head's query tile, row r the query of
head ``kv * group + r``, so one copy of a page serves them all and one pass
of the matrix unit scores them all (mistral-7b: 4 query heads a stored
head, a quarter of the bytes and of the passes of a pool that held a row a
query head; K-EXAONE: 8, an eighth). A decode token's tile is ``max(8,
group rounded up to 8)`` rows; at ``group == 1`` it is the one query
broadcast over the sublane minimum, and everything, the lowered text
included, is what it was when the pool's heads were the query's. ALiBi
slopes are a row's own.

A prefill chunk is the same loop under taller query tiles, ``[query heads,
T, head_dim]`` with a stored head's group one head after another: row r of
each is the query at ``q_start + r``, so positions and masks are worked out
once for all the program's heads, and each query head's two matmuls run
against the one copy of the stored head it shares (:func:`_attend_chunk`,
PR 51: a turn of 512 keys, a query head at a time, the keys as lane tiles
of 128; the running max alike on a row's lanes, so that it crosses lanes
once a head and turn, the running sum a lane apart, one rescale of the
accumulator a head and turn). The
causal and window masks are a row's own, the
pages run from the first query's window to the last real query's page
(``ctx - 1``: ``cache.write`` has put the chunk's own keys into the pool
just before), and rows at or past ``ctx`` are bucket padding whose finite
garbage nobody reads. A chunk's rows are padded to whole tiles of
:data:`_CHUNK_TILE` and the call is made under ``jax.jit``, so the prefill
programs of a serving loop (one a chunk shape) trace ONE shape once; heads
a program shrink with the rows (:func:`_head_group`, counted in QUERY
heads' rows) so that the float32 accumulator and the two running rows stay
inside the chip's scoped VMEM beside the page buffers and ONE head's scores
of a row tile (``[256, 512]`` float32), and a group's page copies are a
loop, not unrolled. Where a whole group's rows are more than a program
holds (:data:`_CHUNK_ROWS`: K-EXAONE's 8 x 256), the group's query heads are
split over programs that each copy the stored head (:func:`_program_heads`).

That is :func:`_loop_kernel`, for heads of whole 128-lane tiles. A pool of
narrower heads (``head_dim % 128``: 64, 80, 96) is padded to 128 lanes a row
in HBM by the chip's compiler, which then refuses a kernel's own copy of the
unpadded part; such pools take :func:`_grid_kernel` (decode only: their
chunks keep the reference, :func:`untileable`), the form every pool
took until PR 28: the pages come through the pipeline (the K/V BlockSpec's
index_map reads the block table), one a grid step along a third axis as long
as the table, dead steps clamped to the last live page. Same math
(:func:`_attend`), chosen from the shape, no switch.

Capability slot of the reference's fused ``softmax_context`` decode kernels
(csrc/transformer/inference/csrc/pt_binding.cpp:1703-1779) generalized to
the vLLM-style paged layout; the multi-page double-buffered copy is shared
in kind with jax's own ``pallas/ops/tpu/paged_attention`` kernel.

**A learned indexer's selection** (PR 45, ``select=``; ``sparse_select.py``):
a layer whose queries attend their top-k keys by an index score hands the
kernel those scores for every key of the lane and, a query row, the value of
its k-th best and the position up to which equal scores count. The scores
ride in HBM as ``[lanes, groups, rows, P * block_size]`` (a chunk's:
``[lanes, groups, tiles, rows, 128]``, a lane tile of a copy group's keys at
a time, as its turn takes them), a copy group's keys whole 128-lane tiles
(so a selecting call takes at least ``128 / block_size`` pages a group),
copied beside the group's pages under a third semaphore; the threshold and
tie position are a lane's VMEM block. A key
the row did not select is masked beside the causal, context and window
masks: the loop still walks every live page (with seeded weights the
selected keys scatter over all of them; reading fewer is ROADMAP M7 (a)).
Decode rows of one token share its selection; a row that selected
everything it sees runs the dense arithmetic.

In-kernel score features (parity with the flash kernel): ALiBi via
per-head slopes, Gemma-2 tanh softcap, causal masking by per-sequence
context length, and a sliding window. The jnp oracle
:func:`paged_attention_reference` computes the identical math by dense
gather — the CPU fallback and the parity target for the interpret-mode
tests.

The pool operand is row-major ``[L?, kvh, num_blocks, block_size, hd]``
(``kvh``: the model's KV heads) and the kernel reads it where it lies.
Whoever writes the pool has to leave it so: ``serving.model_runner`` updates
it in place with dynamic-update-slices because the layout the chip's compiler gives a scatter's operand (slots
major) had the whole pool copied to this one before every call. The int8
tier's scales are read as ``[L?, kvh, num_blocks, 1, lanes]``
(:func:`scale_rows`), a page's slots on the first lanes of whole 128-lane
tiles: the chip pads a ``(1, block_size)`` float32 row in HBM to that anyway
and lets a kernel copy no less.
"""

from __future__ import annotations

from functools import partial, reduce
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF
from .sparse_select import selected as _selected
from .sparse_select import untileable as _select_untileable

__all__ = ["paged_attention", "paged_attention_reference", "scale_rows",
           "untileable", "group_span", "chunk_plan", "chunk_walk"]

#: least query rows a stored head's tile holds — the sublane minimum, so
#: every operand is a legal (>=8)x128 tile: a decode token of a head nobody
#: shares is broadcast over them, a group of up to eight query heads fills
#: them a row each
_QROWS = 8

#: VMEM the K and V group buffers (two slots each) may take together
_VMEM_BUDGET = 4 << 20

#: query rows a program may hold, QUERY heads a program x rows a head: a
#: prefill chunk's accumulator, its two running rows and its query and output
#: tiles grow with them (3.5 KB a row of 128-wide heads)
_CHUNK_ROWS = 1024

#: rows a chunk's query tile is padded to. A serving loop compiles a prefill
#: program a chunk shape (eight in the benchmark's cells) and each traces
#: and lowers this kernel anew at every start: padded to one tile they are
#: one shape, traced once (:func:`paged_attention`), and the rows past a
#: chunk's own cost a first chunk little, since the loop walks live pages
_CHUNK_TILE = 256

#: keys of a CHUNK program's copy group (PR 51). A turn pays a cost a (head,
#: row) whatever its keys (the row's max across lanes, the accumulator read,
#: scaled and written, the copies started and waited for): 256 / 512 / 1 024
#: keys a turn read 2.70 / 2.36 / 2.57 ms for the long-document cell's chunk
#: call behind 5 120 keys, and a first chunk 0.46 / 0.53 / 0.72 (PERF.md,
#: PR 51). One query head's ``[256, 512]`` float32 scores (512 KB) live at
#: once
_CHUNK_KEYS = 512

#: lanes of a vector register: a chunk's turn takes its keys a tile of this
#: many at a time (:func:`_lane_tile`)
_LANES = 128



def _query_rows(T: int, group: int = 1) -> int:
    """Rows of a query tile. A decode token: a stored head's tile holds the
    ``group`` query heads that share it, a row each, at least the sublane
    minimum and whole sublane tiles (``group`` 1: the one token broadcast).
    A chunk: a QUERY head's T rows padded to whole :data:`_CHUNK_TILE`s, so
    that every chunk shape of a serving loop is one call of one shape."""
    if T == 1:
        return max(_QROWS, -(-group // 8) * 8)
    return -(-T // _CHUNK_TILE) * _CHUNK_TILE


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most`` (at least 1)."""
    return max(d for d in range(1, max(1, min(n, most)) + 1) if n % d == 0)


def _head_group(kvh: int, block_k: int, hd: int, itemsize: int,
                T: int = 1, group: int = 1) -> int:
    """Stored heads per program: target ~1MB K blocks, largest divisor of
    ``kvh``; a chunk of T > 1 query rows a query head takes no more stored
    heads than keep the program's rows (``group`` query heads each) within
    :data:`_CHUNK_ROWS`."""
    target = max(1, (1 << 20) // (block_k * hd * itemsize))
    if T > 1:
        target = min(target, _CHUNK_ROWS // (group * _query_rows(T)))
    return _divisor(kvh, target)


def _program_heads(nh: int, kvh: int, block_k: int, hd: int, itemsize: int,
                   T: int = 1):
    """``(hg, gq)``: the stored heads a program takes and the query heads of
    each of their groups it serves. A decode token: :func:`_head_group`'s
    stored heads, their whole groups (rows of one tile). A chunk of T > 1
    rows a head: as many whole groups as keep the program's rows within
    :data:`_CHUNK_ROWS`; where one group alone passes it, ONE stored head
    and the largest divisor of its group that fits, the group's other query
    heads going to further programs that copy the same stored head."""
    group = nh // kvh
    gq = group if T == 1 else _divisor(group, _CHUNK_ROWS // _query_rows(T))
    hg = _head_group(kvh, block_k, hd, itemsize, T, gq) if gq == group else 1
    return hg, gq


def _scale_lanes(bs: int) -> int:
    """Lanes of one page's scale row as the kernel copies it: whole
    128-lane tiles. The chip's compiler pads a (1, bs) float32 row in HBM
    to that anyway and refuses a manual copy of less than the padded row."""
    return -(-bs // 128) * 128


def scale_rows(scale, pool_shape) -> jnp.ndarray:
    """The int8 tier's scales ``[..., num_blocks, 1, lanes]``, a page's
    ``block_size`` slots on the first lanes of a row of
    :func:`_scale_lanes`: as they are when they come so
    (``serving.model_runner`` carries them so through its layer loop), else
    from any shape that reshapes to ``[..., num_blocks, block_size]``,
    padded here (a copy of the whole scale pool)."""
    bs = pool_shape[-2]
    scale = jnp.asarray(scale, jnp.float32)
    rows = pool_shape[:-2] + (1, _scale_lanes(bs))
    if scale.shape == rows:
        return scale
    scale = scale.reshape(pool_shape[:-2] + (1, bs))
    return jnp.pad(scale, [(0, 0)] * (scale.ndim - 1) + [(0, rows[-1] - bs)])


def _pages_per_group(hq: int, bs: int, hd: int, itemsize: int, nbk: int,
                     quant: bool = False, T: int = 1) -> int:
    """Pages of one copy group, from the operand shapes alone, for a program
    that serves ``hq`` QUERY heads: the largest power of two at which K and V
    group buffers of ``hq`` heads, two slots each, stay inside
    :data:`_VMEM_BUDGET` (the int8 tier's scale rows counted as the padded
    (8, 128) float32 tiles they may take in VMEM), never more than the
    table holds; under a chunk of T > 1 query rows a head also no more than
    :data:`_CHUNK_KEYS` keys.

    The buffers hold the STORED heads, ``hq // group`` of them, so a
    grouped-query model's take ``1 / group`` of the budget: the pages are
    counted by the query heads all the same, as they were when the pool held
    a row for each. A decode program's copies are unrolled, a page a
    descriptor at four sites, and each descriptor is 25-35 ms of tracing and
    lowering at every start: at the 16 pages 8 stored heads would allow,
    ``setup_s`` rose 3.3 s (mistral-7b, 4 pages before) and 5.7 s
    (K-EXAONE, 2), and the kernel was no faster than at 4 or 8 (PERF.md,
    PR 44). A chunk's copies are a loop."""
    page = 4 * hq * bs * hd * itemsize            # K and V, two slots
    if quant:                                # :func:`_scale_lanes` of bs
        page += 4 * hq * 8 * (-(-bs // _LANES) * _LANES) * 4
    p = 1
    while 2 * p * page <= _VMEM_BUDGET and 2 * p <= nbk \
            and (T == 1 or 2 * p * bs <= _CHUNK_KEYS):
        p *= 2
    return p


def _lane_tile(n: int) -> int:
    """Keys of one lane tile of a chunk's copy group of ``n`` keys: a vector
    register's lanes, or the whole group where it is no whole tiles (a table
    shorter than one, pages that do not divide one, the interpreter's small
    shapes)."""
    return _LANES if n % _LANES == 0 else n


def group_span(ctx, start, window, P: int, bs: int, nbk: int, xp=jnp):
    """``(first, cnt, g0, g1)``: a lane's first and one-past-last live page
    and the copy groups of ``P`` pages that hold them, for a context of
    ``ctx`` tokens whose first query's window ends at ``start`` (a decode
    token: ``ctx``; a chunk: one past its first row). A window drops the
    pages wholly before it (before the window of a chunk's FIRST query: the
    last real one, at ctx - 1, sets the last page); an idle lane (ctx 0) has
    no group at all. The kernel's own rule (:func:`_loop_kernel`, handed in:
    the serving loop calls it on the host, and the package's linter takes
    every function a traced body names for device work); ``xp``: numpy for a
    host that counts what the kernel will do (:func:`chunk_walk`)."""
    cnt = xp.minimum((ctx + bs - 1) // bs, nbk)
    first = xp.where(window > 0, xp.maximum(start - window, 0) // bs, 0)
    return first, cnt, first // P, (cnt + P - 1) // P


def _plan(nh: int, kvh: int, bs: int, hd: int, itemsize: int, nbk: int,
          T: int, quant: bool = False, select: bool = False):
    """``(hg, gq, rows, P, lanes)`` of a call, from its shapes alone: the
    stored heads a program takes and the query heads of each it serves
    (:func:`_program_heads`), the rows of a query tile
    (:func:`_query_rows`), the pages of its copy group
    (:func:`_pages_per_group`; under a selection a group's keys fill whole
    128-lane rows of the index scores) and the keys of a lane tile of it, as
    a chunk's turn takes them (:func:`_lane_tile`). Made where the call is
    (:func:`paged_attention`) and handed to the jitted one as a static: the
    serving loop asks the same functions on the host
    (:func:`chunk_plan`), and the package's linter takes every function a
    traced body names for device work."""
    hg, gq = _program_heads(nh, kvh, bs, hd, itemsize, T)
    P = _pages_per_group(hg * gq, bs, hd, itemsize, nbk, quant, T)
    if select:
        P = max(P, -(-_LANES // bs))
    return (hg, gq, _query_rows(T, nh // kvh if T == 1 else 1), P,
            _lane_tile(P * bs))


def chunk_plan(nh: int, kvh: int, bs: int, hd: int, itemsize: int, nbk: int,
               T: int, quant: bool = False, select: bool = False):
    """``(programs a lane, pages a copy group, keys a lane tile)`` of a call
    of T > 1 rows a lane, from the shapes as :func:`paged_attention` derives
    them."""
    hg, gq, _, P, lanes = _plan(nh, kvh, bs, hd, itemsize, nbk, T, quant,
                                select)
    return kvh // hg * (nh // kvh // gq), P, lanes


def chunk_walk(q_start: int, ctx: int, window: int, T: int, P: int,
               lanes: int, bs: int, nbk: int):
    """``(turns, key tiles, live key tiles)`` of ONE program of a lane whose
    T > 1 rows start at ``q_start`` in a context of ``ctx`` under a layer's
    ``window`` (<= 0: none), ``P`` pages of ``bs`` slots a copy group,
    ``lanes`` keys a lane tile (:func:`chunk_plan`): the copy groups the
    program walks (:func:`group_span`), the lane tiles those hold, each
    computed for every :data:`_CHUNK_TILE` of the program's rows,
    and those of them that hold a key some REAL row of that row tile sees
    (causal, inside the context and the window): a first chunk's turn, a
    window's, and the turn a context ends in compute tiles that are all
    mask. On the host, with numpy: ``serving.engine`` counts them."""
    n = P * bs
    _, _, g0, g1 = group_span(ctx, q_start + 1, window, P, bs, nbk, np)
    if g1 <= g0:
        return 0, 0, 0
    tile = np.arange(g0 * n, g1 * n, lanes)[:, None]        # its first key
    first = q_start + np.arange(0, T, _CHUNK_TILE)[None]   # its row tiles
    last = np.minimum(first + _CHUNK_TILE, ctx) - 1
    low = np.maximum(first + 1 - window, 0) if window > 0 else 0
    live = (first < ctx) & (tile <= last) & (tile + lanes > low)
    return int(g1 - g0), live.size, int(live.sum())


def _attend(q, k, v, ks, vs, k0, ctx, window, slopes_ref, acc, m_scr, l_scr,
            *, sm_scale, softcap, sel=None):
    """One online-softmax update of a DECODE token: the query tile ``q``
    [hg, rows, hd] of ``hg`` stored heads, a stored head's rows the query
    heads that share it, their one token at ``ctx - 1`` each (a lone head's
    token broadcast), against their keys ``k`` / values ``v`` [hg, n, hd] at
    logical positions ``[k0, k0 + n)``, folded into the running max, sum and
    output. (A prefill chunk's turn is :func:`_attend_chunk`.)

    ``sel`` (a layer with an indexer, ``sparse_select``): ``(scores [1, n],
    thr [1, 1], tie [1, 1])`` of these n keys (the token's query heads share
    its selection); a key that is not one of the top k is masked like one
    the causal mask hides."""
    def matmul(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((0,), (0,))),
                                   preferred_element_type=jnp.float32)

    if ks is not None:
        # int8 tier (round 17): the copies moved int8 rows + one f32 scale
        # per (head, slot); dequantize HERE, on the keys already in VMEM —
        # only int8 crossed HBM. The scales arrive [hg, 1, n] (slots on
        # the LANE axis, the layout the chip's compiler takes), which is
        # the score tile's own layout: q.(k_i * s_i) == (q.k_i) * s_i, so
        # the K scale multiplies the scores and the V scale the
        # probabilities — no lane->sublane relayout, and the int8 ->
        # q.dtype convert is exact (|int8| <= 127)
        k = k.astype(jnp.float32).astype(q.dtype)
        v = v.astype(jnp.float32).astype(q.dtype)
    s = matmul(q, k, ((2,), (2,)))
    if ks is not None:
        s = s * ks
    s = s * sm_scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    # the keys' logical positions do not depend on which PHYSICAL pages the
    # table routed the copies to; one real query a row (a lone head's:
    # broadcast over the 8 padded rows), all at absolute (logical) position
    # ctx - 1
    q_abs = ctx - 1
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    if slopes_ref is not None:
        # a slope a head of the tile, [heads, 1, 1]; a decode tile of a
        # group has a query head a row: [hg, rows, 1]
        slope = (slopes_ref[0][:, :, :1] if len(slopes_ref.shape) == 4
                 else slopes_ref[0][:, :1][:, None, :])
        s = s + slope * (k_pos - q_abs).astype(jnp.float32)
    keep = k_pos <= q_abs                                   # causal + dead tail
    keep &= (q_abs - k_pos < window) | (window <= 0)        # sliding window
    if sel is not None:
        sc, thr, tie = sel
        at = k0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        s = s + jnp.where(_selected(sc, thr, tie, at), 0.0, NEG_INF)[None]
    s = jnp.where(keep, s, NEG_INF)
    m_prev = m_scr[:, :, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_scr[:, :, :1] = l_scr[:, :, :1] * alpha + jnp.sum(p, axis=2,
                                                        keepdims=True)
    pv = p * vs if vs is not None else p
    acc[...] = acc[...] * alpha + matmul(pv.astype(v.dtype), v,
                                         ((2,), (1,)))
    m_scr[:, :, :1] = m_cur


def _finish(o_ref, acc, l_scr):
    l = l_scr[:, :, :1]
    o_ref[0, 0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _attend_chunk(q_ref, k_buf, v_buf, scales, sel, slot, k0, ctx, q0,
                  window, slopes_ref, acc, m_scr, l_scr, *, gq, sm_scale,
                  softcap):
    """One turn of a prefill CHUNK's program: its ``heads = hg x gq`` query
    heads' rows (``q_ref`` [1, 1, heads, rows, hd], row r of each the query
    at ``q0 + r``, head h reading stored head ``h // gq``) against the copy
    group in buffer ``slot`` (``k_buf`` / ``v_buf`` [2, hg, n, hd], keys at
    logical positions ``[k0, k0 + n)``), folded into the running max, sum
    and output; a :data:`_CHUNK_TILE` of rows and a query head at a time,
    the group's keys as lane tiles of ``lanes`` (``m_scr``'s width).

    * The masks once a row tile for all of its heads, as ONE additive tile a
      lane tile, 0 or ``NEG_INF``: causal and the context's end (a key up to
      ``min(row, ctx - 1)``: a row at or past ctx is bucket padding, sees the
      live keys and no page the loop skipped, finite and read by nobody), the
      window (a key behind ``row - window``), and a layer's selection
      (``sel``: the group's index scores ``[2, tiles, rows, lanes]`` beside
      each row's threshold and tie position, ``sparse_select.selected``).
      Added, not selected by, so a key no copy brought has to be finite: the
      first program zeroes the K buffers too (:func:`_loop_kernel`).
    * A head's scores in ONE matmul against the stored head it shares (one
      matmul over a stored head's ``gq x rows`` queries ran a third slower
      on the chip: PERF.md, PR 44); the running max lies alike on all
      ``lanes`` of its row, so the tiles' elementwise max crosses lanes ONCE
      a (head, turn); the running sum lies A LANE APART (a lane its share of
      the keys, added up in :func:`_finish_chunk`); one value matmul and ONE
      rescale of the accumulator a (head, turn).

    With a row's max AND sum across lanes, the four heads' ``[4, 256, 256]``
    scores at once and an accumulator pass every 256 keys the call read 1.5
    to 1.9 times this one's time behind 5 120 keys; loops of a dynamic trip
    count over the live lane tiles only read SLOWER than the parent at every
    shape (PERF.md, PR 51). int8 tier: ``scales`` = the group's K and V
    scales ``[hg, 1, n]`` (:func:`_attend`)."""
    heads, rows, hd = acc.shape
    n, lanes, rt = k_buf.shape[2], m_scr.shape[-1], _CHUNK_TILE
    tiles = [slice(j, j + lanes) for j in range(0, n, lanes)]
    dot = partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
    for r0 in range(0, rows, rt):
        at = pl.ds(r0, rt)
        q_abs = q0 + r0 + jax.lax.broadcasted_iota(jnp.int32, (rt, lanes), 0)
        k_pos = [k0 + t.start + jax.lax.broadcasted_iota(
            jnp.int32, (rt, lanes), 1) for t in tiles]
        last = jnp.minimum(q_abs, ctx - 1)
        behind = q_abs - jnp.where(window > 0, window, 1 << 30)
        keep = [(k <= last) & (k > behind) for k in k_pos]
        if sel is not None:
            sel_buf, thr_ref, tie_ref = sel
            one = slice(None) if lanes == thr_ref.shape[-1] else slice(1)
            thr, tie = thr_ref[0, at, one], tie_ref[0, at, one]
            keep = [ok & _selected(sel_buf[slot, j, at], thr, tie, k)
                    for j, (ok, k) in enumerate(zip(keep, k_pos))]
        bias = [jnp.where(ok, 0.0, NEG_INF) for ok in keep]
        for h in range(heads):
            kv = h // gq
            q, k, v = q_ref[0, 0, h, at], k_buf[slot, kv], v_buf[slot, kv]
            if scales is not None:
                k = k.astype(jnp.float32).astype(q.dtype)
                v = v.astype(jnp.float32).astype(q.dtype)
            s = dot(q, k, (((1,), (1,)), ((), ())))
            if scales is not None:
                s = s * scales[0][kv]
            s = s * sm_scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = [s[:, t] for t in tiles]
            if slopes_ref is not None:
                slope = slopes_ref[0][h:h + 1, :1]
                s = [x + slope * (k - q_abs).astype(jnp.float32)
                     for x, k in zip(s, k_pos)]
            s = [x + b for x, b in zip(s, bias)]
            m_prev = m_scr[h, at]
            m_cur = jnp.maximum(m_prev, jnp.max(
                reduce(jnp.maximum, s), axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = [jnp.exp(x - m_cur) for x in s]
            l_scr[h, at] = l_scr[h, at] * alpha + sum(p[1:], p[0])
            p = jnp.concatenate(p, axis=1)
            if scales is not None:
                p = p * scales[1][kv]
            # the lanes of a row's ``alpha`` that rescale its accumulator:
            # all of them where the head is as wide, else one, broadcast
            acc[h, at] = acc[h, at] * alpha[:, :lanes if hd == lanes else 1] \
                + dot(p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
            m_scr[h, at] = m_cur


def _finish_chunk(o_ref, acc, l_scr):
    l = jnp.sum(l_scr[...], axis=2, keepdims=True)
    o_ref[0, 0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _grid_kernel(bt_ref, lens_ref, misc_ref, q_ref, k_ref, v_ref, *rest, bs,
                 nbk, sm_scale, softcap, has_alibi, stacked, quant):
    """Heads narrower than a lane tile (``hd % 128``): the chip's compiler
    pads such a pool's rows to 128 lanes in HBM and refuses a kernel's own
    copy of less than a padded row, so the pages come through the
    pipeline, one a grid step along a third axis as long as the table. A
    dead step (``j >= cnt``) repeats its sequence's last live page, which
    the pipeline does not copy again, and computes nothing; it still
    costs its 0.2-0.3 us (PERF.md, PR 28), which is why wider heads take
    :func:`_loop_kernel`."""
    if quant:
        ks_ref, vs_ref, slopes_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        slopes_ref, o_ref, acc, m_scr, l_scr = rest
    b, j = pl.program_id(0), pl.program_id(2)
    ctx = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(j < (ctx + bs - 1) // bs)
    def _compute():
        page = lambda ref: ref[0, :, 0] if stacked else ref[:, 0]
        ks = vs = None
        if quant:
            ks, vs = page(ks_ref)[:, :, :bs], page(vs_ref)[:, :, :bs]
        _attend(q_ref[0, 0], page(k_ref), page(v_ref), ks, vs, j * bs, ctx,
                misc_ref[0], slopes_ref if has_alibi else None, acc, m_scr,
                l_scr, sm_scale=sm_scale, softcap=softcap)

    @pl.when(j == nbk - 1)
    def _finalize():
        _finish(o_ref, acc, l_scr)


def _loop_kernel(bt_ref, lens_ref, misc_ref, q_ref, k_hbm, v_hbm, *rest, hg,
                 bs, P, nbk, sm_scale, softcap, has_alibi, stacked, quant,
                 splits=1, chunk=False, select=False, spans=None):
    rest = list(rest)
    ks_hbm, vs_hbm = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    slopes_ref = rest.pop(0)
    # a layer with an indexer: the index scores of the lane's keys, a group's
    # a row of tiles ``[groups, R, P * bs]`` copied beside its pages, and each
    # query row's threshold and tie position (``sparse_select.selected``)
    sel_hbm, thr_ref, tie_ref = (rest.pop(0), rest.pop(0), rest.pop(0)) \
        if select else (None, None, None)
    o_ref, k_buf, v_buf = rest.pop(0), rest.pop(0), rest.pop(0)
    ks_buf, vs_buf = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    sel_buf = rest.pop(0) if select else None
    acc, m_scr, l_scr, state, sem = rest
    b, g = pl.program_id(0), pl.program_id(1)
    nb, ng = pl.num_programs(0), pl.num_programs(1)
    window, layer = misc_ref[0], misc_ref[1]

    def span(b):
        """Sequence b's first and one-past-last live page, and the groups of
        P pages that hold them (a chunk's first query stands at
        ``misc[2 + b]``)."""
        ctx = lens_ref[b]
        return spans(ctx, misc_ref[2 + b] + 1 if chunk else ctx, window, P,
                     bs, nbk)

    def page_copies(heads, b, first, cnt, i, slot, p):
        """(live, its copies) of page p of sequence b's group i into buffer
        ``slot``: the heads of a head group, one physical page of one layer,
        out of the pool where it lies. Starting and waiting rebuild the
        same descriptors."""
        page = i * P + p
        live = (page >= first) & (page < cnt)
        phys = bt_ref[b, jnp.minimum(page, nbk - 1)]
        at = (layer, heads, phys) if stacked else (heads, phys)
        rows = pl.ds(p * bs if isinstance(p, int)
                     else pl.multiple_of(p * bs, bs), bs)
        pairs = [(k_hbm, k_buf.at[slot, :, rows], 0),
                 (v_hbm, v_buf.at[slot, :, rows], 1)]
        if quant:
            pairs += [(ks_hbm, ks_buf.at[slot, :, p], 0),
                      (vs_hbm, vs_buf.at[slot, :, p], 1)]
        return live, [pltpu.make_async_copy(pool.at[at], dst, sem.at[s, slot])
                      for pool, dst, s in pairs]

    def each_copy(act, b, g, *group):
        """``act`` ("start" or "wait") on every live page's copies of group
        ``i`` of sequence b, program g (``group``: first, cnt, i, slot): the
        ``hg`` stored heads of head group g, or, where a stored head's query
        heads are split over ``splits`` programs, the one they share.
        A decode program has them unrolled, P pages a site; a chunk's
        programs loop over the pages instead: every prefill program of a
        serving loop lowers this kernel at every start, and the unrolled
        sites were most of its text (PERF.md, PR 37: ``setup_s``)."""
        heads = pl.ds(g * hg if splits == 1
                       else jax.lax.div(g, jnp.int32(splits)), hg)
        if select:
            i, slot = group[2], group[3]
            getattr(pltpu.make_async_copy(sel_hbm.at[b, i], sel_buf.at[slot],
                                          sem.at[2, slot]), act)()
        if not chunk:
            for live, copies in [page_copies(heads, b, *group, p)
                                 for p in range(P)]:
                for copy in copies:
                    pl.when(live)(getattr(copy, act))
            return

        def body(p, _):
            live, copies = page_copies(heads, b, *group, p)

            @pl.when(live)
            def _():
                for copy in copies:
                    getattr(copy, act)()

        jax.lax.fori_loop(0, P, body, None)

    start, wait = partial(each_copy, "start"), partial(each_copy, "wait")

    first, cnt, g0, g1 = span(b)
    # the program that runs next: its first group is started from this
    # one's last turn of the loop, so only the call's very first group is
    # waited for with nothing to compute
    g_nxt = jnp.where(g + 1 < ng, g + 1, 0)
    b_nxt = jnp.minimum(jnp.where(g + 1 < ng, b, b + 1), nb - 1)
    first_nxt, cnt_nxt, n0, n1 = span(b_nxt)
    has_nxt = ((b + 1 < nb) | (g + 1 < ng)) & (n0 < n1)

    @pl.when((b == 0) & (g == 0))
    def _first_program():
        # a page the loop skips keeps what the buffer held: masked scores
        # give it probability 0, and 0 times a stale NaN is a NaN
        v_buf[...] = jnp.zeros_like(v_buf)
        if quant:
            vs_buf[...] = jnp.zeros_like(vs_buf)
        if chunk:
            # ... and a chunk ADDS its masks to the scores: finite keys
            k_buf[...] = jnp.zeros_like(k_buf)
            if quant:
                ks_buf[...] = jnp.zeros_like(ks_buf)
        state[0] = 0        # the slot this program's loop starts in
        state[1] = 0        # 1: the program before started its first group

    slot0 = state[0]

    @pl.when((g0 < g1) & (state[1] == 0))
    def _start_own():
        start(b, g, first, cnt, g0, slot0)

    state[1] = 0
    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)

    def group(i, _):
        slot = (slot0 + i - g0) % 2

        @pl.when(i + 1 < g1)
        def _next_group():
            start(b, g, first, cnt, i + 1, 1 - slot)

        @pl.when((i + 1 == g1) & has_nxt)
        def _next_program():
            start(b_nxt, g_nxt, first_nxt, cnt_nxt, n0, 1 - slot)
            state[1] = 1

        wait(b, g, first, cnt, i, slot)
        ks = vs = None
        if quant:
            ks, vs = (jnp.concatenate(
                [buf[slot, :, p, :, :bs] for p in range(P)], -1)
                for buf in (ks_buf, vs_buf))
        slopes = slopes_ref if has_alibi else None
        if chunk:
            _attend_chunk(q_ref, k_buf, v_buf, (ks, vs) if quant else None,
                          (sel_buf, thr_ref, tie_ref) if select else None,
                          slot, i * (P * bs), lens_ref[b], misc_ref[2 + b],
                          window, slopes, acc, m_scr, l_scr,
                          gq=acc.shape[0] // hg,
                          sm_scale=sm_scale, softcap=softcap)
            return
        sel = (sel_buf[slot], thr_ref[0][:, :1], tie_ref[0][:, :1]) \
            if select else None
        _attend(q_ref[0, 0], k_buf[slot], v_buf[slot], ks, vs, i * (P * bs),
                lens_ref[b], window, slopes, acc, m_scr, l_scr,
                sm_scale=sm_scale, softcap=softcap, sel=sel)

    jax.lax.fori_loop(g0, g1, group, None)
    state[0] = (slot0 + g1 - g0) % 2
    (_finish_chunk if chunk else _finish)(o_ref, acc, l_scr)


def _stored_heads(nh: int, pool_shape, stacked: bool) -> int:
    """The heads the pool stores (the model's KV heads), which the query's
    ``nh`` heads share ``nh // kvh`` each: read from the two shapes."""
    kvh = pool_shape[1 if stacked else 0]
    if nh % kvh:
        raise ValueError(f"{nh} query heads do not share the pool's {kvh} "
                         "stored heads evenly")
    return kvh


def untileable(q_shape, pool_shape, *, stacked: bool, quant: bool,
               interpret: bool = False, select: bool = False
               ) -> Optional[str]:
    """The kernel's tiling rules as a test made BEFORE the call: the reason
    these shapes cannot ride the kernel, or None when they can. Dispatchers
    route on this instead of catching the kernel's errors, so a refusal by
    the chip's compiler can never be read as "shapes don't tile"."""
    T, hd = q_shape[2], q_shape[3]
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
    if T > 1 and hd % 128 != 0:
        return (f"head_dim {hd} is no multiple of 128 lanes: such a pool's "
                "pages come through the pipeline a grid step each, for one "
                f"query row a sequence (got T={T})")
    if select and hd % 128 != 0:
        return (f"head_dim {hd} is no multiple of 128 lanes: such a pool's "
                "pages come through the pipeline, which carries no selection")
    if select and _select_untileable(bs):     # whole pages a 128-lane row
        return _select_untileable(bs)
    if T > _CHUNK_ROWS:              # whole tiles: the padded rows too
        return (f"{T} query rows a sequence are more than one program "
                f"holds ({_CHUNK_ROWS}): chunk the prefill")
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
                    q_start=None,
                    select=None,
                    interpret: bool = False) -> jnp.ndarray:
    """T query tokens per sequence against a paged KV pool: a decode step's
    one, or a prefill chunk's T > 1, whose own keys the pool holds already.

    q: [B, nh, T, hd], ``nh`` a multiple of the pool's ``kvh`` stored heads
       (query head h reads stored head ``h // (nh // kvh)``; the group is a
       stored head's query tile, module docstring).
       T == 1: each sequence's fresh query, at logical
       position ``context_lens[b] - 1`` (context_lens INCLUDES the new
       token). T > 1: queries at ``q_start[b] + row`` (``q_start`` [B];
       ``context_lens - T`` when not given), causal among themselves; rows
       at or past ``context_lens[b]`` are bucket padding, whose finite
       garbage the caller discards. Heads a program and pages a group
       shrink with T (:func:`_head_group`, :func:`_pages_per_group`).
    k_pool/v_pool: [kvh, num_blocks, block_size, hd]; with ``layer_idx``
       (traced i32 ok) the stacked [L, kvh, num_blocks, block_size, hd]
       layout — the kernel's copies pick the layer straight out of the
       scan-carried pool, no materialized per-layer slice.
    k_scale/v_scale: the int8 tier (round 17) — pools are int8 in the
       ``quant_format.kv_quantize`` layout and these carry the f32
       per-(layer, stored head, slot) scales: :func:`scale_rows`' layout
       (taken as it is) or any shape that reshapes to the pool's
       [..., num_blocks, block_size], e.g. init_pool's
       [L, kvh, num_slots, 1] (padded here: a copy of the scale pool a
       call). The scale rows are copied through the SAME block table
       beside k/v and the dequant happens in-kernel, so the HBM read is
       int8 + one padded scale row a page — no pool-slice f32 copy exists.
    block_tables: [B, max_blocks] i32 — logical block j of sequence b
       lives in physical pool block ``block_tables[b, j]``. Entries past
       the live count are never read.
    context_lens: [B] i32. ``window``: python int or traced i32, <= 0
       means global. ``alibi_slopes``: [nh] per-head slopes (in-kernel
       bias slope * (k_pos - q_pos)). ``softcap``: Gemma-2 tanh cap
       (STATIC float — it changes the compiled math).
    select: a layer with an indexer (``sparse_select.Selection``: the index
       scores ``[B, T, Kp]`` of every key of the lane for every query row,
       and each row's ``thr`` / ``tie`` ``[B, T]``): a row attends the keys
       that ``sparse_select.selected`` keeps, beside the causal, context and
       window masks. The kernel walks every live page and masks: the scores
       of a group's keys are copied beside its pages.

    Returns [B, nh, T, hd]. Raises ValueError (the :func:`untileable`
    reason) when shapes can't tile — callers ask :func:`untileable` FIRST
    and route to :func:`paged_attention_reference` on a reason, so an
    error out of this function is never mistaken for one.
    """
    T = q.shape[2]
    stacked = layer_idx is not None
    quant = k_scale is not None
    reason = untileable(q.shape, k_pool.shape, stacked=stacked, quant=quant,
                        interpret=interpret, select=select is not None)
    if reason is not None:
        raise ValueError(reason)
    if quant:
        if k_pool.dtype != jnp.int8:
            raise ValueError("k_scale/v_scale given but the pool dtype is "
                             f"{k_pool.dtype} — scales pair with int8 pools")
        k_scale = scale_rows(k_scale, k_pool.shape)
        v_scale = scale_rows(v_scale, v_pool.shape)
    elif k_pool.dtype == jnp.int8:
        raise ValueError("int8 KV pool needs k_scale/v_scale "
                         "(quant_format.kv_quantize layout)")
    nh, hd = q.shape[1], q.shape[3]
    plan = _plan(nh, _stored_heads(nh, k_pool.shape, stacked),
                 k_pool.shape[3 if stacked else 2], hd,
                 k_pool.dtype.itemsize, block_tables.shape[1], T, quant,
                 select is not None)
    kw = dict(sm_scale=sm_scale, alibi_slopes=alibi_slopes,
              softcap=float(softcap) if softcap else 0.0, window=window,
              layer_idx=layer_idx, k_scale=k_scale, v_scale=v_scale,
              select=select, interpret=interpret, plan=plan)
    if T == 1:
        return _paged_attention(q, k_pool, v_pool, block_tables,
                                context_lens, q_start=None, **kw)
    # a chunk: its rows padded to whole tiles, each lane's first position
    # made explicit, and the call shared by every program of these shapes
    # (the interpreter's parameter object is not hashable: called as it is)
    lens = jnp.asarray(context_lens, jnp.int32).reshape(q.shape[0])
    q0 = lens - T if q_start is None else jnp.asarray(
        q_start, jnp.int32).reshape(q.shape[0])
    q = jnp.pad(q, [(0, 0), (0, 0), (0, _query_rows(T) - T), (0, 0)])
    call = _shared_chunk_call if isinstance(interpret, bool) \
        else _paged_attention
    return call(q, k_pool, v_pool, block_tables, lens, q_start=q0,
                **kw)[:, :, :T]


def _paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                     sm_scale, alibi_slopes, softcap, window, layer_idx,
                     k_scale, v_scale, q_start, interpret, plan, select=None):
    """:func:`paged_attention`, its shapes found tileable, on a query of
    whole tiles (one row, or a chunk's rows padded to :func:`_query_rows`)
    and scales in :func:`scale_rows`' layout; ``plan``: :func:`_plan`."""
    B, nh, T, hd = q.shape
    stacked = layer_idx is not None
    quant = k_scale is not None
    kvh = _stored_heads(nh, k_pool.shape, stacked)
    bs = k_pool.shape[3 if stacked else 2]
    ks_pool, vs_pool = k_scale, v_scale
    nbk = block_tables.shape[1]
    # the tile of one stored head: the ``group`` query heads that share it,
    # ``gq`` of them a program (all but for a chunk too tall for one). A
    # decode token's tile is [hg stored heads, a row a query head]; a
    # chunk's [heads = hg x gq query heads, a head's rows], as the output
    group = nh // kvh
    hg, gq, rows, P, lanes = plan
    splits = group // gq
    ng = kvh // hg * splits
    heads = hg if T == 1 else hg * gq
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(hd)

    if T > 1 or group == 1:
        qf = q.reshape(B, ng, heads, T, hd)
        if T == 1:
            # broadcast the single query row to the sublane minimum (all 8
            # rows are the real query; row 0 is read back)
            qf = jnp.broadcast_to(qf, (B, ng, heads, rows, hd))
    else:
        # a decode token of every query head of the group, a row each (the
        # rows past them, where the group fills no whole tile, are zeros
        # nobody reads)
        qf = jnp.pad(q.reshape(B, ng, hg, group, hd),
                     [(0, 0)] * 3 + [(0, rows - group), (0, 0)])

    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32).reshape(B)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(())
    li = jnp.asarray(0 if layer_idx is None else layer_idx,
                     jnp.int32).reshape(())
    misc = jnp.stack([win, li])
    if T > 1:
        # a chunk: each lane's first position, behind the window and layer
        misc = jnp.concatenate([misc, q_start])

    qo_spec = pl.BlockSpec((1, 1, heads, rows, hd),
                           lambda b, g, *_: (b, g, 0, 0, 0))
    online = [pltpu.VMEM((heads, rows, hd), jnp.float32),
              pltpu.VMEM((heads, rows, 128), jnp.float32),
              pltpu.VMEM((heads, rows, 128), jnp.float32)]
    static = dict(bs=bs, nbk=nbk, sm_scale=scale, softcap=softcap,
                  has_alibi=alibi_slopes is not None, stacked=stacked,
                  quant=quant)
    sel_ops, sel_specs = [], []
    if hd % 128 == 0 or T > 1 or select is not None:
        # the pools stay in HBM: the kernel copies the pages a lane holds
        # (a chunk of narrower heads comes here under the interpreter only:
        # :func:`untileable`)
        if select is not None:
            sel_ops, sel_specs = _selection_operands(
                select, B, T, rows, -(-nbk // P), P * bs, lanes)
        kernel = partial(_loop_kernel, hg=hg, P=P, splits=splits,
                         chunk=T > 1, select=select is not None,
                         spans=group_span, **static)
        grid = (B, ng)
        kv_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (4 if quant else 2)
        scratch = [pltpu.VMEM((2, hg, P * bs, hd), k_pool.dtype)] * 2
        if quant:
            scratch += [pltpu.VMEM((2, hg, P, 1, _scale_lanes(bs)),
                                   jnp.float32)] * 2
        if select is not None:
            scratch += [pltpu.VMEM((2,) + sel_ops[0].shape[2:], jnp.float32)]
        if T > 1:
            # :func:`_attend_chunk`: the running max alike on a lane tile's
            # lanes, the running sum a lane apart
            online[1:] = [pltpu.VMEM((heads, rows, lanes), jnp.float32)] * 2
        scratch += online + [pltpu.SMEM((2,), jnp.int32),
                             pltpu.SemaphoreType.DMA(
                                 (3 if select is not None else 2, 2))]
    else:
        # narrow heads (:func:`_grid_kernel`): a page a grid step through
        # the pipeline, a dead step clamped to the sequence's last live
        # page, whose repeated index the pipeline does not copy again
        kernel = partial(_grid_kernel, **static)
        grid = (B, ng, nbk)

        def page(b, g, j, bt_s, lens_s, misc_s):
            last = jnp.maximum((lens_s[b] + bs - 1) // bs - 1, 0)
            at = (g, bt_s[b, jnp.minimum(j, last)], 0, 0)
            return (misc_s[1],) + at if stacked else at

        lead = (1,) if stacked else ()
        kv_specs = [pl.BlockSpec(lead + (hg, 1, bs, hd), page)] * 2
        if quant:
            kv_specs += [pl.BlockSpec(
                lead + (hg, 1, 1, _scale_lanes(bs)), page)] * 2
        scratch = online
    operands = [qf, k_pool, v_pool] + ([ks_pool, vs_pool] if quant else [])
    if alibi_slopes is not None and (T > 1 or group == 1):
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(ng, heads)
        operands.append(jnp.broadcast_to(sl[:, :, None], (ng, heads, 128)))
        slopes_spec = pl.BlockSpec((1, heads, 128), lambda b, g, *_: (g, 0, 0))
    elif alibi_slopes is not None:
        # a decode tile of a group: a slope a row, on sublanes as the rows
        sl = jnp.asarray(alibi_slopes, jnp.float32).reshape(ng, hg, group)
        sl = jnp.pad(sl, [(0, 0), (0, 0), (0, rows - group)])
        operands.append(jnp.broadcast_to(sl[..., None], sl.shape + (128,)))
        slopes_spec = pl.BlockSpec((1, hg, rows, 128),
                                   lambda b, g, *_: (g, 0, 0, 0))
    else:
        # constant placeholder so the kernel arity is static
        operands.append(jnp.zeros((1, 1, 128), jnp.float32))
        slopes_spec = pl.BlockSpec((1, 1, 128), lambda b, g, *_: (0, 0, 0))

    operands += sel_ops
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid,
        in_specs=[qo_spec] + kv_specs + [slopes_spec] + sel_specs,
        out_specs=qo_spec, scratch_shapes=scratch)
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, ng, heads, rows, hd), q.dtype),
            interpret=interpret,
        )(bt, lens, misc, *operands)
    return out[:, :, :, :group if T == 1 else T].reshape(B, nh, T, hd)


def _selection_operands(select, B: int, T: int, rows: int, groups: int,
                        n: int, lanes: int):
    """``(operands, their specs)`` of a call's selection: the index scores
    of a copy group's ``n`` keys as ``n // lanes`` lane tiles ``[B, groups,
    tiles, R, lanes]`` (R the query rows: a chunk's padded rows, whose turn
    takes a tile at a time; a decode token's 1, the group ONE row of tiles:
    ``[B, groups, 1, n]``), left in HBM for the kernel's own copies; ``thr``
    and ``tie`` ``[B, R, 128]``, a row's on every lane, a lane's block in
    VMEM. Rows and keys past the call's own read ``-inf``: nothing of them
    is selected."""
    R = 1 if T == 1 else rows
    T, Kp = select.scores.shape[1:]     # the call's own rows, before padding
    keys = min(Kp, groups * n)
    sc = jnp.pad(select.scores[:, :, :keys].astype(jnp.float32),
                 [(0, 0), (0, R - T), (0, groups * n - keys)],
                 constant_values=-jnp.inf)
    if R == 1:
        sc = sc.reshape(B, R, groups, n).transpose(0, 2, 1, 3)
    else:
        sc = sc.reshape(B, R, groups, n // lanes, lanes).transpose(
            0, 2, 3, 1, 4)
    on_lanes = lambda a, fill: jnp.broadcast_to(jnp.pad(
        a, [(0, 0), (0, R - T)], constant_values=fill)[:, :, None],
        (B, R, 128))
    row_spec = pl.BlockSpec((1, R, 128), lambda b, g, *_: (b, 0, 0))
    return ([sc, on_lanes(select.thr.astype(jnp.float32), jnp.inf),
             on_lanes(select.tie.astype(jnp.int32), -1)],
            [pl.BlockSpec(memory_space=pl.ANY), row_spec, row_spec])


#: a chunk's call under ``jax.jit``: the prefill programs of a serving loop
#: all make it at ONE shape (:data:`_CHUNK_TILE`), so the kernel is traced
#: for the first and found for the rest (lowering is still a program's own)
_shared_chunk_call = jax.jit(
    _paged_attention,
    static_argnames=("sm_scale", "softcap", "interpret", "plan"))


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
                              q_start=None,
                              select=None) -> jnp.ndarray:
    """jnp oracle / CPU fallback: dense gather through the block table,
    then exactly the decode-path attention math (f32 scores, softcap
    before the ALiBi bias before the -1e30 masks, f32 softmax). Grouped as
    the kernel is: the ``nh // kvh`` query heads of a stored head are rows
    of one query against that head's gathered K/V, which is never repeated.

    Like the kernel, q may carry T > 1 query tokens (the
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
    kvh = _stored_heads(nh, k_pool.shape, layer_idx is not None)
    group = nh // kvh
    quant = k_scale is not None
    if quant:
        bs = k_pool.shape[-2]
        k_scale = scale_rows(k_scale, k_pool.shape)[..., 0, :bs]
        v_scale = scale_rows(v_scale, v_pool.shape)[..., 0, :bs]
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

    # gather [kvh, B, nbk, bs, hd] -> [B, kvh, K, hd], K = nbk * bs logical
    k = jnp.transpose(k_pool[:, bt], (1, 0, 2, 3, 4)).reshape(
        B, kvh, nbk * bs, hd)
    v = jnp.transpose(v_pool[:, bt], (1, 0, 2, 3, 4)).reshape(
        B, kvh, nbk * bs, hd)
    if quant:
        ks = jnp.transpose(k_scale[:, bt], (1, 0, 2, 3)).reshape(
            B, kvh, nbk * bs)
        vs = jnp.transpose(v_scale[:, bt], (1, 0, 2, 3)).reshape(
            B, kvh, nbk * bs)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)

    if q_start is not None:
        q_abs = (jnp.asarray(q_start, jnp.int32).reshape(B)[:, None]
                 + jnp.arange(T))                          # [B, T]
    else:
        q_abs = (lens[:, None] - T + jnp.arange(T))        # [B, T]
    k_pos = jnp.arange(nbk * bs)                           # [K]
    # a stored head's group of query heads as rows of ITS query, head after
    # head: [B, kvh, group x T, hd] against the one stored head (no copy of
    # K/V a query head)
    q = q.reshape(B, kvh, group * T, hd)
    q_abs = jnp.tile(q_abs, (1, group))                    # [B, group x T]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap:
        from ..attention import apply_softcap
        s = apply_softcap(s, softcap)
    if alibi_slopes is not None:
        sl = jnp.repeat(jnp.asarray(alibi_slopes, jnp.float32).reshape(
            kvh, group), T, axis=1)                        # [kvh, group x T]
        dist = (k_pos[None, None, :] - q_abs[:, :, None]).astype(jnp.float32)
        s = s + sl[None, :, :, None] * dist[:, None]
    keep = k_pos[None, None, :] <= q_abs[:, :, None]       # [B, rows, K]
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        keep = keep & ((q_abs[:, :, None] - k_pos[None, None, :] < win)
                       | (win <= 0))
    if select is not None:
        # a layer with an indexer: a row attends its top-k keys only (a
        # query head's rows are its token's)
        K = nbk * bs
        sc = jnp.pad(select.scores[:, :, :K], [(0, 0), (0, 0), (0, max(
            0, K - select.scores.shape[2]))], constant_values=-jnp.inf)
        pick = _selected(sc, select.thr[..., None], select.tie[..., None],
                         k_pos)                            # [B, T, K]
        keep = keep & jnp.tile(pick, (1, group, 1))
    s = jnp.where(keep[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", prob, v).reshape(B, nh, T, hd)
