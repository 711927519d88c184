"""Learned sparse attention's selection: an indexer's scores over a lane's
keys and the exact top-k of each query row (ROADMAP M7, serving).

A layer with an indexer (DeepSeek-Sparse-Attention's; ``TransformerConfig.
index_heads``) lets query ``t`` attend the ``index_topk`` keys ``s <= t`` of
largest index score::

    I(t, s) = sum_j w(t, j) * relu(qI(t, j) . kI(s))        float32

with ``qI [heads, width]`` and ``w [heads]`` from the query token and ONE
indexer key ``kI [width]`` a token, cached beside K/V. Equal scores go to the
lower position; a row with at most ``index_topk`` visible keys attends them
all.

Two kernels, each with its jnp twin (the CPU path and the kernel's oracle):

* :func:`index_scores` (``sparse_index_scores``): the scores of every query
  row against its lane's indexer keys, ``-inf`` where a key is not visible
  (``s > t`` or past the lane's context). The kernel copies a tile's pages
  out of the pool through the block table, as the paged kernel does (an
  XLA gather had the chip's compiler keep the pool in another layout and
  copy it whole every layer). One algorithm, its tile shaped by the call:
  a call of several rows a lane (a prefill chunk) runs a program a ``(lane,
  256 rows, 1024 keys)`` tile, a matmul a head over the tile, ReLU and the
  head's weight folded in place (the ``[rows, heads, keys]`` products never
  leave VMEM; a tile wholly invisible copies and computes nothing). A call
  of ONE row a lane (the decode call) runs a program a group of 8 lanes:
  a lane's ``[heads, width]`` query is the ROWS of one matmul against a key
  tile, ReLU, the heads' weights and the sum over the heads follow on the
  vector unit, and the program walks only the lane's own key tiles
  (:func:`score_tiles`: up to its context, from its layer's window), the
  next tile's pages in flight while one multiplies; its result is a row a
  lane, ``[B, Kp]``, as the top-k reads it. The operands are the model's
  dtype and the products accumulate in float32: bf16 x bf16 products are
  exact in float32, so a bf16 model's scores are what float32 at
  ``highest`` gives on the same rounded operands. The sixteen float32
  terms of a score are added head after head by the chunk's program and
  by a reduction over the matmul's rows by the decode call's: another
  ORDER of the same additions, a bit or two of a float32 apart (2e-5 on
  scores of 150), and :func:`index_scores_reference` takes the chunk's.
* :func:`topk_threshold` (``sparse_topk``): per row a threshold and a tie
  position ``(thr, tie)``: key ``s`` is selected iff ``I > thr or (I == thr
  and s <= tie)`` (:func:`selected`). Exact, by bisection over the float's
  bits (as a sortable integer): every pass is a compare and a count over a
  row tile that stays in VMEM, and the work follows what the tile can see
  and what is still undecided. A tile passes over the columns up to its
  rows' last visible position only (the extent its caller hands it, in
  column steps of :func:`topk_columns`); it stops once every row has a
  candidate with EXACTLY k scores at or above it (the lower bits cannot
  change which: rows of index scores separate after 17-25 of the 32 passes),
  and ``thr`` is then that candidate, a float in the gap under the k-th
  score, with ``tie`` = every position; only a row whose equal scores
  straddle the k-th runs out of bits, and ``ceil(log2 extent)`` passes over
  the positions then find up to where they are taken. No sort, no index
  list: the paged kernel applies ``(thr, tie)`` to the scores of the keys it
  holds (``ops/pallas/paged_attention.py``), beside its causal, context and
  window masks. A row tile none of whose rows sees more than k keys does no
  pass; a row of at most k beside rows of more keeps every visible key.

:func:`selection_bits` packs a call's selection 32 keys a word, for a
request that asked for its routing (``serving.engine``: ``submit(...,
keep_routing=True)``); :func:`positions_of_bits` (a prompt chunk's rows, on
the device) and :func:`bits_to_positions` (a decode call's row, on the host)
are its inverse.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Selection", "index_scores", "index_scores_reference",
           "topk_threshold", "topk_threshold_reference", "selected",
           "select", "selection_bits", "bits_to_positions",
           "positions_of_bits", "padded_keys", "score_tiles", "topk_tiles",
           "topk_columns", "untileable"]

_INT_MIN = -2 ** 31
#: query rows a score program takes (a call of 2 to 8 rows: the sublane
#: minimum; of one row a lane: :func:`_decode_score_kernel`)
_SCORE_ROWS = 256
#: lanes a decode-shaped score program holds, one query row each: the
#: sublanes of its result's block
_DECODE_LANES = 8
#: the longest table a decode-shaped score program takes: its lanes' rows
#: stay in VMEM (two buffers of ``_DECODE_LANES x Kp`` float32, 8 MB here)
_DECODE_KEYS = 131072
#: rows a top-k program holds in VMEM with their sortable keys. A pass ends
#: in a chain of a lane reduction, a few one-lane operations and a broadcast
#: (~110 cycles on a v5e, whatever the rows): two sublane groups share it
#: (PERF.md, PR 46: 8 / 16 / 32 rows read 1.29 / 0.87 / 0.75 ms a chunk step
#: of eight layers at 8k keys; 32 lose it again on a decode call's one
#: padded tile, 0.18 for 0.10)
_TOPK_ROWS = 16
#: value passes of a top-k tile between two tests of whether every row has
#: separated its k keys (1 / 4 / 8: 0.98 / 0.87 / 0.87 ms at the same shape)
_TOPK_GROUP = 4
#: ``_sortable`` of -inf's bits: what a column no row sees ranks as
_NEG_INF_KEY = -2 ** 31 + 0x7FFFFF


class Selection(NamedTuple):
    """Which keys each query row of a call attends: ``scores [B, T, Kp]``
    float32 (``-inf`` where a key is not visible; ``Kp``:
    :func:`padded_keys` of the lane's key capacity), and per row ``thr [B,
    T]`` float32 / ``tie [B, T]`` int32, to be read through
    :func:`selected` alone: ``thr`` separates the row's k best scores from
    the rest and need not be one of them (:func:`topk_threshold`)."""
    scores: jnp.ndarray
    thr: jnp.ndarray
    tie: jnp.ndarray


def padded_keys(K: int) -> int:
    """A lane's key capacity rounded up to whole 128-lane tiles."""
    return -(-K // 128) * 128


def _key_tile(Kp: int) -> int:
    return max(t for t in (1024, 512, 256, 128) if Kp % t == 0)


def selected(scores, thr, tie, k_pos):
    """Key at position ``k_pos`` with index score ``scores`` is one of its
    row's top k (operands broadcast against each other)."""
    return (scores > thr) | ((scores == thr) & (k_pos <= tie))


# ---------------------------------------------------------------- scores


def _visible(q0, ctx, window, rows0, keys0, shape):
    """Row r of a tile sees key c: causal, inside the lane's context, and
    inside the layer's sliding window (``window <= 0``: none)."""
    q_abs = q0 + rows0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = keys0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (k_pos <= q_abs) & (k_pos < ctx) \
        & ((q_abs - k_pos < window) | (window <= 0))


def _score_kernel(bt_ref, q0_ref, ctx_ref, misc_ref, q_ref, w_ref, pool_hbm,
                  o_ref, k_buf, sem, *, heads, tr, tk, bs, nbk, precision):
    b, it, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q0, ctx = q0_ref[b], ctx_ref[b]
    rows0, keys0 = it * tr, ik * tk
    # keys at or past ``seen`` are visible to no row of the tile: a tile
    # that starts there computes nothing, and of a live tile only the pages
    # below it are copied (what the buffer holds of the rest is masked)
    seen = jnp.minimum(q0 + rows0 + tr, ctx)
    live = keys0 < seen

    @pl.when(live)
    def _compute():
        pages = jnp.minimum((seen - keys0 + bs - 1) // bs, tk // bs)

        def copy(p):
            phys = bt_ref[b, jnp.minimum(ik * (tk // bs) + p, nbk - 1)]
            return pltpu.make_async_copy(
                pool_hbm.at[misc_ref[0], 0, phys],
                k_buf.at[pl.ds(pl.multiple_of(p * bs, bs), bs)], sem.at[0])

        jax.lax.fori_loop(0, pages, lambda p, _: copy(p).start(), None)
        jax.lax.fori_loop(0, pages, lambda p, _: copy(p).wait(), None)
        k = k_buf[...]
        w = w_ref[0]
        acc = jnp.zeros((tr, tk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = jnp.where(
            _visible(q0, ctx, misc_ref[1], rows0, keys0, (tr, tk)), acc,
            -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0] = jnp.full((tr, tk), -jnp.inf, jnp.float32)


def _precision(dtype):
    # float32 operands: every pass; bf16 products are exact in float32
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def score_tiles(q_start, context_lens, window, Kp: int, xp=jnp):
    """``(first, end)`` per lane of a call of ONE query row a lane: the key
    tiles its score program copies and multiplies, from the tile of the
    first key inside the layer's ``window`` (<= 0: none) up to the tile of
    the last key the row sees (of the table's ``Kp`` at most); ``first ==
    end`` for a lane of no key. The kernel's own rule; ``xp``: numpy for a
    host that counts what the kernel will do (``serving.engine``)."""
    tk = _key_tile(Kp)
    seen = xp.minimum(q_start + 1, context_lens)
    end = xp.minimum((seen + tk - 1) // tk, Kp // tk)
    low = xp.where(window > 0, xp.maximum(q_start + 1 - window, 0), 0)
    return xp.minimum(low // tk, end), end


def _decode_score_kernel(bt_ref, q0_ref, ctx_ref, misc_ref, q_ref, w_ref,
                         pool_hbm, o_ref, k_buf, sem, *, tiles, Kp, tk, bs,
                         nbk, precision):
    # a program holds ``lanes`` lanes of one query row each and walks each
    # lane's own key tiles, the next tile's pages (the next lane's first
    # tile behind a lane's last) in flight while one multiplies. ``tiles``
    # is :func:`score_tiles`, handed in: the serving loop calls it on the
    # host under its lock, and the package's linter takes every function a
    # traced body names for device work
    lanes = o_ref.shape[0]
    lane0 = pl.program_id(0) * lanes
    layer, window = misc_ref[0], misc_ref[1]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (lanes, tk), 0)
    key_of = jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)

    def span(b):
        return tiles(q0_ref[b], ctx_ref[b], window, Kp)

    def pages(act, b, i, slot):
        """``act`` ("start" or "wait") on the copies of lane b's tile i into
        buffer ``slot``: the pages that hold a key the row sees (what the
        buffer keeps of the rest is masked), no page past the table's end.
        A page's descriptor is the loop's whole cost (PERF.md, PR 47: a
        tile's 32 starts are 0.9 us, its matmul 0.2), so the table is read
        as one row of words, and a full tile is waited for at once, by the
        bytes of its buffer."""
        seen = jnp.minimum(jnp.minimum(q0_ref[b] + 1, ctx_ref[b]), nbk * bs)
        n = jnp.minimum((seen - i * tk + bs - 1) // bs, tk // bs)
        word = b * nbk + i * (tk // bs)
        if act == "wait":
            full = n == tk // bs

            @pl.when(full)
            def _whole_tile():
                pltpu.make_async_copy(k_buf.at[slot], k_buf.at[slot],
                                      sem.at[slot]).wait()

            n = jnp.where(full, 0, n)

        def page(p, _):
            # waiting takes a descriptor of the same size, whatever its source
            phys = 0 if act == "wait" else bt_ref[word + p]
            getattr(pltpu.make_async_copy(
                pool_hbm.at[layer, 0, phys],
                k_buf.at[slot, pl.ds(pl.multiple_of(p * bs, bs), bs)],
                sem.at[slot]), act)()

        jax.lax.fori_loop(0, n, page, None)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def lane(r, carry):
        slot, started = carry
        b = lane0 + r
        q0, ctx = q0_ref[b], ctx_ref[b]
        first, end = span(b)
        nxt = jnp.minimum(r + 1, lanes - 1)
        nxt_first, nxt_end = span(lane0 + nxt)
        has_nxt = (r + 1 < lanes) & (nxt_first < nxt_end)

        @pl.when((first < end) & (started == 0))
        def _own_first_tile():
            pages("start", b, first, slot)

        def tile(i, slot):
            last = i + 1 == end

            @pl.when(jnp.logical_not(last) | has_nxt)
            def _next_tile():
                pages("start", jnp.where(last, lane0 + nxt, b),
                      jnp.where(last, nxt_first, i + 1), 1 - slot)

            pages("wait", b, i, slot)
            # the lane's heads are the rows of one matmul; ReLU, the heads'
            # weights and the sum over them on the vector unit
            s = jax.lax.dot_general(
                q_ref[r], k_buf[slot], (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            acc = jnp.sum(w_ref[r] * jnp.maximum(s, 0.0), axis=0,
                          keepdims=True)
            k_pos = i * tk + key_of
            vis = (k_pos <= q0) & (k_pos < ctx) \
                & ((q0 - k_pos < window) | (window <= 0))
            at = pl.ds(pl.multiple_of(i * tk, tk), tk)
            o_ref[:, at] = jnp.where(
                row_of == r, jnp.where(vis, acc, -jnp.inf), o_ref[:, at])
            return 1 - slot

        return (jax.lax.fori_loop(first, end, tile, slot),
                ((first < end) & has_nxt).astype(jnp.int32))

    jax.lax.fori_loop(0, lanes, lane, (jnp.int32(0), jnp.int32(0)))


def _decode_index_scores(qi, w, ki_pool, scalars, *, tk, interpret):
    """:func:`index_scores` of a call of one query row a lane: ``[B, Kp]``,
    a lane a row, as the top-k reads it."""
    B, H, _, D = qi.shape
    bs, nbk = ki_pool.shape[3], scalars[0].shape[1]
    Kp = padded_keys(nbk * bs)
    lanes = _DECODE_LANES
    pad = -B % lanes
    # a padding lane holds no key: its program fills its row and no more
    grown = lambda a: jnp.pad(
        a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)) if pad else a
    bt, q0, ctx = map(grown, scalars[:3])
    qi = grown(qi.reshape(B, H, D))
    w = grown(w.astype(jnp.float32).reshape(B, H, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=((B + pad) // lanes,),
        in_specs=[pl.BlockSpec((lanes, H, D), lambda g, *_: (g, 0, 0)),
                  pl.BlockSpec((lanes, H, 1), lambda g, *_: (g, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((lanes, Kp), lambda g, *_: (g, 0)),
        scratch_shapes=[pltpu.VMEM((2, tk, D), ki_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    with jax.named_scope("sparse_index_scores"):
        out = pl.pallas_call(
            partial(_decode_score_kernel, tiles=score_tiles, Kp=Kp, tk=tk,
                    bs=bs, nbk=nbk, precision=_precision(qi.dtype)),
            grid_spec=grid_spec, name="sparse_index_scores",
            out_shape=jax.ShapeDtypeStruct((B + pad, Kp), jnp.float32),
            interpret=interpret,
        )(bt.reshape(-1), q0, ctx, scalars[3], qi, w, ki_pool)
    return out[:B] if pad else out


def index_scores(qi, w, ki_pool, block_tables, layer_idx, q_start,
                 context_lens, *, window=None, interpret=False):
    """``[B, T, Kp]`` float32 index scores, ``Kp`` = :func:`padded_keys` of
    the table's ``nbk x block_size`` keys. ``qi [B, heads, T, lanes]`` the
    indexer's queries (rotated, on the pool's lanes), ``w [B, T, heads]``
    their head weights, ``ki_pool [L, 1, blocks, block_size, lanes]`` the
    indexer-key pool where it lies (the kernel copies a lane's pages out of
    it through ``block_tables [B, nbk]``, layer ``layer_idx``: no gathered
    copy of a lane's keys exists, and the pool keeps the one layout it has
    at the jit boundary), ``q_start [B]`` the position of row 0,
    ``context_lens [B]`` the lane's valid keys, the call's own included,
    ``window`` the layer's sliding window (None or <= 0: none): a key
    outside it is not visible, so the selection is among the keys the
    row's attention can see. The call's shape picks the program: one row a
    lane (``T == 1``, a decode call) a lane's heads as the rows of one
    matmul over the lane's own key tiles, more rows the tiles of a chunk."""
    B, H, T, D = qi.shape
    bs, nbk = ki_pool.shape[3], block_tables.shape[1]
    Kp = padded_keys(nbk * bs)
    tr = _SCORE_ROWS if T > 8 else 8
    Tp = -(-T // tr) * tr
    tk = _key_tile(Kp)
    if tk % bs:
        raise ValueError(untileable(bs))
    # traced where the chunk's program has always had them: its text is the
    # parent's (``scripts/serving_program_text.py``)
    scalars = lambda: (
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(q_start, jnp.int32).reshape(B),
        jnp.asarray(context_lens, jnp.int32).reshape(B),
        jnp.stack([jnp.asarray(layer_idx, jnp.int32).reshape(()),
                   jnp.asarray(0 if window is None else window,
                               jnp.int32).reshape(())]))
    if T == 1 and Kp <= _DECODE_KEYS:
        return _decode_index_scores(qi, w, ki_pool, scalars(), tk=tk,
                                    interpret=interpret)[:, None]
    qi = jnp.pad(qi, [(0, 0), (0, 0), (0, Tp - T), (0, 0)])
    w = jnp.pad(w.astype(jnp.float32), [(0, 0), (0, Tp - T), (0, 0)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, Tp // tr, Kp // tk),
        in_specs=[pl.BlockSpec((1, H, tr, D), lambda b, i, j, *_: (b, 0, i, 0)),
                  pl.BlockSpec((1, tr, H), lambda b, i, j, *_: (b, i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tr, tk), lambda b, i, j, *_: (b, i, j)),
        scratch_shapes=[pltpu.VMEM((tk, D), ki_pool.dtype),
                        pltpu.SemaphoreType.DMA((1,))])
    with jax.named_scope("sparse_index_scores"):
        out = pl.pallas_call(
            partial(_score_kernel, heads=H, tr=tr, tk=tk, bs=bs, nbk=nbk,
                    precision=_precision(qi.dtype)),
            grid_spec=grid_spec, name="sparse_index_scores",
            out_shape=jax.ShapeDtypeStruct((B, Tp, Kp), jnp.float32),
            interpret=interpret,
        )(*scalars(), qi, w, ki_pool)
    return out[:, :T]


def untileable(block_size: int):
    """Why a pool of these blocks cannot ride :func:`index_scores` (a tile
    of keys is whole pages), or None."""
    if 128 % block_size and block_size % 128:
        return (f"block_size {block_size}: a tile of index scores is whole "
                "pages (a power of two up to 128, or a multiple of 128)")
    return None


def index_scores_reference(qi, w, ki, q_start, context_lens, window=None):
    """:func:`index_scores` in plain jnp (the CPU path, the kernel's
    oracle) on a lane's keys ``ki [B, K, width]`` in logical order, gathered
    by the caller."""
    B, H, T, D = qi.shape
    K = ki.shape[1]
    s = jnp.einsum("bhtd,bkd->bthk", qi, ki.astype(qi.dtype),
                   precision=_precision(qi.dtype),
                   preferred_element_type=jnp.float32)
    acc = jnp.zeros((B, T, K), jnp.float32)
    for j in range(H):      # the kernel's order of accumulation
        acc = acc + w[:, :, j, None].astype(jnp.float32) * jnp.maximum(
            s[:, :, j], 0.0)
    q_abs = jnp.asarray(q_start, jnp.int32).reshape(B, 1, 1) \
        + jnp.arange(T)[None, :, None]
    k_pos = jnp.arange(K)[None, None, :]
    vis = (k_pos <= q_abs) & (k_pos < jnp.asarray(
        context_lens, jnp.int32).reshape(B, 1, 1))
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        vis = vis & ((q_abs - k_pos < win) | (win <= 0))
    out = jnp.where(vis, acc, -jnp.inf)
    return jnp.pad(out, [(0, 0), (0, 0), (0, padded_keys(K) - K)],
                   constant_values=-jnp.inf)


# ----------------------------------------------------------------- top-k


def _sortable(bits):
    """A float32's bits as an int32 that orders as the float does (its own
    inverse)."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _topk_kernel(ext_ref, bits_ref, s_ref, thr_ref, tie_ref, key_ref, *, k,
                 cb):
    # no branch: a tile that makes no pass (extent 0) and a tile without a
    # partial tie run the loops below zero times (and every serving program
    # traces and lowers this text at every start: PERF.md, PR 46)
    rows, K = s_ref.shape
    lanes = thr_ref.shape[1]
    kf = jnp.float32(k)
    ext, pos_bits = ext_ref[pl.program_id(0)], bits_ref[pl.program_id(0)]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cb), 1)
    zeros = jnp.zeros((rows, cb), jnp.float32)
    steps = (ext + cb - 1) // cb
    per_row = lambda acc: jnp.sum(acc, axis=1, keepdims=True)

    def over_columns(step, carry, steps=steps):
        """``step(at, first column, carry)`` over the tile's live columns,
        ``cb`` at a time: what lies past the extent is never read."""
        def body(j, c):
            c0 = pl.multiple_of(j * cb, cb)
            return step(pl.ds(c0, cb), c0, c)
        return jax.lax.fori_loop(0, steps, body, carry)

    def count(cmp, bound):
        """Per row, the live columns whose sortable key stands ``cmp`` to the
        row's ``bound [rows, 1]``: partial counts a lane, reduced across the
        lanes once."""
        bound = jnp.broadcast_to(bound, (rows, cb))
        return per_row(over_columns(
            lambda at, c0, acc: acc + cmp(key_ref[:, at], bound).astype(
                jnp.float32), zeros))

    def sortable_keys(at, c0, seen):
        # a column at or past the extent is visible to no row, whatever the
        # buffer holds there; -0.0 (a negative weight times a zero) ranks as
        # +0.0: the passes compare bit patterns, and equal scores are ties
        s = jnp.where(col + c0 < ext, s_ref[:, at], -jnp.inf)
        key_ref[:, at] = _sortable(jax.lax.bitcast_convert_type(
            jnp.where(s == 0.0, 0.0, s), jnp.int32))
        return seen + (s > -jnp.inf).astype(jnp.float32)

    # a row of at most k keys (every row of a tile that makes no pass, or
    # one beside rows of more) keeps every visible key: closed from the
    # start at -inf
    more = per_row(over_columns(sortable_keys, zeros)) > kf

    def value_bit(i, c):
        # ``ans``: the k-th largest key so far, as an UNSIGNED number (its
        # sign bit flipped): the largest v with count(key >= v) >= k. A row
        # is closed at the first candidate that leaves EXACTLY k keys at or
        # above it: the lower bits cannot change which. (An open row counts
        # more than k live columns, so its count is never k at a pattern
        # under -inf's: no closed ``ans`` is a NaN.)
        ans, open_ = c
        cand = ans | jnp.left_shift(jnp.int32(1), 31 - i)
        n = count(jnp.greater_equal, cand ^ _INT_MIN)
        return (jnp.where((open_ > 0) & (n >= kf), cand, ans),
                jnp.where(n == kf, 0, open_))

    passes, ans, open_ = jax.lax.while_loop(
        lambda c: (c[0] < 32) & (jnp.max(c[2]) > 0),
        lambda c: (c[0] + _TOPK_GROUP,) + jax.lax.fori_loop(
            c[0], c[0] + _TOPK_GROUP, value_bit, c[1:]),
        (jnp.int32(0), jnp.where(more, 0, _NEG_INF_KEY ^ _INT_MIN),
         more.astype(jnp.int32)))
    kth = ans ^ _INT_MIN

    # a row still open ran out of bits: ``kth`` is its k-th largest key and
    # MORE than k keys stand at or above it; of the equal ones the lower
    # positions are taken. Every other row's equal scores are among its k.
    partial = jnp.max(open_) > 0
    pos_bits = jnp.where(partial, pos_bits, 0)

    def equal_positions(at, c0, above):
        key = key_ref[:, at]
        key_ref[:, at] = jnp.where(key == kth, col + c0,
                                   jnp.int32(2 ** 31 - 1))
        return above + (key > kth).astype(jnp.float32)

    need = kf - per_row(over_columns(         # >= 1 of the equal ones
        equal_positions, zeros, jnp.where(partial, steps, 0)))

    def pos_bit(i, p):
        # the largest p with fewer than ``need`` equal keys below it: the
        # position of the need-th
        cand = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
        return jnp.where(count(jnp.less, cand) < need, cand, p)

    tie = jax.lax.fori_loop(0, pos_bits, pos_bit,
                            jnp.zeros((rows, 1), jnp.int32))
    thr_ref[...] = jnp.broadcast_to(jax.lax.bitcast_convert_type(
        _sortable(kth), jnp.float32), (rows, lanes))
    # lane 0 the tie position, lanes 1 and 2 the passes the tile made
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    tie_ref[...] = jnp.where(
        lane == 0, jnp.where(open_ > 0, tie, K),
        jnp.where(lane == 1, passes, pos_bits))


def topk_tiles(seen, extent, k: int, Kp: int, xp=jnp):
    """Per tile of the top-k kernel's rows, the column extent it is handed:
    the largest ``extent`` of its rows (one past a row's last visible
    position), 0 for a tile none of whose rows sees more than ``k`` keys
    (``seen``: a row's count of them). ``xp``: numpy for a host that counts
    what the kernel will do (``serving.engine``)."""
    def of_tiles(a):
        a = xp.asarray(a, xp.int32).reshape(-1)
        return xp.pad(a, (0, -a.shape[0] % _TOPK_ROWS)).reshape(
            -1, _TOPK_ROWS).max(axis=1)
    return xp.where(of_tiles(seen) > k, xp.clip(of_tiles(extent), 0, Kp), 0)


def topk_columns(tiles, Kp: int):
    """The columns the top-k kernel's passes take over tiles of these
    extents (:func:`topk_tiles`): whole column steps."""
    cb = _key_tile(Kp)
    return -(-tiles // cb) * cb


def topk_threshold(scores, k: int, seen=None, extent=None, *,
                   return_passes=False, interpret=False):
    """``(thr [R] float32, tie [R] int32)`` of ``scores [R, Kp]`` (``Kp`` whole
    128-lane tiles) such that :func:`selected` keeps exactly row r's k
    largest scores, equal ones to the lower position, or every key of a row
    of at most k (``-inf`` entries counted: a caller masks what is not
    visible). ``thr`` is a value that separates them, NOT always a score of
    the row: a row whose k-th and (k+1)-th largest differ gets any float in
    the gap with ``tie = Kp``; only where equal scores straddle the k-th is
    ``thr`` that score and ``tie`` the position up to which it is taken.
    Read both through :func:`selected` alone.

    ``seen [R]``: the keys each row sees (more than ``-inf``), where the
    caller knows them from the rows' positions; counted here otherwise.
    ``extent [R]``: one past each row's last visible position (a layer's
    window clips ``seen``, not this); ``Kp`` otherwise. The work follows
    them: a tile of ``_TOPK_ROWS`` rows none of which sees more than k makes
    no pass; another passes over the columns below its largest extent only
    (what the buffer holds past it is not read), stops its value passes
    once every row has separated exactly k keys (tested every
    ``_TOPK_GROUP`` passes; 32 at most), and breaks ties by position, in
    ``ceil(log2 extent)`` passes more, only if a row ran out of bits.
    ``return_passes``: a third result ``[tiles, 2]`` int32, the value and
    position passes each tile made (known on the device only)."""
    R, Kp = scores.shape
    rows = _TOPK_ROWS
    Rp = -(-R // rows) * rows
    scores = scores.astype(jnp.float32)
    if seen is None:
        seen = jnp.sum(scores > -jnp.inf, axis=1)
    if Rp != R:
        scores = jnp.pad(scores, [(0, Rp - R), (0, 0)],
                         constant_values=-jnp.inf)
    tiles = topk_tiles(seen, jnp.full((R,), Kp) if extent is None else extent,
                       int(k), Kp)
    # the bits of a tile's positions: ceil(log2 extent)
    pos_bits = 32 - jax.lax.clz(jnp.maximum(tiles, 1) - 1)
    with jax.named_scope("sparse_topk"):
        thr, tie = pl.pallas_call(
            partial(_topk_kernel, k=int(k), cb=_key_tile(Kp)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(Rp // rows,),
                in_specs=[pl.BlockSpec((rows, Kp), lambda r, *_: (r, 0))],
                out_specs=[pl.BlockSpec((rows, 128),
                                        lambda r, *_: (r, 0))] * 2,
                scratch_shapes=[pltpu.VMEM((rows, Kp), jnp.int32)]),
            name="sparse_topk",
            out_shape=[jax.ShapeDtypeStruct((Rp, 128), jnp.float32),
                       jax.ShapeDtypeStruct((Rp, 128), jnp.int32)],
            interpret=interpret,
        )(tiles, pos_bits, scores)
    if return_passes:
        return thr[:R, 0], tie[:R, 0], tie[::rows, 1:3]
    return thr[:R, 0], tie[:R, 0]


def topk_threshold_reference(scores, k: int):
    """:func:`topk_threshold` by ``lax.top_k`` (equal elements: the lower
    index first), the kernel's oracle and the CPU path."""
    R, Kp = scores.shape
    if Kp <= k:
        return (jnp.full((R,), -jnp.inf, jnp.float32),
                jnp.full((R,), Kp, jnp.int32))
    # top_k ranks -0.0 under +0.0; as scores they are equal
    scores = scores.astype(jnp.float32)
    vals, idx = jax.lax.top_k(jnp.where(scores == 0.0, 0.0, scores), k)
    thr = vals[:, -1]
    tie = jnp.max(jnp.where(vals == thr[:, None], idx, -1), axis=1)
    return thr, tie.astype(jnp.int32)


def select(scores, k: int, seen=None, extent=None, *, kernel: bool,
           interpret=False) -> Selection:
    """The :class:`Selection` of ``scores [B, T, Kp]`` (``seen``, ``extent``
    ``[B, T]``: :func:`topk_threshold`)."""
    B, T, Kp = scores.shape
    flat = scores.reshape(B * T, Kp)
    rows = lambda a: None if a is None else a.reshape(B * T)
    thr, tie = (topk_threshold(flat, k, rows(seen), rows(extent),
                               interpret=interpret) if kernel
                else topk_threshold_reference(flat, k))
    return Selection(scores, thr.reshape(B, T), tie.reshape(B, T))


# ------------------------------------------------------------- hand-out


def selection_bits(sel: Selection) -> jnp.ndarray:
    """``[B, T, Kp // 32]`` int32: bit r of word w is set iff the row attends
    the key at position ``32 w + r`` (selected AND visible)."""
    B, T, Kp = sel.scores.shape
    on = selected(sel.scores, sel.thr[..., None], sel.tie[..., None],
                  jnp.arange(Kp)) & (sel.scores > -jnp.inf)
    words = on.reshape(B, T, Kp // 32, 32).astype(jnp.uint32) \
        << jnp.arange(32, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(jnp.sum(words, axis=-1,
                                                dtype=jnp.uint32), jnp.int32)


@partial(jax.jit, static_argnames="k")
def positions_of_bits(bits, k: int):
    """The inverse of :func:`selection_bits` on the device: ``bits [...,
    words]`` int32 -> ``[..., k]`` int32, a row's selected positions in
    rising order, -1 behind its own count (a sort of the set bits' positions:
    a long prompt's chunks are hundreds of thousands of rows, a minute of the
    host's time by :func:`bits_to_positions`)."""
    K = bits.shape[-1] * 32
    on = (bits[..., None] >> jnp.arange(32, dtype=jnp.int32)) & 1
    rank = jnp.where(on.reshape(bits.shape[:-1] + (K,)) != 0,
                     K - jnp.arange(K, dtype=jnp.int32), 0)
    best = jax.lax.top_k(rank, k)[0]        # falling ranks: rising positions
    return jnp.where(best > 0, K - best, -1)


def bits_to_positions(bits: np.ndarray, k: int) -> np.ndarray:
    """:func:`positions_of_bits` on the host, for a few rows: ``bits [...,
    words]`` int32 -> ``[..., k]`` int32, a row's selected positions in
    rising order, -1 behind its own count."""
    bits = np.ascontiguousarray(bits, np.int32)
    lead, words = bits.shape[:-1], bits.shape[-1]
    flat = bits.reshape(-1, words)
    out = np.full((flat.shape[0], k), -1, np.int32)
    for r0 in range(0, flat.shape[0], 512):     # 13 MB of bits at a time
        on = np.unpackbits(flat[r0:r0 + 512].view(np.uint8), axis=1,
                           bitorder="little")
        r, c = np.nonzero(on)
        counts = on.sum(axis=1, dtype=np.int64)
        out[r0 + r, np.arange(r.size) - (np.cumsum(counts) - counts)[r]] = c
    return out.reshape(lead + (k,))
