"""The paged KV cache of the inference decoder, and the serving forward.

One pure function, :func:`paged_forward`, serves BOTH serving regimes:

* **prefill** — B=1, T = padded prompt(-suffix) length: writes the
  prompt's K/V into the sequence's pool blocks and returns logits for
  every query position (the host samples at the last REAL position);
* **decode** — B = max_batch (the padded active set), T=1: one fresh
  token per lane, fixed shapes across admissions/evictions so the jit
  NEVER re-specializes (the serving loop compiles exactly one decode
  step — CUDA-graph discipline, enforced by tests).

It is ``models.generation.decoder_forward``, the layer ``generate()``
runs, over a :class:`PagedCache`, so a paged serve is token-exact with
sequential ``generate()`` calls under greedy sampling. What the cache
makes different: a token's position comes from its lane's ``q_start``;
K/V land in the pool's blocks through the block table, in place, by
unrolled dynamic-update-slices in the layout the paged kernel reads
(:func:`_write_kv` says why not by a scatter); and attention reads ride
``ops.attention.paged_attention``: the Pallas block-table kernel on a TPU,
for a decode token and for a prefill's T rows alike (the write comes
first, so a chunk's own keys are read from the pool with the older ones),
the exact jnp gather reference on the CPU and for shapes the kernel cannot
tile (the dense cache's f32 score path and -1e30 masking).

Inactive / padded lanes are harmless by construction: their block tables
are all-NULL, their writes land in the null block, and their outputs are
discarded by the host. No per-sample left-pad machinery is needed —
paged sequences are always exact-length.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..models.generation import (absorb_output, absorb_query,
                                 attention_constants, decoder_forward)
from ..models.transformer import TransformerConfig
from ..ops.attention import paged_attention
from ..ops.pallas.paged_attention import scale_rows
from ..ops.pallas import latent_attention as latent
from ..ops.pallas import sparse_select
from .kv_cache import NULL_BLOCK

PyTree = Any


class _WritePlan(NamedTuple):
    """Where one ``paged_forward`` call's new K/V goes, the same in every
    layer: per lane the touched blocks' physical ids and which of their
    slots take a new row (``PagedCache.plan`` works them out)."""
    bs: int                 # slots a block
    off: jnp.ndarray        # [B] slot of the first position in its block
    phys: jnp.ndarray       # [B, J] physical block of touched block j
    keep: jnp.ndarray       # [B, J, bs] this slot takes a new row


def _write_kv(pool, li, new, ax, plan: _WritePlan):
    """``new`` [B, kvh, 1, ., .] with its T positions on axis ``ax`` into
    layer ``li`` of ``pool`` [L, kvh, blocks, ., .] (``kvh``: the model's KV
    heads, the heads the pool stores), whose blocks have their
    ``bs`` slots on axis ``ax``: ``lax.dynamic_update_slice`` unrolled in
    Python over lanes and touched blocks. A decode step (T == 1) writes one
    slot a lane; a prefill reads each touched block, merges the real
    positions in and writes the block back (done block-wise, a decode step
    makes the chip's compiler carry the pool in yet another layout).

    NOT a scatter: the chip's compiler gives a scatter's operand the layout
    {3,1,2,0} (slots major) while the paged kernel takes its operand
    row-major, so a scattered pool was copied whole to the kernel's layout
    twice a layer (seven eighths of a decode step, PERF.md PR 25). And not
    a ``fori_loop`` over the lanes, which ends the same way. Unrolled
    updates keep the carried pool in the one layout it has at the jit
    boundary, updated in place; tests/test_chip_compile.py holds the
    compiled programs to that."""
    bs, (B, n_touch), T = plan.bs, plan.phys.shape, new.shape[ax]
    if T == 1:
        for b in range(B):
            at = [li, 0, plan.phys[b, 0], 0, 0]
            at[ax] = plan.off[b]
            pool = jax.lax.dynamic_update_slice(pool, new[b:b + 1], at)
        return pool
    # the new rows padded by one block either side, so that every touched
    # block's window of them is in range: block j starts at (j + 1) * bs - off
    pad = [(0, 0)] * 5
    pad[ax] = (bs, bs)
    new = jnp.pad(new, pad)
    size = (1,) + new.shape[1:ax] + (bs,) + new.shape[ax + 1:]
    for b in range(B):
        for j in range(n_touch):
            at = (li, 0, plan.phys[b, j], 0, 0)
            start = [b, 0, 0, 0, 0]
            start[ax] = (j + 1) * bs - plan.off[b]
            rows = jax.lax.dynamic_slice(new, start, size)
            held = jax.lax.dynamic_slice(pool, at, size)
            mask = plan.keep[b, j].reshape((bs,) + (1,) * (4 - ax))
            pool = jax.lax.dynamic_update_slice(
                pool, jnp.where(mask, rows, held), at)
    return pool


def attention_impl(cfg: TransformerConfig) -> str:
    """``ops.attention.paged_attention``'s ``impl`` for this model: one built
    with ``attention_impl="reference"`` serves on the gather oracle (the
    twin a kernel-routed serve is compared against)."""
    return "reference" if cfg.attention_impl == "reference" else "auto"


class PagedCache:
    """The serving loop's cache behind ``models.generation.decoder_forward``:
    ``init_pool``'s ``[L, kv_heads, slots, hd]`` pools, reached through each
    lane's block table (:func:`paged_forward` documents the arguments). The
    layer hands ``write`` K/V at the model's KV heads and ``attend`` the
    queries at all its heads: the kernel reads one stored head for the
    ``num_heads // kv_heads`` query heads that share it. Everything here
    follows the pool's own shape. A model with an indexer has one leaf more,
    ``ki`` ``[L, 1, slots, 128s]``: the indexer's one key a token on the
    first lanes of whole 128-lane tiles (``init_pool`` says why), in the
    same blocks through the same tables and written by the same plan, so whatever carries a block (a prefix-cache hit, a fork, a
    preemption's release) carries its indexer keys. Built and used inside
    one trace."""

    #: how a call's new rows go into a pool (:class:`_CalledWrites` has its own)
    _write_kv = staticmethod(_write_kv)

    def __init__(self, cfg: TransformerConfig, pools: Dict[str, jnp.ndarray],
                 block_tables, q_start, context_lens, block_size: int,
                 interpret: bool):
        self.cfg, self.pools, self.interpret = cfg, pools, interpret
        self.bs = bs = int(block_size)
        self.quantized = "k_scale" in pools
        # the leaf every other follows: K, or a latent model's one leaf
        lead = pools["ckv" if cfg.kv_lora_rank else "k"]
        if lead.dtype == jnp.int8 and not self.quantized:
            raise ValueError(
                "int8 KV pool without k_scale/v_scale leaves — build pools "
                "with serving.kv_cache.init_pool(dtype=jnp.int8)")
        num_slots = lead.shape[2]
        if num_slots % bs:
            raise ValueError(f"pool slots {num_slots} not divisible by "
                             f"block_size {bs}")
        self.blocked_shape = lead.shape[:2] + (
            num_slots // bs, bs, lead.shape[3])
        self.bt = jnp.asarray(block_tables, jnp.int32)
        B, nbk = self.bt.shape
        self.q_start = jnp.asarray(q_start, jnp.int32).reshape(B)
        self.ctx = jnp.asarray(context_lens, jnp.int32).reshape(B)
        # the pool's per-sequence maximum (a plain-theta table has no length,
        # so this matches generate()'s cache-capacity table exactly)
        self.rope_len = nbk * bs
        self.sm_scale, self.slopes = attention_constants(cfg)
        self.impl = attention_impl(cfg)

    def positions(self, T: int):
        return self.q_start[:, None] + jnp.arange(T)[None, :]      # [B, T]

    def plan(self, T: int) -> None:
        """The K/V write, planned once for every layer. Logical position p
        of lane b lives in pool block bt[b, p // bs] at slot p % bs, and the
        T consecutive positions of a lane touch at most n_touch blocks. Slot
        r of touched block j holds position (q_start // bs + j) * bs + r; it
        takes the new row t = position - q_start where that is a real query
        (0 <= t < T, position < ctx) and keeps what it held otherwise, so a
        chunk may start or end in mid-block. A touched block with no real
        query in it (padding past ctx, the spare block of an aligned chunk)
        is the null block: the fixed-shape step can't corrupt live state."""
        bs, q_start = self.bs, self.q_start
        n_touch = (T + bs - 2) // bs + 1
        off = q_start % bs                                          # [B]
        lblk = (q_start // bs)[:, None] + jnp.arange(n_touch)       # [B, J]
        held_pos = lblk[:, :, None] * bs + jnp.arange(bs)       # [B, J, bs]
        t_idx = held_pos - q_start[:, None, None]
        keep = ((t_idx >= 0) & (t_idx < T)
                & (held_pos < self.ctx[:, None, None]))
        phys = jnp.take_along_axis(
            self.bt, jnp.clip(lblk, 0, self.bt.shape[1] - 1), axis=1)
        phys = jnp.where(keep.any(axis=2), phys, NULL_BLOCK)        # [B, J]
        self.write_plan = _WritePlan(bs, off, phys, keep)

    def real_tokens(self, pos):
        """A real token: a query below its lane's context, in a lane that
        holds a sequence (an idle decode lane's table is all null)."""
        return ((pos < self.ctx[:, None])
                & (self.bt[:, :1] != NULL_BLOCK)).astype(jnp.int32)  # [B, T]

    def carry(self):
        # the loop carries the pools as the kernel reads them, a block's
        # slots on an axis of their own: for K/V a free view of init_pool's
        # flat slot axis; the int8 tier's small scale pools change layout
        # here, at the loop's boundary (a block's scales on the first lanes
        # of a row of whole 128-lane tiles: the least a kernel may copy), not
        # twice a layer inside it
        blocked = lambda pool: pool.reshape(
            pool.shape[:2] + self.blocked_shape[2:4] + pool.shape[3:])
        return {name: (scale_rows(pool, self.blocked_shape)
                       if name.endswith("_scale") else blocked(pool))
                for name, pool in self.pools.items()}

    def finish(self, carry, T: int):
        return {name: (pool[..., :self.bs] if name.endswith("_scale")
                       else pool).reshape(self.pools[name].shape)
                for name, pool in carry.items()}

    def write(self, kv, li, k, v, k_scale, v_scale):
        new, plan = dict(kv), self.write_plan
        if self.quantized:
            # a scale block keeps its slots on the last axis
            B, kvh, T, _ = k.shape
            to_lanes = lambda s: s.reshape(B, kvh, 1, 1, T)
            new["k_scale"] = self._write_kv(kv["k_scale"], li, to_lanes(k_scale),
                                       4, plan)
            new["v_scale"] = self._write_kv(kv["v_scale"], li, to_lanes(v_scale),
                                       4, plan)
        new["k"] = self._write_kv(kv["k"], li,
                             k.astype(kv["k"].dtype)[:, :, None], 3, plan)
        new["v"] = self._write_kv(kv["v"], li,
                             v.astype(kv["v"].dtype)[:, :, None], 3, plan)
        return new

    def write_index(self, kv, li, ki):
        """The indexer's keys ``[B, 1, T, width]`` into layer ``li`` of the
        ``ki`` pool: one more head-less row a token under the K/V plan,
        zeros on the lanes past its width."""
        lanes = kv["ki"].shape[-1]
        ki = jnp.pad(ki.astype(kv["ki"].dtype),
                     [(0, 0)] * 3 + [(0, lanes - ki.shape[-1])])
        return {**kv, "ki": self._write_kv(kv["ki"], li, ki[:, :, None], 3,
                                      self.write_plan)}

    def write_latent(self, kv, li, row):
        """A latent model's one row a token and layer, ``[B, T,
        latent_width]`` (the normed latent, the rotated shared key), into
        the ``ckv`` pool under the K/V plan: no heads, zeros on the lanes
        past its width."""
        lanes = kv["ckv"].shape[-1]
        row = jnp.pad(row.astype(kv["ckv"].dtype),
                      [(0, 0)] * 2 + [(0, lanes - row.shape[-1])])
        return {**kv, "ckv": self._write_kv(kv["ckv"], li, row[:, None, None], 3,
                                       self.write_plan)}

    def attend_latent(self, kv, li, q_nope, q_pe, wk, wv, select=None):
        """Latent attention through the block table, ``[B, heads, T,
        v_head_dim]``; the kernel on a TPU or under ``interpret``, in the
        form its rows ask for (``latent.form``): a decode token ABSORBED
        (``attn_kv_b`` folded into the query and the output here, its heads
        the rows of one tile over the one stored row), a chunk EXPANDED
        inside the kernel, each key tile once for all of its rows. Elsewhere
        the absorbed jnp twin. The call's own rows are in the pool already.
        ``select`` (a layer with an indexer): the rows' selection, applied
        inside whichever of the three runs."""
        cfg, lanes = self.cfg, kv["ckv"].shape[-1]
        T = q_nope.shape[2]
        way, _ = latent.path(q_nope.shape[:3] + (lanes,), kv["ckv"].shape,
                             self.impl, self.interpret)
        if way == "kernel" and latent.form(T) == "expanded":
            with jax.named_scope("attend"):
                return latent.latent_chunk_attention(
                    q_nope, q_pe, wk, wv, kv["ckv"], self.bt, self.ctx,
                    sm_scale=self.sm_scale, layer_idx=li,
                    q_start=self.q_start, interpret=self.interpret,
                    select=select)
        attend = partial(latent.latent_attention, interpret=self.interpret) \
            if way == "kernel" else partial(
                latent.latent_attention_reference, q_start=self.q_start)
        with jax.named_scope("absorb"):
            q = absorb_query(q_nope, q_pe, wk, lanes)
        with jax.named_scope("attend"):
            o = attend(q, kv["ckv"], self.bt, self.ctx,
                       value=cfg.kv_lora_rank, sm_scale=self.sm_scale,
                       layer_idx=li, select=select)
        with jax.named_scope("absorb"):
            return absorb_output(o, wv)

    def select(self, kv, li, qi, wi, window):
        """Which of its lane's keys each row attends: the indexer's scores
        over the lane's cached indexer keys (read through the block table;
        the call's own are written already; a key outside the layer's
        ``window`` is not a candidate), then each row's exact top
        ``cfg.index_topk`` (``ops/pallas/sparse_select.py``: the kernels on
        a TPU or under ``interpret``, their jnp twins elsewhere)."""
        B, T = qi.shape[0], qi.shape[2]
        L, _, nb, bs, width = kv["ki"].shape
        kernel = self.impl != "reference" and (
            jax.default_backend() == "tpu" or bool(self.interpret)) \
            and sparse_select.untileable(bs) is None
        # the queries on the pool's lanes: zeros against the keys' zeros
        qi = jnp.pad(qi, [(0, 0)] * 3 + [(0, width - qi.shape[-1])])
        with jax.named_scope("index"):
            if kernel:
                scores = sparse_select.index_scores(
                    qi, wi, kv["ki"], self.bt, li, self.q_start, self.ctx,
                    window=window, interpret=self.interpret)
            else:
                keys = kv["ki"].reshape(L * nb, bs, width)[li * nb + self.bt]
                scores = sparse_select.index_scores_reference(
                    qi, wi, keys.reshape(B, -1, width), self.q_start,
                    self.ctx, window)
        with jax.named_scope("select"):
            # from where a row stands: one past its last visible position,
            # and the keys it sees, which a window clips
            extent = jnp.minimum(self.positions(T) + 1, self.ctx[:, None])
            seen = jnp.where(window > 0, jnp.minimum(extent, window), extent)
            return sparse_select.select(
                scores, self.cfg.index_topk, seen, extent, kernel=kernel,
                interpret=self.interpret)

    def attend(self, kv, li, q, k, v, window, select=None):
        # attention through the block table (the kernel on a TPU, the exact
        # jnp gather elsewhere); the int8 tier passes the pool AS int8 with
        # its scales — dequant happens in-kernel / post-gather, O(attended
        # blocks), never a pool-slice copy
        scale_kw = (dict(k_scale=kv["k_scale"], v_scale=kv["v_scale"])
                    if self.quantized else {})
        return paged_attention(q, kv["k"], kv["v"], self.bt, self.ctx,
                               sm_scale=self.sm_scale,
                               alibi_slopes=self.slopes,
                               softcap=self.cfg.attn_softcap, window=window,
                               layer_idx=li, q_start=self.q_start,
                               select=select, impl=self.impl,
                               interpret=self.interpret, **scale_kw)


@partial(jax.jit, static_argnames="ax")
def _slot_update(pool, li, new, plan: _WritePlan, b, *, ax: int):
    """Lane ``b``'s one new row into its slot: :func:`_write_kv`'s decode
    update, the lane an operand."""
    at = [li, 0, plan.phys[b, 0], 0, 0]
    at[ax] = plan.off[b]
    return jax.lax.dynamic_update_slice(
        pool, jax.lax.dynamic_slice_in_dim(new, b, 1, axis=0), at)


@partial(jax.jit, static_argnames="ax")
def _block_merge(pool, li, new, plan: _WritePlan, b, j, *, ax: int):
    """Lane ``b``'s touched block ``j`` read, merged with its window of the
    (padded) new rows and written back: :func:`_write_kv`'s prefill update,
    the lane and the block operands."""
    bs = plan.keep.shape[2]
    size = (1,) + new.shape[1:ax] + (bs,) + new.shape[ax + 1:]
    at = (li, 0, plan.phys[b, j], 0, 0)
    start = [b, 0, 0, 0, 0]
    start[ax] = (j + 1) * bs - plan.off[b]
    rows = jax.lax.dynamic_slice(new, start, size)
    held = jax.lax.dynamic_slice(pool, at, size)
    mask = plan.keep[b, j].reshape((bs,) + (1,) * (4 - ax))
    return jax.lax.dynamic_update_slice(pool, jnp.where(mask, rows, held), at)


@partial(jax.jit, static_argnames="ax")
def _write_kv_by_calls(pool, li, new, plan: _WritePlan, *, ax: int):
    """:func:`_write_kv` as ONE function of a program's text, every unrolled
    update in it a CALL of one jitted function a kind of update, the lane
    and the block its operands: the same updates in the same order (the
    chip's compiler inlines the calls, and the pool is updated in place as
    before), but one body a kind, and the decode lanes' updates, whose shapes
    no chunk size changes, traced once a process. A decode call's unrolled
    updates are two thirds of its layer body's text; the mixed program of
    EVERY prefill shape carries them beside its chunk's merges, a pool and a
    layer stack at a time, and would trace and lower all of it again at every
    start. The two programs the mixed one replaces keep :func:`_write_kv`,
    and their text."""
    (B, n_touch), T = plan.phys.shape, new.shape[ax]
    if T == 1:
        for b in range(B):
            pool = _slot_update(pool, li, new, plan, b, ax=ax)
        return pool
    bs = plan.keep.shape[2]
    pad = [(0, 0)] * 5
    pad[ax] = (bs, bs)
    new = jnp.pad(new, pad)
    for b in range(B):
        for j in range(n_touch):
            pool = _block_merge(pool, li, new, plan, b, j, ax=ax)
    return pool


class _CalledWrites(PagedCache):
    """A :class:`PagedCache` of a :class:`MixedCache`: its writes go through
    :func:`_write_kv_by_calls`."""

    @staticmethod
    def _write_kv(pool, li, new, ax, plan: _WritePlan):
        # (the plan's block size is static: not an operand)
        return _write_kv_by_calls(pool, li, new, plan._replace(bs=None),
                                  ax=ax)


@jax.tree_util.register_pytree_node_class
class _LaneCalls(_CalledWrites):
    """The decode lanes' half of a :class:`MixedCache`. Nothing of it
    depends on the chunk's rows, so each of its per-layer methods is ONE call
    of a jitted function with the cache itself an operand (its tables and
    its write plan the leaves, the rest static): traced once a process and
    found again by the mixed program of every other prefill shape, where a
    decode call's cache work would be traced anew a shape."""
    _LEAVES = ("bt", "q_start", "ctx", "slopes", "write_plan")

    def tree_flatten(self):
        static = tuple(sorted((k, v) for k, v in vars(self).items()
                              if k not in self._LEAVES and k != "pools"))
        return tuple(getattr(self, k) for k in self._LEAVES), static

    @classmethod
    def tree_unflatten(cls, static, leaves):
        cache = object.__new__(cls)
        vars(cache).update(static, **dict(zip(cls._LEAVES, leaves)))
        return cache

    def plan(self, T: int) -> None:
        super().plan(T)
        self.write_plan = self.write_plan._replace(bs=None)  # static: no leaf

    def write(self, *args):
        return _lane_call(self, "write", *args)

    def write_index(self, *args):
        return _lane_call(self, "write_index", *args)

    def select(self, *args):
        return _lane_call(self, "select", *args)

    def attend(self, *args, select=None):
        return _lane_call(self, "attend", *args, select)


@partial(jax.jit, static_argnames="method")
def _lane_call(cache: _LaneCalls, method: str, *args):
    return getattr(_CalledWrites, method)(cache, *args)


def _row_major(rows):
    """``rows`` held to the row-major layout. The chunk's new K/V rows are
    cut out of the call's ``T + B`` rows, and the chip's compiler names that
    cut's layout as it pleases (the new axis of one is free); the block
    merges take the pool in the layout of the rows they merge, so with both
    kinds of update on one int8 pool it carried K blocks-major through the
    merges and copied it whole, there and back, every layer
    (tests/test_chip_compile.py holds the program to that)."""
    from jax.experimental.layout import Layout, with_layout_constraint
    return with_layout_constraint(
        rows, Layout(major_to_minor=tuple(range(rows.ndim))))


def _lane_rows(x):
    """``[1, n, B, d]``, the lanes' one row each on the row axis of a call
    of one sequence, as a decode call has them, ``[B, n, 1, d]``; and back."""
    return x.transpose(2, 1, 0, 3)


class MixedCache:
    """Rows of two kinds behind ONE ``decoder_forward`` call of ``[1, T +
    B]`` rows: a prefill chunk's ``T`` rows, then the decode lanes' one row
    each. Everything that is a row's own (projections, norms, the router,
    the experts, the head) sees one batch of rows, so a weight is read once;
    what differs by kind is cut at row ``T`` and handed to the
    :class:`PagedCache` of its kind over the SAME pools, in the form a
    prefill call and a decode call give it: the chunk's block merges and the
    paged kernel's chunk form, the lanes' one-slot writes and its decode
    form, each kind's own selection. Both kinds' rows are written before
    either attends; no lane of a call holds a block its chunk writes (a
    prompt's lane joins the next call), so the order between the kinds is
    nobody's to see. Not for a latent model (the serving loop never builds
    one over a latent pool)."""

    def __init__(self, chunk: PagedCache, lanes: PagedCache, T: int):
        self.chunk, self.lanes, self.T = chunk, lanes, int(T)
        self.quantized, self.rope_len = chunk.quantized, chunk.rope_len

    def _cut(self, x, axis: int = 2):
        """Rows ``[1, n, T + B, d]`` -> (the chunk's ``[1, n, T, d]``, the
        lanes' ``[B, n, 1, d]``)."""
        chunk, lanes = jnp.split(x, [self.T], axis=axis)
        return chunk, _lane_rows(lanes)

    @staticmethod
    def _join(chunk, lanes):
        return jnp.concatenate([chunk, _lane_rows(lanes)], axis=2)

    def _cut_select(self, sel):
        if sel is None:
            return None, None
        # scores [1, T + B, Kp], thr / tie [1, T + B]: rows on axis 1
        cut = [jnp.split(a, [self.T], axis=1) for a in sel]
        return (type(sel)(*(c for c, _ in cut)),
                type(sel)(*(jnp.swapaxes(l, 0, 1) for _, l in cut)))

    def positions(self, rows: int):
        return jnp.concatenate([self.chunk.positions(self.T),
                                self.lanes.positions(1).T], axis=1)

    def plan(self, rows: int) -> None:
        self.chunk.plan(self.T)
        self.lanes.plan(1)

    def real_tokens(self, pos):
        """The real tokens of each kind over all the rows, the chunk's
        first: a call's expert counts are a kind's own, as two calls'
        were."""
        chunk = self.chunk.real_tokens(pos[:, :self.T])           # [1, T]
        lanes = self.lanes.real_tokens(pos[:, self.T:].T).T       # [1, B]
        return (jnp.concatenate([chunk, jnp.zeros_like(lanes)], axis=1),
                jnp.concatenate([jnp.zeros_like(chunk), lanes], axis=1))

    def carry(self):
        return self.chunk.carry()

    def finish(self, carry, rows: int):
        return self.chunk.finish(carry, self.T)

    def write(self, kv, li, k, v, k_scale, v_scale):
        parts = [(None, None) if t is None else self._cut(t)
                 for t in (k, v, k_scale, v_scale)]
        kv = self.chunk.write(kv, li, *(
            None if c is None else _row_major(c) for c, _ in parts))
        return self.lanes.write(kv, li, *(l for _, l in parts))

    def write_index(self, kv, li, ki):
        chunk, lanes = self._cut(ki)
        return self.lanes.write_index(
            self.chunk.write_index(kv, li, chunk), li, lanes)

    def select(self, kv, li, qi, wi, window):
        """Each kind's rows keep their own selection (the chunk's over its
        sequence's keys, a lane's over its own); handed out as one
        ``Selection`` over the call's rows."""
        (qc, ql), (wc, wl) = self._cut(qi), jnp.split(wi, [self.T], axis=1)
        chunk = self.chunk.select(kv, li, qc, wc, window)
        lanes = self.lanes.select(kv, li, ql, jnp.swapaxes(wl, 0, 1), window)
        return type(chunk)(*(jnp.concatenate([c, jnp.swapaxes(l, 0, 1)],
                                             axis=1)
                             for c, l in zip(chunk, lanes)))

    def attend(self, kv, li, q, k, v, window, select=None):
        (qc, ql), (kc, kl), (vc, vl) = map(self._cut, (q, k, v))
        sel_c, sel_l = self._cut_select(select)
        with jax.named_scope("chunk"):
            chunk = self.chunk.attend(kv, li, qc, kc, vc, window,
                                      select=sel_c)
        with jax.named_scope("lanes"):
            lanes = self.lanes.attend(kv, li, ql, kl, vl, window,
                                      select=sel_l)
        return self._join(chunk, lanes)


def mixed_forward(cfg: TransformerConfig, params: PyTree,
                  chunk_ids: jnp.ndarray, lane_ids: jnp.ndarray,
                  pools: Dict[str, jnp.ndarray],
                  chunk_at, lanes_at, block_size: int, head_rows, *,
                  interpret: bool = False, expert_counts: bool = False,
                  expert_picks: bool = False) -> Tuple[jnp.ndarray, ...]:
    """A prefill call's and a decode call's work as ONE forward over ``[1,
    T + B]`` rows (:class:`MixedCache`): ``chunk_ids`` ``[1, T]`` at
    ``chunk_at`` and ``lane_ids`` ``[B]`` at ``lanes_at``, each ``(block
    tables, q_start, context_lens)`` as :func:`paged_forward` takes them for
    a call of that kind. Returns what ``paged_forward`` returns, with logits
    ``[1, len(head_rows), V]`` of the rows ``head_rows`` alone (the head
    reads no other), expert counts ``[L, 2, E]`` (the chunk's real rows, the
    lanes') and picks ``[L, T + B, k]``."""
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            "mixed_forward over a latent pool: a latent model's chunk and "
            "its lanes keep a program each")
    T = chunk_ids.shape[1]
    cache = MixedCache(
        _CalledWrites(cfg, pools, *chunk_at, block_size, interpret),
        _LaneCalls(cfg, pools, *lanes_at, block_size, interpret), T)
    logits, pools, counts, *picks = decoder_forward(
        cfg, params, jnp.concatenate([chunk_ids, lane_ids[None, :]], axis=1),
        cache, interpret=interpret, expert_counts=expert_counts,
        expert_picks=expert_picks, head_rows=head_rows)
    if expert_counts:
        return (logits, pools, counts, *picks)
    return (logits, pools, *picks)


def paged_forward(cfg: TransformerConfig,
                  params: PyTree,
                  input_ids: jnp.ndarray,
                  pools: Dict[str, jnp.ndarray],
                  block_tables: jnp.ndarray,
                  q_start: jnp.ndarray,
                  context_lens: jnp.ndarray,
                  block_size: int,
                  *,
                  interpret: bool = False,
                  expert_counts: bool = False,
                  expert_picks: bool = False
                  ) -> Tuple[jnp.ndarray, ...]:
    """Run T tokens per lane at logical positions [q_start, q_start + T)
    against the paged pool. Returns (logits [B, T, V] f32, updated pools)
    and, with ``expert_counts`` (a dropless MoE config only), ``[L, E]``
    int32: how many of this call's REAL tokens each layer's router sent to
    each expert (padding positions and lanes with no sequence are routed and
    computed like any row, and not counted); with ``expert_picks`` behind
    that ``[L, B x T, k]`` int32, the experts each layer picked for every row
    (``decoder_forward``).

    input_ids: [B, T]. pools: {"k","v"} [L, kv_heads, num_slots, hd]
    (``serving.kv_cache.init_pool`` layout; ``num_slots`` = pool blocks x
    ``block_size``; a token's bytes = 2 x L x kv_heads x hd x item size, and
    L x 128 x item size more for a model with an indexer of up to 128 lanes:
    its ``ki`` leaf ``[L, 1, num_slots, 128]``).
    block_tables: [B, max_blocks_per_seq] i32 — logical
    block j of lane b is physical pool block ``block_tables[b, j]``.
    q_start: [B] i32 — first query's logical position (tokens already in
    the cache below it are attended: a prefix-cache hit prefills only the
    suffix). context_lens: [B] i32 — total valid tokens INCLUDING the
    real queries of this call; query positions >= context_lens are
    PADDING (their K/V writes route to the null block, their logits are
    garbage the host never reads). ``block_size`` is static — it shapes
    the compiled write and gather. ``q_start`` may lie in mid-block and
    ``q_start + T`` may too (a chunk after a 10-token chunk, a prompt's
    last chunk): the write works block by block from what it is given.

    Params must be the scan-layers layout (``ensure_scan_layout``).
    post-LN encoders don't decode; int8 weight-only params work unchanged
    (the dequant rides ``_kernel_of``).

    int8 KV pools: when ``pools`` carries ``k_scale`` / ``v_scale``
    (``init_pool(dtype=jnp.int8)``), K/V rows are QUANTIZED ON WRITE
    (``quant_format.kv_quantize``: one f32 scale per layer, KV head and slot;
    error per element within that row's absmax / 254) and the int8 pool
    plus scales go STRAIGHT to attention: the Pallas decode kernel
    dequantizes the blocks it DMAs in VMEM, the jnp reference after its
    gather. Either way the dequant is O(attended blocks), not O(pool).

    int8 weights: ``kernel_qscale`` leaves (``serving.weight_dtype:
    "int8"``) route every block matmul through ``ops.pallas.quant_matmul``;
    ``interpret`` reaches that kernel as it reaches the paged one.
    """
    cache = PagedCache(cfg, pools, block_tables, q_start, context_lens,
                       block_size, interpret)
    logits, pools, counts, *picks = decoder_forward(
        cfg, params, input_ids, cache, interpret=interpret,
        expert_counts=expert_counts, expert_picks=expert_picks)
    if expert_counts:
        return (logits, pools, counts, *picks)
    return (logits, pools, *picks)
