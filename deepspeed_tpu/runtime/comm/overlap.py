"""Hand-pipelined overlap schedules — chunked allgather→matmul and
chunked grad reduce-scatter (T3 / ZeRO++ style), the comm-plan
``overlap``/``overlap_int8`` algorithm family.

The two seams these executors replace are the last places COVERAGE.md
said "trust XLA's latency-hiding scheduler":

* the ZeRO-3 param fetch — the per-leaf all-gather of a sharded weight
  ahead of its consuming matmul (:func:`make_overlap_gather`);
* the ZeRO-2/3 grad sync — the reduce-scatter of every grad leaf over
  the DP axes (:func:`overlap_grad_sync`).

Instead of ONE whole-tensor collective per leaf (whose wire time the
scheduler may or may not hide), each executor splits the payload into
``chunks`` pieces and issues one chunk-sized collective per piece. The
chunks are data-independent, so the async collective-start/done pairs
XLA emits can interleave chunk k+1's wire time under chunk k's compute
(and under neighboring layers' matmuls) — hand-pipelined fine-grained
overlap rather than scheduler-discovered, which is exactly the regime
T3 (arXiv 2401.16677) and ZeRO++ (arXiv 2306.10209) measure wins in.
A naive auto-SPMD chunking (slice + sharding constraint per chunk) does
NOT survive compilation — the partitioner CSEs the chunk gathers back
into one full-tensor collective (measured on this host) — so every
executor builds the chunks INSIDE a shard_map body where the manual
collectives are final.

``overlap`` moves exact f32 chunks; ``overlap_int8`` composes with the
blockwise-int8 wire format of ``quantized.py`` — each chunk is
quantized independently and its per-block scales ride WITH the chunk,
so a chunk is self-contained on the wire and dequant of chunk k can
start (and overlap) before chunk k+1 lands.

Autodiff: inside a manual shard_map region the transpose of
``lax.all_gather`` is ``lax.psum_scatter`` — differentiating through a
chunked gather therefore yields chunk-sized reduce-scatters in the
backward for free, which is how the overlapped ZeRO-3 step gets BOTH
tentpole structures (chunked allgather forward, chunked grad
reduce-scatter backward) from one executor. The int8 gather carries a
``custom_vjp`` (straight-through past the quantizer, defined INSIDE the
shard_map body — the MoE queue-exchange lesson) whose backward is the
same exact chunk-sized psum_scatter.

Every region is a ``jax.shard_map`` that is fully manual over the
ZeRO/DP axes only (TP axes stay auto).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .quantized import (DEFAULT_BLOCK, _axes_size, _axes_tuple,
                        ag_quantized_local, rs_exact_local,
                        rs_quantized_local)

#: default pieces per overlapped collective; one more chunk = one more
#: opportunity to hide wire time, at one more collective's latency floor
DEFAULT_CHUNKS = 4

OVERLAP_ALGOS = ("overlap", "overlap_int8")


def effective_chunks(length: int, chunks: int) -> int:
    """Largest c <= chunks that divides ``length`` (>= 1): chunk edges
    must be static and equal-sized so every chunk compiles to the same
    collective shape (one program, not per-chunk variants)."""
    c = max(1, min(int(chunks), int(length)))
    while length % c:
        c -= 1
    return c


def _rs_hop(seg, axes, n, *, algo, bits, block, mean):
    """One per-segment reduce-scatter hop (shard-local): the int8 or
    exact variant of the ``rs_*_local`` contract, served chunk out —
    the single definition every chunked executor below shares."""
    if algo == "overlap_int8":
        served, _ = rs_quantized_local(seg, axes, n, bits=bits,
                                       block=block, mean=mean)
    else:
        served, _ = rs_exact_local(seg, axes, n, mean=mean)
    return served


def _segment_bounds(length: int, chunks: int):
    """Static [lo, hi) bounds cutting ``length`` into ``chunks`` nearly
    equal contiguous segments (flat-buffer chunking: segments need not
    be equal — each hop pads itself)."""
    ch = max(1, min(int(chunks), int(length)))
    base, rem = divmod(length, ch)
    bounds, lo = [], 0
    for k in range(ch):
        hi = lo + base + (1 if k < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


# ---------------------------------------------------------------------------
# chunked grad sync (ZeRO-2 seam; the overlap counterpart of grad_sync)
# ---------------------------------------------------------------------------

def overlap_grad_sync(x: jnp.ndarray, *, mesh, axis="data",
                      chunks: int = DEFAULT_CHUNKS,
                      algo: str = "overlap", bits: int = 8,
                      block: int = DEFAULT_BLOCK,
                      mean: bool = True) -> jnp.ndarray:
    """Chunked ZeRO-2 gradient sync: ``grad_sync``'s contract (stacked
    per-rank grads [n, ...] in, reduced value in the original leaf shape
    out) with the flat buffer cut into ``chunks`` segments, each riding
    its OWN reduce-scatter + all-gather hop — no tail-end whole-tensor
    collective for the scheduler to (maybe) hide.

    ``algo``:
      * ``"overlap"`` — exact f32 chunks (same math as the implicit
        sync, only the wire schedule changes);
      * ``"overlap_int8"`` — each chunk blockwise-int8 quantized, its
        per-block scales riding with it (self-contained chunks).
    """
    if algo not in OVERLAP_ALGOS:
        raise ValueError(f"overlap_grad_sync algo {algo!r}: expected "
                         f"{'|'.join(OVERLAP_ALGOS)}")
    n = _axes_size(mesh, axis)
    axes = _axes_tuple(axis)

    def inner(xs):
        x0 = xs[0]
        flat = x0.reshape(-1).astype(jnp.float32)
        outs = []
        for lo, hi in _segment_bounds(flat.size, chunks):
            seg = jax.lax.slice(flat, (lo,), (hi,))
            served = _rs_hop(seg, axes, n, algo=algo, bits=bits,
                             block=block, mean=mean)
            if algo == "overlap_int8":
                full = ag_quantized_local(served, axes, bits=bits,
                                          block=block)
            else:
                full = jax.lax.all_gather(served, axes).reshape(-1)
            outs.append(full[:seg.size])
        out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return out.reshape(x0.shape).astype(x0.dtype)

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(axes), out_specs=P(),
                       axis_names=set(axes), check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(x)


# ---------------------------------------------------------------------------
# chunked param gather (ZeRO-3 seam; replaces the implicit stage-3 allgather)
# ---------------------------------------------------------------------------

def make_overlap_gather(mesh, axis, dim: int, *,
                        chunks: int = DEFAULT_CHUNKS,
                        algo: str = "overlap", bits: int = 8,
                        block: int = DEFAULT_BLOCK):
    """Chunked explicit all-gather for one ZeRO-3 param leaf sharded on
    ``dim`` over mesh axis ``axis`` (a name or the composed ZeRO axis
    tuple). Returns f(x) -> the whole leaf, assembled from
    ``effective_chunks`` chunk-sized all-gathers of shard sub-slices —
    the fetch-coordinator's prefetch granularity, made explicit.

    Forward: chunk k of every rank's shard rides its own
    ``lax.all_gather`` (chunk-shaped wire op, [n, step, ...] out), then
    a local transpose/reshape restores the rank-major row order.
    Backward: the transpose of each chunk gather is a chunk-sized
    ``psum_scatter`` — the overlapped ZeRO-3 backward gets its grads
    reduce-scattered in the same chunks, no full-tensor collective in
    either direction. The leaf spec must name ONLY the gather axes (on
    ``dim``); TP-composed leaves stay on the implicit path (engine
    envelope).

    ``algo="overlap_int8"`` quantizes each chunk blockwise before the
    gather (scales ride with their chunk; ~25% of the f32 chunk bytes)
    with a straight-through ``custom_vjp`` whose backward is the exact
    chunk psum_scatter.
    """
    if algo not in OVERLAP_ALGOS:
        raise ValueError(f"make_overlap_gather algo {algo!r}: expected "
                         f"{'|'.join(OVERLAP_ALGOS)}")
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)

    if algo == "overlap_int8":
        # custom_vjp around the shard-LOCAL chunk exchange (defined at
        # make time, called inside the shard_map body — an outer
        # custom_vjp wrapping the whole shard_map leaks tracers under
        # nn.scan lifting)
        @jax.custom_vjp
        def _chunk_gather(c):
            deq = ag_quantized_local(c.reshape(-1), axes, bits=bits,
                                     block=block)       # [n * L]
            return deq.reshape((n,) + c.shape).astype(c.dtype)

        _chunk_gather.defvjp(
            lambda c: (_chunk_gather(c), None),
            # straight-through past the quantizer: the exact chunk-sized
            # reduce-scatter (all_gather's transpose), reduced in f32
            # and cast back — the bwd cotangent must match the primal
            # dtype on jax lines that enforce custom_vjp avals
            lambda _, g: (jax.lax.psum_scatter(
                g.astype(jnp.float32), axes, scatter_dimension=0,
                tiled=False).astype(g.dtype),))
    else:
        def _chunk_gather(c):
            return jax.lax.all_gather(c, axes)          # [n, *c.shape]

    def inner(wl):
        local = wl.shape[dim]
        ch = effective_chunks(local, chunks)
        step = local // ch
        parts = []
        for k in range(ch):
            c = jax.lax.slice_in_dim(wl, k * step, (k + 1) * step,
                                     axis=dim)
            parts.append(_chunk_gather(c))              # [n, ..step..]
        g = jnp.concatenate(parts, axis=1 + dim) if ch > 1 else parts[0]
        g = jnp.moveaxis(g, 0, dim)                     # [..., n, local, ...]
        if algo == "overlap_int8":
            g = g.astype(wl.dtype)
        return g.reshape(wl.shape[:dim] + (n * local,)
                         + wl.shape[dim + 1:])

    spec_in = [None] * max(dim + 1, 1)
    spec_in[dim] = axes if len(axes) > 1 else axes[0]
    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(*spec_in),
                       out_specs=P(), axis_names=set(axes),
                       check_vma=False)

    def gather(x):
        # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
        return mapped(x)

    return gather


# ---------------------------------------------------------------------------
# benchmark pipelines (ds_bench overlap cells; also the HLO-audit fixtures)
# ---------------------------------------------------------------------------

def chunked_ag_matmul(x: jnp.ndarray, w: jnp.ndarray, *, mesh, axis,
                      chunks: int = DEFAULT_CHUNKS, algo: str = "overlap",
                      bits: int = 8, block: int = DEFAULT_BLOCK
                      ) -> jnp.ndarray:
    """The T3 allgather→matmul pipeline as a self-contained benchmark
    payload: ``w`` [R, C] sharded on dim 0 over ``axis``, ``x`` [B, R]
    replicated; returns ``x @ w`` computed as
    ``sum_k x[:, rows_k] @ all_gather(w_chunk_k)`` so chunk k+1's gather
    has chunk k's matmul to hide under. Row selection per chunk is a
    static index map (rank-major shard layout), precomputed on host."""
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)
    R = w.shape[0]
    S = R // n                      # rows per rank
    ch = effective_chunks(S, chunks)
    step = S // ch
    cols = [np.concatenate([np.arange(r * S + k * step,
                                      r * S + (k + 1) * step)
                            for r in range(n)]) for k in range(ch)]

    def inner(xl, wl):
        acc = jnp.zeros((xl.shape[0], wl.shape[1]), jnp.float32)
        for k in range(ch):
            c = jax.lax.slice_in_dim(wl, k * step, (k + 1) * step, axis=0)
            if algo == "overlap_int8":
                wk = ag_quantized_local(c.reshape(-1), axes, bits=bits,
                                        block=block).reshape(
                                            (-1, wl.shape[1]))
            else:
                wk = jax.lax.all_gather(c, axes, tiled=True)  # [n*step, C]
            xk = jnp.take(xl, jnp.asarray(cols[k]), axis=1)
            acc = acc + xk.astype(jnp.float32) @ wk.astype(jnp.float32)
        return acc.astype(x.dtype)

    mapped = jax.shard_map(inner, mesh=mesh,
                       in_specs=(P(), P(axes if len(axes) > 1 else axes[0])),
                       out_specs=P(), axis_names=set(axes), check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(x, w)


def chunked_rs(g: jnp.ndarray, *, mesh, axis,
               chunks: int = DEFAULT_CHUNKS, algo: str = "overlap",
               bits: int = 8, block: int = DEFAULT_BLOCK,
               mean: bool = True) -> jnp.ndarray:
    """Chunked reduce-scatter of a PRECOMPUTED stacked buffer [n, L]
    (dim 0 over ``axis``): the comm-only half of
    :func:`chunked_matmul_rs` — ds_bench times it to split an overlap
    cell's wall time into its comm and compute parts
    (``overlap_ratio``). Returns this rank's served chunk-concat
    [1, ~L/n] (per-chunk scattered layout, dim 0 over ``axis``)."""
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)

    def inner(gl):
        outs = []
        for lo, hi in _segment_bounds(gl.shape[-1], chunks):
            seg = jax.lax.slice(gl[0], (lo,), (hi,)).astype(jnp.float32)
            outs.append(_rs_hop(seg, axes, n, algo=algo, bits=bits,
                                block=block, mean=mean))
        out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return out[None]

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(axes),
                       out_specs=P(axes), axis_names=set(axes),
                       check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(g)


def chunked_matmul_rs(u: jnp.ndarray, v: jnp.ndarray, *, mesh, axis,
                      chunks: int = DEFAULT_CHUNKS, algo: str = "overlap",
                      bits: int = 8, block: int = DEFAULT_BLOCK,
                      mean: bool = True) -> jnp.ndarray:
    """The grad-production side of the overlap story as a benchmark
    payload: per chunk, a matmul PRODUCES the grad segment
    (``u_local @ v[:, seg_k]``) and that segment immediately rides its
    own reduce-scatter hop — grads are reduce-scattered as they are
    produced, not as one tail-end collective. ``u`` [n, B] stacked over
    ``axis``; ``v`` [B, L] replicated; returns this rank's reduced
    chunk-concat [1, ~L/n] (per-chunk scattered layout — each chunk's
    served piece in chunk order, padded per hop — dim 0 over
    ``axis``)."""
    axes = _axes_tuple(axis)
    n = _axes_size(mesh, axes)
    L = v.shape[1]

    def inner(ul, vl):
        outs = []
        for lo, hi in _segment_bounds(L, chunks):
            gk = (ul[0].astype(jnp.float32)
                  @ jax.lax.slice(vl, (0, lo),
                                  (vl.shape[0], hi)).astype(jnp.float32))
            outs.append(_rs_hop(gk, axes, n, algo=algo, bits=bits,
                                block=block, mean=mean))
        out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
        return out[None]

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=(P(axes), P()),
                       out_specs=P(axes), axis_names=set(axes),
                       check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(u, v)
