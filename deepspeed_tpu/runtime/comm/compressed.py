"""Compressed collectives — 1-bit and int8 allreduce with error feedback.

Capability parity with the reference's cupy compressed-comm backends
(``runtime/comm/nccl.py:52-204`` NcclBackend.compressed_allreduce and the MPI
variant): sign+scale compression, chunked exchange so every rank "serves" one
chunk (average + re-compress with server error feedback), then allgather of
the served chunks. TPU-native: the exchange is `lax.all_to_all` /
`lax.all_gather` over a mesh axis inside partial-auto shard_map — the wire
carries int8 signs + f32 scales, an ~4x (int8) to ~32x (1-bit, byte-packed
sign) reduction vs f32. Pays off over DCN; over fast ICI prefer plain psum
(the reference gates 1-bit the same way: worth it on Ethernet, engine docs).

Error-feedback state (worker_error, server_error) is carried by the caller
(the 1-bit optimizers keep it in their state pytree, reference:
onebit/adam.py worker_error/server_error buffers).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _chunk(x: jnp.ndarray, n: int, multiple: int = 1) -> jnp.ndarray:
    """Pad + reshape flat x to [n, c] with c a multiple of ``multiple``."""
    c = -(-x.size // n)
    c = -(-c // multiple) * multiple
    pad = n * c - x.size
    xp = jnp.pad(x.reshape(-1), (0, pad))
    return xp.reshape(n, -1), pad


def chunk_elems(numel: int, n: int, multiple: int = 8) -> int:
    """Per-rank chunk length the 1-bit path uses for ``numel`` elements."""
    c = -(-numel // n)
    return -(-c // multiple) * multiple


def compressed_allreduce(x: jnp.ndarray,
                         worker_error: jnp.ndarray,
                         server_error: jnp.ndarray,
                         *,
                         mesh,
                         axis: str = "data"
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """1-bit allreduce of per-rank values with two-level error feedback.

    x: stacked per-rank values [n, ...] (dim 0 sharded over `axis` — rank r
    contributes x[r]). worker_error [n, numel] / server_error [n, ceil(numel/n)]
    are the running compensation buffers, same sharding.
    Returns (averaged value [...], new_worker_error, new_server_error).
    """
    n = mesh.shape[axis]

    from ...ops.quantizer import pack_signs, unpack_signs

    def inner(x, w_err, s_err):
        x, w_err, s_err = x[0], w_err[0], s_err[0]
        flat = x.reshape(-1).astype(jnp.float32)
        corrected = flat + w_err
        chunks, pad = _chunk(corrected, n, multiple=8)        # [n, c], c%8==0
        scale = jnp.mean(jnp.abs(chunks), axis=1, keepdims=True)  # [n, 1]
        signs = jnp.where(chunks >= 0, 1.0, -1.0)
        new_w_err = corrected - (signs * scale).reshape(-1)[:corrected.size]

        # exchange: rank r serves chunk r — a2a the PACKED sign bits (1 bit per
        # element on the wire; reference packs via cupy packbits), allgather
        # the tiny per-chunk scales
        c = chunks.shape[1]
        packed = jax.vmap(pack_signs)(signs)                   # [n, c/8] u8
        packed_recv = jax.lax.all_to_all(packed, axis,
                                         split_axis=0, concat_axis=0,
                                         tiled=True)           # [n, c/8]
        signs_recv = jax.vmap(unpack_signs)(packed_recv)       # [n, c]
        scales_all = jax.lax.all_gather(scale[:, 0], axis)     # [n, n]
        my = jax.lax.axis_index(axis)
        my_scales = scales_all[:, my]                          # senders' scales
        served = jnp.mean(signs_recv * my_scales[:, None], axis=0)  # [c]

        # server-side re-compress with server error feedback
        served_c = served + s_err
        s_scale = jnp.mean(jnp.abs(served_c))
        s_signs = jnp.where(served_c >= 0, 1.0, -1.0)
        new_s_err = served_c - s_signs * s_scale

        out_packed = jax.lax.all_gather(pack_signs(s_signs), axis,
                                        tiled=True)            # [n*c/8]
        out_scales = jax.lax.all_gather(s_scale, axis)         # [n]
        out = (unpack_signs(out_packed).reshape(n, c) *
               out_scales[:, None]).reshape(-1)
        out = out[:flat.size].reshape(x.shape).astype(x.dtype)
        return out, new_w_err[None], new_s_err[None]

    mapped = jax.shard_map(inner, mesh=mesh,
                           in_specs=(P(axis), P(axis), P(axis)),
                           out_specs=(P(), P(axis), P(axis)),
                           axis_names={axis}, check_vma=False)
    # graftlint: disable=TPU002 (called from the runner's outer jitted step: one construction per outer trace)
    return jax.jit(mapped)(x, worker_error, server_error)


def quantized_allreduce(x: jnp.ndarray,
                        error: jnp.ndarray,
                        *,
                        mesh,
                        axis: str = "data",
                        bits: int = 8
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 allreduce with error feedback: reduce-scatter int8 chunks,
    average, allgather int8 results (EQuARX-style; ~4x wire reduction).

    x: stacked per-rank values [n, ...], dim 0 sharded over `axis`;
    error [n, numel]. Returns (averaged [...], new_error [n, numel])."""
    n = mesh.shape[axis]
    qmax = float(2 ** (bits - 1) - 1)

    def inner(x, err):
        x, err = x[0], err[0]
        flat = x.reshape(-1).astype(jnp.float32) + err
        chunks, pad = _chunk(flat, n)
        q, scale = _sym_quant(chunks, qmax, axis=1)
        deq = (q * scale).reshape(-1)[:flat.size]
        new_err = flat - deq

        q_recv = jax.lax.all_to_all(q.astype(jnp.int8), axis, split_axis=0,
                                    concat_axis=0, tiled=True)
        scales_all = jax.lax.all_gather(scale[:, 0], axis)
        my = jax.lax.axis_index(axis)
        served = jnp.mean(q_recv.astype(jnp.float32) *
                          scales_all[:, my][:, None], axis=0)
        s_q, s_scale = _sym_quant(served, qmax)

        out_q = jax.lax.all_gather(s_q.astype(jnp.int8), axis, tiled=True)
        out_scales = jax.lax.all_gather(s_scale, axis)
        c = served.shape[0]
        out = (out_q.astype(jnp.float32).reshape(n, c) *
               out_scales[:, None]).reshape(-1)[:flat.size]
        return out.reshape(x.shape).astype(x.dtype), new_err[None]

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=(P(axis), P(axis)),
                           out_specs=(P(), P(axis)),
                           axis_names={axis}, check_vma=False)
    # graftlint: disable=TPU002 (called from the runner's outer jitted step: one construction per outer trace)
    return jax.jit(mapped)(x, error)


# ---------------------------------------------------------------------------
# ZeRO++-style quantized weight gather (qwZ) / gradient reduce-scatter (qgZ)
# ---------------------------------------------------------------------------

def _sym_quant(x: jnp.ndarray, qmax: float, axis=None):
    """Symmetric quant: (clipped-rounded f32 values, f32 scale). axis=None
    scales per-tensor; an int axis scales per-slice (keepdims)."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=axis, keepdims=axis is not None)
    scale = jnp.where(absmax == 0, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(xf / scale), -qmax, qmax)
    return q, scale


def make_quantized_gather(mesh, axis, dim: int, bits: int = 8,
                          spec: "P" = None):
    """ZeRO++-style quantized weight gather (qwZ).

    Returns f(x) where x is sharded on ``dim`` over mesh axis ``axis`` (a
    name or tuple of names, e.g. the composed ZeRO axes): forward
    all-gathers int8 shards + per-shard scales and dequantizes — the wire
    carries 1/4 the bf16 gather bytes (ZeRO++'s quantized weight
    communication). Backward is the exact zero-communication slice back to
    the shard: under SPMD the cotangent reaching this seam is already
    globally reduced, so the gradient-side quantization (qgZ) lives in the
    explicit grad-sync collectives above (``quantized_allreduce``), not
    here. Intended for DCN-bound meshes where gather bandwidth dominates;
    over fast ICI prefer the implicit XLA gathers.

    ``spec``: the leaf's full PartitionSpec (to preserve TP axes on other
    dims); defaults to sharding only ``dim``.
    """
    if not 2 <= bits <= 8:
        raise ValueError(f"bits={bits}: the wire dtype is int8, so only "
                         "2..8-bit quantization is supported")
    qmax = float(2 ** (bits - 1) - 1)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)

    def _specs(ndim):
        base = list(spec) if spec is not None else [None] * ndim
        base = base[:ndim] + [None] * (ndim - len(base))
        in_spec = list(base)
        in_spec[dim] = axis if isinstance(axis, str) else tuple(axis)
        out_spec = list(base)
        out_spec[dim] = None
        # every axis the specs mention must be manual in the shard_map —
        # including TP axes on other dims, over which the inner fn simply
        # operates shard-locally (no collective touches them)
        manual = set(axes)
        for entry in base:
            if entry is None:
                continue
            manual |= {entry} if isinstance(entry, str) else set(entry)
        return P(*in_spec), P(*out_spec), manual

    @jax.custom_vjp
    def qgather(x):
        return _fwd(x)[0]

    def _fwd(x):
        def inner(xs):
            q, scale = _sym_quant(xs, qmax)
            q = q.astype(jnp.int8)
            qg = jax.lax.all_gather(q, axes)              # [k, ...shard]
            sg = jax.lax.all_gather(scale, axes)          # [k]
            deq = qg.astype(jnp.float32) * \
                sg.reshape((-1,) + (1,) * xs.ndim)
            full = jnp.concatenate(list(deq), axis=dim)
            return full.astype(xs.dtype)

        in_spec, out_spec, manual = _specs(x.ndim)
        mapped = jax.shard_map(inner, mesh=mesh, in_specs=in_spec,
                               out_specs=out_spec, axis_names=manual,
                               check_vma=False)
        return mapped(x), None

    def _bwd(_, g):
        def inner(gs):
            # the cotangent is already globally reduced at this seam: the
            # shard's gradient is exactly its slice of it
            k = 1
            for a in axes:
                k *= jax.lax.axis_size(a)
            size = gs.shape[dim] // k
            # axis_index over the tuple = row-major flat rank, matching the
            # all_gather concat order
            idx = jax.lax.axis_index(axes)
            return jax.lax.dynamic_slice_in_dim(gs, idx * size, size,
                                                axis=dim)

        in_spec, out_spec, manual = _specs(g.ndim)
        mapped = jax.shard_map(inner, mesh=mesh, in_specs=out_spec,
                               out_specs=in_spec, axis_names=manual,
                               check_vma=False)
        return (mapped(g),)

    qgather.defvjp(_fwd, _bwd)
    return qgather


def hierarchical_quantized_allreduce(x: jnp.ndarray,
                                     error: jnp.ndarray,
                                     *,
                                     mesh,
                                     intra_axis: str,
                                     inter_axis: str,
                                     bits: int = 8
                                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-level int8 allreduce: exact psum over the fast axis, quantized
    exchange over the slow one (ZeRO++ qgZ's hierarchical scheme; SURVEY §5's
    "data over DCN, model/pipe over ICI" layout).

    Level 1 reduces over ``intra_axis`` (ICI within a slice) at full
    precision — ICI bandwidth makes quantization a loss there. Level 2 runs
    the error-feedback int8 chunk exchange of ``quantized_allreduce`` over
    ``inter_axis`` (DCN across slices), where the 4x byte saving pays.

    x: per-rank values [n_intra * n_inter, ...] stacked on dim 0, sharded
    over (inter, intra); error: [n_inter, numel] per-slice error feedback.
    Returns (averaged [...], new_error).
    """
    n_inter = mesh.shape[inter_axis]
    qmax = float(2 ** (bits - 1) - 1)

    def inner(x, err):
        x, err = x[0], err[0]
        # level 1: exact average within the slice (rides ICI)
        local = jax.lax.pmean(x, intra_axis)
        # level 2: error-feedback int8 chunk exchange across slices
        flat = local.reshape(-1).astype(jnp.float32) + err
        chunks, _ = _chunk(flat, n_inter)
        q, scale = _sym_quant(chunks, qmax, axis=1)
        new_err = flat - (q * scale).reshape(-1)[:flat.size]
        q_recv = jax.lax.all_to_all(q.astype(jnp.int8), inter_axis,
                                    split_axis=0, concat_axis=0, tiled=True)
        scales_all = jax.lax.all_gather(scale[:, 0], inter_axis)
        my = jax.lax.axis_index(inter_axis)
        served = jnp.mean(q_recv.astype(jnp.float32) *
                          scales_all[:, my][:, None], axis=0)
        s_q, s_scale = _sym_quant(served, qmax)
        out_q = jax.lax.all_gather(s_q.astype(jnp.int8), inter_axis,
                                   tiled=True)
        out_scales = jax.lax.all_gather(s_scale, inter_axis)
        c = served.shape[0]
        out = (out_q.astype(jnp.float32).reshape(n_inter, c) *
               out_scales[:, None]).reshape(-1)[:flat.size]
        return out.reshape(x.shape).astype(x.dtype), new_err[None]

    mapped = jax.shard_map(inner, mesh=mesh,
                           in_specs=(P((inter_axis, intra_axis)),
                                     P(inter_axis)),
                           out_specs=(P(), P(inter_axis)),
                           axis_names={intra_axis, inter_axis},
                           check_vma=False)
    # graftlint: disable=TPU002 (called from the runner's outer jitted step: one construction per outer trace)
    return jax.jit(mapped)(x, error)
