"""Blockwise-scaled int8 collectives — quantized reduce-scatter and
all-to-all (ZeRO++ qgZ / EQuARX style, the wire-hot counterparts of
``compressed.py``'s allreduce family).

Two sites run exact-only before the comm-plan subsystem and dominate
cross-node bytes at scale:

* the ZeRO-2 gradient sync — logically a reduce-scatter of every grad
  leaf over the DP axes (the constraint-driven XLA emission moves
  f32/bf16);
* the MoE expert dispatch/combine — an all-to-all of the token queues
  over the expert axis at ep > 1.

Both get an int8 wire format here: values are quantized in fixed-size
BLOCKS with one f32 scale per block (qwZ-style per-shard scales,
generalized to per-block so one outlier poisons 256 elements, not a
whole shard), the int8 payload plus the small scale tensor ride the
collective, and receivers dequantize — ~4x fewer bytes than f32, ~2x
fewer than bf16 (see docs/COMM.md for the exact accounting and the
error model). Unlike ``compressed_allreduce`` these are STATELESS (no
error feedback): the grad sync is used under the comm-plan accuracy
guard, and the dispatch quantization error is bounded per block.

Every region is a ``jax.shard_map``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...quant_format import QUANT_BLOCK, block_dequant, block_quant

#: re-export: the wire format's block granularity is THE shared format's
#: (deepspeed_tpu/quant_format.py — single-sourced round 17; the
#: blockwise quant/dequant imported above live there too)
DEFAULT_BLOCK = QUANT_BLOCK

__all__ = ["DEFAULT_BLOCK", "block_quant", "block_dequant",
           "rs_quantized_local", "rs_exact_local", "ag_quantized_local",
           "a2a_quantized_local", "quantized_reduce_scatter", "grad_sync",
           "quantized_all_to_all", "make_queue_exchange"]


def _axes_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _axes_size(mesh, axis) -> int:
    n = 1
    for a in _axes_tuple(axis):
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# shard-local building blocks (call INSIDE a shard_map region)
# ---------------------------------------------------------------------------

def rs_quantized_local(x_flat: jnp.ndarray, axis, n: int, *,
                       bits: int = 8, block: int = DEFAULT_BLOCK,
                       mean: bool = False) -> Tuple[jnp.ndarray, int]:
    """One reduce-scatter hop: this rank's full flat buffer in, this
    rank's REDUCED chunk out. Wire: int8 all-to-all of the payload + f32
    all-to-all of the per-block scales (~1/block overhead).

    Returns (served [c] f32, pad) with c = padded chunk length."""
    c = -(-x_flat.size // n)
    c = -(-c // block) * block
    pad = n * c - x_flat.size
    chunks = jnp.pad(x_flat.astype(jnp.float32), (0, pad)).reshape(n, c)
    q, scales, _ = block_quant(chunks, bits, block)
    q_recv = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                                tiled=True)
    s_recv = jax.lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                                tiled=True)
    deq = block_dequant(q_recv, s_recv, 0)                 # [n, c]
    served = jnp.mean(deq, axis=0) if mean else jnp.sum(deq, axis=0)
    return served, pad


def rs_exact_local(x_flat: jnp.ndarray, axis, n: int, *,
                   mean: bool = False) -> Tuple[jnp.ndarray, int]:
    """:func:`rs_quantized_local`'s contract with an EXACT f32 wire —
    one reduce-scatter hop via the same dim-0 all-to-all + local
    reduce. Shared by ``grad_sync(algo="exact")`` and every per-segment
    hop of the overlap executors (``overlap.py``), so the pad/reduce
    semantics live in exactly one place."""
    c = -(-x_flat.size // n)
    pad = n * c - x_flat.size
    chunks = jnp.pad(x_flat.astype(jnp.float32), (0, pad)).reshape(n, c)
    recv = jax.lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    served = jnp.mean(recv, axis=0) if mean else jnp.sum(recv, axis=0)
    return served, pad


def ag_quantized_local(x_flat: jnp.ndarray, axis, *, bits: int = 8,
                       block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Quantized all-gather hop: each rank contributes its flat chunk,
    every rank receives the int8-roundtripped concatenation [n * len]."""
    q, scales, pad = block_quant(x_flat, bits, block)
    out_q = jax.lax.all_gather(q, axis)                    # [n, cp]
    out_s = jax.lax.all_gather(scales, axis)               # [n, cp/block]
    deq = block_dequant(out_q, out_s, pad)                 # [n, len]
    return deq.reshape(-1)


def a2a_quantized_local(x: jnp.ndarray, axis, *, bits: int = 8,
                        block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """Drop-in for ``lax.all_to_all(..., split_axis=0, concat_axis=0,
    tiled=True)`` with an int8 wire format: dim-0 chunks are
    blockwise-quantized on their flattened payload (blocks never
    straddle a chunk boundary — rows move intact with their own scale
    rows), payload + scales ride two all-to-alls, receivers dequantize
    back to ``x.dtype``. Asymmetric split/concat layouts are built from
    this involution + local reshapes (see :func:`make_queue_exchange`)."""
    lead, rest = x.shape[0], x.shape[1:]
    flat = x.reshape(lead, -1)
    q, scales, pad = block_quant(flat, bits, block)
    q_recv = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                                tiled=True)
    s_recv = jax.lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                                tiled=True)
    deq = block_dequant(q_recv, s_recv, pad).astype(x.dtype)
    return deq.reshape((lead,) + rest)


# ---------------------------------------------------------------------------
# public collectives (build their own shard_map; stacked per-rank layout)
# ---------------------------------------------------------------------------

def quantized_reduce_scatter(x: jnp.ndarray, *, mesh, axis="data",
                             bits: int = 8, block: int = DEFAULT_BLOCK,
                             mean: bool = False) -> jnp.ndarray:
    """Blockwise-scaled int8 reduce-scatter.

    x: stacked per-rank values [n, ...] with dim 0 sharded over ``axis``
    (rank r contributes x[r] — the layout of ``compressed_allreduce``).
    Returns the reduced flat chunks [n, c] with dim 0 sharded over
    ``axis``: row r is materialized only on rank r and holds its reduced
    (sum or mean) chunk of the flattened input. Wire bytes per rank:
    ~(n-1)/n * numel int8 + scales, vs 4x that for an f32 exchange."""
    n = _axes_size(mesh, axis)
    axes = _axes_tuple(axis)

    def inner(xs):
        served, _ = rs_quantized_local(xs[0].reshape(-1), axes, n,
                                       bits=bits, block=block, mean=mean)
        return served[None]

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(axes),
                       out_specs=P(axes), axis_names=set(axes),
                       check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(x)


def grad_sync(x: jnp.ndarray, *, mesh, axis="data", algo: str = "int8",
              bits: int = 8, block: int = DEFAULT_BLOCK,
              mean: bool = True) -> jnp.ndarray:
    """ZeRO-2 gradient sync: reduce-scatter + all-gather with the chosen
    wire format — the plan-routed replacement for the implicit XLA grad
    reduction.

    x: stacked per-rank grads [n, ...] dim 0 sharded over ``axis``.
    Returns the reduced (mean by default) value in the ORIGINAL leaf
    shape, replicated — callers re-apply their ZeRO grad sharding
    constraint, which lowers to a local slice.

    ``algo``:
      * ``"int8"`` — qgZ's two quantized hops: blockwise-int8 a2a
        (reduce-scatter), dequant+reduce, re-quantize the served chunk,
        int8 all-gather. ~25% of the f32 wire bytes.
      * ``"exact"`` — the same two hops at f32. Exists so wire-byte
        audits and benchmarks compare identical op structures; the
        engine's exact path stays the implicit XLA emission.
    """
    if algo not in ("exact", "int8"):
        raise ValueError(f"grad_sync algo {algo!r}: expected exact|int8")
    n = _axes_size(mesh, axis)
    axes = _axes_tuple(axis)

    def inner(xs):
        x0 = xs[0]
        flat = x0.reshape(-1).astype(jnp.float32)
        if algo == "int8":
            served, pad = rs_quantized_local(flat, axes, n, bits=bits,
                                             block=block, mean=mean)
            full = ag_quantized_local(served, axes, bits=bits, block=block)
        else:
            served, pad = rs_exact_local(flat, axes, n, mean=mean)
            full = jax.lax.all_gather(served, axes).reshape(-1)
        out = full[:flat.size].reshape(x0.shape).astype(x0.dtype)
        return out

    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(axes), out_specs=P(),
                       axis_names=set(axes), check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(x)


def quantized_all_to_all(x: jnp.ndarray, *, mesh, axis="expert",
                         bits: int = 8,
                         block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """int8 all-to-all over ``axis`` (dim-0 split/concat, the facade's
    ``comm.all_to_all`` default layout): the standalone benchmark/test
    wrapper around :func:`a2a_quantized_local`. ``x`` is sharded on dim 0
    over ``axis``; the result mirrors the exact all-to-all's value within
    blockwise-int8 tolerance."""
    axes = _axes_tuple(axis)

    def inner(xl):
        return a2a_quantized_local(xl, axes, bits=bits, block=block)

    spec = [axis] + [None] * (x.ndim - 1)
    mapped = jax.shard_map(inner, mesh=mesh, in_specs=P(*spec),
                       out_specs=P(*spec), axis_names=set(axes),
                       check_vma=False)
    # graftlint: disable=TPU002 (called under the caller's outer jit: one construction per outer trace)
    return jax.jit(mapped)(x)


# ---------------------------------------------------------------------------
# MoE queue exchange (the GShard a2a pair as an explicit, plan-routable seam)
# ---------------------------------------------------------------------------

def make_queue_exchange(mesh, *, algo: str = "int8", bits: int = 8,
                        block: int = DEFAULT_BLOCK):
    """(dispatch, combine) exchange pair for the grouped MoE layout.

    dispatch: [G, E, Cg, H] (G = data*expert*seq product, dim 0 sharded
    over those axes) -> [E, G*Cg, H] queues (E over 'expert', queue dim
    over ('data','seq')) — the reference ``_AllToAll`` exchange, made
    explicit so the wire format is ours to choose. combine is the exact
    inverse. Both are ``custom_vjp``: the backward of each direction is
    the other direction's exchange of the cotangent (straight-through
    past the quantizer), so the BACKWARD a2a is quantized too.

    The row order of the queue dim is a fixed permutation of the
    implicit-path layout; it is self-consistent between the pair (and
    per-expert compute is row-independent), which is the only property
    the MoE math needs.
    """
    if algo not in ("exact", "int8"):
        raise ValueError(f"queue exchange algo {algo!r}: expected "
                         "exact|int8")
    manual = ("data", "expert", "seq")
    ep = mesh.shape["expert"]

    if algo == "int8":
        # The custom_vjp (straight-through past the quantizer; backward
        # cotangents ride the SAME int8 wire format) sits INSIDE the
        # shard_map body, around the shard-local exchange: an outer
        # custom_vjp wrapping the whole shard_map leaks tracers under
        # flax's nn.scan lifting. The dim-0 peer
        # exchange is an involution and its own transpose, so one
        # function serves both directions and both passes.
        @jax.custom_vjp
        def _exchange(x):
            return a2a_quantized_local(x, "expert", bits=bits, block=block)

        _exchange.defvjp(
            lambda x: (_exchange(x), None),
            lambda _, g: (a2a_quantized_local(g, "expert", bits=bits,
                                              block=block),))
    else:
        def _exchange(x):
            return jax.lax.all_to_all(x, "expert", split_axis=0,
                                      concat_axis=0, tiled=True)

    def to_queues_local(xl):          # [1, E, Cg, H] per-device group
        assert xl.shape[0] == 1, (
            f"queue exchange needs the fully-grouped layout (one group "
            f"per device); got {xl.shape[0]} local groups")
        y = _exchange(xl[0])          # block r = peer r's slice of MY experts
        E, Cg, H = y.shape
        return (y.reshape(ep, E // ep, Cg, H).transpose(1, 0, 2, 3)
                .reshape(E // ep, ep * Cg, H))

    def to_groups_local(ql):          # [E/ep, ep*Cg, H]
        El, Q, H = ql.shape
        y = (ql.reshape(El, ep, Q // ep, H).transpose(1, 0, 2, 3)
             .reshape(ep * El, Q // ep, H))
        return _exchange(y)[None]     # [1, E, Cg, H]

    group_spec = P(manual, None, None, None)
    queue_spec = P("expert", ("data", "seq"), None)
    dispatch = jax.shard_map(to_queues_local, mesh=mesh, in_specs=group_spec,
                         out_specs=queue_spec, axis_names=set(manual),
                         check_vma=False)
    combine = jax.shard_map(to_groups_local, mesh=mesh, in_specs=queue_spec,
                        out_specs=group_spec, axis_names=set(manual),
                        check_vma=False)
    return dispatch, combine
