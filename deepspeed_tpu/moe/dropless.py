"""Dropless top-k mixture of experts by sorted-token dispatch.

The one MoE function behind ``moe/layer.MoE`` (``k > 2`` or a dropless
config) and ``models/generation._moe_mlp`` (the inference decoder's MLP,
dense and paged): OLMoE-class routing (64 experts, top-8) that the GShard
capacity path in ``sharded_moe.py`` cannot carry, because a one-hot over
``[tokens, experts, capacity]`` costs ``experts / k`` times the needed
FLOPs and a capacity drops tokens the architecture never drops.

    route     float32 scores of the router's logits (a softmax, or
              independent sigmoids with a selection bias), top-k (optionally
              renormalised over the k picks, and scaled)
    dispatch  sort the ``tokens x k`` assignments by expert; gather the
              token rows in that order; ``group_sizes [E]`` = rows an expert
              (``held``: over the experts this program holds; the picks of
              absent experts sort behind the last group and compute nothing)
    experts   the expert MLP as grouped matmuls over the ragged groups
              (:func:`grouped_matmul`: on TPU the Pallas ``megablox`` kernel,
              ``gmm`` in a trace; elsewhere ``jax.lax.ragged_dot``) -- cost
              proportional to ``tokens x k``, whatever the routing
    combine   undo the sort, weight each pick, sum the k picks of a token

No capacity, no dropped token, no array over ``experts x capacity``. The
four stages are ``jax.named_scope``s; a trace shows them under
``block.mlp``. Differentiable (the gathers have transposes, the grouped
matmul a ``custom_vjp``; the sort order is integer), so the same function
trains.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Routing(NamedTuple):
    """What the router decided, for the caller's loss or counters."""
    probs: jnp.ndarray         # [T, E] float32 scores of the router
    experts: jnp.ndarray       # [T, k] int32 the picks, best first
    weights: jnp.ndarray       # [T, k] float32 their combine weights
    group_sizes: jnp.ndarray   # [E] int32 rows routed to each expert
    #: [T, groups] bool, the routing groups each row kept (a grouped router
    #: only: :func:`kept_groups`)
    groups: Optional[jnp.ndarray] = None


def kept_groups(scores: jnp.ndarray, groups: Tuple[int, int],
                best: int = 1) -> jnp.ndarray:
    """``[T, n]`` bool: of the ``n`` equal groups the ``E`` scores lie in,
    the ``keep`` whose BEST score is largest (DeepSeek-V2's
    ``group_limited_greedy``; of equal maxima the lower group, as
    ``lax.top_k`` orders them). ``best = 2``: a group ranks by the SUM of
    its two best scores (DeepSeek-V3's ``noaux_tc``, on the biased scores)."""
    n, keep = groups
    T, E = scores.shape
    in_groups = scores.reshape(T, n, E // n)
    rank = jnp.max(in_groups, axis=-1) if best == 1 else jnp.sum(
        jax.lax.top_k(in_groups, best)[0], axis=-1)
    _, which = jax.lax.top_k(rank, keep)
    return jnp.zeros((T, n), bool).at[jnp.arange(T)[:, None], which].set(True)


def route_topk(logits: jnp.ndarray, k: int, renorm: bool,
               groups: Optional[Tuple[int, int]] = None,
               scale: float = 1.0) -> Routing:
    """Softmax in float32, then the k largest. ``renorm`` divides the k
    weights by their sum (HF ``norm_topk_prob``); OLMoE keeps the raw
    probabilities. ``groups = (n, keep)``: the k are picked among the
    experts of the row's :func:`kept_groups` only (the others' scores read 0
    for the selection); ``scale`` multiplies the weights
    (``routed_scaling_factor``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    kept = None
    if groups is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        kept = kept_groups(probs, groups)
        weights, experts = jax.lax.top_k(jnp.where(jnp.repeat(
            kept, probs.shape[-1] // groups[0], axis=1), probs, 0.0), k)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return Routing(probs, experts.astype(jnp.int32), weights,
                   _group_sizes(experts, logits.shape[-1]), kept)


def route_sigmoid_topk(logits: jnp.ndarray, k: int, renorm: bool,
                       bias: Optional[jnp.ndarray] = None,
                       scale: float = 1.0,
                       groups: Optional[Tuple[int, int]] = None) -> Routing:
    """The DeepSeek-V3 router: every expert's score is its own float32
    sigmoid; the k largest of ``score + bias`` are picked (the bias steers
    the SELECTION and never weighs); the picks' weights are their scores,
    with ``renorm`` over their sum + 1e-20, times ``scale``. ``groups = (n,
    keep)`` (``noaux_tc``): the k are picked among the experts of the
    ``keep`` groups whose two best BIASED scores add up to most
    (:func:`kept_groups`; the others' read 0 for the selection)."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = scores if bias is None else scores + bias.astype(jnp.float32)
    kept = None
    if groups is not None:
        kept = kept_groups(select, groups, best=2)
        select = jnp.where(jnp.repeat(
            kept, scores.shape[-1] // groups[0], axis=1), select, 0.0)
    _, experts = jax.lax.top_k(select, k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renorm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return Routing(scores, experts.astype(jnp.int32), weights,
                   _group_sizes(experts, logits.shape[-1]), kept)


def _group_sizes(experts: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.zeros((n,), jnp.int32).at[experts.reshape(-1)].add(1)


def balance_stats(r: Routing) -> jnp.ndarray:
    """``[2, E]`` float32: the share of tokens that picked each expert,
    summed over the k pick slots, and the mean router probability. HF's
    ``load_balancing_loss_func`` is ``E * sum(f * P)`` of these, averaged
    over every layer's tokens first (:func:`balance_loss`)."""
    tokens = r.probs.shape[0]
    f = r.group_sizes.astype(jnp.float32) / tokens
    return jnp.stack([f, jnp.mean(r.probs, axis=0)])


def balance_loss(stats: jnp.ndarray) -> jnp.ndarray:
    """The auxiliary loss from ``[2, E]`` stats (one layer's, or the mean
    over layers of equally many tokens each)."""
    return stats.shape[-1] * jnp.sum(stats[0] * stats[1])


#: megablox tile sizes (rows, contraction, output) for 2-byte operands,
#: measured on a v5e at OLMoE's widths (64 experts, 2048 <-> 1024; PERF.md,
#: PR 27): one MoE block of three matmuls takes 1.17 ms at 512 rows and 1.31
#: at 2 048 (XLA's own ragged-dot kernel 2.62 and 2.75; 128 x 128 x 128, the
#: kernel's default, 8.6). The weight tile is 4 MB, twice buffered: a larger
#: one does not fit the kernel's 16 MB
_TILE_ROWS, _TILE_IN, _TILE_OUT = 128, 2048, 1024


def _ragged_dot(rows, kernels, group_sizes):
    return jax.lax.ragged_dot(
        rows, kernels, group_sizes,
        preferred_element_type=jnp.float32).astype(rows.dtype)


def _use_megablox(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def _megablox(rows, kernels, group_sizes, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m = rows.shape[0]
    # float32 operands: half the contraction tile, the same bytes
    tiling = (_TILE_ROWS,
              min(_TILE_IN * 2 // rows.dtype.itemsize, kernels.shape[1]),
              min(_TILE_OUT, kernels.shape[2]))
    # the kernel walks whole row tiles; rows past the last group are not
    # computed and not kept
    rows = jnp.pad(rows, ((0, -m % _TILE_ROWS), (0, 0)))
    return gmm(rows, kernels, group_sizes, preferred_element_type=rows.dtype,
               tiling=tiling, interpret=interpret)[:m]


def grouped_matmul_of_layer(rows: jnp.ndarray, kernels: jnp.ndarray,
                            group_sizes: jnp.ndarray, layer: jnp.ndarray,
                            interpret: bool = False) -> jnp.ndarray:
    """:func:`grouped_matmul` against layer ``layer`` (traced) of kernels
    stacked ``[L, E, in, out]``, for a layer loop that carries the whole
    stack (inference; no gradient). The kernel is handed ALL ``L x E``
    matrices, a free view of the stack, and group sizes that are zero but
    for this layer's experts: it skips an empty group, so the cost is one
    layer's. Slicing the layer out first (what ``lax.scan`` over the stack
    does) costs a copy of the layer's experts every step, because a custom
    call's operand cannot be a slice: 0.8 GB read and written a layer at
    OLMoE's widths, more than the matmuls themselves (PERF.md, section 6, PR 26)."""
    L, E = kernels.shape[:2]
    if _use_megablox(interpret):
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), group_sizes.dtype), group_sizes,
            (layer * E,))
        return _megablox(rows, kernels.reshape((L * E,) + kernels.shape[2:]),
                         sizes, interpret)
    return _ragged_dot(rows, jax.lax.dynamic_index_in_dim(
        kernels, layer, 0, keepdims=False), group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(rows: jnp.ndarray, kernels: jnp.ndarray,
                   group_sizes: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """``rows [R, in]`` sorted by group x ``kernels [E, in, out]`` ->
    ``[R, out]`` in the rows' dtype, float32 accumulation. On TPU (or with
    ``interpret``) the megablox kernel, elsewhere ``jax.lax.ragged_dot``;
    the backward pass is ``ragged_dot``'s own everywhere (megablox's, at
    these tiles, does not fit the chip's fast memory)."""
    if _use_megablox(interpret):
        return _megablox(rows, kernels, group_sizes, interpret)
    return _ragged_dot(rows, kernels, group_sizes)


def _grouped_matmul_fwd(rows, kernels, group_sizes, interpret):
    return (grouped_matmul(rows, kernels, group_sizes, interpret),
            (rows, kernels, group_sizes))


def _grouped_matmul_bwd(interpret, residual, g):
    rows, kernels, group_sizes = residual
    # bilinear: the transposes need no forward result, so the primal
    # ragged_dot that jax.vjp traces here is dead code to the compiler
    _, vjp = jax.vjp(lambda r, k: _ragged_dot(r, k, group_sizes),
                     rows, kernels)
    return vjp(g) + (None,)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def dropless_moe(tokens: jnp.ndarray, router_kernel: jnp.ndarray,
                 experts: Dict[str, Any], *, k: int, renorm: bool,
                 act: Callable, kernel_of: Optional[Callable] = None,
                 interpret: bool = False,
                 layer: Optional[jnp.ndarray] = None,
                 scores: str = "softmax",
                 select_bias: Optional[jnp.ndarray] = None,
                 scale: float = 1.0,
                 held: Optional[Tuple[int, int]] = None,
                 groups: Optional[Tuple[int, int]] = None):
    """``tokens [T, H]`` through a router and ``E`` expert MLPs, k a token.

    ``scores``: "softmax" (:func:`route_topk`, with ``scale`` and the
    group-limited selection ``groups = (n, keep)``) or "sigmoid"
    (:func:`route_sigmoid_topk`, with ``select_bias [E]``, ``scale`` and
    ``groups``, ranked its way). ``held = (first, count)``: the router ranks
    all its ``E`` outputs and ``experts`` holds ``count`` of them, ``first
    ..``: a chip's share of an expert-parallel layer (whole routing groups,
    or a part of one), run without its exchange. The groups are the held
    experts'; a pick of an absent expert keeps its weight in the
    renormalisation, sorts behind the last group (rows the grouped matmul
    never walks) and adds nothing. ``Routing`` is over the router's ``E``.

    ``experts``: ``{"fc", "proj"[, "gate"]}``, each ``{"kernel" [E, in,
    out][, "bias" [E, out]]}`` -- the repo's stacked expert tree. With
    ``gate`` the body is ``proj(act(gate(x)) * fc(x))`` (SwiGLU), without
    it ``proj(act(fc(x)))``. ``kernel_of(p)`` hands a leaf's kernel in the
    compute dtype (a cast by default; the decode path dequantizes there).
    ``interpret`` runs the TPU kernel interpreted (CPU tests). With
    ``layer`` (a traced index) the expert leaves are the WHOLE stack ``[L,
    E, ...]`` and the layer is picked inside the kernel
    (:func:`grouped_matmul_of_layer`); the router's kernel is this layer's.
    Returns ``(y [T, H], Routing)``."""
    T, H = tokens.shape
    if kernel_of is None:
        kernel_of = lambda p: p["kernel"].astype(tokens.dtype)
    with jax.named_scope("route"):
        # the router's few columns decide WHICH weights a token meets, so
        # its products stay float32 on a chip whose default is one bf16 pass
        logits = jnp.dot(tokens.astype(jnp.float32),
                         router_kernel.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if scores == "sigmoid":
            r = route_sigmoid_topk(logits, k, renorm, select_bias, scale,
                                   groups)
        else:
            r = route_topk(logits, k, renorm, groups, scale)
    with jax.named_scope("dispatch"):
        picks, sizes, weights = r.experts.reshape(T * k), r.group_sizes, \
            r.weights
        if held is not None:
            first, count = held
            here = (r.experts >= first) & (r.experts < first + count)
            # the stack's own numbering; an absent pick behind its last group
            picks = jnp.where(here, r.experts - first, count).reshape(T * k)
            sizes = jax.lax.dynamic_slice_in_dim(sizes, first, count)
            weights = jnp.where(here, weights, 0.0)
        order = jnp.argsort(picks, stable=True)       # rows, by expert
        expert_of_row = picks[order]
        rows = tokens[order // k]                      # [T * k, H]

    def dense(x, p):
        if layer is None:
            y = grouped_matmul(x, kernel_of(p), sizes, interpret)
        else:
            y = grouped_matmul_of_layer(x, kernel_of(p), sizes, layer,
                                        interpret)
        if "bias" in p:
            bias = p["bias"] if layer is None else p["bias"][layer]
            # (an absent pick's row reads the last expert's: never kept)
            y = y + bias.astype(y.dtype)[expert_of_row if held is None else
                                         jnp.minimum(expert_of_row,
                                                     held[1] - 1)]
        return y

    with jax.named_scope("experts"):
        if "gate" in experts:
            hidden = act(dense(rows, experts["gate"])) * \
                dense(rows, experts["fc"])
        else:
            hidden = act(dense(rows, experts["fc"]))
        out = dense(hidden, experts["proj"])          # [T * k, H]
    with jax.named_scope("combine"):
        back = jnp.argsort(order)                      # row of pick (t, j)
        picked = out[back].reshape(T, k, H)
        if held is not None:
            # rows no group walked hold whatever the kernel's buffer held
            picked = jnp.where(here[:, :, None], picked, 0)
        y = jnp.einsum("tk,tkh->th", weights,
                       picked.astype(jnp.float32)).astype(tokens.dtype)
    return y, r
