"""Attention ops — jnp reference implementation + dispatch to Pallas kernels.

Capability slot of the reference's attention kernel families:
  csrc/transformer/softmax_kernels.cu + attn_*       -> fused by XLA / Pallas flash
  deepspeed/ops/sparse_attention/* (Triton, block-sparse) -> block-sparse masks here,
       Pallas block-skipping kernel in ops/pallas/flash_attention.py

`attention(...)` is the single entry point models call; `impl=` selects
  "reference" — pure jnp (always available, used as the parity oracle in tests)
  "flash"     — Pallas TPU flash-attention kernel (ops/pallas/flash_attention.py)
  "auto"      — flash on TPU, reference elsewhere

The flash kernel handles boolean masks (padding and full tiles), ALiBi via
per-head slopes, causal sliding windows, and logit softcap IN-KERNEL (fwd and
bwd), so those regimes ride the flash path. Attention dropout and generic
additive biases are the documented fallbacks to the jnp reference.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def causal_mask(q_len: int, k_len: int) -> jnp.ndarray:
    """[q_len, k_len] bool mask, True = attend. Offset so the last q row sees all k."""
    offset = k_len - q_len
    q_pos = jnp.arange(q_len)[:, None]
    k_pos = jnp.arange(k_len)[None, :]
    return k_pos <= q_pos + offset


def apply_softcap(x, cap: float):
    """Gemma-2 logit softcapping: tanh(x / cap) * cap, computed in f32.
    Single definition — used for attention scores (here and the decode
    path) and final LM logits (transformer head, decode head)."""
    return (jnp.tanh(x.astype(jnp.float32) / cap) * cap)


def alibi_bias_from_slopes(slopes, q_len: int, k_len: int) -> jnp.ndarray:
    """[H] per-head slopes -> [1, H, q_len, k_len] additive ALiBi bias,
    last-query-aligned (q positions arange + k_len - q_len, the decode
    offset convention shared with causal_mask). The dense counterpart of
    the flash kernel's in-kernel slope * (k - q) term — only the fallback
    paths materialize it."""
    sl = jnp.asarray(slopes, jnp.float32).reshape(-1)
    q_pos = jnp.arange(q_len) + (k_len - q_len)
    k_pos = jnp.arange(k_len)
    dist = (k_pos[None, :] - q_pos[:, None]).astype(jnp.float32)
    return sl[None, :, None, None] * dist[None, None]


def window_mask(q_len: int, k_len: int, window) -> jnp.ndarray:
    """[1, 1, q_len, k_len] bool sliding-window mask (True = attend):
    q_pos - k_pos < window, q positions last-row-aligned (arange +
    k_len - q_len, the same offset convention as causal_mask). The dense
    counterpart of the flash kernel's in-kernel window — only fallback
    paths materialize it."""
    q_pos = jnp.arange(q_len)[:, None] + (k_len - q_len)
    k_pos = jnp.arange(k_len)[None, :]
    return (q_pos - k_pos < window)[None, None]


def mha_reference(q: jnp.ndarray,
                  k: jnp.ndarray,
                  v: jnp.ndarray,
                  *,
                  causal: bool = True,
                  bias: Optional[jnp.ndarray] = None,
                  mask: Optional[jnp.ndarray] = None,
                  sm_scale: Optional[float] = None,
                  dropout_rate: float = 0.0,
                  dropout_rng: Optional[jax.Array] = None,
                  softcap: float = 0.0) -> jnp.ndarray:
    """Multi-head attention, jnp reference. q,k,v: [batch, heads, seq, head_dim].

    The numerics oracle every Pallas kernel is tested against (mirrors the
    reference's in-tree HF-BERT baseline used by tests/unit/ops/cuda/*).
    softmax accumulates in fp32 regardless of input dtype (as the reference's
    kernels do for fp16).
    """
    *_, q_len, head_dim = q.shape
    k_len = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(head_dim)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap:
        # Gemma-2 attention-logit softcapping, BEFORE mask/softmax (HF
        # Gemma2Attention eager path); logits are already f32 here
        logits = apply_softcap(logits, softcap)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    neg = jnp.asarray(-1e30, jnp.float32)
    if causal:
        logits = jnp.where(causal_mask(q_len, k_len)[None, None], logits, neg)
    if mask is not None:
        logits = jnp.where(mask, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def _window_fine_block(S: int) -> int:
    return 64 if S % 64 == 0 else 16


def sliding_window_untileable(S: int, D: int) -> Optional[str]:
    """Why :func:`sliding_window_attention` cannot tile [.., S, D], or None
    — the shape test dispatch makes BEFORE the call."""
    from .pallas.block_sparse_attention import tile_plan
    fine = _window_fine_block(S)
    if S % fine:
        return f"seq_len {S} not divisible by the window layout block {fine}"
    return tile_plan(S, D, fine)[2]


def sliding_window_attention(q, k, v, window: int, *,
                             sm_scale: Optional[float] = None,
                             interpret: bool = False) -> jnp.ndarray:
    """Causal sliding-window attention on the block-skip kernel: the layout
    visits only blocks intersecting the window (compute AND K/V DMA scale
    with window, not seq) and the kernel applies the EXACT per-token window
    in-block — same numerics as the dense (q_pos - k_pos < window) mask.
    Raises when shapes can't tile; callers ask
    :func:`sliding_window_untileable` first and take the flash kernel's
    in-kernel window (MXU skip only) on a reason."""
    from .pallas.block_sparse_attention import block_sparse_flash_attention
    from .sparse_attention import LocalSlidingWindowSparsityConfig
    B, H, S, D = q.shape
    fine = _window_fine_block(S)
    w_blocks = -(-(window - 1) // fine) + 1 if window > 1 else 1
    cfg = LocalSlidingWindowSparsityConfig(
        num_heads=H, block=fine, num_sliding_window_blocks=w_blocks,
        attention="unidirectional")
    layout = cfg.make_layout(S)
    # the exact pattern is fully defined by the causal + window masks, so
    # the per-program fine-layout mask work is skipped (layout_exact=False)
    return block_sparse_flash_attention(
        q, k, v, layout, fine, causal=True, sm_scale=sm_scale,
        window=window, layout_exact=False, interpret=interpret)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens, *,
                    sm_scale: Optional[float] = None,
                    alibi_slopes=None,
                    softcap: float = 0.0,
                    window=None,
                    layer_idx=None,
                    k_scale=None,
                    v_scale=None,
                    q_start=None,
                    select=None,
                    impl: str = "auto",
                    interpret: bool = False) -> jnp.ndarray:
    """Dispatching paged-attention entry point (the serving loop's reads).

    q [B, nh, T, hd] against a block-pool K/V ([L?, kvh, num_blocks,
    block_size, hd], ``kvh`` the model's KV heads: ``nh // kvh`` query heads
    read each) through per-sequence ``block_tables`` [B, max_blocks]
    and ``context_lens`` [B]. The Pallas kernel
    (ops/pallas/paged_attention.py) serves a decode step (T == 1) and a
    prefill chunk (T > 1 queries at ``q_start + row``, possibly with PADDED
    trailing ones) alike on a TPU or under ``interpret``, with
    ALiBi/softcap/window in-kernel; the CPU and shapes the kernel cannot
    tile (:func:`paged_attention_path` says which and why; warned once on a
    TPU) run the exact jnp gather reference. int8 pools ride both paths via
    ``k_scale``/``v_scale`` (per-(layer, head, slot) f32, dequantized
    in-kernel / post-gather).
    ``select`` (a layer with an indexer: ``ops.pallas.sparse_select.
    Selection``): each query row attends its selected keys only, on both
    paths. ``impl="reference"`` forces the oracle.
    """
    kw = dict(sm_scale=sm_scale, alibi_slopes=alibi_slopes, softcap=softcap,
              window=window, layer_idx=layer_idx, k_scale=k_scale,
              v_scale=v_scale, q_start=q_start, select=select)
    # the shape test comes BEFORE the call: whatever the kernel itself
    # raises (the chip's compiler refusing it, a pool without scales) is an
    # error, never a quiet route to the reference
    path, reason = paged_attention_path(
        q.shape, k_pool.shape, stacked=layer_idx is not None,
        quant=k_scale is not None, impl=impl, interpret=interpret,
        select=select is not None)
    if path == "kernel":
        from .pallas.paged_attention import paged_attention as _kernel
        return _kernel(q, k_pool, v_pool, block_tables, context_lens,
                       interpret=interpret, **kw)
    if reason and jax.default_backend() == "tpu":
        from ..utils.logging import warning_once
        warning_once("paged_attention on TPU takes the jnp gather "
                     f"reference: {reason}")
    from .pallas.paged_attention import paged_attention_reference
    return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens, **kw)


def paged_attention_path(q_shape, pool_shape, *, stacked: bool, quant: bool,
                         impl: str = "auto", interpret: bool = False,
                         select: bool = False) -> Tuple[str, Optional[str]]:
    """``("kernel", None)`` or ``("reference", why)`` for a
    :func:`paged_attention` call of these shapes, decided from them alone:
    ``why`` is the kernel's own ``untileable`` reason on a TPU or under
    ``interpret``, and None where the reference was asked for
    (``impl="reference"``) or no TPU is there to run the kernel."""
    if impl not in ("auto", "flash") or not (
            jax.default_backend() == "tpu" or interpret):
        return "reference", None
    from .pallas.paged_attention import untileable
    reason = untileable(q_shape, pool_shape, stacked=stacked, quant=quant,
                        interpret=interpret, select=select)
    return ("kernel", None) if reason is None else ("reference", reason)


def flash_attention_on_mesh(q, k, v, *, mask=None, alibi_slopes=None,
                            interpret: bool = False, **kw) -> jnp.ndarray:
    """The flash kernel where its operands live (``kw``: the kernel
    wrapper's own arguments).

    On one device, off the kernel path (CPU without ``interpret``), or
    inside a fully manual ``shard_map`` body (shapes are local already)
    this IS ``ops.pallas.flash_attention.flash_attention``. On a mesh of
    several chips the compiler refuses a kernel it would have to split
    ("Mosaic kernels cannot be automatically partitioned"), so the call is
    made per shard under ``jax.shard_map`` over every mesh axis that is not
    manual yet (the lowering wants ALL of them manual, unit axes too): batch over the mesh manager's ``BATCH_AXES``, heads over
    its ``TP_AXIS``. Attention is independent per (batch, head), so the
    split is exact. A dim its axes do not divide stays whole, and so does
    everything along any other axis wider than 1 (pipe, seq): every chip
    along such an axis then repeats the same attention — the result is
    right, the work is multiplied, and on a TPU that is said once.
    """
    from .pallas.flash_attention import flash_attention
    kw["interpret"] = interpret
    mesh = getattr(getattr(jax.typeof(q), "sharding", None), "mesh", None)
    on_tpu = jax.default_backend() == "tpu"
    free = () if mesh is None or mesh.empty or not (on_tpu or interpret) \
        else tuple(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                   if t != jax.sharding.AxisType.Manual)
    size = lambda axes: int(np.prod([mesh.shape[a] for a in axes]))
    if not free or size(free) == 1:
        return flash_attention(q, k, v, mask=mask, alibi_slopes=alibi_slopes,
                               **kw)
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import BATCH_AXES, TP_AXIS
    B, H = q.shape[:2]
    b_axes = tuple(a for a in BATCH_AXES if a in free)
    h_axes = tuple(a for a in (TP_AXIS,) if a in free)
    whole = []
    if b_axes and B % size(b_axes):
        whole.append(f"batch {B} is not divisible by {size(b_axes)}")
        b_axes = ()
    if h_axes and H % size(h_axes):
        whole.append(f"heads {H} are not divisible by {size(h_axes)}")
        h_axes = ()
    repeated = tuple(a for a in free
                     if a not in b_axes + h_axes and mesh.shape[a] > 1)
    if repeated and on_tpu:
        from ..utils.logging import warning_once
        why = "; ".join(whole) or "attention splits over batch and heads only"
        warning_once(
            f"flash attention [B {B}, H {H}] is repeated on each of the "
            f"{size(repeated)} chips along mesh axes {repeated}: {why}")
    b_spec, h_spec = b_axes or None, (h_axes[0] if h_axes else None)
    qspec = P(b_spec, h_spec, None, None)
    args, specs = [q, k, v], [qspec, qspec, qspec]
    if mask is not None:
        mask = jnp.asarray(mask)      # rank > 4 is refused by the local call
        mask = mask.reshape((1,) * (4 - mask.ndim) + mask.shape)
        args.append(mask)
        specs.append(P(b_spec if mask.shape[0] == B else None,
                       h_spec if mask.shape[1] == H and H > 1 else None,
                       None, None))
    if alibi_slopes is not None:
        args.append(jnp.asarray(alibi_slopes, jnp.float32).reshape(H))
        specs.append(P(h_spec))

    def local(q, k, v, *rest):
        rest = list(rest)
        m = rest.pop(0) if mask is not None else None
        sl = rest.pop(0) if alibi_slopes is not None else None
        return flash_attention(q, k, v, mask=m, alibi_slopes=sl, **kw)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qspec, axis_names=frozenset(free),
                         check_vma=False)(*args)


def attention(q: jnp.ndarray,
              k: jnp.ndarray,
              v: jnp.ndarray,
              *,
              causal: bool = True,
              bias: Optional[jnp.ndarray] = None,
              mask: Optional[jnp.ndarray] = None,
              alibi_slopes=None,
              sm_scale: Optional[float] = None,
              dropout_rate: float = 0.0,
              dropout_rng: Optional[jax.Array] = None,
              impl: str = "auto",
              block_q: int = 1024,
              block_k: int = 1024,
              window: int = 0,
              softcap: float = 0.0,
              interpret: bool = False) -> jnp.ndarray:
    """Dispatching attention entry point. Shapes: [batch, heads, seq, head_dim].

    Kernel-capable regimes (flash path, in-kernel fwd+bwd): boolean ``mask``
    (padding or full), ``alibi_slopes`` ([H] per-head slopes — pass these
    instead of a materialized alibi ``bias``), causal ``window`` > 0, and
    ``softcap``. Attention dropout and generic additive ``bias`` fall back
    to the exact jnp reference (documented, warned under impl="flash").

    ``window`` must be a STATIC python int for the kernel routes — model
    paths that trace it (e.g. per-layer windows as scan elements) compose it
    into the dense mask instead; windows <= 0 mean global. A pure sliding
    window (no other features) prefers the block-skip layout kernel, which
    also skips the K/V DMA of out-of-window blocks.
    """
    window = 0 if window is None or window <= 0 else int(window)
    # the flash kernel covers mask/alibi/window/softcap; dropout and generic
    # additive biases have no kernel path — honor them on the reference impl
    # rather than silently dropping them
    kernel_capable = (dropout_rate == 0.0 and bias is None
                      and (window == 0 or causal))
    on_tpu = jax.default_backend() == "tpu"
    pure_window = (window and causal and mask is None and bias is None
                   and alibi_slopes is None and softcap == 0.0
                   and dropout_rate == 0.0)
    if pure_window and on_tpu and impl in ("auto", "flash"):
        reason = sliding_window_untileable(q.shape[-2], q.shape[-1])
        if reason is None:
            return sliding_window_attention(q, k, v, window,
                                            sm_scale=sm_scale,
                                            interpret=interpret)
        # said once; the flash kernel's in-kernel window serves it below
        from ..utils.logging import warning_once
        warning_once("sliding-window attention on TPU skips the block-skip "
                     f"layout kernel: {reason}")
    if impl == "auto":
        impl = "flash" if (on_tpu and kernel_capable) else "reference"
    if impl in ("ring", "ulysses"):
        if mask is not None or bias is not None or alibi_slopes is not None \
                or dropout_rate > 0.0 or window or softcap:
            from ..utils.logging import logger
            logger.warning(f"attention impl='{impl}' does not support "
                           "mask/bias/window/softcap/dropout; falling back "
                           "to reference")
            impl = "reference"
        else:
            from ..parallel.ring_attention import (ring_attention,
                                                   ulysses_attention)
            fn = ring_attention if impl == "ring" else ulysses_attention
            return fn(q, k, v, causal=causal, sm_scale=sm_scale)
    if impl == "flash":
        if not kernel_capable:
            from ..utils.logging import logger
            logger.warning("attention impl='flash' has no kernel path for "
                           "dropout / generic bias / non-causal windows; "
                           "falling back to reference")
            impl = "reference"
        else:
            return flash_attention_on_mesh(
                q, k, v, causal=causal, sm_scale=sm_scale, mask=mask,
                alibi_slopes=alibi_slopes, window=window, softcap=softcap,
                block_q=block_q, block_k=block_k, interpret=interpret)
    # reference: materialize what the kernel computes from indices
    if alibi_slopes is not None:
        ali = alibi_bias_from_slopes(alibi_slopes, q.shape[-2], k.shape[-2])
        bias = ali if bias is None else bias + ali
    if window:
        wmask = window_mask(q.shape[-2], k.shape[-2], window)
        mask = wmask if mask is None else mask & wmask
    return mha_reference(q, k, v, causal=causal, bias=bias, mask=mask,
                         sm_scale=sm_scale, dropout_rate=dropout_rate,
                         dropout_rng=dropout_rng, softcap=softcap)
