"""Weight-only blockwise-int8 Pallas matmul for the serving decode path.

Round 17 (ROADMAP item 2): decode matmuls used to stream full bf16
kernels from HBM every token, or — on the per-channel int8 tier — to
materialize a full f32 dequantized copy OUTSIDE the dot
(``models/generation._kernel_of``). This kernel moves the dequant inside:
weights are stored int8 with one f32 scale per ``QUANT_BLOCK`` = 256
contraction elements — the SAME blockwise format
``runtime/comm/quantized.py`` puts on the wire, single-sourced in
``deepspeed_tpu/quant_format.py`` — so int8 is what crosses HBM (half
the bf16 bytes, a quarter of f32) and the int8 -> f32 convert happens on
a (256, 128) tile already resident in VMEM, fused into the MXU feed.

Blockwise-along-K is the exact identity the wire format proves out
(ZeRO++ 2306.10209 / EQuARX 2506.17615): with ``w[i, n] =
q[i, n] * s[i // 256, n]``,

    y[m, n] = sum_kb  dot(x[m, kb-block], q[kb-block, n]) * s[kb, n]

— each K-block's partial product is scaled once, accumulated f32. The
per-element weight error is bounded by ``block_absmax / 127`` (the
COMM.md model), so the logit error is bounded by the corresponding
matvec norm — pinned by tests/test_low_precision.py.

Packing (:func:`pack_kernel` / :func:`pack_decode_weights`) happens ONCE
at engine construction (``serving.weight_dtype: "int8"``); the hot path
never re-quantizes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...quant_format import QUANT_BLOCK, block_quant

__all__ = ["quant_matmul", "quant_matmul_reference", "pack_kernel",
           "pack_decode_weights"]

#: output rows per program — decode M is the (tiny) batch, padded to the
#: sublane minimum
_BM = 8
#: output cols per program — one lane width
_BN = 128


def pack_kernel(w: jnp.ndarray, block: int = QUANT_BLOCK):
    """[..., K, N] weight -> (q int8 [..., Kp, N], scales f32 [..., Kp/block, N]).

    Blockwise symmetric int8 along the CONTRACTION dim (quant_format's
    wire math applied down columns): Kp is K padded up to a block
    multiple; the padded rows quantize to exactly 0 (zero input, scale
    1), so a zero-padded activation contributes nothing — padding is
    exact, not approximate. Leading dims (the scan-stacked layer axis)
    pack independently per slice."""
    wt = jnp.swapaxes(w, -1, -2)                    # [..., N, K]
    q, s, _pad = block_quant(wt, 8, block)          # [..., N, Kp], [..., N, nkb]
    return jnp.swapaxes(q, -1, -2), jnp.swapaxes(s, -1, -2)


def pack_decode_weights(params, block: int = QUANT_BLOCK):
    """Pack a scan-layout serving param tree's dense kernels to blockwise
    int8 (run ONCE at ``ServingEngine`` construction under
    ``serving.weight_dtype: "int8"``).

    Packs the direct matmul leaves of ``blocks`` (attn_qkv, attn_proj,
    mlp_fc/gate/proj — per-layer slices of the stacked [L, K, N] leaves)
    plus ``lm_head``. The int8 tier leaves the MoE subtree in bf16: a
    quantized router flips picks, and neither the GShard einsums nor the
    dropless path's grouped matmul (``moe/dropless.py``) has an int8 kernel.
    Anything already carrying a per-channel ``kernel_scale`` pack is left
    alone too."""
    def _pack(sub):
        if "kernel_scale" in sub or "kernel_qscale" in sub:
            return sub
        q, s = pack_kernel(sub["kernel"], block)
        out = {k: v for k, v in sub.items() if k != "kernel"}
        out["kernel"], out["kernel_qscale"] = q, s
        return out

    out = dict(params)
    blocks = dict(params["blocks"])
    for name, sub in blocks.items():
        if isinstance(sub, dict) and "kernel" in sub:
            blocks[name] = _pack(sub)
    out["blocks"] = blocks
    if isinstance(params.get("lm_head"), dict) and "kernel" in params["lm_head"]:
        out["lm_head"] = _pack(params["lm_head"])
    return out


def _kernel(x_ref, q_ref, s_ref, o_ref, acc, *, nkb):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    # the int8 -> f32 convert happens HERE, on a (block, _BN) tile in
    # VMEM; one scale row rescales the whole partial product (blockwise
    # identity: every contraction element of this grid step shares it)
    w = q_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    acc[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * s_ref[0]

    @pl.when(kb == nkb - 1)
    def _finalize():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def quant_matmul(x: jnp.ndarray,
                 q: jnp.ndarray,
                 scales: jnp.ndarray,
                 *,
                 interpret: bool = False) -> jnp.ndarray:
    """x [..., K] @ blockwise-int8 weight -> [..., N].

    q: [Kp, N] int8, scales: [Kp/block, N] f32 (:func:`pack_kernel`
    output; Kp >= K, padded rows are exact zeros). Routes to the Pallas
    kernel on TPU (or under ``interpret``) when N tiles to the lane
    width; otherwise — CPU oracle runs, ragged vocab heads — falls back
    to :func:`quant_matmul_reference`, the same per-block math in jnp
    (the paged-attention fallback idiom)."""
    Kp, N = q.shape
    nkb = scales.shape[0]
    lead, K = x.shape[:-1], x.shape[-1]
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret) or N % _BN != 0:
        if on_tpu:
            # the CPU oracle runs in silence; on a TPU the route off the
            # kernel is said once, with the reason
            from ...utils.logging import warning_once
            warning_once(f"quant_matmul on TPU takes the jnp reference: "
                         f"N={N} is not a multiple of the lane width {_BN}")
        return quant_matmul_reference(x, q, scales)

    xf = x.reshape(-1, K)
    M = xf.shape[0]
    Mp = -(-M // _BM) * _BM
    xf = jnp.pad(xf, ((0, Mp - M), (0, Kp - K)))
    block = Kp // nkb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(Mp // _BM, N // _BN, nkb),
        in_specs=[
            pl.BlockSpec((_BM, block), lambda m, n, kb: (m, kb)),
            pl.BlockSpec((block, _BN), lambda m, n, kb: (kb, n)),
            # scales ride as [nkb, 1, N]: the unit axis makes the block's
            # last two dims (1, _BN) legal for the chip's compiler — a
            # (1, _BN) tile of the 2-D [nkb, N] array is refused
            pl.BlockSpec((1, 1, _BN), lambda m, n, kb: (kb, 0, n)),
        ],
        out_specs=pl.BlockSpec((_BM, _BN), lambda m, n, kb: (m, n)),
        scratch_shapes=[pltpu.VMEM((_BM, _BN), jnp.float32)],
    )
    with jax.named_scope("quant_matmul"):
        out = pl.pallas_call(
            partial(_kernel, nkb=nkb),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
            interpret=interpret,
        )(xf, q, scales.reshape(nkb, 1, N))
    return out[:M].reshape(lead + (N,))


def quant_matmul_reference(x: jnp.ndarray,
                           q: jnp.ndarray,
                           scales: jnp.ndarray) -> jnp.ndarray:
    """jnp oracle: the kernel's per-block identity — each K-block's
    partial product scaled once, summed f32."""
    Kp, N = q.shape
    nkb = scales.shape[0]
    block = Kp // nkb
    lead, K = x.shape[:-1], x.shape[-1]
    xf = x.astype(jnp.float32).reshape(-1, K)
    if Kp > K:
        xf = jnp.pad(xf, ((0, 0), (0, Kp - K)))
    xb = xf.reshape(-1, nkb, block)
    qb = q.astype(jnp.float32).reshape(nkb, block, N)
    part = jnp.einsum("mkb,kbn->mkn", xb, qb) * scales[None]
    return part.sum(axis=1).reshape(lead + (N,)).astype(x.dtype)
