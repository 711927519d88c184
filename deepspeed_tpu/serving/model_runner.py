"""Paged transformer forward: generation-path numerics over a block pool.

One pure function, :func:`paged_forward`, serves BOTH serving regimes:

* **prefill** — B=1, T = padded prompt(-suffix) length: writes the
  prompt's K/V into the sequence's pool blocks and returns logits for
  every query position (the host samples at the last REAL position);
* **decode** — B = max_batch (the padded active set), T=1: one fresh
  token per lane, fixed shapes across admissions/evictions so the jit
  NEVER re-specializes (the serving loop compiles exactly one decode
  step — CUDA-graph discipline, enforced by tests).

It mirrors ``models/generation.forward_with_cache`` numerically (same
layer math, same f32 score path, same -1e30 masking), so a paged serve
is token-exact with sequential ``generate()`` calls under greedy
sampling. The differences are mechanical: K/V land in the pool's blocks
through the block table — in place, by unrolled dynamic-update-slices in
the layout the paged kernel reads (:func:`_write_kv` says why not by a
scatter) — instead of one dynamic-update-slice into a dense cache, and
attention reads ride ``ops.attention.paged_attention`` — the Pallas
block-table kernel on TPU decode, the exact jnp gather reference
elsewhere.

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
import numpy as np

from ..models.generation import (_dense, _kv_quantize, _layer_norm, _moe_mlp,
                                 _qk_norm, split_stacked_experts)
from ..models.transformer import TransformerConfig
from ..ops.attention import paged_attention
from ..ops.pallas.paged_attention import scale_rows
from .kv_cache import NULL_BLOCK

PyTree = Any


class _WritePlan(NamedTuple):
    """Where one ``paged_forward`` call's new K/V goes, the same in every
    layer: per lane the touched blocks' physical ids and which of their
    slots take a new row (``paged_forward`` says how they are worked out)."""
    bs: int                 # slots a block
    off: jnp.ndarray        # [B] slot of the first position in its block
    phys: jnp.ndarray       # [B, J] physical block of touched block j
    keep: jnp.ndarray       # [B, J, bs] this slot takes a new row


def _write_kv(pool, li, new, ax, plan: _WritePlan):
    """``new`` [B, nh, 1, ., .] with its T positions on axis ``ax`` into
    layer ``li`` of ``pool`` [L, nh, blocks, ., .], whose blocks have their
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
                  expert_counts: bool = False
                  ) -> Tuple[jnp.ndarray, ...]:
    """Run T tokens per lane at logical positions [q_start, q_start + T)
    against the paged pool. Returns (logits [B, T, V] f32, updated pools)
    and, with ``expert_counts`` (a dropless MoE config only), ``[L, E]``
    int32: how many of this call's REAL tokens each layer's router sent to
    each expert (padding positions and lanes with no sequence are routed and
    computed like any row, and not counted).

    input_ids: [B, T]. pools: {"k","v"} [L, nh, num_slots, hd]
    (``serving.kv_cache.init_pool`` layout; ``num_slots`` = pool blocks x
    ``block_size``). block_tables: [B, max_blocks_per_seq] i32 — logical
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

    int8 KV pools (round 12, in-kernel since round 17): when ``pools``
    carries ``k_scale`` / ``v_scale`` (``init_pool(dtype=jnp.int8)``),
    K/V rows are QUANTIZED ON WRITE — symmetric int8 over the head dim
    with one f32 scale per (layer, head, slot), the single-sourced
    ``quant_format.kv_quantize`` format — and the int8 pool plus scales
    go STRAIGHT to attention: the Pallas decode kernel DMAs int8 blocks
    through the block table and dequantizes them in VMEM; the jnp
    reference dequantizes after its gather. Either way the dequant is
    O(attended blocks), not O(pool) — the round-12 full-pool-slice
    f32 read copy is gone (ROADMAP item-2 rung, this PR). Error per
    element is bounded by that row's absmax / 254; greedy decodes are
    token-for-token identical to the round-12 path (gather and dequant
    are elementwise, so they commute).

    int8 weights (round 17): ``kernel_qscale`` leaves (engine-packed
    under ``serving.weight_dtype: "int8"``) route every block matmul
    through ``ops.pallas.quant_matmul`` — blockwise dequant in-kernel,
    jnp per-block reference elsewhere.
    """
    if cfg.post_ln:
        raise NotImplementedError("post-LN encoders (BERT) do not serve")
    if "blocks" not in params:
        raise ValueError("paged_forward needs scan-layers params "
                         "(models.generation.ensure_scan_layout)")
    B, T = input_ids.shape
    nbk = block_tables.shape[1]
    bs = int(block_size)
    k_pool, v_pool = pools["k"], pools["v"]
    quant_kv = "k_scale" in pools
    if k_pool.dtype == jnp.int8 and not quant_kv:
        raise ValueError(
            "int8 KV pool without k_scale/v_scale leaves — build pools "
            "with serving.kv_cache.init_pool(dtype=jnp.int8)")
    num_slots = k_pool.shape[2]
    if num_slots % bs:
        raise ValueError(f"pool slots {num_slots} not divisible by "
                         f"block_size {bs}")
    nb_pool = num_slots // bs
    L = cfg.num_layers
    nh, hd = cfg.num_heads, cfg.head_dim
    kvh = cfg.kv_heads
    rms = cfg.norm == "rmsnorm"
    from ..models.transformer import _ACTIVATIONS, alibi_slopes, apply_rotary
    act = _ACTIVATIONS[cfg.activation]
    sm_scale = (cfg.attn_scale if cfg.attn_scale is not None
                else 1.0 / np.sqrt(hd))

    # a model built with attention_impl="reference" serves on the gather
    # oracle (the twin a kernel-routed serve is compared against)
    attn_impl = "reference" if cfg.attention_impl == "reference" else "auto"

    bt = jnp.asarray(block_tables, jnp.int32)
    q_start = jnp.asarray(q_start, jnp.int32).reshape(B)
    ctx = jnp.asarray(context_lens, jnp.int32).reshape(B)
    # interpret threads into the weight path too: blockwise-int8 kernels
    # (kernel_qscale) route through the Pallas quant matmul
    dense = partial(_dense, interpret=interpret)

    with jax.named_scope("embed"):
        wte = params["wte"]["embedding"]
        x = wte.astype(cfg.dtype)[input_ids]
        if cfg.embed_scale is not None:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)

        pos = q_start[:, None] + jnp.arange(T)[None, :]        # [B, T] logical
        if cfg.pos_embed == "learned":
            wpe = params["wpe"]["embedding"].astype(cfg.dtype)
            x = x + wpe[jnp.minimum(pos, wpe.shape[0] - 1)]
        if cfg.embed_ln:
            x = _layer_norm(x, params["ln_emb"], cfg.layer_norm_eps, rms)

    slopes = (jnp.asarray(alibi_slopes(nh), jnp.float32)
              if cfg.pos_embed == "alibi" else None)
    windows = (jnp.asarray(cfg.layer_windows, jnp.int32)
               if cfg.layer_windows is not None
               else jnp.zeros((cfg.num_layers,), jnp.int32))

    # the K/V write, planned once for every layer. Logical position p of
    # lane b lives in pool block bt[b, p // bs] at slot p % bs, and the T
    # consecutive positions of a lane touch at most n_touch blocks. Slot r
    # of touched block j holds position (q_start // bs + j) * bs + r; it
    # takes the new row t = position - q_start where that is a real query
    # (0 <= t < T, position < ctx) and keeps what it held otherwise, so a
    # chunk may start or end in mid-block. A touched block with no real
    # query in it (padding past ctx, the spare block of an aligned chunk)
    # is the null block: the fixed-shape step can't corrupt live state.
    n_touch = (T + bs - 2) // bs + 1
    off = q_start % bs                                              # [B]
    lblk = (q_start // bs)[:, None] + jnp.arange(n_touch)           # [B, J]
    held_pos = lblk[:, :, None] * bs + jnp.arange(bs)               # [B, J, bs]
    t_idx = held_pos - q_start[:, None, None]
    keep = (t_idx >= 0) & (t_idx < T) & (held_pos < ctx[:, None, None])
    phys = jnp.take_along_axis(bt, jnp.clip(lblk, 0, nbk - 1), axis=1)
    phys = jnp.where(keep.any(axis=2), phys, NULL_BLOCK)            # [B, J]
    plan = _WritePlan(bs, off, phys, keep)
    if expert_counts:
        if not cfg.moe_is_dropless:
            raise ValueError("expert_counts needs a dropless MoE config")
        # a real token: a query below its lane's context, in a lane that
        # holds a sequence (an idle decode lane's table is all null)
        real = ((pos < ctx[:, None])
                & (bt[:, :1] != NULL_BLOCK)).astype(jnp.int32)   # [B, T]

    def layer(carry, xs):
        x, kv = carry
        p, window, li = xs
        with jax.named_scope("block.attn"):
            with jax.named_scope("qkv"):
                h = _layer_norm(x, p["ln1"], cfg.layer_norm_eps, rms)
                qkv = dense(h, p["attn_qkv"])
                q, k, v = jnp.split(qkv, [nh * hd, (nh + kvh) * hd],
                                    axis=-1)
                to_heads = lambda t, n: t.reshape(B, T, n, hd).transpose(
                    0, 2, 1, 3)
                q, k = _qk_norm(cfg, p, q, k, "projection")
                q, k, v = to_heads(q, nh), to_heads(k, kvh), to_heads(v, kvh)
                q, k = _qk_norm(cfg, p, q, k, "head")
                if cfg.pos_embed == "rotary":
                    # table covers the pool's per-sequence maximum (nbk *
                    # bs) — plain-theta tables are length-independent, so
                    # this matches generate()'s cache-capacity table exactly
                    inv_freq = cfg.rope_inv_freq(nbk * bs)
                    rot = partial(apply_rotary, positions=pos,
                                  rotary_dim=cfg.rotary_dim,
                                  interleaved=cfg.rotary_interleaved,
                                  theta=cfg.rope_theta, inv_freq=inv_freq)
                    q, k = rot(q), rot(k)
            with jax.named_scope("kv_write"):
                if kvh != nh:
                    # GQA: repeat kv to full heads before the pool write (the
                    # pool stays [*, nh, ...] so the paged kernel applies
                    # unchanged)
                    k = jnp.repeat(k, nh // kvh, axis=1)
                    v = jnp.repeat(v, nh // kvh, axis=1)
                kv_new = dict(kv)
                if quant_kv:
                    # quantize-on-write: THE dense path's per-channel format
                    # (same helper — axis=-1 math is rank-agnostic over rows);
                    # a scale block keeps its slots on the last axis
                    (k, ks), (v, vs) = _kv_quantize(k), _kv_quantize(v)
                    to_lanes = lambda s: s.reshape(B, nh, 1, 1, T)
                    kv_new["k_scale"] = _write_kv(kv["k_scale"], li,
                                                  to_lanes(ks), 4, plan)
                    kv_new["v_scale"] = _write_kv(kv["v_scale"], li,
                                                  to_lanes(vs), 4, plan)
                kv_new["k"] = _write_kv(
                    kv["k"], li, k.astype(kv["k"].dtype)[:, :, None], 3, plan)
                kv_new["v"] = _write_kv(
                    kv["v"], li, v.astype(kv["v"].dtype)[:, :, None], 3, plan)
            with jax.named_scope("attend"):
                # attention through the block table (kernel on TPU decode,
                # exact jnp gather elsewhere); the int8 tier passes the pool
                # AS int8 with its scales — dequant happens in-kernel /
                # post-gather, O(attended blocks), never a pool-slice copy
                scale_kw = (dict(k_scale=kv_new["k_scale"],
                                 v_scale=kv_new["v_scale"])
                            if quant_kv else {})
                o = paged_attention(q, kv_new["k"], kv_new["v"], bt, ctx,
                                    sm_scale=sm_scale, alibi_slopes=slopes,
                                    softcap=cfg.attn_softcap, window=window,
                                    layer_idx=li, q_start=q_start,
                                    impl=attn_impl, interpret=interpret,
                                    **scale_kw)
            with jax.named_scope("out"):
                o = o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
                attn_out = dense(o, p["attn_proj"])
                if cfg.post_block_norms:
                    attn_out = _layer_norm(attn_out, p["post_attn_norm"],
                                           cfg.layer_norm_eps, rms)

        counts = None

        def mlp(hin):
            nonlocal counts
            if experts is not None:
                # a dropless mixture: the expert stack stays whole beside
                # the loop, the kernel picks this layer's
                y, routing = _moe_mlp(cfg, dict(p["moe"], experts=experts),
                                      hin, with_routing=True,
                                      interpret=interpret, layer=li)
                if not expert_counts:
                    return y
                with jax.named_scope("route"):
                    counts = jnp.zeros((cfg.moe_experts,), jnp.int32).at[
                        routing.experts.reshape(-1)].add(
                        jnp.repeat(real.reshape(-1), cfg.moe_k))
                return y
            if cfg.moe_experts > 0:
                return _moe_mlp(cfg, p["moe"], hin, interpret=interpret)
            if cfg.gated_mlp:
                g = act(dense(hin, p["mlp_gate"]))
                return dense(g * dense(hin, p["mlp_fc"]), p["mlp_proj"])
            return dense(act(dense(hin, p["mlp_fc"])), p["mlp_proj"])

        with jax.named_scope("block.mlp"):
            if cfg.parallel_residual:
                m_in = (_layer_norm(x, p["ln2"], cfg.layer_norm_eps, rms)
                        if cfg.parallel_residual_dual_ln else h)
                x_out = x + attn_out + mlp(m_in)
            else:
                x_mid = x + attn_out
                h2 = _layer_norm(x_mid, p["ln2"], cfg.layer_norm_eps, rms)
                m = mlp(h2)
                if cfg.post_block_norms:
                    m = _layer_norm(m, p["post_mlp_norm"],
                                    cfg.layer_norm_eps, rms)
                x_out = x_mid + m
        return (x_out, kv_new), counts

    blocks, experts = split_stacked_experts(cfg, params["blocks"])
    xs = (blocks, windows, jnp.arange(cfg.num_layers))
    # the loop carries the pools as the kernel reads them, a block's slots
    # on an axis of their own: for K/V a free view of init_pool's flat slot
    # axis; the int8 tier's small scale pools change layout here, at the
    # loop's boundary (a block's scales on the first lanes of a row of whole
    # 128-lane tiles: the least a kernel may copy), not twice a layer inside
    # it
    blocked = {name: (scale_rows(pool, (L, nh, nb_pool, bs, hd))
                      if name.endswith("_scale")
                      else pool.reshape(L, nh, nb_pool, bs, hd))
               for name, pool in pools.items()}
    with jax.named_scope("layers"):
        (x, kv_out), counts = jax.lax.scan(layer, (x, blocked), xs)
    kv_out = {name: (pool[..., :bs] if name.endswith("_scale")
                     else pool).reshape(pools[name].shape)
              for name, pool in kv_out.items()}
    with jax.named_scope("head"):
        x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps, rms)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bth,vh->btv", x, wte.astype(x.dtype))
        else:
            logits = dense(x, params["lm_head"])
        if cfg.final_logit_softcap:
            from ..ops.attention import apply_softcap
            logits = apply_softcap(logits, cfg.final_logit_softcap)
    if expert_counts:
        return logits.astype(jnp.float32), kv_out, counts
    return logits.astype(jnp.float32), kv_out
