"""The inference decoder over a KV cache, and autoregressive generation.

Capability slot of the reference's inference decode path: the fused
`softmax_context` attention-with-cache kernels and preallocated KV workspace
(csrc/transformer/inference/, inference_context.h) and `InferenceEngine.
generate` (inference/engine.py:537). TPU-native shape: the cache is a
scan-carried pytree of static-shape buffers, the decode step is one jitted
function (XLA's compilation cache plays the role of CUDA-graph capture/replay),
and sampling runs inside `lax.scan` so the whole generation loop is a single
compiled program.

:func:`decoder_forward` is the one decoder layer of the inference tier,
written over a cache object that says where a token's position comes from,
where its K/V row goes and what attends over the cache: ``generate()`` runs
it over :class:`DenseCache` ([L, B, heads, max_len, head_dim] buffers), the
serving loop over a paged block pool (``serving/model_runner.PagedCache``).

All functions are pure: (params, cache, ids) -> (logits, cache). They mirror
models/transformer.Block numerically (same params pytree, scan-layers layout).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import apply_softcap, flash_attention_on_mesh
from ..ops.pallas.sparse_select import (index_scores_reference, select,
                                        selected, selection_bits)
from ..quant_format import kv_quantize
from .transformer import (_ACTIVATIONS, TransformerConfig, alibi_slopes,
                          apply_rotary)

PyTree = Any


def _layer_norm(x, p, eps, rms: bool = False):
    xf = x.astype(jnp.float32)
    if rms:
        # RMSNorm (Llama family): uncentered, scale-only
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["scale"]).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def _qk_norm(cfg: TransformerConfig, p, q, k, kind: str):
    """RMSNorm of q and k before rotary, where the config's ``qk_norm`` is
    of this ``kind``: "projection" (called before the head split) or "head"
    (after it). Both norm the last axis they are given."""
    if cfg.qk_norm_kind != kind:
        return q, k
    return (_layer_norm(q, p["q_norm"], cfg.layer_norm_eps, rms=True),
            _layer_norm(k, p["k_norm"], cfg.layer_norm_eps, rms=True))


def _kernel_of(p, dtype):
    """Matmul weight, dequantizing the int8 weight-only forms in place.

    int8 kernels carry either a per-output-channel symmetric scale
    (``kernel_scale``, the inference engine's format) or per-256-element
    blockwise scales along the contraction dim (``kernel_qscale``, the
    serving pack — quant_format's wire format on a weight); the
    convert+multiply fuses into the consuming dot, so the HBM read is
    half the bf16 bytes — the role of the reference's int8 inference
    kernels (csrc/transformer/inference, pt_binding ds_*_int8 entry
    points). The serving decode hot path does NOT come through here for
    blockwise kernels: ``_dense`` routes those to the Pallas
    ``quant_matmul`` kernel, which dequantizes per block IN-kernel —
    this full materialization is the einsum/oracle fallback only."""
    k = p["kernel"]
    if "kernel_qscale" in p:
        # blockwise along the contraction dim: q [..., Kp, N] int8,
        # scales [..., Kp/block, N] f32 -> w[i, n] = q[i, n] * s[i//block, n]
        # (Kp is the padded contraction — padded rows dequantize to 0)
        s = p["kernel_qscale"]
        nkb = s.shape[-2]
        qb = k.shape[-2] // nkb
        w = (k.astype(jnp.float32).reshape(
                k.shape[:-2] + (nkb, qb, k.shape[-1]))
             * s[..., :, None, :])
        return w.reshape(k.shape).astype(dtype)
    if "kernel_scale" in p:
        # dequantize in f32: the scale is deliberately stored f32 by the
        # inference engine, and an int8->f32 multiply keeps the scale/2
        # error bound; casting the scale to bf16 first would add ~0.4%
        # rounding on top of the quantization error
        return (k.astype(jnp.float32) * p["kernel_scale"]).astype(dtype)
    return k.astype(dtype)


def _dense(x, p, interpret: bool = False):
    if "kernel_qscale" in p:
        # blockwise-int8 packed kernel (serving.weight_dtype
        # "int8") — int8 stays int8 until the Pallas kernel's VMEM
        # dequant; no full-weight f32/bf16 copy materializes here
        from ..ops.pallas.quant_matmul import quant_matmul
        y = quant_matmul(x, p["kernel"], p["kernel_qscale"],
                         interpret=interpret)
    else:
        y = x @ _kernel_of(p, x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# cache lengths round up to this: a cache length is a compile bucket, and
# generations of nearby lengths share one; dead positions are masked
KV_CACHE_ROUND = 256


def padded_cache_len(n: int) -> int:
    return -(-n // KV_CACHE_ROUND) * KV_CACHE_ROUND


def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
               dtype=None, pad_lens=None) -> Dict[str, jnp.ndarray]:
    """Preallocated KV workspace (reference: allocate_workspace, pt_binding).

    ``pad_lens`` [B]: per-sample LEFT-pad lengths for ragged batched
    prompts — cache slots [0, pad_i) are dead for sample i (masked in every
    attention) and logical positions are slot - pad_i. Absent for uniform
    batches (the flash prefill needs the uniform layout).

    ``dtype=jnp.int8``: quantized KV cache — k/v store int8 with a
    per-(layer, batch, head, position) f32 scale (symmetric over the head
    dim), halving the cache's HBM footprint vs bf16 (+~3% for scales):
    2x the context length or batch fits the same workspace. Attention
    dequantizes on read. Capability slot of the reference's int8
    inference kernel family (csrc/transformer/inference ds_*_int8)."""
    dtype = dtype or cfg.dtype
    if cfg.kv_lora_rank:
        # a latent model: ONE row a token and layer, the normed latent and
        # the rotated shared key, no heads and no k / v
        if dtype == jnp.int8:
            raise ValueError(
                "an int8 KV cache with latent attention (kv_lora_rank): the "
                "latent row has no quantized format (ROADMAP M4)")
        cache = {"ckv": jnp.zeros((cfg.num_layers, batch_size, 1, max_len,
                                   cfg.latent_width), dtype),
                 "pos": jnp.zeros((), jnp.int32)}
        if cfg.index_heads:         # the indexer's one key beside the latent
            cache["ki"] = jnp.zeros((cfg.num_layers, batch_size, 1, max_len,
                                     cfg.index_head_dim), cfg.dtype)
        if pad_lens is not None:
            cache["pad"] = jnp.asarray(pad_lens, jnp.int32)
        return cache
    shape = (cfg.num_layers, batch_size, cfg.num_heads, max_len, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
             "pos": jnp.zeros((), jnp.int32)}
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros(shape[:-1] + (1,), jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1] + (1,), jnp.float32)
    if cfg.index_heads:
        # a layer with an indexer caches its one key a token beside K/V
        cache["ki"] = jnp.zeros((cfg.num_layers, batch_size, 1, max_len,
                                 cfg.index_head_dim), cfg.dtype)
    if pad_lens is not None:
        cache["pad"] = jnp.asarray(pad_lens, jnp.int32)
    return cache


def ensure_scan_layout(params: PyTree, num_layers: int) -> PyTree:
    """Restack a scan_layers=False param tree (blocks_0..blocks_{L-1}) into the
    scanned layout (blocks leaves [L, ...]) that the decode path consumes."""
    if "blocks" in params:
        return params
    names = [f"blocks_{i}" for i in range(num_layers)]
    missing = [n for n in names if n not in params]
    if missing:
        raise ValueError(
            f"params have neither 'blocks' (scan layout) nor all of "
            f"blocks_0..blocks_{num_layers - 1} (missing {missing[:3]}...); "
            "cannot build the KV-cache decode layout")
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *[params[n] for n in names])
    rest = {k: v for k, v in params.items() if k not in names}
    return {**rest, "blocks": stacked}


def split_stacked_experts(cfg: TransformerConfig, blocks):
    """``(blocks for the layer loop to slice, the expert stack it must
    not)``: a dropless MoE's ``moe/experts`` leaves ``[L, E, in, out]`` stay
    whole beside the loop and ``_moe_mlp(..., layer=li)`` picks the layer
    inside the grouped-matmul kernel (``moe/dropless.
    grouped_matmul_of_layer`` says what slicing them costs). ``None`` for
    every other model."""
    if not cfg.moe_is_dropless:
        return blocks, None
    moe = {k: v for k, v in blocks["moe"].items() if k != "experts"}
    return {**blocks, "moe": moe}, blocks["moe"]["experts"]


def _moe_mlp(cfg: TransformerConfig, p_moe, h, interpret: bool = False,
             layer=None):
    """Decode-path MoE MLP, ``(y, Routing or None)``. A dropless config
    (``cfg.moe_is_dropless``: OLMoE) runs ``moe/dropless.dropless_moe``, the
    function its training module runs, and returns its routing for the
    caller's counters; ``p_moe["experts"]`` is then the whole stack and
    ``layer`` picks from it (:func:`split_stacked_experts`). Top-1/2 GShard
    configs keep the gating math of moe/layer.MoE with a no-drop capacity —
    incremental decode can't see the other timesteps a capacity limit would
    make it compete with (run eval with a capacity_factor that avoids drops
    for exact decode/full-forward parity)."""
    B, T, H = h.shape
    tokens = h.reshape(B * T, H)
    if cfg.moe_is_dropless:
        from ..moe.dropless import dropless_moe
        act = _ACTIVATIONS[cfg.activation] if cfg.gated_mlp else jax.nn.gelu
        y, routing = dropless_moe(
            tokens, p_moe["gate"]["kernel"], p_moe["experts"], k=cfg.moe_k,
            renorm=cfg.moe_norm_topk, act=act,
            kernel_of=lambda p: _kernel_of(p, h.dtype), interpret=interpret,
            layer=layer, scores=cfg.moe_scores,
            select_bias=p_moe["gate"].get("bias"),
            scale=cfg.moe_routed_scale, held=cfg.moe_held,
            groups=cfg.moe_group_limit)
        if cfg.moe_shared_dim:
            # the shared expert: the experts' body on every token, unweighted
            with jax.named_scope("shared"):
                sh = p_moe["shared"]
                up = _dense(tokens, sh["fc"], interpret)
                if cfg.gated_mlp:
                    up = act(_dense(tokens, sh["gate"], interpret)) * up
                else:
                    up = act(up)
                y = y + _dense(up, sh["proj"], interpret)
        return y.reshape(B, T, H), routing
    from ..moe.sharded_moe import top1_gating, top2_gating
    gate_logits = tokens.astype(jnp.float32) @ p_moe["gate"]["kernel"]
    gating = top1_gating if cfg.moe_k == 1 else top2_gating
    _aux, combine, dispatch, _ = gating(gate_logits, capacity=B * T)
    disp = jnp.einsum("tec,th->ech", dispatch.astype(h.dtype), tokens)

    def edense(x, p, contract="ech,ehm->ecm"):
        y = jnp.einsum(contract, x, _kernel_of(p, h.dtype))
        if "bias" in p:
            y = y + p["bias"][:, None].astype(h.dtype)
        return y

    if "gate" in p_moe["experts"]:
        # SwiGLU experts (Mixtral family): proj(act(gate(x)) * fc(x))
        act = _ACTIVATIONS[cfg.activation]
        g = act(edense(disp, p_moe["experts"]["gate"]))
        hh = g * edense(disp, p_moe["experts"]["fc"])
    else:
        hh = jax.nn.gelu(edense(disp, p_moe["experts"]["fc"]))
    out = edense(hh, p_moe["experts"]["proj"], "ecm,emh->ech")
    y = jnp.einsum("tec,ech->th", combine.astype(h.dtype), out)
    return y.reshape(B, T, H), None


def latent_weights(cfg: TransformerConfig, p_kv_b, dtype):
    """``attn_kv_b`` ``[rank, heads x (nope + v)]`` as ``(wk [heads, rank,
    nope], wv [heads, rank, v])``: what expands a latent to a head's
    unrotated key and its value, or folds into the query and the output."""
    w = _kernel_of(p_kv_b, dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim).transpose(1, 0, 2)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorb_query(q_nope, q_pe, wk, lanes: int):
    """The absorbed query ``[B, heads, T, lanes]``: ``[q_nope Wk[h] | q_pe |
    zeros]``, a row as the cache stores a token's (``q_nope . (c Wk[h]) ==
    (q_nope Wk[h]^T) . c``)."""
    q_abs = jnp.einsum("bhtd,hcd->bhtc", q_nope, wk)
    q = jnp.concatenate([q_abs, q_pe], axis=-1)
    return jnp.pad(q, [(0, 0)] * 3 + [(0, lanes - q.shape[-1])])


def absorb_output(o_latent, wv):
    """``[B, heads, T, rank]`` attended latents through each head's value
    matrix: ``(a c) Wv[h] == a (c Wv[h])``."""
    return jnp.einsum("bhtc,hcd->bhtd", o_latent, wv)


def attention_constants(cfg: TransformerConfig):
    """``(softmax scale, ALiBi slopes [nh] or None)`` of a config."""
    sm_scale = (cfg.attn_scale if cfg.attn_scale is not None
                else 1.0 / np.sqrt(cfg.head_dim))
    if cfg.rope_scaling_type == "yarn":
        sm_scale = cfg.softmax_scale        # YaRN's mscale squared in it
    slopes = (jnp.asarray(alibi_slopes(cfg.num_heads), jnp.float32)
              if cfg.pos_embed == "alibi" else None)
    return sm_scale, slopes


def decoder_forward(cfg: TransformerConfig, params: PyTree,
                    input_ids: jnp.ndarray, cache, *,
                    interpret: bool = False, expert_counts: bool = False,
                    expert_picks: bool = False, head_rows=None):
    """The inference decoder over a KV cache: ``input_ids`` [B, T] ->
    ``(logits [B, T, V] f32, the cache as its caller keeps it, expert counts
    [L, E] or None)`` and, with ``expert_picks`` (a dropless MoE config
    only), ``[L, B x T, k]`` int32: the experts every layer's router picked
    for every row of the call, best first (``Routing.experts``; padding rows
    are routed like any row). ``L`` there is the SPARSE layers
    (``cfg.sparse_layers``: a mixture's leading dense layers have no router)
    and ``E`` the router's outputs, held here or not (``cfg.moe_held``).
    A model with an indexer hands its selection out with the picks:
    ``[L, B x T, k + Kp / 32]``, behind a row's experts the keys it attends
    as bits (``sparse_select.selection_bits``: bit r of word w is the key at
    position ``32 w + r``; ``Kp`` the cache's key capacity in whole 128s).
    Its ``L`` is every layer that has picks OR a selection
    (``cfg.routed_layers``): a leading dense layer with an indexer hands
    out a row whose picks are -1, before the sparse layers' rows.
    ``forward_with_cache`` and ``serving.model_runner.
    paged_forward`` are this function over two caches; a new kind of
    per-sequence state (a latent cache, a recurrent state) is a third.

    ``cache`` is plain Python closed over at trace time (:class:`DenseCache`,
    ``model_runner.PagedCache``) and answers what differs between them:

    * ``positions(T)``: the ``[T]`` or ``[B, T]`` logical positions of the
      call's tokens; ``rope_len``: the length the rotary table covers;
    * ``plan(T)``: called once before the layer loop, for what is the same
      in every layer (a mask, where the new rows go);
    * ``carry()`` / ``finish(carry, T)``: the arrays the loop carries and the
      caller's cache made of them. The whole ``[L, ...]`` buffers ride in the
      carry, so a layer's write is an in-place dynamic-update-slice inside
      the compiled loop (stacked scan outputs were copied whole every layer);
    * ``write(carry, li, k, v, k_scale, v_scale) -> carry``: layer ``li``'s
      new rows ``[B, kv_heads, T, hd]`` (``quantized``: int8, with f32
      scales);
    * ``attend(carry, li, q, k, v, window, select=None) -> [B, nh, T, hd]``
      for the queries ``[B, nh, T, hd]``, ``nh // kv_heads`` of them a K/V
      head;
    * a model with an indexer (``cfg.index_heads``; ``ops/pallas/
      sparse_select.py``): ``write_index(carry, li, ki) -> carry`` stores
      the layer's indexer keys ``[B, 1, T, width]`` beside K/V, and
      ``select(carry, li, qi, wi, window) -> Selection`` answers which
      cached keys each of the call's rows attends (among those its causal,
      context and window masks leave), from its indexer queries ``[B, heads,
      T, width]`` and head weights ``[B, T, heads]``; ``attend`` (a latent
      model's ``attend_latent``) takes that answer as ``select``;
    * ``real_tokens(pos)``: ``[B, T]`` int32, the tokens a request owns
      (``expert_counts``, a dropless MoE config only: how many of them each
      layer's router sent to each expert); a cache whose rows are of
      several kinds (``model_runner.MixedCache``) answers with a tuple, one
      mask a kind, and the counts are then ``[L, kinds, E]``.

    ``head_rows`` (int32 ``[n]``): the head reads these of the ``T`` rows
    and no other, and the logits are ``[B, n, V]``.

    Covers the policy architectures (learned/rotary/alibi positions, GQA,
    parallel residual, per-layer windows, sandwich norms, softcaps, q/k
    norms, MoE MLPs, int8 weights: ``interpret`` runs their Pallas matmul
    interpreted). post_ln (BERT) has no decode path."""
    if cfg.post_ln:
        raise NotImplementedError("post-LN encoders (BERT) do not decode")
    if "blocks" not in params:
        raise ValueError(
            "the decoder needs scan-layers params (a 'blocks' subtree "
            "stacked [L, ...]): models.generation.ensure_scan_layout")
    if cfg.dense_layers and "dense_blocks" not in params:
        raise ValueError(
            f"dense_layers {cfg.dense_layers}: the leading dense layers' "
            "parameters are a 'dense_blocks' subtree stacked beside 'blocks'")
    if (expert_counts or expert_picks) and not cfg.moe_is_dropless:
        raise ValueError("expert_counts and expert_picks need a dropless "
                         "MoE config")
    B, T = input_ids.shape
    nh, hd, kvh = cfg.num_heads, cfg.head_dim, cfg.kv_heads
    rms = cfg.norm == "rmsnorm"
    act = _ACTIVATIONS[cfg.activation]
    norm = lambda t, p: _layer_norm(t, p, cfg.layer_norm_eps, rms)
    dense = partial(_dense, interpret=interpret)

    with jax.named_scope("embed"):
        wte = params["wte"]["embedding"]
        x = wte.astype(cfg.dtype)[input_ids]
        if cfg.embed_scale is not None:
            x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        pos = cache.positions(T)
        if cfg.pos_embed == "learned":
            wpe = params["wpe"]["embedding"].astype(cfg.dtype)
            # clamped: a paged lane's padding may lie past the table's end
            x = x + wpe[jnp.minimum(pos, wpe.shape[0] - 1)]
        if cfg.embed_ln:
            x = norm(x, params["ln_emb"])

    windows = (jnp.asarray(cfg.layer_windows, jnp.int32)
               if cfg.layer_windows is not None
               else jnp.zeros((cfg.num_layers,), jnp.int32))
    cache.plan(T)
    real = cache.real_tokens(pos) if expert_counts else None

    def indexer(p, q_in, h, turn):
        """The indexer's queries ``[B, heads, T, width]`` (from ``q_in``: the
        normed input, or a latent model's normed query latent), its ONE key
        a token ``[B, 1, T, width]`` and its head weights ``[B, T, heads]``
        (both from the normed input ``h``); ``turn`` rotates a head."""
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        qi = dense(q_in, p["index_q"]).reshape(B, T, Hi, Di).transpose(
            0, 2, 1, 3)
        ki = _layer_norm(dense(h, p["index_k"]), p["index_k_norm"],
                         cfg.layer_norm_eps)[:, None]
        wi = dense(h, p["index_w"])
        return turn(qi), turn(ki), wi

    def heads_attention(x, kv, p, window, rope, li):
        """The attention branch over per-head K/V: ``(the cache, the
        branch's output, the normed input, the layer's selection or
        None)``."""
        with jax.named_scope("block.attn"):
            with jax.named_scope("qkv"):
                h = norm(x, p["ln1"]) if cfg.pre_norm else x
                qkv = dense(h, p["attn_qkv"])
                q, k, v = jnp.split(qkv, [nh * hd, (nh + kvh) * hd],
                                    axis=-1)
                to_heads = lambda t, n: t.reshape(B, T, n, hd).transpose(
                    0, 2, 1, 3)
                q, k = _qk_norm(cfg, p, q, k, "projection")  # OLMoE: whole
                q, k, v = to_heads(q, nh), to_heads(k, kvh), to_heads(v, kvh)
                q, k = _qk_norm(cfg, p, q, k, "head")        # Qwen3: a head
                def rotated(rot, *ts):
                    if rope is None:
                        return [rot(t) for t in ts]
                    # a hybrid: this layer may carry no positions
                    return [jnp.where(rope, rot(t), t) for t in ts]

                if cfg.pos_embed == "rotary":
                    # the table covers the cache's capacity (dynamic NTK
                    # stretches once; a plain-theta table has no length)
                    q, k = rotated(partial(
                        apply_rotary, positions=pos,
                        rotary_dim=cfg.rotary_dim,
                        interleaved=cfg.rotary_interleaved,
                        theta=cfg.rope_theta,
                        inv_freq=cfg.rope_inv_freq(cache.rope_len)), q, k)
            sel = None
            if cfg.index_heads:
                # the indexer: its queries and head weights from this token,
                # its ONE key a token cached beside K/V; which cached keys a
                # row attends is the cache's to answer
                with jax.named_scope("index"):
                    # the whole indexer head turns, at the model's theta
                    turn = (lambda t: t) if cfg.pos_embed != "rotary" \
                        else lambda t: rotated(partial(
                            apply_rotary, positions=pos, rotary_dim=None,
                            interleaved=cfg.rotary_interleaved,
                            theta=cfg.rope_theta), t)[0]
                    qi, ki, wi = indexer(p, h, h, turn)
                with jax.named_scope("index_write"):
                    kv = cache.write_index(kv, li, ki)
                sel = cache.select(kv, li, qi, wi, window)
            with jax.named_scope("kv_write"):
                # GQA: K/V go to the cache at the model's kv heads; what a
                # cache stores a query head it repeats itself (DenseCache),
                # the paged pool stores the kv heads (ROADMAP S2)
                ks = vs = None
                if cache.quantized:     # on write: one format, every cache
                    (k, ks), (v, vs) = kv_quantize(k), kv_quantize(v)
                kv = cache.write(kv, li, k, v, ks, vs)
            with jax.named_scope("attend"):
                o = cache.attend(kv, li, q, k, v, window, select=sel)
            with jax.named_scope("out"):
                o = o.transpose(0, 2, 1, 3).reshape(B, T, nh * hd)
                attn_out = dense(o, p["attn_proj"])
                if cfg.post_block_norms:
                    # Gemma-2 sandwich: norm each branch output pre-residual
                    attn_out = norm(attn_out, p["post_attn_norm"])
        return kv, attn_out, h, sel

    def latent_attention(x, kv, p, li):
        """The attention branch of a latent model (``cfg.kv_lora_rank``):
        the token's one latent row goes to the cache, and what attends over
        the cached rows, in which form, is the cache's to answer
        (``attend_latent``; ``ops/pallas/latent_attention.py``). With an
        indexer (DeepSeek Sparse Attention) the cache first answers which
        cached rows each query attends, as the heads branch asks it, from
        indexer queries that read the normed QUERY LATENT. ``(the cache,
        the branch's output, the layer's selection or None)``."""
        rank, nope, vw = cfg.kv_lora_rank, cfg.qk_nope_head_dim, \
            cfg.v_head_dim
        rms_ = lambda t, q: _layer_norm(t, q, cfg.layer_norm_eps, rms=True)
        with jax.named_scope("block.attn"):
            with jax.named_scope("latent_q"):
                h = norm(x, p["ln1"])
                qr = rms_(dense(h, p["attn_q_a"]), p["q_a_norm"])
                q = dense(qr, p["attn_q_b"]).reshape(
                    B, T, nh, hd).transpose(0, 2, 1, 3)
                ckv = dense(h, p["attn_kv_a"])               # [B, T, rank+rope]
                c = rms_(ckv[..., :rank], p["kv_a_norm"])
                rot = partial(apply_rotary, positions=pos, rotary_dim=None,
                              interleaved=cfg.rotary_interleaved,
                              theta=cfg.rope_theta,
                              inv_freq=cfg.rope_inv_freq(cache.rope_len))
                q_nope, q_pe = q[..., :nope], rot(q[..., nope:])
                k_pe = rot(ckv[:, None, :, rank:])[:, 0]     # ONE head
            sel = None
            if cfg.index_heads:
                with jax.named_scope("index"):
                    # the head's leading lanes turn under the model's own
                    # table, halves rotated; the rest carry no position
                    qi, ki, wi = indexer(p, qr, h, partial(
                        rot, rotary_dim=cfg.index_rope_dim,
                        interleaved=False))
                with jax.named_scope("index_write"):
                    kv = cache.write_index(kv, li, ki)
                sel = cache.select(kv, li, qi, wi, jnp.int32(0))
            with jax.named_scope("latent_write"):
                kv = cache.write_latent(
                    kv, li, jnp.concatenate([c, k_pe], axis=-1))
            wk, wv = latent_weights(cfg, p["attn_kv_b"], x.dtype)
            o = cache.attend_latent(kv, li, q_nope, q_pe, wk, wv, select=sel)
            with jax.named_scope("out"):
                attn_out = dense(o.transpose(0, 2, 1, 3).reshape(
                    B, T, nh * vw), p["attn_proj"])
        return kv, attn_out, sel

    def layer(carry, xs, dense_mlp=False):
        """One layer; ``dense_mlp``: one of a mixture's leading dense
        layers (its MLP is ``p``'s own, whatever the width)."""
        x, kv = carry
        p, window, rope, li = xs
        if cfg.kv_lora_rank:
            (kv, attn_out, sel), h = latent_attention(x, kv, p, li), None
        else:
            kv, attn_out, h, sel = heads_attention(x, kv, p, window, rope, li)

        def mlp(hin):
            """``(the MLP branch, (this layer's expert counts or None, its
            picks or None))``; behind a row's picks the keys it attends, as
            bits, where the layer has an indexer."""
            y, (counts, picks) = routed_mlp(hin)
            if expert_picks and sel is not None:
                if picks is None:       # a leading dense layer: no router
                    picks = jnp.full((B * T, cfg.moe_k), -1, jnp.int32)
                bits = selection_bits(sel)
                picks = jnp.concatenate(
                    [picks, bits.reshape(B * T, bits.shape[-1])], axis=1)
            return y, (counts, picks)

        def routed_mlp(hin):
            if cfg.moe_experts > 0 and not dense_mlp:
                # a dropless mixture's expert stack stays whole beside the
                # loop, the kernel picks this layer's: the stack is indexed
                # by the sparse layer's number, the cache by the model's
                p_moe = (p["moe"] if experts is None
                         else dict(p["moe"], experts=experts))
                y, routing = _moe_mlp(
                    cfg, p_moe, hin, interpret,
                    layer=li - cfg.dense_layers if cfg.dense_layers else li)
                picks = routing.experts if expert_picks else None
                if not expert_counts:
                    return y, (None, picks)
                def count(real):
                    counts = jnp.zeros((cfg.moe_experts,), jnp.int32).at[
                        routing.experts.reshape(-1)].add(
                        jnp.repeat(real.reshape(-1), cfg.moe_k))
                    if routing.groups is not None:
                        # a grouped router: behind the experts' counts, how
                        # many real rows kept each group
                        counts = jnp.concatenate([counts, jnp.sum(
                            routing.groups * real.reshape(-1, 1), axis=0,
                            dtype=jnp.int32)])
                    return counts

                with jax.named_scope("route"):
                    return y, (jnp.stack([count(r) for r in real])
                               if isinstance(real, tuple) else count(real),
                               picks)
            if cfg.gated_mlp:            # SwiGLU (Llama family)
                g = act(dense(hin, p["mlp_gate"]))
                return (dense(g * dense(hin, p["mlp_fc"]), p["mlp_proj"]),
                        (None, None))
            return (dense(act(dense(hin, p["mlp_fc"])), p["mlp_proj"]),
                    (None, None))

        with jax.named_scope("block.mlp"):
            if cfg.parallel_residual:
                # GPT-NeoX feeds the MLP branch from its own ln2; GPT-J
                # shares ln1
                m, counts = mlp(norm(x, p["ln2"])
                                if cfg.parallel_residual_dual_ln else h)
                x_out = x + attn_out + m
            else:
                x_mid = x + attn_out
                m, counts = mlp(norm(x_mid, p["ln2"]) if cfg.pre_norm
                                else x_mid)
                if cfg.post_block_norms:
                    m = norm(m, p["post_mlp_norm"])
                x_out = x_mid + m
        return (x_out, kv), counts

    blocks, experts = split_stacked_experts(cfg, params["blocks"])
    ropes = (jnp.asarray(cfg.layer_rope) if cfg.layer_rope is not None
             else None)
    n_dense = cfg.dense_layers
    per_layer = (windows, ropes, jnp.arange(cfg.num_layers))
    carry = (x, cache.carry())
    with jax.named_scope("layers"):
        if n_dense:
            # a mixture's leading dense layers: their own stack first, the
            # same layer body, the pools in the one carry
            carry, (_, dense_picks) = jax.lax.scan(
                partial(layer, dense_mlp=True), carry,
                (params["dense_blocks"],)
                + jax.tree.map(lambda a: a[:n_dense], per_layer))
            per_layer = jax.tree.map(lambda a: a[n_dense:], per_layer)
        (x, carry), (counts, picks) = jax.lax.scan(layer, carry,
                                                   (blocks,) + per_layer)
        if n_dense and dense_picks is not None:
            # the leading dense layers' selections, before the sparse rows
            picks = jnp.concatenate([dense_picks, picks], axis=0)
    out = cache.finish(carry, T)
    with jax.named_scope("head"):
        if head_rows is not None:
            x = jnp.take(x, head_rows, axis=1)
        x = norm(x, params["ln_f"])
        if cfg.tie_embeddings:
            logits = jnp.einsum("bth,vh->btv", x, wte.astype(x.dtype))
        else:
            logits = dense(x, params["lm_head"])
        if cfg.final_logit_softcap:
            # stays f32 (the return casts to f32 anyway): a bf16 round-trip
            # of the capped logits could flip near-tie argmaxes
            logits = apply_softcap(logits, cfg.final_logit_softcap)
    if expert_picks:
        return logits.astype(jnp.float32), out, counts, picks
    return logits.astype(jnp.float32), out, counts


class DenseCache:
    """``generate()``'s cache behind :func:`decoder_forward`: ``init_cache``'s
    ``[L, B, nh, len, hd]`` buffers, the whole batch writing at the one slot
    ``cache["pos"]``, ragged prompts left-padded (``cache["pad"]``).
    Attention masks the whole preallocated cache (f32 scores, -1e30), or,
    where ``prefill_flash`` holds, runs the flash kernel over the fresh K/V."""

    def __init__(self, cfg: TransformerConfig, cache: Dict, prefill_flash):
        self.cfg, self.cache, self.prefill_flash = cfg, cache, prefill_flash
        rows = cache["ckv" if cfg.kv_lora_rank else "k"]
        self.quantized = rows.dtype == jnp.int8
        self.rope_len = rows.shape[3]
        self.sm_scale, self.slopes = attention_constants(cfg)

    def positions(self, T: int):
        slots = self.cache["pos"] + jnp.arange(T)
        pad = self.cache.get("pad")         # ragged: less the left pad, [B, T]
        return (slots if pad is None
                else jnp.maximum(slots[None, :] - pad[:, None], 0))

    def plan(self, T: int) -> None:
        cfg, pad = self.cfg, self.cache.get("pad")
        # prefill on the flash kernel (empty cache: the caller's contract).
        # Alibi, softcap and a UNIFORM static window all run in-kernel; mixed
        # per-layer windows trace through one scan body, and ragged or int8
        # caches need the masked read: those keep the jnp path
        self.flash = (bool(self.prefill_flash) and T > 1 and pad is None
                      and not self.quantized and not cfg.index_heads
                      and not cfg.kv_lora_rank
                      and cfg.uniform_window() is not None
                      and cfg.attention_impl in ("auto", "flash")
                      and (jax.default_backend() == "tpu"
                           or self.prefill_flash == "interpret"))
        self.q_slot = q = self.cache["pos"] + jnp.arange(T)        # [T]
        self.k_slot = k = jnp.arange(self.rope_len)                # [len]
        # causal-with-cache [T, len]; dead left-pad slots never attend (per
        # sample): [B, T, len]
        self.mask = k[None, :] <= q[:, None]
        if pad is not None:
            self.mask = self.mask[None] & (k[None, None, :]
                                           >= pad[:, None, None])
        self.alibi = None
        if self.slopes is not None:                         # [nh, T, len]
            dist = (k[None, :] - q[:, None]).astype(jnp.float32)
            self.alibi = self.slopes[:, None, None] * dist[None]

    def carry(self):
        return {n: a for n, a in self.cache.items() if n not in ("pos", "pad")}

    def write_index(self, kv, li, ki):
        return {**kv, "ki": jax.lax.dynamic_update_slice(
            kv["ki"], ki.astype(kv["ki"].dtype)[None],
            (li, 0, 0, self.cache["pos"], 0))}

    def select(self, kv, li, qi, wi, window):
        """Which of the cache's slots each row attends, by the indexer's
        scores (the dense-masked form: the jnp twins of ``sparse_select``'s
        kernels). A ragged batch's left pad is dead to the indexer as it is
        to attention, and so is a key outside the layer's ``window``."""
        B, T = qi.shape[0], qi.shape[2]
        keys = jax.lax.dynamic_index_in_dim(kv["ki"], li, 0,
                                            keepdims=False)[:, 0]
        q0 = jnp.broadcast_to(self.cache["pos"], (B,))
        with jax.named_scope("index"):
            scores = index_scores_reference(qi, wi, keys, q0, q0 + T,
                                            window)
            pad = self.cache.get("pad")
            if pad is not None:
                slots = jnp.arange(scores.shape[-1])
                scores = jnp.where(slots[None, None, :] >= pad[:, None, None],
                                   scores, -jnp.inf)
        with jax.named_scope("select"):
            return select(scores, self.cfg.index_topk, kernel=False)

    def finish(self, carry, T: int):
        return {**self.cache, **carry, "pos": self.cache["pos"] + T}

    def write_latent(self, kv, li, row):
        """A latent model's one row a token, ``[B, T, latent_width]``."""
        return {**kv, "ckv": jax.lax.dynamic_update_slice(
            kv["ckv"], row.astype(kv["ckv"].dtype)[None, :, None],
            (li, 0, 0, self.cache["pos"], 0))}

    def attend_latent(self, kv, li, q_nope, q_pe, wk, wv, select=None):
        """Latent attention over the whole preallocated buffer, absorbed
        (the dense-masked form; ``attend``'s f32 scores and -1e30 masks):
        ``[B, heads, T, v_head_dim]``. ``select``: the rows' top-k keys by
        their indexer, masked beside the causal mask."""
        rank = self.cfg.kv_lora_rank
        rows = jax.lax.dynamic_index_in_dim(kv["ckv"], li, 0,
                                            keepdims=False)[:, 0]
        with jax.named_scope("absorb"):
            q = absorb_query(q_nope, q_pe, wk, rows.shape[-1])
        with jax.named_scope("attend"):
            s = jnp.einsum("bhtw,bkw->bhtk", q, rows).astype(jnp.float32)
            m = self.mask
            if select is not None:
                m = m & selected(select.scores[:, :, :self.rope_len],
                                 select.thr[..., None], select.tie[..., None],
                                 self.k_slot)
            s = jnp.where(m[:, None] if m.ndim == 3 else m[None, None],
                          s * self.sm_scale, -1e30)
            prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            o = jnp.einsum("bhtk,bkc->bhtc", prob, rows[..., :rank])
        with jax.named_scope("absorb"):
            return absorb_output(o, wv)

    def _per_query_head(self, t):
        """GQA: the buffers hold a row a QUERY head, so K/V (and the int8
        tier's scales) are repeated to full heads before they are stored
        or, on the flash prefill, attended (storing them at the kv heads is
        ROADMAP S2's other half, the paged pool's only so far)."""
        nh, kvh = self.cfg.num_heads, self.cfg.kv_heads
        return t if kvh == nh else jnp.repeat(t, nh // kvh, axis=1)

    def write(self, kv, li, k, v, k_scale, v_scale):
        new = {"k": k, "v": v, "k_scale": k_scale, "v_scale": v_scale}
        at = (li, 0, 0, self.cache["pos"], 0)
        return {n: (jax.lax.dynamic_update_slice(
            a, self._per_query_head(new[n])[None], at) if n in new else a)
            for n, a in kv.items()}

    def attend(self, kv, li, q, k, v, window, select=None):
        cfg = self.cfg
        if self.flash:
            # empty cache: attention over the FRESH k/v is exactly the
            # causal prefill (alibi distances from arange positions match
            # the slots because pos == 0)
            return flash_attention_on_mesh(
                q, self._per_query_head(k), self._per_query_head(v),
                causal=True, sm_scale=self.sm_scale,
                window=cfg.uniform_window(), softcap=cfg.attn_softcap,
                alibi_slopes=self.slopes,
                interpret=self.prefill_flash == "interpret")
        # the slice reads fuse into the attention consumers (no copy)
        of_layer = lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                          keepdims=False)
        k_all, v_all = of_layer(kv["k"]), of_layer(kv["v"])
        if self.quantized:
            # dequantize on read: int8 x f32 per-position scale (the HBM
            # read is the int8 bytes; the multiply fuses)
            k_all = (k_all.astype(jnp.float32)
                     * of_layer(kv["k_scale"])).astype(q.dtype)
            v_all = (v_all.astype(jnp.float32)
                     * of_layer(kv["v_scale"])).astype(q.dtype)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_all).astype(jnp.float32)
        s = s * self.sm_scale
        if cfg.attn_softcap:
            s = apply_softcap(s, cfg.attn_softcap)
        if self.alibi is not None:
            s = s + self.alibi[None]
        # local sliding window (0 = global); slot distance == logical
        # distance for valid pairs (the left-pad offset cancels). The mask
        # is [B, T, len] for ragged batches, [T, len] otherwise
        m = self.mask & ((self.q_slot[:, None] - self.k_slot[None, :] < window)
                         | (window <= 0))
        if select is not None:      # a row's top-k keys by its indexer
            m = m & selected(select.scores[:, :, :self.rope_len],
                             select.thr[..., None], select.tie[..., None],
                             self.k_slot)
        s = jnp.where(m[:, None] if m.ndim == 3 else m[None, None], s, -1e30)
        prob = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", prob, v_all)


def forward_with_cache(cfg: TransformerConfig, params: PyTree,
                       input_ids: jnp.ndarray, cache: Dict,
                       prefill_flash=False) -> Tuple[jnp.ndarray, Dict]:
    """Run T_new tokens at positions [cache.pos, cache.pos+T_new) against the
    cache: :func:`decoder_forward` over a :class:`DenseCache`. Returns
    (logits [B, T_new, V], updated cache). Params must be the scan-layers
    layout (blocks leaves [L, ...]): ensure_scan_layout restacks a tree.

    ``prefill_flash``: the caller guarantees the cache is EMPTY (pos == 0) —
    the prefill attention then runs the Pallas flash kernel over the fresh
    K/V (causal, with in-kernel alibi slopes / softcap / uniform sliding
    window) instead of masking the whole preallocated cache, so prefill cost
    scales with the prompt, not max_len. TPU only (pass "interpret" to force
    the interpreted kernel in tests); ragged (left-padded), int8-cache, and
    mixed-per-layer-window models keep the jnp path."""
    logits, cache, _ = decoder_forward(
        cfg, params, input_ids, DenseCache(cfg, cache, prefill_flash))
    return logits, cache


def apply_top_p(logits, top_p: float):
    """Nucleus filter: keep the smallest prefix of the descending-prob
    distribution with cumulative mass >= top_p, mask the rest (HF
    TopPLogitsWarper semantics: tokens whose cumulative probability AFTER
    themselves exceeds top_p survive; the top token always survives).

    Masking is POSITIONAL in the sorted order (scattered back through the
    inverse permutation), not value-thresholded — tied logits at the
    nucleus boundary keep exactly the sorted-prefix count, as HF does."""
    order = jnp.argsort(-logits, axis=-1)                  # descending
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a sorted position is kept while the mass BEFORE it is < top_p
    keep_sorted = (cum - probs) < top_p
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, -1e30)


def apply_repetition_penalty(logits, seen, penalty: float):
    """CTRL-style (HF RepetitionPenaltyLogitsProcessor): for every already-
    seen token, positive logits divide by the penalty, negative multiply."""
    pen = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, pen, logits)


def _sample(logits, rng, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None):
    """logits [B, V] -> token ids [B]."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p is not None and top_p < 1.0:
        logits = apply_top_p(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1)


def generate(cfg: TransformerConfig,
             params: PyTree,
             input_ids: jnp.ndarray,
             max_new_tokens: int,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             repetition_penalty: Optional[float] = None,
             attention_mask: Optional[jnp.ndarray] = None,
             kv_cache_dtype: Optional[str] = None) -> jnp.ndarray:
    """Host wrapper over the jitted generation program: validates the
    attention_mask HERE (the shared entry point — benchmarks and library
    users call generate() directly, not only through InferenceEngine).
    HF tokenizers pad RIGHT by default, and a right-padded mask would
    silently decode garbage (the ragged path assumes pads-first). See
    _generate for the full contract."""
    # (under an outer jit/vmap/scan the mask is a tracer: host validation
    # is impossible there, and the jitted program inlines)
    if attention_mask is not None and not isinstance(attention_mask,
                                                     jax.core.Tracer):
        # int cast first: np.diff on a BOOL array is XOR (always >= 0), so
        # a bool right-padded mask would sail through the guard
        mask_np = np.asarray(attention_mask, dtype=np.int32)
        if not (np.diff(mask_np, axis=1) >= 0).all():
            raise ValueError(
                "generate() requires LEFT-padded prompts: every "
                "attention_mask row must be non-decreasing (0s then 1s). "
                "Re-tokenize with padding_side='left'.")
        # uniform batch: dropping the mask keeps the flash prefill eligible
        # (per-sample masks take the masked jnp attention)
        attention_mask = None if mask_np.all() else jnp.asarray(mask_np)
    return _generate(cfg, params, input_ids, max_new_tokens, temperature,
                     rng, top_k, top_p, repetition_penalty, attention_mask,
                     kv_cache_dtype)


@partial(jax.jit, static_argnums=(0, 3, 4, 6, 7, 8, 10))
def _generate(cfg: TransformerConfig,
             params: PyTree,
             input_ids: jnp.ndarray,
             max_new_tokens: int,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             repetition_penalty: Optional[float] = None,
             attention_mask: Optional[jnp.ndarray] = None,
             kv_cache_dtype: Optional[str] = None) -> jnp.ndarray:
    """Prefill + single-token decode loop, one compiled program.

    input_ids [B, T_prompt] -> [B, T_prompt + max_new_tokens].

    Ragged batches: pass ``attention_mask`` [B, T_prompt] with prompts
    LEFT-padded (pads first — the layout where every sample's last prompt
    token sits at the same slot, so one batched prefill serves mixed
    context lengths); positions and attention are pad-corrected per sample,
    matching HF's left-padded batched generate.

    Sampling: temperature / top_k / top_p (nucleus) compose in the HF
    processor order (temperature, then k, then p); ``repetition_penalty``
    applies the CTRL rescale to every token already in the sample's prompt
    or generation.
    """
    B, T_in = input_ids.shape
    max_len = T_in + max_new_tokens
    if max_len > cfg.max_seq_len:
        raise ValueError(f"generation length {max_len} exceeds max_seq_len "
                         f"{cfg.max_seq_len}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    params = ensure_scan_layout(params, cfg.num_layers)
    pad_lens = None
    if attention_mask is not None:
        pad_lens = (T_in - jnp.sum(attention_mask.astype(jnp.int32), axis=1)
                    ).astype(jnp.int32)
    # round the workspace up to its compile bucket (positions past the
    # logical max are masked, never attended).
    # kv_cache_dtype="int8": half the KV HBM (2x context/batch capacity),
    # dequant-on-read attention — see init_cache.
    kv_dtype = jnp.int8 if kv_cache_dtype == "int8" else None
    padded_len = padded_cache_len(max_len)
    cache = init_cache(cfg, B, padded_len, dtype=kv_dtype,
                       pad_lens=pad_lens)
    # the first forward runs against the freshly-initialized (empty) cache:
    # prefill attention rides the flash kernel where eligible
    logits, cache = forward_with_cache(cfg, params, input_ids, cache,
                                       prefill_flash=True)

    rep = repetition_penalty is not None and repetition_penalty != 1.0
    if rep:
        # seen-token table [B, V]: every prompt token INCLUDING pads (HF's
        # RepetitionPenaltyLogitsProcessor penalizes the pad id of a
        # left-padded batch too — parity means reproducing that), updated
        # with each generated token. Direct scatter — a one_hot here would
        # materialize a [B, T, V] transient.
        seen = jnp.zeros((B, cfg.vocab_size), jnp.bool_).at[
            jnp.arange(B)[:, None], input_ids].set(True)
    else:
        seen = jnp.zeros((B, 1), jnp.bool_)     # placeholder carry

    def pick(logits_last, seen, r):
        if rep:
            logits_last = apply_repetition_penalty(logits_last, seen,
                                                   repetition_penalty)
        tok = _sample(logits_last, r, temperature, top_k, top_p)
        if rep:
            seen = seen | jax.nn.one_hot(tok, cfg.vocab_size,
                                         dtype=jnp.bool_)
        return tok, seen

    rng, r0 = jax.random.split(rng)
    tok, seen = pick(logits[:, -1], seen, r0)

    def step(carry, _):
        tok, cache, rng, seen = carry
        logits, cache = forward_with_cache(cfg, params, tok[:, None], cache)
        rng, r = jax.random.split(rng)
        nxt, seen = pick(logits[:, -1], seen, r)
        return (nxt, cache, rng, seen), tok

    (last, _, _, _), toks = jax.lax.scan(
        step, (tok, cache, rng, seen), None, length=max_new_tokens - 1)
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)  # [B, max_new]
    return jnp.concatenate([input_ids, out], axis=1)
