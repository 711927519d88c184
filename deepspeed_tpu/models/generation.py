"""KV-cache decode + autoregressive generation for the flagship transformer.

Capability slot of the reference's inference decode path: the fused
`softmax_context` attention-with-cache kernels and preallocated KV workspace
(csrc/transformer/inference/, inference_context.h) and `InferenceEngine.
generate` (inference/engine.py:537). TPU-native shape: the cache is a
scan-carried pytree of static-shape buffers ([L, B, heads, max_len, head_dim]),
the decode step is one jitted function (XLA's compilation cache plays the role
of CUDA-graph capture/replay), and sampling runs inside `lax.scan` so the
whole generation loop is a single compiled program.

All functions are pure: (params, cache, ids) -> (logits, cache). They mirror
models/transformer.Block numerically (same params pytree, scan-layers layout).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..quant_format import kv_quantize as _kv_quantize  # noqa: F401 (shared
#   format, round 17 — re-exported: serving/model_runner imports it here)
from .transformer import TransformerConfig

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
    round-17 serving pack — quant_format's wire format on a weight); the
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
        # round 17: blockwise-int8 packed kernel (serving.weight_dtype
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


# cache lengths round up to this so the decode kernel always has a >=128
# block tiling (ops/pallas/decode_attention.py); dead positions are masked
KV_CACHE_ROUND = 256


def padded_cache_len(n: int) -> int:
    return -(-n // KV_CACHE_ROUND) * KV_CACHE_ROUND


def init_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
               dtype=None, pad_lens=None) -> Dict[str, jnp.ndarray]:
    """Preallocated KV workspace (reference: allocate_workspace, pt_binding).

    ``pad_lens`` [B]: per-sample LEFT-pad lengths for ragged batched
    prompts — cache slots [0, pad_i) are dead for sample i (masked in every
    attention) and logical positions are slot - pad_i. Absent for uniform
    batches (the decode kernel path needs the uniform layout).

    ``dtype=jnp.int8``: quantized KV cache — k/v store int8 with a
    per-(layer, batch, head, position) f32 scale (symmetric over the head
    dim), halving the cache's HBM footprint vs bf16 (+~3% for scales):
    2x the context length or batch fits the same workspace. Attention
    dequantizes on read (jnp path; the block-skip decode kernel needs the
    bf16 layout and is bypassed). Capability slot of the reference's int8
    inference kernel family (csrc/transformer/inference ds_*_int8)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch_size, cfg.num_heads, max_len, cfg.head_dim)
    if dtype == jnp.int8:
        cache = {"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
                 "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
                 "pos": jnp.zeros((), jnp.int32)}
    else:
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                 "pos": jnp.zeros((), jnp.int32)}
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


def _moe_mlp(cfg: TransformerConfig, p_moe, h, with_routing: bool = False,
             interpret: bool = False, layer=None):
    """Decode-path MoE MLP. A dropless config (``cfg.moe_is_dropless``: OLMoE)
    runs ``moe/dropless.dropless_moe``, the function its training module
    runs; ``with_routing`` then returns ``(y, Routing)`` for the caller's
    counters, and with ``layer`` ``p_moe["experts"]`` is the whole stack
    (:func:`split_stacked_experts`). Top-1/2 GShard configs keep the gating
    math of moe/layer.MoE with a no-drop capacity — incremental decode can't
    see the other timesteps a capacity limit would make it compete with (run
    eval with a capacity_factor that avoids drops for exact
    decode/full-forward parity)."""
    B, T, H = h.shape
    tokens = h.reshape(B * T, H)
    if cfg.moe_is_dropless:
        from ..moe.dropless import dropless_moe
        from .transformer import _ACTIVATIONS
        y, routing = dropless_moe(
            tokens, p_moe["gate"]["kernel"], p_moe["experts"], k=cfg.moe_k,
            renorm=cfg.moe_norm_topk,
            act=(_ACTIVATIONS[cfg.activation] if cfg.gated_mlp
                 else jax.nn.gelu),
            kernel_of=lambda p: _kernel_of(p, h.dtype), interpret=interpret,
            layer=layer)
        y = y.reshape(B, T, H)
        return (y, routing) if with_routing else y
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
        from .transformer import _ACTIVATIONS
        act = _ACTIVATIONS[cfg.activation]
        g = act(edense(disp, p_moe["experts"]["gate"]))
        hh = g * edense(disp, p_moe["experts"]["fc"])
    else:
        hh = jax.nn.gelu(edense(disp, p_moe["experts"]["fc"]))
    out = edense(hh, p_moe["experts"]["proj"], "ecm,emh->ech")
    y = jnp.einsum("tec,ech->th", combine.astype(h.dtype), out)
    return y.reshape(B, T, H)


def forward_with_cache(cfg: TransformerConfig, params: PyTree,
                       input_ids: jnp.ndarray, cache: Dict,
                       prefer_kernel: Optional[bool] = None,
                       prefill_flash=False
                       ) -> Tuple[jnp.ndarray, Dict]:
    """Run T_new tokens at positions [cache.pos, cache.pos+T_new) against the
    cache. Returns (logits [B, T_new, V], updated cache). Params must be the
    scan-layers layout (blocks leaves [L, ...]) — use ensure_scan_layout to
    restack a per-layer tree.

    ``prefill_flash``: the caller guarantees the cache is EMPTY (pos == 0) —
    the prefill attention then runs the Pallas flash kernel over the fresh
    K/V (causal, with in-kernel alibi slopes / softcap / uniform sliding
    window) instead of masking the whole preallocated cache, so prefill cost
    scales with the prompt, not max_len. TPU only (pass "interpret" to force
    the interpreted kernel in tests); ragged (left-padded), int8-cache, and
    mixed-per-layer-window models keep the jnp path.

    Covers the policy architectures: rotary/alibi positions, parallel
    residual (GPT-J), per-layer local windows (GPT-Neo), relu/gelu
    activations, unscaled attention, MoE MLPs. post_ln (BERT) has no decode
    path — encoders don't generate."""
    if cfg.post_ln:
        raise NotImplementedError("post-LN encoders (BERT) do not decode")
    if "blocks" not in params:
        raise ValueError(
            "forward_with_cache needs scan-layers params (a 'blocks' subtree "
            "stacked [L, ...]); this model was built with scan_layers=False — "
            "restack with models.generation.ensure_scan_layout(params, L)")
    B, T_new = input_ids.shape
    pos = cache["pos"]
    max_len = cache["k"].shape[3]
    nh, hd = cfg.num_heads, cfg.head_dim
    kvh = cfg.kv_heads
    rms = cfg.norm == "rmsnorm"
    from .transformer import _ACTIVATIONS, alibi_slopes, apply_rotary
    act = _ACTIVATIONS[cfg.activation]
    sm_scale = (cfg.attn_scale if cfg.attn_scale is not None
                else 1.0 / np.sqrt(hd))

    wte = params["wte"]["embedding"]
    x = wte.astype(cfg.dtype)[input_ids]
    if cfg.embed_scale is not None:
        x = x * jnp.asarray(cfg.embed_scale, x.dtype)
    q_abs = pos + jnp.arange(T_new)                 # cache-slot positions [T]
    pad = cache.get("pad")                          # [B] left-pad lengths
    # logical positions (rotary / learned-wpe / HF position_ids semantics):
    # slot - pad for left-padded ragged batches, the slot itself otherwise
    if pad is not None:
        q_log = jnp.maximum(q_abs[None, :] - pad[:, None], 0)    # [B, T]
    else:
        q_log = q_abs
    if cfg.pos_embed == "learned":
        wpe = params["wpe"]["embedding"].astype(cfg.dtype)
        x = x + (wpe[q_log] if pad is not None else wpe[q_log][None])
    if cfg.embed_ln:
        x = _layer_norm(x, params["ln_emb"], cfg.layer_norm_eps, rms)

    k_pos = jnp.arange(max_len)                     # [max_len]
    # causal-with-cache mask [T_new, max_len]
    mask = k_pos[None, :] <= q_abs[:, None]
    if pad is not None:
        # dead left-pad slots never attend (per sample): [B, T, max_len]
        mask = mask[None] & (k_pos[None, None, :] >= pad[:, None, None])
    ali = None
    if cfg.pos_embed == "alibi":
        slopes = jnp.asarray(alibi_slopes(nh), jnp.float32)
        dist = (k_pos[None, :] - q_abs[:, None]).astype(jnp.float32)
        ali = slopes[:, None, None] * dist[None]    # [nh, T_new, max_len]

    windows = (jnp.asarray(cfg.layer_windows, jnp.int32)
               if cfg.layer_windows is not None
               else jnp.zeros((cfg.num_layers,), jnp.int32))

    quant_kv = cache["k"].dtype == jnp.int8

    # Pallas decode kernel: visits only the live ceil(cur_len/block_k) K/V
    # blocks — the slot of the reference's fused softmax_context kernels
    # (pt_binding.cpp:1703-1779). Regime-aware routing under "auto"
    # (round-4 measurements, docs/BENCHMARKS.md): the block-skip pays in
    # BATCHED LONG GENERATION (B>=2, a mostly-dead preallocated cache —
    # 1.77x at B=4, 128-prompt + 2048-new, gpt2-350m) and LOSES 2-8x at
    # B=1 / short caches, where per-layer kernel dispatch dominates.
    # ``prefer_kernel`` (generate passes it from the static prompt/gen
    # plan) overrides the local B/max_len heuristic. "flash" forces the
    # kernel. ALiBi slopes and the Gemma-2 softcap run IN-KERNEL (round-8
    # parity with the flash prefill kernel); ragged (left-padded) batches
    # need per-sample masks -> jnp path; the int8 cache needs the dequant
    # read -> jnp path.
    if prefer_kernel is None:
        prefer_kernel = B >= 2 and max_len >= 4 * 512
    use_kernel = ((cfg.attention_impl == "flash"
                   or (cfg.attention_impl == "auto" and prefer_kernel))
                  and jax.default_backend() == "tpu")
    if use_kernel:
        # the route away from the kernel is decided HERE, from shapes and
        # regime, and said once — never by catching what the kernel raises
        from ..ops.pallas.decode_attention import untileable
        from ..utils.logging import warning_once
        reason = ("ragged (left-padded) batches need per-sample masks"
                  if pad is not None else
                  "the int8 cache needs the dequant read" if quant_kv else
                  untileable(T_new, max_len, hd))
        if reason is not None:
            use_kernel = False
            # a whole-prompt prefill (T > 64) off the decode kernel is the
            # documented regime, not news
            if T_new <= 64:
                warning_once("decode attention on TPU takes the jnp path: "
                             f"{reason}")

    # prefill on the flash kernel (empty cache — caller's contract): alibi,
    # softcap and a UNIFORM static window all run in-kernel; mixed per-layer
    # windows trace through one scan body, so they stay on the jnp path
    uw = cfg.uniform_window()
    uniform_ok = uw is not None
    uniform_window = uw or 0
    flash_interp = prefill_flash == "interpret"
    use_prefill_flash = (bool(prefill_flash) and T_new > 1 and pad is None
                         and not quant_kv and uniform_ok
                         and cfg.attention_impl in ("auto", "flash")
                         and (jax.default_backend() == "tpu" or flash_interp))
    prefill_slopes = (jnp.asarray(alibi_slopes(nh), jnp.float32)
                      if cfg.pos_embed == "alibi" else None)

    def layer(carry, xs):
        # the FULL [L, ...] caches ride in the carry so the per-token write
        # is an in-place dynamic-update-slice inside the compiled loop — the
        # stacked-ys layout copied the whole cache every layer (O(L x
        # max_len) HBM traffic per token, the decode bottleneck)
        if quant_kv:
            x, k_all, v_all, ks_all, vs_all = carry
        else:
            x, k_all, v_all = carry
            ks_all = vs_all = None
        p, window, li = xs
        h = _layer_norm(x, p["ln1"], cfg.layer_norm_eps, rms)
        qkv = _dense(h, p["attn_qkv"])
        q, k, v = jnp.split(qkv, [nh * hd, (nh + kvh) * hd], axis=-1)
        to_heads = lambda t, n: t.reshape(B, T_new, n, hd).transpose(
            0, 2, 1, 3)
        q, k = _qk_norm(cfg, p, q, k, "projection")   # OLMoE: whole vector
        q, k, v = to_heads(q, nh), to_heads(k, kvh), to_heads(v, kvh)
        q, k = _qk_norm(cfg, p, q, k, "head")         # Qwen3: per head
        if cfg.pos_embed == "rotary":
            # q_log: logical (pad-corrected) positions — [B, T] for ragged
            # left-padded batches, [T] otherwise (apply_rotary handles both)
            # table covers the cache capacity (dynamic NTK stretches once;
            # None = plain-theta table)
            inv_freq = cfg.rope_inv_freq(max_len)
            q = apply_rotary(q, q_log, cfg.rotary_dim, cfg.rotary_interleaved,
                             cfg.rope_theta, inv_freq=inv_freq)
            k = apply_rotary(k, q_log, cfg.rotary_dim, cfg.rotary_interleaved,
                             cfg.rope_theta, inv_freq=inv_freq)
        if kvh != nh:
            # GQA: repeat kv to full heads BEFORE the cache write — the
            # cache stays [L, B, nh, len, hd], so the decode kernel and
            # int8 tiers apply unchanged. (Storing kv heads only would
            # shrink the cache nh/kvh-fold; future optimization.)
            k = jnp.repeat(k, nh // kvh, axis=1)
            v = jnp.repeat(v, nh // kvh, axis=1)
        if quant_kv:
            k, k_s = _kv_quantize(k)
            v, v_s = _kv_quantize(v)
            ks_all = jax.lax.dynamic_update_slice(ks_all, k_s[None],
                                                  (li, 0, 0, pos, 0))
            vs_all = jax.lax.dynamic_update_slice(vs_all, v_s[None],
                                                  (li, 0, 0, pos, 0))
        k_all = jax.lax.dynamic_update_slice(k_all, k[None],
                                             (li, 0, 0, pos, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v[None],
                                             (li, 0, 0, pos, 0))
        o = None
        if use_prefill_flash:
            from ..ops.attention import flash_attention_on_mesh
            # empty cache: attention over the FRESH k/v is exactly the
            # causal prefill; alibi distances from arange positions match
            # q_abs because pos == 0
            o = flash_attention_on_mesh(
                q, k, v, causal=True, sm_scale=sm_scale,
                window=uniform_window, softcap=cfg.attn_softcap,
                alibi_slopes=prefill_slopes, interpret=flash_interp)
        if o is None and use_kernel:
            from ..ops.pallas.decode_attention import decode_attention
            # stacked form: the kernel indexes layer li out of the
            # carried [L, ...] cache itself — no materialized slice;
            # alibi slopes / softcap ride in-kernel. Shapes were tested
            # above (``untileable``), so an error here is an error
            o = decode_attention(q, k_all, v_all, pos + T_new,
                                 window=window, sm_scale=sm_scale,
                                 layer_idx=li,
                                 alibi_slopes=prefill_slopes,
                                 softcap=cfg.attn_softcap)
        if o is None:
            # the slice reads fuse into the attention consumers (no copy)
            k_cache = jax.lax.dynamic_index_in_dim(k_all, li, 0,
                                                   keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(v_all, li, 0,
                                                   keepdims=False)
            if quant_kv:
                # dequantize on read: int8 x f32 per-position scale (the
                # HBM read is the int8 bytes; the multiply fuses)
                k_sc = jax.lax.dynamic_index_in_dim(ks_all, li, 0,
                                                    keepdims=False)
                v_sc = jax.lax.dynamic_index_in_dim(vs_all, li, 0,
                                                    keepdims=False)
                k_cache = (k_cache.astype(jnp.float32) * k_sc).astype(q.dtype)
                v_cache = (v_cache.astype(jnp.float32) * v_sc).astype(q.dtype)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache).astype(jnp.float32)
            s = s * sm_scale
            if cfg.attn_softcap:
                from ..ops.attention import apply_softcap
                s = apply_softcap(s, cfg.attn_softcap)
            if ali is not None:
                s = s + ali[None]
            m = mask
            # local sliding window (0 = global); slot distance == logical
            # distance for valid pairs (the left-pad offset cancels)
            win = (q_abs[:, None] - k_pos[None, :] < window) | (window <= 0)
            m = m & (win[None] if m.ndim == 3 else win)
            # mask is [B, T, max_len] for ragged batches, [T, max_len] else
            s = jnp.where(m[:, None] if m.ndim == 3 else m[None, None],
                          s, -1e30)
            prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            o = jnp.einsum("bhqk,bhkd->bhqd", prob, v_cache)
        o = o.transpose(0, 2, 1, 3).reshape(B, T_new, nh * hd)
        attn_out = _dense(o, p["attn_proj"])
        if cfg.post_block_norms:
            # Gemma-2 sandwich: norm each branch output pre-residual
            attn_out = _layer_norm(attn_out, p["post_attn_norm"],
                                   cfg.layer_norm_eps, rms)

        def mlp(hin):
            if cfg.moe_experts > 0:
                if experts is not None:
                    return _moe_mlp(cfg, dict(p["moe"], experts=experts),
                                    hin, layer=li)
                return _moe_mlp(cfg, p["moe"], hin)
            if cfg.gated_mlp:            # SwiGLU (Llama family)
                g = act(_dense(hin, p["mlp_gate"]))
                return _dense(g * _dense(hin, p["mlp_fc"]), p["mlp_proj"])
            return _dense(act(_dense(hin, p["mlp_fc"])), p["mlp_proj"])

        if cfg.parallel_residual:
            # GPT-NeoX feeds the MLP branch from its own ln2; GPT-J shares ln1
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
        if quant_kv:
            return (x_out, k_all, v_all, ks_all, vs_all), None
        return (x_out, k_all, v_all), None

    blocks, experts = split_stacked_experts(cfg, params["blocks"])
    xs = (blocks, windows, jnp.arange(cfg.num_layers))
    if quant_kv:
        (x, k_new, v_new, ks_new, vs_new), _ = jax.lax.scan(
            layer, (x, cache["k"], cache["v"], cache["k_scale"],
                    cache["v_scale"]), xs)
    else:
        (x, k_new, v_new), _ = jax.lax.scan(
            layer, (x, cache["k"], cache["v"]), xs)
    x = _layer_norm(x, params["ln_f"], cfg.layer_norm_eps, rms)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bth,vh->btv", x, wte.astype(x.dtype))
    else:
        logits = _dense(x, params["lm_head"])
    if cfg.final_logit_softcap:
        # stay f32: the return below casts to f32 anyway, and a bf16
        # round-trip of the capped logits could flip near-tie argmaxes
        from ..ops.attention import apply_softcap
        logits = apply_softcap(logits, cfg.final_logit_softcap)
    new_cache = {"k": k_new, "v": v_new, "pos": pos + T_new}
    if quant_kv:
        new_cache["k_scale"] = ks_new
        new_cache["v_scale"] = vs_new
    if pad is not None:
        new_cache["pad"] = pad
    return logits.astype(jnp.float32), new_cache


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
    if isinstance(attention_mask, jax.core.Tracer):
        # under an outer jit/vmap/scan the mask is a tracer — host
        # validation is impossible there; inline the jitted program as the
        # pre-wrapper generate() did
        return _generate(cfg, params, input_ids, max_new_tokens,
                         temperature, rng, top_k, top_p, repetition_penalty,
                         attention_mask, kv_cache_dtype)
    if attention_mask is not None:
        # int cast first: np.diff on a BOOL array is XOR (always >= 0), so
        # a bool right-padded mask would sail through the guard
        mask_np = np.asarray(attention_mask, dtype=np.int32)
        if not (np.diff(mask_np, axis=1) >= 0).all():
            raise ValueError(
                "generate() requires LEFT-padded prompts: every "
                "attention_mask row must be non-decreasing (0s then 1s). "
                "Re-tokenize with padding_side='left'.")
        if mask_np.all():
            # uniform batch: dropping the mask keeps the Pallas decode
            # kernel engaged (per-sample masks force the jnp fallback)
            attention_mask = None
        else:
            attention_mask = jnp.asarray(mask_np)
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
    # round the workspace up to a decode-kernel-friendly block multiple
    # (positions past the logical max are masked, never attended).
    # kv_cache_dtype="int8": half the KV HBM (2x context/batch capacity),
    # dequant-on-read attention — see init_cache.
    kv_dtype = jnp.int8 if kv_cache_dtype == "int8" else None
    padded_len = padded_cache_len(max_len)
    cache = init_cache(cfg, B, padded_len, dtype=kv_dtype,
                       pad_lens=pad_lens)
    # static routing hint for the decode kernel: batched long generation
    # (most of the preallocated cache dead through the run) is its regime
    prefer_kernel = (B >= 2 and padded_len >= 4 * 512
                     and T_in <= padded_len // 2)
    # the first forward runs against the freshly-initialized (empty) cache:
    # prefill attention rides the flash kernel where eligible
    logits, cache = forward_with_cache(cfg, params, input_ids, cache,
                                       prefer_kernel=prefer_kernel,
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
        logits, cache = forward_with_cache(cfg, params, tok[:, None], cache,
                                           prefer_kernel=prefer_kernel)
        rng, r = jax.random.split(rng)
        nxt, seen = pick(logits[:, -1], seen, r)
        return (nxt, cache, rng, seen), tok

    (last, _, _, _), toks = jax.lax.scan(
        step, (tok, cache, rng, seen), None, length=max_new_tokens - 1)
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)  # [B, max_new]
    return jnp.concatenate([input_ids, out], axis=1)
