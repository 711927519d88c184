"""EXAONE-MoE family (HF ``model_type`` ``exaone_moe``: K-EXAONE-236B-A23B).

``config.json`` gives the sizes and the layer pattern; what it does not say
is taken from the family's published EXAONE 4.0 modelling code and from the
DeepSeek-V3 router its mixture's keys are named after, and is marked
``assumed`` here and in the configuration file. For ``x [S, hidden]``, layer
``l``, ``rms`` = RMSNorm with ``rms_norm_eps``::

    q, k, v = x Wq, x Wk, x Wv     -> q [S, heads, hd], k, v [S, kv, hd]
                                      (no bias; NO input norm: assumed)
    q, k = rms(q, q_norm[hd]), rms(k, k_norm[hd])    per head (assumed)
    if layer_types[l] == "sliding_attention":
        q, k = rotate_half(q, k; rope_theta)   (a full layer carries no
                                                positions: assumed)
    o  = causal attention, GQA; on a sliding layer key j is seen by query i
         iff 0 <= i - j < sliding_windows[l]
    x  = x + rms(o Wo, post_attention_layernorm)   (norm on the branch's
                                                    OUTPUT: assumed)
    m  = down(silu(gate(x)) * up(x))               mlp_layer_types[l] "dense"
       | s = sigmoid(x Wr) float32 [S, E];  c = s + e_score_correction_bias
         e = top_k(c);  w = s[e]                (the bias selects, it never
         w = routed_scaling_factor * w / (sum_j w_j + 1e-20)  weighs: assumed)
         sum_j w_j down_{e_j}(silu(gate_{e_j}(x)) * up_{e_j}(x))
           + down_s(silu(gate_s(x)) * up_s(x))  the shared expert    "sparse"
    x  = x + rms(m, post_feedforward_layernorm)
    logits = rms(x_L, norm) W_head

**A chip's share.** With ``deployment`` in the configuration (``router_outputs``
E, ``experts_held`` ``[first, count]``) the parameter tree holds ``count``
experts a sparse layer, the router keeps its E outputs and its k picks, and
``sum_j`` runs over the picks whose expert is held; ``w`` is still
normalised over all k. What the absent experts would have added is left out,
here as in the program, and that partial result goes on to the next layer.
Without the key every expert is held: the uncut layer, which the shares of
``tests`` add up to.

**Departures**, each on purpose: ``num_nextn_predict_layers`` must be 0 (the
multi-token-prediction layer drafts token t+2 from the last hidden state and
changes no logit above); ``n_group`` and ``topk_group`` must be 1 (no groups:
the published values).

The reference computes every held expert on every token and masks (no sort,
no grouped matmul, no cache): it shares nothing with ``deepspeed_tpu/moe/``.

**The picks' deficits** (``reference_logits(..., picks=)``) are in units of
the router's LOGIT SPREAD at that token: how far a pick's logit ``z`` would
have to rise for ``sigmoid(z) + bias`` to reach this router's own k-th best
``s + bias``, over the standard deviation of the token's 128 logits (0 where
the reference picks that expert too). Two choices, both so that
``reference.ROUTE_TIE_TOL`` (0.1) admits here what it admits for OLMoE's
softmax router, whose deficits are logits of unit spread. (1) Logits and not
scores: the sigmoid's slope is at most 1/4 (0.15 at the k-th best of 128
unit-spread logits), so 0.1 of a SCORE would be 0.4-0.7 of a logit. (2) Over
the spread: this layer has no norm on its input, so the router reads the
residual stream itself, whose size is not 1 and grows with depth (under the
driver's draw every branch adds an output of unit rms: the logits' spread is
1.5 at the first sparse layer, 2.1, 2.5 and 2.9 at the fourth, read on the chip,
PERF.md section 6, PR 43), where OLMoE's router reads a normed input and its
logits' spread is 1 by construction. The program's bf16 rounding of that
stream is relative, so the logit error it causes, and the honest deficits,
grow with the spread (raw: 0.089-0.123 over seven seeds, over the tolerance
in five); a tie is a tie relative to the scores' spread.

Config keys (HF ``config.json`` names): ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size`` (a dense layer's width),
``moe_intermediate_size`` (one expert's), ``first_k_dense_replace``,
``layer_types``, ``mlp_layer_types``, ``sliding_windows`` (each whole as
published; the first ``num_hidden_layers`` entries run), ``num_experts``
(HELD here), ``num_experts_per_tok``, ``num_shared_experts``,
``scoring_func``, ``norm_topk_prob``, ``routed_scaling_factor``, ``n_group``,
``topk_group``, ``num_nextn_predict_layers``, ``rms_norm_eps``,
``rope_parameters``, ``max_position_embeddings``, ``vocab_size``,
``tie_word_embeddings``, ``hidden_act``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as ref

#: the renormalisation's guard (DeepSeek-V3's modelling code)
NORM_EPS = 1e-20


def share(c: Dict[str, Any]) -> Tuple[int, int, int]:
    """``(the router's outputs, the first expert held, how many)``."""
    d = c.get("deployment")
    if d is None:
        return c["num_experts"], 0, c["num_experts"]
    first, count = d["experts_held"]
    if count != c["num_experts"]:
        raise ValueError(f"num_experts {c['num_experts']} is the count HELD; "
                         f"deployment.experts_held says {count}")
    return d["router_outputs"], first, count


def layer_kinds(c: Dict[str, Any]) -> List[Tuple[bool, int]]:
    """``(dense MLP?, window or 0)`` of each layer that runs."""
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    mlps, kinds = c["mlp_layer_types"][:L], c["layer_types"][:L]
    windows = c["sliding_windows"][:L]
    if mlps != ["dense"] * dense + ["sparse"] * (L - dense):
        raise ValueError("exaone_moe family: first_k_dense_replace dense "
                         "layers, then sparse ones")
    for kind, w in zip(kinds, windows):
        if (kind == "sliding_attention") != (w > 0) or \
                kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_types {kind!r} with window {w}")
    return [(m == "dense", int(w)) for m, w in zip(mlps, windows)]


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"]:
        raise ValueError("exaone_moe family: silu SwiGLU, an untied head")
    if c["scoring_func"] != "sigmoid" or c["n_group"] != 1 \
            or c["topk_group"] != 1:
        raise ValueError("exaone_moe family: a sigmoid router without groups")
    if c["num_nextn_predict_layers"]:
        raise ValueError("exaone_moe family: the multi-token-prediction "
                         "layer is not served (num_nextn_predict_layers 0)")
    if c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("exaone_moe family: plain rotary positions")
    kinds = layer_kinds(c)
    router, first, count = share(c)
    return dict(vocab_size=c["vocab_size"],
                max_seq_len=c["max_position_embeddings"],
                hidden_size=c["hidden_size"],
                num_layers=c["num_hidden_layers"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"],
                head_dim_override=c["head_dim"],
                mlp_dim_override=c["moe_intermediate_size"],
                dense_layers=c["first_k_dense_replace"],
                dense_mlp_dim=c["intermediate_size"],
                layer_norm_eps=c["rms_norm_eps"], norm="rmsnorm",
                gated_mlp=True, activation="silu", pos_embed="rotary",
                rotary_interleaved=False,
                rope_theta=float(c["rope_parameters"]["rope_theta"]),
                layer_rope=tuple(w > 0 for _, w in kinds),
                layer_windows=tuple(w for _, w in kinds),
                use_bias=False, tie_embeddings=False, qk_norm="head",
                pre_norm=False, post_block_norms=True,
                moe_experts=router, moe_k=c["num_experts_per_tok"],
                moe_held=None if count == router else (first, count),
                moe_dropless=True, moe_norm_topk=bool(c["norm_topk_prob"]),
                moe_scores="sigmoid", moe_select_bias=True,
                moe_routed_scale=float(c["routed_scaling_factor"]),
                moe_shared_dim=c["num_shared_experts"]
                * c["moe_intermediate_size"],
                moe_aux_weight=0.0)


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """``experts`` and ``mlp_dim`` are the HELD experts and one expert's
    width (what ``moe_cost`` takes); ``heads`` the heads a page stores."""
    kinds = layer_kinds(c)
    return dict(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                mlp_dim=c["moe_intermediate_size"], mlp_matrices=3,
                vocab=c["vocab_size"], experts=share(c)[2],
                experts_per_token=c["num_experts_per_tok"],
                router_outputs=share(c)[0],
                dense_layers=c["first_k_dense_replace"],
                dense_mlp_dim=c["intermediate_size"],
                shared_dim=c["num_shared_experts"]
                * c["moe_intermediate_size"],
                window_layers=sum(1 for _, w in kinds if w > 0),
                window=max(w for _, w in kinds))


def logit_deficit(z, bias, picks) -> jnp.ndarray:
    """``[S, k]``: how far the logit of each of ``picks [S, k]`` would have
    to rise for its selection score ``sigmoid(z) + bias`` to reach this
    router's own k-th best, in units of the token's logit spread (the module
    docstring says why); 0 for one of the reference's own picks and for a
    row marked -1."""
    k = picks.shape[-1]
    select = jax.nn.sigmoid(z) + bias
    kth = jax.lax.top_k(select, k)[0][:, -1:]
    at = jnp.maximum(picks, 0)
    need = jnp.clip(kth - bias[at], 1e-6, 1.0 - 1e-6)   # the score it needs
    z_need = jnp.log(need) - jnp.log1p(-need)
    short = jnp.maximum(z_need - jnp.take_along_axis(z, at, axis=-1), 0.0)
    # one of the reference's own picks reads 0 exactly, not the inverse's
    # rounding
    own = jnp.take_along_axis(select, at, axis=-1) >= kth
    return jnp.where((picks >= 0) & ~own, short, 0.0) \
        / jnp.std(z, axis=-1, keepdims=True)


def reference_router(gate, h, k: int, renorm: bool, scale: float,
                     picks=None):
    """``(scores [S, E], weights [S, k], picks [S, k], deficit [S, k])`` in
    float32 from ``gate`` (``kernel [hidden, E]``, ``bias [E]``). With
    ``picks`` (the program's, -1 where it has none) the layer routes by
    them: the weights are THIS router's sigmoid scores at those experts,
    renormalised over all k and scaled as the config says."""
    z = h @ gate["kernel"]
    s = jax.nn.sigmoid(z)
    _, own = jax.lax.top_k(s + gate["bias"], k)
    deficit = jnp.zeros(own.shape, jnp.float32)
    if picks is not None:
        deficit = logit_deficit(z, gate["bias"], picks)
        own = ref.pinned_picks(own, picks)
    weights = jnp.take_along_axis(s, own, axis=-1)
    if renorm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + NORM_EPS)
    return s, weights * scale, own, deficit


def swiglu(h, gate, up, down):
    return (ref.silu(h @ gate) * (h @ up)) @ down


def reference_moe(moe, h, *, k: int, renorm: bool, scale: float,
                  first: int = 0, picks=None, shared: bool = True):
    """One sparse layer's mixture on ``h [S, hidden]`` from the program's
    ``moe`` subtree (``gate/{kernel [hidden, E], bias [E]}``, ``experts/
    {gate,fc,proj}/kernel [held, in, out]``: experts ``first ..`` of the E,
    ``shared/{gate,fc,proj}/kernel``), all float32: every held expert on
    every token, kept where the router picked it (or, with ``picks``, where
    the program did); a pick of an expert not held adds nothing. Returns
    ``(y, scores, picks, deficit)``."""
    s, weights, picks, deficit = reference_router(moe["gate"], h, k, renorm,
                                                  scale, picks)
    ex = moe["experts"]
    y = jnp.zeros_like(h)
    for e in range(ex["fc"]["kernel"].shape[0]):
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(h, ex["gate"]["kernel"][e],
                                      ex["fc"]["kernel"][e],
                                      ex["proj"]["kernel"][e])
    if shared and "shared" in moe:
        sh = moe["shared"]
        y = y + swiglu(h, sh["gate"]["kernel"], sh["fc"]["kernel"],
                       sh["proj"]["kernel"])
    return y, s, picks, deficit


@functools.lru_cache(maxsize=None)
def _step(dense: bool, window: int, heads: int, kv_heads: int, head_dim: int,
          eps: float, theta: float, k: int, renorm: bool, scale: float,
          first: int):
    """One layer of that kind as a jitted step ``(p, x[, picks]) -> (x,
    (scores, picks, deficit) or None)``."""
    def block(p, x, picks=None):
        S = x.shape[0]
        qkv = x @ p["attn_qkv"]["kernel"]
        q, kk, v = jnp.split(qkv, [heads * head_dim,
                                   (heads + kv_heads) * head_dim], axis=-1)
        q = ref.rms_norm(q.reshape(S, heads, head_dim),
                         p["q_norm"]["scale"], eps)
        kk = ref.rms_norm(kk.reshape(S, kv_heads, head_dim),
                          p["k_norm"]["scale"], eps)
        if window:
            q, kk = ref.rotary_half(q, theta), ref.rotary_half(kk, theta)
        a = ref.causal_attention(q, kk, v.reshape(S, kv_heads, head_dim),
                                 window)
        x = x + ref.rms_norm(a @ p["attn_proj"]["kernel"],
                             p["post_attn_norm"]["scale"], eps)
        routing = None
        if dense:
            m = swiglu(x, p["mlp_gate"]["kernel"], p["mlp_fc"]["kernel"],
                       p["mlp_proj"]["kernel"])
        else:
            m, *routing = reference_moe(p["moe"], x, k=k, renorm=renorm,
                                        scale=scale, first=first, picks=picks)
        return x + ref.rms_norm(m, p["post_mlp_norm"]["scale"], eps), routing

    return ref.layer_step(block)


def reference_logits_and_routing(c: Dict[str, Any], params, ids, picks=None):
    """The logits, and each sparse layer's ``(scores [S, E], picks [S, k],
    deficit [S, k])``; the layers route by ``picks`` where given."""
    _, first, _ = share(c)
    eps, dense_n = float(c["rms_norm_eps"]), c["first_k_dense_replace"]
    f32 = lambda a: a.astype(jnp.float32)
    routing = []
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"])[ids]
        for li, (dense, window) in enumerate(layer_kinds(c)):
            step = _step(dense, window, c["num_attention_heads"],
                         c["num_key_value_heads"], c["head_dim"], eps,
                         float(c["rope_parameters"]["rope_theta"]),
                         c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
                         float(c["routed_scaling_factor"]), first)
            stack, at = (params["dense_blocks"], li) if dense \
                else (params["blocks"], li - dense_n)
            p = jax.tree.map(lambda a: a[at], stack)
            more = () if dense or picks is None else (picks[:, at],)
            x, r = step(p, x, *more)
            if not dense:
                routing.append(r)
        x = ref.rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        return x @ f32(params["lm_head"]["kernel"]), routing


def reference_logits(c: Dict[str, Any], params, ids, picks=None):
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (``dense_blocks`` stacked by leading dense
    layer, ``blocks`` by sparse layer).

    With ``picks [S, sparse layers, k]`` (the experts the PROGRAM picked for
    each token in each sparse layer, ids over the router's outputs, held here
    or not; -1 where it has none) every sparse layer routes by them, with
    weights from its OWN sigmoid scores there, and the result is ``(logits,
    deficits [S, sparse layers, k])``: the deficits in units of the router's
    logit spread (:func:`logit_deficit`), held to
    ``reference.ROUTE_TIE_TOL``."""
    logits, routing = reference_logits_and_routing(c, params, ids, picks)
    if picks is None:
        return logits
    return logits, jnp.stack([r[2] for r in routing], axis=1)
