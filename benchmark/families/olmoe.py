"""OLMoE family (HF ``OlmoeForCausalLM``, ``modeling_olmoe.py``): pre-norm
blocks, RMSNorm, multi-head attention with an RMSNorm over the WHOLE projected
q and k vectors (before the head split), rotate-half rotary positions, and in
place of the MLP a mixture of ``num_experts`` SwiGLU experts of width
``intermediate_size``: a float32 softmax router picks ``num_experts_per_tok``
of them, their weights are NOT renormalised (``norm_topk_prob`` false),
nothing is dropped and there is no capacity and no shared expert. No biases,
untied head.

For ``x [S, hidden]``::

    h  = rmsnorm(x, input_layernorm)
    q  = rmsnorm(h Wq, q_norm);  k = rmsnorm(h Wk, k_norm);  v = h Wv
    q, k -> [S, heads, head_dim], rotary; x = x + causal_attention(q, k, v) Wo
    h2 = rmsnorm(x, post_attention_layernorm)
    p  = softmax(h2 Wr);  (w, e) = top_k(p)
    x  = x + sum_j w_j * down_{e_j}(silu(gate_{e_j}(h2)) * up_{e_j}(h2))

Training adds ``router_aux_loss_coef`` x HF's ``load_balancing_loss_func``:
``E * sum_e f_e P_e`` with ``f`` the share of tokens that picked expert e
(summed over the pick slots) and ``P`` the mean router probability, both over
ALL layers' tokens together.

Departures from the published model, each on purpose: the paper's router
z-loss (coefficient 0.001) is left out (the HF modelling code has none
either); ``clip_qkv`` is null in the published config and a non-null value is
refused; attention dropout is 0. The reference computes every expert for every
token and masks (no sort, no grouped matmul, no cache): it shares nothing with
``deepspeed_tpu/moe/``.

Config keys (HF ``config.json`` names): ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size`` (ONE expert's width), ``num_experts``,
``num_experts_per_tok``, ``norm_topk_prob``, ``router_aux_loss_coef``,
``rms_norm_eps``, ``rope_theta``, ``max_position_embeddings``, ``vocab_size``,
``tie_word_embeddings``, ``hidden_act``, ``attention_bias``, ``clip_qkv``,
``rope_scaling``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as ref


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"]:
        raise ValueError("olmoe family: silu SwiGLU experts, an untied head")
    if c["attention_bias"] or c["clip_qkv"] is not None \
            or c["rope_scaling"] is not None:
        raise ValueError("olmoe family: no attention bias, no clip_qkv, no "
                         "rope scaling (the published config has none)")
    return dict(vocab_size=c["vocab_size"],
                max_seq_len=c["max_position_embeddings"],
                hidden_size=c["hidden_size"],
                num_layers=c["num_hidden_layers"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"],
                mlp_dim_override=c["intermediate_size"],
                layer_norm_eps=c["rms_norm_eps"], norm="rmsnorm",
                gated_mlp=True, activation="silu", pos_embed="rotary",
                rotary_interleaved=False, rope_theta=float(c["rope_theta"]),
                use_bias=False, tie_embeddings=False, qk_norm="projection",
                moe_experts=c["num_experts"], moe_k=c["num_experts_per_tok"],
                moe_dropless=True, moe_norm_topk=bool(c["norm_topk_prob"]),
                moe_aux_weight=float(c["router_aux_loss_coef"]))


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                head_dim=c["hidden_size"] // c["num_attention_heads"],
                mlp_dim=c["intermediate_size"], mlp_matrices=3,
                vocab=c["vocab_size"], experts=c["num_experts"],
                experts_per_token=c["num_experts_per_tok"])


def reference_router(gate_kernel, h, k: int, renorm: bool, picks=None):
    """``(probs [S, E], weights [S, k], picks [S, k], deficit [S, k])`` in
    float32. With ``picks`` (the program's, -1 where it has none) the layer
    routes by them: the weights are THIS router's probabilities at those
    experts, renormalised over them if ``renorm``, and ``deficit`` is how far
    each pick's logit lies under this router's own k-th best
    (``reference.pick_deficit``; the softmax ranks as the logits do)."""
    logits = h @ gate_kernel
    probs = jax.nn.softmax(logits, axis=-1)
    weights, own = jax.lax.top_k(probs, k)
    deficit = jnp.zeros_like(weights)
    if picks is not None:
        deficit = ref.pick_deficit(logits, picks)
        own = ref.pinned_picks(own, picks)
        weights = jnp.take_along_axis(probs, own, axis=-1)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, own, deficit


def reference_moe(moe, h, k: int, renorm: bool, leave_out: int = -1,
                  picks=None):
    """The mixture of one layer on ``h [S, hidden]`` from the program's
    ``moe`` subtree (``gate/kernel [hidden, E]``, ``experts/{gate,fc,proj}/
    kernel [E, in, out]``), all float32: every expert on every token, kept
    where the router picked it (or, with ``picks``, where the program did:
    :func:`reference_router`). ``leave_out`` j drops every token's j-th
    pick (what a parity tolerance has to notice). Returns ``(y, probs,
    picks, deficit)``."""
    probs, weights, picks, deficit = reference_router(
        moe["gate"]["kernel"], h, k, renorm, picks)
    if leave_out >= 0:
        weights = weights.at[:, leave_out].set(0.0)
    ex = moe["experts"]
    y = jnp.zeros_like(h)
    for e in range(ex["fc"]["kernel"].shape[0]):
        w_e = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1)
        out = (ref.silu(h @ ex["gate"]["kernel"][e])
               * (h @ ex["fc"]["kernel"][e])) @ ex["proj"]["kernel"][e]
        y = y + w_e[:, None] * out
    return y, probs, picks, deficit


@functools.lru_cache(maxsize=None)
def _steps(heads: int, kv_heads: int, head_dim: int, eps: float,
           theta: float, k: int, renorm: bool):
    def block(p, x, picks=None):
        S = x.shape[0]
        h = ref.rms_norm(x, p["ln1"]["scale"], eps)
        qkv = h @ p["attn_qkv"]["kernel"]
        q, kk, v = jnp.split(qkv, [heads * head_dim,
                                   (heads + kv_heads) * head_dim], axis=-1)
        q = ref.rms_norm(q, p["q_norm"]["scale"], eps)
        kk = ref.rms_norm(kk, p["k_norm"]["scale"], eps)
        q = ref.rotary_half(q.reshape(S, heads, head_dim), theta)
        kk = ref.rotary_half(kk.reshape(S, kv_heads, head_dim), theta)
        a = ref.causal_attention(q, kk, v.reshape(S, kv_heads, head_dim))
        x = x + a @ p["attn_proj"]["kernel"]
        h2 = ref.rms_norm(x, p["ln2"]["scale"], eps)
        y, probs, picks, deficit = reference_moe(p["moe"], h2, k, renorm,
                                                 picks=picks)
        return x + y, (probs, picks, deficit)

    @jax.jit
    def embed(params, ids):
        return params["wte"]["embedding"].astype(jnp.float32)[ids]

    @jax.jit
    def head(params, x):
        x = ref.rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)

    return (embed, ref.layer_step(lambda p, x: block(p, x)[0]),
            ref.layer_step(block), head)


def _steps_of(c: Dict[str, Any]):
    d = dims(c)
    return d, _steps(d["heads"], d["kv_heads"], d["head_dim"],
                     float(c["rms_norm_eps"]), float(c["rope_theta"]),
                     d["experts_per_token"], bool(c["norm_topk_prob"]))


def reference_logits(c: Dict[str, Any], params, ids, picks=None):
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (scan layout: ``blocks`` stacked by layer).

    With ``picks [S, layers, k]`` (the experts the PROGRAM picked for each
    token in each layer, -1 where it has none) every layer routes by them
    and the result is ``(logits, deficits [S, layers, k])``: what the
    program computed is then held to the reference token for token, and its
    picks to the reference's own scores (``reference.ROUTE_TIE_TOL``)."""
    if picks is not None:
        logits, routing = reference_logits_and_routing(c, params, ids, picks)
        return logits, jnp.stack([r[2] for r in routing], axis=1)
    d, (embed, step, _, head) = _steps_of(c)
    with jax.default_matmul_precision("highest"):
        x = ref.walk_layers(step, params["blocks"], embed(params, ids),
                            d["layers"])
        return head(params, x)


def reference_logits_and_routing(c: Dict[str, Any], params, ids, picks=None
                                 ) -> Tuple[jnp.ndarray, list]:
    """The logits, and each layer's ``(probs [S, E], picks [S, k], deficit
    [S, k])``; the layers route by ``picks [S, layers, k]`` where given."""
    d, (embed, _, step, head) = _steps_of(c)
    routing = []
    with jax.default_matmul_precision("highest"):
        x = embed(params, ids)
        for li in range(d["layers"]):
            x, r = step(jax.tree.map(lambda a: a[li], params["blocks"]), x,
                        None if picks is None else picks[:, li])
            routing.append(r)
        return head(params, x), routing


def reference_train_loss(c: Dict[str, Any], params, batch) -> jnp.ndarray:
    """Causal-LM loss of ``batch [rows, S]`` (the mean over rows: each has
    S-1 targets) + ``router_aux_loss_coef`` x the load-balancing loss over
    every layer's tokens of the whole batch."""
    E, rows = c["num_experts"], []
    probs, picks = [], []
    for ids in batch:
        logits, routing = reference_logits_and_routing(c, params,
                                                       jnp.asarray(ids))
        rows.append(ref.next_token_nll(logits, jnp.asarray(ids)))
        probs += [r[0] for r in routing]
        picks += [r[1] for r in routing]
    probs, picks = jnp.concatenate(probs), jnp.concatenate(picks)
    f = jnp.mean(jax.nn.one_hot(picks, E, dtype=jnp.float32), axis=0)  # [k,E]
    aux = E * jnp.sum(f * jnp.mean(probs, axis=0)[None, :])
    return jnp.mean(jnp.stack(rows)) + float(c["router_aux_loss_coef"]) * aux
