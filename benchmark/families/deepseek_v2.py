"""DeepSeek-V2 family (HF ``model_type`` ``deepseek_v2``: multi-head latent
attention, YaRN rotary, a group-limited softmax router, shared experts).

``config.json`` gives the sizes; what it does not say (the rope's lane order,
ties of equal group maxima) is the published ``modeling_deepseek.py`` as
remembered, marked ``assumed`` in the configuration file. For ``x [S, 5120]``,
``rms`` = RMSNorm with ``rms_norm_eps``::

    h    = rms(x, input_layernorm)
    q    = rms(h Wqa, q_a_layernorm[1536]) Wqb   -> [S,128,192] = q_nope[128] | q_pe[64]
    ckv  = h Wkva                                 -> [S,576]     = c[512] | k_pe[64] (ONE head)
    c    = rms(c, kv_a_layernorm[512])
    q_pe, k_pe = rope(q_pe), rope(k_pe)     HF's form: de-interleave the 64 lanes
                                            (view d/2 x 2, transpose), then rotate_half
      inv_freq (YaRN): extra_i = theta^(-2i/64), inter_i = extra_i / factor;
        low, high = floor / ceil of 64 ln(4096 / (beta 2 pi)) / (2 ln theta) at
        beta_fast, beta_slow; ramp_i = clip((i - low) / (high - low), 0, 1);
        inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i); cos/sin scaled by
        mscale(factor, mscale) / mscale(factor, mscale_all_dim) (= 1 here)
    k_nope | v = c Wkvb                           -> [S,128,128] each
    scale = 192^-0.5 mscale(factor, mscale_all_dim)^2, mscale(s, m) = 0.1 m ln s + 1
    a    = softmax_causal(scale (q_nope . k_nope + q_pe . k_pe)) float32;  o = a v
    x    = x + o Wo
    m    = down(silu(gate(g)) up(g)), g = rms(x, post_attention_layernorm)   a dense layer
         | s = softmax(g Wr) float32 [S, E]; G = max of s over each of n_group
           groups; keep the topk_group groups of largest G; s' = s where its
           group is kept, else 0; e = top_k(s'); w = routed_scaling_factor s[e]
           (norm_topk_prob false: no renormalisation)
           sum_j w_j down_ej(silu(gate_ej(g)) up_ej(g)) [picks held here]
           + down_s(silu(gate_s(g)) up_s(g))    [n_shared_experts as one MLP]
    x    = x + m;   logits = rms(x_L, norm) W_head

This reference is the EXPANDED form only: every cached latent goes through
``Wkvb`` and attention runs at 192/128-wide heads, so the program's absorbed
path (``Wkvb`` folded into the query and the output, 128 heads as the rows
of one tile over the 576-wide stored row) is held to different arithmetic.
Float32, ``highest`` precision, no cache, no kernel: heads in groups of
``HEADS`` and query rows in blocks of ``ROWS`` (``lax.map``), so that the
scores ``[heads, rows, S]`` of a 24 704-position sequence fit the chip beside
a layer in float32. Every held expert runs on every token and is masked. It
shares nothing with ``deepspeed_tpu/``.

**A chip's share** (``deployment``: ``router_outputs`` E, ``experts_held``
``[first, count]``, whole routing groups): the tree holds ``count`` experts a
sparse layer, the router keeps its E outputs, its groups and its k picks, and
the sum runs over the picks held here. A token none of whose kept groups is
held adds the shared experts only. Without the key every expert is held: the
uncut layer, which the eight shares of ``tests`` add up to.

**The picks' deficits** (``reference_logits(..., picks=)``). A pick's
deficit is the larger of (a) how far its group's best logit lies under the
reference's ``topk_group``-th best group maximum, and (b) how far its logit
lies under the k-th best among the groups the program kept, which are the
groups of its picks completed by the reference's best other groups: a
program that kept another group on a tie of group maxima dips deeper into
the groups both kept, and is held to ITS candidates, not the reference's
(the softmax ranks experts, and a group's best, as the logits do). Both are
in RAW router logits, OLMoE's unit (this router too reads a normed input:
the spread of a token's 160 logits is 0.99 on the chip), and are handed to
the driver's comparison with ``reference.ROUTE_TIE_TOL`` (not this file's
to change) multiplied by ``ROUTE_TIE_TOL / PICK_DEFICIT_LIMIT``: a pick may
lie :data:`PICK_DEFICIT_LIMIT` = 0.25 of a logit under the reference's
own, not OLMoE's 0.1. Why not 0.1: that tolerance was sized
where bf16 puts 0.4% of rounding into a block (PR 42: honest deficits
0.019-0.039 of a logit). A latent-attention block rounds ELEVEN tensors
between the normed input and the branch's output where an MHA block rounds
five (``h``; the query latent, its norm, the query; the KV latent, its norm;
the absorbed query; the probabilities; the attended latent, the head
outputs, the projection: 0.14-0.29% each, 0.64% together, read stage by
stage on a CPU twin), and YaRN's ``mscale^2`` makes its softmax 1.59 times
sharper, so the program's router input lies 1.3% (first sparse layer) to
2.1% (fourth) off the float32 reference's (CPU bf16 readings at a mid size)
and honest flips reach 0.11-0.125 of a logit on the chip (the first run
0.1249, ``correct: false`` against 0.1 on a sound program, 97.7% of a
million picks the reference's own; by layer 0.05, 0.07, 0.08, 0.11-0.125,
growing with that noise, the 24 576-token request's 0.090 no larger than a
1 000-token one's 0.111; the group part and the score part alike). None of
the eleven is this program's choice (each is a module boundary of the
published model served in bf16; the two that a float32 intermediate would
save are a tenth of the whole). **The limit lies between two chip readings
at the cell's own widths** (``PERF.md`` section 6, PR 49; raw logits, the
largest over a request's picks): honest 0.062-0.125; and the smallest any
planted fault read, 0.84: a router that takes the seventh-best expert for
the sixth 0.84-1.26, one that keeps the fourth-best group for the third
0.94-1.36, one with no group limit 1.12-1.42, the float8 reference's own
picks (the control's) 0.95-1.25, another token's picks 4.5-5.6. What 0.25
still admits: ONE pick up to a quarter of a logit under the sixth-best,
which at this router's spacing (the seventh lies 0.09 under the sixth at
the median token) is the seventh- to ninth-ranked expert of a kept group;
a router that errs so at every token reads 0.84 and more at some token of
every request. A row marked -1 reads 0.

Config keys (HF ``config.json`` names): ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``first_k_dense_replace``, ``moe_layer_freq``, ``n_routed_experts`` (HELD
here), ``n_shared_experts``, ``num_experts_per_tok``, ``n_group``,
``topk_group``, ``topk_method``, ``scoring_func``, ``norm_topk_prob``,
``routed_scaling_factor``, ``rms_norm_eps``, ``rope_theta``, ``rope_scaling``,
``max_position_embeddings``, ``vocab_size``, ``tie_word_embeddings``,
``hidden_act``, ``attention_bias``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as ref
from benchmark.families.exaone_moe import swiglu

#: query rows a block of the reference takes, and heads a group
ROWS, HEADS = 128, 16

#: how far under the reference's own a pick may lie, in raw router logits
#: (module docstring: between the chip's honest 0.125 and the smallest
#: planted fault's 0.84)
PICK_DEFICIT_LIMIT = 0.25


def share(c: Dict[str, Any]) -> Tuple[int, int, int]:
    """``(the router's outputs, the first expert held, how many)``."""
    d = c.get("deployment")
    if d is None:
        return c["n_routed_experts"], 0, c["n_routed_experts"]
    first, count = d["experts_held"]
    if count != c["n_routed_experts"]:
        raise ValueError(f"n_routed_experts {c['n_routed_experts']} is the "
                         f"count HELD; deployment.experts_held says {count}")
    return d["router_outputs"], first, count


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def softmax_scale(c: Dict[str, Any]) -> float:
    rs = c["rope_scaling"]
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 \
        * mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def yarn_inv_freq(c: Dict[str, Any]) -> np.ndarray:
    """The rotary frequencies ``[qk_rope_head_dim / 2]`` (module docstring)."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], \
        float(c["rope_theta"])
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turn = lambda beta: dim * np.log(
        rs["original_max_position_embeddings"] / (beta * 2 * np.pi)) / (
        2 * np.log(base))
    low = max(int(np.floor(turn(rs["beta_fast"]))), 0)
    high = min(int(np.ceil(turn(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / rs["factor"] * ramp + extra * (1 - ramp)).astype(
        np.float32)


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    rs = c["rope_scaling"]
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"] \
            or c["attention_bias"]:
        raise ValueError("deepseek_v2 family: silu SwiGLU, an untied head, "
                         "no attention bias")
    if c["scoring_func"] != "softmax" \
            or c["topk_method"] != "group_limited_greedy" \
            or c["moe_layer_freq"] != 1:
        raise ValueError("deepseek_v2 family: a softmax router, "
                         "group_limited_greedy, every later layer a mixture")
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("deepseek_v2 family: YaRN rotary whose cos/sin "
                         "scale is 1 (mscale == mscale_all_dim)")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("deepseek_v2 family: latent attention has no KV "
                         "heads (num_key_value_heads == num_attention_heads)")
    router, first, count = share(c)
    return dict(vocab_size=c["vocab_size"],
                max_seq_len=c["max_position_embeddings"],
                hidden_size=c["hidden_size"],
                num_layers=c["num_hidden_layers"],
                num_heads=c["num_attention_heads"],
                kv_lora_rank=c["kv_lora_rank"], q_lora_rank=c["q_lora_rank"],
                qk_nope_head_dim=c["qk_nope_head_dim"],
                qk_rope_head_dim=c["qk_rope_head_dim"],
                v_head_dim=c["v_head_dim"],
                mlp_dim_override=c["moe_intermediate_size"],
                dense_layers=c["first_k_dense_replace"],
                dense_mlp_dim=c["intermediate_size"],
                layer_norm_eps=c["rms_norm_eps"], norm="rmsnorm",
                gated_mlp=True, activation="silu", pos_embed="rotary",
                # the pairs (2i, 2i+1) turn together; the program keeps them
                # interleaved where HF de-interleaves first (the same dot
                # products: q and k are permuted alike)
                rotary_interleaved=True, rope_theta=float(c["rope_theta"]),
                rope_scaling_type="yarn",
                rope_scaling_factor=float(rs["factor"]),
                rope_beta_fast=float(rs["beta_fast"]),
                rope_beta_slow=float(rs["beta_slow"]),
                rope_mscale=float(rs["mscale"]),
                rope_mscale_all_dim=float(rs["mscale_all_dim"]),
                rope_original_max_position=rs[
                    "original_max_position_embeddings"],
                use_bias=False, tie_embeddings=False,
                moe_experts=router, moe_k=c["num_experts_per_tok"],
                moe_held=None if count == router else (first, count),
                moe_dropless=True, moe_norm_topk=bool(c["norm_topk_prob"]),
                moe_scores="softmax",
                moe_routed_scale=float(c["routed_scaling_factor"]),
                moe_groups=c["n_group"], moe_topk_groups=c["topk_group"],
                moe_shared_dim=c["n_shared_experts"]
                * c["moe_intermediate_size"],
                moe_aux_weight=0.0)


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """``experts`` and ``mlp_dim`` are the HELD experts and one expert's
    width (what ``moe_cost`` takes); ``kv_heads`` the heads a page stores
    (one row, no heads); the latent's sizes for ``mla_cost``."""
    return dict(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"], kv_heads=1,
                head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                mlp_dim=c["moe_intermediate_size"], mlp_matrices=3,
                vocab=c["vocab_size"], experts=share(c)[2],
                experts_per_token=c["num_experts_per_tok"],
                router_outputs=share(c)[0],
                dense_layers=c["first_k_dense_replace"],
                dense_mlp_dim=c["intermediate_size"],
                shared_dim=c["n_shared_experts"] * c["moe_intermediate_size"],
                kv_lora_rank=c["kv_lora_rank"],
                qk_nope_head_dim=c["qk_nope_head_dim"],
                qk_rope_head_dim=c["qk_rope_head_dim"],
                v_head_dim=c["v_head_dim"])


def rope(x, inv_freq):
    """HF's form on ``x [S, n, d]`` at positions 0..S-1: de-interleave the
    lanes (view ``d/2 x 2``, transpose), then rotate_half."""
    S, n, d = x.shape
    x = x.reshape(S, n, d // 2, 2).transpose(0, 1, 3, 2).reshape(S, n, d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def expanded_attention(p, h, *, heads: int, nope: int, rope_w: int, vw: int,
                       rank: int, eps: float, scale: float, inv_freq):
    """The attention branch's output ``[S, hidden]`` from the normed input
    ``h [S, hidden]``: every latent expanded through ``Wkvb``, heads in
    groups of :data:`HEADS`, query rows in blocks of :data:`ROWS``."""
    S = h.shape[0]
    qa = ref.rms_norm(h @ p["attn_q_a"]["kernel"], p["q_a_norm"]["scale"],
                      eps)
    ckv = h @ p["attn_kv_a"]["kernel"]
    c = ref.rms_norm(ckv[:, :rank], p["kv_a_norm"]["scale"], eps)
    k_pe = rope(ckv[:, None, rank:], inv_freq)                  # [S, 1, rope]
    G = HEADS if heads % HEADS == 0 else heads
    rows = ROWS if S % ROWS == 0 else S
    by_group = lambda w, lead: w.reshape(
        (lead, heads // G, G, -1)).transpose(1, 0, 2, 3)
    wq = by_group(p["attn_q_b"]["kernel"], qa.shape[1])
    wkv = by_group(p["attn_kv_b"]["kernel"], rank)
    wo = p["attn_proj"]["kernel"].reshape(heads // G, G, vw, -1)
    pos = jnp.arange(S)

    def head_group(out, w):
        wq_g, wkv_g, wo_g = w
        q = jnp.einsum("sr,rgd->sgd", qa, wq_g)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv_freq)],
                            axis=-1)
        kv = jnp.einsum("sc,cgd->sgd", c, wkv_g)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, (S, G, rope_w))], axis=-1)
        v = kv[..., nope:]

        def block(args):
            row0, q_b = args
            s = jnp.einsum("rgd,sgd->grs", q_b, k) * scale
            keep = pos[None, :] <= (row0 + jnp.arange(rows))[:, None]
            a = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("grs,sgd->rgd", a, v)

        o = jax.lax.map(block, (jnp.arange(S // rows) * rows,
                                q.reshape(S // rows, rows, G, -1)))
        return out + jnp.einsum("sgd,gdh->sh", o.reshape(S, G, vw), wo_g), \
            None

    out, _ = jax.lax.scan(head_group, jnp.zeros_like(h), (wq, wkv, wo))
    return out


def logit_deficit(z, picks, groups: int, keep: int) -> jnp.ndarray:
    """``[S, k]`` in units of ``PICK_DEFICIT_LIMIT / ROUTE_TIE_TOL`` raw
    logits (module docstring): the larger of how far a pick's group maximum
    lies under the ``keep``-th best group maximum and how far its logit lies
    under the k-th best among the groups the program kept; 0 for a row
    marked -1."""
    S, E = z.shape
    k, size = picks.shape[-1], E // groups
    at = jnp.maximum(picks, 0)
    gmax = jnp.max(z.reshape(S, groups, size), axis=-1)          # [S, groups]
    under_group = jax.lax.top_k(gmax, keep)[0][:, -1:] \
        - jnp.take_along_axis(gmax, at // size, axis=-1)
    # the program's kept groups: its picks', then the reference's best others
    theirs = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], at // size].set(True)
    kept = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None],
        jax.lax.top_k(jnp.where(theirs, jnp.inf, gmax), keep)[1]].set(True)
    kth = jax.lax.top_k(jnp.where(jnp.repeat(kept, size, axis=1), z,
                                  -jnp.inf), k)[0][:, -1:]
    under_score = kth - jnp.take_along_axis(z, at, axis=-1)
    return jnp.where(picks >= 0, jnp.maximum(
        jnp.maximum(under_group, under_score), 0.0), 0.0) \
        * (ref.ROUTE_TIE_TOL / PICK_DEFICIT_LIMIT)


def reference_router(gate_kernel, g, *, k: int, groups: int, keep: int,
                     renorm: bool, scale: float, picks=None):
    """``(scores [S, E], weights [S, k], picks [S, k], deficit [S, k])`` in
    float32. With ``picks`` (the program's, -1 where it has none) the layer
    routes by them, with weights from THIS router's softmax there."""
    z = g @ gate_kernel
    s = jax.nn.softmax(z, axis=-1)
    S, E = s.shape
    gmax = jnp.max(s.reshape(S, groups, E // groups), axis=-1)
    kept = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], jax.lax.top_k(gmax, keep)[1]].set(True)
    _, own = jax.lax.top_k(jnp.where(jnp.repeat(kept, E // groups, axis=1),
                                     s, 0.0), k)
    deficit = jnp.zeros(own.shape, jnp.float32)
    if picks is not None:
        deficit = logit_deficit(z, picks, groups, keep)
        own = ref.pinned_picks(own, picks)
    weights = jnp.take_along_axis(s, own, axis=-1)
    if renorm:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return s, weights * scale, own, deficit


def reference_moe(moe, g, *, k: int, groups: int, keep: int, renorm: bool,
                  scale: float, first: int = 0, picks=None,
                  shared: bool = True):
    """One sparse layer's mixture on ``g [S, hidden]`` from the program's
    ``moe`` subtree (``gate/kernel [hidden, E]``, ``experts/{gate,fc,proj}/
    kernel [held, in, out]``: experts ``first ..`` of the E, ``shared/...``),
    float32: every held expert on every token, kept where the router picked
    it (with ``picks``: where the program did); a pick of an expert not held
    adds nothing. Returns ``(y, scores, picks, deficit)``."""
    s, weights, picks, deficit = reference_router(
        moe["gate"]["kernel"], g, k=k, groups=groups, keep=keep,
        renorm=renorm, scale=scale, picks=picks)
    ex = moe["experts"]
    y = jnp.zeros_like(g)
    for e in range(ex["fc"]["kernel"].shape[0]):
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(g, ex["gate"]["kernel"][e],
                                      ex["fc"]["kernel"][e],
                                      ex["proj"]["kernel"][e])
    if shared and "shared" in moe:
        sh = moe["shared"]
        y = y + swiglu(g, sh["gate"]["kernel"], sh["fc"]["kernel"],
                       sh["proj"]["kernel"])
    return y, s, picks, deficit


@functools.lru_cache(maxsize=None)
def _step(dense: bool, heads: int, nope: int, rope_w: int, vw: int, rank: int,
          eps: float, scale: float, inv_freq: Tuple[float, ...], k: int,
          groups: int, keep: int, renorm: bool, routed_scale: float,
          first: int):
    """One layer of that kind as a jitted step ``(p, x[, picks]) -> (x,
    deficit [S, k] or None)``."""
    inv = jnp.asarray(inv_freq, jnp.float32)

    def block(p, x, picks=None):
        x = x + expanded_attention(
            p, ref.rms_norm(x, p["ln1"]["scale"], eps), heads=heads,
            nope=nope, rope_w=rope_w, vw=vw, rank=rank, eps=eps, scale=scale,
            inv_freq=inv)
        g = ref.rms_norm(x, p["ln2"]["scale"], eps)
        if dense:
            return x + swiglu(g, p["mlp_gate"]["kernel"],
                              p["mlp_fc"]["kernel"],
                              p["mlp_proj"]["kernel"]), None
        m, _, _, deficit = reference_moe(
            p["moe"], g, k=k, groups=groups, keep=keep, renorm=renorm,
            scale=routed_scale, first=first, picks=picks)
        return x + m, deficit

    return ref.layer_step(block)


def reference_logits(c: Dict[str, Any], params, ids, picks=None):
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (``dense_blocks`` stacked by leading dense
    layer, ``blocks`` by sparse layer).

    With ``picks [S, sparse layers, k]`` (the experts the PROGRAM picked for
    each fed token in each sparse layer, ids over the router's outputs, held
    here or not; -1 where it has none) every sparse layer routes by them and
    the result is ``(logits, deficits [S, sparse layers, k])``, each in
    :func:`logit_deficit`'s unit, in which ``reference.ROUTE_TIE_TOL`` is
    :data:`PICK_DEFICIT_LIMIT` of a raw logit."""
    eps, dense_n = float(c["rms_norm_eps"]), c["first_k_dense_replace"]
    f32 = lambda a: a.astype(jnp.float32)
    step = functools.partial(
        _step, heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
        rope_w=c["qk_rope_head_dim"], vw=c["v_head_dim"],
        rank=c["kv_lora_rank"], eps=eps, scale=softmax_scale(c),
        inv_freq=tuple(float(f) for f in yarn_inv_freq(c)),
        k=c["num_experts_per_tok"], groups=c["n_group"],
        keep=c["topk_group"], renorm=bool(c["norm_topk_prob"]),
        routed_scale=float(c["routed_scaling_factor"]), first=share(c)[1])
    deficits = []
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"])[ids]
        for li in range(c["num_hidden_layers"]):
            dense = li < dense_n
            stack, at = (params["dense_blocks"], li) if dense \
                else (params["blocks"], li - dense_n)
            p = jax.tree.map(lambda a: a[at], stack)
            more = () if dense or picks is None else (picks[:, at],)
            x, d = step(dense=dense)(p, x, *more)
            if not dense:
                deficits.append(d)
        x = ref.rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
    if picks is None:
        return logits
    return logits, jnp.stack(deficits, axis=1)
