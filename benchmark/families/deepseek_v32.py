"""DeepSeek-V3.2-Exp's language model (HF ``model_type`` ``deepseek_v32``):
multi-head latent attention whose keys a learned indexer picks (DeepSeek
Sparse Attention), YaRN rotary, a ``noaux_tc`` sigmoid router in groups, one
shared expert.

``config.json`` gives the sizes; what it does not say is ``inference/
model.py`` of the model's repository as remembered, marked ``assumed`` in the
configuration file. For ``x [S, 7168]``, ``rms`` = RMSNorm with
``rms_norm_eps``, position t and keys s <= t::

    h   = rms(x, attn_norm);  qr = rms(h Wqa, q_norm[1536])
    q   = qr Wqb                -> [S,128,192] = q_nope[128] | q_pe[64]
    ckv = h Wkva                -> [S,576]     = c[512] | k_pe[64] (ONE head)
    c   = rms(c, kv_norm[512]); q_pe, k_pe = rope(.)   the YaRN table, pairs
                                (2i, 2i+1) turning together (deepseek_v2.rope)
    qI  = qr WIq [S,64,128];  kI = LayerNorm(h WIk)[128] (scale AND bias)
    the first 64 lanes of every qI head and of kI turn by the SAME table,
      halves rotated; the other 64 do not
    w   = (h WIw)[64] x 64^-0.5 x 128^-0.5
    I(t,s) = sum_j w(t,j) relu(qI(t,j) . kI(s))                      float32
    T(t) = the min(index_topk, t+1) keys s <= t of largest I(t,s), equal
           scores to the lower position
    k_nope | v = c Wkvb         -> [S,128,128] each
    scale = 192^-0.5 mscale(40, 1)^2, mscale(s, m) = 0.1 m ln s + 1
    a   = softmax over s in T(t) of scale (q_nope . k_nope + q_pe . k_pe)
    x   = x + (a v) Wo
    m   = down(silu(gate(g)) up(g)), g = rms(x, ffn_norm)      a dense layer
        | s = sigmoid(g Wr) float32 [S,E];  s' = s + b  (selection only)
          G_n = the sum of the two largest s' in group n; keep the
          topk_group groups of largest G; e = top_k of s' over their experts
          w_j = routed_scaling_factor s[e_j] / (sum_j s[e_j] + 1e-20)
          sum_j w_j expert_ej(g) [picks held here] + shared(g)
    x   = x + m;   logits = rms(x_L, norm) W_head

**The program's head weights.** The program's ``index_w`` leaf holds ``WIw``
alone; the constant ``64^-0.5 x 128^-0.5`` is positive and the same for
every key of a row, so it changes no selection and is left out of both sides
(the configuration's ``assumed``).

**This reference is the EXPANDED form**, float32 at ``highest``, no cache, no
kernel, and shares nothing with ``deepspeed_tpu/``. The parameters stay as
they are stored (bfloat16 on the chip: 9.27 GB, which leave no room for a
sparse layer in float32 beside 24 704 positions) and are cast a projection,
a head group or an expert at a time: attention is one jitted step that takes
heads in groups of ``HEADS`` and query rows in blocks of ``ROWS``, the
selection ``[S, S]`` worked out ONCE a layer (as a mask) and shared by the
head groups; the mixture runs an expert a call.

**A chip's share** (``deployment``: ``router_outputs`` E, ``experts_held``
``[first, count]``, whole routing groups or an equal part of one): the tree
holds ``count`` experts a sparse layer, the router keeps its E outputs, its
groups and its k picks, the renormalisation runs over all k, and the sum over
the picks held here. Without the key every expert is held: the uncut layer,
which the sixteen shares of ``tests`` add up to.

**The reference follows the program's routing** (``reference_logits(...,
picks=)``, ``[S, layers, k + index_topk]`` as ``Request.routed_experts``
hands it out: a row a layer that has picks or a selection, a dense layer's
picks -1). The keys' deficits are Keye's (``keye_vl2.py``: the reference's
own index score of each selected key against its own topk-th best, over the
RANGE of the row's scores, held to ``reference.ROUTE_TIE_TOL``). A pick's
deficit is in router LOGITS (this router reads a normed input, the spread of
a token's logits is 1 under the driver's draw), the larger of (a) how far
apart, in logits, the sum of its group's two best biased scores lies from
the reference's ``topk_group``-th best sum (twice the shortfall over the two
groups' slopes ``sum s (1 - s)`` of their two best: where the logits of one
would rise and of the other fall to meet; with equal slopes, how far the
pick's two would have to rise alone. A shortfall over the pick's own slope
alone reads five times a tie's size where its group's two best are
saturated and the other's are not), and (b) how far its logit would have to rise for its biased score to
reach the k-th best among the experts of its picks' OWN groups (the
candidates the program is known to have had; ``deepseek_v2.py`` completes
them by the reference's best other groups, which a group ranked by two
scores does not bear: it may be kept and hold no pick), and
is handed over times ``ROUTE_TIE_TOL / PICK_DEFICIT_LIMIT``: a pick may lie
:data:`PICK_DEFICIT_LIMIT` of a logit under the reference's own (``PERF.md``
section 6, PR 52 has the two readings it stands between).

Config keys (HF ``config.json`` names): those of ``deepseek_v2.py`` and
``index_n_heads``, ``index_head_dim``, ``index_topk``,
``num_nextn_predict_layers`` (0: the MTP module changes no logit).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as ref
from benchmark.families.deepseek_v2 import (rope, share, softmax_scale,
                                            yarn_inv_freq)
from benchmark.families.exaone_moe import NORM_EPS
from benchmark.families.keye_vl2 import own_selection

#: query rows a block of the reference takes, and heads a group
ROWS, HEADS = 128, 8

#: how far under the reference's own a pick may lie, in router logits
#: (module docstring)
PICK_DEFICIT_LIMIT = 0.5

#: of the last ``reference_logits(..., picks=)``: a sparse layer's largest
#: (group part, score part) of its picks' deficits, for whoever sizes the
#: limit (``PERF.md`` section 6, PR 52)
DEFICIT_PARTS: list = []


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    rs = c["rope_scaling"]
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"] \
            or c["attention_bias"]:
        raise ValueError("deepseek_v32 family: silu SwiGLU, an untied head, "
                         "no attention bias")
    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
            or c["moe_layer_freq"] != 1:
        raise ValueError("deepseek_v32 family: a sigmoid router, noaux_tc, "
                         "every later layer a mixture")
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("deepseek_v32 family: YaRN rotary whose cos/sin "
                         "scale is 1 (mscale == mscale_all_dim)")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("deepseek_v32 family: latent attention has no KV "
                         "heads (num_key_value_heads == num_attention_heads)")
    if c["num_nextn_predict_layers"]:
        raise ValueError("deepseek_v32 family: num_nextn_predict_layers 0 "
                         "(the MTP module is not served)")
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
                index_heads=c["index_n_heads"],
                index_head_dim=c["index_head_dim"],
                index_topk=c["index_topk"],
                mlp_dim_override=c["moe_intermediate_size"],
                dense_layers=c["first_k_dense_replace"],
                dense_mlp_dim=c["intermediate_size"],
                layer_norm_eps=c["rms_norm_eps"], norm="rmsnorm",
                gated_mlp=True, activation="silu", pos_embed="rotary",
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
                moe_scores="sigmoid", moe_select_bias=True,
                moe_routed_scale=float(c["routed_scaling_factor"]),
                moe_groups=c["n_group"], moe_topk_groups=c["topk_group"],
                moe_shared_dim=c["n_shared_experts"]
                * c["moe_intermediate_size"],
                moe_aux_weight=0.0)


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """``experts`` and ``mlp_dim`` are the HELD experts and one expert's
    width (what ``moe_cost`` takes); ``kv_heads`` the heads a page stores
    (one row, no heads); the latent's sizes for ``mla_cost`` and the
    indexer's for ``sparse_cost``."""
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
                v_head_dim=c["v_head_dim"],
                index_heads=c["index_n_heads"],
                index_head_dim=c["index_head_dim"],
                index_topk=c["index_topk"])


f32 = lambda a: a.astype(jnp.float32)


def rope_halves(x, inv_freq, row0=0):
    """The first ``2 x len(inv_freq)`` lanes of ``x [S, n, d]`` turned at
    positions ``row0 .. row0 + S - 1``, halves rotated (lane i with lane i +
    len); the rest as they are."""
    S, half = x.shape[0], inv_freq.shape[0]
    ang = (row0 + jnp.arange(S)).astype(jnp.float32)[:, None] \
        * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def index_scores(qi, ki, w, row0, group: int = 16):
    """``I [rows, S]`` of the query rows ``row0 ..`` (``qi [rows, ih, iw]``,
    ``w [rows, ih]``) against every indexer key ``ki [S, iw]``, the heads
    ``group`` at a time; ``-inf`` where the key lies behind the row."""
    rows, ih, _ = qi.shape
    g = group if ih % group == 0 else ih

    def some(acc, args):
        q_g, w_g = args
        prod = jnp.einsum("rjd,sd->rjs", q_g, ki)
        return acc + jnp.sum(w_g[:, :, None] * jnp.maximum(prod, 0.0),
                             axis=1), None

    by_group = lambda a: jnp.moveaxis(
        a.reshape((rows, ih // g, g) + a.shape[2:]), 1, 0)
    scores, _ = jax.lax.scan(some, jnp.zeros((rows, ki.shape[0])),
                             (by_group(qi), by_group(w)))
    at = row0 + jnp.arange(rows)[:, None]
    # -0.0 (a negative weight times a zero) is the score 0.0
    scores = jnp.where(scores == 0.0, 0.0, scores)
    return jnp.where(jnp.arange(ki.shape[0])[None, :] <= at, scores,
                     -jnp.inf)


def selection(qr, h, p, inv_freq, *, ih: int, iw: int, topk: int, eps: float,
              picks=None, ignore: bool = False):
    """``(mask [S, S] bool: the keys each row attends, deficit [S, topk])``
    of one layer, in blocks of :data:`ROWS` rows (``keye_vl2.
    sparse_attention``'s selection and deficits: ``picks [S, topk]`` the
    positions the PROGRAM attended, -1 behind a row's count, a row of -1
    selects by itself). ``ignore``: every visible key (the tests' model
    without its indexer)."""
    S = h.shape[0]
    rows = ROWS if S % ROWS == 0 else S
    ki = rope_halves(ref.layer_norm(
        h @ f32(p["index_k"]["kernel"]), f32(p["index_k_norm"]["scale"]),
        f32(p["index_k_norm"]["bias"]), eps)[:, None], inv_freq)[:, 0]
    w = h @ f32(p["index_w"]["kernel"])
    wq = f32(p["index_q"]["kernel"])

    def block(args):
        row0, qr_b, w_b, picks_b = args
        qi = rope_halves((qr_b @ wq).reshape(rows, ih, iw), inv_freq, row0)
        scores = index_scores(qi, ki, w_b, row0)
        mask, kth = own_selection(scores, topk)
        visible = scores > -jnp.inf
        if ignore:
            mask = visible
        deficit = jnp.zeros((rows, topk), jnp.float32)
        if picks_b is not None:
            given = picks_b >= 0
            theirs = jnp.zeros((rows, S), bool).at[
                jnp.arange(rows)[:, None], jnp.where(given, picks_b, S)
            ].set(True, mode="drop")
            mask = jnp.where(given.any(axis=1, keepdims=True), theirs, mask)
            size = jnp.max(scores, axis=1, keepdims=True) - jnp.min(
                jnp.where(visible, scores, jnp.inf), axis=1, keepdims=True)
            got = jnp.take_along_axis(scores, jnp.maximum(picks_b, 0), axis=1)
            short = jnp.where(given & (kth > -jnp.inf),
                              jnp.maximum(kth - got, 0.0), 0.0)
            # a position the row cannot see is no tie
            short = jnp.where(given & (got == -jnp.inf), jnp.inf, short)
            deficit = short / jnp.maximum(size, 1e-30)
        return mask, deficit

    n = S // rows
    split = lambda a: a.reshape((n, rows) + a.shape[1:])
    mask, deficit = jax.lax.map(block, (
        jnp.arange(n) * rows, split(qr), split(w),
        None if picks is None else split(picks)))
    return mask.reshape(S, S), deficit.reshape(S, topk)


def expanded_attention(p, qr, c, k_pe, mask, *, heads: int, nope: int,
                       vw: int, scale: float, inv_freq):
    """The attention branch's output ``[S, hidden]``: every latent expanded
    through ``Wkvb``, heads in groups of :data:`HEADS` (their weights cast
    a group at a time), query rows in blocks of :data:`ROWS``, each row
    over the keys ``mask [S, S]`` gives it."""
    S, rank = c.shape
    G = HEADS if heads % HEADS == 0 else heads
    rows = ROWS if S % ROWS == 0 else S
    by_group = lambda w, lead: w.reshape(
        (lead, heads // G, G, -1)).transpose(1, 0, 2, 3)
    wq = by_group(p["attn_q_b"]["kernel"], qr.shape[1])
    wkv = by_group(p["attn_kv_b"]["kernel"], rank)
    wo = p["attn_proj"]["kernel"].reshape(heads // G, G, vw, -1)
    rope_w = k_pe.shape[-1]
    mask = mask.reshape(S // rows, rows, S)

    def head_group(out, w):
        wq_g, wkv_g, wo_g = (f32(a) for a in w)
        q = jnp.einsum("sr,rgd->sgd", qr, wq_g)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv_freq)],
                            axis=-1)
        kv = jnp.einsum("sc,cgd->sgd", c, wkv_g)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, (S, G, rope_w))], axis=-1)
        v = kv[..., nope:]

        def block(args):
            q_b, keep = args
            s = jnp.einsum("rgd,sgd->grs", q_b, k) * scale
            a = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("grs,sgd->rgd", a, v)

        o = jax.lax.map(block, (q.reshape(S // rows, rows, G, -1), mask))
        return out + jnp.einsum("sgd,gdh->sh", o.reshape(S, G, vw), wo_g), \
            None

    out, _ = jax.lax.scan(head_group, jnp.zeros((S, wo.shape[-1])),
                          (wq, wkv, wo))
    return out


@functools.lru_cache(maxsize=None)
def _attention_step(heads: int, nope: int, rope_w: int, vw: int, rank: int,
                    ih: int, iw: int, topk: int, eps: float, scale: float,
                    inv_freq, ignore: bool = False):
    """``(p, x[, key picks]) -> (x + the attention branch, key deficits)``,
    jitted; ``p`` as stored."""
    inv = jnp.asarray(inv_freq, jnp.float32)

    def step(p, x, picks=None):
        h = ref.rms_norm(x, f32(p["ln1"]["scale"]), eps)
        qr = ref.rms_norm(h @ f32(p["attn_q_a"]["kernel"]),
                          f32(p["q_a_norm"]["scale"]), eps)
        ckv = h @ f32(p["attn_kv_a"]["kernel"])
        c = ref.rms_norm(ckv[:, :rank], f32(p["kv_a_norm"]["scale"]), eps)
        k_pe = rope(ckv[:, None, rank:], inv)                   # [S, 1, rope]
        mask, deficit = selection(qr, h, p, inv, ih=ih, iw=iw, topk=topk,
                                  eps=eps, picks=picks, ignore=ignore)
        return x + expanded_attention(
            p, qr, c, k_pe, mask, heads=heads, nope=nope, vw=vw, scale=scale,
            inv_freq=inv), deficit

    return jax.jit(step)


@jax.jit
def _swiglu_add(y, g, weight, gate, up, down):
    """``y + weight[:, None] x down(silu(gate(g)) up(g))``, the three
    matrices cast here."""
    return y + weight[:, None] * (
        (ref.silu(g @ f32(gate)) * (g @ f32(up))) @ f32(down))


@jax.jit
def _expert_add(y, g, weights, picks, expert, gate, up, down):
    """``y`` + expert ``expert``'s output on ``g``, weighted where ``picks
    [S, k]`` has it with ``weights [S, k]`` (one program for every expert:
    ``expert`` is an operand)."""
    return _swiglu_add(y, g, jnp.sum(jnp.where(picks == expert, weights,
                                               0.0), axis=-1),
                       gate, up, down)


def logit_deficit(z, s, biased, picks, groups: int, keep: int):
    """``[2, S, k]`` in units of ``PICK_DEFICIT_LIMIT / ROUTE_TIE_TOL``
    router logits (module docstring): (a) the group part, (b) the score
    part; 0 for a row marked -1. ``z`` the logits, ``s`` their sigmoids,
    ``biased`` = ``s + b``."""
    S, E = z.shape
    k, size = picks.shape[-1], E // groups
    at = jnp.maximum(picks, 0)
    in_groups = lambda a: a.reshape(S, groups, size)
    two, where = jax.lax.top_k(in_groups(biased), 2)            # [S, n, 2]
    gsum = jnp.sum(two, axis=-1)
    s2 = jnp.take_along_axis(in_groups(s), where, axis=-1)
    # what a common rise of the group's two best logits adds to its sum
    slope = jnp.sum(s2 * (1.0 - s2), axis=-1)
    of_pick = lambda a: jnp.take_along_axis(a, at // size, axis=-1)
    # the reference's keep-th best group and the pick's meet where the
    # logits of one rise and of the other fall by half of this much each
    # (with equal slopes: how far the pick's two would have to rise alone)
    best, which = jax.lax.top_k(gsum, keep)
    edge = jnp.take_along_axis(slope, which[:, -1:], axis=-1)
    under_group = 2.0 * (best[:, -1:] - of_pick(gsum)) \
        / jnp.maximum(of_pick(slope) + edge, 1e-6)
    # the candidates the program is known to have had: the experts of its
    # picks' groups (a group it kept and picked nothing from is not handed
    # out, and a group ranked by TWO scores may well hold no pick: the
    # reference's own best other group in its place held an honest program
    # to experts it never ranked, 0.25-0.37 of a logit on the chip)
    theirs = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], at // size].set(True)
    kth = jax.lax.top_k(jnp.where(jnp.repeat(theirs, size, axis=1), biased,
                                  -jnp.inf), k)[0][:, -1:]
    # the logit at which the pick's biased score reaches the k-th best
    need = jnp.clip(kth - (jnp.take_along_axis(biased, at, axis=-1)
                           - jnp.take_along_axis(s, at, axis=-1)),
                    1e-6, 1.0 - 1e-6)
    under_score = jnp.log(need) - jnp.log1p(-need) \
        - jnp.take_along_axis(z, at, axis=-1)
    return jnp.where(picks >= 0, jnp.maximum(
        jnp.stack([under_group, under_score]), 0.0), 0.0) \
        * (ref.ROUTE_TIE_TOL / PICK_DEFICIT_LIMIT)


def reference_router(gate, g, *, k: int, groups: int, keep: int,
                     renorm: bool, scale: float, picks=None,
                     group_rank: int = 2, bias: bool = True):
    """``(scores [S, E], weights [S, k], picks [S, k], deficit [2, S, k]:
    its group part and its score part)`` in
    float32 from the program's ``moe/gate`` subtree (``kernel``, ``bias``).
    With ``picks`` (the program's, -1 where it has none) the layer routes by
    them, with weights from THIS router's sigmoids there. ``group_rank`` 1
    (a group by its maximum) and ``bias`` False (no bias in the selection)
    are the tests' wrong routers."""
    z = g @ f32(gate["kernel"])
    s = jax.nn.sigmoid(z)
    biased = s + f32(gate["bias"]) if bias and "bias" in gate else s
    S, E = s.shape
    gsum = jnp.sum(jax.lax.top_k(biased.reshape(S, groups, E // groups),
                                 group_rank)[0], axis=-1)
    kept = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], jax.lax.top_k(gsum, keep)[1]].set(True)
    _, own = jax.lax.top_k(jnp.where(jnp.repeat(kept, E // groups, axis=1),
                                     biased, 0.0), k)
    deficit = jnp.zeros((2,) + own.shape, jnp.float32)
    if picks is not None:
        deficit = logit_deficit(z, s, biased, picks, groups, keep)
        own = ref.pinned_picks(own, picks)
    weights = jnp.take_along_axis(s, own, axis=-1)
    if renorm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + NORM_EPS)
    return s, weights * scale, own, deficit


@functools.lru_cache(maxsize=None)
def _router_step(k: int, groups: int, keep: int, renorm: bool, scale: float,
                 eps: float, group_rank: int = 2, bias: bool = True):
    def step(gate, ln2, x, picks=None):
        g = ref.rms_norm(x, f32(ln2["scale"]), eps)
        _, weights, own, deficit = reference_router(
            gate, g, k=k, groups=groups, keep=keep, renorm=renorm,
            scale=scale, picks=picks, group_rank=group_rank, bias=bias)
        return g, weights, own, deficit
    return jax.jit(step)


def reference_moe(moe, g, weights, picks, first: int = 0,
                  shared: bool = True, layer=None):
    """One sparse layer's mixture on the normed ``g [S, hidden]`` from the
    program's ``moe`` subtree (``experts/{gate,fc,proj}/kernel [held, in,
    out]``: experts ``first ..`` of the router's, ``shared/...``; with
    ``layer`` the leaves are the stack's and that layer's are taken a matrix
    at a time), an expert a call: every held expert on every token, kept
    where ``picks`` has it; a pick of an expert not held adds nothing."""
    of = (lambda leaf, *at: leaf[at] if at else leaf) if layer is None \
        else (lambda leaf, *at: leaf[(layer,) + at])
    ex = moe["experts"]
    y = jnp.zeros_like(g)
    for e in range(ex["fc"]["kernel"].shape[-3]):
        y = _expert_add(y, g, weights, picks, first + e,
                        *(of(ex[n]["kernel"], e)
                          for n in ("gate", "fc", "proj")))
    if shared and "shared" in moe:
        y = _swiglu_add(y, g, jnp.ones((g.shape[0],), jnp.float32),
                        *(of(moe["shared"][n]["kernel"])
                          for n in ("gate", "fc", "proj")))
    return y


@functools.partial(jax.jit, static_argnums=2)
def _normed(x, scale, eps):
    return ref.rms_norm(x, f32(scale), eps)


@functools.partial(jax.jit, static_argnums=3)
def _head(x, scale, head, eps):
    return ref.rms_norm(x, f32(scale), eps) @ f32(head)


def reference_logits(c: Dict[str, Any], params, ids, picks=None, *,
                     ignore_selection: bool = False, group_rank: int = 2,
                     bias: bool = True):
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (``dense_blocks`` stacked by leading dense
    layer, ``blocks`` by sparse layer).

    With ``picks [S, layers, k + index_topk]`` (what the PROGRAM handed out
    for each fed token in every layer: its experts, -1 in a dense layer,
    then the positions of the keys it attended, -1 behind a row's own
    count) every layer routes and attends by them and the result is
    ``(logits, deficits [S, layers, k + index_topk])`` (numpy, made a layer
    at a time): the experts' in :func:`logit_deficit`'s unit, the keys' over
    the range of the row's index scores, both held to
    ``reference.ROUTE_TIE_TOL``. The keyword switches are the tests' wrong
    models."""
    eps, dense_n = float(c["rms_norm_eps"]), c["first_k_dense_replace"]
    k, topk = c["num_experts_per_tok"], c["index_topk"]
    attend = _attention_step(
        c["num_attention_heads"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
        c["index_n_heads"], c["index_head_dim"], topk, eps, softmax_scale(c),
        tuple(float(f) for f in yarn_inv_freq(c)), ignore_selection)
    route = _router_step(k, c["n_group"], c["topk_group"],
                         bool(c["norm_topk_prob"]),
                         float(c["routed_scaling_factor"]), eps, group_rank,
                         bias)
    first = share(c)[1]
    deficits = []
    del DEFICIT_PARTS[:]
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"][ids])
        for li in range(c["num_hidden_layers"]):
            dense = li < dense_n
            stack, at = (params["dense_blocks"], li) if dense \
                else (params["blocks"], li - dense_n)
            p = jax.tree.map(lambda a: a[at], {
                n: v for n, v in stack.items() if n != "moe"})
            theirs = None if picks is None else picks[:, li]
            # the attention branch's leaves alone: one tree, so one program,
            # for a dense layer and a sparse one
            x, key_deficit = attend(
                {n: v for n, v in p.items() if not n.startswith("mlp_")}, x,
                *(() if theirs is None else (theirs[:, k:],)))
            if dense:
                x = _swiglu_add(
                    x, _normed(x, p["ln2"]["scale"], eps),
                    jnp.ones((x.shape[0],), jnp.float32),
                    p["mlp_gate"]["kernel"], p["mlp_fc"]["kernel"],
                    p["mlp_proj"]["kernel"])
                pick_deficit = jnp.zeros((x.shape[0], k), jnp.float32)
            else:
                moe = stack["moe"]
                g, weights, own, pick_deficit = route(
                    jax.tree.map(lambda a: a[at], moe["gate"]), p["ln2"], x,
                    *(() if theirs is None else (theirs[:, :k],)))
                x = x + reference_moe(moe, g, weights, own, first, layer=at)
                parts = np.asarray(pick_deficit)
                DEFICIT_PARTS.append(parts.max(axis=(1, 2)).tolist())
                pick_deficit = parts.max(axis=0)
            if picks is not None:
                deficits.append(np.concatenate(
                    [np.asarray(pick_deficit), np.asarray(key_deficit)], 1))
        logits = _head(x, params["ln_f"]["scale"],
                       params["lm_head"]["kernel"], eps)
    if picks is None:
        return logits
    return logits, np.stack(deficits, axis=1)
