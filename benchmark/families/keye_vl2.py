"""Keye-VL-2.0's language model (HF ``model_type`` ``KeyeVL2``:
Keye-VL-2.0-30B-A3B): grouped-query attention whose keys a learned indexer
picks (``sa_config``: DeepSeek-Sparse-Attention's index score), every layer a
softmax mixture with renormalised picks.

``config.json`` gives the sizes; what it does not say is taken from the
family's Qwen3-MoE-shaped text model and from DeepSeek-V3.2-Exp's published
index score, and is marked ``assumed`` here and in the configuration file.
For ``x [S, hidden]``, layer ``l``, ``rms`` = RMSNorm with ``rms_norm_eps``,
position t and keys s <= t::

    h  = rms(x, input_layernorm)
    q, k, v = h Wq, h Wk, h Wv      -> q [S, heads, hd], k, v [S, kv, hd]
    q, k = rms(q, q_norm[hd]), rms(k, k_norm[hd])     per head (assumed)
    q, k = rotate_half(q, k; rope_theta)    (text only: the three position
                                  streams of mrope_section coincide)
    qI = h WqI [S, ih, iw];  kI = LayerNorm(h WkI) [S, iw];  w = h Ww [S, ih]
    qI, kI = rotate_half(qI, kI; rope_theta)     the whole indexer head
    I(t, s) = sum_j w(t, j) relu(qI(t, j) . kI(s))
    S_t = the topk keys s <= t of largest I(t, s); equal scores to the lower
          position; all of them while t < topk
    o_t = softmax over s in S_t of (q_t . k_s / sqrt(hd)) v_s,  GQA
    x  = x + o Wo
    m  = rms(x, post_attention_layernorm)
    p  = softmax(m Wr) [S, E];  e = top_k(p);  g = p[e] / sum p[e]
    x  = x + sum_j g_j down_{e_j}(silu(gate_{e_j}(m)) * up_{e_j}(m))
    logits = rms(x_L, norm) W_head

**A chip's share.** With ``deployment`` in the configuration the parameter
tree holds ``experts_held[1]`` experts a layer, the router keeps its
``router_outputs`` and its k picks, and ``sum_j`` runs over the picks whose
expert is held; ``g`` is still normalised over all k (``exaone_moe.py`` says
the same of its share; ``tests`` tie the eight shares to the uncut layer).

**The reference follows the program's selection.** ``reference_logits(...,
picks=)`` takes ``picks [S, layers, k + topk]``: a fed token's routing as
the program hands it out, columns ``0 .. k-1`` the experts each layer picked
(``reference.pinned_picks``; weights from THIS router's own probabilities,
deficits by ``reference.pick_deficit`` over the router's logits, whose
spread is 1: the router reads a normed input, as OLMoE's), columns ``k ..``
the positions of the keys the row attends in that layer, -1 behind its own
count. A layer then attends exactly those keys, and reports for each how
far the reference's OWN index score of it lies under its own topk-th best
of that row, **as a share of the range of the row's index scores** (its best
less its worst over the keys t sees). Why a share of the row's own scores:
``I`` is no logit of unit size. Under the driver's draw ``qI . kI`` has
spread 8 (64 products of unit entries) and a row's 16 head weights are 16
draws of its own, so rows' scores differ severalfold in size, and the
bfloat16 program's error in a score (its residual stream is rounded a block
at a time, both operands of the product carry it, the indexer's keys are
stored in bfloat16) is relative to that size. Why the range and not the
standard deviation: a row ranks up to 24 576 keys where a router ranks 128
experts, so a tenth of a standard deviation, which is 1.6 experts' distance
at a router's cut, is hundreds of keys' distance at this one, and the largest
honest deficit of the 5 x 10^8 selected keys of a run's checked requests
read 0.109-0.201 standard deviations on the chip (two seeds; 0.018-0.094 a
layer at the shorter prompts) where the experts' read 0.010-0.027 of a
router logit: over the range (8-13 standard deviations in those rows)
the same keys read **0.014-0.016**, the experts' size. The two faults
read, over the range: the selection of the token before, 0.63-0.78 at a
seed's worst layer (0.18 at its best); an indexer without its ReLU,
0.41-0.67 (0.19). ``reference.ROUTE_TIE_TOL`` (0.1, not this file's to
set) stands six times over the honest readings and four times under the
faults'; over the standard deviation it stood inside the honest ones
(``PERF.md``, section 6, PR 45, has every reading). So one tolerance
judges experts and keys alike. A key the row cannot see reads infinity. A
row marked -1 throughout (padding, or a prompt position whose K/V came
from the prefix cache) selects by itself. Without ``picks`` every row does.

A key whose score lies within rounding of the topk-th best flips between the
program and a float32 reference; with some 750 keys carrying a row's output
one flipped key moves it by a few percent, and dozens flip a row at long
contexts (ISSUE 45): a reference that selected by itself would compare
another model, as PR 42 found for renormalised picks.

Blocks of ``ROWS`` query rows at a time (``lax.map``), so that the index
products ``[rows, ih, S]`` and the attention scores ``[heads, rows, S]`` of
a 24 704-position sequence fit the chip beside a layer in float32. No
kernel, no cache, no paging: it shares nothing with ``ops/pallas/``.

**Departures**, each on purpose: the vision tower is not part of the
language model's layer; DSA's Hadamard rotation of the indexer's operands
and its FP8 index cache are numerics of its kernels (the program stores
``kI`` in bfloat16); ``q_chunk_size`` / ``kv_chunk_size`` are the published
kernels' tile sizes and change no result.

Config keys (HF ``config.json`` names): ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``num_experts`` (HELD here, with
``num_local_experts``), ``num_experts_per_tok``, ``norm_topk_prob``,
``decoder_sparse_step``, ``mlp_only_layers``, ``rms_norm_eps``,
``rope_theta``, ``rope_scaling``, ``sa_config`` (``indexer_num_heads``,
``indexer_head_dim``, ``indexer_num_kv_heads``, ``topk``),
``max_position_embeddings``, ``vocab_size``, ``tie_word_embeddings``,
``hidden_act``, ``attention_bias``, ``use_sliding_window``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as ref
from benchmark.families.exaone_moe import share, swiglu
from benchmark.families.olmoe import reference_router

#: query rows a block of the reference takes
ROWS = 128


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    sa = c["sa_config"]
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"] \
            or c["attention_bias"]:
        raise ValueError("keye_vl2 family: silu SwiGLU, an untied head, no "
                         "attention bias")
    if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] \
            or c["use_sliding_window"]:
        raise ValueError("keye_vl2 family: every layer a mixture, no window")
    if c["rope_scaling"]["rope_type"] != "default" \
            or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("keye_vl2 family: plain rotary positions (text "
                         "only), one indexer key a token")
    if c["num_local_experts"] != c["num_experts"]:
        raise ValueError("num_local_experts and num_experts both count the "
                         "experts HELD")
    router, first, count = share(c)
    return dict(vocab_size=c["vocab_size"],
                max_seq_len=c["max_position_embeddings"],
                hidden_size=c["hidden_size"],
                num_layers=c["num_hidden_layers"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"],
                head_dim_override=c["head_dim"],
                mlp_dim_override=c["moe_intermediate_size"],
                layer_norm_eps=c["rms_norm_eps"], norm="rmsnorm",
                gated_mlp=True, activation="silu", pos_embed="rotary",
                rotary_interleaved=False, rope_theta=float(c["rope_theta"]),
                use_bias=False, tie_embeddings=False, qk_norm="head",
                moe_experts=router, moe_k=c["num_experts_per_tok"],
                moe_held=None if count == router else (first, count),
                moe_dropless=True, moe_norm_topk=bool(c["norm_topk_prob"]),
                moe_aux_weight=0.0,
                index_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"],
                index_topk=sa["topk"])


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    """``experts`` and ``mlp_dim`` are the HELD experts and one expert's
    width (what ``moe_cost`` takes); ``heads`` the query heads,
    ``kv_heads`` the heads a page stores; the indexer's sizes for
    ``sparse_cost``."""
    sa = c["sa_config"]
    return dict(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                mlp_dim=c["moe_intermediate_size"], mlp_matrices=3,
                vocab=c["vocab_size"], experts=share(c)[2],
                experts_per_token=c["num_experts_per_tok"],
                router_outputs=share(c)[0],
                index_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"],
                index_topk=sa["topk"])


def index_scores(qi, ki, w, row0, relu: bool = True):
    """``I [rows, S]`` of the query rows ``row0 ..`` (``qi [rows, ih, iw]``,
    ``w [rows, ih]``) against every indexer key ``ki [S, iw]``; ``-inf``
    where the key lies behind the row. ``relu`` False: the tests' wrong
    indexer."""
    prod = jnp.einsum("rjd,sd->rjs", qi, ki)
    scores = jnp.sum(w[:, :, None] * (jnp.maximum(prod, 0.0) if relu
                                      else prod), axis=1)
    rows = row0 + jnp.arange(qi.shape[0])[:, None]
    # -0.0 (a negative weight times a zero) is the score 0.0
    scores = jnp.where(scores == 0.0, 0.0, scores)
    return jnp.where(jnp.arange(ki.shape[0])[None, :] <= rows, scores,
                     -jnp.inf)


def own_selection(scores, topk: int):
    """``(mask [rows, S] of each row's topk best visible keys, equal scores
    to the lower position; the topk-th best score [rows, 1], -inf for a row
    that sees no more than topk)``."""
    visible = scores > -jnp.inf
    if scores.shape[1] <= topk:
        return visible, jnp.full((scores.shape[0], 1), -jnp.inf)
    # top_k puts the lower index first among equals
    vals, idx = jax.lax.top_k(scores, topk)
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return mask & visible, vals[:, -1:]


def sparse_attention(q, k, v, qi, ki, w, topk: int, picks=None,
                     relu: bool = True, unit: str = "range"):
    """``(o [S, heads x hd], deficit [S, topk])``: causal attention of each
    query over its selected keys, in blocks of :data:`ROWS` rows. ``picks
    [S, topk]``: the positions each row attends (-1 behind its count; a row
    of -1 selects by itself). ``relu`` False is the tests' wrong indexer;
    ``unit`` "spread" reads the deficits over the row's standard deviation
    (for sizing: the module docstring has both readings)."""
    S, nh, hd = q.shape
    kvh = k.shape[1]
    rows = ROWS if S % ROWS == 0 else S

    def block(args):
        row0, q_b, qi_b, w_b, picks_b = args
        scores = index_scores(qi_b, ki, w_b, row0, relu)
        mask, kth = own_selection(scores, topk)
        deficit = jnp.zeros((rows, topk), jnp.float32)
        if picks_b is not None:
            given = picks_b >= 0
            theirs = jnp.zeros((rows, S), bool).at[
                jnp.arange(rows)[:, None], jnp.where(given, picks_b, S)
            ].set(True, mode="drop")
            mask = jnp.where(given.any(axis=1, keepdims=True), theirs, mask)
            # the reference's own score of each selected key against its
            # own topk-th best, over the range of the row's scores
            visible = scores > -jnp.inf
            if unit == "range":
                size = jnp.max(scores, axis=1, keepdims=True) - jnp.min(
                    jnp.where(visible, scores, jnp.inf), axis=1,
                    keepdims=True)
            else:
                n = jnp.maximum(visible.sum(axis=1, keepdims=True), 1)
                mean = jnp.where(visible, scores, 0.0).sum(
                    1, keepdims=True) / n
                size = jnp.sqrt(jnp.where(
                    visible, jnp.square(scores - mean), 0.0).sum(
                    1, keepdims=True) / n)
            got = jnp.take_along_axis(scores, jnp.maximum(picks_b, 0), axis=1)
            short = jnp.where(given & (kth > -jnp.inf),
                              jnp.maximum(kth - got, 0.0), 0.0)
            # a position the row cannot see (or -inf of it) is no tie
            short = jnp.where(given & (got == -jnp.inf), jnp.inf, short)
            deficit = short / jnp.maximum(size, 1e-30)
        s = jnp.einsum("rghd,sgd->ghrs",
                       q_b.reshape(rows, kvh, nh // kvh, hd), k) / np.sqrt(hd)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ghrs,sgd->rghd", p, v).reshape(rows, nh * hd)
        return o, deficit

    n = S // rows
    split = lambda a: a.reshape((n, rows) + a.shape[1:])
    o, deficit = jax.lax.map(block, (
        jnp.arange(n) * rows, split(q), split(qi), split(w),
        None if picks is None else split(picks)))
    return o.reshape(S, nh * hd), deficit.reshape(S, topk)


def held_mixture(moe, h, *, k: int, renorm: bool, first: int = 0,
                 picks=None):
    """One layer's mixture on ``h [S, hidden]`` from the program's ``moe``
    subtree (``gate/kernel [hidden, E]``, ``experts/{gate,fc,proj}/kernel
    [held, in, out]``: experts ``first ..`` of the E), float32: every held
    expert on every token, kept where the router picked it (with ``picks``:
    where the program did); a pick of an expert not held adds nothing.
    Returns ``(y, probs, picks, deficit)``."""
    probs, weights, picks, deficit = reference_router(
        moe["gate"]["kernel"], h, k, renorm, picks)
    ex = moe["experts"]
    y = jnp.zeros_like(h)
    for e in range(ex["fc"]["kernel"].shape[0]):
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(h, ex["gate"]["kernel"][e],
                                      ex["fc"]["kernel"][e],
                                      ex["proj"]["kernel"][e])
    return y, probs, picks, deficit


@functools.lru_cache(maxsize=None)
def _step(heads: int, kv_heads: int, head_dim: int, eps: float, theta: float,
          ih: int, iw: int, topk: int, k: int, renorm: bool, first: int,
          relu: bool = True, unit: str = "range"):
    """One layer as a jitted step ``(p, x[, picks]) -> (x, deficit [S, k +
    topk])``."""
    def block(p, x, picks=None):
        S = x.shape[0]
        h = ref.rms_norm(x, p["ln1"]["scale"], eps)
        qkv = h @ p["attn_qkv"]["kernel"]
        q, kk, v = jnp.split(qkv, [heads * head_dim,
                                   (heads + kv_heads) * head_dim], axis=-1)
        q = ref.rotary_half(ref.rms_norm(q.reshape(S, heads, head_dim),
                                         p["q_norm"]["scale"], eps), theta)
        kk = ref.rotary_half(ref.rms_norm(kk.reshape(S, kv_heads, head_dim),
                                          p["k_norm"]["scale"], eps), theta)
        qi = ref.rotary_half((h @ p["index_q"]["kernel"]).reshape(S, ih, iw),
                             theta)
        ki = ref.rotary_half(ref.layer_norm(
            h @ p["index_k"]["kernel"], p["index_k_norm"]["scale"],
            p["index_k_norm"]["bias"], eps)[:, None], theta)[:, 0]
        o, key_deficit = sparse_attention(
            q, kk, v.reshape(S, kv_heads, head_dim), qi, ki,
            h @ p["index_w"]["kernel"], topk,
            None if picks is None else picks[:, k:], relu, unit)
        x = x + o @ p["attn_proj"]["kernel"]
        m, _, _, pick_deficit = held_mixture(
            p["moe"], ref.rms_norm(x, p["ln2"]["scale"], eps), k=k,
            renorm=renorm, first=first,
            picks=None if picks is None else picks[:, :k])
        return x + m, jnp.concatenate([pick_deficit, key_deficit], axis=1)

    return ref.layer_step(block)


def reference_logits(c: Dict[str, Any], params, ids, picks=None,
                     relu: bool = True, unit: str = "range"):
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (``blocks`` stacked by layer).

    With ``picks [S, layers, k + topk]`` (what the PROGRAM handed out for
    each fed token and layer: its experts, then the positions of the keys
    it attended, -1 behind a row's own count) every layer routes and
    attends by them and the result is ``(logits, deficits [S, layers, k +
    topk])``: the experts' in router logits, the keys' in units of the
    range of the row's index scores (module docstring), both held to
    ``reference.ROUTE_TIE_TOL``. ``relu`` False: an indexer without its
    ReLU, for the tests of the check; ``unit`` "spread": the keys' deficits
    over the row's standard deviation instead."""
    sa, eps = c["sa_config"], float(c["rms_norm_eps"])
    step = _step(c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"], eps, float(c["rope_theta"]),
                 sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
                 c["num_experts_per_tok"], bool(c["norm_topk_prob"]),
                 share(c)[1], relu, unit)
    f32 = lambda a: a.astype(jnp.float32)
    deficits = []
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"]["embedding"])[ids]
        for li in range(c["num_hidden_layers"]):
            p = jax.tree.map(lambda a: a[li], params["blocks"])
            x, d = step(p, x, *(() if picks is None else (picks[:, li],)))
            deficits.append(d)
        x = ref.rms_norm(x, f32(params["ln_f"]["scale"]), eps)
        logits = x @ f32(params["lm_head"]["kernel"])
    if picks is None:
        return logits
    return logits, jnp.stack(deficits, axis=1)
