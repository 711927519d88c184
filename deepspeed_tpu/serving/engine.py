"""ServingEngine — the continuous-batching serving loop.

Turns (cfg, params) into a long-lived server: requests are submitted from
any thread, admitted under the block-pool budget, prefilled into paged KV
blocks (reusing cached prefix blocks for shared system prompts), and
decoded in-flight — new prefills join as finishing sequences free their
blocks, with NO batch-drain barrier.

Fixed-shape discipline: the decode step is ONE jitted program over
``max_batch`` lanes and a ``[max_batch, max_blocks_per_seq]`` block
table. Admissions, evictions and completions only change the DATA in
those arrays, never their shapes, so the loop compiles exactly one
decode step for its whole lifetime (pinned by tests via
``_cache_size``); prefills compile once per prompt-suffix bucket
(``_prefill_rows``: whole blocks, whole 256-row tiles where the cache is
latent): the prefill program, or, in a chunked engine
whose cache is not latent, the mixed program that carries the chunk AND the
decode lanes (``_mixed_step``). This is the role CUDA-graph capture plays in
the reference's ``InferenceEngine`` — here XLA's compile cache IS the graph
cache, and the fixed shapes are what keep it hot.

Supervision: each loop iteration stamps a ``SERVE`` heartbeat phase
(runtime/heartbeat.py), so the PR-6 watchdog/health stack bounds a wedged
serving loop exactly the way it bounds a wedged train step —
``watchdog.serve_timeout`` in ds_config arms the rc-117 deadline.

Token-exactness: greedy serving output is token-exact with sequential
``models.generation.generate()`` calls (same layer math, same f32 score
path — see serving/model_runner.py), which the integration tests pin
across staggered arrivals and mixed lengths.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generation import ensure_scan_layout
from ..models.transformer import TransformerConfig
from ..runtime.heartbeat import PHASE_SERVE
from ..testing import chaos
from ..utils import telemetry
from ..utils.logging import log_dist, logger
from ..ops.pallas import sparse_select
from ..ops.pallas.latent_attention import (chunk_expanded_keys,
                                           chunk_key_tiles, chunk_tiles)
from ..ops.pallas.latent_attention import form as latent_form
from ..ops.pallas.latent_attention import path as latent_path
from ..ops.pallas.paged_attention import chunk_plan, chunk_walk
from ..ops.pallas.sparse_select import bits_to_positions, positions_of_bits
from .kv_cache import (NULL_BLOCK, BlockPoolExhausted, SharedPagedState)
from .model_runner import attention_impl, mixed_forward, paged_forward
from .scheduler import (BATCH, FAILED, FINISHED, PREFILL, PRIORITY_TIERS,
                        QUEUED, RUNNING, STANDARD, TIER_RANK, TIMEOUT,
                        Request, Scheduler)

PyTree = Any

#: the engine's counters; ``ServingEngine.stats`` IS its recorder's dict
#: (utils/telemetry.py), which the paged state counts into as well
#: (``kv.alloc`` ..., ``prefix.*``: serving/kv_cache.py). The ``*_sum``
#: counters add one reading a step, taken on entry to ``step()``; the two
#: ``step_inputs.*`` count host arrays handed to the device (one a device
#: call) and lane rows rewritten (one an install or a freed lane: a decode
#: step that changes no lane writes none). The two ``paged.chunk_*`` add one
#: reading a PREFILL call: the pages the call's attention walks (up to its
#: last real token) and the table's width, which the gather reference reads.
#: The four ``decode_ahead.*`` say how the launch of a decode call before the
#: fetch of the one in flight went (``ServingEngine._decode_step``): calls
#: launched that way, lane inputs the device found for itself (the previous
#: call's output or a prefill's), lanes computed once more after their end
#: (an EOS is seen one call late), calls in flight retired outside a step.
#: The two ``mixed.*``: the steps whose prefill chunk and decode lanes were ONE
#: device call (``ServingEngine._mixed_step``: every step that advances a
#: chunk, where the cache is not latent), and the live lane rows that rode
#: them; both stay 0 where the chunk and the lanes keep a program each.
#: ``prefill_rows``: the rows the prefill calls brought, ``prefill_tokens``
#: and their padding (``_prefill_rows``): 1 - tokens / rows is the share of
#: rows computed for nothing.
_COUNTERS = (
    "completed", "failed", "timeout", "tokens_generated", "prefill_tokens",
    "prefix_hit_tokens", "preempted",
    "steps", "steps_with_queue", "queue_len_sum", "lane_sum",
    "admit_blocked.no_lane", "admit_blocked.no_blocks",
    "admit_blocked.prefilling", "compiles",
    "kv.held_blocks_sum", "kv.blocks_reserved_sum", "kv.tokens_written_sum",
    "prefix.prompt_tokens", "paged.live_pages_sum", "paged.table_pages_sum",
    "paged.window_pages_sum",
    "paged.chunk_live_pages_sum", "paged.chunk_table_pages_sum",
    "paged.chunk_turns_sum", "paged.chunk_key_tiles_sum",
    "paged.chunk_key_tiles_live_sum",
    "step_inputs.transfers_sum", "step_inputs.lane_rows_written_sum",
    "decode_ahead.launched", "decode_ahead.device_lane_tokens_sum",
    "decode_ahead.wasted_lane_tokens", "decode_ahead.retired_unread",
    "mixed.calls", "mixed.lane_rows_sum", "prefill_rows")
#: a dropless MoE model's router load, from the [sparse layers, E] counts
#: that ride the tokens' own fetch (``_count_experts``); a dense model has
#: none of these. ``moe.held_assignments``: the assignments whose expert this
#: program holds (``cfg.moe_held``; all of them without a share): over
#: ``moe.assignments`` the share's load, 1/8 for an eighth under even routing
_MOE_COUNTERS = ("moe.assignments", "moe.held_assignments", "moe.layer_steps",
                 "moe.load_max_over_mean_sum", "moe.experts_idle_sum")
#: a grouped router (``cfg.moe_groups``), from the kept-group counts behind
#: the experts' in the same fetch: the real rows, over the sparse layers,
#: whose kept groups include one this program holds (``cfg.moe_held``; every
#: row without a share). Over rows x layers: the share of the traffic this
#: device sees under device-limited routing, ``topk_groups / groups`` (3/8)
#: under even routing; the rest compute nothing here
_GROUP_COUNTERS = ("moe.group_rows_sum",)
#: a latent model (``cfg.kv_lora_rank``), counted on the host from the
#: positions of a call's rows, summed over the layers: query rows (a decode
#: lane's token, a chunk's tokens), the cached tokens they attend (a row's
#: own included) and the pages the latent kernel walks for them (a lane's
#: live pages once a call, whatever its head programs copy again). The two
#: ``mla.chunk_*`` are the prefill calls' part: the cached tokens their rows
#: attend (of ``mla.ctx_tokens_sum``), the cached tokens a call sees, once
#: a call (what a chunk reads, and what the mathematics has to put through
#: ``attn_kv_b``: ``benchmark/mla_cost.py`` prices both forms from it), and
#: the cached tokens a head of the call DOES put through ``attn_kv_b``, by
#: the kernel's own grid and rule (``latent_attention.chunk_expanded_keys``):
#: a program expands a key tile once for all of its rows and a chunk of up
#: to 1 536 rows is one program a head, so ``chunk_expanded_keys_sum /
#: chunk_keys_sum`` reads 1.0 there (a whole prompt of 24 576 rows in one
#: call, sixteen row tiles: 8.5; a form that expanded a 256-row tile at a
#: time would read up to rows / 256; the absorbed jnp twin, which expands
#: nothing, 0)
_MLA_COUNTERS = ("mla.rows_sum", "mla.ctx_tokens_sum", "mla.pages_walked_sum",
                 "mla.chunk_ctx_tokens_sum", "mla.chunk_keys_sum",
                 "mla.chunk_expanded_keys_sum")
#: a latent model WITH an indexer (DeepSeek Sparse Attention): the keys its
#: latent calls attend, ``min(a row's cached tokens, index_topk)`` a row
#: (beside ``mla.ctx_tokens_sum``, the keys the kernel walks for them: their
#: ratio is what reading the selected rows only would save, ROADMAP M7 (a);
#: ``mla.chunk_selected_keys_sum`` is the prefill calls' part),
#: and, of the expanded chunk form, the (256-row tile, key turn) pairs of the
#: turns its programs walk and those whose matmuls its own rule lets them
#: make (``latent_attention.chunk_key_tiles``, a head group; a pair none of
#: whose rows selected a key is skipped besides, on the device alone)
_MLA_SELECT_COUNTERS = ("mla.selected_keys_sum",
                        "mla.chunk_selected_keys_sum",
                        "mla.chunk_key_tiles_sum",
                        "mla.chunk_key_tiles_live_sum")
#: a model with an indexer (``cfg.index_heads``), counted on the host from
#: the positions of a call's rows, summed over the layers: query rows (a
#: decode lane's token, a chunk's tokens), the keys the indexer scored for
#: them (every key a row sees), the keys it selected (all of them up to
#: ``index_topk``), and the pages the paged kernel walked to attend those
#: (every live page: it masks, it does not skip; beside
#: ``paged.live_pages_sum``, which counts a call's pages once)
_SPARSE_COUNTERS = ("sparse.rows_sum", "sparse.keys_scored_sum",
                    "sparse.keys_selected_sum", "sparse.pages_walked_sum",
                    "sparse.topk_tiles_sum", "sparse.topk_tiles_idle_sum",
                    "sparse.topk_columns_sum",
                    "sparse.topk_columns_table_sum",
                    "sparse.score_tiles_sum", "sparse.score_tiles_table_sum")

_KV_DTYPES = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
              "f32": jnp.float32, "float32": jnp.float32,
              "int8": jnp.int8, None: None}


def resolve_kv_dtype(serving):
    """``serving.kv_cache_dtype`` -> jnp dtype (None = model dtype);
    shared by engine construction and the disagg pair's shared-state
    builder so both roles resolve identically."""
    if serving.kv_cache_dtype not in _KV_DTYPES:
        raise ValueError(
            f"serving.kv_cache_dtype={serving.kv_cache_dtype!r} is not "
            f"supported; choose one of "
            f"{sorted(k for k in _KV_DTYPES if k)} or null for the "
            "model dtype")
    return _KV_DTYPES[serving.kv_cache_dtype]


def lane_topk_topp(logits: jnp.ndarray, top_k: jnp.ndarray,
                   top_p: jnp.ndarray) -> jnp.ndarray:
    """Vectorized PER-LANE top-k / top-p filter for the compiled decode
    step (round 12): ``logits`` [B, V] (already temperature-scaled),
    ``top_k`` [B] i32 (<= 0 = off), ``top_p`` [B] f32 (>= 1 = off).

    Exactly ``models.generation._sample``'s masking math per lane — kth
    value keeps ties (every logit >= the kth largest survives), then HF
    TopPLogitsWarper nucleus semantics on the top-k-masked logits
    (``apply_top_p``: positional in the sorted order, top token always
    survives) — so a one-lane filter + categorical at the same key is
    token-identical to one-shot ``generate()`` sampling (pinned by
    test).

    ONE ordering pass: both filters read the same descending argsort
    (top-k masking only demotes a suffix of the sorted view, so the
    nucleus pass reuses the order), and the result scatters back through
    it — no second argsort, no inverse argsort."""
    B, V = logits.shape
    order = jnp.argsort(-logits, axis=-1)                        # [B, V]
    sl = jnp.take_along_axis(logits, order, axis=-1)             # desc
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sl, (k - 1)[:, None], axis=-1)     # [B, 1]
    keep_k = (top_k[:, None] <= 0) | (sl >= kth)
    probs = jax.nn.softmax(jnp.where(keep_k, sl, -1e30), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = ((cum - probs) < top_p[:, None]) | (top_p[:, None] >= 1.0)
    final_sorted = jnp.where(keep_k & keep_p, sl, -1e30)
    return jnp.full_like(logits, -1e30).at[
        jnp.arange(B)[:, None], order].set(final_sorted)


def _split(buf, *fields):
    """Consecutive fields of a flat int32 buffer, ``(words, dtype)`` each:
    views of a numpy buffer (the host writes through them), bit-casts of a
    traced one (the program reads them)."""
    host, out, at = isinstance(buf, np.ndarray), [], 0
    for words, dtype in fields:
        # lax.slice, not jnp indexing: a tenth of the trace and lowering
        # work, nine programs at every start
        x = buf[at:at + words] if host else jax.lax.slice(
            buf, (at,), (at + words,))
        if dtype != np.int32:
            x = x.view(dtype) if host else jax.lax.bitcast_convert_type(
                x, dtype)
        out.append(x)
        at += words
    assert at == buf.shape[0], (at, buf.shape)
    return out


class StepLayout:
    """Where each input of a device call lies in the ONE int32 buffer the
    call gets (one host-to-device transfer a call, made by the jitted call
    itself). The host fills a numpy buffer through :meth:`decode` /
    :meth:`prefill`'s views, the program reads the same fields of its traced
    argument: the layout is written once, here.

    decode, ``B`` lanes:   ``toks[B] | ctx[B] | top_k[B] | tables[B, nbk] |
    temps[B] | top_p[B] | key``; a lane's ``toks`` word is its token, or
    below zero the place the device finds it in (:meth:`lane_tokens`):
    :data:`FROM_DECODE`, the previous decode call's output at the lane's own
    index, or :data:`FROM_PREFILL`, the first word of the last final prefill
    call's output. The host need not have seen either.
    prefill, ``T`` tokens: ``ids[1, T] | table[1, nbk] | q0[1] | ctx[1] |
    last_idx[1] | top_k[1] | temp[1] | top_p[1] | key``
    mixed, ``T`` tokens beside ``B`` lanes: a prefill buffer, then a decode
    buffer, each whole and with its own key (:meth:`mixed`)

    ``key`` (the last words of either buffer) is the call's sampling key as
    raw words, derived on the host (``ServingEngine._call_key``): host data
    like the rest, so no key is split or folded by a device program of its
    own between two steps. The table width and the key's width are the
    layout's own; ``B`` and ``T`` follow from a buffer's length."""

    FROM_DECODE, FROM_PREFILL = -1, -2

    def __init__(self, table_width: int, key_words: int = 2):
        self.nbk, self.kw = int(table_width), int(key_words)

    @classmethod
    def lane_tokens(cls, toks, prev, first):
        """The decode lanes' input tokens, on the device: ``toks`` where the
        host wrote a token, else the lane's word of ``prev`` (the previous
        decode call's output) or the first word of ``first`` (a prefill
        call's), as they came off the device, whatever rides behind their
        tokens. One select over ``[B]``."""
        B = toks.shape[0]
        return jax.lax.select_n(
            jnp.clip(-toks, 0, -cls.FROM_PREFILL), toks,
            jax.lax.slice(prev, (0,), (B,)),
            jnp.broadcast_to(jax.lax.slice(first, (0,), (1,)), (B,)))

    def decode_words(self, lanes: int) -> int:
        return lanes * (5 + self.nbk) + self.kw

    def prefill_words(self, tokens: int) -> int:
        return tokens + self.nbk + 6 + self.kw

    def mixed(self, buf, lanes: int):
        """(the prefill buffer, the decode buffer over ``lanes`` lanes) of a
        mixed call's ``buf``."""
        words = self.decode_words(lanes)
        return _split(buf, (buf.shape[0] - words, np.int32),
                      (words, np.int32))

    def decode(self, buf):
        """(toks, ctx, top_k, tables, temps, top_p, key) of ``buf``."""
        B, rest = divmod(buf.shape[0] - self.kw, 5 + self.nbk)
        assert rest == 0, (buf.shape, self.nbk, self.kw)
        i32, f32 = np.int32, np.float32
        toks, ctx, tks, tables, temps, tps, key = _split(
            buf, (B, i32), (B, i32), (B, i32), (B * self.nbk, i32),
            (B, f32), (B, f32), (self.kw, np.uint32))
        return toks, ctx, tks, tables.reshape(B, self.nbk), temps, tps, key

    def prefill(self, buf):
        """(ids, table, q0, ctx, last_idx, top_k, temp, top_p, key) of
        ``buf``."""
        T = buf.shape[0] - self.prefill_words(0)
        i32, f32 = np.int32, np.float32
        ids, table, q0, ctx, last_idx, tk, temp, tp, key = _split(
            buf, (T, i32), (self.nbk, i32), (1, i32), (1, i32), (1, i32),
            (1, i32), (1, f32), (1, f32), (self.kw, np.uint32))
        return (ids.reshape(1, T), table.reshape(1, self.nbk), q0, ctx,
                last_idx, tk, temp, tp, key)


def step_programs(cfg, block_size: int, table_width: int, *,
                  interpret: bool = False, use_filters: bool = False,
                  key_words: int = 2, mixed: bool = False):
    """The loop's device programs as plain functions, ``(decode, prefill)``
    and with ``mixed`` ``(decode, prefill, mixed)``: ``prefill(params,
    pools, step_in)`` and ``decode(params,
    pools, step_in, prev, first)``, each ``-> (tokens, pools)``, with
    ``step_in`` the call's one int32 buffer (:class:`StepLayout`, built
    from ``table_width`` and ``key_words``) and ``prev`` / ``first`` the
    previous decode call's and the last final prefill call's ``tokens`` as
    they came off the device (:func:`token_words` long), for the lanes whose
    token the host never held (:meth:`StepLayout.lane_tokens`). The engine
    jits them with the pools donated, and tests/test_chip_compile.py
    compiles the same two for a described chip.

    For a dropless MoE config (``cfg.moe_is_dropless``) the int32 token
    vector each returns carries, behind the tokens, the ``[L, E]`` expert
    counts of the call (``L`` the sparse layers, ``E`` the router's
    outputs), flattened (``ServingEngine._count_experts`` splits
    them): one array, the fetch the step has already; and beside that vector,
    as a second output, the call's picks ``[L, lanes x T, k]`` int32
    (``decoder_forward``'s ``expert_picks``), which stay on the device
    unless a request of the call asked for them (``submit``'s
    ``keep_routing``). Every other config gets the plain token vector.

    ``mixed(params, pools, step_in, prev, first) -> ((decode's tokens,
    prefill's tokens), pools)`` is a prefill call and the decode call behind
    it as ONE program (``model_runner.mixed_forward``: the chunk's ``T`` rows
    and the ``B`` lanes' one row each through every matmul together, so the
    weights are read once a step; not for a latent model). Its ``step_in`` is
    a prefill buffer and a decode buffer end to end, its two outputs are
    what the two programs would return, each with its own expert counts and
    picks, each sampled by its kind's own sampler under its own key; ``B``
    follows from ``prev``. No lane of the call may hold a block its chunk
    writes: the prompt's own lane joins the next call."""
    bs, layout = int(block_size), StepLayout(table_width, key_words)
    counting = bool(cfg.moe_is_dropless)

    def _pick(logits, key, temps, tks, tps):
        """Per-lane sampling: greedy lanes take argmax, temperature
        lanes a categorical over logits / temp — one compiled program
        for any mix. ``key``: the call's own key, raw words out of its
        buffer. With ``serving.sampling_filters`` (a construction-time
        constant: the program is still compiled once) the vectorized
        per-lane top-k/top-p filter runs on the scaled logits first."""
        with jax.named_scope("sample"):
            r = jax.random.wrap_key_data(key)
            greedy = jnp.argmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            if use_filters:
                scaled = lane_topk_topp(scaled, tks, tps)
            sampled = jax.random.categorical(r, scaled, axis=-1)
            return jnp.where(temps <= 0.0, greedy, sampled)

    @jax.jit        # no chunk size changes its shapes: traced once
    def _pick_kinds(logits, keys, temps, tks, tps):
        """:func:`_pick` for a mixed call's ``1 + B`` rows, the chunk's row
        first: each kind's rows are drawn under its own key (the lanes' as
        the decode program draws them), but as ONE batched draw over the two
        keys, the chunk's row in a block of the lanes' shape: a second
        ``categorical`` is a second unrolled threefry in the text of every
        prefill shape's program (0.5 s a program at every start on a TPU
        host, PERF.md, PR 31)."""
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            if use_filters:
                scaled = lane_topk_topp(scaled, tks, tps)
            lanes = scaled[1:]
            blocks = jnp.stack([jnp.zeros_like(lanes).at[0].set(scaled[0]),
                                lanes])
            drawn = jax.vmap(lambda key, rows: jax.random.categorical(
                jax.random.wrap_key_data(key), rows, axis=-1))(
                    jnp.stack(keys), blocks)                    # [2, B]
            sampled = jnp.concatenate([drawn[0, :1], drawn[1]])
            return jnp.where(temps <= 0.0, greedy, sampled)

    def _out(tokens, routed):
        # a dropless mixture: the call's expert counts behind its tokens,
        # and its picks beside them
        if not counting:
            return tokens
        counts, picks = routed
        return jnp.concatenate([tokens.astype(jnp.int32),
                                counts.reshape(-1)]), picks

    def _decode(params, pools, step_in, prev, first):
        toks, ctx, tks, bt, temps, tps, key = layout.decode(step_in)
        toks = layout.lane_tokens(toks, prev, first)
        # toks [B] sit at logical position ctx[b]; after the write the
        # valid length is ctx + 1
        logits, pools, *routed = paged_forward(
            cfg, params, toks[:, None], pools, bt, ctx, ctx + 1, bs,
            interpret=interpret, expert_counts=counting,
            expert_picks=counting)
        return _out(_pick(logits[:, -1], key, temps, tks, tps), routed), pools

    def _prefill(params, pools, step_in):
        ids, bt, q0, ctx, last_idx, tks, temps, tps, key = \
            layout.prefill(step_in)
        logits, pools, *routed = paged_forward(
            cfg, params, ids, pools, bt, q0, ctx, bs, interpret=interpret,
            expert_counts=counting, expert_picks=counting)
        last = jax.lax.dynamic_index_in_dim(logits, last_idx[0], 1,
                                            keepdims=False)   # [1, V]
        return _out(_pick(last, key, temps, tks, tps), routed), pools

    def _mixed(params, pools, step_in, prev, first):
        B = prev.shape[0] - token_words(cfg, 0)
        chunk_in, lanes_in = layout.mixed(step_in, B)
        ids, cbt, q0, cctx, last_idx, ctk, ctemp, ctp, ckey = \
            layout.prefill(chunk_in)
        toks, ctx, tks, bt, temps, tps, key = layout.decode(lanes_in)
        toks = layout.lane_tokens(toks, prev, first)
        T = ids.shape[1]
        # the head reads the chunk's last real row and the lanes' rows
        head_rows = jnp.concatenate([last_idx, T + jnp.arange(B)])
        logits, pools, *routed = mixed_forward(
            cfg, params, ids, toks, pools, (cbt, q0, cctx),
            (bt, ctx, ctx + 1), bs, head_rows, interpret=interpret,
            expert_counts=counting, expert_picks=counting)
        chunk_routed = lanes_routed = None
        if counting:
            counts, picks = routed          # [L, 2, E], [L, T + B, k]
            chunk_routed = counts[:, 0], picks[:, :T]
            lanes_routed = counts[:, 1], picks[:, T:]
        picked = _pick_kinds(logits[0], (ckey, key),
                             *(jnp.concatenate(two) for two in (
                                 (ctemp, temps), (ctk, tks), (ctp, tps))))
        return (_out(picked[1:], lanes_routed),
                _out(picked[:1], chunk_routed)), pools

    return (_decode, _prefill, _mixed) if mixed else (_decode, _prefill)


def token_words(cfg, lanes: int) -> int:
    """Length of the int32 vector a device call over ``lanes`` lanes returns
    (``step_programs``): its tokens, and behind them a dropless mixture's
    ``[L, E]`` expert counts (``[L, E + groups]`` of a grouped router)."""
    return lanes + (cfg.sparse_layers * _count_words(cfg)
                    if cfg.moe_is_dropless else 0)


def _count_words(cfg) -> int:
    """Counts a sparse layer hands out: one a router output and, behind
    them, one a routing group of a grouped router."""
    return cfg.moe_experts + (cfg.moe_groups if cfg.moe_groups > 1 else 0)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _blank_outputs(weight, decode_words: int, prefill_words: int):
    """Zeros in the shapes of a decode and a prefill call's outputs, made by
    a program that reads a weight (and adds nothing of it): what a serving
    program's own outputs are on that weight's device and under its kind of
    sharding, before there is one."""
    return (jnp.zeros((decode_words,), jnp.int32)
            + 0 * weight.ravel()[:1].astype(jnp.int32),
            jnp.zeros((prefill_words,), jnp.int32))


@dataclass
class _Seq:
    """One active lane's owner record: the request and every block it
    holds. The lane's context, last token, sampling parameters and block
    table live in the engine's lane arrays (:class:`_Lanes`), row = the
    lane's index, and nowhere else."""
    req: Request
    blocks: List[int]                  # every block this seq holds


@dataclass
class _Prefilled:
    """A prompt whose K/V is whole in the pool and whose first token is
    sampled, on its way to a decode lane (or, in serving/disagg.py, across
    the handoff): what decode resumes from."""
    req: Request
    blocks: List[int]
    table: np.ndarray                  # [max_blocks_per_seq] i32 physical ids
    ctx: int                           # tokens whose K/V is in the pool
    #: sampled, not yet written back; ``StepLayout.FROM_PREFILL`` while the
    #: prefill call's output is all that holds it
    last_tok: int


class _Lanes:
    """The decode lanes' state on the host, kept between steps IN the
    buffer the decode program gets (:meth:`StepLayout.decode`'s views of
    it): a row is written when a sequence is installed and when its lane is
    freed, the whole is advanced in bulk when a call is launched, and a
    call's build is one copy of the buffer. An idle lane reads token 0,
    context 0, greedy, an all-null table (its write sinks into the null
    block). ``left`` counts, a lane, the tokens that calls not yet launched
    still owe it: the host knows a lane's last call before it is made."""

    def __init__(self, layout: StepLayout, lanes: int):
        self.layout = layout
        self.buf = np.zeros((layout.decode_words(lanes),), np.int32)
        (self.toks, self.ctx, self.tks, self.tables, self.temps, self.tps,
         _) = layout.decode(self.buf)
        self.tables[:] = NULL_BLOCK
        self.tps[:] = 1.0
        self.live = np.zeros((lanes,), bool)
        self.left = np.zeros((lanes,), np.int32)

    def write(self, i: int, seq: _Prefilled, left: int) -> None:
        req = seq.req
        self.toks[i], self.ctx[i] = seq.last_tok, seq.ctx
        self.tables[i] = seq.table
        self.temps[i] = req.temperature
        self.tks[i] = req.top_k or 0
        self.tps[i] = 1.0 if req.top_p is None else req.top_p
        self.live[i], self.left[i] = True, left

    def clear(self, i: int) -> None:
        self.toks[i] = self.ctx[i] = self.tks[i] = self.left[i] = 0
        self.tables[i] = NULL_BLOCK
        self.temps[i], self.tps[i] = 0.0, 1.0
        self.live[i] = False

    def next_call(self) -> np.ndarray:
        """The lanes the next decode call computes: those a token is still
        owed that no launched call produces. A lane whose last token is in
        flight keeps its slot and stays out."""
        return self.live & (self.left > 0)

    def launch(self, go: np.ndarray) -> np.ndarray:
        """The buffer of a decode call over the lanes ``go`` (the copy is
        what the device call owns; a live lane left out reads idle in it),
        and the lanes' state moved past that call: each lane of ``go`` will
        have written one token more and reads its next from the call's own
        output, which only the device holds yet."""
        step_in = self.buf.copy()
        out = self.live & ~go
        if out.any():
            toks, ctx, tks, tables, temps, tps, _ = self.layout.decode(
                step_in)
            toks[out] = ctx[out] = tks[out] = 0
            tables[out] = NULL_BLOCK
            temps[out], tps[out] = 0.0, 1.0
        np.add(self.ctx, go, out=self.ctx)
        np.subtract(self.left, go, out=self.left)
        self.toks[go] = StepLayout.FROM_DECODE
        return step_in


@dataclass
class _ChunkOut:
    """The prefill chunk a step launched, until the step fetches it: its
    output on the device and its call's number; of a prompt's LAST chunk
    also the sequence, and the lane it was staged in (None: none)."""
    out: Any
    call: int
    seq: Optional[_Prefilled] = None
    slot: Optional[int] = None


@dataclass
class _InFlight:
    """A decode call (or a mixed one: the lanes' half of it) launched and
    not yet fetched: its output on the device,
    the lanes whose token of it is still wanted (a lane freed meanwhile is
    struck out), and the call's number; of a dropless mixture also the
    call's picks ``[L, lanes, k]``, on the device."""
    out: Any
    go: np.ndarray
    call: int
    picks: Any = None
    #: a mixed call (:meth:`ServingEngine._mixed_step`): its chunk, whose
    #: output is fetched with the lanes' tokens
    chunk: Optional[_ChunkOut] = None


class _HeldBlocks:
    """Running counts of the pool blocks held by the decode lanes and the
    prompt in prefill, kept where a block list joins or leaves them: what a
    recount over every holder's list would read on entry to a step.
    ``distinct`` counts a forked prefix once however many lanes read it;
    ``reserved`` counts it for each holder."""

    def __init__(self, num_blocks: int):
        self.refs = np.zeros((num_blocks,), np.int32)
        self.distinct = self.reserved = 0

    def add(self, blocks: List[int]) -> None:
        idx = np.asarray(blocks, np.intp)      # distinct within one holder
        self.refs[idx] += 1
        self.distinct += int(np.count_nonzero(self.refs[idx] == 1))
        self.reserved += len(blocks)

    def drop(self, blocks: List[int]) -> None:
        idx = np.asarray(blocks, np.intp)
        self.refs[idx] -= 1
        self.distinct -= int(np.count_nonzero(self.refs[idx] == 0))
        self.reserved -= len(blocks)


@dataclass
class _Prefilling:
    """A prompt mid-chunked-prefill (round 12): blocks are fully
    allocated (admission control is unchanged — lifetime budget up
    front), ``done`` tokens of K/V are in the pool, and each loop
    iteration advances at most ``serving.prefill_chunk_tokens`` more —
    decode steps run in between, so a long prompt never stalls running
    lanes for more than one chunk."""
    req: Request
    blocks: List[int]
    table: np.ndarray
    done: int                          # tokens already in the pool
    total: int                         # == len(req.prompt)


class ServingEngine:
    """Continuous-batching server over a paged KV cache (module docstring).

    ``serving``: a ``config.config.ServingConfig`` (or plain dict of its
    fields). ``interpret=True`` runs the Pallas paged kernel interpreted
    (CPU tests); on the CPU backend the jnp gather reference is used
    automatically.
    """

    #: heartbeat-gauge role tag; disagg subclasses override (visible in
    #: ``dstpu health`` as ``role=PREFILL`` / ``role=DECODE``)
    role: Optional[str] = None

    def __init__(self,
                 cfg: TransformerConfig,
                 params: PyTree,
                 serving=None,
                 heartbeat=None,
                 rng: Optional[jax.Array] = None,
                 interpret: bool = False,
                 shared: Optional[SharedPagedState] = None):
        # the recorder first: all the constructor does lies in serve.init,
        # so set-up and its compiles are recorded like any step
        self.rec = telemetry.Recorder("serve")
        self.stats: Dict[str, int] = self.rec.counters
        self.stats.update(dict.fromkeys(
            _COUNTERS + telemetry.COMPILE_COUNTERS, 0))
        with self.rec.span("serve.init"):
            self._init(cfg, params, serving, heartbeat, rng, interpret,
                       shared)

    def _init(self, cfg, params, serving, heartbeat, rng, interpret,
              shared) -> None:
        from ..config.config import ServingConfig
        if serving is None:
            serving = ServingConfig()
        elif isinstance(serving, dict):
            serving = ServingConfig(**serving)
        self.scfg = serving
        self.cfg = cfg
        bs = int(serving.block_size)
        self.block_size = bs
        self.max_batch = int(serving.max_batch)
        self.max_model_len = min(int(serving.max_blocks_per_seq) * bs,
                                 cfg.max_seq_len)
        self.nbk = -(-self.max_model_len // bs)      # table width
        self.interpret = interpret
        # the windows of the layers that have one (``paged.window_pages_sum``)
        self._windows = np.asarray(
            [w for w in cfg.layer_windows or () if w > 0], np.int64)
        # window (0: none) -> the layers that have it (the indexer's
        # counters and the paged kernel's chunk turns are a window's)
        self._index_windows = collections.Counter(
            max(int(w), 0) for w in cfg.layer_windows or (0,) * cfg.num_layers)
        if cfg.rope_scaling_type == "dynamic":
            # dynamic NTK derives its table from the cache capacity, which
            # differs between the pool (max_blocks_per_seq * block_size)
            # and a one-shot generate() cache — serving would silently
            # break the token-exactness contract; linear/llama3 scaling is
            # length-independent and serves fine
            raise NotImplementedError(
                "serving does not support rope_scaling_type='dynamic' "
                "(length-dependent table); use linear/llama3 scaling or "
                "one-shot generate()")
        self.params = ensure_scan_layout(params, cfg.num_layers)
        kv_dtype = resolve_kv_dtype(serving)
        # int8 pools decode through the Pallas kernel's in-kernel dequant
        # tier (round 17) — the round-12 construction guard is gone.
        if serving.weight_dtype is not None:
            if serving.weight_dtype != "int8":
                raise ValueError(
                    f"serving.weight_dtype {serving.weight_dtype!r}: only "
                    "'int8' (blockwise weight-only) or null")
            # pack ONCE at construction: dense kernels -> blockwise int8
            # + per-256-element f32 scales (quant_format's wire format);
            # the decode matmuls then ride ops/pallas/quant_matmul and
            # never materialize a full-precision weight copy
            from ..ops.pallas.quant_matmul import pack_decode_weights
            self.params = pack_decode_weights(self.params)
        # a dropless MoE model only: the outputs of calls nobody fetched
        # (the disagg prefill role's middle chunks) stay on the device with
        # their expert counts, (call number, output), and ride the next
        # fetch of a later call
        self._moe_pending: List[Any] = []
        if cfg.moe_is_dropless:
            self.stats.update(dict.fromkeys(_MOE_COUNTERS, 0))
        if cfg.index_heads:
            self.stats.update(dict.fromkeys(_SPARSE_COUNTERS, 0))
        if cfg.moe_is_dropless and cfg.moe_groups > 1:
            self.stats.update(dict.fromkeys(_GROUP_COUNTERS, 0))
        if cfg.kv_lora_rank:
            self.stats.update(dict.fromkeys(_MLA_COUNTERS, 0))
            if cfg.index_heads:
                self.stats.update(dict.fromkeys(_MLA_SELECT_COUNTERS, 0))
        # the paged-KV state: PRIVATE by default, SHARED when a
        # disaggregated pair (serving/disagg.py) passes one in — block
        # IDs then mean the same pool slots to both roles, which is what
        # makes the prefill->decode handoff zero-copy
        self._shared = shared if shared is not None else SharedPagedState(
            cfg, self.params, serving, dtype=kv_dtype, counters=self.stats)
        # what a token costs the pool, from the pool itself (a grouped-query
        # model's is stored at its KV heads): 2 x layers x stored heads x
        # head_dim x item size, the int8 tier's scales and an indexer's one
        # key a layer (the ``ki`` leaf) with it
        pool_k = self.pools["ckv" if cfg.kv_lora_rank else "k"]
        self.rec.gauge("kv.stored_heads", int(pool_k.shape[1]))
        self.rec.gauge("kv.bytes_per_token", sum(
            a.size * a.dtype.itemsize for a in self.pools.values())
            // pool_k.shape[2])
        if cfg.kv_lora_rank:
            # a latent model's one row a token and layer, as the pool lays
            # it out (its width rounded up to whole 128-lane tiles)
            self.rec.gauge("kv.latent_lanes", int(pool_k.shape[3]))
        self.scheduler = Scheduler(self.pool, serving.max_queue,
                                   self.max_model_len, self.prefix_cache,
                                   aging_s=serving.fleet.priority_aging_s,
                                   batch_highwater=serving.fleet
                                   .batch_highwater, rec=self.rec)
        self._slots: List[Optional[_Seq]] = [None] * self.max_batch
        self._prefilling: Optional[_Prefilling] = None
        self._held = _HeldBlocks(self.pool.num_blocks)
        self._warming = False      # role warms: no prefix-cache inserts
        self._chunk = int(serving.prefill_chunk_tokens)
        self._use_filters = bool(serving.sampling_filters)
        # the base sampling key, as raw words on the host: every device
        # call's own key is derived from it and the call's number HERE
        # (_call_key) and rides the call's buffer, so the loop dispatches
        # nothing for a key (the one eager call is this one)
        if rng is None:
            rng = jax.random.PRNGKey(serving.seed)
        self._key = np.asarray(jax.random.key_data(rng),
                               np.uint32).reshape(-1)
        self._layout = StepLayout(self.nbk, self._key.size)
        self._lanes = _Lanes(self._layout, self.max_batch)
        self._calls = 0                    # device calls made so far
        # the decode call launched and not yet fetched, and this step's
        # chunk until it is fetched: both behind the launch of the next
        # decode call
        self._flight: Optional[_InFlight] = None
        self._chunk_out: Optional[_ChunkOut] = None
        # what the decode program reads its lanes' tokens from when the host
        # never held them: the outputs of the last decode call and of the
        # last final prefill call, as they came off the device. Until there
        # is one, zeros that a program made FROM THE WEIGHTS, as those two
        # make their outputs: placed and committed the way theirs will be
        # (on the weights' device, under the weights' kind of sharding), so
        # that the first decode call and every later one are ONE
        # specialization
        self._dec_out, self._pre_out = _blank_outputs(
            jax.tree_util.tree_leaves(self.params)[0],
            token_words(cfg, self.max_batch), token_words(cfg, 1))
        self._prefill_shapes: set = set()  # query rows of the prefill calls
        # ... -> ``paged_attention.chunk_plan`` of those the paged kernel
        # takes: programs a lane, pages a copy group, keys a lane tile
        self._chunk_plans: dict = {}
        self._heartbeat = heartbeat
        self._watchdog = None
        self._lock = threading.Lock()
        self.steps = 0                     # decode steps executed

        # ---- compiled programs (fixed shapes; ONE decode specialization) ----
        # where the cache is not latent, a step that advances a prefill
        # chunk is ONE program, the chunk's rows and the lanes' together
        # (_mixed_step); a latent model's chunk and lanes keep a program
        # each, and the third is never built for it
        _decode, _prefill, *_mixed = step_programs(
            cfg, bs, self.nbk, interpret=self.interpret,
            use_filters=self._use_filters, key_words=self._key.size,
            mixed=self._chunk > 0 and not cfg.kv_lora_rank)
        # pools are donated: the loop's only live copy moves through the
        # step, so the update is in-place on TPU (no 2x pool HBM)
        self._decode_fn = jax.jit(_decode, donate_argnums=(1,))
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._mixed_fn = self._mixed_program = None
        if _mixed:
            self._mixed_fn = jax.jit(_mixed[0], donate_argnums=(1,))
            self._mixed_program = "jit_" + _mixed[0].__name__
        # a third, for a request that asked for its routing of a model with
        # an indexer: a prompt chunk's selection from bits to positions
        self._positions_fn = positions_of_bits
        # the names a device trace's "XLA Modules" line gives their runs:
        # a dispatch span carries its program's, to be paired with the run
        self._decode_program = "jit_" + _decode.__name__
        self._prefill_program = "jit_" + _prefill.__name__
        log_dist(
            f"ServingEngine: pool={serving.pool_blocks}x{bs} tokens "
            f"(~{(serving.pool_blocks - 1) * bs} cacheable, "
            f"{self.rec.gauges['kv.stored_heads']} stored heads, "
            f"{self.rec.gauges['kv.bytes_per_token']} bytes a token"
            + (f", an indexer key of {cfg.index_head_dim} on "
               f"{self.pools['ki'].shape[-1]} lanes a layer among them"
               if cfg.index_heads else "")
            + (f": one latent row of {cfg.latent_width} on "
               f"{pool_k.shape[3]} lanes a layer"
               if cfg.kv_lora_rank else "") + "), "
            f"max_batch={self.max_batch}, max_model_len="
            f"{self.max_model_len}, prefix_cache={serving.prefix_cache}, "
            f"prefill_chunk={self._chunk or 'whole'}",
            ranks=[0])

    # -- the paged-KV state, possibly SHARED with a disagg partner role --

    @property
    def pool(self):
        return self._shared.pool

    @property
    def pools(self):
        return self._shared.pools

    @property
    def prefix_cache(self):
        return self._shared.prefix_cache

    def _run_device(self, fn, *args):
        """One jitted call over the live pool buffers (donation-safe
        under the shared state's device lock)."""
        return self._shared.run(fn, self.params, *args)

    def _call_key(self, call: int, *kind: int) -> np.ndarray:
        """The sampling key of device call number ``call``, as raw words:
        numpy's ``SeedSequence`` hashes (base key, call) into them, on the
        host (``kind``: a second sampler of the call, a mixed call's chunk
        beside its lanes). Same seed and same calls, same keys, run to run. (Not
        ``jax.random.fold_in`` inside the programs: lowering its unrolled
        threefry costs 0.5 s a program at every start on a TPU host,
        PERF.md, PR 31; and not an eager split: a device program of its own
        between two steps.)"""
        return np.random.SeedSequence(
            self._key.tolist(), spawn_key=(call, *kind)).generate_state(
                self._key.size, np.uint32)

    def _call_device(self, fn, step_in: np.ndarray, *on_device):
        """One device call of the loop: number it, write its key into the
        buffer's last words (:class:`StepLayout`) and hand the program its
        one host array as it is, for the jitted call to transfer
        (``on_device``: what the decode program reads where it lies)."""
        self._calls += 1
        step_in[-self._key.size:] = self._call_key(self._calls).view(np.int32)
        self.stats["step_inputs.transfers_sum"] += 1
        out = self._run_device(fn, step_in, *on_device)
        if self.cfg.moe_is_dropless:
            # the call's picks stay on the device, for a request that asked
            out, self._picks_out = out
        return out

    def _count_selection(self, extent: np.ndarray, real, pages: int,
                         decode: bool = False) -> None:
        """A model with an indexer: a call's query rows as its kernels tile
        them (padding rows and idle lanes too), ``extent`` one past each
        row's last visible position; ``real`` indexes the rows that are fed
        a token, each seeing that many keys, its own included; the ``pages``
        the call's attention walks; in every layer
        (:data:`_SPARSE_COUNTERS`). ``decode``: a call of one row a lane,
        whose score programs walk the lanes' own key tiles."""
        cfg = self.cfg
        if not cfg.index_heads:
            return
        L, c, seen = cfg.num_layers, self.stats, extent[real]
        c["sparse.rows_sum"] += L * int(seen.size)
        c["sparse.keys_scored_sum"] += L * int(seen.sum())
        c["sparse.keys_selected_sum"] += L * int(
            np.minimum(seen, cfg.index_topk).sum())
        c["sparse.pages_walked_sum"] += L * int(pages)
        # what ``sparse_topk`` does with them, by its own rule: a layer's
        # window clips the keys a row counts, not where its last one lies
        Kp = sparse_select.padded_keys(self.nbk * self.block_size)
        for window, layers in self._index_windows.items():
            tiles = sparse_select.topk_tiles(
                np.minimum(extent, window) if window else extent, extent,
                cfg.index_topk, Kp, np)
            busy = tiles[tiles > 0]
            c["sparse.topk_tiles_sum"] += layers * tiles.size
            c["sparse.topk_tiles_idle_sum"] += layers * (tiles.size
                                                         - busy.size)
            c["sparse.topk_columns_sum"] += layers * int(
                sparse_select.topk_columns(busy, Kp).sum())
            c["sparse.topk_columns_table_sum"] += layers * busy.size * Kp
            if decode:
                # ``sparse_index_scores`` by its own rule: the key tiles a
                # lane's program copies and multiplies, beside lanes x the
                # table's tiles (what a grid over the table launched)
                first, end = sparse_select.score_tiles(
                    extent - 1, extent, window, Kp, np)
                c["sparse.score_tiles_sum"] += layers * int(
                    (end - first).sum())
                c["sparse.score_tiles_table_sum"] += layers * int(
                    extent.size * sparse_select.score_tiles(
                        Kp - 1, Kp, 0, Kp, np)[1])

    def _count_latent(self, extent: np.ndarray, pages: int,
                      chunk: int = 0) -> None:
        """A latent model: a call's REAL query rows, ``extent`` the cached
        tokens each attends (its own included), and the ``pages`` the call's
        attention walks; in every layer (:data:`_MLA_COUNTERS`). ``chunk``: a
        prefill call's padded rows."""
        cfg = self.cfg
        if not cfg.kv_lora_rank:
            return
        L, c = cfg.num_layers, self.stats
        c["mla.rows_sum"] += L * int(extent.size)
        c["mla.ctx_tokens_sum"] += L * int(extent.sum())
        c["mla.pages_walked_sum"] += L * int(pages)
        if cfg.index_heads:
            picked = L * int(np.minimum(extent, cfg.index_topk).sum())
            c["mla.selected_keys_sum"] += picked
            if chunk:
                c["mla.chunk_selected_keys_sum"] += picked
        if chunk:
            c["mla.chunk_ctx_tokens_sum"] += L * int(extent.sum())
            c["mla.chunk_keys_sum"] += L * int(extent.max())
            if self._latent_prefill_path(chunk)[0] == "kernel" \
                    and latent_form(chunk) == "expanded":
                c["mla.chunk_expanded_keys_sum"] += L * chunk_expanded_keys(
                    chunk, int(extent[0]) - 1, int(extent.max()))
                if cfg.index_heads:
                    pairs, live = chunk_key_tiles(
                        chunk, int(extent[0]) - 1, int(extent.max()),
                        self.block_size, self.nbk)
                    c["mla.chunk_key_tiles_sum"] += L * pairs
                    c["mla.chunk_key_tiles_live_sum"] += L * live

    def _count_experts(self, out: np.ndarray, call: int) -> None:
        """A dropless MoE model's router load, from the fetched output of
        device call number ``call``: its ``[L, E]`` expert counts sit behind
        its tokens (``step_programs``). Those of the calls nobody fetched
        that were launched before it are counted with it: the device runs
        calls in the order of their launch, so fetching them waits for
        nothing more (a chunk launched behind a decode call in flight waits
        for the next fetch)."""
        pending = self._moe_pending
        n = sum(1 for at, _ in pending if at < call)
        got = [out] + [np.asarray(a) for _, a in pending[:n]]
        del pending[:n]
        E, c = self.cfg.moe_experts, self.stats
        first, held_n = self.cfg.moe_held or (0, E)
        words, size = _count_words(self.cfg), E // self.cfg.moe_groups
        for a in got:
            counts = a[len(a) - self.cfg.sparse_layers * words:].reshape(
                -1, words)
            if words > E:
                # a grouped router: the (row, group) pairs whose row kept a
                # group this program lies in (a share is whole groups, or a
                # part of one; one group a device or more, so a row counts
                # once)
                counts, kept = counts[:, :E], counts[:, E:]
                c["moe.group_rows_sum"] += int(
                    kept[:, first // size:-(-(first + held_n) // size)].sum())
            routed = counts.sum(axis=1)
            live = routed > 0               # a call of padding only: nothing
            c["moe.assignments"] += int(routed.sum())
            c["moe.layer_steps"] += int(live.sum())
            # load and idleness are of the experts HELD: the kernel's groups
            held = counts[:, first:first + held_n]
            rows = held.sum(axis=1)
            c["moe.held_assignments"] += int(rows.sum())
            fed = rows > 0
            c["moe.load_max_over_mean_sum"] += float(
                (held.max(axis=1)[fed] * held_n / rows[fed]).sum())
            c["moe.experts_idle_sum"] += int((held[live] == 0).sum())

    # ------------------------------------------------------------- submission

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0, eos_token_id: Optional[int] = None,
               on_finish=None, top_k=None, top_p=None,
               deadline_s: Optional[float] = None,
               priority: str = STANDARD,
               keep_routing: bool = False) -> Request:
        """Enqueue a generation request (thread-safe); returns the live
        :class:`Request` whose ``output_tokens``/``state`` the caller (or
        ``on_finish``) observes. ``deadline_s`` is a queue-wait TTL: a
        request still QUEUED that long after arrival is shed with a
        TIMEOUT result instead of waiting behind a too-big head forever
        (admitted requests always run to completion). ``priority``
        (round 19) picks the latency/standard/batch tier — dispatch
        order and the overload ladder's shed order; see
        docs/SERVING.md §Priority.

        ``keep_routing`` (a dropless mixture; a dense model ignores it):
        the finished request carries ``routed_experts``, an int32 array
        ``[len(prompt) + len(output_tokens) - 1, layers, k]``: for every
        token the model was FED (the prompt, then every generated token but
        the last) the experts each mixture layer picked, best first. A
        prompt position whose K/V came from the prefix cache was never
        computed and reads -1. For a model with an indexer
        (``cfg.index_heads``) the array is ``[..., layers, k + index_topk]``:
        a token's routing, its experts and then the positions of the keys it
        attended in that layer, rising, -1 behind the row's own count (a row
        that sees no more than ``index_topk`` keys lists them all); its
        ``layers`` are those with picks OR a selection (``cfg.
        routed_layers``: a mixture's leading dense layer with an indexer
        hands out a row whose picks read -1, before the sparse layers'). The
        device hands the selection out as bits, 32 keys a word, beside the
        picks of every call; the positions are made from them when the
        request finishes (a prompt chunk's on the device, by a sort). Costs
        one small fetch a device call that carries the request
        (``routing.fetches``); a request that does not ask costs none. Not
        carried through a fleet's requeue or a disagg handoff.

        ``top_k``/``top_p`` (round 12) require
        ``serving.sampling_filters`` — the vectorized per-lane filter
        rides the compiled decode step (one program for any mix of
        filtered/greedy lanes); with the flag off they raise, as the
        filter would put a [B, V] sort in every decode step."""
        if (top_k is not None or top_p is not None) \
                and not self._use_filters:
            raise NotImplementedError(
                "per-lane top_k/top_p need serving.sampling_filters=true "
                "(the nucleus filter adds a [B, V] sort to the compiled "
                "decode step); without it use greedy/temperature or "
                "one-shot generate()")
        if priority not in TIER_RANK:
            raise ValueError(f"unknown priority tier {priority!r}; pick "
                             f"one of {PRIORITY_TIERS}")
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature),
                      top_k=int(top_k) if top_k is not None else None,
                      top_p=float(top_p) if top_p is not None else None,
                      eos_token_id=eos_token_id, on_finish=on_finish,
                      priority=priority,
                      keep_routing=bool(keep_routing)
                      and self.cfg.moe_is_dropless)
        if deadline_s is not None:
            req.deadline_ts = req.arrival_ts + float(deadline_s)
        with self.rec.span("serve.submit", rid=req.rid):
            return self.scheduler.submit(req)

    # -------------------------------------------------------------- the loop

    @property
    def active(self) -> int:
        return int(np.count_nonzero(self._lanes.live))

    @property
    def idle(self) -> bool:
        # a call in flight is work: the step that fetches it is still due
        return (self.active == 0 and self.scheduler.pending == 0
                and self._prefilling is None and self._flight is None)

    @property
    def has_work(self) -> bool:
        """Would a :meth:`step` make progress? (fleet worker pacing)."""
        return bool(self.active or self.scheduler.pending
                    or self._prefilling is not None
                    or self._flight is not None)

    @property
    def wants_dispatch(self) -> bool:
        """Should the fleet hand this engine another request? Keeping the
        per-engine queue empty IS the load balancing."""
        return self.scheduler.pending == 0 and self.active < self.max_batch

    def held_state(self, timeout: float = 1.0):
        """Death-path collection (disagg fleet): atomically detach and
        return ``(block_lists, requests)`` for every sequence this engine
        holds — decode lanes and any in-flight prefill — so a dead
        replica's share of a SHARED pool can be released once its thread
        is provably gone (releasing earlier could race the abandoned
        worker's final in-flight step). Returns None if the engine lock
        cannot be taken within ``timeout`` (a wedge inside a step): the
        caller parks and retries."""
        if not self._lock.acquire(timeout=timeout):
            return None
        try:
            # the call in flight is dropped unread: the requests resume from
            # prompt + emitted. Its K/V rows fall into blocks listed below;
            # whoever gets them next launches later (_finish)
            flight = self._flight
            self._retire(book=False)
            blocks: List[List[int]] = []
            reqs: List[Request] = []
            chunk, self._chunk_out = self._chunk_out, None
            if chunk is None and flight is not None:
                chunk = flight.chunk        # a mixed call's, dropped with it
            # (a step died between a chunk's launch and its fetch) a staged
            # lane is among the slots, a middle chunk's prompt is the one in
            # prefill
            if chunk is not None and chunk.seq is not None \
                    and chunk.slot is None:
                blocks.append(chunk.seq.blocks)
                reqs.append(chunk.seq.req)
            if self._prefilling is not None:
                blocks.append(self._prefilling.blocks)
                reqs.append(self._prefilling.req)
                self._set_prefilling(None)
            for i, s in enumerate(self._slots):
                if s is not None:
                    blocks.append(s.blocks)
                    reqs.append(s.req)
                    self._vacate(i)
            self._collect_held(blocks, reqs)
            return blocks, reqs
        finally:
            self._lock.release()

    def _collect_held(self, blocks, reqs) -> None:
        """Subclass hook: detach role-specific block holders (runs under
        the engine lock inside :meth:`held_state`)."""

    def preempt_request(self, req: Request, timeout: float = 1.0) -> bool:
        """Evict ONE running decode lane mid-generation (round 19 tier
        preemption): under the engine lock the lane's blocks return to
        the pool, the slot frees, and the request reverts to QUEUED with
        its emitted tokens intact — the fleet's exactly-once requeue
        path resumes it from prompt + emitted, exactly the death-path
        contract (tokens decoded but never synced are dropped and
        regenerated identically under greedy). Only a RUNNING lane is
        preemptible: an in-flight prefill is about to finish paying for
        its blocks and evicting it frees no lane. The decode call in
        flight is retired first, its tokens booked: the request may finish
        by them, and then holds no lane. Returns False when the
        request holds no lane here or the lock cannot be taken within
        ``timeout`` (a step in flight — the caller retries next poll)."""
        if not self._lock.acquire(timeout=timeout):
            return False
        try:
            self._retire()
            for i, s in enumerate(self._slots):
                if s is not None and s.req is req:
                    self._vacate(i)
                    self.pool.release(s.blocks)
                    req.state = QUEUED
                    self.stats["preempted"] += 1
                    return True
            return False
        finally:
            self._lock.release()

    def cancel_request(self, req: Request, timeout: float = 1.0) -> bool:
        """Withdraw a request wholesale (the process fleet's ``cancel``
        command): drop it from the scheduler queue if still queued, else
        evict its running lane. Never concludes the request — the hub
        owns its ledger and requeues it elsewhere."""
        if self.scheduler.withdraw(req):
            return True
        return self.preempt_request(req, timeout=timeout)

    def step(self) -> int:
        """One loop iteration: admit (whole prefill, or START a chunked
        one), advance an in-flight chunked prefill by AT MOST one chunk,
        then one fixed-shape decode step over the active set — so with
        ``serving.prefill_chunk_tokens > 0`` running lanes emit a token
        every iteration even while a long prompt prefills (the fairness
        bound tests pin). Where the cache is not latent the chunk and the
        decode step are ONE device call (:meth:`_mixed_step`: the weights
        are read once); a latent model's are two. The decode step LAUNCHES
        the next call before it
        fetches the one the last iteration launched
        (:meth:`_decode_step`): an iteration returns with the tokens of the
        call it retired appended, and with one call in flight wherever a
        lane goes on. Returns requests completed this iteration."""
        with self._lock, self._step_span():
            done = self._admit()
            if self._mixed_fn is not None and self._prefilling is not None:
                done += self._mixed_step()
            else:
                self._advance_prefill()
                done += self._decode_step()
            self.steps += 1
            self.stats["timeout"] = self.scheduler.timed_out
            self._stamp_heartbeat()
            return done

    def _step_span(self):
        """The span of one ``step()`` (caller holds the engine lock), and
        the step's readings of queue, lanes and KV reservation, taken on
        entry: what an outside observer would count before calling
        ``step()``."""
        span = self.rec.step_span("serve.step", step=self.steps)
        c = self.stats
        c["steps"] += 1
        queued = self.scheduler.pending
        if queued:
            c["steps_with_queue"] += 1
            c["queue_len_sum"] += queued
        c["lane_sum"] += self.active
        # an idle lane's context reads 0; a lane of the call in flight is
        # one ahead of what the host has booked
        written = int(self._lanes.ctx.sum())
        if self._flight is not None:
            written -= int(np.count_nonzero(self._flight.go))
        if self._prefilling is not None:
            written += self._prefilling.done
        # distinct blocks: a forked prefix is held once however many
        # lanes read it; the reservation counts it for each holder, as
        # ``ctx`` counts its tokens for each (running counts: _HeldBlocks)
        held = self._held.distinct
        c["kv.held_blocks_sum"] += held
        c["kv.blocks_reserved_sum"] += self._held.reserved
        c["kv.tokens_written_sum"] += written
        if held > self.rec.gauges.get("kv.held_blocks_peak", 0):
            self.rec.gauge("kv.held_blocks_peak", held)
        return span

    def _set_prefilling(self, pf: Optional[_Prefilling]) -> None:
        """The prompt in prefill joins or leaves the holders of blocks."""
        if self._prefilling is not None:
            self._held.drop(self._prefilling.blocks)
        self._prefilling = pf
        if pf is not None:
            self._held.add(pf.blocks)

    def _place(self, slot: int, seq: _Prefilled) -> None:
        """A sequence takes a decode lane: its row of the lane arrays is
        written once, here, and only advanced after that. Its first token
        is among its outputs already, or still on the device
        (``FROM_PREFILL``) and owed like the rest."""
        req = seq.req
        req.state = RUNNING
        self._slots[slot] = _Seq(req, seq.blocks)
        self._lanes.write(slot, seq, req.max_new_tokens
                          - len(req.output_tokens) - (seq.last_tok < 0))
        self._held.add(seq.blocks)
        self.stats["step_inputs.lane_rows_written_sum"] += 1

    def _vacate(self, slot: int) -> None:
        """A lane is freed (finished, preempted, collected): its row reads
        idle again. The blocks' release is the caller's. A lane of the call
        in flight (an end the host could not count ahead: EOS) is struck
        out of it: the call computes the lane once more and nobody reads
        that token."""
        self._held.drop(self._slots[slot].blocks)
        self._slots[slot] = None
        self._lanes.clear(slot)
        self.stats["step_inputs.lane_rows_written_sum"] += 1
        if self._flight is not None and self._flight.go[slot]:
            self._flight.go[slot] = False
            self.stats["decode_ahead.wasted_lane_tokens"] += 1

    def telemetry(self) -> Dict[str, Any]:
        """The recorder's snapshot: counters (``stats`` and, with a shared
        paged state, that state's ``kv.*`` / ``prefix.*``), gauges, and
        per-span count / total / self time over the ring.
        ``srv.rec.dump(path)`` writes the ring itself."""
        snap = self.rec.snapshot()
        if self.pool.counters is not self.stats:
            snap["counters"] = {**self.pool.counters, **snap["counters"]}
        return snap

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        """Drive the loop until queue and lanes drain (tests, batch use)
        and no call is in flight (``idle``)."""
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError(f"serving loop not idle after {max_steps} steps")

    def run_forever(self, stop=None, idle_wait: float = 0.01) -> None:
        """The long-lived server entry: iterate until ``stop`` (a
        ``threading.Event``) is set, idle-waiting (and still stamping the
        SERVE heartbeat) between requests. The loop's EXIT is always
        stamped as a terminal heartbeat via :meth:`close` — a finished
        serving loop must read as a conclusion, never as rc-117 silence
        (``dstpu health`` shows ``clean exit``, not ``SILENT``)."""
        stop = stop if stop is not None else threading.Event()
        try:
            while not stop.is_set():
                if self.idle:
                    with self._lock:
                        self._beat()      # no span: idling is not recorded
                    stop.wait(idle_wait)
                    continue
                self.step()
        finally:
            self.close()

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        # context-manager exit IS the loop exit: stamp the EXIT terminal
        # heartbeat so a drained-and-abandoned server never reads silent
        self.close()

    def generate_batch(self, prompts: Sequence[Sequence[int]],
                       max_new_tokens: int = 32, temperature: float = 0.0,
                       eos_token_id=None) -> List[List[int]]:
        """Convenience: submit all, drain, return outputs in order."""
        reqs = [self.submit(p, max_new_tokens, temperature,
                            eos_token_id=eos_token_id) for p in prompts]
        self.run_until_idle()
        return [r.output_tokens for r in reqs]

    # ----------------------------------------------------------- supervision

    def arm_watchdog(self, serve_timeout: float, **kw):
        """PR-6 stack: a serving loop that stops iterating for
        ``serve_timeout`` seconds is a wedge — rc 117, stack dumps, the
        launcher tears the world down."""
        from ..runtime.watchdog import StallWatchdog
        self._watchdog = StallWatchdog(
            stall_timeout=0.0, phase_timeouts={PHASE_SERVE: serve_timeout},
            phase=PHASE_SERVE, heartbeat=self._heartbeat, **kw).start()
        return self._watchdog

    def close(self) -> None:
        # the call in flight is dropped unread (a loop wedged inside a step
        # keeps its lock and its call)
        if self._lock.acquire(timeout=1.0):
            try:
                self._retire(book=False)
            finally:
                self._lock.release()
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self._heartbeat is not None:
            try:
                from ..runtime.heartbeat import PHASE_EXIT
                self._heartbeat.stamp_terminal(PHASE_EXIT)
            except Exception:
                pass

    def _stamp_heartbeat(self) -> None:
        with self.rec.span("serve.heartbeat"):
            self._beat()

    def _beat(self) -> None:
        if self._watchdog is not None:
            self._watchdog.beat(self.steps)
        if self._heartbeat is not None:
            try:
                # queue-depth / active-lane gauges ride the record so
                # `dstpu health` shows load, not just liveness; disagg
                # roles also stamp role=PREFILL/DECODE
                gauges = {"queue": self.scheduler.pending,
                          "active": self.active,
                          "lanes": self.max_batch}
                if self.role is not None:
                    gauges["role"] = self.role
                self._heartbeat.write(PHASE_SERVE, self.steps, extra=gauges)
            except Exception:
                pass                      # diagnostics must not kill serving

    # ------------------------------------------------------------- admission

    def _free_slot(self) -> Optional[int]:
        i = int(np.argmin(self._lanes.live))       # the first idle lane
        return None if self._lanes.live[i] else i

    def _admission_capacity(self) -> bool:
        """Can a new prefill begin? Base engine: a free decode lane (the
        finished prefill needs one); disagg roles override."""
        return self._free_slot() is not None

    def _admit(self) -> int:
        """Fill free lanes from the queue head; returns requests that
        FINISHED during admission (max_new_tokens == 1 one-shots).
        Expired queued requests are shed first, even with every lane
        busy — the deadline bounds queue wait precisely when nothing can
        be admitted. With chunked prefill armed, admission only STARTS a
        prefill (allocates the lifetime blocks); the chunks themselves
        run one per loop iteration in :meth:`_advance_prefill`, so at
        most ONE request is admitted per iteration and decode is never
        blocked behind a whole long prompt. A pass that admits nobody
        while requests wait counts its one cause
        (``admit_blocked.prefilling`` / ``.no_lane`` / ``.no_blocks``)."""
        with self.rec.span("serve.admit"):
            with self.rec.span("serve.shed"):
                self.scheduler.shed_expired()
            stopped_by, admitted, done = self._admit_pass()
            if not admitted and self.scheduler.pending:
                self.stats["admit_blocked." + stopped_by] += 1
            return done

    def _admit_pass(self):
        """(what stopped the pass, requests admitted, requests finished)."""
        admitted = done = 0
        if self._chunked_mode():
            if self._prefilling is not None:
                return "prefilling", admitted, done
            if not self._admission_capacity():
                return "no_lane", admitted, done
            req = self.scheduler.next_admission()
            if req is None:
                return "no_blocks", admitted, done
            try:
                self._set_prefilling(self._start_prefill(req))
            except (BlockPoolExhausted, chaos.ChaosError) as e:
                logger.warning("serving: admission of request %d "
                               "deferred (%s)", req.rid, e)
                self.scheduler.requeue_front(req)
                return "no_blocks", admitted, done
            return "prefilling", 1, done
        while self._free_slot() is not None:
            req = self.scheduler.next_admission()
            if req is None:
                return "no_blocks", admitted, done
            try:
                done += self._prefill_request(req)
                admitted += 1
            except (BlockPoolExhausted, chaos.ChaosError) as e:
                # transient (chaos 'serve.oom' or a racing allocation):
                # the request goes back to the HEAD — queued, not crashed
                logger.warning("serving: admission of request %d deferred "
                               "(%s)", req.rid, e)
                self.scheduler.requeue_front(req)
                return "no_blocks", admitted, done
        return "no_lane", admitted, done

    def _reserve(self, req: Request):
        """Admission's reservation: fork what the prefix cache holds of
        the prompt, allocate the rest of the request's LIFETIME blocks,
        and stamp the end of its queue wait. Returns (prefix tokens,
        blocks, block table)."""
        with self.rec.span("serve.admit.alloc", rid=req.rid):
            P = len(req.prompt)
            req.state = PREFILL
            n_pref, forked = (self.prefix_cache.match(req.prompt)
                              if self.prefix_cache is not None else (0, []))
            try:
                total_blocks = self.pool.blocks_for_tokens(
                    P + max(req.max_new_tokens - 1, 0))
                priv = self.pool.alloc(total_blocks - len(forked))
            except BaseException:
                if forked:
                    self.pool.release(forked)
                req.state = QUEUED
                raise
            blocks = list(forked) + priv
            table = np.full((self.nbk,), NULL_BLOCK, np.int32)
            table[:len(blocks)] = blocks
            req.prefix_hit_tokens = n_pref
            req.prefill_progress = n_pref
            self.stats["prefix_hit_tokens"] += n_pref
            self.stats["prefix.prompt_tokens"] += P
            req.admitted_ts = time.monotonic()
            self.rec.event("serve.req.admitted", rid=req.rid,
                           arrival_ts=req.arrival_ts, ts=req.admitted_ts)
            return n_pref, blocks, table

    def _chunked_mode(self) -> bool:
        return self._chunk > 0

    def _start_prefill(self, req: Request) -> _Prefilling:
        """Allocate a request's LIFETIME blocks (admission control is
        identical to whole prefill) and stage it for chunked prefill."""
        n_pref, blocks, table = self._reserve(req)
        return _Prefilling(req, blocks, table, done=n_pref,
                           total=len(req.prompt))

    def _advance_prefill(self) -> None:
        """Run AT MOST one chunk of the in-flight chunked prefill (the
        ``serve.chunk`` failpoint fires per chunk). On the final chunk
        the next token is sampled from the last real position's logits
        and the sequence is staged (:meth:`_stage`: a decode lane here,
        its token still on the device); the token is fetched behind the
        launch of the next decode call (:meth:`_take_chunk`)."""
        pf = self._prefilling
        if pf is None:
            return
        req = pf.req
        n = (pf.total - pf.done if self._chunk <= 0
             else min(self._chunk, pf.total - pf.done))
        with self.rec.span("serve.prefill", rid=req.rid, tokens=n,
                           final=int(pf.done + n >= pf.total)):
            self._prefill_chunk(pf, n)

    def _prefill_inputs(self, req: Request, toks: Sequence[int], table,
                        q0: int) -> np.ndarray:
        """A prefill call's one buffer: ``toks`` (padded to
        :meth:`_prefill_rows`, so the compile count is bounded by the
        table's width) at positions ``q0 ..`` of ``req``'s ``table``. A new
        buffer each call: a middle chunk's call is fetched by nobody, so its
        transfer may still be in flight when the next is built."""
        n = len(toks)
        Tb = self._prefill_rows(n)
        self.rec.count("paged.chunk_live_pages_sum",
                       -(-(q0 + n) // self.block_size))
        self.rec.count("paged.chunk_table_pages_sum", self.nbk)
        self._count_selection(np.minimum(q0 + 1 + np.arange(Tb), q0 + n),
                              slice(n), -(-(q0 + n) // self.block_size))
        self._count_latent(q0 + 1 + np.arange(n),
                           -(-(q0 + n) // self.block_size), chunk=Tb)
        if Tb not in self._prefill_shapes:
            self._note_prefill_path(Tb)
        self._count_chunk_turns(q0, n, Tb)
        buf = np.zeros((self._layout.prefill_words(Tb),), np.int32)
        ids, bt, first, ctx, last_idx, tk, temp, tp, _ = \
            self._layout.prefill(buf)
        ids[0, :n] = toks
        bt[0] = table
        first[0], ctx[0], last_idx[0] = q0, q0 + n, n - 1
        temp[0] = req.temperature
        tk[0] = req.top_k or 0
        tp[0] = 1.0 if req.top_p is None else req.top_p
        return buf

    def _prefill_rows(self, n: int) -> int:
        """Rows a prefill call of ``n`` tokens brings: the rows its cache's
        chunk kernel computes anyway. A latent cache's takes a call's rows
        as whole 256-row tiles (``latent_attention.chunk_tiles``: fewer are
        padded there, and under a selection their scores copied, a layer),
        so a chunk of 1 536 has six programs to trace, lower and compile at
        a start where whole blocks of 32 have forty-eight; K/V pools' takes
        whole blocks, and keeps them (a short prompt's last chunk padded to
        a tile would be rows through every matmul of a mixed step for
        nothing). ``prefill_rows`` beside ``prefill_tokens`` counts what
        either rule pads."""
        if self.cfg.kv_lora_rank:
            tiles, per = chunk_tiles(n)
            return tiles * per
        return -(-n // self.block_size) * self.block_size

    def _note_prefill_path(self, Tb: int) -> None:
        """Gauge ``paged.prefill_path``: which way the attention of the
        prefill programs goes, ``{"kernel" | "reference[: why]": [query rows
        of the programs that went it]}``, from the shapes alone, as the
        dispatcher decides it when a program is traced. A latent model's
        kernel is named with the form a chunk's rows take in it: ``"kernel
        (expanded)"``."""
        from ..ops.attention import paged_attention_path
        cfg = self.cfg
        if cfg.kv_lora_rank:
            path, why = self._latent_prefill_path(Tb)
            if path == "kernel":
                path = f"kernel ({latent_form(Tb)})"
        else:
            pool = self.pools["k"]
            path, why = paged_attention_path(
                (1, cfg.num_heads, Tb, cfg.head_dim),
                pool.shape[:2] + (pool.shape[2] // self.block_size,
                                  self.block_size, cfg.head_dim),
                stacked=True, quant="k_scale" in self.pools,
                impl=attention_impl(cfg), interpret=self.interpret)
        self._prefill_shapes.add(Tb)
        if path == "kernel":
            self._chunk_plans[Tb] = chunk_plan(
                cfg.num_heads, pool.shape[1], self.block_size, cfg.head_dim,
                pool.dtype.itemsize, self.nbk, Tb, "k_scale" in self.pools,
                bool(cfg.index_heads))
        paths = self.rec.gauges.setdefault("paged.prefill_path", {})
        paths.setdefault(f"{path}: {why}" if why else path, []).append(Tb)

    def _count_chunk_turns(self, q0: int, n: int, Tb: int) -> None:
        """A prefill call of ``n`` tokens at ``q0`` (``Tb`` rows) that rides
        the paged kernel, by the kernel's own rule
        (``paged_attention.chunk_walk``), over its programs and the layers:
        ``paged.chunk_turns_sum`` (the turns its programs make, a copy group
        of keys each: what a (head, row, turn) cost is paid for),
        ``paged.chunk_key_tiles_sum`` (the 128-key tiles those turns
        compute) and ``paged.chunk_key_tiles_live_sum`` (those of them that
        hold a key some row of the chunk sees: the rest are all mask, a first
        chunk's, a window's and a context's last turn)."""
        plan = self._chunk_plans.get(Tb)
        if plan is None:
            return
        programs, P, lanes = plan
        c = self.stats
        for window, layers in self._index_windows.items():
            turns, tiles, live = chunk_walk(q0, q0 + n, window, Tb, P, lanes,
                                            self.block_size, self.nbk)
            c["paged.chunk_turns_sum"] += layers * programs * turns
            c["paged.chunk_key_tiles_sum"] += layers * programs * tiles
            c["paged.chunk_key_tiles_live_sum"] += layers * programs * live

    def _latent_prefill_path(self, Tb: int):
        """``PagedCache.attend_latent``'s way for a chunk of ``Tb`` rows, as
        ``paged_attention_path`` answers for K/V pools."""
        pool = self.pools["ckv"]
        return latent_path(
            (1, self.cfg.num_heads, Tb, pool.shape[3]),
            (pool.shape[0], 1, pool.shape[2] // self.block_size,
             self.block_size, pool.shape[3]),
            attention_impl(self.cfg), self.interpret)

    def _prefill_chunk(self, pf: _Prefilling, n: int) -> None:
        req, rec = pf.req, self.rec
        with rec.span("serve.prefill.build"):
            step_in = self._prefill_inputs(
                req, req.prompt[pf.done:pf.done + n], pf.table, pf.done)
        try:
            chaos.failpoint("serve.chunk")
            with rec.span("serve.prefill.dispatch",
                          program=self._prefill_program):
                tok = self._call_device(self._prefill_fn, step_in)
            if req.keep_routing:
                req._routing.append((pf.done, n, self._picks_out))
        except BaseException as e:
            self._chunk_failed(pf, e)
            raise
        chunk = self._chunk_launched(pf, n, tok)
        if chunk.seq is None:
            self._mid_chunk(tok)          # sampled token of a mid-chunk
        else:                             # call is discarded — only the
            self._chunk_out = chunk       # final chunk's is real

    def _chunk_failed(self, pf: _Prefilling, e: BaseException) -> None:
        """A failed chunk must not leak the lifetime allocation: release
        EVERYTHING (partial K/V is recomputed on retry; the chunk progress
        survives on ``req.prefill_progress`` for the fleet's death ledger).
        Chaos/interrupt-class escapes leave the request QUEUED for a requeue
        path; a plain Exception is a deterministic per-request failure."""
        req = pf.req
        self._set_prefilling(None)
        self.pool.release(pf.blocks)
        if isinstance(e, Exception) and not isinstance(e, chaos.ChaosError):
            self.stats["failed"] += 1
            req._finish(FAILED, error=repr(e))
        else:
            req.state = QUEUED

    def _chunk_launched(self, pf: _Prefilling, n: int, tok) -> _ChunkOut:
        """The call that carries ``n`` more of the prompt's tokens is
        launched (``tok``: its output, on the device): book the progress;
        behind a prompt's LAST chunk the sequence is staged (:meth:`_stage`)
        and later decode calls find its first token in ``tok``."""
        req = pf.req
        pf.done += n
        req.prefill_progress = pf.done
        self.stats["prefill_tokens"] += n
        self.stats["prefill_rows"] += self._prefill_rows(n)
        if pf.done < pf.total:
            return _ChunkOut(tok, self._calls)
        self._set_prefilling(None)
        seq = _Prefilled(req, pf.blocks, pf.table, pf.total,
                         StepLayout.FROM_PREFILL)
        self._pre_out = tok
        with self.rec.span("serve.prefill.install"):
            return _ChunkOut(tok, self._calls, seq, self._stage(seq))

    def _mixed_step(self) -> int:
        """A step that advances the prompt in prefill, where the cache is
        not latent: its chunk and the decode call behind it are ONE device
        call (``step_programs``' ``mixed``), so the step reads the weights
        once. The lanes are those a decode call would take now; the prompt's
        own lane, staged behind its last chunk, joins the NEXT call, so no
        lane reads what the call's chunk writes. The call is in flight like a
        decode call (launched before the one in flight is fetched), with its
        chunk riding it: the chunk's first token is booked with the lanes'
        tokens, one call later, and a step's tokens come one mixed call
        after those of the step before. It runs whether or not a lane
        decodes (idle lanes are what they are in a decode call), in place of
        the prefill program of its shape. Returns requests finished."""
        pf, rec = self._prefilling, self.rec
        req, prev = pf.req, self._flight
        n = min(self._chunk, pf.total - pf.done)
        with rec.span("serve.prefill", rid=req.rid, tokens=n,
                      final=int(pf.done + n >= pf.total)):
            with rec.span("serve.prefill.build"):
                chunk_in = self._prefill_inputs(
                    req, req.prompt[pf.done:pf.done + n], pf.table, pf.done)
            try:
                # before the lanes are moved past a call that is not made
                chaos.failpoint("serve.chunk")
                go = self._lanes.next_call()
                with rec.span("serve.decode.build"):
                    step_in = np.concatenate(
                        [chunk_in, self._decode_inputs(go)])
                with rec.span("serve.prefill.dispatch",
                              program=self._mixed_program):
                    out, tok, picks = self._call_mixed(step_in, chunk_in.size)
                if req.keep_routing:
                    req._routing.append((pf.done, n, picks[1]))
            except BaseException as e:
                self._chunk_failed(pf, e)
                raise
            self.stats["mixed.calls"] += 1
            self.stats["mixed.lane_rows_sum"] += int(np.count_nonzero(go))
            chunk = self._chunk_launched(pf, n, tok)
        if prev is not None:
            self.stats["decode_ahead.launched"] += 1
        self._dec_out = out
        self._flight = _InFlight(out, go, self._calls, picks[0], chunk)
        if prev is None:
            return 0
        with rec.span("serve.decode", lanes=self.active):
            return self._book(prev)

    def _call_mixed(self, step_in: np.ndarray, chunk_words: int):
        """One mixed call (:meth:`_call_device`'s work for a buffer of two
        halves, each with its own key): ``(the lanes' output, the chunk's,
        (the lanes' picks, the chunk's))``."""
        self._calls += 1
        kw = self._key.size
        step_in[chunk_words - kw:chunk_words] = self._call_key(
            self._calls, 1).view(np.int32)
        step_in[-kw:] = self._call_key(self._calls).view(np.int32)
        self.stats["step_inputs.transfers_sum"] += 1
        out, tok = self._run_device(self._mixed_fn, step_in, self._dec_out,
                                    self._pre_out)
        if self.cfg.moe_is_dropless:
            (out, lane_picks), (tok, chunk_picks) = out, tok
            return out, tok, (lane_picks, chunk_picks)
        return out, tok, (None, None)

    def _mid_chunk(self, tok) -> None:
        """A prompt's middle chunk is launched: the step waits for it behind
        the launch of the next decode call, as it waits for a last chunk's
        first token. A step then returns when all it launched but that
        decode call has run, and the tokens of one step come a decode call,
        or a decode call and ONE chunk, after those of the step before:
        never two chunks, which a step that returned at the decode call's
        end followed by one that waits for its chunk would put between them
        (PERF.md, PR 40). The host has the next call's time for the next
        step's work either way. (The disagg prefill role waits for no
        middle chunk: it has no lane to keep in step.)"""
        self._chunk_out = _ChunkOut(tok, self._calls)

    def _stage(self, seq: _Prefilled) -> Optional[int]:
        """A prompt's last chunk is launched and its first token is on the
        device: a request that goes on takes its decode lane now, its token
        "from the prefill call", and is in the decode call this step
        launches. Returns the lane (None: nothing staged; the disagg prefill
        role hands over a token the host has read)."""
        if seq.req.max_new_tokens <= 1:
            return None
        slot = self._free_slot()
        self._place(slot, seq)
        return slot

    def _take_chunk(self) -> int:
        """Fetch what this step's chunk left on the device, behind the
        launch of the next decode call: a last chunk's first token, which
        is booked; of a middle chunk (:meth:`_mid_chunk`) its end. Returns
        requests finished."""
        chunk, self._chunk_out = self._chunk_out, None
        return 0 if chunk is None else self._book_chunk(chunk)

    def _book_chunk(self, chunk: _ChunkOut) -> int:
        """Fetch a launched chunk's output (its end) and, of a prompt's last
        chunk, book the first token. Returns requests finished."""
        with self.rec.span("serve.prefill.fetch"):
            first = int(self._fetch(chunk.out, chunk.call)[0])
        if chunk.seq is None:
            return 0
        chunk.seq.last_tok = first
        return self._first_token(chunk.seq, chunk.slot,
                                 insert=not self._warming)

    def _fetch(self, out, call: int) -> np.ndarray:
        """The tokens of device call number ``call`` or of one before it
        (a dropless mixture's expert counts ride behind them and are
        counted here)."""
        out = np.asarray(out)
        if self.cfg.moe_is_dropless:
            self._count_experts(out, call)
            out = out[:len(out)
                      - self.cfg.sparse_layers * self.cfg.moe_experts]
        return out

    def _first_token(self, seq: _Prefilled, slot: Optional[int] = None,
                     insert: bool = True) -> int:
        """A prompt's last chunk is in the pool and its first token
        fetched: stamp it, register the prompt's full blocks with the
        prefix cache, and install the sequence unless ``slot`` says it holds
        a lane already (or finish a request that ends with this token; a
        staged one gives its lane back and is computed once for nothing).
        Returns requests finished."""
        req, rec = seq.req, self.rec
        req.first_token_ts = time.monotonic()
        rec.event("serve.req.first_token", rid=req.rid,
                  ts=req.first_token_ts)
        req.output_tokens.append(seq.last_tok)
        self.stats["tokens_generated"] += 1
        if self.prefix_cache is not None and insert:
            # a warm's dummy prompt must not fork blocks into the
            # (possibly SHARED) prefix cache on every launch/restart
            with rec.span("serve.prefill.prefix_insert"):
                self.prefix_cache.insert(
                    req.prompt, seq.blocks[:seq.ctx // self.block_size])
        ended = req.max_new_tokens <= 1 or (
            req.eos_token_id is not None
            and seq.last_tok == req.eos_token_id)
        if slot is not None:
            if ended:
                self._vacate(slot)
                self._finish(seq)
            return int(ended)
        with rec.span("serve.prefill.install"):
            if ended:
                self._finish(seq)
                return 1
            self._install(seq)
            return 0

    def _install(self, seq: _Prefilled) -> None:
        """Place a fully-prefilled sequence whose first token the host has
        read where decode will find it — a free lane here (whole prefill);
        the disagg prefill role hands it off instead."""
        self._place(self._free_slot(), seq)

    def _prefill_request(self, req: Request) -> int:
        n_pref, blocks, table = self._reserve(req)
        with self.rec.span("serve.prefill", rid=req.rid,
                           tokens=len(req.prompt) - n_pref, final=1):
            return self._prefill_whole(req, n_pref, blocks, table)

    def _prefill_whole(self, req: Request, n_pref: int, blocks, table) -> int:
        P, rec = len(req.prompt), self.rec
        # prefill the suffix the prefix cache does not hold
        with rec.span("serve.prefill.build"):
            suffix = req.prompt[n_pref:]
            step_in = self._prefill_inputs(req, suffix, table, n_pref)
        try:
            with rec.span("serve.prefill.dispatch",
                          program=self._prefill_program):
                tok = self._call_device(self._prefill_fn, step_in)
            if req.keep_routing:
                req._routing.append((n_pref, len(suffix), self._picks_out))
        except BaseException as e:
            # a failed forward (device OOM, interrupt) must not leak the
            # refcounted blocks — capacity survives the exception. A
            # plain Exception is a deterministic per-request failure:
            # mark it FAILED (its owner/callback unblocks, stats record
            # it) before propagating; KeyboardInterrupt-class exits leave
            # it QUEUED for a resumed loop
            self.pool.release(blocks)
            if isinstance(e, Exception):
                self.stats["failed"] += 1
                req._finish(FAILED, error=repr(e))
            else:
                req.state = QUEUED
            raise
        # whole prefill keeps its fetch inside admission: the lane gets a
        # token the host has read
        with rec.span("serve.prefill.fetch"):
            first = int(self._fetch(tok, self._calls)[0])
        req.prefill_progress = P
        self.stats["prefill_tokens"] += len(suffix)
        self.stats["prefill_rows"] += self._prefill_rows(len(suffix))
        return self._first_token(_Prefilled(req, blocks, table, P, first))

    # ---------------------------------------------------------------- decode

    def _decode_step(self) -> int:
        """Launch the next decode call, THEN fetch what is owed to the host:
        the first token of a prompt whose last chunk this step launched, and
        the tokens of the decode call the last step launched. The device
        finds the new call queued when the old one ends, and the completion
        of the old one, this step's bookkeeping and all of the next step's
        host path up to its own launch run beside it. Nothing the new call
        needs is on the host: a lane that goes on reads its token from the
        old call's output, a staged lane from the prefill call's
        (:meth:`StepLayout.lane_tokens`), and the host has counted which
        lanes get their last token from the call in flight
        (:meth:`_Lanes.next_call`). Returns requests finished."""
        go, prev = self._lanes.next_call(), self._flight
        launch = bool(go.any())
        if prev is None and not launch:
            return self._take_chunk()
        with self.rec.span("serve.decode", lanes=self.active):
            if launch:
                self._launch(go, ahead=prev is not None)
            else:
                self._flight = None
            done = self._take_chunk()
            if prev is not None:
                done += self._book(prev)
        return done

    def _launch(self, go: np.ndarray, ahead: bool) -> None:
        """Build and dispatch one decode call over the lanes ``go``; it is
        in flight from here."""
        rec = self.rec
        with rec.span("serve.decode.build"):
            step_in = self._decode_inputs(go)
        with rec.span("serve.decode.dispatch",
                      program=self._decode_program):
            out = self._call_device(self._decode_fn, step_in, self._dec_out,
                                    self._pre_out)
        if ahead:
            self.stats["decode_ahead.launched"] += 1
        self._dec_out = out
        self._flight = _InFlight(out, go, self._calls,
                                 getattr(self, "_picks_out", None))

    def _decode_inputs(self, go: np.ndarray) -> np.ndarray:
        """The buffer of a decode call over the lanes ``go`` (or of a mixed
        call's decode half), counted, and the lanes moved past that call
        (:meth:`_Lanes.launch`)."""
        B, rec, lanes = self.max_batch, self.rec, self._lanes
        # the pages the paged kernel walks this call (every lane up to
        # the token it writes; an idle lane its one null page) against
        # the tables' full width
        rec.count("paged.live_pages_sum",
                  int((lanes.ctx * go // self.block_size + 1).sum()))
        rec.count("paged.table_pages_sum", B * self.nbk)
        self._count_selection(lanes.ctx * go + 1, go, int(
            (lanes.ctx[go] // self.block_size + 1).sum()), decode=True)
        self._count_latent(lanes.ctx[go] + 1, int(
            (lanes.ctx[go] // self.block_size + 1).sum()))
        if self._windows.size:
            # of those, what the window layers' calls walk, summed over
            # those layers: from the page of a lane's first key in reach
            n = lanes.ctx * go + 1
            reach = np.maximum(n - self._windows[:, None], 0)
            rec.count("paged.window_pages_sum", int(
                (-(-n // self.block_size)
                 - reach // self.block_size).sum()))
        rec.count("decode_ahead.device_lane_tokens_sum",
                  int(np.count_nonzero(lanes.toks[go] < 0)))
        return lanes.launch(go)

    def _book(self, call: _InFlight) -> int:
        """Fetch a decode call's tokens and book them: a token a lane, and
        the end of every lane that ends by it; of a mixed call its chunk
        first (:meth:`_book_chunk`)."""
        rec = self.rec
        done = 0 if call.chunk is None else self._book_chunk(call.chunk)
        with rec.span("serve.decode.fetch"):
            toks = self._fetch(call.out, call.call).tolist()
        with rec.span("serve.decode.bookkeep"):
            live = np.flatnonzero(call.go).tolist()
            self.stats["tokens_generated"] += len(live)
            picks = None
            if any(self._slots[i].req.keep_routing for i in live):
                # the call fed each live lane one token: its picks, fetched
                # with the call's tokens and only for a lane that asked
                picks = np.asarray(call.picks)          # [L, lanes, k]
                self.stats["routing.fetches"] = \
                    self.stats.get("routing.fetches", 0) + 1
            for i in live:
                seq = self._slots[i]
                req, tok = seq.req, toks[i]
                if req.keep_routing:
                    req._routing.append(picks[:, i])
                req.output_tokens.append(tok)
                if tok == req.eos_token_id \
                        or len(req.output_tokens) >= req.max_new_tokens:
                    self._vacate(i)
                    self._finish(seq)
                    done += 1
        return done

    def _retire(self, book: bool = True) -> int:
        """Whatever reads or moves the lanes outside a step's own order
        retires the call in flight first: its tokens booked as a step would
        (``book``), or dropped unread where the lanes are given up anyway
        (without a wait: the device may be why they are). Returns requests
        finished."""
        call, self._flight = self._flight, None
        if call is None:
            return 0
        self.stats["decode_ahead.retired_unread"] += 1
        return self._book(call) if book else 0

    def _gather_routing(self, req: Request) -> None:
        """``req.routed_experts`` from what its calls left: a prompt chunk's
        picks still on the device (every call before the one whose tokens
        ended the request has run), a decode call's row as it was booked."""
        cfg = self.cfg
        k, topk = cfg.moe_k, cfg.index_topk

        def handed(picks, on_device: bool):
            """A call's picks as they are handed out: behind a row's experts
            the POSITIONS of the keys it attended, from the device's bits
            (a chunk's rows turned on the device, a decode row here)."""
            if not cfg.index_heads:
                return np.asarray(picks)
            keys = np.asarray(self._positions_fn(picks[..., k:], topk)) \
                if on_device else bits_to_positions(picks[..., k:], topk)
            return np.concatenate([np.asarray(picks[..., :k]), keys], axis=-1)

        rows = np.full((len(req.prompt), cfg.routed_layers, k + topk), -1,
                       np.int32)
        fed = []
        for part in req._routing:
            if isinstance(part, tuple):
                q0, n, picks = part
                rows[q0:q0 + n] = handed(picks, True)[:, :n].transpose(1, 0, 2)
                self.stats["routing.fetches"] = \
                    self.stats.get("routing.fetches", 0) + 1
            else:
                fed.append(part)
        req.routed_experts = np.concatenate(
            [rows, handed(np.stack(fed), False)]) if fed else rows
        req._routing = []

    def _finish(self, seq: _Seq) -> None:
        # the blocks go back at once, though a decode call launched before
        # the host saw this end (an EOS) may still write the lane's next
        # K/V row: into a block the sequence held when that call was
        # launched. Every later writer of that block is launched later, and
        # the donated pools chain the calls in that order; a decode row
        # never falls into the full prompt blocks the prefix cache retains
        self.pool.release(seq.blocks)
        self.stats["completed"] += 1
        if seq.req.keep_routing:
            self._gather_routing(seq.req)
        seq.req._finish(FINISHED)
        self.rec.event("serve.req.finished", rid=seq.req.rid,
                       ts=seq.req.finish_ts)
