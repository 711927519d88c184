"""Flagship transformer family — GPT-style decoder / BERT-style encoder, TPU-first.

The reference wraps *user* torch models and ships only fused kernels for them
(DeepSpeedTransformerLayer, csrc/transformer/*; model zoo in tests:
tests/unit/simple_model.py, tests/unit/modeling.py BERT). Here the model family
is in-tree and TPU-native:

  - flax.linen modules, bf16 compute / fp32 params (engine holds fp32 master)
  - layers run under `nn.scan` (one compiled block body for all layers — the
    XLA-friendly equivalent of the reference's per-layer CUDA kernel reuse) with
    optional `nn.remat` (activation checkpointing, reference:
    runtime/activation_checkpointing/checkpointing.py)
  - Megatron-style tensor parallelism expressed as sharding *rules*
    (`TransformerConfig.tp_rules()`): qkv/fc1 column-parallel, proj/fc2
    row-parallel, vocab-parallel embedding. XLA inserts the psum/allgather the
    reference delegates to an external mpu object.
  - attention dispatches through ops.attention (Pallas flash on TPU)

Batch contract: a dict with "input_ids" [B, S] (+ optional "labels",
"attention_mask", "position_ids"); the module returns logits and
`causal_lm_loss` / `masked_lm_loss` turn them into the scalar loss the engine
expects (reference contract: loss = engine(batch)).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention

Dtype = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    causal: bool = True            # False => BERT-style bidirectional encoder
    tie_embeddings: bool = True
    use_bias: bool = True
    dtype: Dtype = jnp.bfloat16    # compute dtype; params are fp32 (master in engine)
    remat: bool = False            # activation checkpointing of each block
    # remat policy: "full" recomputes everything (min memory, +~33% flops);
    # "dots" saves matmul outputs and recomputes elementwise only (the
    # selective-checkpointing middle ground the reference approximates with
    # per-layer checkpoint granularity, runtime/activation_checkpointing/
    # checkpointing.py:372)
    remat_policy: str = "dots"
    scan_layers: bool = True       # lax.scan over layers (fast compile, ZeRO-3-friendly)
    # fused_loss: __call__ returns the scalar causal-LM loss directly, computing
    # the vocab projection chunk-wise over the sequence so the fp32 [B,S,V]
    # logits are never materialized (HBM: ~3GB saved at 350M/bs8/seq1024)
    fused_loss: bool = False
    loss_chunk: int = 128
    # "auto" | "flash" | "reference" | "ring" | "ulysses" | "sparse"
    # (ring/ulysses: sequence parallelism, wired by the engine from the
    # sequence_parallel config section; sparse: block-sparse layouts from
    # the sparse_attention section — see the sparse_attention field)
    attention_impl: str = "auto"
    layer_norm_eps: float = 1e-5
    # -- architecture knobs covering the HF import policies (models/hf.py;
    #    reference: module_inject/replace_policy.py's per-arch policies) -----
    activation: str = "gelu"       # gelu (tanh) | gelu_exact | relu
    attn_scale: Optional[float] = None   # None = 1/sqrt(head_dim); GPT-Neo: 1.0
    pos_embed: str = "learned"     # learned | rotary (GPT-J) | alibi (BLOOM) | none
    rotary_dim: int = 0            # 0 = whole head_dim
    # True = GPT-J interleaved pairs (rotate_every_two); False = GPT-NeoX
    # half-split (rotate_half)
    rotary_interleaved: bool = True
    parallel_residual: bool = False  # GPT-J: x + attn(ln(x)) + mlp(ln(x))
    # GPT-NeoX: parallel residual with a SEPARATE ln2 feeding the MLP branch:
    # x + attn(ln1(x)) + mlp(ln2(x))
    parallel_residual_dual_ln: bool = False
    post_ln: bool = False          # BERT: LayerNorm AFTER each residual add
    embed_ln: bool = False         # BLOOM/BERT: LayerNorm on the embeddings
    token_type_vocab: int = 0      # BERT segment embeddings
    mlm_head: bool = False         # BERT: transform (dense+act+LN) + decoder bias
    lm_head_bias: bool = False     # GPT-J: untied lm_head carries a bias
    # no LM head at all: __call__ returns final hidden states [B, S, H]
    # (CLIP text encoder; reference: module_inject CLIP policy)
    no_lm_head: bool = False
    qkv_bias: Optional[bool] = None       # None = use_bias (GPT-Neo/J: False)
    attn_out_bias: Optional[bool] = None  # None = use_bias (GPT-J: False)
    # per-layer local attention window, 0 = global (GPT-Neo alternates 0/256)
    layer_windows: Optional[Tuple[int, ...]] = None
    # random-LTD (reference: data_pipeline/data_routing + csrc/random_ltd):
    # layers in [ltd_start, ltd_end) process only ltd_tokens randomly-sampled
    # tokens per step; the rest pass through on the residual. Requires
    # scan_layers=False (the token subset changes the layer's shapes).
    ltd_tokens: int = 0
    ltd_start: int = 0
    ltd_end: int = 0
    # progressive layer drop (reference: runtime/progressive_layer_drop.py):
    # keep layer l with prob 1 - (l/L)(1-theta); theta arrives per step via
    # the "pld_theta" batch key (so no recompile as the schedule moves)
    pld: bool = False
    # -- modern-decoder knobs (Llama/Mistral family — post-dates the
    #    reference v0.8.1; exceeds its policy list) ---------------------------
    norm: str = "layernorm"        # "rmsnorm": no-mean, no-bias (Llama)
    # SwiGLU MLP: down(silu(gate(x)) * up(x)) — three matmuls; activation
    # field selects the gate nonlinearity ("silu" for Llama)
    gated_mlp: bool = False
    # grouped-query attention: k/v heads < q heads, repeated at attention
    # (None = MHA). num_heads % num_kv_heads must be 0.
    num_kv_heads: Optional[int] = None
    rope_theta: float = 10000.0    # rotary base (Llama-3 uses 500000)
    # scaled RoPE (HF config.rope_scaling; llama3 per-frequency remap /
    # linear position interpolation / dynamic NTK). All parameters are
    # trace-time static, so the scaled inv_freq table costs nothing at run
    # time. HF formula sources: transformers modeling_rope_utils
    # _compute_{linear_scaling,dynamic_ntk,llama3}_parameters.
    rope_scaling_type: Optional[str] = None   # "linear"|"dynamic"|"llama3"
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0         # llama3 only
    rope_high_freq_factor: float = 4.0        # llama3 only
    rope_original_max_position: int = 0       # 0 = max_seq_len
    # "yarn" (HF _compute_yarn_parameters / DeepSeek-V2's
    # DeepseekV2YarnRotaryEmbedding): frequencies above beta_fast turns of
    # the original window keep theirs, below beta_slow are divided by the
    # factor, a linear ramp between; the softmax scale is multiplied by
    # mscale(factor, rope_mscale_all_dim)^2, mscale(s, m) = 0.1 m ln s + 1
    # (0 = none). cos/sin are scaled by mscale(factor, rope_mscale) /
    # mscale(factor, rope_mscale_all_dim), which no path carries unless it
    # is 1 (the two equal: DeepSeek-V2's 0.707 / 0.707)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0
    rope_mscale_all_dim: float = 0.0
    # decoupled head_dim (Mistral-Nemo/Gemma style): attention head width
    # independent of hidden_size/num_heads; qkv projects to
    # (nh + 2*kv) * head_dim and attn_proj maps nh*head_dim back to H
    head_dim_override: Optional[int] = None
    # biases on the gated-MLP projections (HF LlamaConfig.mlp_bias);
    # None = use_bias
    mlp_bias: Optional[bool] = None
    # RMSNorm on q and k before rotary, of two kinds: True / "head" is
    # Qwen3's (per head, over head_dim, scale [head_dim]); "projection" is
    # OLMoE's (over the whole projected vector before the head split,
    # scale [heads * head_dim])
    qk_norm: Any = False
    # Gemma: token embeddings scaled by sqrt(hidden_size), applied in the
    # COMPUTE dtype (HF casts the normalizer to the hidden dtype, so bf16
    # runs see the same rounding)
    embed_scale: Optional[float] = None
    # Gemma-2 "sandwich" norms: each branch output is normed AGAIN before
    # its residual add (post_attn_norm / post_mlp_norm; the pre-MLP norm
    # keeps the ln2 slot)
    post_block_norms: bool = False
    # Gemma-2 logit softcapping: tanh(x/cap)*cap on attention scores
    # (applied IN-KERNEL on the Pallas flash path; exact reference impl
    # elsewhere) and on the final LM logits; 0 = off
    attn_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # explicit MLP width when it is not ratio*H (Llama: 11008 at H=4096)
    mlp_dim_override: Optional[int] = None
    # MoE (reference: deepspeed/moe/*): >0 replaces every block's MLP with a
    # mixture of moe_experts experts; aux loss returned next to the logits
    moe_experts: int = 0
    moe_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # dropless routing (OLMoE): every token reaches all moe_k experts, no
    # capacity; always so for moe_k > 2 (moe/dropless.py). The aux loss is
    # then HF's load_balancing_loss_func over all layers' tokens
    moe_dropless: bool = False
    # renormalise the moe_k router weights (HF norm_topk_prob; the GShard
    # top-2 path always does). OLMoE: False
    moe_norm_topk: bool = True
    # -- a dropless mixture's router and share (moe/dropless.py) -------------
    # "softmax" (OLMoE) | "sigmoid" (the DeepSeek-V3 router: independent
    # scores; with moe_norm_topk the picks' scores are divided by their sum)
    moe_scores: str = "softmax"
    # a per-expert bias (``moe/gate/bias`` [E]) added to the scores for the
    # top-k SELECTION only, never to the weights (e_score_correction_bias)
    moe_select_bias: bool = False
    # the picks' weights are multiplied by this (routed_scaling_factor)
    moe_routed_scale: float = 1.0
    # (first, count): this program HOLDS experts first .. first + count - 1
    # of the moe_experts the router ranks (a chip's share of an
    # expert-parallel layer, run without its exchange): the expert stack has
    # ``count`` experts, a pick of an absent one keeps its weight in the
    # renormalisation and computes nothing. None = all of them
    moe_held: Optional[Tuple[int, int]] = None
    # group-limited selection: the router's outputs lie in moe_groups groups
    # of equal size, a token keeps moe_topk_groups of them and picks its
    # moe_k among their experts only. 1 = no groups. A softmax router ranks a
    # group by its best score (DeepSeek-V2's ``group_limited_greedy``), a
    # sigmoid router by the sum of its two best scores, the selection bias in
    # them (DeepSeek-V3's ``noaux_tc``). ``moe_held`` is then whole groups
    # (device-limited routing: a group a device) or an equal part of one
    # (several devices a group)
    moe_groups: int = 1
    moe_topk_groups: int = 1
    # width of a shared expert (``moe/shared``: a SwiGLU / MLP of the
    # experts' kind on EVERY token, added unweighted); 0 = none
    moe_shared_dim: int = 0
    # the first dense_layers layers keep a dense MLP of width dense_mlp_dim
    # (first_k_dense_replace); their parameters are ``dense_blocks``
    # [dense_layers, ...], scanned before ``blocks`` [the rest, ...]
    dense_layers: int = 0
    dense_mlp_dim: Optional[int] = None
    # per-layer rotary: False = that layer carries no positions (a hybrid's
    # global layers); None = every layer (pos_embed "rotary" only)
    layer_rope: Optional[Tuple[bool, ...]] = None
    # False: no norm on a branch's INPUT (no ln1 / ln2); the branch outputs
    # are normed instead, so post_block_norms must be set (EXAONE 4.0)
    pre_norm: bool = True
    # a learned indexer picks each query's keys (DeepSeek-Sparse-Attention's
    # index score, ops/pallas/sparse_select.py): index_heads query heads of
    # index_head_dim against ONE indexer key a token (``index_q``,
    # ``index_k`` + ``index_k_norm``, ``index_w`` in the layer's tree); query
    # t attends the index_topk keys s <= t of largest score, all of them
    # while it sees no more. 0 = none. Beside latent attention (kv_lora_rank:
    # DeepSeek-V3.2) ``index_q`` reads the normed query latent ([q_lora_rank,
    # heads x width]) and the head turns its first ``index_rope_dim`` lanes
    # under the model's own rotary table, halves rotated; elsewhere it reads
    # the normed input and turns whole, under the plain table. The inference
    # decoder only (ROADMAP M7): a training Block with these set refuses
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # multi-head latent attention (DeepSeek-V2's MLA; kv_lora_rank 0 = none).
    # A token's keys and values are ONE normed latent of kv_lora_rank and one
    # rotated key of qk_rope_head_dim shared by all heads (``attn_kv_a`` +
    # ``kv_a_norm``); ``attn_kv_b`` [kv_lora_rank, heads x (qk_nope_head_dim +
    # v_head_dim)] expands the latent to a head's unrotated key and its value.
    # The query is ``attn_q_b(rms(attn_q_a(h)))`` through q_lora_rank, a head
    # qk_nope_head_dim | qk_rope_head_dim wide. The caches store the latent
    # row and nothing a head (``latent_lanes``); attention runs absorbed
    # (``attn_kv_b`` folded into the query and the output: the heads are rows
    # over the one stored row; models/generation.py), but for the paged
    # kernel's prefill chunks, which expand a key tile once for all of their
    # rows (ops/pallas/latent_attention.py). The inference decoder only: a training
    # Block with these set refuses (ROADMAP M4)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # block-sparse attention layout (ds_config "sparse_attention" section;
    # the engine wires it here and sets attention_impl="sparse"): a hashable
    # tuple of (key, value) items — lists as tuples — so the frozen config
    # stays usable as a jit static argument. Keys mirror
    # config.SparseAttentionConfig ("mode", "block", "num_local_blocks", ...).
    sparse_attention: Optional[Tuple[Tuple[str, Any], ...]] = None
    # round-17 low-precision training EXPERIMENT (not a default): "int8"
    # or "fp8" fake-quantizes every block matmul input (straight-through
    # gradients, quant_format.fake_quant_act) — emulated low-precision
    # compute numerics at full-precision speed. The engine wires it from
    # compression_training.activation_quantization and REQUIRES the
    # integrity sentinel's skip/rollback ladder to be armed.
    activation_quant: Optional[str] = None

    def __post_init__(self):
        # gated_mlp + moe_experts is the Mixtral family: SwiGLU experts
        # (moe/layer.GatedExpertMLP); the 3-matmul count flows through
        # _mlp_params so the 6N accounting stays honest
        if self.post_block_norms and self.parallel_residual:
            # the parallel-residual paths return before the sandwich
            # norms; silently skipping them would diverge train vs decode
            raise NotImplementedError(
                "post_block_norms (Gemma-2 sandwich) + parallel_residual "
                "is not implemented")
        if self.qk_norm not in (False, True, "head", "projection"):
            raise ValueError(
                f"qk_norm {self.qk_norm!r}: expected False, True / 'head' "
                "(per head) or 'projection' (over the whole q / k vector)")
        if self.activation_quant not in (None, "int8", "fp8"):
            raise ValueError(
                f"activation_quant {self.activation_quant!r}: expected "
                "'int8', 'fp8' or None")
        if self.moe_scores not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scores {self.moe_scores!r}: expected "
                             "'softmax' or 'sigmoid'")
        routed = [n for n, on in (
            ("moe_scores", self.moe_scores != "softmax"),
            ("moe_select_bias", self.moe_select_bias),
            ("moe_routed_scale", self.moe_routed_scale != 1.0),
            ("moe_held", self.moe_held is not None),
            ("moe_groups", self.moe_groups != 1),
            ("moe_shared_dim", self.moe_shared_dim > 0)) if on]
        if routed and not self.moe_is_dropless:
            raise ValueError(
                f"{', '.join(routed)}: only the dropless mixture "
                "(moe_experts > 0 with moe_dropless or moe_k > 2) carries "
                "them; the GShard capacity path (moe_k <= 2) has a softmax "
                "router over experts it holds whole, and no shared expert")
        if self.moe_held is not None:
            first, count = self.moe_held
            if not (0 <= first and count > 0
                    and first + count <= self.moe_experts):
                raise ValueError(
                    f"moe_held {self.moe_held}: (first, count) inside the "
                    f"router's {self.moe_experts} experts")
        if self.moe_groups != 1 or self.moe_topk_groups != 1:
            size = self.moe_experts // max(self.moe_groups, 1)
            if not (0 < self.moe_topk_groups <= self.moe_groups
                    and self.moe_experts % self.moe_groups == 0
                    and self.moe_topk_groups * size >= self.moe_k):
                raise ValueError(
                    f"moe_groups {self.moe_groups}, moe_topk_groups "
                    f"{self.moe_topk_groups}: the router's {self.moe_experts}"
                    " outputs in equal groups, of which a token keeps enough "
                    f"to hold its {self.moe_k} picks")
            if self.moe_select_bias and self.moe_scores != "sigmoid":
                raise ValueError(
                    "moe_groups with moe_select_bias: the softmax router "
                    "ranks a group by its best SCORE (group_limited_greedy);"
                    " a bias on the selection is the sigmoid router's "
                    "(noaux_tc)")
            if self.moe_scores == "sigmoid" and size < 2:
                raise ValueError(
                    f"moe_groups {self.moe_groups} of a sigmoid router: a "
                    "group is ranked by its TWO best scores (noaux_tc)")
            if self.moe_held is not None:
                first, count = self.moe_held
                whole = first % size == 0 and count % size == 0
                part = size % count == 0 and first % count == 0
                if not (whole or part):
                    raise ValueError(
                        f"moe_held {self.moe_held}: with moe_groups the "
                        f"share held is whole groups of {size} experts, or "
                        "an equal part of one group")
        if self.dense_layers:
            if not (0 < self.dense_layers < self.num_layers
                    and self.moe_experts > 0 and self.dense_mlp_dim):
                raise ValueError(
                    "dense_layers: leading dense layers of a mixture "
                    "(0 < dense_layers < num_layers, moe_experts > 0, "
                    "dense_mlp_dim their width)")
            if not self.scan_layers or self.pld or self.ltd_tokens:
                raise ValueError(
                    "dense_layers needs scan_layers (dense_blocks and "
                    "blocks are two scanned stacks), without progressive "
                    "layer drop or random-LTD")
        if self.layer_rope is not None and (
                self.pos_embed != "rotary" or self.pld
                or len(self.layer_rope) != self.num_layers):
            raise ValueError("layer_rope: one flag a layer, with "
                             "pos_embed='rotary' and without progressive "
                             "layer drop")
        if self.index_heads or self.index_head_dim or self.index_topk:
            if min(self.index_heads, self.index_head_dim,
                   self.index_topk) <= 0:
                raise ValueError(
                    f"index_heads {self.index_heads}, index_head_dim "
                    f"{self.index_head_dim}, index_topk {self.index_topk}: "
                    "an indexer has all three, each > 0")
            if not self.causal or self.post_ln or self.index_head_dim % 2 \
                    or (self.rope_scaling_type not in (None, "default")
                        and not self.kv_lora_rank):
                raise ValueError(
                    "an indexer selects the keys of a causal decoder (not "
                    "post_ln), its head width is even (rotary), and its "
                    "rotary table is the plain one (no rope_scaling_type) "
                    "unless it stands beside latent attention, whose own "
                    "table it turns under")
        if self.rope_scaling_type == "yarn":
            if self.rope_scaling_factor < 1.0 \
                    or self.rope_beta_fast <= self.rope_beta_slow:
                raise ValueError(
                    "rope_scaling_type 'yarn': rope_scaling_factor >= 1 and "
                    "rope_beta_fast > rope_beta_slow")
            if self.rope_mscale != self.rope_mscale_all_dim:
                raise ValueError(
                    f"rope_mscale {self.rope_mscale} != rope_mscale_all_dim "
                    f"{self.rope_mscale_all_dim}: YaRN then scales cos and "
                    "sin by mscale(factor, rope_mscale) / mscale(factor, "
                    "rope_mscale_all_dim), which no rotary path carries "
                    "(equal, the ratio is 1 and only the softmax scale "
                    "moves)")
        latent = (self.kv_lora_rank, self.q_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if any(latent):
            if min(latent) <= 0 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"latent attention has all five sizes, each > 0 "
                    f"(kv_lora_rank, q_lora_rank, qk_nope_head_dim, "
                    f"qk_rope_head_dim (even), v_head_dim): got {latent}")
            refused = [why for why, on in (
                ("rotary positions (pos_embed='rotary'; ALiBi and learned "
                 "positions have no rotated shared key)",
                 self.pos_embed != "rotary"),
                ("no sliding window (layer_windows)",
                 self.layer_windows is not None),
                ("an indexer head at least as wide as the rotated shared "
                 "key (index_head_dim >= qk_rope_head_dim: its leading lanes "
                 "turn under the model's table)",
                 0 < self.index_head_dim < self.qk_rope_head_dim),
                ("one stored row a token, no KV heads (num_kv_heads)",
                 self.num_kv_heads not in (None, self.num_heads)),
                ("a causal pre-norm decoder without q/k norms, softcap, "
                 "parallel residual or per-layer rotary",
                 not self.causal or self.post_ln or not self.pre_norm
                 or bool(self.qk_norm) or bool(self.attn_softcap)
                 or self.parallel_residual or self.layer_rope is not None),
                ("RMSNorm and no biases (its latent norms are RMSNorms)",
                 self.norm != "rmsnorm" or self.use_bias
                 or bool(self.qkv_bias) or bool(self.attn_out_bias)),
                ("rotary_dim 0 or qk_rope_head_dim",
                 self.rotary_dim not in (0, self.qk_rope_head_dim)),
                ("head_dim_override unset (a head is qk_nope_head_dim + "
                 "qk_rope_head_dim wide)",
                 self.head_dim_override is not None)) if on]
            if refused:
                raise ValueError("latent attention (kv_lora_rank) needs "
                                 + "; ".join(refused))
        if not self.pre_norm and (not self.post_block_norms or self.post_ln
                                  or self.parallel_residual):
            raise ValueError(
                "pre_norm=False leaves a branch's input unnormed: its "
                "output must be (post_block_norms=True; not post_ln, not "
                "parallel_residual)")

    @property
    def qk_norm_kind(self) -> Optional[str]:
        """None, "head" or "projection"."""
        return "head" if self.qk_norm is True else (self.qk_norm or None)

    @property
    def moe_is_dropless(self) -> bool:
        return self.moe_experts > 0 and (self.moe_dropless or self.moe_k > 2)

    @property
    def moe_held_count(self) -> int:
        """Experts in the stack: the share held, or all the router ranks."""
        return self.moe_held[1] if self.moe_held else self.moe_experts

    @property
    def moe_group_limit(self) -> Optional[Tuple[int, int]]:
        """``(moe_groups, moe_topk_groups)`` of a grouped router, else None."""
        return (self.moe_groups, self.moe_topk_groups) \
            if self.moe_groups > 1 else None

    @property
    def sparse_layers(self) -> int:
        """Layers whose MLP is the mixture (all of them, or none)."""
        return self.num_layers - self.dense_layers if self.moe_experts else 0

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        if self.kv_lora_rank:       # a latent model's query and key head
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.hidden_size // self.num_heads

    @property
    def latent_width(self) -> int:
        """What a latent model caches a token and layer: the normed latent
        and the rotated shared key (0: no latent attention)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def index_rope_dim(self) -> int:
        """The leading lanes of an indexer head that turn with position:
        the whole head (a model with K/V heads rotates whole heads), or,
        beside latent attention, as many as its rotated shared key has
        (DeepSeek-V3.2's indexer: 64 of 128), halves rotated, under the
        model's own table."""
        return min(self.qk_rope_head_dim, self.index_head_dim) \
            if self.kv_lora_rank else self.index_head_dim

    @property
    def routed_layers(self) -> int:
        """Layers that hand a row out to a request that asked for its
        routing: those with picks or a selection (an indexer stands in
        every layer, a mixture's leading dense ones too)."""
        return self.num_layers if self.index_heads and self.moe_experts \
            else self.sparse_layers

    @property
    def latent_lanes(self) -> int:
        """:attr:`latent_width` in whole 128-lane rows, as the paged pool
        stores it (``serving/kv_cache.init_pool`` says why)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        """``attn_scale`` or ``head_dim ** -0.5``, times YaRN's
        ``mscale(factor, rope_mscale_all_dim) ** 2``."""
        scale = (self.attn_scale if self.attn_scale is not None
                 else 1.0 / float(np.sqrt(self.head_dim)))
        if self.rope_scaling_type == "yarn" and self.rope_mscale_all_dim \
                and self.rope_scaling_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * float(
                np.log(self.rope_scaling_factor)) + 1.0
            scale = scale * m * m
        return scale

    def uniform_window(self) -> Optional[int]:
        """The single static window every layer shares, when layer_windows
        is uniform: 0 for no/global windows, the window size otherwise;
        None when layers MIX windows (per-layer routing must stay dynamic).
        Shared by the training path (keeps the window static under nn.scan)
        and the generation prefill (flash-kernel eligibility)."""
        if self.layer_windows is None:
            return 0
        vals = {max(int(w), 0) for w in self.layer_windows}
        return vals.pop() if len(vals) == 1 else None

    def rope_inv_freq(self, seq_len: Optional[int] = None):
        """Static inverse-frequency table for rotary embeddings with the
        configured rope_scaling applied (mirrors HF modeling_rope_utils for
        linear / dynamic / llama3). Returns None when no scaling is
        configured so apply_rotary keeps its original in-trace table —
        bit-identical to what every unscaled arch's token-exact parity was
        validated against.

        ``seq_len``: the target length the table must cover — dynamic NTK
        stretches the base once this exceeds the original window (HF
        recomputes per forward from max(position)+1; passing the static
        trace-time S here matches that exactly). Decode passes the cache
        capacity instead: one table for the whole planned generation,
        where HF re-rotates nothing and lets keys cached under earlier
        tables disagree — ours is the path-independent variant."""
        t = self.rope_scaling_type
        if t is None or t == "default":
            return None
        # float32 arithmetic end-to-end: HF computes these tables in
        # torch.float32, and parity is checked token-exact
        rd = self.qk_rope_head_dim or self.rotary_dim or self.head_dim
        inv = 1.0 / (self.rope_theta ** (np.arange(0, rd, 2,
                                                   dtype=np.float32) / rd))
        f = self.rope_scaling_factor
        orig = self.rope_original_max_position or self.max_seq_len
        if t == "linear":
            inv = inv / f
        elif t == "dynamic":
            # NTK: the base stretches once positions exceed the original
            # window; seq_len is static under jit, so the table for
            # max_seq_len is the one HF would have converged to at that
            # length (identical to default while max_seq_len <= orig)
            eff = max(seq_len or self.max_seq_len, orig)
            base = self.rope_theta * (
                (f * eff / orig) - (f - 1)) ** (rd / (rd - 2))
            inv = 1.0 / (base ** (np.arange(0, rd, 2,
                                            dtype=np.float32) / rd))
        elif t == "llama3":
            lo, hi = self.rope_low_freq_factor, self.rope_high_freq_factor
            low_wl, high_wl = orig / lo, orig / hi
            wavelen = 2.0 * np.pi / inv
            inv_l = np.where(wavelen > low_wl, inv / f, inv)
            smooth = (orig / wavelen - lo) / (hi - lo)
            smoothed = (1.0 - smooth) * inv_l / f + smooth * inv_l
            is_medium = (wavelen >= high_wl) & (wavelen <= low_wl)
            inv = np.where(is_medium, smoothed, inv_l)
        elif t == "yarn":
            # the dimension whose wavelength makes `turns` turns over the
            # original window; between the two corrections a linear ramp
            # from the model's own frequency to the interpolated one
            at = lambda turns: rd * np.log(orig / (turns * 2.0 * np.pi)) / (
                2.0 * np.log(self.rope_theta))
            low = max(int(np.floor(at(self.rope_beta_fast))), 0)
            high = min(int(np.ceil(at(self.rope_beta_slow))), rd - 1)
            ramp = np.clip((np.arange(rd // 2, dtype=np.float32) - low)
                           / max(high - low, 1e-3), 0.0, 1.0)
            inv = inv / f * ramp + inv * (1.0 - ramp)
        else:
            raise NotImplementedError(
                f"rope_scaling type {t!r} is not implemented (linear, "
                "dynamic, llama3 and yarn are; longrope's per-dimension "
                "factor lists are not)")
        return inv.astype(np.float32)

    @property
    def mlp_dim(self) -> int:
        if self.mlp_dim_override is not None:
            return self.mlp_dim_override
        return self.hidden_size * self.mlp_ratio

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def _embed_params(self) -> int:
        h, v = self.hidden_size, self.vocab_size
        n = v * h
        if self.pos_embed == "learned":
            n += self.max_seq_len * h
        if not self.tie_embeddings and not self.no_lm_head:
            n += v * h                        # untied lm_head
        return n

    def _attn_params(self) -> int:
        h = self.hidden_size
        if self.kv_lora_rank:
            # q_a + its norm, q_b, kv_a + its norm, kv_b, the output
            qr, kr, nh = self.q_lora_rank, self.kv_lora_rank, self.num_heads
            n = (h * qr + qr + qr * nh * self.head_dim
                 + h * self.latent_width + kr
                 + kr * nh * (self.qk_nope_head_dim + self.v_head_dim)
                 + nh * self.v_head_dim * h)
            if self.index_heads:
                # the indexer beside a latent: its queries from the query
                # latent, its one key (and that key's LayerNorm) and its
                # head weights from the normed input
                n += qr * self.index_heads * self.index_head_dim \
                    + h * (self.index_head_dim + self.index_heads) \
                    + 2 * self.index_head_dim
            return n
        n = (self.num_heads + 2 * self.kv_heads) * self.head_dim * h \
            + self.num_heads * self.head_dim * h   # qkv (GQA) + out proj
        if self.index_heads:
            # the indexer: its queries, its one key (and that key's
            # LayerNorm), its head weights
            n += h * (self.index_heads * self.index_head_dim
                      + self.index_head_dim + self.index_heads) \
                + 2 * self.index_head_dim
        return n

    def _mlp_params(self, width: Optional[int] = None) -> int:
        """One MLP of ``width`` (default: the model's, an expert's)."""
        return (3 if self.gated_mlp else 2) * self.hidden_size \
            * (self.mlp_dim if width is None else width)

    def _dense_layer_params(self) -> int:
        """The leading dense layers' (``dense_layers`` of them)."""
        return self.dense_layers * (
            self._attn_params() + self._mlp_params(self.dense_mlp_dim or 0))

    def num_params(self) -> int:
        """Parameters this program holds (``moe_held``: the share's)."""
        per_layer = self._attn_params() \
            + self._mlp_params() * max(self.moe_held_count, 1)
        if self.moe_experts > 0:
            per_layer += self.hidden_size * self.moe_experts  # router
            per_layer += self._mlp_params(self.moe_shared_dim)
        return self._embed_params() + self._dense_layer_params() \
            + (self.num_layers - self.dense_layers) * per_layer

    def num_active_params(self) -> int:
        """Params touched per token (== num_params for dense; MoE routes each
        token through moe_k of moe_experts expert MLPs). This is the N that
        belongs in the 6N FLOPs-per-token model."""
        if self.moe_experts <= 0:
            return self.num_params()
        per_layer = (self._attn_params() + self._mlp_params() * self.moe_k
                     + self.hidden_size * self.moe_experts
                     + self._mlp_params(self.moe_shared_dim))
        return self._embed_params() + self._dense_layer_params() \
            + (self.num_layers - self.dense_layers) * per_layer

    # -- tensor-parallel sharding rules (regex on param path -> PartitionSpec) --
    def tp_rules(self) -> Dict[str, P]:
        """Megatron-style TP over the 'model' mesh axis.

        Scanned layers carry a leading layer dim, so block-param specs lead
        with None. Column-parallel: qkv & fc1 (output dim sharded);
        row-parallel: attn proj & fc2 (input dim sharded); embedding is
        vocab-parallel (reference inference TP slices the same way:
        module_inject/replace_policy.py).
        """
        # scanned layers live under "blocks/..." with a leading layer dim;
        # unrolled layers are "blocks_<i>/..." without it
        lead = (None,) if self.scan_layers else ()
        prefix = r"blocks/" if self.scan_layers else r"blocks_\d+/"

        def block(spec):
            return P(*(lead + spec))

        return {
            prefix + r".*attn_qkv/kernel": block((None, "model")),
            prefix + r".*attn_qkv/bias": block(("model",)),
            prefix + r".*attn_proj/kernel": block(("model", None)),
            prefix + r".*mlp_fc/kernel": block((None, "model")),
            prefix + r".*mlp_fc/bias": block(("model",)),
            prefix + r".*mlp_gate/kernel": block((None, "model")),
            prefix + r".*mlp_gate/bias": block(("model",)),
            prefix + r".*mlp_proj/kernel": block(("model", None)),
            r"wte/embedding": P("model", None),
            r"lm_head/kernel": P(None, "model"),
            # MoE expert stacks: [.., E, in, out] — expert axis + row/col TP
            # (gate = the SwiGLU expert's column-parallel gate projection,
            # Mixtral family; the ROUTER at moe/gate is deliberately
            # unmatched — _Gate pins it replicated)
            prefix + r".*experts/fc/kernel": block(("expert", None, "model")),
            prefix + r".*experts/fc/bias": block(("expert", "model")),
            prefix + r".*experts/gate/kernel": block(("expert", None,
                                                      "model")),
            prefix + r".*experts/gate/bias": block(("expert", "model")),
            prefix + r".*experts/proj/kernel": block(("expert", "model", None)),
            prefix + r".*experts/proj/bias": block(("expert", None)),
        }


# -- presets (sizes follow the reference's BASELINE ladder: GPT-2 125M→6.7B,
#    BERT base/large; docs/_pages/training.md) --------------------------------
_PRESETS = {
    "gpt2-tiny": dict(hidden_size=128, num_layers=2, num_heads=4, vocab_size=1024,
                      max_seq_len=256),
    "gpt2-125m": dict(hidden_size=768, num_layers=12, num_heads=12),
    "gpt2-350m": dict(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt2-760m": dict(hidden_size=1536, num_layers=24, num_heads=16),
    "gpt2-1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16),
    "gpt2-2.7b": dict(hidden_size=2560, num_layers=32, num_heads=32),
    "gpt2-6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32),
    # TinyLlama-1.1B shapes: the modern-decoder leg (RMSNorm + SwiGLU +
    # GQA 32q/4kv + rotary) of the perf table
    "llama-1.1b": dict(hidden_size=2048, num_layers=22, num_heads=32,
                       num_kv_heads=4, mlp_dim_override=5632,
                       norm="rmsnorm", gated_mlp=True, activation="silu",
                       pos_embed="rotary", rotary_interleaved=False,
                       use_bias=False, tie_embeddings=False,
                       vocab_size=32000, max_seq_len=2048),
    "bert-base": dict(hidden_size=768, num_layers=12, num_heads=12, causal=False,
                      vocab_size=30522, max_seq_len=512),
    "bert-large": dict(hidden_size=1024, num_layers=24, num_heads=16, causal=False,
                       vocab_size=30522, max_seq_len=512),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    if name not in _PRESETS:
        raise ValueError(f"unknown preset '{name}'; have {sorted(_PRESETS)}")
    kw = dict(_PRESETS[name])
    kw.update(overrides)
    return TransformerConfig(**kw)


_ACTIVATIONS = {
    "gelu": nn.gelu,                                    # tanh approximation
    "gelu_exact": lambda x: nn.gelu(x, approximate=False),
    "relu": nn.relu,
    "quick_gelu": lambda x: x * nn.sigmoid(1.702 * x),  # CLIP
    "silu": nn.silu,                                    # Llama SwiGLU gate
}


def apply_rotary(x: jnp.ndarray, positions: jnp.ndarray,
                 rotary_dim: int = 0, interleaved: bool = True,
                 theta: float = 10000.0, inv_freq=None) -> jnp.ndarray:
    """Rotary embedding; interleaved=True is the GPT-J rotate_every_two pair
    layout, False is the GPT-NeoX rotate_half half-split layout.

    x: [B, nh, S, hd]; positions: [B, S] or [S]. Only the first rotary_dim
    channels rotate (GPT-J: 64 of 256; NeoX: rotary_pct * hd); the rest pass
    through. ``inv_freq`` (a static [rd/2] table, e.g. from
    TransformerConfig.rope_inv_freq for scaled-RoPE variants) overrides the
    plain-theta table. reference arch sources: HF
    GPTJAttention._apply_rotary_pos_emb, HF GPTNeoXAttention (rotate_half).
    """
    B, nh, S, hd = x.shape
    rd = rotary_dim or hd
    if positions.ndim == 1:
        positions = positions[None, :]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, rd, 2) / rd))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[:, :, None].astype(jnp.float32) * inv_freq[None, None, :]
    sin = jnp.sin(ang)[:, None, :, :]                   # [B, 1, S, rd/2]
    cos = jnp.cos(ang)[:, None, :, :]
    xr = x[..., :rd].astype(jnp.float32)
    if interleaved:
        x1 = xr[..., 0::2]
        x2 = xr[..., 1::2]
        rot1 = x1 * cos - x2 * sin
        rot2 = x2 * cos + x1 * sin
        rot = jnp.stack([rot1, rot2], axis=-1).reshape(B, nh, S, rd)
    else:
        x1 = xr[..., :rd // 2]
        x2 = xr[..., rd // 2:]
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """ALiBi per-head slopes (BLOOM; HF build_alibi_tensor formula)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))
    if np.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    base = 2 ** int(np.floor(np.log2(num_heads)))
    extra = pow2_slopes(2 * base)[0::2][:num_heads - base]
    return np.concatenate([pow2_slopes(base), extra])


def alibi_bias(num_heads: int, q_pos: jnp.ndarray, k_pos: jnp.ndarray
               ) -> jnp.ndarray:
    """Additive bias -slope * (q - k): [B, H, Sq, Sk] for [B, S] positions
    (packed/per-sample position ids), [1, H, Sq, Sk] for shared [S]."""
    slopes = jnp.asarray(alibi_slopes(num_heads), jnp.float32)
    if q_pos.ndim == 1:
        q_pos, k_pos = q_pos[None], k_pos[None]
    dist = (k_pos[:, None, :] - q_pos[:, :, None]).astype(jnp.float32)
    return slopes[None, :, None, None] * dist[:, None]


def _sparse_block_attention(cfg, q, k, v, *, mask, bias, slopes, window,
                            sm_scale, dropout_rate, dropout_rng):
    """attention_impl == "sparse": execute the ds_config-selected block-sparse
    layout (engine wires the parsed section into cfg.sparse_attention).

    Clean calls (no mask/bias/dropout/softcap/window) run the Pallas
    layout-skip kernel via ops.sparse_attention.sparse_attention — FLOPs
    scale with layout density. Anything extra composes the layout into a
    dense mask over the exact jnp reference instead: the configured sparsity
    is still honored bit-exactly, only the FLOP scaling is lost. Unknown
    modes raise here (and in the engine wiring) — never silently dense.
    """
    import dataclasses as _dc

    from ..ops.attention import alibi_bias_from_slopes, mha_reference
    from ..ops.sparse_attention import (SPARSITY_CONFIGS, layout_to_dense_mask,
                                        sparse_attention)
    B, H, S, D = q.shape
    kwargs = {key: (list(val) if isinstance(val, tuple) else val)
              for key, val in (cfg.sparse_attention or ())}
    mode = kwargs.pop("mode", "fixed")
    if mode not in SPARSITY_CONFIGS:
        raise ValueError(f"unknown sparse attention mode '{mode}'; "
                         f"have {sorted(SPARSITY_CONFIGS)}")
    cls = SPARSITY_CONFIGS[mode]
    allowed = {f.name for f in _dc.fields(cls)} - {"num_heads"}
    sp_cfg = cls(num_heads=H,
                 **{key: val for key, val in kwargs.items()
                    if key in allowed and val is not None})
    clean = (mask is None and bias is None and slopes is None
             and dropout_rate == 0.0 and not window and not cfg.attn_softcap)
    if clean:
        return sparse_attention(q, k, v, sp_cfg, causal=cfg.causal,
                                sm_scale=sm_scale)
    if slopes is not None:
        bias = alibi_bias_from_slopes(slopes, S, S)
    lmask = layout_to_dense_mask(sp_cfg.make_layout(S), sp_cfg.block)[None]
    mask = lmask if mask is None else mask & lmask
    if window:
        from ..ops.attention import window_mask
        mask = mask & window_mask(S, S, window)
    return mha_reference(q, k, v, causal=cfg.causal, bias=bias, mask=mask,
                         sm_scale=sm_scale, dropout_rate=dropout_rate,
                         dropout_rng=dropout_rng, softcap=cfg.attn_softcap)


def _spec_constraint(x, spec: P):
    """Sharding constraint that works both under plain ``jax.jit`` and
    inside a shard_map.

    Under plain jit there is no ambient mesh, so a bare PartitionSpec would
    raise — and the round-3 try/except silently swallowed that, leaving
    activation layouts to partitioner inference (the involuntary-remat
    warnings). There the spec is resolved against the session's global mesh
    into a NamedSharding. Inside a shard_map (e.g. the pipeline executor's
    Manual-'pipe' context) a full-mesh NamedSharding is REJECTED — there the
    bare spec is exactly right: it resolves against the context mesh and
    ignores the manual axes (our specs never name 'pipe')."""
    # the comm-plan stacked-grads step traces the model SHARD-LOCALLY
    # (manual over the DP axes): every mesh constraint is meaningless
    # there — and naming a manual axis in one is an error on jax lines
    # without the abstract-mesh probe below — so the local-region flag
    # turns them all off for that trace. The TP-composed stacked step
    # (round 14) instead passes its manual-axes set: entries naming a
    # manual axis are stripped, the surviving TP entries resolve against
    # the partial-auto region's context mesh.
    from ..comm_plan.runtime import in_local_region, local_region_manual_axes
    if in_local_region():
        manual = local_region_manual_axes()
        if manual is None:
            return x
        filtered = []
        for entry in spec:
            if entry is None:
                filtered.append(None)
                continue
            names = tuple(n for n in
                          ((entry,) if isinstance(entry, str) else entry)
                          if n not in manual)
            filtered.append(None if not names
                            else names[0] if len(names) == 1 else names)
        if not any(e is not None for e in filtered):
            return x
        return jax.lax.with_sharding_constraint(x, P(*filtered))
    if not jax.sharding.get_abstract_mesh().empty:
        return jax.lax.with_sharding_constraint(x, spec)
    from ..parallel.mesh import get_global_mesh
    mm = get_global_mesh()
    if mm is None:
        return x                       # plain CPU tests: no mesh, no layout
    # scope check (sharding-in-types): activations of a computation whose
    # inputs are laid out on a mesh carry that mesh in their aval; a plain
    # -jit call on single-device/committed-elsewhere data carries an EMPTY
    # abstract mesh, and pinning IT to the session mesh would be a device
    # -scope error — exactly the ad-hoc case (profiler init, one-device
    # side computation) that must run unconstrained. The flip side of the
    # contract: a program gets mesh layouts only when its INPUTS are
    # placed on the mesh (engine APIs do this; raw jit over uncommitted
    # arrays runs unconstrained — device_put params/batch with a
    # NamedSharding to opt in). Engine init traces run uncommitted and
    # intentionally skip constraints: param placement comes from the init
    # jit's out_shardings, not activation constraints.
    # programs placing inputs via jit(in_shardings=...) ALSO trace with an
    # empty aval mesh (verified on jax 0.9) and would skip constraints;
    # DSTPU_FORCE_MESH_CONSTRAINTS=1 restores the always-constrain
    # behavior for that idiom (documented in docs/USAGE.md)
    import os
    if os.environ.get("DSTPU_FORCE_MESH_CONSTRAINTS") != "1":
        aval_mesh = getattr(getattr(jax.typeof(x), "sharding", None),
                            "mesh", None)
        if aval_mesh is None or aval_mesh.empty:
            return x
    # a computation not laid out on the session mesh (e.g. a smaller
    # ad-hoc batch) can't take the constraint — detectable as
    # non-divisible sharded dims
    for dim, entry in enumerate(spec[:np.ndim(x)]):
        if entry is None or entry is P.UNCONSTRAINED:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= mm.shape.get(a, 1)
        if size and np.shape(x)[dim] % size != 0:
            return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mm.mesh, spec))


def _batch_constraint(x):
    """Constrain activations [B, S, H] to the mesh's batch/seq layout (H
    left to the partitioner)."""
    return _spec_constraint(
        x, P(("data", "expert"), "seq", P.UNCONSTRAINED))


class _TDense(nn.Module):
    """nn.Dense (same param names/init, drop-in) whose kernel read is pinned
    to its gathered, TP-only layout.

    Under ZeRO-3 the stacked kernels arrive sharded over the ZeRO axes on
    their contraction dim; left to inference, the partitioner computes the
    backward's dx = dy @ W^T with W still sharded and emits dx H-sharded —
    clashing with the batch/seq activation layout at the backward scan
    boundary (the round-3 'involuntary full rematerialization' warnings).
    Pinning the kernel read makes the ZeRO-3 gather-on-use explicit in
    forward AND (via the constraint's transpose) backward, so dx stays in
    batch layout and the dW cotangent resharding lowers to the usual
    reduce-scatter. The reference's analogue is the stage-3 allgather in
    both passes (partitioned_param_coordinator.fetch_sub_module)."""
    features: int
    kernel_spec: Optional[Tuple] = None
    use_bias: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), self.param_dtype)
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           (self.features,), self.param_dtype)
                if self.use_bias else None)
        if self.kernel_spec is not None:
            kernel = _spec_constraint(kernel, P(*self.kernel_spec))
        y = x.astype(self.dtype) @ kernel.astype(self.dtype)
        if bias is not None:
            y = y + bias.astype(self.dtype)
        return y


class Block(nn.Module):
    """One transformer block (attention + MLP).

    Default is the pre-LN GPT shape; cfg knobs reconfigure it into the other
    policy architectures: post_ln (BERT), parallel_residual (GPT-J), rotary /
    alibi positions, per-layer local windows (GPT-Neo), activations.
    """
    cfg: TransformerConfig
    # one of a mixture's leading dense layers (``cfg.dense_layers``): a dense
    # MLP of width ``cfg.dense_mlp_dim`` in the mixture's place
    dense_mlp: bool = False

    @nn.compact
    def __call__(self, x, attn_mask=None, train: bool = False, window=None,
                 positions=None, rope=None):
        """``rope`` (``cfg.layer_rope``): whether THIS layer rotates q and k,
        a Python bool or a traced one under the layer scan; None = the
        model's ``pos_embed`` alone decides."""
        cfg = self.cfg
        # entry constraint pairs with the exit constraints below: its
        # TRANSPOSE pins the block-input cotangent — the backward layer-scan
        # carry — to the same batch/seq layout. Without it the partitioner
        # may pick a contraction-dim (H) sharding for dx inside the backward
        # while-loop and pay an involuntary replicate-and-reshard at every
        # iteration (the last two spmd_partitioner warnings of round 3).
        x = _batch_constraint(x)
        B, S, H = x.shape
        nh, hd = cfg.num_heads, cfg.head_dim
        act = _ACTIVATIONS[cfg.activation]
        # TP-only (gathered) kernel layouts by name — the ZeRO axes are
        # deliberately absent: _TDense pins the kernel READ to this spec
        _KSPEC = {"attn_qkv": (None, "model"), "attn_proj": ("model", None),
                  "mlp_fc": (None, "model"), "mlp_gate": (None, "model"),
                  "mlp_proj": ("model", None)}
        _mk_dense = lambda feats, name, bias=None: _TDense(
            feats, kernel_spec=_KSPEC.get(name),
            use_bias=cfg.use_bias if bias is None else bias,
            dtype=cfg.dtype, param_dtype=jnp.float32, name=name)
        if cfg.activation_quant is None:
            dense = _mk_dense
        else:
            # round-17 low-precision experiment: every block matmul sees
            # an int8/fp8-rounded INPUT (straight-through gradient) — the
            # module is built eagerly so the flax param order is identical
            # to the unquantized block (checkpoints interchange freely)
            from ..quant_format import fake_quant_act
            dense = lambda feats, name, bias=None: (
                lambda h, _m=_mk_dense(feats, name, bias): _m(
                    fake_quant_act(h, cfg.activation_quant)))
        if cfg.norm == "rmsnorm":
            ln = lambda name: nn.RMSNorm(epsilon=cfg.layer_norm_eps,
                                         dtype=cfg.dtype,
                                         param_dtype=jnp.float32, name=name)
        else:
            ln = lambda name: nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                           dtype=cfg.dtype,
                                           param_dtype=jnp.float32, name=name)

        with jax.named_scope("block.attn"):
            # attention ----------------------------------------------------------
            kv = cfg.kv_heads
            if nh % kv != 0:
                raise ValueError(f"num_heads {nh} not divisible by "
                                 f"num_kv_heads {kv}")
            h = x if cfg.post_ln or not cfg.pre_norm else ln("ln1")(x)
            if cfg.kv_lora_rank:
                # latent attention's seven leaves are made here so that a
                # model's tree holds them; the attention itself, absorbed
                # over a latent cache, lives in the inference decoder
                # (models/generation.decoder_forward)
                rms = lambda name: nn.RMSNorm(
                    epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name)
                rms("q_a_norm")(dense(cfg.q_lora_rank, "attn_q_a",
                                      bias=False)(h))
                dense(nh * hd, "attn_q_b", bias=False)(
                    jnp.zeros((B, S, cfg.q_lora_rank), cfg.dtype))
                dense(cfg.latent_width, "attn_kv_a", bias=False)(h)
                latent = rms("kv_a_norm")(
                    jnp.zeros((B, S, cfg.kv_lora_rank), cfg.dtype))
                dense(nh * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                      "attn_kv_b", bias=False)(latent)
                if cfg.index_heads:
                    # the indexer beside a latent: its queries read the
                    # normed query latent, its key and head weights ``h``
                    Hi, Di = cfg.index_heads, cfg.index_head_dim
                    dense(Hi * Di, "index_q", bias=False)(
                        jnp.zeros((B, S, cfg.q_lora_rank), cfg.dtype))
                    nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                                 param_dtype=jnp.float32, name="index_k_norm")(
                        dense(Di, "index_k", bias=False)(h))
                    dense(Hi, "index_w", bias=False)(h)
                if not self.is_initializing():
                    raise NotImplementedError(
                        "kv_lora_rank: latent attention is served "
                        "(init_inference / generate) and not trained or run "
                        "by Block: ROADMAP M4")
                out = jnp.zeros((B, S, nh * cfg.v_head_dim), cfg.dtype)
            else:
                # one fused qkv matmul even under GQA: [H, (nh + 2*kv) * hd]
                qkv = dense((nh + 2 * kv) * hd, "attn_qkv", bias=cfg.qkv_bias)(h)
                q, k, v = jnp.split(qkv, [nh * hd, (nh + kv) * hd], axis=-1)
                to_heads = lambda t, n: t.reshape(B, S, n, hd).transpose(0, 2, 1, 3)
                qk_ln = lambda name: nn.RMSNorm(
                    epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name)
                if cfg.qk_norm_kind == "projection":
                    # OLMoE: RMSNorm over the whole projected q / k vector,
                    # before the head split (HF OlmoeAttention.q_norm/k_norm)
                    q, k = qk_ln("q_norm")(q), qk_ln("k_norm")(k)
                q, k, v = to_heads(q, nh), to_heads(k, kv), to_heads(v, kv)
                if cfg.qk_norm_kind == "head":
                    # Qwen3: RMSNorm over head_dim on q/k, before rotary (HF
                    # Qwen3Attention.q_norm/k_norm — per-head, scale-only)
                    q, k = qk_ln("q_norm")(q), qk_ln("k_norm")(k)
                if cfg.pos_embed == "rotary" and rope is not False:
                    pos = positions if positions is not None else jnp.arange(S)
                    inv_freq = cfg.rope_inv_freq(S)     # None = plain-theta table
                    rot = lambda t: apply_rotary(
                        t, pos, cfg.rotary_dim, cfg.rotary_interleaved,
                        cfg.rope_theta, inv_freq=inv_freq)
                    if rope is None or rope is True:
                        q, k = rot(q), rot(k)
                    else:       # traced: a hybrid's layers share one scan body
                        q, k = jnp.where(rope, rot(q), q), jnp.where(rope, rot(k), k)
                if cfg.index_heads:
                    # the indexer's four leaves are made here so that a model's
                    # tree holds them; the selection itself lives in the
                    # inference decoder (models/generation.decoder_forward)
                    Hi, Di = cfg.index_heads, cfg.index_head_dim
                    dense(Hi * Di, "index_q", bias=False)(h)
                    nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                                 param_dtype=jnp.float32, name="index_k_norm")(
                        dense(Di, "index_k", bias=False)(h))
                    dense(Hi, "index_w", bias=False)(h)
                    if not self.is_initializing():
                        raise NotImplementedError(
                            "index_heads: a learned indexer's selection is "
                            "served (init_inference / generate) and not "
                            "trained or run by Block: ROADMAP M7")
                if kv != nh:
                    # grouped-query: each k/v head serves nh/kv query heads
                    k = jnp.repeat(k, nh // kv, axis=1)
                    v = jnp.repeat(v, nh // kv, axis=1)
                bias = None
                slopes = None
                if cfg.pos_embed == "alibi":
                    if positions is None:
                        # default arange positions: pass the per-head slopes so the
                        # flash kernel rebuilds the bias from block indices — no
                        # [B, H, S, S] materialization on the kernel path
                        slopes = jnp.asarray(alibi_slopes(nh), jnp.float32)
                    else:
                        # packed / per-sample position ids: the distance matrix is
                        # genuinely data-dependent, materialize it
                        bias = alibi_bias(nh, positions, positions)
                mask = attn_mask
                win = 0
                if window is not None:
                    # local sliding window (GPT-Neo): q attends k in (q-window, q].
                    # attention() routes this to the block-skip sliding-window kernel
                    # on TPU (compute scales with the window); with a user mask or
                    # under tracing where `window` is dynamic, it composes into the
                    # dense mask (exact either way)
                    if isinstance(window, (int, np.integer)):
                        win = max(int(window), 0)          # <=0 means global
                    else:
                        q_pos = jnp.arange(S)[:, None]
                        k_pos = jnp.arange(S)[None, :]
                        wmask = (q_pos - k_pos < window) | (window <= 0)
                        mask = (wmask[None, None] if mask is None
                                else mask & wmask[None, None])
                drop_rng = (self.make_rng("dropout")
                            if train and cfg.dropout > 0.0 else None)
                if cfg.attention_impl == "sparse":
                    out = _sparse_block_attention(
                        cfg, q, k, v, mask=mask, bias=bias, slopes=slopes,
                        window=win, sm_scale=cfg.attn_scale,
                        dropout_rate=cfg.dropout if train else 0.0,
                        dropout_rng=drop_rng)
                else:
                    out = attention(q, k, v, causal=cfg.causal, mask=mask, bias=bias,
                                    alibi_slopes=slopes, sm_scale=cfg.attn_scale,
                                    dropout_rate=cfg.dropout if train else 0.0,
                                    dropout_rng=drop_rng, impl=cfg.attention_impl,
                                    window=win, softcap=cfg.attn_softcap)
                # tag so the "dots" remat policy keeps it: the Pallas kernel output is
                # not a dot_general, and recomputing flash fwd in bwd costs ~2ms/layer
                from jax.ad_checkpoint import checkpoint_name
                out = checkpoint_name(out, "attn_out")
                # nh*hd == H unless head_dim_override decouples them (Mistral-Nemo)
                out = out.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
            out = dense(H, "attn_proj", bias=cfg.attn_out_bias)(out)
            if cfg.dropout > 0.0 and train:
                out = nn.Dropout(cfg.dropout)(out, deterministic=False)

        aux = jnp.zeros((), jnp.float32)

        def mlp(h, norm=None):
            """The MLP branch (``norm``: the pre-norm to apply first), one
            device scope."""
            with jax.named_scope("block.mlp"):
                if norm is not None:
                    h = ln(norm)(h)
                return _mlp(h)

        def _mlp(h):
            if cfg.moe_experts > 0 and not self.dense_mlp:
                from ..moe.layer import MoE
                # gated_mlp: SwiGLU experts (Mixtral, OLMoE); else the
                # fc -> gelu -> proj expert
                return MoE(
                    hidden_size=H,
                    num_experts=cfg.moe_experts,
                    mlp_dim=cfg.mlp_dim, gated=cfg.gated_mlp,
                    activation=cfg.activation, use_bias=cfg.use_bias,
                    k=cfg.moe_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    eval_capacity_factor=cfg.moe_capacity_factor,
                    dropless=cfg.moe_is_dropless,
                    norm_topk=cfg.moe_norm_topk,
                    aux_stats=cfg.moe_is_dropless,
                    scores=cfg.moe_scores,
                    select_bias=cfg.moe_select_bias,
                    routed_scale=cfg.moe_routed_scale, held=cfg.moe_held,
                    groups=cfg.moe_group_limit,
                    shared_dim=cfg.moe_shared_dim,
                    dtype=cfg.dtype,
                    name="moe")(h, train=train)
            width = cfg.dense_mlp_dim if self.dense_mlp else cfg.mlp_dim
            if cfg.gated_mlp:
                # SwiGLU (Llama family): down(act(gate(x)) * up(x)); the
                # gate/up matmuls fuse side by side on the MXU
                g = act(dense(width, "mlp_gate", bias=cfg.mlp_bias)(h))
                h = g * dense(width, "mlp_fc", bias=cfg.mlp_bias)(h)
                return dense(H, "mlp_proj", bias=cfg.mlp_bias)(h), aux
            h = dense(width, "mlp_fc")(h)
            h = act(h)
            h = dense(H, "mlp_proj")(h)
            return h, aux

        if cfg.parallel_residual:
            # GPT-J: one shared LN feeds both branches; GPT-NeoX: a separate
            # ln2 feeds the MLP branch. Single residual add either way.
            m, aux = (mlp(x, "ln2") if cfg.parallel_residual_dual_ln
                      else mlp(h))
            if cfg.dropout > 0.0 and train:
                m = nn.Dropout(cfg.dropout)(m, deterministic=False)
            return _batch_constraint(x + out + m), aux

        if cfg.post_ln:
            # BERT: LN after each residual add
            x = ln("ln1")(x + out)
            m, aux = mlp(x)
            if cfg.dropout > 0.0 and train:
                m = nn.Dropout(cfg.dropout)(m, deterministic=False)
            return _batch_constraint(ln("ln2")(x + m)), aux

        if cfg.post_block_norms:
            # Gemma-2 sandwich: norm each branch OUTPUT before its residual
            out = ln("post_attn_norm")(out)
        x = _batch_constraint(x + out)
        m, aux = mlp(x, "ln2" if cfg.pre_norm else None)
        if cfg.post_block_norms:
            m = ln("post_mlp_norm")(m)
        if cfg.dropout > 0.0 and train:
            m = nn.Dropout(cfg.dropout)(m, deterministic=False)
        return _batch_constraint(x + m), aux


class Transformer(nn.Module):
    """GPT-style LM (causal=True) or BERT-style encoder (causal=False)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, batch, train: bool = False):
        cfg = self.cfg
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            attention_mask = batch.get("attention_mask")
            position_ids = batch.get("position_ids")
        else:
            input_ids, attention_mask, position_ids = batch, None, None
        B, S = input_ids.shape

        if cfg.ltd_tokens > 0 and cfg.scan_layers:
            raise ValueError("random-LTD needs scan_layers=False (the token "
                             "subset changes layer shapes per depth)")
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="wte")
        # blocks receive the USER's position_ids only (None for the default
        # arange): rotary rebuilds arange internally, and alibi with default
        # positions rides the flash kernel's slope path instead of a
        # materialized [B, H, S, S] bias
        user_positions = position_ids
        if position_ids is None:
            position_ids = jnp.arange(S)[None, :]
        with jax.named_scope("embed"):
            x = wte(input_ids)
            if cfg.embed_scale is not None:
                x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
            if cfg.pos_embed == "learned":
                wpe = nn.Embed(cfg.max_seq_len, cfg.hidden_size, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name="wpe")
                x = x + wpe(position_ids)
            if cfg.token_type_vocab > 0:
                tte = nn.Embed(cfg.token_type_vocab, cfg.hidden_size,
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               name="tte")
                token_type_ids = (batch.get("token_type_ids")
                                  if isinstance(batch, dict) else None)
                if token_type_ids is None:
                    token_type_ids = jnp.zeros_like(input_ids)
                x = x + tte(token_type_ids)
            if cfg.embed_ln:
                x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                                 param_dtype=jnp.float32, name="ln_emb")(x)
            if cfg.dropout > 0.0 and train:
                x = nn.Dropout(cfg.dropout)(x, deterministic=False)
            x = _batch_constraint(x)

        # padding mask [B, 1, 1, S] broadcast over heads and query positions
        attn_mask = (attention_mask[:, None, None, :].astype(bool)
                     if attention_mask is not None else None)

        block = Block
        if cfg.remat:
            policies = {
                "full": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names("attn_out")),
                # leanest useful set: keep ONLY the flash-attention outputs
                # (recomputing flash fwd in bwd is the one expensive recompute)
                # and re-run qkv/mlp matmuls from the layer input — activation
                # memory per layer drops ~10x vs "dots", buying micro-batch
                "attn": jax.checkpoint_policies.save_only_these_names(
                    "attn_out"),
            }
            # CPU activation checkpointing (reference: checkpointing.py
            # cpu_checkpointing — saved activations live in host memory):
            # offload the attention outputs to pinned host, recompute the rest
            policies["offload"] = \
                jax.checkpoint_policies.save_and_offload_only_these_names(
                    names_which_can_be_saved=[],
                    names_which_can_be_offloaded=["attn_out"],
                    offload_src="device", offload_dst="pinned_host")
            if cfg.remat_policy not in policies:
                raise ValueError(f"unknown remat_policy '{cfg.remat_policy}'; "
                                 f"have {sorted(policies)}")
            # train AND window are static: a traced window would defeat the
            # sliding-window kernel routing in the unrolled path
            block = nn.remat(Block, static_argnums=(3, 4),
                             policy=policies[cfg.remat_policy])
        # uniform windows (Mistral-class): keep the window a STATIC python
        # int even under nn.scan so attention() can route to the
        # sliding-window / flash kernels; MIXED per-layer windows scan a
        # traced window that can only compose into the dense mask
        uw = cfg.uniform_window()
        static_window = uw or None
        windows = (jnp.asarray(cfg.layer_windows, jnp.int32)
                   if uw is None else None)
        ropes = (jnp.asarray(cfg.layer_rope) if cfg.layer_rope is not None
                 else None)
        pld_on = cfg.pld and train and self.has_rng("pld")
        theta = jnp.asarray(1.0, jnp.float32)
        if pld_on and isinstance(batch, dict) and \
                batch.get("pld_theta") is not None:
            theta = batch["pld_theta"].reshape(-1)[0].astype(jnp.float32)
        L = cfg.num_layers

        def pld_gate(mdl_rng, carry, out, aux, layer_idx):
            keep_p = 1.0 - ((layer_idx + 1.0) / L) * (1.0 - theta)
            keep = jax.random.bernoulli(mdl_rng, keep_p)
            return (jnp.where(keep, out, carry),
                    jnp.where(keep, aux, 0.0))

        if cfg.scan_layers:
            # the PLD variant threads an extra rng stream + layer index
            # through the scan; keep the plain body when PLD is off — the
            # extra scanned state disturbs the remat policy's saved set
            # (measured ~20% step-time regression on the bench model)
            if pld_on:
                def body(mdl, carry, xs):
                    w, li = xs
                    out, aux = mdl(carry, attn_mask, train,
                                   static_window if w is None else w,
                                   user_positions)
                    out, aux = pld_gate(mdl.make_rng("pld"), carry, out, aux,
                                        li.astype(jnp.float32))
                    return out, aux

                xs = (windows, jnp.arange(L))
                split = {"params": True, "dropout": True, "gating": True,
                         "pld": True}
            else:
                def body(mdl, carry, xs):
                    w, rope = xs if ropes is not None else (xs, None)
                    return mdl(carry, attn_mask, train,
                               static_window if w is None else w,
                               user_positions, rope)

                xs = windows if ropes is None else (windows, ropes)
                split = {"params": True, "dropout": True, "gating": True}

            def stack(x, name, lo, hi, **kind):
                """Layers lo .. hi - 1: one scanned stack of parameters."""
                part = xs if (lo, hi) == (0, L) else jax.tree.map(
                    lambda a: a[lo:hi], xs)
                return nn.scan(
                    body,
                    variable_axes={"params": 0},
                    split_rngs=split,
                    length=hi - lo,
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(block(cfg, name=name, **kind), x, part)

            # "layers" names what the loop itself costs (slicing the
            # stacked parameters, stacking residuals and gradients); the
            # blocks' own scopes lie inside it
            with jax.named_scope("layers"):
                if cfg.dense_layers:
                    # a mixture's leading dense layers, a stack of their
                    # own (no router: nothing for the auxiliary loss)
                    x, _ = stack(x, "dense_blocks", 0, cfg.dense_layers,
                                 dense_mlp=True)
                x, auxes = stack(x, "blocks", cfg.dense_layers, L)
            aux_total = jnp.sum(auxes, axis=0)
        else:
            aux_total = jnp.zeros((), jnp.float32)
            ltd_active = (train and cfg.ltd_tokens > 0
                          and cfg.ltd_end > cfg.ltd_start)
            if ltd_active and cfg.layer_windows is not None:
                raise ValueError(
                    "random-LTD + layer_windows is unsupported: the local "
                    "window would apply to compacted subset indices, voiding "
                    "the true token-distance constraint")
            for i in range(cfg.num_layers):
                # static python ints here (unlike the scanned path) so
                # attention() can route to the sliding-window kernel
                w = (int(cfg.layer_windows[i]) or None) \
                    if cfg.layer_windows is not None else None
                blk = block(cfg, name=f"blocks_{i}")
                if pld_on:
                    x_in = x
                if ltd_active and cfg.ltd_start <= i < cfg.ltd_end \
                        and cfg.ltd_tokens < S:
                    # random-LTD: this layer sees only a sampled token subset
                    # (sorted to keep causal order); dropped tokens ride the
                    # residual stream unchanged (reference: random_ltd
                    # gather/scatter kernels, csrc/random_ltd)
                    r = self.make_rng("gating")
                    idx = jnp.sort(jax.random.permutation(
                        jax.random.fold_in(r, i), S)[:cfg.ltd_tokens])
                    x_kept = jnp.take(x, idx, axis=1)
                    mask_kept = (attn_mask[..., idx]
                                 if attn_mask is not None else None)
                    out, aux = blk(x_kept, mask_kept, train, w,
                                   jnp.take(position_ids, idx, axis=1))
                    x = x.at[:, idx].set(out)
                else:
                    x, aux = blk(x, attn_mask, train, w, user_positions,
                                 None if cfg.layer_rope is None
                                 else bool(cfg.layer_rope[i]))
                if pld_on:
                    x, aux = pld_gate(self.make_rng("pld"), x_in, x, aux,
                                      float(i))
                aux_total = aux_total + aux

        if cfg.moe_is_dropless:
            # the blocks handed out [2, E] balance statistics: HF's
            # load_balancing_loss_func takes the means over ALL layers'
            # tokens before the product (a layer PLD dropped adds zeros)
            from ..moe.dropless import balance_loss
            aux_total = balance_loss(aux_total / cfg.sparse_layers)
        with jax.named_scope("head"):
            if not cfg.post_ln:
                # post-LN stacks (BERT) end already normalized by each block's ln2
                norm_cls = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
                x = norm_cls(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=jnp.float32, name="ln_f")(x)
        if cfg.mlm_head:
            # BERT cls.predictions: transform (dense+act+LN) then decoder
            # (tied embedding + output bias)
            h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="mlm_transform")(x)
            h = _ACTIVATIONS[cfg.activation](h)
            h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=jnp.float32, name="mlm_ln")(h)
            logits = wte.attend(h)
            bias = self.param("mlm_bias", nn.initializers.zeros,
                              (cfg.vocab_size,), jnp.float32)
            return (logits + bias).astype(jnp.float32)
        if cfg.no_lm_head:
            # encoder use (CLIP text): final hidden states are the output
            return x.astype(jnp.float32)
        if cfg.fused_loss:
            if cfg.final_logit_softcap:
                raise ValueError(
                    "fused_loss with final_logit_softcap is not supported "
                    "(the chunked CE has no softcap term); disable "
                    "fused_loss for Gemma-2-class models")
            if cfg.tie_embeddings:
                emb = wte.embedding
            else:
                # untied head (Llama family): declare the SAME lm_head/
                # kernel param the non-fused nn.Dense path creates, so
                # checkpoints and HF imports are layout-identical
                if cfg.lm_head_bias:
                    raise ValueError(
                        "fused_loss with a BIASED untied lm_head is not "
                        "supported (the chunked CE has no bias term)")
                emb = _HeadKernel(cfg.vocab_size, cfg.hidden_size,
                                  name="lm_head")().T
            labels = batch.get("labels", input_ids) if isinstance(batch, dict) \
                else input_ids
            # encoder stacks (BERT bench path) predict in place: no shift
            with jax.named_scope("loss"):
                loss = _fused_causal_lm_loss(x, emb, labels,
                                             cfg.loss_chunk,
                                             shift=1 if cfg.causal else 0)
            if cfg.moe_experts > 0:
                return loss, aux_total
            return loss
        with jax.named_scope("head"):
            if cfg.tie_embeddings:
                logits = wte.attend(x)
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias,
                                  dtype=cfg.dtype,
                                  param_dtype=jnp.float32, name="lm_head")(x)
            logits = logits.astype(jnp.float32)
            if cfg.final_logit_softcap:
                from ..ops.attention import apply_softcap
                logits = apply_softcap(logits, cfg.final_logit_softcap)
        if cfg.moe_experts > 0:
            return logits, aux_total
        return logits


class _HeadKernel(nn.Module):
    """Bare lm_head kernel for the fused-CE path: the param path/shape/init
    match nn.Dense(name="lm_head") exactly, so fused and non-fused models
    share checkpoints."""
    vocab_size: int
    hidden: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (self.hidden, self.vocab_size), jnp.float32)


def _fused_causal_lm_loss(x, emb, labels, chunk: int, shift: int = 1):
    """Next-token CE without materializing [B, S, V] logits.

    x: [B, S, H] final hidden states (compute dtype); emb: [V, H] fp32 tied
    embedding; labels: [B, S] token ids. The vocab projection runs per
    sequence-chunk under `jax.checkpoint`, so forward AND backward hold at
    most one [B, chunk, V] logits tile; XLA keeps the chunk matmuls on the
    MXU with fp32 accumulation. Replaces the reference's fused CE epilogue
    (csrc/transformer/general_kernels.cu cross-entropy path) the XLA way.

    Where the batch is split over chips the loop runs per shard
    (``_per_shard_nll_sum``). The only input is the mesh `x` is traced
    under: its ``BATCH_AXES`` that are wider than 1 and not manual already
    have to divide B. On one chip, under no mesh, inside a region that is
    manual over the batch axes, or where they do not divide B, this is the
    plain loop.
    """
    mesh = getattr(getattr(jax.typeof(x), "sharding", None), "mesh", None)
    wide, axes, n = (), (), 1
    if mesh is not None and not mesh.empty:
        from ..parallel.mesh import BATCH_AXES
        # the axes a chip's share can still be split along
        wide = tuple(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t != jax.sharding.AxisType.Manual
                     and mesh.shape[a] > 1)
        axes = tuple(a for a in wide if a in BATCH_AXES)
        n = int(np.prod([mesh.shape[a] for a in axes]))
    if n == 1 or x.shape[0] % n:
        total, count = _chunked_nll_sum(x, emb, labels, chunk, shift)
    else:
        total, count = _per_shard_nll_sum(
            mesh, axes, axes == wide and x.shape[2] % n == 0, x,
            emb.astype(x.dtype), labels, chunk, shift)
    return total / jnp.maximum(count, 1.0)


def _per_shard_nll_sum(mesh, axes, scatter: bool, x, emb_c, labels,
                       chunk: int, shift: int):
    """``_chunked_nll_sum`` per batch shard under ``jax.shard_map`` over the
    batch axes ``axes`` of ``mesh``, every other mesh axis left automatic;
    the two sums ``psum``'d.

    Why: the backward loop carries the [V, H] sum of the chunks' head
    gradients. Left to sharding propagation that carry takes the layout of
    the accumulated gradient (ZeRO's ``grad_spec``), and every chunk's
    product is reduced over the chips INSIDE the loop (PERF.md, PR 34: 32
    reductions of 412 MB a step of the dp4 cell). Here the carry is each
    chip's own partial sum and crosses ``axes`` ONCE a call, behind the
    loop, in the cotangent's dtype (scope ``grad_reduce``).

    The one reduction takes one of two forms, by the shape of the mesh.
    ``scatter``: ``axes`` are its only axes wider than 1 (and divide H), and
    it is a reduce-scatter over H: the head matrix goes in twice, whole, as it lies
    on every chip, for the arithmetic, and split over H, unused, to take
    the gradient, which leaves H-sharded over ``axes``; where ZeRO shards it
    so nothing follows, anywhere else the compiler adds the gather that
    makes it an all-reduce. Where another automatic axis is wider than 1
    (tensor parallelism: the head may be vocab-parallel over "model", which
    this code cannot see) it is the all-reduce: that the partitioner splits
    along such an axis, while it gathers a reduce-scatter's operand whole
    over it first."""
    vary = lambda a: jax.lax.pcast(a, axes, to="varying")

    @jax.custom_vjp
    def head_matrix(whole, split):
        return vary(whole)

    # XLA's CPU pipeline widens a 16-bit reduction to float32 itself
    # (AllReducePromotion) and aborts on a reducer that carries this
    # partly automatic region's sharding annotation: there the widening is
    # written out
    wire = jnp.float32 if jax.default_backend() == "cpu" else emb_c.dtype

    def head_matrix_bwd(_, g):
        with jax.named_scope("grad_reduce"):
            wide = g.astype(jnp.promote_types(g.dtype, wire))
            if scatter:
                return None, jax.lax.psum_scatter(
                    wide, axes, scatter_dimension=1, tiled=True).astype(g.dtype)
            return jax.lax.psum(wide, axes).astype(g.dtype), None

    head_matrix.defvjp(lambda whole, split: (vary(whole), None),
                       head_matrix_bwd)

    def per_shard(x, whole, split, labels):
        return jax.lax.psum(_chunked_nll_sum(
            x, head_matrix(whole, split), labels, chunk, shift, vary), axes)

    return jax.shard_map(
        per_shard, mesh=mesh, out_specs=(P(), P()), axis_names=frozenset(axes),
        in_specs=(P(axes), P(), P(None, axes) if scatter else None, P(axes)))(
            x, emb_c, emb_c if scatter else None, labels)


def _chunked_nll_sum(x, emb, labels, chunk: int, shift: int,
                     vary=lambda zero: zero):
    """(sum of the token NLLs, count of tokens that are not -100) of
    ``_fused_causal_lm_loss``: the loop over sequence chunks. ``vary``
    makes the two sums' zeros a shard's own inside ``shard_map``."""
    B, S, H = x.shape
    if shift:
        xs = x[:, :-1]          # causal LM: predict the NEXT token
        tgt = labels[:, 1:]
    else:
        xs, tgt = x, labels     # encoder/MLM-style: predict in place
    n = S - shift
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        # -100 padding folds seq padding into the ignore_index mask
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)), constant_values=-100)
    nc = (n + pad) // chunk
    xs = xs.reshape(B, nc, chunk, H).transpose(1, 0, 2, 3)       # [nc,B,C,H]
    tgt = tgt.reshape(B, nc, chunk).transpose(1, 0, 2)           # [nc,B,C]
    emb_c = emb.astype(x.dtype)

    @jax.checkpoint
    def chunk_nll(xc, tc):
        vc = (tc != -100).astype(jnp.float32)        # ignore_index + padding
        safe = jnp.maximum(tc, 0)
        logits = jnp.einsum("bch,vh->bcv", xc, emb_c,
                            preferred_element_type=jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * vc), jnp.sum(vc)

    def body(acc, inp):
        xc, tc = inp
        nll, cnt = chunk_nll(xc, tc)
        return (acc[0] + nll, acc[1] + cnt), None

    return jax.lax.scan(
        body, (vary(jnp.zeros((), jnp.float32)),
               vary(jnp.zeros((), jnp.float32))), (xs, tgt))[0]


def fused_loss_passthrough(outputs, batch):
    """Engine loss_fn for models built with fused_loss=True (outputs IS the loss)."""
    return outputs


# ---------------------------------------------------------------------------
# Loss functions (engine `loss_fn` contract: loss_fn(outputs, batch) -> scalar)
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, ignore_index: int = -100):
    """Token-level CE with ignore mask; fp32 accumulation."""
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def causal_lm_loss(logits, batch):
    """Next-token prediction: shift logits/labels by one."""
    labels = batch.get("labels", batch["input_ids"]) if isinstance(batch, dict) else batch
    return cross_entropy(logits[:, :-1], labels[:, 1:])


def masked_lm_loss(logits, batch):
    """BERT-style: loss only where labels != -100."""
    return cross_entropy(logits, batch["labels"])


def make_moe_loss(aux_weight: float = 0.01, base_loss=None):
    """Loss for MoE models returning (logits, aux): task loss + aux_weight*aux
    (reference: l_aux scaled into the training loss by the client; the engine
    keeps the same contract)."""
    base = base_loss or causal_lm_loss

    def moe_loss(outputs, batch):
        logits, aux = outputs
        return base(logits, batch) + aux_weight * aux

    # marker for schedule dispatch: the 1F1B executor computes the aux term
    # itself (the aux scalar rides the pipe), so the pipe engine must NOT
    # route a moe loss through the per-micro custom-loss path (which would
    # hand it a bare logits array and double-count the aux)
    moe_loss._moe_loss = True
    moe_loss._moe_base_loss = base
    moe_loss._moe_aux_weight = aux_weight
    return moe_loss


def build_model(name_or_cfg, **overrides) -> Tuple[Transformer, TransformerConfig]:
    cfg = (name_or_cfg if isinstance(name_or_cfg, TransformerConfig)
           else get_config(name_or_cfg, **overrides))
    return Transformer(cfg), cfg


class DeepSpeedTransformerLayer(nn.Module):
    """Reference-parity fused transformer layer
    (ops/transformer/transformer.py:459 DeepSpeedTransformerLayer): one
    attention+MLP block applied to [B, S, H] hidden states. On TPU the
    "fused kernels" are XLA fusion + the Pallas attention the Block
    routes to; configure with TransformerConfig (exported under the
    reference's name DeepSpeedTransformerConfig — batch size and seq
    length are runtime shapes here, not config fields)."""
    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None,
                 train: bool = False):
        if attention_mask is not None:
            if jnp.issubdtype(jnp.asarray(attention_mask).dtype,
                              jnp.floating):
                # the reference feeds ADDITIVE float masks ((1-m)*-1e4);
                # this layer's contract is boolean True=attend — passing
                # the additive form through jnp.where would attend exactly
                # the inverted positions with no error
                raise ValueError(
                    "DeepSpeedTransformerLayer takes a boolean/int "
                    "attention_mask (True/1 = attend), not the additive "
                    "float mask; convert with mask = additive_mask >= 0")
            attention_mask = jnp.asarray(attention_mask).astype(bool)
            if attention_mask.ndim == 2:      # HF-style [B, S] key mask
                attention_mask = attention_mask[:, None, None, :]
        if self.config.moe_experts > 0:
            # the single-layer shim has no channel for the router's
            # load-balancing aux loss; dropping it silently would collapse
            # the experts — use build_model(..., moe_experts=...) whose
            # (logits, aux) contract carries it
            raise ValueError(
                "DeepSpeedTransformerLayer does not support MoE configs "
                "(the router aux loss would be silently dropped); build "
                "the full model via models.build_model(moe_experts=...)")
        y, _aux = Block(self.config)(hidden_states, attention_mask, train)
        return y


# reference export name (deepspeed/__init__.py:24-25)
DeepSpeedTransformerConfig = TransformerConfig
