"""Mistral family (HF ``MistralForCausalLM``): pre-norm blocks, RMSNorm,
rotate-half rotary positions, grouped-query attention under a sliding
window, SwiGLU MLP, no biases, untied output head.

Config keys (HF ``config.json`` names): ``num_hidden_layers``,
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``rms_norm_eps``, ``rope_theta``, ``sliding_window``,
``max_position_embeddings``, ``vocab_size``, ``tie_word_embeddings``,
``hidden_act``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark import reference as ref


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"]:
        raise ValueError("mistral family: silu SwiGLU and an untied head")
    window = int(c["sliding_window"] or 0)
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
                use_bias=False, tie_embeddings=False,
                layer_windows=((window,) * c["num_hidden_layers"]
                               if window else None))


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(layers=c["num_hidden_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                head_dim=c["hidden_size"] // c["num_attention_heads"],
                mlp_dim=c["intermediate_size"], mlp_matrices=3,
                vocab=c["vocab_size"])


@functools.lru_cache(maxsize=None)
def _steps(heads: int, kv_heads: int, head_dim: int, eps: float,
           theta: float, window: int):
    def block(p, x):
        S = x.shape[0]
        h = ref.rms_norm(x, p["ln1"]["scale"], eps)
        qkv = h @ p["attn_qkv"]["kernel"]
        q, k, v = jnp.split(qkv, [heads * head_dim,
                                  (heads + kv_heads) * head_dim], axis=-1)
        q = ref.rotary_half(q.reshape(S, heads, head_dim), theta)
        k = ref.rotary_half(k.reshape(S, kv_heads, head_dim), theta)
        a = ref.causal_attention(q, k, v.reshape(S, kv_heads, head_dim),
                                 window=window)
        x = x + a @ p["attn_proj"]["kernel"]
        h = ref.rms_norm(x, p["ln2"]["scale"], eps)
        g = ref.silu(h @ p["mlp_gate"]["kernel"]) * (h @ p["mlp_fc"]["kernel"])
        return x + g @ p["mlp_proj"]["kernel"]

    @jax.jit
    def embed(params, ids):
        return params["wte"]["embedding"].astype(jnp.float32)[ids]

    @jax.jit
    def head(params, x):
        x = ref.rms_norm(x, params["ln_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)

    return embed, ref.layer_step(block), head


def reference_logits(c: Dict[str, Any], params, ids) -> jnp.ndarray:
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (scan layout: ``blocks`` stacked by layer)."""
    d = dims(c)
    embed, step, head = _steps(d["heads"], d["kv_heads"], d["head_dim"],
                               float(c["rms_norm_eps"]),
                               float(c["rope_theta"]),
                               int(c["sliding_window"] or 0))
    with jax.default_matmul_precision("highest"):
        x = ref.walk_layers(step, params["blocks"], embed(params, ids),
                            d["layers"])
        return head(params, x)
