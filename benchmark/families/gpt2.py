"""GPT-2 / GPT-3 family as Megatron-DeepSpeed trains it: pre-LN blocks,
LayerNorm with bias, learned positions, fused qkv with bias, GELU (tanh) MLP
of 4x hidden, output head tied to the token embedding.

Config keys (Megatron argument names): ``num_layers``, ``hidden_size``,
``num_attention_heads``, ``ffn_hidden_size``, ``max_position_embeddings``,
``vocab_size``, ``layernorm_epsilon``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark import reference as ref


def model_kwargs(c: Dict[str, Any]) -> Dict[str, Any]:
    """Keyword arguments of ``deepspeed_tpu.models.TransformerConfig``."""
    if c["ffn_hidden_size"] != 4 * c["hidden_size"]:
        raise ValueError("gpt2 family: ffn_hidden_size must be 4 x hidden")
    return dict(vocab_size=c["vocab_size"],
                max_seq_len=c["max_position_embeddings"],
                hidden_size=c["hidden_size"], num_layers=c["num_layers"],
                num_heads=c["num_attention_heads"], mlp_ratio=4,
                layer_norm_eps=c["layernorm_epsilon"], activation="gelu",
                pos_embed="learned", tie_embeddings=True, use_bias=True,
                norm="layernorm")


def dims(c: Dict[str, Any]) -> Dict[str, Any]:
    return dict(layers=c["num_layers"], hidden=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_attention_heads"],
                head_dim=c["hidden_size"] // c["num_attention_heads"],
                mlp_dim=c["ffn_hidden_size"], mlp_matrices=2,
                vocab=c["vocab_size"])


@functools.lru_cache(maxsize=None)
def _steps(layers: int, heads: int, head_dim: int, eps: float):
    def block(p, x):
        S = x.shape[0]
        h = ref.layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], eps)
        qkv = h @ p["attn_qkv"]["kernel"] + p["attn_qkv"]["bias"]
        q, k, v = (t.reshape(S, heads, head_dim)
                   for t in jnp.split(qkv, 3, axis=-1))
        a = ref.causal_attention(q, k, v)
        x = x + a @ p["attn_proj"]["kernel"] + p["attn_proj"]["bias"]
        h = ref.layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], eps)
        h = ref.gelu_tanh(h @ p["mlp_fc"]["kernel"] + p["mlp_fc"]["bias"])
        return x + h @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]

    @jax.jit
    def embed(params, ids):
        wte = params["wte"]["embedding"].astype(jnp.float32)
        wpe = params["wpe"]["embedding"].astype(jnp.float32)
        return wte[ids] + wpe[:ids.shape[0]]

    @jax.jit
    def head(params, x):
        x = ref.layer_norm(x, params["ln_f"]["scale"].astype(jnp.float32),
                           params["ln_f"]["bias"].astype(jnp.float32), eps)
        return x @ params["wte"]["embedding"].astype(jnp.float32).T

    return embed, ref.layer_step(block), head


def reference_logits(c: Dict[str, Any], params, ids) -> jnp.ndarray:
    """``[S, vocab]`` float32 logits of one sequence ``ids [S]``, from the
    program's parameter tree (scan layout: ``blocks`` stacked by layer)."""
    d = dims(c)
    embed, step, head = _steps(d["layers"], d["heads"], d["head_dim"],
                               float(c["layernorm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        x = ref.walk_layers(step, params["blocks"], embed(params, ids),
                            d["layers"])
        return head(params, x)
