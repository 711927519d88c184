"""Operations a model NEEDS, from its sizes (never from the compiled program:
XLA's cost analysis counts recomputed operations too).

``dims`` is what a family's ``dims(config)`` returns: layers, hidden, heads,
kv_heads, head_dim, mlp_dim, mlp_matrices (2, or 3 for a gated MLP), vocab,
tied_head, learned_positions.

Conventions, stated because ``benchmarks/training_bench.py`` differs:

* matmul parameters: per layer qkv ``hidden x (heads + 2 kv_heads) x
  head_dim``, output projection ``heads x head_dim x hidden``, MLP
  ``mlp_matrices x hidden x mlp_dim``; plus the output head ``vocab x
  hidden`` ONCE, tied or not (the embedding lookup is no matmul, positions
  are none). Forward 2 FLOPs a parameter a token, backward 4: ``6 N``.
* attention is CAUSAL: a query at position t needs t+1 keys, S/2 on average,
  so QK^T and PV need ``2 x 2 x (S/2) x heads x head_dim = 2 S heads
  head_dim`` forward FLOPs a token a layer, three times that with the
  backward: ``6 L S heads head_dim``. The non-causal figure (12 L H S, what
  ``training_bench`` counts) is twice this.
* recomputation (remat) is NOT counted: it is work the program chose.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(d: Dict[str, Any]) -> int:
    attn = d["hidden"] * (d["heads"] + 2 * d["kv_heads"]) * d["head_dim"] \
        + d["heads"] * d["head_dim"] * d["hidden"]
    mlp = d["mlp_matrices"] * d["hidden"] * d["mlp_dim"]
    return d["layers"] * (attn + mlp) + d["vocab"] * d["hidden"]


def train_flops_per_token(d: Dict[str, Any], seq_len: int) -> float:
    """Needed forward + backward FLOPs for one trained token at ``seq_len``."""
    return 6.0 * matmul_params(d) \
        + 6.0 * d["layers"] * d["heads"] * d["head_dim"] * seq_len

