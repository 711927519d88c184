"""What DeepSeek Sparse Attention over a latent cache NEEDS, from the
program's ``sparse.*`` and ``mla.*`` counters (``deepspeed_tpu/serving/
engine.py``; both count in a latent model with an indexer), whatever kernels
do it. ``moe_cost.roofline`` turns needed operations and bytes and a measured
time into a share.

The need is of the MATHEMATICS, so that no implementation can read over
100%:

* **index scores** (``sparse_index_scores``) and the **top-k**
  (``sparse_topk``): ``sparse_cost``'s, as they stand: ``2 x 64 x 128``
  operations a (row, visible key) and each live indexer key once a lane and
  call; an exact selection looks at every score once.
* **attention over the SELECTED keys only** (``paged_attention_latent``):
  a decode row the absorbed count (``mla_cost.pair_flops``) over its
  ``min(ctx, index_topk)`` selected rows of ``(rank + rope) x itemsize``
  bytes (1 152), whatever the kernel walks (it walks every live page and
  masks: ``mla.ctx_tokens_sum / mla.selected_keys_sum`` times the need at
  long contexts, which is what reading the selected rows only would win
  back); a prefill call the SMALLER of the two forms' counts over its (row,
  selected key) pairs, the expansion charged once a cached token the call
  sees and no more often than a pair selects one; its bytes each cached
  token the call sees once, but no more than its rows select.
* the rows' queries in and outputs out at the absorbed widths.

Counted from real rows: padding rows and idle lanes are work nobody needs.
One call of :func:`attention` is one loop step's counters.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import mla_cost, sparse_cost

COUNTERS = (
    "sparse.rows_sum", "sparse.keys_scored_sum", "sparse.keys_selected_sum",
    "sparse.pages_walked_sum", "mla.rows_sum", "mla.ctx_tokens_sum",
    "mla.selected_keys_sum", "mla.chunk_selected_keys_sum",
    "mla.chunk_keys_sum")


def attention(c: Dict[str, float], dims: Dict[str, Any], block_size: int = 0,
              itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) attention over the selected latent rows needs
    for ONE step's counters ``c``."""
    absorbed, expanded, expand = mla_cost.pair_flops(dims)
    row = dims["kv_lora_rank"] + dims["qk_rope_head_dim"]
    chunk_pairs = c.get("mla.chunk_selected_keys_sum", 0)
    chunk_keys = min(c.get("mla.chunk_keys_sum", 0), chunk_pairs)
    decode_pairs = c["mla.selected_keys_sum"] - chunk_pairs
    flops = absorbed * decode_pairs + min(
        absorbed * chunk_pairs, expanded * chunk_pairs + expand * chunk_keys)
    moved = itemsize * (row * (decode_pairs + chunk_keys)
                        + dims["heads"] * (row + dims["kv_lora_rank"])
                        * c["mla.rows_sum"])
    return flops, float(moved)


#: kernel name in the trace -> its needed (operations, bytes) of one step's
#: counters
KERNELS = {
    "sparse_index_scores": sparse_cost.index_scores,
    "sparse_topk": lambda c, dims, block_size: sparse_cost.topk(c),
    "paged_attention_latent": attention,
}
