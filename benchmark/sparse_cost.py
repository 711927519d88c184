"""What a layer with an indexer NEEDS for its selection and for attention
over it, from the program's ``sparse.*`` counters (``deepspeed_tpu/serving/
engine.py``: query rows, keys scored, keys selected and pages walked, each
summed over layers and calls), whatever kernels do it. ``moe_cost.roofline``
turns needed operations and bytes and a measured time into a share.

* **index scores** (``sparse_index_scores``): every (row, visible key) pair
  is ``index_heads`` dot products of ``index_head_dim``: ``2 x heads x width``
  operations a pair. Bytes: the indexer keys of the pages a call walks, read
  once (a chunk's rows share them), the rows' queries and head weights in,
  one float32 score a pair out (the scores ARE this kernel's product: the
  top-k and the attention mask read them).
* **top-k** (``sparse_topk``): an exact selection has to look at every score
  once: one comparison and four bytes a scored key, two words a row out. The
  bisection makes 47 passes over a row tile in VMEM, so its share of this
  roofline is what a single-pass selection would gain, not a fault.
* **attention over the selection** (``paged_attention``): ``4 x heads x
  head_dim`` operations a (row, selected key) pair (scores and values).
  Bytes: K and V of the selected keys at the stored heads, but no more than
  the pages the call walks hold (a chunk's 256 rows select 2048 keys each
  out of one lane's: together nearly all of them, read once), the queries
  in and the outputs out. The kernel reads EVERY walked page and masks: at
  long contexts its bytes are ``seen / 2048`` times the decode rows' need,
  which is the share a page-skipping kernel would win back.

Counted from real rows: padding rows and idle lanes are work nobody needs.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def index_scores(c: Dict[str, float], dims: Dict[str, Any], block_size: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of the index scores behind counters ``c``."""
    heads, width = dims["index_heads"], dims["index_head_dim"]
    flops = 2.0 * heads * width * c["sparse.keys_scored_sum"]
    moved = (c["sparse.pages_walked_sum"] * block_size * width * itemsize
             + c["sparse.rows_sum"] * heads * (width * itemsize + 4)
             + 4.0 * c["sparse.keys_scored_sum"])
    return flops, moved


def topk(c: Dict[str, float]) -> Tuple[float, float]:
    """(operations, bytes) of an exact top-k over the scored keys."""
    return (float(c["sparse.keys_scored_sum"]),
            4.0 * c["sparse.keys_scored_sum"] + 8.0 * c["sparse.rows_sum"])


def attention(c: Dict[str, float], dims: Dict[str, Any], block_size: int,
              itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of attention over the selected keys."""
    heads, kvh, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    flops = 4.0 * heads * hd * c["sparse.keys_selected_sum"]
    keys = min(c["sparse.keys_selected_sum"],
               c["sparse.pages_walked_sum"] * block_size)
    moved = itemsize * (2.0 * kvh * hd * keys
                        + 2.0 * heads * hd * c["sparse.rows_sum"])
    return flops, moved


#: kernel name in the trace -> its needed (operations, bytes)
KERNELS = {
    "sparse_index_scores": index_scores,
    "sparse_topk": lambda c, dims, block_size: topk(c),
    "paged_attention": attention,
}
