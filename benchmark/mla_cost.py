"""What latent attention NEEDS, from the program's ``mla.*`` counters
(``deepspeed_tpu/serving/engine.py``: query rows, the cached tokens they
attend, and for prefill calls the cached tokens their rows attend and the
cached tokens a call sees, each summed over layers), whatever kernel does
it. ``moe_cost.roofline`` turns needed operations and bytes and a measured
time into a share.

The need is of the MATHEMATICS, so that no implementation can read over
100%:

* **a decode row** (one token of a lane against its ``ctx`` cached tokens):
  one row a head cannot pay for re-expanding its keys, so its least work is
  the absorbed form's: ``heads x 2 x ((rank + rope) + rank)`` operations a
  cached token (the score over the whole stored row, the value over its
  latent; 278 528 at DeepSeek-V2's widths) and each live latent row's
  ``(rank + rope) x itemsize`` bytes (1 152) read once a lane.
* **a prefill call** (a chunk's rows against the cached tokens they see, its
  own included): the SMALLER of the two forms' counts. Absorbed: the same
  operations a (row, cached token) pair. Expanded: ``heads x 2 x ((nope +
  rope) + v)`` a pair (81 920) and ``heads x 2 x rank x (nope + v)`` (33.6 M)
  to expand each cached token the call sees, charged ONCE a call (a kernel
  that re-expands a row tile or a head program at a time does more than it
  needs to). The bytes: each cached token's row once a call.
* both: the rows' queries in and outputs out at the absorbed widths,
  ``heads x ((rank + rope) + rank) x itemsize`` a row.

Counted from real rows: padding rows and idle lanes are work nobody needs.
One call of :func:`attention` is one loop step's counters (a step makes at
most one prefill call, so the smaller form is chosen a call);
``mla_roofline.py`` adds the steps up.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

COUNTERS = ("mla.rows_sum", "mla.ctx_tokens_sum", "mla.pages_walked_sum",
            "mla.chunk_ctx_tokens_sum", "mla.chunk_keys_sum")


def pair_flops(dims: Dict[str, Any]) -> Tuple[float, float, float]:
    """``(absorbed operations a (row, cached token) pair, expanded ones a
    pair, operations to expand one cached token)``, over all heads."""
    heads, rank = dims["heads"], dims["kv_lora_rank"]
    nope, rope, v = (dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                     dims["v_head_dim"])
    return (2.0 * heads * ((rank + rope) + rank),
            2.0 * heads * ((nope + rope) + v),
            2.0 * heads * rank * (nope + v))


def attention(c: Dict[str, float], dims: Dict[str, Any], itemsize: int = 2
              ) -> Tuple[float, float]:
    """(operations, bytes) latent attention needs for ONE step's counters
    ``c`` (its decode call's rows and, where it made one, its prefill
    call's)."""
    absorbed, expanded, expand = pair_flops(dims)
    row = dims["kv_lora_rank"] + dims["qk_rope_head_dim"]
    chunk_pairs = c.get("mla.chunk_ctx_tokens_sum", 0)
    chunk_keys = c.get("mla.chunk_keys_sum", 0)
    decode_pairs = c["mla.ctx_tokens_sum"] - chunk_pairs
    flops = absorbed * decode_pairs + min(
        absorbed * chunk_pairs, expanded * chunk_pairs + expand * chunk_keys)
    moved = itemsize * (row * (decode_pairs + chunk_keys)
                        + dims["heads"] * (row + dims["kv_lora_rank"])
                        * c["mla.rows_sum"])
    return flops, float(moved)
