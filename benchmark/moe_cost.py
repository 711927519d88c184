"""What one layer's expert matmuls NEED, from the sizes of its groups, and the
share of a chip's roofline that a measured time is.

The mixture's expert body is three grouped matmuls (gate, up, down; two
without a gate) over the ``rows = tokens x k`` assignments sorted by expert
(``deepspeed_tpu/moe/dropless.py``). Needed, whatever kernel does it:

* operations: ``2 x matrices x rows x hidden x width`` (a row meets one
  expert's matrices and no other);
* bytes: the matrices of every expert that HAS a row, read once (an expert
  nobody picked need not be read), plus the rows in and the rows out at the
  model's width. The intermediate ``[rows, width]`` arrays between the three
  matmuls are the implementation's traffic, not the mixture's need, and are
  not counted.

Counted from REAL tokens: a kernel that also computes padding rows (an idle
decode lane, a chunk's padding) does work nobody needs, and its share falls.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple


def needed(rows: int, active_experts: int, *, hidden: int, width: int,
           matrices: int = 3, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of expert matmuls over ``rows`` assignments that
    reach ``active_experts`` experts (summed over layers and calls)."""
    flops = 2.0 * matrices * rows * hidden * width
    moved = float(itemsize) * (active_experts * matrices * hidden * width
                               + 2 * rows * hidden)
    return flops, moved


def needed_from_groups(group_sizes: Sequence[int], **dims) -> Tuple[float,
                                                                   float]:
    """:func:`needed` of one layer's ``group_sizes [E]``."""
    sizes = [int(g) for g in group_sizes]
    return needed(sum(sizes), sum(1 for g in sizes if g > 0), **dims)


def roofline(flops: float, moved: float, seconds: float,
             peaks: Dict[str, Any]) -> Dict[str, Any]:
    """The least time the chip could take (the larger of operations over
    its bf16 peak and bytes over its memory bandwidth, ``peaks.json``) over
    the measured ``seconds``, in %, and which of the two bounds it."""
    by_flops = flops / (peaks["bf16_tflops"] * 1e12)
    by_bytes = moved / (peaks["hbm_gb_per_s"] * 1e9)
    return {"pct": 100.0 * max(by_flops, by_bytes) / seconds,
            "bound": "compute" if by_flops > by_bytes else "memory",
            "least_ms": 1e3 * max(by_flops, by_bytes),
            "measured_ms": 1e3 * seconds}
