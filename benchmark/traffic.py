"""The one traffic generator: a mix is a data file under ``traffic/``.

Two kinds of mix, chosen by the file's ``kind``:

``token_batches``  training: every step a fresh batch of uniform random
    tokens, ``rows_per_chip`` x ``seq_len`` for each chip.
``closed_loop``    serving: ``clients`` callers, each submitting its next
    request the moment its last one finishes. Requests come in order from one
    stream.

What ``--seed`` changes and what it does not. The SIZES of the work (batch
shape; the sequence of prompt and output lengths) are fixed by the mix file:
lengths are the mid-quantiles of the file's clipped lognormals, ``cycle`` of
them, shuffled by the file's own ``mix_seed``, so every seed offers the same
requests in the same order and two runs differ only by the clock. The
CONTENT (every token id) comes from ``--seed``.

Why a replay and not a draw from ``--seed``: the serving loop does not look
at the clock, so one order of sizes gives one sequence of admissions, step
for step, and a window of some 80 requests is too few to average over
orders (PERF.md section 6 has the spreads over ``mix_seed``). A metric whose
value hangs on the order is therefore not given a bound at all; the replay
keeps the others comparable between two commits.
"""

from __future__ import annotations

import itertools
import statistics
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def token_batches(mix: Dict[str, Any], vocab: int, seed: int,
                  chips: int) -> Iterator[np.ndarray]:
    """Endless ``[rows_per_chip * chips, seq_len]`` int32 batches."""
    if mix["kind"] != "token_batches":
        raise ValueError(f"mix kind {mix['kind']!r} is not token_batches")
    rows = int(mix["micro_batch_per_chip"]) * int(mix["grad_accum_steps"]) \
        * chips
    rng = np.random.default_rng([int(seed), 0])
    while True:
        yield rng.integers(0, vocab, size=(rows, int(mix["seq_len"])),
                           dtype=np.int32)


def _quantile_lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of a lognormal given
    by its median and sigma, clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = float(dist["median"]) * float(np.exp(float(dist["sigma"]) * z))
        out.append(int(min(max(round(v), int(dist["min"])),
                           int(dist["max"]))))
    return out


def request_sizes(mix: Dict[str, Any]) -> Iterator[Tuple[int, int]]:
    """The endless sequence of (prompt length, output length): the same for
    every seed. Each cycle holds the same multiset, in an order of its own."""
    n = int(mix["cycle"])
    prompts = _quantile_lengths(mix["prompt_len"], n)
    outputs = _quantile_lengths(mix["output_len"], n)
    for c in itertools.count():
        rng = np.random.default_rng([int(mix["mix_seed"]), c])
        po, oo = rng.permutation(n), rng.permutation(n)
        for i in range(n):
            yield prompts[po[i]], outputs[oo[i]]


def request_stream(mix: Dict[str, Any], vocab: int, seed: int
                   ) -> Iterator[Tuple[List[int], int]]:
    """Endless (prompt tokens, max_new_tokens): the sizes of
    :func:`request_sizes`, every token id the request's own."""
    if mix["kind"] != "closed_loop":
        raise ValueError(f"mix kind {mix['kind']!r} is not closed_loop")
    rng = np.random.default_rng([int(seed), 1])
    for p_len, o_len in request_sizes(mix):
        yield rng.integers(1, vocab, size=p_len).tolist(), o_len


def seeded_tokens(vocab: int, seed: int, stream: int, n: int) -> List[int]:
    """``n`` token ids of a side stream (warm-up, the checked requests)."""
    rng = np.random.default_rng([int(seed), 2, int(stream)])
    return rng.integers(1, vocab, size=n).tolist()
