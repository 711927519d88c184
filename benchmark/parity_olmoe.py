#!/usr/bin/env python3
"""OLMoE at its published widths, held to the plain reference on the chip.

    python3 benchmark/parity_olmoe.py [--workload serve-olmoe-1b-7b-l8-gen] [--seeds 3]

The benchmark's own ``correct`` (the served token's reference logit within
0.15 of the best) cannot see a wrong mixture: with random weights the experts
add a few percent to the residual. These two checks can, and a later PR that
touches ``moe/dropless.py`` or ``paged_forward`` reruns them:

1. **Block parity**: ``deepspeed_tpu.moe.dropless.dropless_moe`` in bfloat16
   against ``families/olmoe.reference_moe`` in float32 (``highest``) on the
   same seeded inputs (256 and 64 rows of unit RMS, a prefill chunk's and a
   decode step's) and one layer's seeded weights. The reference routes by
   the picks the program returns (``Routing.experts``) and holds each to its
   own float32 scores: ``reference.pick_deficit`` within
   ``reference.ROUTE_TIE_TOL``, what the serving check holds a served
   request's picks to, so the hand tool and the check that decides agree on
   what a tie is. EVERY token's output has to lie within ``BLOCK_TOL`` of
   the reference's (relative, in the 2-norm). The reference with every
   token's weakest pick left out, and the reference fed float8 inputs and
   weights, both have to FAIL that tolerance.
2. **Model parity**: prefill in chunks, then decode, through
   ``serving.model_runner.paged_forward`` and the paged pool (the shapes the
   serving loop uses) against the reference's full forward on the cell's
   checked prompt lengths: the logits of the last 128 positions, teacher
   forced, never sampled tokens.

Prints one JSON object; exits non-zero where a check fails. ``paged_logits``
is also what ``tests/test_olmoe.py`` holds to the reference at a tiny size on
the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark.reference import ROUTE_TIE_TOL  # noqa: E402

#: relative 2-norm error of one token's mixture output, bf16 against float32.
#: Measured on the chip (PERF.md, PR 27): the largest of any token over three
#: seeds and both shapes is 0.0045 (median 0.0039: bf16 rounding of inputs,
#: weights and the three matmuls' results). The reference with every token's
#: weakest pick left out lies 0.038 to 0.071 off at its CLOSEST token, and with
#: float8 inputs and weights 0.070 to 0.072 at the median. 0.01 is twice the
#: largest seen and fails both by 3.8 and 7 times
BLOCK_TOL = 0.01
#: model parity, the root mean square over the last 128 positions and the
#: whole vocabulary of (paged bf16 logits - reference float32 logits); the
#: logits' own spread is 1.00. Measured on the chip (PERF.md, PR 27): bf16
#: weights, activations and KV pool over 8 layers give 0.011 to 0.015 on the
#: four checked lengths, the largest single logit 0.15 off. 0.03 is twice the
#: largest seen; the reference itself with float8 weights (the nearest
#: precision below) has to lie further off than that, and the script checks
#: that it does. A missing expert, a wrong rotary position or a stale cache
#: slot gives tenths
MODEL_RMS_TOL = 0.03
MODEL_MAX_TOL = 0.5
KEEP = 128


def paged_logits(cfg, params, seqs: Sequence[np.ndarray], n_new: int, *,
                 block_size: int, chunk: int, keep: int = KEEP,
                 interpret: bool = False) -> List[np.ndarray]:
    """Teacher-forced logits of every sequence's last ``keep`` positions
    through ``paged_forward``: each prompt (all but the last ``n_new``
    tokens) prefilled alone in chunks of ``chunk`` (the last padded to a
    block multiple, as the engine pads it), then all of them decoded
    together, one token a lane a step, beside two idle lanes."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.serving.kv_cache import NULL_BLOCK, init_pool
    from deepspeed_tpu.serving.model_runner import paged_forward

    bs = int(block_size)
    nbk = -(-max(len(s) for s in seqs) // bs)
    lanes = len(seqs) + 2
    tables = np.full((lanes, nbk), NULL_BLOCK, np.int32)
    for i in range(len(seqs)):
        tables[i] = 1 + i * nbk + np.arange(nbk)
    pools = init_pool(cfg, 1 + len(seqs) * nbk, bs)
    step = jax.jit(
        lambda pools, params, ids, bt, q0, ctx: paged_forward(
            cfg, params, ids, pools, bt, q0, ctx, bs, interpret=interpret),
        donate_argnums=(0,))
    out: List[List[np.ndarray]] = [[] for _ in seqs]
    for i, s in enumerate(seqs):
        n = len(s) - n_new
        for q0 in range(0, n, chunk):
            real = min(chunk, n - q0)
            ids = np.zeros((1, -(-real // bs) * bs), np.int32)
            ids[0, :real] = s[q0:q0 + real]
            logits, pools = step(pools, params, jnp.asarray(ids),
                                 jnp.asarray(tables[i:i + 1]),
                                 jnp.asarray([q0], jnp.int32),
                                 jnp.asarray([q0 + real], jnp.int32))
            out[i].append(np.asarray(logits[0, :real]))
    for j in range(n_new):
        ids = np.zeros((lanes, 1), np.int32)
        ctx = np.zeros((lanes,), np.int32)
        for i, s in enumerate(seqs):
            ctx[i] = len(s) - n_new + j
            ids[i, 0] = s[ctx[i]]
        logits, pools = step(pools, params, jnp.asarray(ids),
                             jnp.asarray(tables), jnp.asarray(ctx),
                             jnp.asarray(ctx + 1))
        for i in range(len(seqs)):
            out[i].append(np.asarray(logits[i]))
    return [np.concatenate(o)[-keep:] for o in out]


def block_parity(config: Dict[str, Any], seed: int, rows: int
                 ) -> Dict[str, Any]:
    """One layer's mixture on ``rows`` seeded tokens: the system in bf16,
    the reference in float32, and the two readings that have to fail."""
    import jax
    import jax.numpy as jnp
    from benchmark.families import olmoe as fam
    from deepspeed_tpu.moe.dropless import dropless_moe

    H, M = config["hidden_size"], config["intermediate_size"]
    E, k = config["num_experts"], config["num_experts_per_tok"]
    renorm = bool(config["norm_topk_prob"])
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))

    def draw(i, shape, fan_in):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * fan_in ** -0.5
                ).astype(jnp.bfloat16)

    moe = {"gate": {"kernel": draw(0, (H, E), H)},
           "experts": {"gate": {"kernel": draw(1, (E, H, M), H)},
                       "fc": {"kernel": draw(2, (E, H, M), H)},
                       "proj": {"kernel": draw(3, (E, M, H), M)}}}
    x = draw(4, (rows, H), 1.0)                   # unit RMS, as after a norm
    y, routing = jax.jit(lambda x, moe: dropless_moe(
        x, moe["gate"]["kernel"], moe["experts"], k=k, renorm=renorm,
        act=jax.nn.silu))(x, moe)
    picks = routing.experts
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    f8 = lambda t: jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), t)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(fam.reference_moe, static_argnums=(2, 3, 4))
        # the program's picks, held to the reference's own scores
        want, _, _, deficit = ref(f32(moe), f32(x), k, renorm, -1, picks)
        short = ref(f32(moe), f32(x), k, renorm, k - 1, picks)[0]
        # float8 mixture, the same picks: the experts' precision
        low = ref(dict(f8(moe), gate=f32(moe)["gate"]), f8(x), k, renorm, -1,
                  picks)[0]
    deficit = np.asarray(deficit)
    want = np.asarray(want)

    def rel(got):
        got = np.asarray(got, np.float32)
        return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(
            want, axis=-1)

    err, err_short, err_low = rel(y), rel(short), rel(low)
    return {"rows": rows, "seed": seed,
            "picks_not_the_references_own": int((deficit > 0).sum()),
            "largest_pick_deficit": float(deficit.max()),
            "worst_rel_err": float(err.max()),
            "median_rel_err": float(np.median(err)),
            "one_pick_left_out_smallest_rel_err": float(err_short.min()),
            "float8_median_rel_err": float(np.median(err_low)),
            "ok": bool(err.max() <= BLOCK_TOL
                       and deficit.max() <= ROUTE_TIE_TOL),
            "one_pick_left_out_fails": bool(err_short.min() > BLOCK_TOL),
            "float8_fails": bool(np.median(err_low) > BLOCK_TOL)}


def model_parity(config: Dict[str, Any], serving: Dict[str, Any],
                 prompt_lens: Sequence[int], n_new: int, seed: int,
                 dtype="bfloat16") -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from benchmark import harness
    from benchmark.drivers.serve import make_params
    from benchmark.traffic import seeded_tokens
    from deepspeed_tpu.models import TransformerConfig, build_model

    fam = harness.load_family(config["family"])
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype]
    model, cfg = build_model(TransformerConfig(**fam.model_kwargs(config),
                                               dtype=dt))
    params = make_params(model, cfg, seed, dt)
    seqs = [np.asarray(seeded_tokens(cfg.vocab_size, seed, 2000 + i,
                                     n + n_new), np.int32)
            for i, n in enumerate(prompt_lens)]
    got = paged_logits(cfg, params, seqs, n_new,
                       block_size=serving["block_size"],
                       chunk=serving["prefill_chunk_tokens"])
    f8_params = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
    rows = []
    # padded at the END to one length (one compiled reference): under a
    # causal mask padding after a position cannot reach it
    pad_to = -(-max(len(s) for s in seqs) // 128) * 128
    for s, g in zip(seqs, got):
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(s)] = s
        want = np.asarray(fam.reference_logits(
            config, params, jnp.asarray(ids)))[len(s) - len(g):len(s)]
        d = g - want
        low = np.asarray(fam.reference_logits(
            config, f8_params, jnp.asarray(ids)))[len(s) - len(g):len(s)]
        rows.append({"tokens": int(len(s)),
                     "rms": float(np.sqrt(np.mean(d * d))),
                     "float8_weights_rms": float(np.sqrt(np.mean(
                         (low - want) ** 2))),
                     "max": float(np.abs(d).max()),
                     "logit_spread": float(want.std()),
                     "argmax_equal": int((g.argmax(-1) == want.argmax(-1)
                                          ).sum()),
                     "positions": int(len(g))})
    return {"seed": seed, "sequences": rows,
            "ok": all(r["rms"] <= MODEL_RMS_TOL and r["max"] <= MODEL_MAX_TOL
                      for r in rows),
            "float8_fails": all(r["float8_weights_rms"] > MODEL_RMS_TOL
                                for r in rows)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-olmoe-1b-7b-l8-gen")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args()
    from benchmark import harness
    import jax
    cell = harness.load_cell(args.workload)
    print(f"[parity] {jax.devices()}", flush=True)
    blocks = [block_parity(cell.config, args.seed + i, rows)
              for i in range(args.seeds) for rows in (256, 64)]
    for b in blocks:
        print("[parity] block", json.dumps(b), flush=True)
    check = cell.system["check"]
    model = model_parity(cell.config, cell.system["serving"],
                         check["prompt_lens"], int(check["new_tokens"]),
                         args.seed)
    print("[parity] model", json.dumps(model), flush=True)
    ok = all(b["ok"] and b["one_pick_left_out_fails"] and b["float8_fails"]
             for b in blocks) and model["ok"] and model["float8_fails"]
    print(json.dumps({"ok": ok, "route_tie_tol": ROUTE_TIE_TOL,
                      "block_tol": BLOCK_TOL, "model_rms_tol": MODEL_RMS_TOL,
                      "model_max_tol": MODEL_MAX_TOL, "block": blocks,
                      "model": model,
                      "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
