"""The plain reference, and the comparisons that decide ``correct``.

Straightforward ``jax.numpy`` in float32 with ``highest`` matmul precision
(on a TPU a float32 matmul otherwise runs in bf16 passes): no kernel, no
cache, no scan, no batching. A family (``families/<name>.py``) builds its
forward pass from the pieces here and walks the program's STACKED weights one
layer at a time, so only one layer is ever held in float32 (one Mistral-7B
layer is 0.87 GB). It reads the program's parameter tree and nothing else of
the program.

Tolerances (each with its reason) are the constants below; the drivers use
them and no others.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Training: |engine's first-step loss - reference loss| on the same batch and
#: the same initial weights. The engine computes the blocks in bf16 (f32
#: accumulation in the matmuls, f32 softmax statistics and loss), the
#: reference in f32 throughout, so they differ by bf16 rounding of the
#: activations, which averages out over the 8184 targets of a batch: on the
#: chip the difference was 0.00002 to 0.00011 at a loss of 11.3 in every run
#: of PR 23 (PERF.md). 0.002 is eighteen times the largest seen and 0.02% of
#: the loss. A forward in less than bf16 shows: an int8 or fp8 rounding of
#: the matmul inputs moves this loss by hundredths, and a wrong mask, a
#: dropped layer or a head that is not tied move it by tenths.
TRAIN_LOSS_TOL = 0.002

#: Serving: at every generated position, how far the served token's REFERENCE
#: logit may lie under the reference's largest logit. Token equality is the
#: wrong test with random weights: the top two of 32000 logits of unit spread
#: lie ~0.1 apart, and bf16 (weights, activations and the KV pool are bf16;
#: the reference is f32) moves a logit by hundredths, so the served argmax is
#: now and then the reference's runner-up: on the chip 89 to 96 of the 96
#: checked tokens were the reference's argmax and the largest gap of any run
#: of PR 23 was 0.045 (PERF.md). 0.15 is three times that, and a seventh of
#: the logits' spread: a token read from a wrong cache slot, a wrong rotary
#: position or a missing layer lands whole units under the maximum (a random
#: token sits ~4 under it), and an int8 pool moves logits by tenths.
SERVE_LOGIT_MARGIN = 0.15


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, scale, bias, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotary_half(x, theta: float):
    """Rotate-half (GPT-NeoX / HF Llama, Mistral) rotary embedding of
    ``x [S, heads, head_dim]`` at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, window: int = 0):
    """``q [S, heads, hd]``, ``k``/``v`` ``[S, kv_heads, hd]``: each group of
    ``heads / kv_heads`` query heads reads one K/V head. Query t sees keys
    ``max(0, t - window + 1) .. t`` (``window`` 0: all of 0..t)."""
    S, nh, hd = q.shape
    group = nh // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = ki <= qi
    if window:
        keep = keep & (qi - ki < window)
    s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(S, nh * hd)


def layer_step(block: Callable) -> Callable:
    """``block(layer_params_f32, x)`` as one jitted step that takes a layer's
    weights as stored and turns them to float32 inside."""
    return jax.jit(lambda p, x: block(jax.tree.map(_f32, p), x))


def walk_layers(step: Callable, blocks, x, n_layers: int):
    """Apply ``step`` (from :func:`layer_step`) for each layer of the stacked
    tree ``blocks`` (leading axis = layer); one layer in float32 at a time."""
    for li in range(n_layers):
        x = step(jax.tree.map(lambda a: a[li], blocks), x)
    return x


def next_token_nll(logits, ids) -> jnp.ndarray:
    """Mean over positions 0..S-2 of -log p(ids[t+1] | ids[..t])."""
    logz = jax.nn.logsumexp(logits[:-1], axis=-1)
    gold = jnp.take_along_axis(logits[:-1], ids[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def batch_loss(logits_fn: Callable, params, batch: np.ndarray) -> float:
    """The reference's causal-LM loss of a ``[rows, S]`` batch, row by row
    (every row has S-1 targets, so the batch mean is the mean of rows)."""
    with jax.default_matmul_precision("highest"):
        rows = [float(next_token_nll(logits_fn(params, jnp.asarray(r)),
                                     jnp.asarray(r))) for r in batch]
    return float(np.mean(rows))


def served_token_gaps(logits_fn: Callable, params, prompt: Sequence[int],
                      served: Sequence[int], pad_to: int) -> np.ndarray:
    """Teacher-force the reference on prompt + served tokens; for every
    generated position return (largest reference logit) - (reference logit of
    the token the server emitted). Zero where the server's token is the
    reference's argmax. The sequence is padded at its END to ``pad_to`` (one
    compiled shape for all checked requests): under a causal mask padding
    after a position cannot reach it."""
    seq = list(prompt) + list(served[:-1])
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits = logits_fn(params, jnp.asarray(ids))
        at = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
        got = jnp.take_along_axis(at, jnp.asarray(served, jnp.int32)[:, None],
                                  axis=-1)[:, 0]
        return np.asarray(jnp.max(at, axis=-1) - got)
