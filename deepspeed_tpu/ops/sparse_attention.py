"""Block-sparse attention — sparsity layouts + sparse self-attention.

Capability parity with the reference's ``deepspeed/ops/sparse_attention/*``
(Triton block-sparse sdd/dsd matmuls + softmax, SparseSelfAttention, and the
sparsity pattern zoo in sparsity_config.py:94-686: Fixed / Variable / BigBird
/ BSLongformer / LocalSlidingWindow). This was the reference's long-context
mechanism (~10x longer sequences, docs/_pages/training.md:108).

Here a sparsity config produces a BLOCK LAYOUT [heads, q_blocks, k_blocks]
(bool: attend/skip), exactly like the reference's `make_layout`. Execution:
  * `sparse_attention(...)` applies the layout as a mask over the jnp
    reference (XLA fuses mask+softmax; correctness oracle, works everywhere)
  * the Pallas flash kernel's causal block-skip generalizes to layout-driven
    skip (same `@pl.when` mechanism) — the layout is the single source of
    truth for both paths.
Ring/Ulysses sequence parallelism (parallel/ring_attention.py) is the other
long-context axis; they compose (sparse within a rank's chunk).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class SparsityConfig:
    """Base: dense layout (reference: sparsity_config.py SparsityConfig)."""
    num_heads: int
    block: int = 16
    different_layout_per_head: bool = False

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        return np.ones((self.num_heads, n, n), dtype=bool)

    def _num_blocks(self, seq_len: int) -> int:
        if seq_len % self.block != 0:
            raise ValueError(f"seq_len {seq_len} not divisible by block "
                             f"{self.block}")
        return seq_len // self.block


@dataclasses.dataclass
class DenseSparsityConfig(SparsityConfig):
    pass


@dataclasses.dataclass
class FixedSparsityConfig(SparsityConfig):
    """Fixed pattern (Sparse Transformers): local windows of
    `num_local_blocks` + global attention to the last `num_global_blocks`
    of each preceding window (reference: sparsity_config.py Fixed)."""
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    attention: str = "bidirectional"   # or "unidirectional"

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        L, G = self.num_local_blocks, self.num_global_blocks
        layout = np.zeros((self.num_heads, n, n), dtype=bool)
        for qi in range(n):
            win = qi // L
            # local window
            lo = win * L
            hi = min(lo + L, n)
            layout[:, qi, lo:hi] = True
            # global: last G blocks of every previous window
            for w in range(win):
                gs = (w + 1) * L - G
                layout[:, qi, max(gs, 0):(w + 1) * L] = True
        if self.attention == "unidirectional":
            tril = np.tril(np.ones((n, n), dtype=bool))
            layout &= tril[None]
        return layout


@dataclasses.dataclass
class BSLongformerSparsityConfig(SparsityConfig):
    """Longformer: symmetric sliding window + designated global blocks."""
    num_sliding_window_blocks: int = 3
    global_block_indices: tuple = (0,)

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        w = self.num_sliding_window_blocks // 2
        layout = np.zeros((self.num_heads, n, n), dtype=bool)
        for qi in range(n):
            layout[:, qi, max(0, qi - w):min(n, qi + w + 1)] = True
        for g in self.global_block_indices:
            if g < n:
                layout[:, g, :] = True     # global block attends everything
                layout[:, :, g] = True     # everything attends global block
        return layout


@dataclasses.dataclass
class BigBirdSparsityConfig(SparsityConfig):
    """BigBird: random + sliding window + global blocks."""
    num_random_blocks: int = 1
    num_sliding_window_blocks: int = 3
    num_global_blocks: int = 1
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        rng = np.random.default_rng(self.seed)
        w = self.num_sliding_window_blocks // 2
        layout = np.zeros((self.num_heads, n, n), dtype=bool)
        for qi in range(n):
            layout[:, qi, max(0, qi - w):min(n, qi + w + 1)] = True
        g = self.num_global_blocks
        layout[:, :g, :] = True
        layout[:, :, :g] = True
        heads = self.num_heads if self.different_layout_per_head else 1
        for h in range(heads):
            for qi in range(n):
                picks = rng.choice(n, size=min(self.num_random_blocks, n),
                                   replace=False)
                layout[h if heads > 1 else slice(None), qi, picks] = True
        return layout


@dataclasses.dataclass
class LocalSlidingWindowSparsityConfig(SparsityConfig):
    """Plain sliding window (optionally causal)."""
    num_sliding_window_blocks: int = 3
    attention: str = "unidirectional"

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        w = self.num_sliding_window_blocks
        layout = np.zeros((self.num_heads, n, n), dtype=bool)
        for qi in range(n):
            if self.attention == "unidirectional":
                layout[:, qi, max(0, qi - w + 1):qi + 1] = True
            else:
                half = w // 2
                layout[:, qi, max(0, qi - half):min(n, qi + half + 1)] = True
        return layout


@dataclasses.dataclass
class VariableSparsityConfig(SparsityConfig):
    """Variable: per-window local sizes + custom global indices."""
    num_random_blocks: int = 0
    local_window_blocks: tuple = (4,)
    global_block_indices: tuple = (0,)
    attention: str = "bidirectional"
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._num_blocks(seq_len)
        layout = np.zeros((self.num_heads, n, n), dtype=bool)
        # consecutive local windows of the given sizes (last repeats)
        sizes = list(self.local_window_blocks)
        start = 0
        while start < n:
            size = sizes.pop(0) if len(sizes) > 1 else sizes[0]
            end = min(start + size, n)
            layout[:, start:end, start:end] = True
            start = end
        for g in self.global_block_indices:
            if g < n:
                layout[:, g, :] = True
                layout[:, :, g] = True
        if self.num_random_blocks:
            rng = np.random.default_rng(self.seed)
            for qi in range(n):
                picks = rng.choice(n, size=min(self.num_random_blocks, n),
                                   replace=False)
                layout[:, qi, picks] = True
        if self.attention == "unidirectional":
            layout &= np.tril(np.ones((n, n), dtype=bool))[None]
        return layout


SPARSITY_CONFIGS = {
    "dense": DenseSparsityConfig,
    "fixed": FixedSparsityConfig,
    "variable": VariableSparsityConfig,
    "bigbird": BigBirdSparsityConfig,
    "bslongformer": BSLongformerSparsityConfig,
    "local_sliding_window": LocalSlidingWindowSparsityConfig,
}


def build_sparsity_config(mode: str, num_heads: int, **kwargs) -> SparsityConfig:
    """reference: runtime/config.py:270-453 sparse_attention section parsing."""
    if mode not in SPARSITY_CONFIGS:
        raise ValueError(f"unknown sparse attention mode '{mode}'; "
                         f"have {sorted(SPARSITY_CONFIGS)}")
    return SPARSITY_CONFIGS[mode](num_heads=num_heads, **kwargs)


def layout_to_dense_mask(layout: np.ndarray, block: int) -> jnp.ndarray:
    """[H, nq, nk] block layout -> [H, S, S] element mask."""
    return jnp.asarray(np.repeat(np.repeat(layout, block, axis=1),
                                 block, axis=2))


def sparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     config: SparsityConfig,
                     *,
                     causal: bool = False,
                     sm_scale: Optional[float] = None,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False) -> jnp.ndarray:
    """Block-sparse attention. q,k,v: [B, H, S, D].

    Execution: the Pallas layout-skip kernel
    (ops/pallas/block_sparse_attention.py) when on TPU and shapes tile —
    attention FLOPs scale with layout density, like the reference's Triton
    sdd/dsd path — otherwise the dense-mask oracle (XLA fuses mask+softmax;
    correct everywhere, no compute saving).
    """
    S = q.shape[-2]
    layout = config.make_layout(S)
    auto = use_kernel is None
    if auto:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from .pallas.block_sparse_attention import (
            block_sparse_flash_attention, tile_plan)
        # only the AUTO path may take the dense-mask oracle for shapes that
        # don't tile (said once, with the reason); an explicit
        # use_kernel=True means the caller wants the FLOP-scaling contract
        # and must hear that it can't be met. The test is made BEFORE the
        # call: whatever the kernel itself raises is an error, not a route
        reason = tile_plan(S, q.shape[-1], config.block)[2] if auto else None
        if reason is None:
            return block_sparse_flash_attention(
                q, k, v, layout, config.block, causal=causal,
                sm_scale=sm_scale, interpret=interpret)
        from ..utils.logging import warning_once
        warning_once(f"sparse_attention on TPU takes the dense-mask "
                     f"reference: {reason}")
    mask = layout_to_dense_mask(layout, config.block)[None]   # [1, H, S, S]
    from .attention import mha_reference
    return mha_reference(q, k, v, causal=causal, mask=mask, sm_scale=sm_scale)


class SparseSelfAttention:
    """Module-style wrapper (reference: sparse_self_attention.py:11)."""

    def __init__(self, sparsity_config: SparsityConfig, causal: bool = False):
        self.config = sparsity_config
        self.causal = causal

    def __call__(self, q, k, v):
        return sparse_attention(q, k, v, self.config, causal=self.causal)
