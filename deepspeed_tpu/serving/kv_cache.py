"""Paged KV cache: a fixed pool of blocks + per-sequence block tables.

The dense serving layout (``models/generation.init_cache``) preallocates
``[L, B, nh, max_len, hd]`` per batch — every sequence pays for its WORST
CASE length, and a finished sequence's slack is unreclaimable until the
whole batch drains. For a long-lived serving loop that is the capacity
bottleneck, not FLOPs. This module replaces it with the vLLM-style paged
layout:

* one preallocated device pool ``[L, kv_heads, num_blocks * block_size,
  hd]`` (per k and v) shared by every in-flight sequence: a token costs
  ``2 x L x kv_heads x hd x item size`` bytes, so a grouped-query model's
  costs ``num_heads // kv_heads`` times less than a row a query head would
  (gauges ``kv.stored_heads`` / ``kv.bytes_per_token``);
* a host-side :class:`BlockPool` allocator handing out fixed-size blocks
  with REFERENCE COUNTS — ``fork`` shares blocks between sequences
  (prefix-cache reuse for common system prompts) and a block returns to
  the free list when its last holder releases it;
* a :class:`PrefixCache` mapping token-prefix hashes to full-block runs
  of previously prefilled prompts, so a new request sharing a prompt
  prefix skips recomputing (and re-storing) those blocks entirely.

Copy-on-write discipline: blocks are shared at FULL-BLOCK granularity
only (a forked prefix always ends on a block boundary), and a sequence
only ever writes K/V at logical positions >= its fork point — which land
in its own private blocks. Shared blocks are therefore read-only by
construction; no device-side copy is ever needed, and the refcount is
the entire consistency protocol.

Physical block 0 is the NULL block: never allocated, the write target of
padded/inactive lanes in the fixed-shape decode step, and never read
(every read is masked by the per-sequence context length).
"""

from __future__ import annotations

import functools
import hashlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..testing import chaos

#: physical block 0 — the write sink for padded lanes, never allocated
NULL_BLOCK = 0


class BlockPoolExhausted(RuntimeError):
    """Allocation would exceed the pool — the scheduler's signal to keep
    the request QUEUED (admission control), never a crash."""


def init_pool(cfg, num_blocks: int, block_size: int,
              dtype=None, device=None) -> Dict[str, jnp.ndarray]:
    """Device-side paged pool: k/v ``[L, kvh, num_blocks*block_size, hd]``,
    ``kvh = cfg.kv_heads``: K and V are stored at the model's KV heads (a
    grouped-query model's ``num_heads // kv_heads`` query heads read one
    stored head; the paged kernel makes them rows of one tile), so a token
    takes ``2 x L x kvh x hd x item size`` bytes of the pool. A model with an
    indexer (``cfg.index_heads``) has a third leaf, ``ki`` ``[L, 1,
    num_blocks*block_size, index_head_dim rounded up to 128]`` in the
    model's dtype: the indexer's one key a token and layer, no heads, on the
    first ``index_head_dim`` lanes of a row of whole 128-lane tiles (the
    rest zeros), so ``L x 128 x item size`` bytes more a token for a 64-wide
    key. The chip pads a narrower row to 128 lanes in HBM anyway (ROADMAP
    S12 (b)) and then wants the whole pool in another layout for every
    layer's write and gather (a 64-wide leaf was copied whole several times
    a step, ``tests/test_chip_compile.py``); stored at the padded width the
    leaf is laid out as K and V are, written by the same in-place updates,
    and the zeros add nothing to a score. It lives in the SAME blocks as
    K/V, slot for slot: block tables, ``fork``, the prefix cache and a
    preemption's release carry it with no allocator of its own.

    A LATENT model (``cfg.kv_lora_rank``: DeepSeek-V2's MLA) has ONE leaf
    and no ``k`` / ``v``: ``ckv`` ``[L, 1, num_blocks*block_size,
    latent_lanes]``, a row a token and layer holding ``[the normed latent
    (kv_lora_rank) | the rotated key all heads share (qk_rope_head_dim) |
    zeros]`` on whole 128-lane tiles: 576 of 640 lanes at DeepSeek-V2's
    widths, ``L x 640 x item size`` bytes a token (an eleventh more than the
    576 it needs; a row a head would be 57 times that). At its own width the
    row is 4.5 lane tiles and the chip's compiler handles such a leaf as it
    handled a 64-wide one (above; PERF.md section 6, PR 49 has the readings);
    the zeros add nothing to a score against a query padded the same way.
    A decode call's absorbed kernel reads the row once for scores (all its
    lanes) and values (its first ``kv_lora_rank``); a chunk's expands the
    latent to its heads' keys and values once for all of the chunk's rows:
    ``ops/pallas/latent_attention.py``. A latent model WITH an indexer
    (DeepSeek Sparse Attention over MLA) has both leaves, ``ckv`` and ``ki``,
    in the same blocks, written by the same plan: a prefix-cache hit, a fork
    or a release carries both.

    Flat slot layout (slot = block * block_size + offset), row-major: the
    paged forward and the paged-attention kernel both view the same buffer
    as ``[L, kvh, num_blocks, block_size, hd]`` (a free reshape), the kernel
    to DMA whole blocks through the block table, the forward to write new
    K/V into it in place with ``lax.dynamic_update_slice`` (one slot a lane
    in a decode step, a block at a time in a prefill). Not with a scatter:
    the chip's compiler lays a scatter's operand out slots-major, and the
    pool was then copied whole to the kernel's layout twice a layer
    (``serving.model_runner._write_kv``).

    ``dtype=jnp.int8`` (round 12): the quantized pool tier — k/v store
    int8 with a per-(layer, KV head, slot) f32 scale (symmetric over the
    head dim, ``quant_format.kv_quantize`` — the single-sourced format),
    halving pool HBM vs bf16. The paged forward quantizes on write;
    reads dequantize IN-kernel (round 17): the Pallas paged-attention
    kernel takes the int8 blocks plus scales and dequantizes per block
    in VMEM, so int8 is what crosses HBM (no pool-slice f32 copy).

    ``device`` (a device or a sharding; :func:`beside`): where the leaves
    are made, COMMITTED there. Without one they are uncommitted arrays on
    the default device, which a jitted call beside committed weights hands
    back committed: its second call is then another signature, and the
    program is compiled twice."""
    dtype = dtype or cfg.dtype
    slots = num_blocks * block_size
    zeros = functools.partial(jnp.zeros, device=device)
    index = ({"ki": zeros((cfg.num_layers, 1, slots,
                           -(-cfg.index_head_dim // 128) * 128),
                          cfg.dtype)} if cfg.index_heads else {})
    if cfg.kv_lora_rank:
        if dtype == jnp.int8:
            raise ValueError(
                "an int8 KV pool with latent attention (kv_lora_rank): the "
                "latent leaf has no quantized format (ROADMAP M4)")
        return {"ckv": zeros((cfg.num_layers, 1, slots, cfg.latent_lanes),
                             dtype), **index}
    shape = (cfg.num_layers, cfg.kv_heads, slots, cfg.head_dim)
    if dtype == jnp.int8:
        return {"k": zeros(shape, jnp.int8), "v": zeros(shape, jnp.int8),
                "k_scale": zeros(shape[:-1] + (1,), jnp.float32),
                "v_scale": zeros(shape[:-1] + (1,), jnp.float32),
                **index}
    return {"k": zeros(shape, dtype), "v": zeros(shape, dtype), **index}


def beside(params):
    """Where a pool that is called beside ``params`` is made (:func:`
    init_pool`'s ``device``): where the serving programs hand it back. They
    return the pools on the weights' device under the weights' own kind of
    sharding (``init_inference`` replicates them over its mesh, of one
    device or of several; a tree put on a device sits there), so a pool
    made there goes into its first call as it goes into every later one.
    Weights that are committed nowhere (host arrays, a fresh ``init``'s)
    leave every output uncommitted, and the pools with them: None. Weights
    SPLIT over a mesh (tensor parallelism): the pools start replicated over
    it; the compiler then splits them over the stored heads itself, and the
    first call's shape is compiled once more, as it was (no rule here says
    which split it will choose)."""
    leaf = jax.tree_util.tree_leaves(params)[0]
    if not getattr(leaf, "committed", False):
        return None
    if leaf.sharding.is_fully_replicated:
        return leaf.sharding
    return jax.sharding.NamedSharding(leaf.sharding.mesh,
                                      jax.sharding.PartitionSpec())


class BlockPool:
    """Host-side block allocator with refcounts (see module docstring).

    ``num_blocks`` COUNTS the reserved null block: a pool of N blocks has
    N - 1 allocatable.

    Thread-safe (round 12): disaggregated serving shares ONE pool between
    prefill-role and decode-role replicas on different threads, so
    alloc/fork/release are atomic under an internal lock. ``free_count``
    probes stay optimistic — a racing allocation after a passing probe
    surfaces as :class:`BlockPoolExhausted`, which every admission path
    already treats as keep-queued."""

    def __init__(self, num_blocks: int, block_size: int,
                 counters: Optional[Dict[str, int]] = None):
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (block 0 is the null "
                             "block)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: List[int] = list(range(num_blocks - 1, NULL_BLOCK, -1))
        self._refs: Dict[int, int] = {}
        self._mu = threading.Lock()
        #: where the pool counts what it decides (the serving engine hands
        #: in its recorder's counters): blocks handed out, blocks that
        #: came back to the free list, allocations refused
        self.counters = {} if counters is None else counters
        for key in ("kv.alloc", "kv.release", "kv.exhausted"):
            self.counters.setdefault(key, 0)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.block_size)

    def alloc(self, n: int) -> List[int]:
        """``n`` fresh private blocks (refcount 1). Raises
        :class:`BlockPoolExhausted` when the pool can't cover them — and
        the ``serve.oom`` failpoint can force that path (chaos tests pin
        queued-not-crashed)."""
        chaos.failpoint("serve.oom")
        with self._mu:
            if n > len(self._free):
                self.counters["kv.exhausted"] += 1
                raise BlockPoolExhausted(
                    f"need {n} blocks, {len(self._free)} free "
                    f"(pool {self.num_blocks - 1} x {self.block_size} "
                    "tokens)")
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            self.counters["kv.alloc"] += n
            return out

    def fork(self, blocks: Sequence[int]) -> List[int]:
        """Share ``blocks`` with another holder: +1 refcount each. The
        caller must treat them as READ-ONLY (full-block prefix sharing
        guarantees it never writes below its fork point)."""
        with self._mu:
            for b in blocks:
                if b == NULL_BLOCK or b not in self._refs:
                    raise ValueError(f"fork of unallocated block {b}")
            for b in blocks:
                self._refs[b] += 1
            return list(blocks)

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block; a block returns to the free list
        when its last holder releases it."""
        with self._mu:
            for b in blocks:
                refs = self._refs.get(b)
                if refs is None:
                    raise ValueError(f"release of unallocated block {b}")
                if refs > 1:
                    self._refs[b] = refs - 1
                else:
                    del self._refs[b]
                    self._free.append(b)
                    self.counters["kv.release"] += 1

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)


def _chain_keys(tokens: Sequence[int], block_size: int,
                max_blocks: int) -> List[str]:
    """Per-block-boundary prefix digests, computed INCREMENTALLY: key k
    hashes tokens[:k*block_size] by extending one running sha1, so the
    whole ladder costs O(len(tokens)) — not O(len^2 / block_size) as
    hashing each prefix from scratch would (admission is a hot path and
    prompts reach tens of thousands of tokens)."""
    keys: List[str] = []
    h = hashlib.sha1()
    for k in range(max_blocks):
        for t in tokens[k * block_size:(k + 1) * block_size]:
            h.update(int(t).to_bytes(4, "little", signed=True))
        keys.append(h.hexdigest())
    return keys


class PrefixCache:
    """Token-prefix hash -> full-block run of an already-prefilled prompt.

    Entries hold their own refcount on the blocks (via ``pool.fork``), so
    a cached prefix survives the request that created it; eviction (LRU,
    on allocation pressure) releases those references. Hash collisions
    are guarded by comparing the stored token prefix on match; entries of
    one insert share a single tokens tuple (no per-entry prefix copies)."""

    def __init__(self, pool: BlockPool):
        self.pool = pool
        #: the pool's counters: lookups that took blocks, entries made,
        #: entries evicted, and the entries each eviction's scan read
        self.counters = pool.counters
        for key in ("prefix.lookups", "prefix.inserted_entries",
                    "prefix.evicted_entries",
                    "prefix.evict_scanned_entries"):
            self.counters.setdefault(key, 0)
        # key -> (tokens ref, n_blocks, blocks, last_used), KEPT IN ORDER
        # OF LAST USE (a dict keeps insertion order; a touch re-inserts):
        # the least recently used entry is the first, so an eviction reads
        # one entry. (Until PR 45 each eviction took the minimum over every
        # entry: a long prompt makes an entry a block and frees its blocks
        # with its LAST entry, so admitting an 8k-token prompt into a full
        # pool evicted ~256 entries at ~8 000 reads each: 86 million reads a
        # run of the Keye cell, seconds of the host's time inside steps.)
        self._entries: Dict[str, Tuple[Tuple[int, ...], int, List[int],
                                       int]] = {}
        self._clock = 0
        # round 12: multiple prefill-role replicas share one cache —
        # match/insert/evict are atomic (RLock: clear() calls evict())
        self._mu = threading.RLock()

    def __len__(self) -> int:
        return len(self._entries)

    def _lookup(self, tokens: Sequence[int]
                ) -> Tuple[int, Optional[str], List[int]]:
        """(n_cached_tokens, entry key, blocks) of the longest cached
        full-block prefix — NO fork, no LRU touch."""
        bs = self.pool.block_size
        max_blocks = (len(tokens) - 1) // bs
        if max_blocks <= 0 or not self._entries:
            return 0, None, []
        keys = _chain_keys(tokens, bs, max_blocks)
        for k in range(max_blocks, 0, -1):
            ent = self._entries.get(keys[k - 1])
            if ent is None:
                continue
            etoks, ek, blocks, _ = ent
            if ek != k or tuple(etoks[:k * bs]) != \
                    tuple(int(t) for t in tokens[:k * bs]):
                continue                       # hash collision — skip
            return k * bs, keys[k - 1], blocks
        return 0, None, []

    def peek(self, tokens: Sequence[int]) -> Tuple[int, Optional[str]]:
        """Admission-budget probe: (n_cached_tokens, entry key) WITHOUT
        taking a reference — the scheduler uses it to net the hit out of
        the block budget and to protect the entry from its own
        make-room eviction."""
        with self._mu:
            n, key, _ = self._lookup(tokens)
            return n, key

    def match(self, tokens: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest cached FULL-BLOCK prefix of ``tokens``, capped at
        ``len(tokens) - 1`` so a fully-cached prompt still leaves >= 1
        token to prefill (the last prompt token's logits seed sampling).
        Returns ``(n_cached_tokens, forked_blocks)`` — the blocks already
        carry the caller's refcount."""
        with self._mu:
            self.counters["prefix.lookups"] += 1
            n, key, blocks = self._lookup(tokens)
            if key is None:
                return 0, []
            self._touch(key)
            return n, self.pool.fork(blocks)

    def _touch(self, key: str) -> None:
        """Entry ``key`` was used now: the last to be evicted."""
        self._clock += 1
        ent = self._entries.pop(key)
        self._entries[key] = (ent[0], ent[1], ent[2], self._clock)

    def insert(self, tokens: Sequence[int], blocks: Sequence[int]) -> None:
        """Register every full-block prefix of a prefilled prompt. The
        cache forks (refcounts) the blocks it retains; duplicate keys are
        refreshed, not re-forked."""
        bs = self.pool.block_size
        nfull = len(tokens) // bs
        if nfull <= 0:
            return
        shared = tuple(int(t) for t in tokens[:nfull * bs])
        keys = _chain_keys(shared, bs, nfull)
        with self._mu:
            for k in range(1, nfull + 1):
                key = keys[k - 1]
                ent = self._entries.get(key)
                if ent is not None and ent[1] == k \
                        and ent[0][:k * bs] == shared[:k * bs]:
                    self._touch(key)
                    continue
                self._clock += 1
                if ent is not None:             # a collision's entry goes
                    self.pool.release(self._entries.pop(key)[2])
                held = self.pool.fork(list(blocks[:k]))
                self._entries[key] = (shared, k, held, self._clock)
                self.counters["prefix.inserted_entries"] += 1

    def evict(self, need_blocks: int,
              protect: Optional[str] = None) -> int:
        """Release least-recently-used entries until ``need_blocks`` are
        free in the pool (or nothing evictable remains). Returns entries
        evicted. ``protect`` exempts one entry key — the prefix the
        admission candidate itself is about to reuse must not be the
        victim of its own make-room pass. Releasing an entry only frees
        blocks no live request still holds — refcounts make eviction
        safe mid-flight."""
        evicted = 0
        with self._mu:
            while self.pool.free_count < need_blocks:
                # the first entry that is not the protected one: the least
                # recently used (the entries lie in that order)
                key, read = None, 0
                for key in self._entries:
                    read += 1
                    if key != protect:
                        break
                else:
                    key = None
                self.counters["prefix.evict_scanned_entries"] += read
                if key is None:
                    break
                _, _, blocks, _ = self._entries.pop(key)
                self.pool.release(blocks)
                evicted += 1
            self.counters["prefix.evicted_entries"] += evicted
        return evicted

    def clear(self) -> None:
        self.evict(self.pool.num_blocks)


class SharedPagedState:
    """The paged-KV state a disaggregated prefill/decode pair SHARES
    (round 12, serving/disagg.py): one device pool dict, one refcounted
    :class:`BlockPool`, one :class:`PrefixCache` — so a prefill role can
    hand finished blocks to a decode role by transferring block IDs, with
    zero device-side copies (the handoff moves logical ownership, never
    bytes).

    ``device_lock`` serializes the roles' jitted calls: both programs
    DONATE the pool buffers (the in-place-update discipline of
    serving/engine.py), so exactly one program may hold the live buffer
    at a time — each call takes the pools, runs, and writes the returned
    pools back under the lock. A single-threaded engine pays one
    uncontended acquire per step.

    The pools are made :func:`beside` ``params``, the weights they will be
    called with: committed where every call hands them back, so the first
    call of a shape is the only one that compiles it."""

    def __init__(self, cfg, params, serving, dtype=None,
                 counters: Optional[Dict[str, int]] = None):
        self.pool = BlockPool(serving.pool_blocks, serving.block_size,
                              counters=counters)
        self.pools: Dict[str, Any] = init_pool(
            cfg, serving.pool_blocks, serving.block_size, dtype=dtype,
            device=beside(params))
        self.prefix_cache = (PrefixCache(self.pool)
                             if serving.prefix_cache else None)
        self.device_lock = threading.Lock()

    def run(self, fn, params, *args):
        """Execute ``fn(params, pools, *args) -> (out, new_pools)`` with
        the live pool buffers, serialized against the other role."""
        with self.device_lock:
            # the lock MUST span fn: it donates self.pools, and the other
            # role dispatching against donated-invalidated buffers is the
            # exact aliasing bug this class exists to prevent
            out, self.pools = fn(params, self.pools, *args)  # graftlint: disable=TPU017
            return out
