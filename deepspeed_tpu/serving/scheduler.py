"""Continuous-batching request scheduler: admission + tiered FIFO queue.

The serving loop (serving/engine.py) is a fixed-shape decode step over
``max_batch`` lanes; this module decides WHICH requests occupy those
lanes. Design contract:

* **Admission control by block budget.** A request is admitted only when
  the pool can cover its whole lifetime — ``ceil((prompt + max_new - 1)
  / block_size)`` blocks, minus whatever a prefix-cache hit contributes.
  Admitting on the full lifetime (not just the prompt) means an admitted
  sequence can NEVER hit the pool mid-decode: exhaustion is a
  queue-time, not a crash-time, condition.
* **Strict FIFO.** If the head of the queue does not fit, nothing behind
  it is admitted either — a stream of small requests cannot starve a big
  one (fairness under a full pool is a pinned test).
* **Per-request deadlines (round 11).** Strict FIFO has an unbounded-wait
  edge: a too-big head makes everything behind it wait for as long as the
  head waits. A request submitted with ``deadline_s`` (a TTL relative to
  arrival) is SHED with a ``TIMEOUT`` result once the deadline passes and
  it is still queued — checked at every admission pass, anywhere in the
  queue, so backpressure degrades into bounded-latency load shedding
  instead of silent starvation. A request already admitted (PREFILL /
  RUNNING) is never shed: its blocks are paid for and killing it would
  waste the work — deadlines bound *queue wait*, not generation.
* **In-flight batching.** ``next_admission`` is consulted every loop
  iteration, so new prefills enter as soon as finishing sequences return
  their blocks — no batch drain barrier.
* **Priority tiers (round 19).** ``submit(priority=)`` picks one of
  latency / standard / batch. :class:`TieredQueue` serves the highest
  tier first, strict FIFO *within* a tier, with one starvation bound: a
  tier head that has waited longer than ``aging_s`` is served as if it
  were latency-tier (the aging floor — batch work is deferrable, not
  droppable). All-default traffic lives in one tier and degenerates to
  exactly the old FIFO, so every strict-FIFO pin still holds.
* **Overload ladder (round 19).** Backpressure escalates, never hangs and
  never silently drops: (1) expired queued requests are shed with
  TIMEOUT (round 11); (2) past ``batch_highwater`` of ``max_queue`` new
  batch-tier submissions get a machine-readable
  :class:`AdmissionRejected`; (3) at a hard-full queue a higher-tier
  arrival SHEDs the youngest queued request of the lowest tier below it
  (victim concludes ``SHED``, callback fires) — and when no lower-tier
  victim exists the arrival itself is rejected machine-readably.

Failpoints (testing/chaos.py): ``serve.enqueue`` fires in :meth:`submit`
(a rejected/exploding enqueue must surface to the caller, not wedge the
loop); ``serve.oom`` fires inside ``BlockPool.alloc`` (the engine treats
it exactly like a genuinely full pool: the request stays queued).
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

from ..testing import chaos
from ..utils import telemetry
from ..utils.logging import logger
from .kv_cache import BlockPool, PrefixCache

#: request lifecycle states. TIMEOUT (round 11) is a terminal shed: the
#: request's deadline passed while it was still QUEUED — never applied to
#: an admitted request. HANDOFF (round 12) is the disaggregated-serving
#: window between a finished prefill and its installation into a decode
#: lane: the request's blocks sit in the block-handoff queue
#: (serving/disagg.py) with its sampler state (first token, table).
#: SHED (round 19) is the overload ladder's terminal: a queued request
#: evicted to admit a higher-tier arrival at a hard-full queue — like
#: TIMEOUT it only ever applies to a QUEUED request and its callback
#: fires with a machine-readable error.
QUEUED, PREFILL, RUNNING, FINISHED, FAILED, TIMEOUT, HANDOFF, SHED = (
    "QUEUED", "PREFILL", "RUNNING", "FINISHED", "FAILED", "TIMEOUT",
    "HANDOFF", "SHED")

#: priority tiers (round 19), highest first. Rank 0 dispatches first.
LATENCY, STANDARD, BATCH = "latency", "standard", "batch"
PRIORITY_TIERS = (LATENCY, STANDARD, BATCH)
TIER_RANK = {LATENCY: 0, STANDARD: 1, BATCH: 2}

_rid = itertools.count()


class AdmissionRejected(RuntimeError):
    """Machine-readable admission rejection (round 19 overload ladder).

    Subclasses RuntimeError so callers catching the round-8 full-queue
    error keep working; ``info`` carries the structured verdict a client
    can branch on (retry-after vs downgrade-tier vs give-up) and the
    message embeds it as JSON — never a hang, never a silent drop."""

    def __init__(self, reason: str, tier: str, queue: int, max_queue: int):
        self.info = {"error": "admission_rejected", "reason": reason,
                     "tier": tier, "queue": queue, "max_queue": max_queue}
        super().__init__(
            f"serving queue full ({queue}/{max_queue}): "
            + json.dumps(self.info, sort_keys=True))


def check_admissible(prompt_tokens: int, max_new_tokens: int,
                     block_size: int, num_blocks: int,
                     max_model_len: Optional[int],
                     label: str = "request") -> None:
    """THE admissibility predicate, shared by engine-level
    ``Scheduler.submit`` and fleet-level ``ServingFleet.submit`` (every
    replica has the same pool geometry): empty prompts, requests beyond
    ``max_model_len``, and lifetime block budgets no pool of
    ``num_blocks`` (one reserved null block) could EVER cover are
    rejected synchronously — under strict FIFO an inadmissible head
    would wedge the queue forever while the loop keeps heartbeating."""
    if prompt_tokens <= 0:
        raise ValueError("empty prompt")
    total = prompt_tokens + max_new_tokens
    if max_model_len is not None and total > max_model_len:
        raise ValueError(
            f"{label}: prompt + max_new_tokens = {total} "
            f"exceeds max_model_len {max_model_len}")
    life = prompt_tokens + max(max_new_tokens - 1, 0)
    need = -(-max(life, 0) // block_size)       # BlockPool.blocks_for_tokens
    allocatable = num_blocks - 1                # null block reserved
    if need > allocatable:
        raise ValueError(
            f"{label}: needs {need} KV blocks, pool has {allocatable} "
            "total — raise serving.pool_blocks or shrink the request")


@dataclass
class Request:
    """One generation request riding the serving loop."""
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    on_finish: Optional[Callable[["Request"], None]] = None
    #: absolute monotonic deadline; a still-QUEUED request past it is shed
    #: with TIMEOUT at the next admission pass (None = wait forever)
    deadline_ts: Optional[float] = None
    #: priority tier (round 19): latency | standard | batch
    priority: str = STANDARD
    #: a dropless mixture: keep the experts every layer picked for every
    #: token the model was fed (``ServingEngine.submit``)
    keep_routing: bool = False
    rid: int = field(default_factory=lambda: next(_rid))
    # -- filled by the engine -------------------------------------------------
    state: str = QUEUED
    output_tokens: List[int] = field(default_factory=list)
    prefix_hit_tokens: int = 0
    #: prompt tokens whose K/V reached the pool (round 12): chunked
    #: prefill advances it per chunk, so a requeue after a mid-prefill
    #: replica death carries how far the dead leg got (death ledger /
    #: observability; the retry recomputes from its own prefix hits)
    prefill_progress: int = 0
    arrival_ts: float = field(default_factory=time.monotonic)
    #: the moment admission reserved the request's lifetime blocks: the
    #: end of its queue wait (arrival -> admitted -> first token -> finish)
    admitted_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    finish_ts: Optional[float] = None
    error: Optional[str] = None
    #: ``keep_routing``: int32 ``[len(prompt) + len(output_tokens) - 1,
    #: sparse layers, k]`` once FINISHED, ids over the router's outputs (-1:
    #: a prompt position the prefix cache served); None for a request that
    #: did not ask and for a dense model. Set on the instance by the engine
    #: at the request's end, so no field of the constructor
    routed_experts = None
    _routing: List[Any] = field(default_factory=list, repr=False)

    @property
    def tokens(self) -> List[int]:
        return list(self.prompt) + list(self.output_tokens)

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, FAILED, TIMEOUT, SHED)

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline_ts is None:
            return False
        return (time.monotonic() if now is None else now) > self.deadline_ts

    def _finish(self, state: str = FINISHED,
                error: Optional[str] = None) -> None:
        self.state = state
        self.error = error
        self.finish_ts = time.monotonic()
        if self.on_finish is not None:
            try:
                self.on_finish(self)
            except Exception:           # callbacks must not kill the loop
                logger.exception("serving: on_finish callback for request "
                                 "%d raised", self.rid)


class TieredQueue:
    """Priority-tiered FIFO queue (round 19): one deque per tier, highest
    tier dispatched first, strict FIFO within a tier, and an aging floor
    — a tier head that has waited longer than ``aging_s`` seconds is
    served as if it were top-tier, so batch work is deferred, never
    starved. NOT internally locked: every caller (engine Scheduler,
    ServingFleet, ProcessFleet) already serializes queue access under its
    own lock, and a second lock here would only add ordering hazards
    (graftlint TPU017). With all traffic in one tier this is exactly a
    deque — the strict-FIFO contract the round-8/11 tests pin."""

    def __init__(self, aging_s: float = 30.0):
        self.aging_s = float(aging_s)
        self._tiers: Dict[str, deque] = {t: deque() for t in PRIORITY_TIERS}

    @staticmethod
    def _tier(req) -> str:
        t = getattr(req, "priority", STANDARD)
        return t if t in TIER_RANK else STANDARD

    def append(self, req) -> None:
        self._tiers[self._tier(req)].append(req)

    def appendleft(self, req) -> None:
        """Front of the request's OWN tier (requeue-after-death /
        preemption): it resumes ahead of its peers, not ahead of higher
        tiers — preempting batch work must not promote it."""
        self._tiers[self._tier(req)].appendleft(req)

    def __len__(self) -> int:
        return sum(len(q) for q in self._tiers.values())

    def __iter__(self) -> Iterator:
        for t in PRIORITY_TIERS:
            yield from self._tiers[t]

    def peeknext(self, now: Optional[float] = None):
        """The ONE logical head: among the three tier heads, the best
        (effective-rank, arrival) pair. Effective rank is the tier rank
        unless the head has aged past ``aging_s`` — then it competes at
        rank 0. Strict head-blocking admission applies to THIS head only
        (the round-8 fairness pin, per tier)."""
        if now is None:
            now = time.monotonic()
        best_key, best = None, None
        for tier in PRIORITY_TIERS:
            q = self._tiers[tier]
            if not q:
                continue
            head = q[0]
            rank = TIER_RANK[tier]
            if rank and self.aging_s > 0 and \
                    (now - head.arrival_ts) > self.aging_s:
                rank = 0
            key = (rank, head.arrival_ts, TIER_RANK[tier])
            if best_key is None or key < best_key:
                best_key, best = key, head
        return best

    def popnext(self, now: Optional[float] = None):
        head = self.peeknext(now)
        if head is not None:
            self._tiers[self._tier(head)].popleft()
        return head

    def remove(self, req) -> bool:
        """Remove a specific request (admission pop after a peek, or a
        shed): True iff it was queued."""
        q = self._tiers[self._tier(req)]
        try:
            q.remove(req)
            return True
        except ValueError:
            return False

    def remove_expired(self, now: float) -> List:
        """Extract every queued request past its deadline (the caller
        concludes them with TIMEOUT outside its lock)."""
        expired: List = []
        for tier, q in self._tiers.items():
            if any(r.expired(now) for r in q):
                expired.extend(r for r in q if r.expired(now))
                self._tiers[tier] = deque(r for r in q if not r.expired(now))
        return expired

    def shed_victim(self, arriving_rank: int):
        """The overload ladder's hard-full rung: extract the YOUNGEST
        queued request of the LOWEST tier strictly below ``arriving_rank``
        (None when no lower tier has anything — the arrival itself must
        then be rejected). Youngest-first minimizes wasted queue wait."""
        for tier in reversed(PRIORITY_TIERS):
            if TIER_RANK[tier] <= arriving_rank:
                return None
            q = self._tiers[tier]
            if q:
                return q.pop()
        return None

    def pressured(self, window_s: float, now: float) -> int:
        """Deadline pressure: queued requests whose remaining TTL is
        inside ``window_s`` (the autoscaler's second trigger). 0 when the
        window is off."""
        if window_s <= 0:
            return 0
        return sum(1 for q in self._tiers.values() for r in q
                   if r.deadline_ts is not None
                   and (r.deadline_ts - now) < window_s)


def admit_or_shed(tq: TieredQueue, req, max_queue: int,
                  batch_highwater: float = 1.0):
    """THE shared admission ladder (engine Scheduler + both fleet
    placements; caller holds its own queue lock). Appends ``req`` and
    returns the shed victim to conclude (outside the lock), or raises
    :class:`AdmissionRejected` — never a hang, never a silent drop."""
    tier = TieredQueue._tier(req)
    depth = len(tq)
    if depth >= max_queue:
        victim = tq.shed_victim(TIER_RANK[tier])
        if victim is None:
            raise AdmissionRejected("queue_full", tier, depth, max_queue)
        tq.append(req)
        return victim
    if tier == BATCH and depth >= batch_highwater * max_queue:
        raise AdmissionRejected("batch_highwater", tier, depth, max_queue)
    tq.append(req)
    return None


class Scheduler:
    """FIFO queue + block-budget admission over a shared :class:`BlockPool`.

    Thread-safe on the queue: ``submit`` may be called from any thread
    (the Poisson load generator, an RPC handler); admission and
    completion run on the serving loop's thread.
    """

    def __init__(self, pool: BlockPool, max_queue: int = 4096,
                 max_model_len: Optional[int] = None,
                 prefix_cache: Optional[PrefixCache] = None,
                 aging_s: float = 30.0, batch_highwater: float = 1.0,
                 rec: Optional[telemetry.Recorder] = None):
        self.pool = pool
        #: the engine's recorder (a scheduler on its own keeps a private one)
        self.rec = rec if rec is not None else telemetry.Recorder(
            "scheduler", keep=False)
        self.prefix_cache = prefix_cache
        self.max_queue = int(max_queue)
        self.max_model_len = max_model_len
        self._queue = TieredQueue(aging_s=aging_s)
        self.batch_highwater = float(batch_highwater)
        self._lock = threading.Lock()
        self.timed_out = 0           # requests shed past their deadline
        self.shed = 0                # requests shed by the overload ladder

    # ------------------------------------------------------------ queue side

    def submit(self, req: Request) -> Request:
        """Enqueue; raises on a full queue or an over-long request (the
        caller must know synchronously — a silently dropped request is a
        hung client). At a hard-full queue the round-19 ladder applies:
        a higher-tier arrival sheds the youngest lowest-tier queued
        request instead of being rejected (see :func:`admit_or_shed`)."""
        chaos.failpoint("serve.enqueue")
        check_admissible(len(req.prompt), req.max_new_tokens,
                         self.pool.block_size, self.pool.num_blocks,
                         self.max_model_len, label=f"request {req.rid}")
        with self._lock:
            victim = admit_or_shed(self._queue, req, self.max_queue,
                                   self.batch_highwater)
            if victim is not None:
                self.shed += 1
        if victim is not None:
            victim._finish(SHED, error=json.dumps(
                {"error": "shed", "reason": "displaced_by_tier",
                 "tier": TieredQueue._tier(victim)}, sort_keys=True))
        return req

    def __len__(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def pending(self) -> int:
        return len(self)

    # -------------------------------------------------------- admission side

    def blocks_needed(self, req: Request, prefix_tokens: int = 0) -> int:
        """Lifetime block budget: the cache holds prompt + max_new - 1
        tokens (the final sampled token is never written back), minus the
        full blocks a prefix hit already provides."""
        life = len(req.prompt) + max(req.max_new_tokens - 1, 0)
        return self.pool.blocks_for_tokens(life - prefix_tokens)

    def shed_expired(self) -> List[Request]:
        """Remove every still-queued request whose deadline has passed and
        conclude each with a TIMEOUT result (callback fires — the caller
        learns synchronously that the request was shed, not silently
        dropped). Runs at every admission pass; callbacks fire OUTSIDE the
        queue lock so an on_finish that resubmits cannot deadlock."""
        now = time.monotonic()
        with self._lock:
            expired = self._queue.remove_expired(now)
            self.timed_out += len(expired)
        for req in expired:
            logger.warning("serving: request %d shed past its deadline "
                           "after %.2fs queued", req.rid,
                           now - req.arrival_ts)
            req._finish(TIMEOUT, error="deadline exceeded while queued")
        return expired

    def next_admission(self) -> Optional[Request]:
        """Pop the head iff its block budget fits (strict FIFO: a head
        that does not fit blocks everything behind it). The caller runs
        :meth:`shed_expired` once per admission PASS (the engine's
        ``_admit`` does, even with every lane busy) — not per pop, which
        would rescan the whole queue for each admitted request. Tries
        prefix-cache eviction before giving up — cached-but-unused
        blocks must never starve admissions."""
        with self._lock:
            head = self._queue.peeknext()
            if head is None:
                return None
            with self.rec.span("serve.admit.peek"):
                hit_tokens, hit_key = (
                    (0, None) if self.prefix_cache is None
                    else self.prefix_cache.peek(head.prompt))
            # budget NET of the prefix hit, and the make-room eviction
            # protects the hit's entry — the head's own reusable prefix
            # must never be the victim of admitting the head
            need = self.blocks_needed(head, prefix_tokens=hit_tokens)
            if need > self.pool.free_count and self.prefix_cache is not None:
                with self.rec.span("serve.admit.evict"):
                    self.prefix_cache.evict(need, protect=hit_key)
            if need > self.pool.free_count:
                return None
            self._queue.remove(head)
            return head

    def withdraw(self, req: Request) -> bool:
        """Remove a still-queued request without concluding it (the
        process-fleet cancel path); True iff it was queued here."""
        with self._lock:
            return self._queue.remove(req)

    def requeue_front(self, req: Request) -> None:
        """Put an admission back at the HEAD (transient allocation failure
        — chaos 'serve.oom' or a racing allocation): FIFO order is
        preserved and the request is retried next iteration."""
        with self._lock:
            self._queue.appendleft(req)
