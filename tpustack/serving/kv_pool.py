"""Paged KV substrate — block pool, block-table bookkeeping, paged radix cache.

The engine's one KV store (``ContinuousEngine`` has no other; a pool is
made in one place, ``PagedKVRuntime.build``): ONE HBM-resident pool of
fixed-size KV *blocks*:

- Every layer's K/V lives in pool tensors ``[n_blocks, block_tokens, ...]``
  (``tpustack.models.llama.init_kv_pool``).  A sequence's logical cache
  line is a *block table* — ``max_seq // block_tokens`` block ids — and the
  device programs gather/scatter through it
  (``Generator._decode_scan_paged`` and friends).
- **Admission is capacity-true**: a request needs
  ``ceil((prompt + max_new) / block)`` blocks, not a whole ``max_seq``
  line, so concurrency at ctx 4k–8k rises to what HBM actually holds
  instead of an ``HBM / max_seq`` slot cap.
- **Prefix reuse is zero-copy**: a finished prefill's *full* blocks are
  recorded in a radix trie keyed by token ids (``PagedPrefixCache``).  A
  later request sharing the prefix points its block table at the SAME
  physical blocks — a refcount increment, no extract, no host round trip,
  no restore.  Blocks are freed only at refcount 0, so eviction can never
  pull KV out from under a decoding slot.

This module is the host side only: allocator (free list + refcounts),
admission math, and the block-id radix store.  It is dependency-free and
device-agnostic — the device surgery lives in ``llm_generate``, the engine
integration in ``llm_continuous``, and the HTTP policy in ``llm_server``.

Block 0 is reserved (never allocated): unoccupied block-table entries point
at it, so a gather of an idle region reads deterministic garbage that the
attention mask never admits, and nothing ever scatters into it.

Thread-safe: the server event loop reads stats and admits while the engine
thread allocates/frees at chunk boundaries.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tpustack import sanitize
from tpustack.utils import get_logger, knobs

log = get_logger("serving.kv_pool")


class OutOfBlocks(RuntimeError):
    """Allocation failed: the pool has fewer free blocks than requested."""


def eta_until_blocks(releases, need_blocks: int) -> float:
    """Wall-clock seconds until ``need_blocks`` pool blocks are projected
    to free: walk ``releases`` — one ``(eta_seconds, blocks_held)`` pair
    per in-flight request, each ETA computed by the caller from the
    request's remaining budget over its LIVE token rate (the engine feeds
    the measured wave rate times the slot's tokens-per-wave stride EMA, so
    a slot speculation is advancing k+1 tokens per dispatch projects k+1
    times sooner than a one-token-per-wave assumption would) — in finish
    order and report when the cumulative release covers the need.  Pure
    math, separated from the engine for testability; 1.0 s when nothing is
    in flight (the caller has no basis for an estimate)."""
    rel = sorted(releases)
    freed = 0
    for eta, n in rel:
        freed += n
        if freed >= need_blocks:
            return eta
    return rel[-1][0] if rel else 1.0


class KVBlockPool:
    """Fixed-size block allocator with per-block refcounts.

    ``n_blocks`` includes the reserved block 0, so ``capacity_blocks`` (the
    allocatable count) is ``n_blocks - 1``.  ``block_tokens`` is the tokens
    per block — the paged analog of the prefix cache's chunk granularity
    AND the rounding quantum of the admission math.

    Refcount protocol: ``alloc_tokens`` returns blocks at refcount 1 (the
    caller — an engine slot — owns that reference).  Sharing increfs
    (``PagedPrefixCache.match`` for a hitting slot, ``insert`` for the
    cache's own resident reference).  ``decref`` returns a block to the
    free list only when the count reaches 0 — a cached block being decoded
    against (count ≥ 2) survives any eviction attempt by construction.

    ``filled`` tracks the tokens each allocation committed per block, so
    ``fragmentation()`` can report the slack the fixed block size wastes
    (reserved-but-unfillable tail tokens): larger blocks → fewer
    gather/scatter indices but more slack and coarser prefix sharing.
    """

    def __init__(self, n_blocks: int, block_tokens: int):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (0 is reserved), got {n_blocks}")
        if block_tokens <= 0:
            raise ValueError(f"block_tokens must be positive, got {block_tokens}")
        self.n_blocks = n_blocks
        self.block = block_tokens
        self._lock = threading.RLock()
        # the free list and refcounts are the allocator's whole integrity:
        # every mutation holds the lock (tpulint TPL201); the lock-free
        # n_free/refcount READS are advisory (len() is atomic, admission
        # re-checks under the lock inside alloc_tokens)
        self._free: deque = deque(range(1, n_blocks))  # guarded-by: _lock (writes)
        self._ref = np.zeros(n_blocks, np.int64)  # guarded-by: _lock (writes)
        self._filled = np.zeros(n_blocks, np.int64)  # guarded-by: _lock (writes)
        # per-block allocation wall clock (time.time at alloc_tokens) —
        # the alloc→release residency window the block-seconds accounting
        # (tenant cost attribution, tpustack.obs.accounting) bills; the
        # pool-level total below is the ground truth those per-tenant
        # charges are a partition of
        self._alloc_t = np.zeros(n_blocks, np.float64)  # guarded-by: _lock (writes)
        # monotonic counters for stats()
        self.allocated_blocks_total = 0  # guarded-by: _lock (writes)
        self.freed_blocks_total = 0  # guarded-by: _lock (writes)
        # cumulative block-seconds of every block's full alloc→free
        # lifetime (accumulated when a block returns to the free list)
        self.block_seconds_total = 0.0  # guarded-by: _lock (writes)
        #: optional observer (tpustack.obs.kvprof.KVProfiler) notified of
        #: alloc/free events OUTSIDE the allocator lock; None (the
        #: TPUSTACK_KVPROF_RATE=0 default) keeps alloc/decref exactly the
        #: profiler-free paths
        self.profiler = None
        sanitize.install_guards(self)

    # ------------------------------------------------------------ capacity
    @property
    def capacity_blocks(self) -> int:
        return self.n_blocks - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.capacity_blocks - len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks a sequence of ``n_tokens`` occupies (ceil)."""
        return max(0, (n_tokens + self.block - 1) // self.block)

    def can_admit(self, n_tokens: int) -> bool:
        return self.blocks_for(n_tokens) <= self.n_free

    # ---------------------------------------------------------- allocation
    def alloc_tokens(self, n_tokens: int) -> List[int]:
        """Allocate blocks covering ``n_tokens`` (refcount 1 each).  Raises
        :class:`OutOfBlocks` without side effects when the pool is short —
        admission must gate, not half-allocate."""
        need = self.blocks_for(n_tokens)
        now = time.time()
        with self._lock:
            if need > len(self._free):
                raise OutOfBlocks(
                    f"need {need} blocks for {n_tokens} tokens, "
                    f"{len(self._free)} free of {self.capacity_blocks}")
            ids = [self._free.popleft() for _ in range(need)]
            remaining = n_tokens
            for bid in ids:
                self._ref[bid] = 1
                self._filled[bid] = min(self.block, remaining)
                self._alloc_t[bid] = now
                remaining -= min(self.block, remaining)
            self.allocated_blocks_total += need
        prof = self.profiler
        if prof is not None and need:
            prof.on_block_alloc(need, now)
        return ids

    def incref(self, ids: Sequence[int]) -> None:
        with self._lock:
            for bid in ids:
                if self._ref[bid] <= 0:
                    raise ValueError(f"incref on free block {bid}")
                self._ref[bid] += 1

    def decref(self, ids: Sequence[int],
               outcome: Optional[str] = None) -> int:
        """Drop one reference per id; blocks reaching 0 return to the free
        list.  Returns how many were actually freed.

        ``outcome`` names WHY the reference dropped for the profiler's
        block-lifetime split — "retired" (sequence completed), "evicted_warm"
        / "evicted_cold" (prefix-cache eviction), "died_queued" (released
        before ever decoding) — and is ignored when no profiler is
        attached."""
        freed = 0
        now = time.time()
        ages: List[float] = []
        with self._lock:
            track = self.profiler is not None
            for bid in ids:
                if self._ref[bid] <= 0:
                    raise ValueError(f"decref on free block {bid}")
                self._ref[bid] -= 1
                if self._ref[bid] == 0:
                    self._filled[bid] = 0
                    if self._alloc_t[bid]:
                        age = max(0.0, now - self._alloc_t[bid])
                        self.block_seconds_total += age
                        self._alloc_t[bid] = 0.0
                        if track:
                            ages.append(age)
                    self._free.append(bid)
                    freed += 1
            self.freed_blocks_total += freed
            n_free = len(self._free)
        prof = self.profiler
        if prof is not None and freed:
            prof.on_block_free(ages, now, n_free, outcome)
        return freed

    def refcount(self, bid: int) -> int:
        return int(self._ref[bid])

    # ------------------------------------------------------------- metrics
    def fragmentation(self) -> float:
        """Internal fragmentation of the current allocation: the fraction
        of reserved token slots in used blocks that no token can ever fill
        (block-rounding slack).  0.0 when idle."""
        with self._lock:
            used = self.n_used
            if used == 0:
                return 0.0
            filled = int(self._filled.sum())
            return max(0.0, 1.0 - filled / (used * self.block))

    def flight_snapshot(self) -> Tuple[int, int, float]:
        """``(free, used, fragmentation)`` under ONE lock acquisition —
        the per-wave flight-recorder read (three separate property reads
        would take the allocator lock three times per wave, and could see
        a half-applied alloc between them)."""
        with self._lock:
            used = self.n_used
            filled = int(self._filled.sum())
            frag = (max(0.0, 1.0 - filled / (used * self.block))
                    if used else 0.0)
            return self.n_free, used, frag

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "block_tokens": self.block,
                "pool_blocks": self.capacity_blocks,
                "free_blocks": self.n_free,
                "used_blocks": self.n_used,
                "utilization": (self.n_used / self.capacity_blocks
                                if self.capacity_blocks else 0.0),
                "fragmentation": round(self.fragmentation(), 4),
                "allocated_blocks_total": self.allocated_blocks_total,
                "freed_blocks_total": self.freed_blocks_total,
                "block_seconds_total": round(self.block_seconds_total, 3),
            }


class PagedMatch:
    """Result of a paged lookup: ``length`` cached tokens (block-snapped, 0
    on a miss) and the matched ``block_ids``.  The caller OWNS one
    reference per matched block (taken under the trie lock) — the engine
    folds them into the slot's block list so a single retire-time decref
    releases hit and fresh blocks alike.

    ``host_payloads`` (host-tier caches only) are claimed host-RAM KV
    payloads for the blocks immediately FOLLOWING the HBM match — one
    per block, in prefix order.  The caller owns them outright (they
    left the tier at claim time): it allocates fresh pool blocks and the
    engine scatters the payloads back before the warm start, or drops
    them (``HostKVTier.abandon``) when allocation fails."""

    __slots__ = ("length", "block_ids", "host_payloads")

    def __init__(self, length: int, block_ids: List[int],
                 host_payloads: Optional[list] = None):
        self.length = length
        self.block_ids = block_ids
        self.host_payloads = host_payloads or []


_NODE_UIDS = itertools.count(1)


class _Node:
    """One block of a cached prefix: edge label = its token ids, payload =
    the physical block id (the cache holds one pool reference on it)."""

    __slots__ = ("key", "parent", "children", "block_id", "last_used",
                 "last_hit_wall", "uid", "tier")

    def __init__(self, key: Tuple[int, ...], parent: Optional["_Node"],
                 block_id: int):
        self.key = key
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.block_id = block_id
        self.last_used = 0
        # wall clock of the last touch (insert or match hit) — what the
        # eviction path reads to tell an avoidable warm eviction from a
        # cold one, and what the reuse-gap histogram measures between
        self.last_hit_wall = 0.0
        self.uid = next(_NODE_UIDS)
        # which tier holds this chunk's KV bytes: "hbm" (block_id is a
        # live pool block the cache holds one reference on) or "host"
        # (block_id is -1; the bytes live in the HostKVTier arena — or
        # nowhere, if the tier entry was claimed/expired, in which case
        # the node is a reusable stub that a later insert re-promotes)
        self.tier = "hbm"


class PagedPrefixCache:
    """Radix trie of cached prefixes keyed on token ids, valued in BLOCK
    IDS — the paged rekeying of ``prefix_cache.PrefixCache``.

    The dense store held host numpy KV and a hit paid restore (host→HBM
    copy-in); here a node is one pool block id and a hit is pointer
    arithmetic: the engine writes the matched ids into the slot's block
    table and attention gathers the shared blocks directly.  Zero KV bytes
    move on either hit or insert.

    Only *complete* blocks are cached (``insert`` takes the blocks covering
    ``floor(n_prompt / block) * block`` prompt tokens): a partial tail
    block keeps receiving the owning slot's decode K/V writes, so sharing
    it would let two slots write different tokens into the same physical
    positions.  Matches are additionally capped at ``len(ids) - 1`` tokens
    — the engine must prefill at least one token for next-token logits.

    Eviction (`evict`) drops least-recently-used leaves whose block nobody
    else references (pool refcount == 1, i.e. only the cache's own ref) —
    a block a live slot shares is skipped, never reclaimed.  There is no
    byte cap: the pool itself bounds residency, and the server evicts on
    demand when admission runs short of free blocks.
    """

    def __init__(self, pool: KVBlockPool, on_evict=None,
                 on_evict_warm=None, warm_s: Optional[float] = None):
        self.pool = pool
        self.block = pool.block
        #: optional hook called (outside the lock) with the number of
        #: blocks an evict() pass freed — the server bumps its eviction
        #: counter here, mirroring the dense store's contract
        self.on_evict = on_evict
        #: optional hook: how many of an evict() pass's victims were WARM
        #: (last hit within warm_s — evictions more capacity would have
        #: avoided); the server bumps the warm-eviction counter here
        self.on_evict_warm = on_evict_warm
        self.warm_s = (knobs.get_float("TPUSTACK_KVPROF_WARM_S")
                       if warm_s is None else float(warm_s))
        #: optional observer (tpustack.obs.kvprof.KVProfiler) fed lookup
        #: and eviction events OUTSIDE the trie lock; None = profiler off
        self.profiler = None
        #: optional second-chance tier (tpustack.serving.kv_host_tier
        #: .HostKVTier) — when set, evict() offers each victim's KV bytes
        #: to host RAM instead of dropping them, and match() extends hits
        #: through spilled chunks (returning claimed payloads for the
        #: caller to restore pool-side).  None = spill disabled; every
        #: path below degrades to the exact pre-tier behaviour.
        self.host_tier = None
        self._root = _Node((), None, -1)  # guarded-by: _lock (writes)
        self._lock = threading.Lock()
        self._tick = 0  # guarded-by: _lock (writes)
        # stats
        self.entries = 0
        self.hits = 0
        self.misses = 0
        self.lookups = 0
        self.evictions = 0
        self.hit_tokens = 0
        self.inserted_tokens = 0
        self.evicted_warm_total = 0
        self.evicted_cold_total = 0
        self.host_hits = 0
        self.host_hit_tokens = 0
        sanitize.install_guards(self)

    # ------------------------------------------------------------- lookup
    def match(self, ids: List[int]) -> PagedMatch:
        """Longest cached prefix of ``ids`` (whole blocks, capped at
        ``len(ids) - 1`` tokens).  Increfs every matched block before
        returning — the caller owns those references (see PagedMatch).

        With a host tier attached, the walk continues past the HBM
        frontier through contiguous ``tier=host`` chunks: if the
        restore-vs-recompute crossover says copying beats recomputing,
        each chunk's payload is CLAIMED out of the tier (it now belongs
        to the caller, who restores it into freshly allocated pool
        blocks — or abandons it if allocation fails).  Claimed nodes stay
        in the trie as payload-less stubs; the restoring request's
        ``insert`` re-promotes them to HBM, keeping any deeper spilled
        descendants reachable."""
        max_blocks = max(0, (len(ids) - 1) // self.block)
        now = time.time()
        prev_hit = 0.0
        host_payloads: list = []
        with self._lock:
            self._tick += 1
            self.lookups += 1
            node, depth, blocks = self._root, 0, []
            while depth < max_blocks:
                key = tuple(ids[depth * self.block:(depth + 1) * self.block])
                child = node.children.get(key)
                if child is None or child.tier != "hbm":
                    break
                child.last_used = self._tick
                prev_hit = child.last_hit_wall
                child.last_hit_wall = now
                blocks.append(child.block_id)
                node, depth = child, depth + 1
            tier = self.host_tier
            if tier is not None and depth < max_blocks:
                # probe the contiguous host chain first, then consult the
                # crossover with the full restorable length
                hnode, hdepth, chain = node, depth, []
                while hdepth < max_blocks:
                    key = tuple(
                        ids[hdepth * self.block:(hdepth + 1) * self.block])
                    c = hnode.children.get(key)
                    if c is None or c.tier != "host":
                        break
                    chain.append(c)
                    hnode, hdepth = c, hdepth + 1
                if chain and tier.should_restore(len(chain)):
                    for c in chain:
                        payload = tier.claim(c)
                        if payload is None:
                            # stub (already claimed / LRU-expired): the
                            # chunk's bytes are gone — hit ends here
                            break
                        host_payloads.append(payload)
                        c.last_used = self._tick
                        c.last_hit_wall = now
            if not blocks and not host_payloads:
                self.misses += 1
                res = PagedMatch(0, [])
            else:
                if blocks:
                    self.pool.incref(blocks)
                self.hits += 1
                self.hit_tokens += depth * self.block
                if host_payloads:
                    self.host_hits += 1
                    self.host_hit_tokens += len(host_payloads) * self.block
                res = PagedMatch(depth * self.block, blocks, host_payloads)
        prof = self.profiler
        if prof is not None:
            # reuse gap = time since the DEEPEST matched node's previous
            # touch (the prefix's whole-entry revisit interval); misses
            # and first touches carry no gap
            gap = (now - prev_hit) if (blocks and prev_hit) else None
            prof.on_lookup(ids, reuse_gap_s=gap)
        return res

    # ------------------------------------------------------------- insert
    def insert(self, ids: List[int], block_ids: Sequence[int]) -> int:
        """Record ``block_ids`` as the cache entry for the first
        ``len(block_ids)`` whole blocks of ``ids``.  Newly recorded blocks
        gain one pool reference (the cache's); blocks whose chunk is
        already cached — possibly under a DIFFERENT physical id from a
        concurrent identical prompt — are skipped (the caller's copy is
        simply not recorded and frees at retire).  Returns newly cached
        tokens."""
        if len(block_ids) * self.block > len(ids):
            raise ValueError(
                f"{len(block_ids)} blocks cover "
                f"{len(block_ids) * self.block} tokens > prompt {len(ids)}")
        new_tokens = 0
        now = time.time()
        with self._lock:
            self._tick += 1
            node = self._root
            for d, bid in enumerate(block_ids):
                key = tuple(ids[d * self.block:(d + 1) * self.block])
                child = node.children.get(key)
                if child is None:
                    self.pool.incref([bid])
                    child = _Node(key, node, bid)
                    node.children[key] = child
                    self.entries += 1
                    new_tokens += self.block
                elif child.tier != "hbm":
                    # re-promote a spilled chunk: the caller holds fresh
                    # HBM bytes for it (a restored host hit, or a plain
                    # recompute of a claimed/expired stub) — adopt the new
                    # block and retire any stale host copy
                    self.pool.incref([bid])
                    child.block_id = bid
                    child.tier = "hbm"
                    if self.host_tier is not None:
                        self.host_tier.drop(child)
                    self.entries += 1
                    new_tokens += self.block
                child.last_used = self._tick
                child.last_hit_wall = now
                node = child
            self.inserted_tokens += new_tokens
        return new_tokens

    # ----------------------------------------------------------- eviction
    @staticmethod
    def _hbm_children(node: "_Node") -> bool:
        """True when any direct child still holds a pool block.  Host
        stubs are TRANSPARENT for eviction: a node whose children all
        spilled is as evictable as a leaf (spilled descendants hold no
        pool reference and survive in the host arena regardless)."""
        return any(c.tier == "hbm" for c in node.children.values())

    def evictable_blocks(self) -> int:
        """Blocks the cache could release right now: resident nodes whose
        block only the cache references (no slot is decoding against it).
        This is what capacity-true admission adds to the free count."""
        with self._lock:
            return sum(1 for n in self._walk()
                       if n.tier == "hbm"
                       and self.pool.refcount(n.block_id) == 1)

    def evict(self, need_blocks: int) -> int:
        """Release up to ``need_blocks`` blocks, LRU leaves first (interior
        nodes become leaves — and eviction candidates — as their subtrees
        drain, via the parent-promotion push below).  Leaves a live slot
        shares (pool refcount > 1) are skipped — eviction is blocked while
        referenced; the block frees later when the slot retires and its
        decref reaches 0.  One trie walk total (a heap orders candidates),
        not one per freed block — this runs on the serving thread under
        admission pressure.  Returns blocks actually freed.

        With a host tier attached, each victim's KV bytes are offered to
        host RAM before the block dies: on acceptance the node is
        retagged ``tier=host`` (it stays in the trie; the payload lives
        in the tier's arena) and the block frees with outcome
        ``spilled``; on decline (copy failed, or the tier can never hold
        a block) the node is removed exactly as before with outcome
        ``evicted_warm``/``evicted_cold``.  EVERY victim takes exactly
        one ``pool.decref(outcome=...)`` — the single path kvprof's
        lifetime histogram and the tier counters both hang off, so a
        declined spill can never double-count."""
        import heapq

        freed = 0
        warm = 0
        spilled = 0
        now = time.time()
        hit_ages: List[float] = []
        tier = self.host_tier
        with self._lock:
            heap = [(n.last_used, n.uid, n) for n in self._walk()
                    if n.tier == "hbm" and not self._hbm_children(n)
                    and self.pool.refcount(n.block_id) == 1]
            heapq.heapify(heap)
            while heap and freed < need_blocks:
                _, _, leaf = heapq.heappop(heap)
                # a promoted parent may have been re-checked stale; guard
                if (leaf.tier != "hbm" or self._hbm_children(leaf)
                        or leaf.parent.children.get(leaf.key) is not leaf
                        or self.pool.refcount(leaf.block_id) != 1):
                    continue
                bid = leaf.block_id
                # warm = the entry was hit recently enough that a bigger
                # pool would plausibly have kept it (avoidable eviction)
                age = ((now - leaf.last_hit_wall)
                       if leaf.last_hit_wall else -1.0)
                kept = False
                if tier is not None:
                    payload = tier.snapshot_block(bid)
                    if payload is None:
                        tier.decline()
                    else:
                        kept = tier.offer(leaf, payload)
                if kept:
                    outcome = "spilled"
                    spilled += 1
                    leaf.block_id = -1
                    leaf.tier = "host"
                else:
                    leaf.parent.children.pop(leaf.key)
                    # spilled descendants of a dying node would become
                    # unreachable — retire their arena entries with it
                    self._drop_host_subtree(leaf)
                    if 0.0 <= age <= self.warm_s:
                        warm += 1
                        self.evicted_warm_total += 1
                        outcome = "evicted_warm"
                    else:
                        self.evicted_cold_total += 1
                        outcome = "evicted_cold"
                self.entries -= 1
                self.evictions += 1
                if age >= 0.0:
                    hit_ages.append(age)
                freed += self.pool.decref([bid], outcome=outcome)
                parent = leaf.parent
                if (parent is not self._root and parent.tier == "hbm"
                        and not self._hbm_children(parent)
                        and self.pool.refcount(parent.block_id) == 1):
                    heapq.heappush(heap,
                                   (parent.last_used, parent.uid, parent))
        if freed:
            log.info("paged prefix cache evicted %d block(s) "
                     "(%d tokens, %d warm, %d spilled to host)",
                     freed, freed * self.block, warm, spilled)
            if self.on_evict is not None:
                self.on_evict(freed)
            if warm and self.on_evict_warm is not None:
                self.on_evict_warm(warm)
            prof = self.profiler
            if prof is not None:
                prof.on_evictions(hit_ages, warm)
        return freed

    def _drop_host_subtree(self, node: "_Node") -> None:
        """Retire the tier entries of every host node under ``node``
        (inclusive) — called when a node leaves the trie, so the arena
        never holds bytes no lookup can reach.  Caller holds ``_lock``.
        By construction the subtree of an eviction victim is host-only
        (a candidate has no HBM children, and insert promotes ancestors
        before descendants), but this walks everything to be safe."""
        if self.host_tier is None:
            return
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.tier == "host":
                self.host_tier.drop(n)

    def _walk(self):
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    # -------------------------------------------------------------- admin
    def clear(self) -> int:
        """Drop every resident node (decref all) — returns blocks freed."""
        with self._lock:
            ids = [n.block_id for n in self._walk() if n.tier == "hbm"]
            self._root = _Node((), None, -1)
            self.entries = 0
            if self.host_tier is not None:
                self.host_tier.clear()
            return self.pool.decref(ids) if ids else 0

    def stats(self) -> Dict[str, object]:
        with self._lock:
            lookups = self.hits + self.misses
            out = {
                "enabled": True,
                "paged": True,
                "block_tokens": self.block,
                "entries": self.entries,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": (self.hits / lookups) if lookups else 0.0,
                "evictions": self.evictions,
                "evicted_warm": self.evicted_warm_total,
                "evicted_cold": self.evicted_cold_total,
                "cached_tokens_served": self.hit_tokens,
                "inserted_tokens": self.inserted_tokens,
            }
        tier = self.host_tier
        if tier is not None:
            out["host_hits"] = self.host_hits
            out["host_hit_tokens"] = self.host_hit_tokens
            out["host_tier"] = tier.stats()
        return out


class PagedKVRuntime:
    """Everything the serving stack shares about one paged KV pool: the
    host allocator, the persistent DEVICE pool arrays (handed to each
    ``ContinuousEngine`` run and handed back — cached blocks must survive
    across busy periods), and the optional paged prefix cache.

    ``arrays`` is the per-layer list of pool tensors from
    ``tpustack.models.llama.init_kv_pool``; the engine donates them to
    every paged dispatch and stores the returned buffers back here, so
    there is exactly one pool's worth of HBM however many runs come and
    go.  ``block_tables(B)`` returns a fresh host-side table (int32,
    ``[B, max_seq // block]``, all entries the reserved block 0).
    """

    def __init__(self, arrays, pool: KVBlockPool, max_seq: int,
                 cache: Optional[PagedPrefixCache] = None):
        if max_seq % pool.block:
            raise ValueError(
                f"max_seq {max_seq} not a multiple of block {pool.block}")
        self.arrays = arrays
        self.pool = pool
        self.cache = cache
        self.max_seq = max_seq
        self.block = pool.block
        self.blocks_per_seq = max_seq // pool.block
        # per-shard HBM accounting (tensor-parallel serving): total pool
        # bytes, the largest single-device shard (what one chip actually
        # holds — pool/tp when the kv-head axis shards, the whole pool
        # unsharded), and the implied shard ways.  Computed once — the
        # pool's shape and sharding are fixed for its lifetime (donation
        # rotates buffers, never layouts).
        from tpustack.parallel.sharding import (tree_bytes,
                                                tree_per_shard_bytes)

        self.pool_bytes = tree_bytes(arrays)
        self.per_shard_bytes = tree_per_shard_bytes(arrays)
        self.kv_shards = max(1, round(self.pool_bytes
                                      / max(1, self.per_shard_bytes)))

    @staticmethod
    def build(cfg, slots: int, *, block: int = 0, pool_blocks: int = 0,
              dtype=None, mesh=None, prefix_cache: bool = False,
              host_tier_mb: float = 0.0) -> "PagedKVRuntime":
        """THE place a pool is made: allocator, device arrays and (asked
        for) the block trie and its host tier, for an engine of ``slots``
        slots serving ``cfg``.

        ``block`` (tokens; 0 = ``min(64, max(8, max_seq // 8))``) snaps
        down by halving until it divides the context.  ``pool_blocks``
        (allocatable; 0 = ``slots x max_seq / block``, what ``slots``
        private cache lines would hold — the concurrency win comes from
        admission charging each request its ACTUAL ``prompt + max_new``
        instead of a whole line) excludes the reserved block 0, which is
        added here.  ``dtype`` is the cache dtype of a float pool (None =
        ``init_kv_pool``'s own); ``mesh`` lands the pool tensors
        head-axis-sharded over its ``tp`` axis, so each chip holds
        ``pool_bytes / tp`` — what ``per_shard_bytes`` reports back."""
        from tpustack.models.llama import init_kv_pool

        max_seq = cfg.max_seq
        if block <= 0:
            block = min(64, max(8, max_seq // 8))
        block = min(block, max_seq)
        while block > 1 and max_seq % block:
            block //= 2
        if pool_blocks <= 0:
            pool_blocks = slots * (max_seq // block)
        pool = KVBlockPool(pool_blocks + 1, block)
        cache = PagedPrefixCache(pool) if prefix_cache else None
        kw = {} if dtype is None else {"dtype": dtype}
        rt = PagedKVRuntime(
            init_kv_pool(cfg, pool_blocks + 1, block, mesh=mesh, **kw),
            pool, max_seq, cache)
        if cache is not None and host_tier_mb > 0:
            from tpustack.serving.kv_host_tier import HostKVTier

            # arrays_fn, not arrays: decode dispatches donate the pool
            # buffers, so the tier must re-read the runtime's CURRENT
            # reference at every spill
            cache.host_tier = HostKVTier(
                int(host_tier_mb * 1024 * 1024), pool,
                arrays_fn=lambda: rt.arrays)
            log.info("host KV tier on: %.0f MB arena behind the %d-block "
                     "pool", host_tier_mb, pool_blocks)
        log.info("KV pool: %d blocks x %d tokens (ctx %d, %d slots), "
                 "%.2f GB total / %.2f GB per chip (%d shard%s), prefix "
                 "cache %s", pool_blocks, block, max_seq, slots,
                 rt.pool_bytes / 1e9, rt.per_shard_bytes / 1e9,
                 rt.kv_shards, "s" if rt.kv_shards != 1 else "",
                 "on" if cache is not None else "off")
        return rt

    # ------------------------------------------------------ admission math
    def need_tokens(self, n_prompt: int, max_new: int) -> int:
        """Tokens a request reserves: prompt + its REAL budget (clamped to
        the context window) — the engine's own budget formula, so admission
        and allocation can never disagree.  Multi-token strides
        (speculative verify steps advancing 1..k+1 tokens per dispatch)
        never change this bound: the engine clamps draft length to the
        remaining budget and the verify programs clip their KV scatter at
        the accepted frontier, so no dispatch can write past
        ``prompt + budget`` however many tokens it lands at once."""
        return n_prompt + max(0, min(max_new, self.max_seq - n_prompt))

    def need_blocks(self, n_prompt: int, max_new: int) -> int:
        return self.pool.blocks_for(self.need_tokens(n_prompt, max_new))

    def ensure_free(self, n_blocks: int) -> bool:
        """True when ``n_blocks`` are free, evicting unreferenced cached
        blocks (LRU) to get there if needed."""
        short = n_blocks - self.pool.n_free
        if short > 0 and self.cache is not None:
            self.cache.evict(short)
        return self.pool.n_free >= n_blocks

    def admissible_blocks(self) -> int:
        """Blocks admission may count on immediately: free + evictable."""
        n = self.pool.n_free
        if self.cache is not None:
            n += self.cache.evictable_blocks()
        return n

    def stats(self) -> Dict[str, object]:
        out = dict(self.pool.stats())
        out["blocks_per_seq"] = self.blocks_per_seq
        out["pool_bytes"] = self.pool_bytes
        out["per_shard_bytes"] = self.per_shard_bytes
        out["kv_shards"] = self.kv_shards
        out["prefix_cache"] = (self.cache.stats() if self.cache is not None
                               else {"enabled": False})
        return out
