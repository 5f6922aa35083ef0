"""Host-tier KV (round 17): spill evicted prefix blocks to host RAM and
chunk long prefills into decode waves.

The acceptance bars this file holds:

- **Tier ledger** — the LRU arena's conservation identity
  (``spilled == restored + expired + resident``) survives every
  transition: offer, capacity expiry, claim, drop, abandon, clear — and
  an oversized payload is declined, never half-admitted.
- **Crossover guard** — restore-vs-recompute answers from the measured
  per-block EMAs; unmeasured → restore; ``crossover=False`` (the
  TPUSTACK_KV_HOST_TIER_CROSSOVER=0 bisection) restores unconditionally.
- **Trie integration** — ``evict`` retags refcount-0 victims
  ``tier=host`` (blocks free, payloads survive); ``match`` walks past
  the HBM frontier and CLAIMS contiguous host chunks; claimed nodes are
  payload-less stubs (a second match misses); ``insert`` re-promotes a
  stub with fresh HBM bytes.
- **Byte identity** — greedy engine outputs identical tier-on vs
  tier-off across plain / speculative / int8-KV engines with a working
  set ≫ the pool (spills AND restores provably happened), and across
  the HTTP server with the tier's Prometheus counters live.  A cold
  subprocess proves TPUSTACK_KV_HOST_TIER_MB=0 constructs NOTHING and
  matches byte-for-byte (the bisection contract).
- **Chunked prefill** — a long prompt split into block-aligned chunk
  waves (TPUSTACK_PREFILL_CHUNK_TOKENS) produces byte-identical greedy
  output, reports its chunk count, and the stats key is ABSENT with the
  knob off (perfsig signature stability).
- **Sanitizer** — ``check_kv_quiesce`` catches a broken cross-tier
  conservation ledger with an actionable report.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpustack import sanitize  # noqa: E402
from tpustack.models.llama import LlamaConfig  # noqa: E402
from tpustack.models.llm_continuous import (ContinuousEngine,  # noqa: E402
                                            SlotRequest)
from tpustack.models.llm_generate import Generator, SampleConfig  # noqa: E402
from tpustack.sanitize import SanitizerViolation, locks as san_locks  # noqa: E402
from tpustack.serving.kv_host_tier import HostKVTier, block_nbytes  # noqa: E402
from tpustack.serving.kv_pool import (KVBlockPool, OutOfBlocks,  # noqa: E402
                                      PagedKVRuntime, PagedPrefixCache)

GREEDY = SampleConfig(greedy=True)
BLOCK = 8

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(autouse=True)
def _sanitize_on():
    """Run with the sanitizer raising (self-sufficient standalone; the
    tier-1 plugin already arms it) and a fresh lock-order graph."""
    sanitize.activate(mode="raise")
    san_locks._reset_graph()
    yield
    sanitize.activate(mode="raise")


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


# --------------------------------------------------------------- helpers
class _FakeNode:
    """Trie-node stand-in for tier unit tests: the tier keys entries by
    ``uid`` and never touches anything else."""
    _next = iter(range(1, 1 << 20))

    def __init__(self):
        self.uid = next(self._next)
        self.tier = "host"


def _payload(fill=0.0):
    """One-layer, 64-byte block payload (k+v, 8 floats each)."""
    return [{"k": np.full((2, 4), fill, np.float32),
             "v": np.full((2, 4), fill, np.float32)}]


def _conserved(tier):
    st = tier.stats()
    return (st["spilled_total"]
            == st["restored_total"] + st["expired_total"]
            + st["resident_blocks"]) and \
        st["resident_bytes"] <= st["capacity_bytes"]


def _make_rt(gen, capacity_blocks, block=BLOCK, tier_mb=None, cache=True):
    rt = PagedKVRuntime.build(gen.cfg, 1, block=block,
                              pool_blocks=capacity_blocks,
                              dtype=gen.cache_dtype, prefix_cache=cache)
    if tier_mb and cache:
        # crossover OFF: on CPU-tiny shapes both EMAs measure dispatch
        # noise and the guard would (correctly) decline every restore
        rt.cache.host_tier = HostKVTier(
            int(tier_mb * 1024 * 1024), rt.pool,
            arrays_fn=lambda: rt.arrays, crossover=False)
    return rt


def _admit(rt, ids, max_new):
    """The server's ``_paged_admit`` flow, test-side (same shape as the
    bench's): prefix hit increfs shared blocks; claimed host payloads
    get fresh pool blocks riding the prefix lifecycle; a full pool
    abandons the claims so the ledger stays exact."""
    cache = rt.cache
    tier = getattr(cache, "host_tier", None)
    prefix, host_restore = None, None
    m = cache.match(ids)
    if m.length:
        prefix = (m.length, m.block_ids)
    if m.host_payloads:
        n_host = len(m.host_payloads)
        try:
            rt.ensure_free(n_host)
            restore_ids = rt.pool.alloc_tokens(n_host * rt.block)
        except OutOfBlocks:
            tier.abandon(n_host)
        else:
            prefix = (m.length + n_host * rt.block,
                      m.block_ids + list(restore_ids))
            host_restore = (restore_ids, m.host_payloads)
    n_shared = len(prefix[1]) if prefix else 0
    fresh = rt.need_tokens(len(ids), max_new) - n_shared * rt.block
    rt.ensure_free(rt.pool.blocks_for(fresh))
    kv_blocks = rt.pool.alloc_tokens(fresh)
    on_insert = (lambda bids, ids_c=list(ids): cache.insert(ids_c, bids))
    return dict(prefix=prefix, kv_blocks=kv_blocks,
                on_prefill_blocks=on_insert, host_restore=host_restore)


def _run_engine(gen, rt, prompts, max_new=4, spec=None, prefill_chunk=None,
                slots=1, admit=True):
    results = {}
    queue = list(enumerate(prompts))

    def feed():
        if not queue:
            return None
        i, ids = queue.pop(0)
        kw = _admit(rt, ids, max_new) if (admit and rt.cache is not None) \
            else {}
        return SlotRequest(ids=ids, max_new=max_new, sample=GREEDY, **kw,
                           on_done=lambda t, s, i=i:
                           results.__setitem__(i, (t, s)))

    eng = ContinuousEngine(gen, slots=slots, chunk=4, paged=rt, spec=spec,
                           prefill_chunk=prefill_chunk)
    stats = eng.run(feed)
    return results, stats


# ------------------------------------------------------ tier unit ledger
def test_tier_offer_claim_drop_conservation():
    tier = HostKVTier(128, pool=None, crossover=False)  # holds 2 payloads
    n1, n2, n3, n4 = (_FakeNode() for _ in range(4))
    assert tier.offer(n1, _payload(1.0))
    assert tier.offer(n2, _payload(2.0))
    assert tier.resident_blocks == 2 and tier.resident_bytes == 128
    # at capacity: the COLDEST entry (n1) expires to make room
    assert tier.offer(n3, _payload(3.0))
    st = tier.stats()
    assert st["spilled_total"] == 3 and st["expired_total"] == 1
    assert st["resident_blocks"] == 2 and _conserved(tier)
    assert tier.claim(n1) is None            # expired → stub
    got = tier.claim(n2)                     # resident → restored
    assert got is not None and float(got[0]["k"][0, 0]) == 2.0
    assert tier.claim(n2) is None            # a claim is a pop
    assert tier.stats()["restored_total"] == 1 and _conserved(tier)
    tier.drop(n3)                            # subtree removed → expired
    assert tier.stats()["expired_total"] == 2
    assert tier.resident_blocks == 0 and tier.resident_bytes == 0
    assert _conserved(tier)
    # abandon: a claim that never reached HBM moves restored → expired
    assert tier.offer(n4, _payload(4.0))
    assert tier.claim(n4) is not None
    tier.abandon(1)
    st = tier.stats()
    assert st["restored_total"] == 1 and st["expired_total"] == 3
    assert st["spilled_total"] == 4 and _conserved(tier)


def test_tier_declines_oversized_payload_and_clear_counts_expired():
    tier = HostKVTier(32, pool=None, crossover=False)  # payload is 64 B
    n = _FakeNode()
    assert tier.offer(n, _payload()) is False
    st = tier.stats()
    assert st["spill_declined_total"] == 1 and st["spilled_total"] == 0
    assert tier.resident_blocks == 0 and _conserved(tier)
    big = HostKVTier(1 << 12, pool=None, crossover=False)
    big.offer(_FakeNode(), _payload())
    big.offer(_FakeNode(), _payload())
    assert big.clear() == 2
    assert big.stats()["expired_total"] == 2 and _conserved(big)
    assert big.resident_bytes == 0


def test_tier_capacity_blocks_estimate_and_nbytes():
    arrays = [{"k": np.zeros((4, 8, 2, 3), np.float32),
               "v": np.zeros((4, 8, 2, 3), np.float32)}]
    per = 8 * 2 * 3 * 4 * 2                   # block slice bytes, k+v
    assert block_nbytes(arrays) == per
    tier = HostKVTier(10 * per, pool=None, arrays_fn=lambda: arrays,
                      crossover=False)
    assert tier.capacity_blocks == 10         # estimate before any spill


def test_tier_crossover_guard_ema_and_override(monkeypatch):
    arrays = [{"k": np.ones((4, 8, 2), np.float32)}]
    tier = HostKVTier(1 << 20, pool=None, arrays_fn=lambda: arrays,
                      crossover=True)
    assert tier.should_restore(1)             # unmeasured → restore
    assert tier.snapshot_block(1) is not None  # seeds the copy EMA
    tier.note_prefill(1000, 1e-9)             # recompute ≪ copy
    assert tier.should_restore(1) is False    # guard declines
    for _ in range(64):
        tier.note_prefill(1, 10.0)            # recompute ≫ copy again
    assert tier.should_restore(1) is True
    # the =0 bisection: measured-or-not, restore unconditionally
    off = HostKVTier(1 << 20, pool=None, arrays_fn=lambda: arrays,
                     crossover=False)
    off.snapshot_block(1)
    off.note_prefill(1000, 1e-9)
    assert off.should_restore(1) is True
    # crossover=None defers to the knob (default ON)
    monkeypatch.delenv("TPUSTACK_KV_HOST_TIER_CROSSOVER", raising=False)
    assert HostKVTier(1, pool=None)._crossover is True
    monkeypatch.setenv("TPUSTACK_KV_HOST_TIER_CROSSOVER", "0")
    assert HostKVTier(1, pool=None)._crossover is False


# ------------------------------------------------------- trie integration
def _trie(n_blocks=9, block=4, cap_bytes=1 << 20, crossover=False):
    pool = KVBlockPool(n_blocks, block)
    cache = PagedPrefixCache(pool)
    rng = np.random.default_rng(7)
    arrays = [{"k": rng.random((n_blocks, block, 2)).astype(np.float32),
               "v": rng.random((n_blocks, block, 2)).astype(np.float32)}]
    tier = HostKVTier(cap_bytes, pool, arrays_fn=lambda: arrays,
                      crossover=crossover)
    cache.host_tier = tier
    return pool, cache, tier, arrays


def test_trie_evict_spills_and_match_claims_then_stubs():
    pool, cache, tier, arrays = _trie()
    ids = list(range(16))
    blocks = pool.alloc_tokens(16)
    assert cache.insert(ids, blocks) == 16
    pool.decref(blocks)                       # cache holds the only refs
    assert cache.evict(4) == 4                # every victim spills
    st = tier.stats()
    assert st["spilled_total"] == 4 and st["resident_blocks"] == 4
    assert pool.n_used == 0                   # HBM blocks freed
    m = cache.match(ids + [99])               # walk is ALL host chunks
    assert m.length == 0 and m.block_ids == []
    assert len(m.host_payloads) == 4
    # claimed payloads are the exact spilled rows, shallow→deep
    for d, p in enumerate(m.host_payloads):
        assert np.array_equal(p[0]["k"], arrays[0]["k"][blocks[d]])
    assert tier.stats()["restored_total"] == 4 and _conserved(tier)
    # claimed nodes are stubs now: a second identical match misses
    m2 = cache.match(ids + [99])
    assert m2.length == 0 and not m2.host_payloads
    tier.abandon(4)                           # we never restored them
    assert _conserved(tier)


def test_trie_partial_spill_walks_past_hbm_frontier():
    pool, cache, tier, _ = _trie()
    ids = list(range(16))
    blocks = pool.alloc_tokens(16)
    cache.insert(ids, blocks)
    pool.decref(blocks)
    assert cache.evict(1) == 1                # deepest leaf only
    m = cache.match(ids + [99])
    assert m.length == 12 and m.block_ids == blocks[:3]
    assert len(m.host_payloads) == 1          # the spilled tail chunk
    pool.decref(m.block_ids)
    tier.abandon(1)
    assert _conserved(tier)


def test_trie_insert_repromotes_claimed_stub():
    pool, cache, tier, _ = _trie()
    ids = list(range(16))
    blocks = pool.alloc_tokens(16)
    cache.insert(ids, blocks)
    pool.decref(blocks)
    cache.evict(4)
    m = cache.match(ids + [99])               # claim all four
    assert len(m.host_payloads) == 4
    tier.abandon(4)
    fresh = pool.alloc_tokens(16)             # "recomputed" HBM bytes
    assert cache.insert(ids, fresh) == 16     # stubs re-promoted
    pool.decref(fresh)
    m2 = cache.match(ids + [99])
    assert m2.length == 16 and m2.block_ids == fresh
    assert not m2.host_payloads
    pool.decref(m2.block_ids)
    assert _conserved(tier)


def test_trie_crossover_decline_leaves_chain_resident():
    """A guard that answers 'recompute' must leave the host chain
    untouched — the payloads stay claimable for a later, cheaper walk."""
    pool, cache, tier, _ = _trie(crossover=True)
    ids = list(range(16))
    blocks = pool.alloc_tokens(16)
    cache.insert(ids, blocks)
    pool.decref(blocks)
    cache.evict(4)                            # spills seed the copy EMA
    tier.note_prefill(1000, 1e-9)             # recompute ≪ copy
    m = cache.match(ids + [99])
    assert m.length == 0 and not m.host_payloads
    assert tier.stats()["resident_blocks"] == 4
    assert tier.stats()["restored_total"] == 0 and _conserved(tier)


# -------------------------------------------- engine byte-identity matrix
def _doc_prompts(n_docs=4, rounds=2, doc_tokens=16, base=11):
    """Working set ≫ pool: ``n_docs`` distinct 2-block docs, revisited
    each round with a fresh 3-token tail (prefix-shareable, never
    whole-prompt identical)."""
    prompts = []
    for r in range(rounds):
        for d in range(n_docs):
            body = [(base + d * 31 + j) % 200 + 3 for j in range(doc_tokens)]
            prompts.append(body + [220, 221, (r * n_docs + d) % 7 + 2])
    return prompts


@pytest.mark.parametrize("variant", ["plain", "spec", "kv_int8"])
def test_engine_tier_onoff_byte_identity(gen, variant):
    """ACCEPTANCE: greedy outputs byte-identical tier-on vs tier-off with
    a working set ≫ the pool — spills AND restores provably happened, the
    conservation ledger is exact, and the drained pool leaks nothing —
    across the plain, speculative, and int8-KV engines."""
    if variant == "kv_int8":
        cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64),
                                  kv_quant="int8")
        g = Generator(cfg, dtype=jnp.float32, seed=3)
    else:
        g = gen
    spec = None
    if variant == "spec":
        from tpustack.serving.speculative import SpecConfig
        spec = SpecConfig(tokens=3)
    prompts = _doc_prompts()
    outs = {}
    for tier_mb in (0, 8):
        rt = _make_rt(g, capacity_blocks=6, tier_mb=tier_mb)
        results, _ = _run_engine(g, rt, prompts, spec=spec)
        assert len(results) == len(prompts)
        outs[tier_mb] = [results[i][0] for i in sorted(results)]
        tier = rt.cache.host_tier
        if tier is not None:
            st = tier.stats()
            assert st["spilled_total"] > 0, "working set never spilled"
            assert st["restored_total"] > 0, "no host hit restored"
            assert _conserved(tier)
            # the arena mirrors the pool layout (int8: scales included)
            assert st["block_bytes"] == block_nbytes(rt.arrays)
        sanitize.check_kv_quiesce(rt, where=f"{variant} tier={tier_mb}")
        rt.cache.host_tier = None             # ledger captured; evict-all
        rt.cache.evict(rt.pool.capacity_blocks)  # must not re-spill
        assert rt.pool.n_used == 0
    assert outs[0] == outs[8]


def test_engine_abandons_claims_when_pool_full(gen):
    """A claim whose restore allocation loses the race moves
    restored→expired (the ledger stays exact) and the request proceeds
    as a plain recompute — the tier is never load-bearing."""
    rt = _make_rt(gen, capacity_blocks=6, tier_mb=8)
    tier = rt.cache.host_tier
    ids = list(range(3, 19))                  # two full blocks
    blocks = rt.pool.alloc_tokens(16)
    rt.cache.insert(ids, blocks)
    rt.pool.decref(blocks)
    rt.cache.evict(2)
    assert tier.stats()["resident_blocks"] == 2
    # wedge the pool: everything allocated and externally held, so the
    # claims' restore allocation fails and admission answers capacity
    wedge = rt.pool.alloc_tokens(rt.pool.n_free * rt.block)
    with pytest.raises(OutOfBlocks):
        _admit(rt, ids + [99, 98, 97], max_new=2)
    st = tier.stats()
    assert st["restored_total"] == 0 and st["expired_total"] == 2
    assert _conserved(tier)
    rt.pool.decref(wedge)
    assert rt.pool.n_used == 0


# --------------------------------------------------------- HTTP server e2e
def test_server_tier_onoff_byte_identity_and_counters(gen):
    """The HTTP bar: greedy completions byte-identical tier-on vs
    tier-off through the full server admission path, with the tier's
    Prometheus counters live on /metrics and the ledger conserved."""
    from tests.test_kv_pool import _post_all, _server

    docs = [f"document number {d} body padding xyzw" for d in range(6)]
    payloads = [{"prompt": p, "n_predict": 4, "temperature": 0}
                for p in docs * 2]
    outs = {}
    for tier_mb in (0, 8):
        rt = _make_rt(gen, capacity_blocks=6, tier_mb=tier_mb)
        server, _ = _server(gen, paged=rt)
        res, _, metrics = _post_all(server, payloads)
        outs[tier_mb] = res
        tier = rt.cache.host_tier
        if tier is not None:
            st = tier.stats()
            assert st["spilled_total"] > 0 and st["restored_total"] > 0
            assert _conserved(tier)
            # the server attached its metric set; counters exported live
            for line in metrics.splitlines():
                if line.startswith("tpustack_llm_kv_host_spilled"):
                    assert float(line.split()[-1]) == st["spilled_total"]
                    break
            else:
                pytest.fail("host spill counter missing from /metrics")
        sanitize.check_kv_quiesce(rt, where=f"server tier={tier_mb}")
    assert outs[0] == outs[8]


# ------------------------------------------------- cold-subprocess bisection
_BISECT = """
import json, sys
import numpy as np
import jax.numpy as jnp
sys.path.insert(0, ".")
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.serving.kv_pool import OutOfBlocks
from tpustack.serving.llm_server import LLMServer

gen = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
rt = LLMServer._build_paged(gen, max_batch=2)  # env decides the tier
cache = rt.cache
prompts = []
for r in range(2):
    for d in range(4):
        body = [(11 + d * 31 + j) % 200 + 3 for j in range(16)]
        prompts.append(body + [220, 221, (r * 4 + d) % 7 + 2])
res = {}
queue = list(enumerate(prompts))

def feed():
    if not queue:
        return None
    i, ids = queue.pop(0)
    prefix, host_restore = None, None
    m = cache.match(ids)
    if m.length:
        prefix = (m.length, m.block_ids)
    if m.host_payloads:
        n_host = len(m.host_payloads)
        try:
            rt.ensure_free(n_host)
            restore_ids = rt.pool.alloc_tokens(n_host * rt.block)
        except OutOfBlocks:
            cache.host_tier.abandon(n_host)
        else:
            prefix = (m.length + n_host * rt.block,
                      m.block_ids + list(restore_ids))
            host_restore = (restore_ids, m.host_payloads)
    shared = len(prefix[1]) if prefix else 0
    fresh = rt.need_tokens(len(ids), 4) - shared * rt.block
    rt.ensure_free(rt.pool.blocks_for(fresh))
    return SlotRequest(
        ids=ids, max_new=4, sample=SampleConfig(greedy=True), prefix=prefix,
        kv_blocks=rt.pool.alloc_tokens(fresh), host_restore=host_restore,
        on_prefill_blocks=lambda b, c=list(ids): cache.insert(c, b),
        on_done=lambda t, s, i=i: res.__setitem__(i, t))

eng = ContinuousEngine(gen, slots=1, chunk=4, paged=rt)
eng.run(feed)
tier = cache.host_tier
print(json.dumps({"out": [res[i] for i in sorted(res)],
                  "tier": tier is not None,
                  "stats": tier.stats() if tier else {}}))
"""


@pytest.mark.slow
def test_host_tier_env_bisection_subprocess():
    """ACCEPTANCE: TPUSTACK_KV_HOST_TIER_MB=0 constructs NO tier (the
    server's env-driven build) and a fresh-interpreter run is
    byte-identical to the tier-on one, which provably spilled AND
    restored."""
    outs = {}
    for mb in ("0", "8"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0",
                   TPUSTACK_KV_HOST_TIER_MB=mb,
                   TPUSTACK_KV_HOST_TIER_CROSSOVER="0",
                   TPUSTACK_KV_POOL_BLOCKS="6",
                   TPUSTACK_PREFIX_CACHE="1")
        proc = subprocess.run([sys.executable, "-c", _BISECT], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-800:]
        outs[mb] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert outs["0"]["tier"] is False and outs["0"]["stats"] == {}
    assert outs["8"]["tier"] is True
    assert outs["8"]["stats"]["spilled_total"] > 0
    assert outs["8"]["stats"]["restored_total"] > 0
    assert outs["0"]["out"] == outs["8"]["out"]


# ------------------------------------------------------------ chunked prefill
def test_chunked_prefill_byte_identity_and_stats(gen):
    """Chunk on vs off: greedy outputs byte-identical; the long prompt
    reports its chunk waves; the run-stats key is ABSENT with the knob
    off (the perfsig signature bisection contract)."""
    long_p = [(5 + j) % 200 + 3 for j in range(35)]   # spans 2+ chunks
    shorts = [[30 + d, 31, 32, 33, 34] for d in range(4)]
    prompts = [long_p] + shorts
    outs = {}
    for chunk in (0, 16):
        rt = _make_rt(gen, capacity_blocks=16, cache=False)
        results, stats = _run_engine(gen, rt, prompts, max_new=6,
                                     slots=2, admit=False,
                                     prefill_chunk=chunk)
        outs[chunk] = [results[i][0] for i in sorted(results)]
        if chunk:
            assert stats["prefill_chunks"] >= 2
            assert results[0][1]["prefill_chunks"] >= 2
            # retire stats report the ORIGINAL prompt split, not the
            # resume's history-as-prefix view
            assert results[0][1]["prefill_tokens"] == len(long_p)
        else:
            assert "prefill_chunks" not in stats
            assert "prefill_chunks" not in results[0][1]
        assert rt.pool.n_used == 0
    assert outs[0] == outs[16]


def test_chunked_prefill_env_knob_arms_engine(gen, monkeypatch):
    """TPUSTACK_PREFILL_CHUNK_TOKENS arms an engine that was given no
    ``prefill_chunk`` of its own."""
    monkeypatch.setenv("TPUSTACK_PREFILL_CHUNK_TOKENS", "16")
    rt = _make_rt(gen, capacity_blocks=16, cache=False)
    assert ContinuousEngine(gen, slots=1, paged=rt)._chunk_tokens == 16
    monkeypatch.setenv("TPUSTACK_PREFILL_CHUNK_TOKENS", "0")
    assert ContinuousEngine(gen, slots=1, paged=rt)._chunk_tokens == 0


def test_chunked_prefill_with_speculative_byte_identity(gen):
    """The matrix leg the QoS preemption tests don't cover: chunk waves
    interleaving with speculative verify dispatches stay byte-identical
    to the monolithic-prefill spec engine."""
    from tpustack.serving.speculative import SpecConfig

    long_p = [(5 + j) % 200 + 3 for j in range(35)]
    prompts = [long_p, [40, 41, 42, 43, 44]]
    outs = {}
    for chunk in (0, 16):
        rt = _make_rt(gen, capacity_blocks=16, cache=False)
        results, _ = _run_engine(gen, rt, prompts, max_new=6, slots=2,
                                 admit=False, prefill_chunk=chunk,
                                 spec=SpecConfig(tokens=3))
        outs[chunk] = [results[i][0] for i in sorted(results)]
        assert rt.pool.n_used == 0
    assert outs[0] == outs[16]


# ----------------------------------------------------- sanitizer integration
def test_quiesce_catches_broken_tier_conservation(gen):
    rt = _make_rt(gen, capacity_blocks=6, tier_mb=8)
    tier = rt.cache.host_tier
    sanitize.check_kv_quiesce(rt, where="clean")      # no violation
    with tier._lock:
        tier.spilled_total += 3                       # leak 3 spills
    with pytest.raises(SanitizerViolation) as ei:
        sanitize.check_kv_quiesce(rt, where="drain")
    msg = str(ei.value)
    assert "host-tier conservation broken" in msg and "drain" in msg
    with tier._lock:
        tier.spilled_total -= 3
    sanitize.check_kv_quiesce(rt, where="clean again")


def test_quiesce_catches_tier_over_capacity(gen):
    rt = _make_rt(gen, capacity_blocks=6, tier_mb=8)
    tier = rt.cache.host_tier
    with tier._lock:
        tier.capacity_bytes = 0                       # resident > cap
        tier._bytes = 64
        tier.spilled_total += 1
        tier._entries[_FakeNode().uid] = types.SimpleNamespace(
            node=None, payload=None, nbytes=64)
    with pytest.raises(SanitizerViolation) as ei:
        sanitize.check_kv_quiesce(rt, where="drain")
    assert "host-tier over cap" in str(ei.value)


# ------------------------------------------------------------ bench smokes
@pytest.mark.slow
def test_bench_llm_host_tier_smoke():
    """bench_llm --tiny --host-tier: off/on byte-identity, a conserved
    ledger with real spills+restores, and a leak-free teardown — the
    counters the perf-gate scenario commits."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--tiny", "--host-tier", "--requests", "8"],
        env=env, capture_output=True, text=True, timeout=590, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    art = json.loads(proc.stdout.strip().splitlines()[-1])
    st = art["host_tier"]
    assert st["spilled_total"] > 0 and st["restored_total"] > 0
    assert st["spilled_total"] == (st["restored_total"]
                                   + st["expired_total"]
                                   + st["resident_blocks"])
    assert art["signature"]["outputs_identical"] == 1
    assert art["signature"]["leak_check_ok"] == 1
    assert art["tier_on"]["prefix_hit_ratio"] \
        > art["tier_off"]["prefix_hit_ratio"]


@pytest.mark.slow
def test_bench_llm_chunked_prefill_smoke():
    """bench_llm --tiny --chunked-prefill: chunk waves dispatched, the
    off-run clean of them, outputs byte-identical."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--tiny", "--chunked-prefill"],
        env=env, capture_output=True, text=True, timeout=590, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    art = json.loads(proc.stdout.strip().splitlines()[-1])
    assert art["signature"]["prefill.chunks"] > 0
    assert art["signature"]["prefill.off.chunks"] == 0
    assert art["signature"]["outputs_identical"] == 1
