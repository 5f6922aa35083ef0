"""Paged KV substrate (block pool + block tables) — the allocator, the
refcounted block-id radix cache, the paged ContinuousEngine, and the HTTP
server's capacity-true admission.  The acceptance bars: greedy outputs
byte-identical to the solo path (``generate_fused``; engine / HTTP) and
cache-on-vs-off; a prefix hit moves ZERO KV bytes (copy-avoided counter);
out-of-blocks admission answers 429 with a capacity-true Retry-After; and
the pool's free-block count returns to its initial value after a burst
(no leaks), with ``cache_prompt: false`` honoring refcounts (no insert,
no leaked blocks)."""

import asyncio
import os
import subprocess
import sys

import pytest

import jax.numpy as jnp

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.serving.kv_pool import (KVBlockPool, OutOfBlocks,
                                      PagedKVRuntime, PagedPrefixCache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SampleConfig(greedy=True)
BLOCK = 8


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def make_runtime(gen, capacity_blocks=32, block=BLOCK, cache=True):
    return PagedKVRuntime.build(gen.cfg, 2, block=block,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32, prefix_cache=cache)


# ------------------------------------------------------------ the allocator
def test_pool_alloc_refcount_free():
    pool = KVBlockPool(9, 4)  # 8 allocatable
    assert pool.capacity_blocks == 8 and pool.n_free == 8
    assert pool.blocks_for(9) == 3
    ids = pool.alloc_tokens(9)
    assert len(ids) == 3 and 0 not in ids  # block 0 reserved
    assert pool.n_free == 5 and pool.n_used == 3
    pool.incref(ids[:1])
    assert pool.decref(ids) == 2          # shared block survives
    assert pool.refcount(ids[0]) == 1
    assert pool.decref(ids[:1]) == 1
    assert pool.n_free == 8


def test_pool_out_of_blocks_is_atomic():
    pool = KVBlockPool(4, 4)  # 3 allocatable
    with pytest.raises(OutOfBlocks):
        pool.alloc_tokens(20)             # needs 5 > 3
    assert pool.n_free == 3               # nothing half-allocated
    assert not pool.can_admit(20) and pool.can_admit(12)
    with pytest.raises(ValueError):
        pool.decref([1])                  # free block: refcount error


def test_pool_fragmentation_tracks_block_rounding():
    pool = KVBlockPool(9, 8)
    assert pool.fragmentation() == 0.0
    ids = pool.alloc_tokens(9)            # 2 blocks for 9 tokens: 7 slack
    assert pool.fragmentation() == pytest.approx(7 / 16)
    pool.alloc_tokens(8)                  # tight block: slack ratio drops
    assert pool.fragmentation() == pytest.approx(7 / 24)
    pool.decref(ids)
    assert pool.stats()["used_blocks"] == 1


# ------------------------------------------------- the block-id radix cache
def test_paged_cache_match_snaps_and_never_covers_whole_prompt():
    pool = KVBlockPool(17, 4)
    pc = PagedPrefixCache(pool)
    ids = list(range(16))
    blocks = pool.alloc_tokens(16)
    assert pc.insert(ids, blocks) == 16
    m = pc.match(ids)                     # 16 cached, but capped at len-1
    assert m.length == 12 and m.block_ids == blocks[:3]
    assert pool.refcount(blocks[0]) == 3  # alloc + cache + this match
    pool.decref(m.block_ids)
    m2 = pc.match(ids + [99])
    assert m2.length == 16
    pool.decref(m2.block_ids)


def test_paged_cache_insert_idempotent_and_divergent():
    pool = KVBlockPool(33, 4)
    pc = PagedPrefixCache(pool)
    a, b = list(range(16)) + [1, 2, 3, 4], list(range(16)) + [5, 6, 7, 8]
    blocks_a = pool.alloc_tokens(20)
    blocks_b = pool.alloc_tokens(20)
    assert pc.insert(a, blocks_a) == 20
    # b shares the first 4 chunks (already cached → b's copies not
    # recorded, no extra refs) and adds its divergent 5th
    assert pc.insert(b, blocks_b) == 4
    assert pc.entries == 6
    assert pool.refcount(blocks_b[0]) == 1   # only b's own alloc ref
    assert pool.refcount(blocks_a[0]) == 2   # alloc + cache
    # simulate both requests retiring
    pool.decref(blocks_a), pool.decref(blocks_b)
    assert pc.match(a + [0]).length == 20
    assert pc.match(b + [0]).length == 20


def test_paged_cache_evict_blocked_while_referenced():
    """The refcount lifecycle bar: admit → share → evict blocked while a
    'slot' still references the blocks → freed only at refcount 0."""
    pool = KVBlockPool(9, 4)
    evicted = []
    pc = PagedPrefixCache(pool, on_evict=evicted.append)
    ids = list(range(8))
    blocks = pool.alloc_tokens(8)
    pc.insert(ids, blocks)
    pool.decref(blocks)                   # original requester retired
    assert pc.evictable_blocks() == 2
    m = pc.match(ids + [9])               # a sharing slot holds refs now
    assert m.length == 8
    assert pc.evictable_blocks() == 0
    assert pc.evict(10) == 0              # blocked: nothing reclaimable
    assert pc.entries == 2 and pool.n_free == 6
    pool.decref(m.block_ids)              # sharer retires
    assert pc.evict(10) == 2              # now LRU eviction frees them
    assert pool.n_free == 8 and pc.entries == 0
    assert evicted == [2]                 # the exported-counter hook fired


# ------------------------------------------------- the one place a pool is made
@pytest.mark.parametrize("max_seq,asked,slots,pool_blocks,want_block,want_cap", [
    (64, 0, 2, 0, 8, 16),         # default: min(64, max(8, 64 // 8))
    (4096, 0, 8, 0, 64, 512),     # the Deployment's: 8 slots x 4096 / 64
    (256, 0, 3, 0, 32, 24),       # default scales with the context...
    (32, 0, 1, 0, 8, 4),          # ...and never drops below 8
    (64, 24, 4, 10, 1, 10),       # halved 12 -> 6 -> 3 -> 1: none divides
    (64, 16, 2, 0, 16, 8),        # an asked block that divides is kept
    (64, 1000, 2, 0, 64, 2),      # clamped to the context: one block a line
    (96, 64, 2, 5, 32, 5),        # 96 % 64 != 0 -> 32; asked pool size kept
])
@pytest.mark.parametrize("cache", [False, True])
def test_runtime_build_snaps_block_sizes_pool_and_reserves_block0(
        max_seq, asked, slots, pool_blocks, want_block, want_cap, cache):
    """``PagedKVRuntime.build`` owns the block-snapping rule, the default
    pool of ``slots x max_seq / block`` blocks and the reserved block 0."""
    cfg = LlamaConfig.tiny(max_seq=max_seq)
    rt = PagedKVRuntime.build(cfg, slots, block=asked,
                              pool_blocks=pool_blocks, dtype=jnp.float32,
                              prefix_cache=cache)
    assert rt.block == rt.pool.block == want_block
    assert max_seq % rt.block == 0
    assert rt.blocks_per_seq == max_seq // want_block
    assert rt.pool.capacity_blocks == want_cap == rt.pool.n_free
    # block 0 is extra, on the device and in the allocator, and never handed out
    assert rt.pool.n_blocks == want_cap + 1
    assert all(x.shape[0] == want_cap + 1
               for layer in rt.arrays for x in layer.values())
    assert len(rt.arrays) == cfg.n_layers
    assert rt.arrays[0]["k"].dtype == jnp.float32
    ids = rt.pool.alloc_tokens(want_cap * want_block)
    assert sorted(ids) == list(range(1, want_cap + 1))
    rt.pool.decref(ids)
    assert (rt.cache is not None) == cache
    assert rt.cache is None or rt.cache.host_tier is None
    assert rt.stats()["prefix_cache"]["enabled"] == cache


def test_runtime_build_host_tier_needs_a_prefix_cache():
    cfg = LlamaConfig.tiny(max_seq=64)
    rt = PagedKVRuntime.build(cfg, 2, prefix_cache=True, host_tier_mb=1)
    assert rt.cache.host_tier is not None
    assert PagedKVRuntime.build(cfg, 2, host_tier_mb=1).cache is None


def test_default_engine_builds_its_own_pool(gen):
    """An engine given no runtime holds a pool of ``slots x max_seq``
    tokens, without a prefix cache, and serves through it."""
    eng = ContinuousEngine(gen, slots=3, chunk=4)
    rt = eng.paged
    assert rt.pool.capacity_blocks * rt.block == 3 * gen.cfg.max_seq
    assert rt.cache is None and rt.max_seq == gen.cfg.max_seq
    assert rt.arrays[0]["k"].dtype == gen.cache_dtype
    res, stats = _run(eng, [{"ids": [5, 6, 7], "max_new": 6}])
    assert stats["decode_kernel"] == "gather"  # CPU: 'auto' picks gather
    assert stats["kernel_gather_dispatches"] >= 1
    assert res[0][0] == gen.generate_fused([5, 6, 7], max_new_tokens=6,
                                           sample=GREEDY, chunk=4)[0]
    assert rt.pool.n_used == 0  # retired: every block back
    with pytest.raises(ValueError, match="max_seq"):
        ContinuousEngine(gen, slots=2, paged=PagedKVRuntime.build(
            LlamaConfig.tiny(max_seq=32), 2))


# ------------------------------------------------------- engine-level parity
def _run(engine, requests):
    results = {}
    queue = [SlotRequest(ids=r["ids"], max_new=r["max_new"],
                         sample=r.get("sample", GREEDY),
                         seed=r.get("seed"),
                         on_done=(lambda t, s, i=i:
                                  results.__setitem__(i, (t, s))))
             for i, r in enumerate(requests)]
    stats = engine.run(lambda: queue.pop(0) if queue else None)
    return results, stats


def test_engine_paged_matches_dense_and_solo(gen):
    """The tentpole bar: greedy outputs byte-identical to the solo path,
    on the pool an engine builds for itself and on one handed in,
    including slot reuse (more requests than slots) and mixed lengths."""
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [20],
               [30 + i for i in range(12)], [40, 41]]
    reqs = [{"ids": p, "max_new": 8} for p in prompts]
    solo = [gen.generate_fused(p, max_new_tokens=8, sample=GREEDY,
                               stop_tokens=(2,), chunk=4)[0] for p in prompts]
    own, _ = _run(ContinuousEngine(gen, slots=2, chunk=4,
                                   stop_tokens=(2,)), reqs)
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    paged, _ = _run(ContinuousEngine(gen, slots=2, chunk=4, stop_tokens=(2,),
                                     paged=rt), reqs)
    for i, s in enumerate(solo):
        assert own[i][0] == s, f"own-pool row {i} diverged from solo"
        assert paged[i][0] == s, f"paged row {i} diverged from solo"
    assert rt.pool.n_free == free0  # burst leak check (no cache inserts)


def test_engine_paged_seeded_sampling_parity(gen):
    """Per-slot PRNG streams do not depend on where the KV rests: a
    seeded sampled request draws the same tokens over pools of different
    block size and capacity, and its greedy peer the solo path's."""
    reqs = [{"ids": [5, 6, 7, 8], "max_new": 8, "seed": 1234,
             "sample": SampleConfig(temperature=1.2, top_k=8)},
            {"ids": [9, 10], "max_new": 6}]
    own, _ = _run(ContinuousEngine(gen, slots=2, chunk=4), reqs)
    paged, _ = _run(ContinuousEngine(
        gen, slots=2, chunk=4,
        paged=make_runtime(gen, capacity_blocks=12, block=16)), reqs)
    assert paged[0][0] == own[0][0]
    assert paged[1][0] == own[1][0] == gen.generate_fused(
        [9, 10], max_new_tokens=6, sample=GREEDY, chunk=4)[0]


def test_engine_paged_prefix_sharing_lifecycle(gen):
    """Zero-copy reuse end to end: miss inserts block ids, hits share them
    (refcount up, suffix-only prefill), eviction is blocked mid-decode,
    and the pool returns to cache-only residency after the burst."""
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    shared = list(range(5, 5 + 24))
    prompts = [shared + [40 + i] for i in range(4)]
    solo = [gen.generate_fused(p, max_new_tokens=8, sample=GREEDY,
                               chunk=4)[0] for p in prompts]

    evict_mid = {"freed": None}
    results = {}

    def request(i, p):
        m = rt.cache.match(p)

        def on_tokens(_):
            if i == 1 and evict_mid["freed"] is None:
                # mid-decode of the first SHARING request: the shared
                # blocks are refcount-2 → eviction must reclaim nothing
                evict_mid["freed"] = rt.cache.evict(100)

        return SlotRequest(
            ids=p, max_new=8, sample=GREEDY,
            prefix=(m.length, m.block_ids) if m.length else None,
            on_tokens=on_tokens,
            on_prefill_blocks=lambda bids, p=list(p): rt.cache.insert(p, bids),
            on_done=lambda t, s, i=i: results.__setitem__(i, (t, s)))

    for i, p in enumerate(prompts):
        q = [request(i, p)]
        ContinuousEngine(gen, slots=2, chunk=4, paged=rt).run(
            lambda: q.pop(0) if q else None)

    for i in range(4):
        assert results[i][0] == solo[i], f"row {i} diverged"
    assert results[0][1]["cached_tokens"] == 0
    for i in (1, 2, 3):
        assert results[i][1]["cached_tokens"] == 24  # 3 shared blocks
        assert results[i][1]["prefill_tokens"] == 1
    assert evict_mid["freed"] == 0  # evict-blocked-while-referenced
    st = rt.cache.stats()
    assert st["hits"] == 3 and st["misses"] == 1
    # leak check: only the cache's 3 shared blocks remain resident
    assert rt.pool.n_used == 3 == rt.cache.evictable_blocks()
    rt.cache.evict(100)
    assert rt.pool.n_free == free0


def test_engine_paged_long_prompt_and_big_suffix_paths():
    """The two paged admission paths tiny shapes never reach with the
    production thresholds: (a) a cold admission that walks its bucket in
    chunks inside the fused program (bucket > ADMIT_CHUNK; PR 34 — the
    separate long-prompt dispatches above PREFILL_CHUNK went into it),
    (b) big-suffix prefix hit via row gather + the traced-offset chunk
    loop (past MASKED_PREFILL_MAX).  Shrinking the instance thresholds
    forces both; outputs must still match the solo path bit-for-bit."""
    g = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
    # a 40-token prompt → bucket 64 → four chunks of capacity, three run
    g.PREFILL_CHUNK = g.ADMIT_CHUNK = 16
    g.MASKED_PREFILL_MAX = 1  # every suffix prefill → gather + chunk loop
    rt = make_runtime(g)
    shared = list(range(5, 5 + 24))
    long_p = list(range(1, 41))
    hit_p = shared + [50, 51]
    solo_long = g.generate_fused(long_p, max_new_tokens=6, sample=GREEDY,
                                 chunk=4)[0]
    solo_hit = g.generate_fused(hit_p, max_new_tokens=6, sample=GREEDY,
                                chunk=4)[0]
    results = {}

    def request(i, p):
        m = rt.cache.match(p)
        return SlotRequest(
            ids=p, max_new=6, sample=GREEDY,
            prefix=(m.length, m.block_ids) if m.length else None,
            on_prefill_blocks=lambda b, p=list(p): rt.cache.insert(p, b),
            on_done=lambda t, s, i=i: results.__setitem__(i, (t, s)))

    for i, p in enumerate([long_p, shared + [40], hit_p]):
        q = [request(i, p)]
        ContinuousEngine(g, slots=2, chunk=4, paged=rt).run(
            lambda: q.pop(0) if q else None)
    assert results[0][0] == solo_long     # long-prompt paged splice
    assert results[2][0] == solo_hit      # big-suffix zero-copy warm start
    assert results[2][1]["cached_tokens"] == 24
    assert rt.pool.n_used == rt.cache.evictable_blocks()  # no leaks


def test_engine_paged_out_of_blocks_error_retire(gen):
    """An engine-level allocation shortfall error-retires the request
    (on_done with an error) instead of crashing the run or leaking."""
    rt = make_runtime(gen, capacity_blocks=2, cache=False)  # 16 tokens
    res = {}
    reqs = [{"ids": [5, 6, 7], "max_new": 40}]  # needs 43 tokens > 16
    queue = [SlotRequest(ids=r["ids"], max_new=r["max_new"], sample=GREEDY,
                         on_done=lambda t, s: res.update(t=t, s=s))
             for r in reqs]
    ContinuousEngine(gen, slots=2, chunk=4, paged=rt).run(
        lambda: queue.pop(0) if queue else None)
    assert res["t"] is None and "blocks" in res["s"]["error"]
    assert rt.pool.n_free == 2


# ------------------------------------------------------------- HTTP server
def _post_all(server, payloads, collect_status=False):
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            outs = []
            for body in payloads:
                r = await client.post("/completion", json=body)
                if collect_status:
                    outs.append((r.status, dict(r.headers),
                                 await r.json()))
                else:
                    assert r.status == 200, await r.text()
                    outs.append((await r.json())["content"])
            props = await (await client.get("/props")).json()
            metrics = await (await client.get("/metrics")).text()
            return outs, props, metrics
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(scenario())


def _server(gen, max_batch=4, **kw):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs import Registry
    from tpustack.serving.llm_server import LLMServer

    reg = kw.pop("registry", None) or Registry()
    return LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                     max_batch=max_batch, registry=reg, **kw), reg


def test_server_paged_vs_dense_and_cache_onoff_parity(gen):
    """The HTTP bar: greedy completions byte-identical across the engine
    (cache on), the engine (cache off), and the solo route (one dense
    line, ``generate_fused``)."""
    prompts = [{"prompt": "shared system preamble for paged tests! " + t,
                "n_predict": 6, "temperature": 0}
               for t in ("q1", "q2", "q1")]
    dense, _ = _server(gen, max_batch=1, prefix_cache=None)
    assert dense.paged is None
    outs_dense, props_dense, _ = _post_all(dense, prompts)
    assert props_dense["paged_kv"] == {"enabled": False,
                                       "dense_fallback": True}

    rt_off = make_runtime(gen, cache=False)
    paged_off, _ = _server(gen, paged=rt_off)
    outs_off, props_off, _ = _post_all(paged_off, prompts)
    assert outs_off == outs_dense

    rt = make_runtime(gen)
    paged_on, reg = _server(gen, paged=rt)
    outs_on, props_on, metrics = _post_all(paged_on, prompts)
    assert outs_on == outs_dense  # byte-identical greedy completions

    pk = props_on["paged_kv"]
    assert pk["enabled"] and not pk["dense_fallback"]
    assert pk["block_tokens"] == BLOCK and pk["pool_blocks"] == 32
    assert {"free_blocks", "used_blocks", "utilization",
            "fragmentation"} <= set(pk)
    pc = props_on["prefix_cache"]
    assert pc["enabled"] and pc["paged"] and pc["hits"] >= 2
    # zero-copy assertion: every hit/insert token was pointer-shared, and
    # the counter proves no dense copy path ran
    avoided = reg.get_sample_value(
        "tpustack_llm_kv_copy_avoided_tokens_total")
    assert avoided == pc["cached_tokens_served"] + pc["inserted_tokens"] > 0
    assert "tpustack_llm_kv_free_blocks" in metrics
    assert "tpustack_llm_kv_used_blocks" in metrics
    assert "tpustack_llm_kv_block_fragmentation_ratio" in metrics


def test_server_cache_prompt_false_no_insert_no_leak(gen):
    """`cache_prompt: false` bypasses the paged trie entirely — no lookup,
    no insert — and every block the request held returns to the pool."""
    rt = make_runtime(gen)
    server, _ = _server(gen, paged=rt)
    body = {"prompt": "another shared preamble for paged optout tests",
            "n_predict": 4, "temperature": 0, "cache_prompt": False}
    free0 = rt.pool.n_free
    _post_all(server, [body, body])
    assert rt.cache.lookups == 0 and rt.cache.entries == 0
    assert rt.pool.n_free == free0  # no leaked blocks


def test_server_out_of_blocks_429_capacity_true(gen):
    """Out-of-blocks admission answers 429 + Retry-After while the pool is
    held, 200 once capacity frees — and a request that could NEVER fit is
    a 400, not a retry loop."""
    rt = make_runtime(gen, capacity_blocks=6)  # 48 tokens
    server, reg = _server(gen, paged=rt)
    held = rt.pool.alloc_tokens(48)  # simulate in-flight occupancy
    body = {"prompt": "hello paged world", "n_predict": 8, "temperature": 0}
    outs, _, _ = _post_all(server, [body], collect_status=True)
    status, headers, payload = outs[0]
    assert status == 429
    assert int(headers["Retry-After"]) >= 1
    assert "KV blocks" in payload["error"]
    assert reg.get_sample_value(
        "tpustack_requests_shed_total",
        {"server": "llm", "reason": "out_of_kv_blocks"}) == 1
    rt.pool.decref(held)
    outs, _, _ = _post_all(server, [body], collect_status=True)
    assert outs[0][0] == 200
    # a request larger than the whole pool: permanent 400
    big = {"prompt": "x" * 60, "n_predict": 64, "temperature": 0}
    rt2 = make_runtime(gen, capacity_blocks=2, cache=False)
    server2, _ = _server(gen, paged=rt2)
    outs, _, _ = _post_all(server2, [big], collect_status=True)
    assert outs[0][0] == 400
    assert "pool holds" in outs[0][2]["error"]


def test_server_burst_leak_check(gen):
    """The acceptance leak bar: after a burst of mixed hit/miss requests
    the free-block count returns to initial minus ONLY the cache-resident
    (evictable) blocks."""
    rt = make_runtime(gen)
    server, _ = _server(gen, paged=rt)
    free0 = rt.pool.n_free
    bodies = [{"prompt": "the same long shared preamble here! " + t,
               "n_predict": 5, "temperature": 0}
              for t in ("a", "b", "c", "d", "e")]
    _post_all(server, bodies)
    resident = rt.cache.evictable_blocks()
    assert rt.pool.n_used == resident > 0
    rt.cache.evict(100)
    assert rt.pool.n_free == free0


def test_server_refuses_a_host_prefix_cache_beside_the_engine(gen):
    """A host ``PrefixCache`` serves the ``LLM_MAX_BATCH=1`` solo route
    only: handed to a server that runs the engine it is an error at
    construction (it used to switch engines silently)."""
    from tpustack.serving.prefix_cache import PrefixCache

    pc = PrefixCache(chunk_tokens=8, capacity_bytes=1 << 20)
    with pytest.raises(ValueError, match="PrefixCache"):
        _server(gen, prefix_cache=pc)
    solo, _ = _server(gen, max_batch=1, prefix_cache=pc)
    assert solo.paged is None and solo.prefix_cache is pc


def test_build_paged_env_knobs(gen, monkeypatch):
    from tpustack.serving.llm_server import LLMServer

    assert LLMServer._build_paged(gen, 1) is None  # solo stays dense
    monkeypatch.setenv("TPUSTACK_KV_BLOCK", "24")  # 64 % 24 != 0 → snaps down
    monkeypatch.setenv("TPUSTACK_KV_POOL_BLOCKS", "10")
    rt = LLMServer._build_paged(gen, 4)
    assert gen.cfg.max_seq % rt.block == 0
    assert rt.pool.capacity_blocks == 10
    monkeypatch.setenv("TPUSTACK_PREFIX_CACHE", "0")
    rt = LLMServer._build_paged(gen, 4)
    assert rt.cache is None


def test_server_spec_paged_burst_leak_check(gen):
    """The PR 7 extension of the burst leak bar: speculation × paged KV —
    bursts of repetitive (drafting) prompts with a mid-stream
    cancellation mixed in leave no leaked or double-freed blocks; the
    verify step's rejected-draft KV never lands, so residency afterwards
    is exactly the cache's evictable blocks."""
    from tpustack.serving.speculative import SpecConfig

    rt = make_runtime(gen)
    server, reg = _server(gen, paged=rt, spec=SpecConfig(tokens=4))
    server.chunk = 4  # tiny-shape wave cadence (prod chunk covers a whole
    # tiny budget in one pipelined fill, leaving speculation nothing)
    free0 = rt.pool.n_free
    bodies = [{"prompt": "abcabcabcabcabcabcabcabcabc" + t,
               "n_predict": 24, "temperature": 0}
              for t in ("a", "b", "a", "c", "b")]

    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            for body in bodies:
                r = await client.post("/completion", json=body)
                assert r.status == 200
            # mid-stream cancellation: read two SSE events then drop the
            # connection — the engine notices at the next wave boundary
            r = await client.post("/completion", json=dict(
                bodies[0], n_predict=40, stream=True))
            assert r.status == 200
            n = 0
            async for _ in r.content:
                n += 1
                if n >= 2:
                    break
            r.close()
            await asyncio.sleep(0.3)  # let the cancel land at a boundary
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())
    # speculation actually happened on this repetitive traffic
    assert reg.get_sample_value(
        "tpustack_llm_spec_drafted_tokens_total") > 0
    # every non-cache block returned: used == evictable (cache-held only)
    resident = rt.cache.evictable_blocks()
    assert rt.pool.n_used == resident
    rt.cache.evict(100)
    assert rt.pool.n_free == free0


def test_bench_paged_tiny_smoke_cli():
    """Shell ``tools/bench_llm.py --paged --tiny`` — the CPU-runnable
    proof behind the acceptance bar: paged admitted concurrency at the
    mid footprint strictly exceeds the dense slot cap, greedy outputs
    identical, pool leak check green."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--paged", "--tiny"],
        capture_output=True, text=True, timeout=420,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["outputs_identical"] is True
    assert out["leak_check_ok"] is True
    assert out["value"] > out["dense_slot_cap"]
    mid = out["sweep"][len(out["sweep"]) // 2]
    assert (mid["paged"]["admitted_concurrency"]
            > mid["dense"]["admitted_concurrency"])
