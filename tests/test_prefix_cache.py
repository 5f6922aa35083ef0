"""Cross-request prefix KV cache (radix reuse) — the solo route's host
store itself, the Generator's extract/restore/suffix-prefill surgery, and
end-to-end parity: greedy outputs must be IDENTICAL with the cache on vs
off, across the solo path, the continuous engine and the HTTP server (both
through the pool's block trie, ``PagedPrefixCache``).  The ISSUE's acceptance
bars: cache-warm requests skip ≥50% of prefill tokens; the cache-off path
is the unchanged pre-cache behavior; memory is bounded (LRU, byte cap)."""

import asyncio

import numpy as np
import pytest

import jax.numpy as jnp

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.serving.kv_pool import PagedKVRuntime
from tpustack.serving.prefix_cache import PrefixCache

GREEDY = SampleConfig(greedy=True)


# ---------------------------------------------------------- the radix store
def _seg(n, val, layers=2, kvh=2, hd=4):
    return [{"k": np.full((n, kvh, hd), val + li, np.float32),
             "v": np.full((n, kvh, hd), val - li, np.float32)}
            for li in range(layers)]


def test_store_miss_then_hit_snapped():
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=1 << 20)
    ids = list(range(20))
    assert pc.match(ids).length == 0
    assert pc.insert(ids, 0, _seg(16, 1.0)) == 16
    m = pc.match(ids)
    assert m.length == 16  # snapped: chunks fully inside [0, 19]
    assert m.kv[0]["k"].shape == (16, 2, 4)
    # assembled segments preserve per-chunk content order
    assert float(m.kv[0]["k"][0, 0, 0]) == 1.0
    assert m.key is not None


def test_store_never_matches_whole_prompt():
    """At least one token must remain to prefill (the engine samples from
    the last real token's logits)."""
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=1 << 20)
    ids = list(range(16))
    pc.insert(ids, 0, _seg(16, 1.0))
    assert pc.match(ids).length == 12  # not 16, though 16 is cached
    assert pc.match(ids + [99]).length == 16


def test_store_insert_idempotent_and_divergent_branches():
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=1 << 20)
    a = list(range(16)) + [1, 2, 3, 4]
    b = list(range(16)) + [5, 6, 7, 8]
    assert pc.insert(a, 0, _seg(16, 1.0)) == 16
    assert pc.insert(b, 0, _seg(16, 1.0)) == 0  # same chunks: no new bytes
    before = pc.bytes
    # extend both with their divergent 4th chunk
    assert pc.insert(a, 16, _seg(4, 2.0)) == 4
    assert pc.insert(b, 16, _seg(4, 3.0)) == 4
    assert pc.bytes > before
    assert pc.match(a + [0]).length == 20
    assert pc.match(b + [0]).length == 20
    # the two branches kept distinct KV
    assert float(pc.match(a + [0]).kv[0]["k"][16, 0, 0]) == 2.0
    assert float(pc.match(b + [0]).kv[0]["k"][16, 0, 0]) == 3.0


def test_store_byte_accounting_and_lru_eviction():
    one_chunk = sum(a.nbytes for layer in _seg(4, 0) for a in layer.values())
    evicted = []
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=3 * one_chunk,
                     on_evict=evicted.append)
    pc.insert(list(range(8)), 0, _seg(8, 1.0))     # 2 chunks
    assert pc.bytes == 2 * one_chunk and pc.entries == 2
    pc.match(list(range(8)) + [0])                  # touch path A (LRU-newer)
    pc.insert([50, 51, 52, 53, 60, 61, 62, 63], 0, _seg(8, 2.0))  # 4 chunks
    # over cap → LRU leaves evicted until bytes <= cap
    assert pc.bytes <= 3 * one_chunk
    assert pc.entries == 3
    assert pc.evictions == 1 and evicted == [1]
    # path A was touched more recently than path B's first chunk... whatever
    # survived, accounting must be exact
    assert pc.bytes == pc.entries * one_chunk


def test_store_insert_requires_alignment_and_parent_path():
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=1 << 20)
    with pytest.raises(ValueError):
        pc.insert(list(range(10)), 0, _seg(6, 1.0))  # unaligned length
    with pytest.raises(ValueError):
        pc.insert(list(range(10)), 2, _seg(4, 1.0))  # unaligned start
    with pytest.raises(ValueError):
        pc.insert(list(range(6)), 4, _seg(4, 1.0))   # exceeds prompt
    # parent path [0, 4) not cached → insert at 4 attaches nothing
    assert pc.insert(list(range(8)), 4, _seg(4, 1.0)) == 0
    assert pc.entries == 0


def test_store_stats_shape():
    pc = PrefixCache(chunk_tokens=4, capacity_bytes=1 << 20)
    pc.insert(list(range(8)), 0, _seg(8, 1.0))
    pc.match(list(range(8)) + [9])
    st = pc.stats()
    assert st["enabled"] is True
    assert st["chunk_tokens"] == 4 and st["entries"] == 2
    assert st["hits"] == 1 and st["hit_rate"] > 0
    assert st["resident_bytes"] == pc.bytes


# ------------------------------------------------- generator-level surgery
@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def test_solo_prefix_restore_matches_cold(gen):
    """generate / generate_fused with a restored prefix produce exactly the
    cold outputs, and stats account cached vs prefilled tokens."""
    shared = list(range(5, 5 + 24))
    p1, p2 = shared + [40, 41, 42], shared + [50, 51]
    store = {}
    cold1, st1 = gen.generate_fused(
        p1, max_new_tokens=8, sample=GREEDY, chunk=4,
        kv_extract=(0, 24), on_prefill_kv=lambda kv: store.update(kv=kv))
    assert st1["cached_tokens"] == 0 and st1["prefill_tokens"] == len(p1)
    kv = store["kv"]
    assert kv[0]["k"].shape[0] == 24

    cold2, _ = gen.generate_fused(p2, max_new_tokens=8, sample=GREEDY, chunk=4)
    warm2, st2 = gen.generate_fused(p2, max_new_tokens=8, sample=GREEDY,
                                    chunk=4, prefix=(24, kv))
    assert warm2 == cold2
    assert st2["cached_tokens"] == 24 and st2["prefill_tokens"] == 2
    warm2b, _ = gen.generate(p2, max_new_tokens=8, sample=GREEDY,
                             prefix=(24, kv))
    assert warm2b == cold2


def test_solo_prefix_sampled_seeded_matches_cold(gen):
    """Prefix reuse is sampling-agnostic: a seeded non-greedy request is
    reproducible warm vs cold (same logits → same draws)."""
    shared = list(range(5, 5 + 24))
    p = shared + [33, 34]
    store = {}
    gen.generate_fused(p, max_new_tokens=6, sample=GREEDY, chunk=4,
                       kv_extract=(0, 24),
                       on_prefill_kv=lambda kv: store.update(kv=kv))
    sample = SampleConfig(temperature=0.9, top_k=12)
    cold, _ = gen.generate_fused(p, max_new_tokens=6, sample=sample, seed=7,
                                 chunk=4)
    warm, _ = gen.generate_fused(p, max_new_tokens=6, sample=sample, seed=7,
                                 chunk=4, prefix=(24, store["kv"]))
    assert warm == cold


def test_prefix_rejects_degenerate_cover(gen):
    with pytest.raises(ValueError):
        gen.generate_fused([1, 2, 3, 4], max_new_tokens=4, sample=GREEDY,
                           prefix=(4, _seg(4, 0.0)))


# ------------------------------------------------------- continuous engine
def _pool(gen):
    """A pool with a block trie at the old store's granularity (8)."""
    return PagedKVRuntime.build(gen.cfg, 2, block=8, pool_blocks=32,
                                dtype=gen.cache_dtype, prefix_cache=True)


def _server_style_request(rt, ids, i, results, max_new=8):
    """Wire a SlotRequest the way llm_server does: lookup before admission
    (a hit's shared blocks ride ``prefix``), insert the prompt's full
    blocks once the engine says prefill has landed."""
    m = rt.cache.match(ids)
    return SlotRequest(
        ids=ids, max_new=max_new, sample=GREEDY,
        prefix=(m.length, m.block_ids) if m.length else None,
        on_prefill_blocks=(lambda bids, ids=list(ids):
                           rt.cache.insert(ids, bids)),
        on_done=lambda t, s, i=i: results.__setitem__(i, (t, s)))


def test_engine_prefix_parity_and_stats(gen):
    shared = list(range(5, 5 + 24))
    prompts = [shared + [40 + i] for i in range(4)]

    cold = {}
    q = [SlotRequest(ids=p, max_new=8, sample=GREEDY,
                     on_done=lambda t, s, i=i: cold.__setitem__(i, (t, s)))
         for i, p in enumerate(prompts)]
    ContinuousEngine(gen, slots=2, chunk=4).run(
        lambda: q.pop(0) if q else None)

    rt = _pool(gen)
    warm = {}
    for i, p in enumerate(prompts):
        q2 = [_server_style_request(rt, p, i, warm)]
        ContinuousEngine(gen, slots=2, chunk=4, paged=rt).run(
            lambda: q2.pop(0) if q2 else None)

    for i in range(4):
        assert warm[i][0] == cold[i][0], f"row {i} diverged"
        assert warm[i][0] == gen.generate_fused(
            prompts[i], max_new_tokens=8, sample=GREEDY, chunk=4)[0]
    assert warm[0][1]["cached_tokens"] == 0
    for i in (1, 2, 3):
        assert warm[i][1]["cached_tokens"] == 24
        assert warm[i][1]["prefill_tokens"] == 1
    st = rt.cache.stats()
    assert st["hits"] == 3 and st["misses"] == 1
    # acceptance bar: ≥50% of prefill tokens skipped on cache-warm requests
    skipped = sum(warm[i][1]["cached_tokens"] for i in (1, 2, 3))
    total = sum(len(prompts[i]) for i in (1, 2, 3))
    assert skipped / total >= 0.5


def test_engine_prefix_hits_mixed_with_misses_in_one_wave(gen):
    """A wave mixing a prefix hit with plain misses admits both paths in
    one run and every row still matches its solo output."""
    shared = list(range(5, 5 + 24))
    hit_p = shared + [41]
    miss_p = [9, 10, 11]
    rt = _pool(gen)
    seed_res = {}
    q0 = [_server_style_request(rt, shared + [40], 0, seed_res)]
    ContinuousEngine(gen, slots=1, chunk=4, paged=rt).run(
        lambda: q0.pop(0) if q0 else None)
    assert rt.cache.entries > 0

    solo_hit = gen.generate_fused(hit_p, max_new_tokens=8, sample=GREEDY,
                                  chunk=4)[0]
    solo_miss = gen.generate_fused(miss_p, max_new_tokens=8, sample=GREEDY,
                                   chunk=4)[0]
    res = {}
    q = [_server_style_request(rt, hit_p, "hit", res),
         _server_style_request(rt, miss_p, "miss", res)]
    ContinuousEngine(gen, slots=2, chunk=4, paged=rt).run(
        lambda: q.pop(0) if q else None)
    assert res["hit"][0] == solo_hit
    assert res["miss"][0] == solo_miss
    assert res["hit"][1]["cached_tokens"] == 24
    assert res["miss"][1]["cached_tokens"] == 0


def test_engine_prefix_with_int8_kv_cache():
    """The store/restore path is layout-generic: int8 KV caches carry
    their per-vector scales through extract → host → restore on the solo
    route, and through shared pool blocks (scale planes included) on the
    engine."""
    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64), kv_quant="int8")
    g = Generator(cfg, dtype=jnp.float32, seed=5)
    shared = list(range(5, 5 + 16))
    p1, p2 = shared + [40], shared + [50]
    store = {}
    g.generate_fused(p1, max_new_tokens=6, sample=GREEDY, chunk=4,
                     kv_extract=(0, 16),
                     on_prefill_kv=lambda kv: store.update(kv=kv))
    assert {"k", "v", "k_scale", "v_scale"} <= set(store["kv"][0])
    cold, _ = g.generate_fused(p2, max_new_tokens=6, sample=GREEDY, chunk=4)
    warm, st = g.generate_fused(p2, max_new_tokens=6, sample=GREEDY, chunk=4,
                                prefix=(16, store["kv"]))
    assert warm == cold and st["cached_tokens"] == 16
    rt, res = _pool(g), {}
    assert {"k", "v", "k_scale", "v_scale"} <= set(rt.arrays[0])
    for i, p in enumerate((p1, p2)):
        q = [_server_style_request(rt, p, i, res, max_new=6)]
        ContinuousEngine(g, slots=1, chunk=4, paged=rt).run(
            lambda: q.pop(0) if q else None)
    assert res[1][0] == cold and res[1][1]["cached_tokens"] == 16


# ------------------------------------------------------------- HTTP server
def _post_all(server, prompts, n_predict=6):
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            outs = []
            for p in prompts:
                r = await client.post("/completion", json={
                    "prompt": p, "n_predict": n_predict, "temperature": 0})
                assert r.status == 200, await r.text()
                outs.append((await r.json())["content"])
            props = await (await client.get("/props")).json()
            metrics = await (await client.get("/metrics")).text()
            return outs, props, metrics
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(scenario())


def test_server_cache_on_off_parity_props_and_metrics(gen):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs import Registry
    from tpustack.serving.llm_server import LLMServer

    prompts = ["shared system preamble used by every request! " + t
               for t in ("q1", "q2", "q1")]
    off = LLMServer(generator=gen, tokenizer=ByteTokenizer(512), max_batch=4,
                    registry=Registry(), prefix_cache=None)
    outs_off, props_off, _ = _post_all(off, prompts)
    assert props_off["prefix_cache"] == {"enabled": False}

    on = LLMServer(generator=gen, tokenizer=ByteTokenizer(512), max_batch=4,
                   registry=Registry(), paged=_pool(gen))
    outs_on, props_on, metrics = _post_all(on, prompts)
    assert outs_on == outs_off  # bit-identical greedy completions
    p = props_on["prefix_cache"]
    assert p["enabled"] and p["block_tokens"] == 8
    assert p["hits"] >= 2 and p["entries"] > 0 and p["hit_rate"] > 0
    # catalog metrics moved: lookups counted
    assert 'tpustack_llm_prefix_cache_lookups_total{result="hit"} 2' in metrics
    assert ('tpustack_llm_prefix_cache_lookups_total{result="miss"} 1'
            in metrics)
    # the host store serves only the solo route: handing one to a server
    # that runs the engine is an error, not a silent switch of engines
    with pytest.raises(ValueError, match="PrefixCache"):
        LLMServer(generator=gen, tokenizer=ByteTokenizer(512), max_batch=4,
                  registry=Registry(),
                  prefix_cache=PrefixCache(chunk_tokens=8,
                                           capacity_bytes=1 << 22))


def test_server_cache_prompt_opt_out(gen):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs import Registry
    from tpustack.serving.llm_server import LLMServer

    rt = _pool(gen)
    pc = rt.cache
    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       max_batch=4, registry=Registry(), paged=rt)
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            for _ in range(2):
                r = await client.post("/completion", json={
                    "prompt": "another shared preamble for optout tests",
                    "n_predict": 4, "temperature": 0,
                    "cache_prompt": False})
                assert r.status == 200
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())
    assert pc.lookups == 0 and pc.entries == 0  # fully bypassed


def test_server_env_knobs(monkeypatch):
    from tpustack.serving.llm_server import LLMServer

    monkeypatch.setenv("TPUSTACK_PREFIX_CACHE", "0")
    assert LLMServer._build_prefix_cache() is None
    monkeypatch.setenv("TPUSTACK_PREFIX_CACHE", "1")
    monkeypatch.setenv("TPUSTACK_PREFIX_CACHE_MB", "64")
    monkeypatch.setenv("TPUSTACK_PREFIX_CACHE_CHUNK", "128")
    pc = LLMServer._build_prefix_cache()
    assert pc.chunk == 128
    assert pc.capacity_bytes == 64 * 1024 * 1024
