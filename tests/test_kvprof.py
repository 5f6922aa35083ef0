"""KV working-set observatory (tpustack/obs/kvprof.py).

The contract under test, layer by layer:

- **estimator accuracy** — the SHARDS-sampled miss-ratio curve's 1x
  point must track the hit rate the real ``PagedPrefixCache`` actually
  measured on the same seeded Zipf trace, and its 2x counterfactual
  must predict what a genuinely doubled pool then measures;
- **attribution is accounting** — per-tenant working sets partition the
  global sample (sum equals the whole, ownership follows the last
  toucher);
- **calibration** — a paged 429's predicted block-release ETA is scored
  against the observed release wall;
- **wiring** — ``GET /debug/kvcache``, the scrape-time gauges, the
  warm/cold eviction split, and ``tools/kv_report.py --tiny``;
- **bisection** — ``TPUSTACK_KVPROF_RATE=0`` is byte-identical to the
  profiler-on server (same completions, same prefix-cache and recompile
  signatures, no kvprof series minted), proven across subprocesses.
"""

import json
import math
import os
import random
import subprocess
import sys
import time

import pytest

import jax.numpy as jnp

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_generate import Generator
from tpustack.obs import Registry
from tpustack.obs import accounting as obs_accounting
from tpustack.obs.kvprof import (CAPACITY_SCALES, KVProfiler, chunk_hashes,
                                 from_env)
from tpustack.serving.kv_pool import (KVBlockPool, OutOfBlocks,
                                      PagedKVRuntime, PagedPrefixCache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 4


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


# ------------------------------------------------------------ chunk keys
def test_chunk_hashes_prefix_property_and_cap():
    ids = list(range(1, 14))  # 13 tokens -> (13-1)//4 = 3 complete chunks
    keys = chunk_hashes(ids, BLOCK)
    assert len(keys) == 3
    # rolling hash: a shared prefix shares its leading chunk keys and
    # diverges exactly where the tokens do
    other = ids[:8] + [99] * 5
    keys2 = chunk_hashes(other, BLOCK)
    assert keys2[:2] == keys[:2] and keys2[2] != keys[2]
    # the cap mirrors PagedPrefixCache.match: a prompt of exactly one
    # block has NO cacheable whole block (the last token never caches)
    assert chunk_hashes(list(range(BLOCK)), BLOCK) == []
    assert chunk_hashes([], BLOCK) == []
    # stable across calls (FNV, not Python's salted hash)
    assert chunk_hashes(ids, BLOCK) == keys


# ----------------------------------------------------- the MRC estimator
def _zipf_trace(n_items=400, n_access=4000, alpha=0.9, seed=7):
    """Seeded Zipf-popularity accesses over distinct one-chunk prompts
    (BLOCK+1 tokens: exactly one cacheable whole block each)."""
    rng = random.Random(seed)
    prompts = []
    for i in range(n_items):
        base = (31 * i + 1) % 499  # injective for i < 499 (gcd(31,499)=1)
        prompts.append([(base + j) % 499 + 1 for j in range(BLOCK + 1)])
    weights = [1.0 / (i + 1) ** alpha for i in range(n_items)]
    picks = rng.choices(range(n_items), weights=weights, k=n_access)
    return [prompts[i] for i in picks]


def _serve_trace(trace, capacity_blocks, rate):
    """The serving loop in miniature: match -> alloc (evict on pressure)
    -> insert -> release, against a REAL pool + trie with a profiler
    attached.  Returns (cache, profiler)."""
    pool = KVBlockPool(capacity_blocks + 1, BLOCK)
    cache = PagedPrefixCache(pool)
    prof = KVProfiler(pool, cache=cache, rate=rate).attach()
    for ids in trace:
        m = cache.match(ids)
        need = max(0, (len(ids) - 1) // BLOCK) - len(m.block_ids)
        if need > 0:
            try:
                fresh = pool.alloc_tokens(need * BLOCK)
            except OutOfBlocks:
                cache.evict(need)
                fresh = pool.alloc_tokens(need * BLOCK)
            cache.insert(ids, list(m.block_ids) + fresh)
            pool.decref(fresh)  # the trie holds its own reference now
        if m.block_ids:
            pool.decref(m.block_ids, outcome="retired")
    return cache, prof


def test_mrc_tracks_measured_and_predicts_doubled_pool():
    """Acceptance: |predicted@1x - measured| <= 0.05 on the seeded trace,
    and the 2x counterfactual from run ONE matches what run TWO measures
    with the pool actually doubled."""
    C = 64
    trace = _zipf_trace()
    cache1, prof1 = _serve_trace(trace, C, rate=0.25)
    snap1 = prof1.snapshot()
    st1 = cache1.stats()
    measured1 = st1["hit_rate"]

    pred_1x = snap1["counterfactual_hit_ratio"]["1x"]
    assert pred_1x is not None
    assert abs(pred_1x - measured1) <= 0.05, (pred_1x, measured1)
    # sanity: the trace actually exercised both hits and eviction churn
    assert 0.1 < measured1 < 0.95 and st1["evictions"] > 0

    # the exact (rate=1) estimator sits even closer — the sampling is
    # the only approximation in play
    _, prof_exact = _serve_trace(trace, C, rate=1.0)
    exact_1x = prof_exact.snapshot()["counterfactual_hit_ratio"]["1x"]
    assert abs(exact_1x - measured1) <= 0.02, (exact_1x, measured1)

    # counterfactual validation: rerun the SAME trace on a 2x pool and
    # hold run one's 2x prediction to what the bigger pool measured
    cache2, _ = _serve_trace(trace, 2 * C, rate=0.25)
    measured2 = cache2.stats()["hit_rate"]
    pred_2x = snap1["counterfactual_hit_ratio"]["2x"]
    assert measured2 > measured1  # the bigger pool must actually help
    assert abs(pred_2x - measured2) <= 0.05, (pred_2x, measured2)

    # working-set estimate: ~400 distinct chunks, scaled from the sample
    assert 250 <= snap1["working_set_blocks"] <= 600
    # the curve is monotone non-decreasing in capacity
    curve = [p["hit_ratio"] for p in snap1["curve"]]
    assert all(a <= b + 1e-9 for a, b in zip(curve, curve[1:]))
    assert set(snap1["counterfactual_hit_ratio"]) == {
        f"{s:g}x" for s in CAPACITY_SCALES}


# --------------------------------------------------- tenant attribution
def test_tenant_working_sets_partition_the_sample():
    pool = KVBlockPool(17, BLOCK)
    prof = KVProfiler(pool, rate=1.0).attach()

    def lookups(tenant, prompts):
        tok = obs_accounting.current_tenant.set(tenant)
        try:
            for ids in prompts:
                prof.on_lookup(ids)
        finally:
            obs_accounting.current_tenant.reset(tok)

    a_prompts = [[10 + i, 11 + i, 12 + i, 13 + i, 14 + i] for i in range(6)]
    b_prompts = [[90 + i, 91 + i, 92 + i, 93 + i, 94 + i] for i in range(4)]
    lookups("alice", a_prompts)
    lookups("bob", b_prompts)
    snap = prof.snapshot()
    assert set(snap["tenants"]) == {"alice", "bob"}
    # attribution is accounting: the per-tenant sets PARTITION the global
    # sample — the sum IS the whole (rate=1: one block per sampled key)
    total = sum(t["working_set_blocks"] for t in snap["tenants"].values())
    assert total == snap["working_set_blocks"] == 10

    # ownership follows the last toucher: bob re-reads alice's prompts
    lookups("bob", a_prompts[:2])
    snap = prof.snapshot()
    assert snap["tenants"]["alice"]["working_set_blocks"] == 4
    assert snap["tenants"]["bob"]["working_set_blocks"] == 6
    total = sum(t["working_set_blocks"] for t in snap["tenants"].values())
    assert total == snap["working_set_blocks"] == 10

    # requests outside any tenant context land in the bounded bucket
    prof.on_lookup([201, 202, 203, 204, 205])
    assert "unattributed" in prof.tenant_working_sets()


# -------------------------------------------------- 429 calibration
def test_retry_after_calibration_scores_observed_release():
    reg = Registry()
    pool = KVBlockPool(9, BLOCK)  # 8 allocatable
    prof = KVProfiler(pool, rate=1.0, registry=reg).attach()
    held = pool.alloc_tokens(8 * BLOCK)
    assert pool.n_free == 0
    predicted = 0.05
    prof.note_retry_after(3, predicted)
    t0 = time.time()
    time.sleep(0.15)
    pool.decref(held[:3], outcome="died_queued")  # 3 free >= target 3
    waited = time.time() - t0
    snap = prof.snapshot()
    calib = snap["calibration"]
    assert calib["count"] == 1 and calib["pending"] == 0
    # the deterministic fault: released ~0.15s after a 0.05s promise
    assert abs(calib["mean_abs_error_s"] - (waited - predicted)) < 0.05
    assert snap["block_lifetime"]["died_queued"]["count"] == 3
    text = reg.render()
    assert ("tpustack_llm_kv_retry_after_error_seconds_count 1"
            in text)
    assert ('tpustack_llm_kv_block_lifetime_seconds_count'
            '{outcome="died_queued"} 3') in text

    # an unreachable shortfall stays pending (target clamps to capacity)
    pool.decref(held[3:])
    held2 = pool.alloc_tokens(8 * BLOCK)
    prof.note_retry_after(10_000, 1.0)
    pool.decref(held2)
    assert prof.snapshot()["calibration"]["count"] == 2  # clamped -> met


# ----------------------------------------- warm/cold eviction split
def test_eviction_warm_cold_split_and_last_hit_stamp():
    """Satellite fix, profiler-independent: trie leaves stamp last-hit
    wall time; evictions within the warm window count warm, the rest
    cold — with or without a profiler attached."""
    pool = KVBlockPool(17, BLOCK)
    cache = PagedPrefixCache(pool, warm_s=0.05)
    old = [1, 2, 3, 4, 5]
    new = [7, 8, 9, 10, 11]
    for ids in (old,):
        b = pool.alloc_tokens(BLOCK)
        cache.insert(ids, b)
        pool.decref(b)
    time.sleep(0.12)  # `old` ages past the warm window
    for ids in (new,):
        b = pool.alloc_tokens(BLOCK)
        cache.insert(ids, b)
        pool.decref(b)
    warm_events = []
    cache.on_evict_warm = warm_events.append
    freed = cache.evict(2)
    assert freed == 2
    st = cache.stats()
    assert st["evicted_warm"] == 1 and st["evicted_cold"] == 1
    assert warm_events == [1]

    # with a profiler: the same split lands as lifetime outcomes and
    # eviction ages
    pool2 = KVBlockPool(17, BLOCK)
    cache2 = PagedPrefixCache(pool2, warm_s=10.0)
    prof = KVProfiler(pool2, cache=cache2, rate=1.0).attach()
    b = pool2.alloc_tokens(BLOCK)
    cache2.insert([1, 2, 3, 4, 5], b)
    pool2.decref(b)
    cache2.match([1, 2, 3, 4, 5])  # a hit, then release the match refs
    pool2.decref([b[0]])
    cache2.evict(1)
    snap = prof.snapshot()
    assert snap["block_lifetime"]["evicted_warm"]["count"] == 1
    assert snap["eviction_age"]["count"] == 1
    assert 0.0 <= snap["eviction_age"]["mean_s"] < 5.0
    # the reuse gap of the re-hit entry was observed
    assert snap["reuse_gap"]["count"] == 1


# ---------------------------------------------------- server wiring
def _server(gen, **kw):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    reg = kw.pop("registry", None) or Registry()
    return LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                     max_batch=4, registry=reg, **kw), reg


def _make_runtime(gen, capacity_blocks=32, block=8, cache=True):
    return PagedKVRuntime.build(gen.cfg, 4, block=block,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32, prefix_cache=cache)


def test_debug_kvcache_route_and_scrape_gauges(gen, monkeypatch):
    import asyncio

    monkeypatch.setenv("TPUSTACK_KVPROF_RATE", "1.0")
    rt = _make_runtime(gen)
    server, reg = _server(gen, paged=rt)
    assert server.kvprof is not None and server.kvprof.ledger is server.ledger

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            bodies = [{"prompt": "shared observatory preamble! " + t,
                       "n_predict": 4, "temperature": 0}
                      for t in ("q1", "q2", "q1")]
            for body in bodies:
                r = await client.post("/completion", json=body,
                                      headers={"X-Tenant-Id": "alice"})
                assert r.status == 200, await r.text()
            kv = await (await client.get("/debug/kvcache")).json()
            tenants = await (await client.get("/debug/tenants")).json()
            metrics = await (await client.get("/metrics")).text()
            return kv, tenants, metrics
        finally:
            await client.close()

    kv, tenants, metrics = asyncio.new_event_loop().run_until_complete(
        scenario())
    assert kv["enabled"] and kv["rate"] == 1.0
    assert kv["lookups"] >= 3 and kv["working_set_blocks"] > 0
    assert kv["counterfactual_hit_ratio"]["1x"] is not None
    assert [p["scale"] for p in kv["curve"]] == [0.25, 0.5, 1, 2, 4, 8]
    assert kv["pool"]["pool_blocks"] == 32
    assert kv["prefix_cache"]["enabled"]
    # per-tenant attribution surfaced in /debug/tenants
    assert "kv_working_set" in tenants
    # scrape-time gauges: working set + counterfactual curve points, and
    # the tenant split routed through the ledger (TPL502's single writer)
    assert "tpustack_llm_kv_working_set_blocks " in metrics
    assert 'tpustack_llm_kv_counterfactual_hit_ratio{capacity="2x"}' \
        in metrics
    assert "tpustack_tenant_kv_working_set_blocks{" in metrics


def test_from_env_rate_zero_builds_nothing(monkeypatch):
    monkeypatch.setenv("TPUSTACK_KVPROF_RATE", "0")
    pool = KVBlockPool(9, BLOCK)
    cache = PagedPrefixCache(pool)
    assert from_env(pool, cache=cache) is None
    assert pool.profiler is None and cache.profiler is None


# ----------------------------------------------------- kv_report tool
def test_kv_report_renders_snapshot_and_gates():
    from tools import kv_report

    _, prof = _serve_trace(_zipf_trace(n_access=800), 64, rate=1.0)
    snap = prof.snapshot()
    got, how = kv_report.extract_snapshot({"server_kvcache": snap})
    assert how == "server_kvcache" and got is snap
    rep = kv_report.build_report(snap, max_hbm_ratio=0.0)
    assert rep["ok"] and rep["capacity_blocks"] == 64
    assert len(rep["table"]) == 6 and rep["recommendation"]
    text = kv_report.render_text(rep, "unit")
    assert "predicted hit rate" in text and "recommendation:" in text
    # the gate: this trace's working set (~400 blocks) dwarfs a 64-block
    # pool, so a 1.0 HBM ratio bar must trip
    rep2 = kv_report.build_report(snap, max_hbm_ratio=1.0)
    assert not rep2["ok"] and rep2["capacity_ratio"] > 1.0
    # a profiler-off payload is a clean refusal, not a crash
    assert kv_report.extract_snapshot({"enabled": False})[0] is None


def test_kv_report_tiny_smoke(tmp_path):
    """The CI path end to end: self-hosted replay --tiny -> artifact ->
    report JSON -> exit 0."""
    from tools import kv_report

    out = tmp_path / "kv.json"
    rc = kv_report.main(["--tiny", "--json", "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["metric"] == "kv_working_set_report"
    assert rep["capacity_blocks"] >= 1 and rep["ok"]
    # 6 counterfactual scale rows + the round-17 labeled host_tier point
    # (the self-hosted tiny server runs with its host KV tier on)
    assert len(rep["table"]) == 7
    labels = [r.get("label") for r in rep["table"]]
    assert labels.count("host_tier") == 1 and labels.count(None) == 6
    assert rep["host_tier"]["capacity_bytes"] > 0


# ------------------------------------------------- the =0 bisection path
_BISect_CODE = """
import os
os.environ["TPUSTACK_KVPROF_RATE"] = {rate!r}
import asyncio, json
import jax.numpy as jnp
from tpustack.obs import Registry
from tpustack.obs import perfsig
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_generate import Generator
from tpustack.models.text_tokenizer import ByteTokenizer
from tpustack.serving.kv_pool import PagedKVRuntime
from tpustack.serving.llm_server import LLMServer

gen = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
watch = perfsig.compile_watch(gen)
rt = PagedKVRuntime.build(gen.cfg, 4, block=8, pool_blocks=32,
                          dtype=jnp.float32, prefix_cache=True)
reg = Registry()
server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                   model_name="t", max_batch=4, registry=reg, paged=rt)
assert (server.kvprof is None) == ({rate!r} == "0")

async def go():
    from aiohttp.test_utils import TestClient, TestServer
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        outs = []
        for t in ("q1", "q2", "q1"):
            r = await client.post(
                "/completion",
                json={{"prompt": "bisection preamble! " + t,
                       "n_predict": 8, "temperature": 0}})
            assert r.status == 200
            outs.append((await r.json())["content"])
        return outs
    finally:
        await client.close()

outs = asyncio.new_event_loop().run_until_complete(go())
sig = perfsig.signature(prefix_cache=rt.cache.stats(), watch=watch)
render = reg.render()
print("CONTENT:" + json.dumps(outs))
print("SIG:" + json.dumps(sig))
print("KVSERIES:" + json.dumps(
    "tpustack_llm_kv_counterfactual_hit_ratio{{" in render))
"""


def _run_bisect(rate: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0",
               TPUSTACK_KVPROF_RATE=rate)
    proc = subprocess.run(
        [sys.executable, "-c", _BISect_CODE.format(rate=rate)], cwd=REPO,
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = {}
    for ln in proc.stdout.splitlines():
        for tag in ("CONTENT:", "SIG:", "KVSERIES:"):
            if ln.startswith(tag):
                out[tag[:-1]] = json.loads(ln[len(tag):])
    return out


def test_kvprof_off_is_byte_identical():
    """TPUSTACK_KVPROF_RATE=0 vs rate=1.0, two cold subprocesses, same
    seeded server and greedy requests: identical completions, identical
    prefix-cache AND recompile signatures (the observer perturbs no
    counter the perf gate ratchets on), and no kvprof series minted in
    the off run."""
    off = _run_bisect("0")
    on = _run_bisect("1.0")
    assert off["CONTENT"] == on["CONTENT"]
    assert off["SIG"] == on["SIG"]
    # the profiler added zero entries to the signature itself
    assert all(k.startswith(("prefix_cache.", "recompiles."))
               for k in on["SIG"])
    assert off["KVSERIES"] is False
    assert on["KVSERIES"] is True
