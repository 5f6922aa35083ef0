"""Engine flight recorder + live roofline attribution (ISSUE 11).

Acceptance bars covered here:

- the continuous engine feeds one record per dispatch whose token/
  occupancy/kv accounting matches the run's real stats;
- injected watchdog hang, SIGTERM drain, fatal engine error and a seeded
  sanitizer violation each produce a JSON flight dump whose last records
  match the engine's actual final waves;
- the live MFU/HBM-utilization gauges agree with bench_llm's computed
  utilization (same shared arithmetic) within tolerance on the tiny
  model, and are ABSENT — not wrong — on unknown device kinds;
- ``GET /debug/flight`` serves the ring + aggregates on the servers and
  the stdlib metrics sidecar; ``POST /profile`` exists on every serving
  surface; ``tools/xprof_summary.py`` degrades cleanly.
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpustack.obs import Registry  # noqa: E402
from tpustack.obs import flight as obs_flight  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore")


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _clear_fault_env(monkeypatch):
    for k in ("TPUSTACK_FAULT_SLOW_PREFILL_S", "TPUSTACK_FAULT_DEVICE_ERROR_NTH",
              "TPUSTACK_FAULT_HANG_NTH", "TPUSTACK_FAULT_HANG_S",
              "TPUSTACK_FAULT_SIGTERM_AFTER", "TPUSTACK_MAX_QUEUE_DEPTH",
              "TPUSTACK_WATCHDOG_S"):
        monkeypatch.delenv(k, raising=False)


@pytest.fixture(scope="module")
def gen():
    import jax.numpy as jnp

    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_generate import Generator

    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def _llm_server(gen, **kw):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    kw.setdefault("max_batch", 4)
    kw.setdefault("registry", Registry())
    return LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                     model_name="tiny-test", **kw)


@pytest.fixture(scope="module")
def warm_programs(gen):
    """Compile the serving engine's programs once (4 slots × the server
    chunk) so the watchdog-timing tests below never race a cold
    multi-second jit — a cold compile would trip a 0.x-second watchdog
    before the injected hang does, with an empty ring to dump."""
    server = _llm_server(gen, registry=Registry())

    async def go():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post("/completion", json={
                "prompt": "warm", "n_predict": 4, "temperature": 0})
            assert r.status == 200
        finally:
            await client.close()

    _run(go())
    return True


def _engine_fleet(gen, n=3, max_new=10, **engine_kw):
    from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
    from tpustack.models.llm_generate import SampleConfig

    eng = ContinuousEngine(gen, slots=2, chunk=4, **engine_kw)
    q = [SlotRequest(ids=[5 + i, 6, 7], max_new=max_new,
                     sample=SampleConfig(greedy=True)) for i in range(n)]
    stats = eng.run(lambda: q.pop(0) if q else None)
    return eng, stats


# ------------------------------------------------------------ the recorder
def test_recorder_ring_capacity_and_seq():
    rec = obs_flight.FlightRecorder("t", capacity=4)
    for i in range(10):
        rec.record("wave", tokens=i)
    recs = rec.recent()
    assert len(recs) == 4  # ring capped
    assert [r["seq"] for r in recs] == [7, 8, 9, 10]  # monotonic, newest-last
    assert rec.last()["tokens"] == 9
    assert rec.recent(2)[0]["seq"] == 9


def test_recorder_aggregates_window_and_rates(monkeypatch):
    rec = obs_flight.FlightRecorder("t", capacity=16)
    t0 = time.time()
    for i, ts in enumerate((t0 - 10.0, t0 - 1.0, t0)):
        r = rec.record("wave", tokens=8, weight_passes=4, occupancy=2,
                       wave_s=0.5, drafted=4, accepted=2)
        r["ts"] = ts  # deterministic spacing
    agg = rec.aggregates()
    assert agg["waves"] == 3 and agg["tokens"] == 24
    assert agg["mean_occupancy"] == 2
    assert agg["tokens_per_s"] == pytest.approx(24 / 10.0)
    assert agg["tokens_per_weight_pass"] == pytest.approx(2.0)
    assert agg["spec_acceptance"] == pytest.approx(0.5)
    # a 5s window drops the old record
    agg5 = rec.aggregates(window_s=5.0)
    assert agg5["waves"] == 2


def test_recorder_dump_honours_env_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", str(tmp_path / "dumps"))
    rec = obs_flight.FlightRecorder("unit", capacity=8)
    rec.record("wave", tokens=1)
    path = rec.dump("smoke test/..")
    assert path and os.path.exists(path)
    payload = json.loads(open(path).read())
    assert payload["server"] == "unit" and payload["reason"] == "smoke test/.."
    assert payload["records"][-1]["tokens"] == 1
    assert "/" not in os.path.basename(path).replace("flight-", "", 1)
    # empty dir knob disables dumping, never crashes
    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", "")
    assert rec.dump("x") is None


# ----------------------------------------------------- engine feed (waves)
def test_engine_feeds_wave_and_prefill_records(gen):
    rec = obs_flight.FlightRecorder("eng", capacity=256)
    depth = {"v": 3}
    eng, stats = _engine_fleet(gen, n=3, flight=rec,
                               queue_depth=lambda: depth["v"])
    recs = rec.recent()
    kinds = {r["kind"] for r in recs}
    assert "wave" in kinds and "prefill" in kinds
    waves = [r for r in recs if r["kind"] == "wave"]
    # the admission-sampled first token is delivered at resolve, not in a
    # wave — so wave tokens == generated minus one first per request
    assert sum(r["tokens"] for r in waves) == (
        stats["generated_tokens"] - stats["requests"])
    assert all(0 <= r["occupancy"] <= 2 for r in waves)
    assert all(r["weight_passes"] == 4 for r in waves)  # chunk
    assert all(r.get("queue_depth") == 3 for r in waves)
    # prefill records carry the admission shape
    pre = [r for r in recs if r["kind"] == "prefill"]
    assert sum(r["rows"] for r in pre) == stats["requests"]
    assert all(r["prompt_tokens"] >= 3 for r in pre)
    # wave wall time recorded from the second wave on
    assert any(r.get("wave_s") is not None for r in waves)


def test_engine_spec_records_drafted_accepted(gen):
    from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
    from tpustack.models.llm_generate import SampleConfig
    from tpustack.serving.speculative import SpecConfig

    rec = obs_flight.FlightRecorder("eng", capacity=256)
    eng = ContinuousEngine(gen, slots=2, chunk=4,
                           spec=SpecConfig(tokens=3), flight=rec)
    # repetitive prompt: prompt lookup finds drafts
    ids = [7, 11, 13, 7, 11, 13, 7, 11, 13, 7, 11]
    q = [SlotRequest(ids=list(ids), max_new=24,
                     sample=SampleConfig(greedy=True))]
    stats = eng.run(lambda: q.pop(0) if q else None)
    verifies = [r for r in rec.recent() if r["kind"] == "verify"]
    if stats.get("spec_dispatches"):
        assert verifies, "verify dispatches must be recorded"
        assert sum(r["drafted"] for r in verifies) == stats["spec_drafted_tokens"]
        assert sum(r["accepted"] for r in verifies) == stats["spec_accepted_tokens"]
        assert all(r["weight_passes"] == 1 for r in verifies)
        agg = rec.aggregates()
        assert agg["spec_acceptance"] == pytest.approx(
            stats["spec_acceptance"])


def test_engine_paged_records_kv_state(gen):
    from tpustack.serving.kv_pool import PagedKVRuntime

    rt = PagedKVRuntime.build(gen.cfg, 2, block=8)  # 2 x 64 / 8 blocks
    rec = obs_flight.FlightRecorder("eng", capacity=256)
    _, stats = _engine_fleet(gen, n=2, flight=rec, paged=rt)
    waves = [r for r in rec.recent() if r["kind"] == "wave"]
    assert waves
    assert all("kv_free" in r and "kv_used" in r
               and "kv_fragmentation" in r for r in waves)
    assert any(r["kv_used"] > 0 for r in waves)
    assert rec.aggregates()["kv_used_last"] == waves[-1]["kv_used"]


def test_pool_flight_snapshot_matches_properties():
    from tpustack.serving.kv_pool import KVBlockPool

    pool = KVBlockPool(9, 4)
    ids = pool.alloc_tokens(6)  # 2 blocks, second half-filled
    free, used, frag = pool.flight_snapshot()
    assert (free, used) == (pool.n_free, pool.n_used)
    assert frag == pytest.approx(pool.fragmentation())
    pool.decref(ids)
    assert pool.flight_snapshot() == (pool.capacity_blocks, 0, 0.0)


# --------------------------------------------------- roofline attribution
def test_wave_arith_matches_bench_formula(gen):
    """The shared helper IS bench_llm's roofline accounting: replicate the
    original bench formulas independently and require equality — the
    live gauges and the bench must never drift apart."""
    import jax
    import jax.numpy as jnp

    cfg = gen.cfg
    arith = obs_flight.llm_wave_arith(cfg, gen.params, gen.cache_dtype)

    def leaf_name(p):
        return str(p[-1].key if hasattr(p[-1], "key") else p[-1])

    flat = jax.tree_util.tree_leaves_with_path(gen.params)
    weight_bytes = sum(
        x.nbytes for p, x in flat
        if not any("embed" in str(getattr(k, "key", k)) for k in p))
    flops = 2 * sum(x.size for p, x in flat if leaf_name(p) == "kernel")
    kv_elt = jnp.dtype(gen.cache_dtype).itemsize
    kv_bytes = (cfg.n_layers * 2 * cfg.max_seq * cfg.n_kv_heads
                * cfg.head_dim * kv_elt)
    assert arith["flops_per_token"] == flops
    assert arith["weight_stream_bytes"] == weight_bytes
    assert arith["kv_step_bytes_per_slot"] == kv_bytes


def test_live_utilization_agrees_with_bench_math(gen):
    """Acceptance: live MFU/HBM gauges vs bench_llm's computed utilization
    on the tiny model, same traffic — within tolerance (both derive their
    rates from the same engine run; the flight window's first→last span
    vs the fetch-mark slope is the only difference)."""
    rec = obs_flight.FlightRecorder("eng", capacity=1024)
    _, stats = _engine_fleet(gen, n=4, max_new=24, flight=rec)
    agg = rec.aggregates()
    arith = obs_flight.llm_wave_arith(gen.cfg, gen.params, gen.cache_dtype)
    peaks = (100e12, 800e9)  # injected: CPU has no known peaks by design
    util = obs_flight.llm_utilization(agg, arith, peaks)
    assert util is not None
    # bench-style: steady decode rate x per-token FLOPs over the peak
    bench_mfu = (stats["steady_tokens_per_s"] * arith["flops_per_token"]
                 / peaks[0])
    assert util["mfu"] == pytest.approx(bench_mfu, rel=0.25)
    assert 0 < util["hbm_util"] < 1
    # unknown device kind → no utilization at all, never a wrong number
    assert obs_flight.llm_utilization(agg, arith, None) is None


def test_sd_flops_rate_skips_uncosted_batches():
    """An uncostable signature (flops None) contributes NEITHER flops nor
    busy seconds to device_flops_per_s — its denoise time must not
    deflate the MFU below the true utilization."""
    rec = obs_flight.FlightRecorder("sd", capacity=8)
    rec.record("batch", batch=4, denoise_vae_s=2.0, flops=8e9)
    rec.record("batch", batch=4, denoise_vae_s=100.0, flops=None)
    agg = rec.aggregates()
    assert agg["flops"] == pytest.approx(8e9)
    assert agg["device_busy_s"] == pytest.approx(102.0)  # honest total
    assert agg["device_flops_per_s"] == pytest.approx(8e9 / 2.0)


def test_utilization_none_without_rates():
    arith = {"flops_per_token": 1.0, "weight_stream_bytes": 1.0,
             "kv_step_bytes_per_slot": 1.0}
    assert obs_flight.llm_utilization({"records": 0}, arith,
                                      (1e12, 1e9)) is None
    assert obs_flight.sd_utilization({"records": 0}, (1e12, 1e9)) is None
    assert obs_flight.sd_utilization({"device_flops_per_s": 5e11},
                                     (1e12, 1e9))["mfu"] == pytest.approx(0.5)


# ------------------------------------------------- llm server HTTP surface
def test_llm_debug_flight_endpoint_and_roofline_gauges(gen, monkeypatch):
    """Tier-1 /debug/flight smoke against a tiny engine, plus the gauge
    contract: with a known device kind the MFU/HBM gauges are sampled and
    positive; on the real (unknown-kind CPU) device they are absent."""
    _clear_fault_env(monkeypatch)
    server = _llm_server(gen)
    reg = server._registry

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            for i in range(2):
                r = await client.post("/completion", json={
                    "prompt": f"flight {i}", "n_predict": 6,
                    "temperature": 0})
                assert r.status == 200
            r = await client.get("/debug/flight")
            assert r.status == 200
            snap = await r.json()
            r2 = await client.get("/debug/flight?window=60&n=5")
            snap5 = await r2.json()
            return snap, snap5
        finally:
            await client.close()

    snap, snap5 = _run(scenario())
    assert snap["server"] == "llm"
    assert snap["meta"]["model"] == "tiny-test" and snap["meta"]["slots"] == 4
    assert snap["aggregates"]["waves"] >= 1
    assert any(r["kind"] == "wave" for r in snap["records"])
    assert len(snap5["records"]) <= 5

    # scrape on the REAL device (CPU → unknown kind): utilization gauges
    # absent (HELP/TYPE only), occupancy gauge present
    text = reg.render()
    assert "tpustack_llm_mfu_ratio{" not in text
    assert "tpustack_llm_hbm_util_ratio{" not in text
    assert "tpustack_llm_wave_occupancy_slots" in text

    # scrape with an injected known device kind: gauges sampled, labelled,
    # and equal to the shared-arithmetic utilization of the same window
    peaks = (100e12, 800e9)
    monkeypatch.setattr(obs_flight, "device_peaks_info",
                        lambda: ("TPU v99 test", peaks))
    monkeypatch.setenv("TPUSTACK_FLIGHT_WINDOW_S", "3600")
    text = reg.render()
    mfu = reg.get_sample_value("tpustack_llm_mfu_ratio",
                               {"device_kind": "TPU v99 test"})
    hbm = reg.get_sample_value("tpustack_llm_hbm_util_ratio",
                               {"device_kind": "TPU v99 test"})
    assert mfu is not None and mfu > 0
    assert hbm is not None and hbm > 0
    agg = server.flight.aggregates(3600.0)
    want = obs_flight.llm_utilization(agg, server._flight_arith, peaks,
                                      chips=server._flight_chips)
    assert mfu == pytest.approx(want["mfu"], rel=0.05)
    assert hbm == pytest.approx(want["hbm_util"], rel=0.05)
    occ = reg.get_sample_value("tpustack_llm_wave_occupancy_slots")
    assert 0 < occ <= 4

    # idle window: the gauges CLEAR to 0 instead of freezing at the last
    # busy window's values (a scaler reading "current scrape" must not see
    # hour-old utilization)
    monkeypatch.setenv("TPUSTACK_FLIGHT_WINDOW_S", "0.000001")
    reg.render()
    assert reg.get_sample_value("tpustack_llm_wave_occupancy_slots") == 0
    assert reg.get_sample_value("tpustack_llm_spec_efficiency_tokens") == 0
    assert reg.get_sample_value("tpustack_llm_mfu_ratio",
                                {"device_kind": "TPU v99 test"}) == 0
    assert reg.get_sample_value("tpustack_llm_hbm_util_ratio",
                                {"device_kind": "TPU v99 test"}) == 0


def test_llm_profile_endpoint(gen, monkeypatch, tmp_path):
    _clear_fault_env(monkeypatch)
    monkeypatch.setenv("TPUSTACK_PROFILE_DIR", str(tmp_path))
    server = _llm_server(gen)

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            # the new body: seconds of whatever the engine is serving —
            # no device lock, no request of its own
            r = await client.post("/profile", json={"seconds": 0.2})
            assert r.status == 200, await r.text()
            prof = await r.json()
            assert prof["seconds"] == 0.2
            assert prof["trace_dir"].startswith(
                os.path.join(str(tmp_path), "llm"))
            assert prof["files"] and all(
                f.endswith(".xplane.pb") for f in prof["files"])
            # a second capture lists only its own files
            r2 = await client.post("/profile", json={"seconds": 0.2})
            prof2 = await r2.json()
            assert prof2["trace_dir"] != prof["trace_dir"]
            assert not set(prof2["files"]) & set(prof["files"])
            # validation: bad bodies → 4xx, never a 500
            for bad in ([1, 2], {"seconds": "abc"}):
                r = await client.post("/profile", json=bad)
                assert r.status == 422, f"{bad} → {r.status}"
        finally:
            await client.close()

    _run(scenario())


# -------------------------------------------------------- post-mortem dumps
def _find_dump(dump_dir, server, reason):
    out = []
    for p in sorted(glob.glob(os.path.join(dump_dir, "*.json"))):
        payload = json.loads(open(p).read())
        if payload["server"] == server and payload["reason"] == reason:
            out.append(payload)
    return out


def test_watchdog_fire_dumps_flight(gen, warm_programs, monkeypatch,
                                    tmp_path):
    """Acceptance: injected hang (TPUSTACK_FAULT_HANG_NTH) + watchdog →
    a flight dump exists and its records match the engine's in-memory
    ring (same seq → same record)."""
    _clear_fault_env(monkeypatch)
    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("TPUSTACK_FAULT_HANG_NTH", "2")
    monkeypatch.setenv("TPUSTACK_FAULT_HANG_S", "1.2")
    monkeypatch.setenv("TPUSTACK_WATCHDOG_S", "0.2")
    server = _llm_server(gen, registry=Registry())

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            # first completion populates the ring (the hang fires at the
            # SECOND admission dispatch, so there is history to dump)
            r = await client.post("/completion", json={
                "prompt": "fill the ring", "n_predict": 8,
                "temperature": 0})
            assert r.status == 200
            task = asyncio.ensure_future(client.post("/completion", json={
                "prompt": "hang and dump", "n_predict": 8,
                "temperature": 0}))
            for _ in range(200):
                if _find_dump(str(tmp_path), "llm", "watchdog"):
                    break
                await asyncio.sleep(0.02)
            r = await task
            assert r.status == 200
        finally:
            await client.close()

    try:
        _run(scenario())
    finally:
        server.resilience.close()
    dumps = _find_dump(str(tmp_path), "llm", "watchdog")
    assert dumps, "watchdog fire must dump the flight ring"
    # dump_all also dumps recorders of earlier tests' servers — the dump
    # for THIS server is the one whose records match its live ring at the
    # same seq (flakiness-proof identification)
    live = {r["seq"]: r for r in server.flight.recent()}
    assert any(
        d["records"] and all(live.get(r["seq"]) == r for r in d["records"])
        for d in dumps), "a dump must carry THIS engine's pre-hang records"


def test_sigterm_drain_dumps_final_waves(gen, warm_programs, monkeypatch,
                                         tmp_path):
    """Acceptance: SIGTERM drain → dump whose LAST records are the
    engine's actual final waves (the drain dump happens after in-flight
    work finished)."""
    _clear_fault_env(monkeypatch)
    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("TPUSTACK_FAULT_SIGTERM_AFTER", "2")
    monkeypatch.setenv("TPUSTACK_DRAIN_TIMEOUT_S", "5")
    server = _llm_server(gen, registry=Registry())
    server.chunk = 2
    exits = []
    server.resilience.on_exit = exits.append

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post("/completion", json={
                "prompt": "drain and dump", "n_predict": 10,
                "temperature": 0})
            assert r.status == 200
            for _ in range(150):
                if exits:
                    break
                await asyncio.sleep(0.02)
        finally:
            await client.close()

    _run(scenario())
    assert exits == [0]
    dumps = _find_dump(str(tmp_path), "llm", "drain")
    assert dumps, "drain must dump the flight ring before exiting"
    final = [r for r in server.flight.recent()
             if r["kind"] in ("wave", "verify")]
    assert final

    def matches(d):
        dumped = [r for r in d["records"]
                  if r["kind"] in ("wave", "verify")]
        return bool(dumped) and dumped[-len(final):] == final

    assert any(matches(d) for d in dumps), \
        "the dump's last records must be the engine's actual final waves"


def test_engine_error_dumps_flight(gen, monkeypatch, tmp_path):
    """A fatal engine error (injected transient device error) dumps the
    ring through the engine's failure path."""
    _clear_fault_env(monkeypatch)
    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("TPUSTACK_FAULT_DEVICE_ERROR_NTH", "2")
    server = _llm_server(gen, registry=Registry())

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post("/completion", json={
                "prompt": "ok first", "n_predict": 4, "temperature": 0})
            assert r.status == 200
            r = await client.post("/completion", json={
                "prompt": "boom", "n_predict": 4, "temperature": 0})
            assert r.status == 503
        finally:
            await client.close()

    _run(scenario())
    dumps = _find_dump(str(tmp_path), "llm", "engine_error")
    assert dumps and dumps[-1]["records"]


def test_sanitizer_violation_dumps_flight(monkeypatch, tmp_path):
    """Acceptance: a seeded sanitizer violation dumps every registered
    non-empty recorder, tagged with the check name."""
    from tpustack import sanitize
    from tpustack.serving.kv_pool import KVBlockPool

    monkeypatch.setenv("TPUSTACK_FLIGHT_DUMP_DIR", str(tmp_path))
    rec = obs_flight.register(obs_flight.FlightRecorder("sanproof",
                                                        capacity=8))
    rec.record("wave", tokens=5, occupancy=1, weight_passes=4)
    sanitize.activate(mode="raise")
    # the dump is once-per-check-class per process: clear the throttle so
    # this test is order-independent under the full (sanitized) tier-1 run
    sanitize._DUMPED_CHECKS.clear()
    pool = KVBlockPool(8, 4)
    ids = pool.alloc_tokens(8)
    with pool._lock:
        pool._free.append(ids[0])  # the seeded violation: free ∧ referenced
    with pytest.raises(sanitize.SanitizerViolation):
        sanitize.check_kv_conservation(pool, "wave")
    dumps = _find_dump(str(tmp_path), "sanproof", "sanitizer_kv_leak")
    assert dumps, "sanitizer violations must dump the flight rings"
    assert dumps[-1]["records"][-1]["tokens"] == 5


# ------------------------------------------------------------ sd + graph
class _StubDev:
    def __init__(self, value):
        self._value = value

    def __array__(self, dtype=None, copy=None):
        return self._value

    def block_until_ready(self):
        return self


class _StubPipe:
    def generate_async(self, prompt, *, steps=30, guidance_scale=7.5,
                       seed=None, width=512, height=512, negative_prompt="",
                       batch_size=1, mesh=None):
        prompts = ([prompt] * batch_size if isinstance(prompt, str)
                   else list(prompt))
        return _StubDev(np.zeros((len(prompts), height, width, 3), np.uint8))

    def pipeline_flops(self, *, steps, width, height, batch_size):
        return 1e9 * batch_size * steps


def test_sd_batch_records_and_mfu_gauge(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.serving.sd_server import SDServer

    reg = Registry()
    server = SDServer(pipeline=_StubPipe(), mesh=None, batch_window_ms=5,
                      max_batch=4, registry=reg)

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            body = {"prompt": "stub", "steps": 2, "width": 32, "height": 32}
            rs = await asyncio.gather(*[
                client.post("/generate", json=dict(body, seed=s))
                for s in (1, 2, 3)])
            assert all(r.status == 200 for r in rs)
            r = await client.get("/debug/flight")
            return await r.json()
        finally:
            await client.close()

    snap = _run(scenario())
    assert snap["server"] == "sd"
    batches = [r for r in snap["records"] if r["kind"] == "batch"]
    assert batches and batches[0]["batch"] == 3 and batches[0]["pad"] == 1
    assert batches[0]["flops"] == pytest.approx(1e9 * 4 * 2)
    assert batches[0]["denoise_vae_s"] >= 0
    agg = server.flight.aggregates()
    assert agg["images"] == 3 and agg["device_flops_per_s"] > 0

    # unknown device kind (CPU): the gauge is absent
    assert "tpustack_sd_mfu_ratio{" not in reg.render()
    # known kind: sampled, equal to flops/denoise over the peak
    peaks = (1e13, 1e12)
    monkeypatch.setattr(obs_flight, "device_peaks_info",
                        lambda: ("TPU v99 test", peaks))
    monkeypatch.setenv("TPUSTACK_FLIGHT_WINDOW_S", "3600")
    reg.render()
    mfu = reg.get_sample_value("tpustack_sd_mfu_ratio",
                               {"device_kind": "TPU v99 test"})
    agg = server.flight.aggregates(3600.0)
    assert mfu == pytest.approx(agg["device_flops_per_s"] / peaks[0],
                                rel=0.05)


def test_graph_node_records_and_profile(tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.serving.graph_server import GraphServer, WanRuntime

    monkeypatch.setenv("TPUSTACK_PROFILE_DIR", str(tmp_path / "prof"))
    server = GraphServer(runtime=WanRuntime(models_dir=str(tmp_path / "m"),
                                            output_dir=str(tmp_path / "o")),
                         registry=Registry())
    try:
        server.executor.execute(
            {"1": {"class_type": "CLIPTextEncode", "inputs": {"text": "x"}}})

        async def scenario():
            client = TestClient(TestServer(server.build_app()))
            await client.start_server()
            try:
                r = await client.get("/debug/flight")
                snap = await r.json()
                # default /profile: symbolic text-encode graph (cheap)
                r2 = await client.post("/profile", json={})
                prof = await r2.json()
                assert r2.status == 200, prof
                # unknown node class → clean 400
                r3 = await client.post("/profile", json={
                    "prompt": {"1": {"class_type": "NoSuchNode"}}})
                assert r3.status == 400
                return snap, prof
            finally:
                await client.close()

        snap, prof = _run(scenario())
    finally:
        server.shutdown()
    assert snap["server"] == "graph"
    nodes = [r for r in snap["records"] if r["kind"] == "node"]
    assert any(r["class_type"] == "CLIPTextEncode" for r in nodes)
    assert snap["aggregates"]["nodes"]["CLIPTextEncode"]["count"] >= 1
    assert prof["trace_dir"].startswith(str(tmp_path / "prof"))
    assert isinstance(prof["files"], list)


def test_sidecar_serves_debug_flight():
    from tpustack.obs.http import start_metrics_sidecar

    rec = obs_flight.register(obs_flight.FlightRecorder("sidecar-test",
                                                        capacity=8))
    rec.record("wave", tokens=2, occupancy=1, weight_passes=4)
    server = start_metrics_sidecar(0, Registry())
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/flight", timeout=5) as resp:
            payload = json.loads(resp.read())
        names = [s["server"] for s in payload["recorders"]]
        assert "sidecar-test" in names
        mine = next(s for s in payload["recorders"]
                    if s["server"] == "sidecar-test")
        assert mine["records"][-1]["tokens"] == 2
    finally:
        server.shutdown()


# ------------------------------------------------------- xprof_summary CLI
def _xprof_main(argv):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "xprof_summary_mod", os.path.join(REPO, "tools", "xprof_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(argv)


def test_xprof_summary_missing_path_fails_clean(tmp_path, capsys):
    rc = _xprof_main([str(tmp_path / "nope")])
    assert rc != 0
    err = capsys.readouterr().err
    assert "no such trace path" in err and "Traceback" not in err


def test_xprof_summary_no_xplanes_json_error(tmp_path, capsys):
    rc = _xprof_main([str(tmp_path), "--json"])
    assert rc != 0
    out = capsys.readouterr().out
    assert json.loads(out)["error"].startswith("no .xplane.pb")


def test_xprof_summary_missing_package_is_one_line(tmp_path, monkeypatch,
                                                   capsys):
    (tmp_path / "fake.xplane.pb").write_bytes(b"\x00")
    monkeypatch.setitem(sys.modules, "xprof", None)
    monkeypatch.setitem(sys.modules, "xprof.convert", None)
    rc = _xprof_main([str(tmp_path / "fake.xplane.pb")])
    assert rc == 3
    err = capsys.readouterr().err
    assert "xprof" in err and "not installed" in err
    assert "Traceback" not in err
    rc = _xprof_main([str(tmp_path / "fake.xplane.pb"), "--json"])
    assert rc == 3
    assert "error" in json.loads(capsys.readouterr().out)


def test_wave_arith_counts_by_layer_kind():
    """A model with routed-expert and window layers (ISSUE 28): of an
    expert stack the arithmetic charges the share a token's choices make
    of it and the share a step's rows are expected to touch — not a dense
    stream — and of a window layer's cache line its window."""
    import jax
    import jax.numpy as jnp

    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_generate import Generator

    cfg = LlamaConfig.tiny_moe(max_seq=64)
    g = Generator(cfg, dtype=jnp.float32, seed=0)
    moe = cfg.moe
    flat = jax.tree_util.tree_leaves_with_path(g.params)
    name = lambda p: [str(getattr(k, "key", k)) for k in p]
    stack = lambda p, x: x.ndim == 3 and name(p)[-1] == "kernel"
    stacks = sum(x.size for p, x in flat if stack(p, x))
    others = sum(x.size for p, x in flat
                 if name(p)[-1] == "kernel" and not stack(p, x))
    assert stacks > 0
    for rows in (1, 2, 16):
        arith = obs_flight.llm_wave_arith(cfg, g.params, g.cache_dtype,
                                          rows=rows)
        chosen = moe.top_k / moe.n_experts
        assert arith["flops_per_token"] == pytest.approx(
            2 * (others + stacks * chosen))
        touched = 1 - (1 - chosen) ** rows
        dense_bytes = sum(x.nbytes for p, x in flat
                          if not stack(p, x) and "embed_tokens" not in name(p))
        assert arith["weight_stream_bytes"] == pytest.approx(
            dense_bytes + 4 * stacks * touched)
    # 3 window layers of 8 positions, one full layer of max_seq
    per_pos = 2 * cfg.n_kv_heads * cfg.head_dim * 4
    assert arith["kv_step_bytes_per_slot"] == (3 * 8 + 64) * per_pos
