"""Multi-tenant QoS (tpustack.serving.qos): priority classes, token-bucket
quotas, SLO-aware shedding, and wave-boundary preemption.

The acceptance bars this file carries:

- **Preemption parity** — a batch request preempted for an interactive
  request and later resumed returns greedy output BYTE-IDENTICAL to an
  uninterrupted solo run (paged engine, spec on and off), with the pool
  leak-free afterwards and per-tenant chip-second conservation
  (test_accounting's invariant) still holding across the preempted
  slot's two occupancies.
- **Bisection** — ``TPUSTACK_QOS=0`` leaves the admission path and the
  engine outputs byte-for-byte unchanged, subprocess-proven like
  ``TPUSTACK_SANITIZE=0``.
- Admission: quota-exhausted tenants get 429 with their OWN bucket's
  refill ETA as Retry-After (+ ``X-Shed-Reason: quota``), and batch
  sheds at half the queue depth while interactive still admits.
"""

import asyncio
import json
import math
import os
import subprocess
import sys

import pytest

import jax.numpy as jnp

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.obs import Registry
from tpustack.serving import qos as qos_mod
from tpustack.serving.kv_pool import PagedKVRuntime
from tpustack.serving.qos import QosPolicy, TokenBucket
from tpustack.serving.resilience import ResilienceManager
from tpustack.serving.speculative import SpecConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SampleConfig(greedy=True)


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def make_runtime(gen, capacity_blocks=32, block=8, cache=False):
    return PagedKVRuntime.build(gen.cfg, 1, block=block,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32, prefix_cache=cache)


# ------------------------------------------------------------ token bucket
def test_token_bucket_refill_debt_and_eta():
    clock = {"t": 100.0}
    b = TokenBucket(rate_per_s=10.0, burst=20.0, clock=lambda: clock["t"])
    assert b.ready() and b.refill_eta_s() == 0.0
    b.charge(50.0)  # measured cost lands as debt: 20 - 50 = -30
    assert not b.ready()
    assert b.refill_eta_s() == pytest.approx(3.0, abs=0.01)
    clock["t"] += 2.0  # refill 20 → level -10
    assert not b.ready()
    assert b.refill_eta_s() == pytest.approx(1.0, abs=0.01)
    clock["t"] += 1.5  # past zero
    assert b.ready()
    clock["t"] += 100.0  # refill clamps at burst
    b._refill()
    assert b.level == pytest.approx(20.0)
    with pytest.raises(ValueError):
        TokenBucket(rate_per_s=0.0, burst=1.0)


def test_queue_wait_recorded_per_server_and_priority():
    """tpustack_qos_queue_wait_seconds carries a server label (PR 14's
    llm-only follow-up): llm records at the engine-queue pop, sd at the
    micro-batch build, graph at the worker pickup — all through ONE
    observe_queue_wait, with None priority falling to the policy
    default."""
    reg = Registry()
    p = QosPolicy({"default_priority": "interactive"}, registry=reg)
    p.observe_queue_wait("llm", "interactive", 0.25)
    p.observe_queue_wait("sd", "batch", 1.5)
    p.observe_queue_wait("graph", None, 0.1)  # → default priority
    wait_lines = [ln for ln in reg.render().splitlines()
                  if ln.startswith("tpustack_qos_queue_wait_seconds")]
    for labels in ('server="llm",priority="interactive"',
                   'server="sd",priority="batch"',
                   'server="graph",priority="interactive"'):
        # label order in the exposition follows the catalog declaration
        assert any(labels in ln for ln in wait_lines), (labels, wait_lines)


# ------------------------------------------------------------------ policy
def test_policy_parse_and_priority_resolution():
    p = QosPolicy({
        "default_priority": "interactive",
        "batch_shed_ratio": 0.25,
        "tenants": {"bulk": {"priority": "batch", "tokens_per_s": 100}},
    }, registry=Registry())
    # header > body > tenant default > policy default; unknown values
    # fall through, never 500
    assert p.resolve_priority("batch", "interactive", "anyone") == "batch"
    assert p.resolve_priority(None, "batch", "anyone") == "batch"
    assert p.resolve_priority(None, None, "bulk") == "batch"
    assert p.resolve_priority(None, None, "anyone") == "interactive"
    assert p.resolve_priority("URGENT", "nope", "bulk") == "batch"
    # a policy-pinned BATCH tenant can never self-promote: the header/
    # body value is clamped (one X-Priority header must not reinstate
    # the batch-starves-interactive failure the policy exists to stop)
    assert p.resolve_priority(" Interactive ", None, "bulk") == "batch"
    assert p.resolve_priority(None, "interactive", "bulk") == "batch"
    # ...but self-DEMOTION is always honoured (cooperative)
    assert p.resolve_priority("batch", None, "anyone") == "batch"
    # batch sheds at the configured fraction of the depth cap
    assert p.batch_shed_depth(64) == 16
    assert p.batch_shed_depth(1) == 1
    # default burst = 2 x rate
    snap = p.snapshot()
    assert snap["tenants"]["bulk"]["buckets"]["tokens"]["burst"] == 200.0
    with pytest.raises(ValueError):
        QosPolicy({"default_priority": "urgent"})
    with pytest.raises(ValueError):
        QosPolicy({"batch_shed_ratio": 0.0})
    with pytest.raises(ValueError):
        QosPolicy({"tenants": {"a": {"priority": "nope"}}})


def test_policy_from_env_gate_and_file(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUSTACK_QOS", "0")
    assert QosPolicy.from_env(registry=Registry()) is None
    monkeypatch.setenv("TPUSTACK_QOS", "1")
    monkeypatch.setenv("TPUSTACK_QOS_POLICY",
                       '{"tenants": {"a": {"tokens_per_s": 5}}}')
    p = QosPolicy.from_env(registry=Registry())
    assert "a" in p.snapshot()["tenants"]
    cfg = tmp_path / "qos.json"
    cfg.write_text(json.dumps({"default_priority": "batch"}))
    monkeypatch.setenv("TPUSTACK_QOS_POLICY", str(cfg))
    p = QosPolicy.from_env(registry=Registry())
    assert p.default_priority == "batch"
    monkeypatch.setenv("TPUSTACK_QOS_POLICY", "{not json")
    with pytest.raises(ValueError):
        QosPolicy.from_env(registry=Registry())


def test_ledger_charges_drive_quota_buckets():
    """The ledger listener is the quota's input: measured tokens and
    chip-seconds push the tenant's buckets into debt; quota_check then
    answers with the max refill ETA over the exhausted dimensions."""
    from tpustack.obs import accounting

    reg = Registry()
    led = accounting.TenantLedger(reg, cardinality=8)
    p = QosPolicy({"tenants": {"bulk": {
        "tokens_per_s": 10.0, "burst_tokens": 5.0,
        "chip_seconds_per_s": 1.0, "burst_chip_seconds": 2.0}}},
        registry=reg)
    led.add_listener(p.on_ledger_charge)
    led.add_listener(p.on_ledger_charge)  # idempotent by identity
    assert len(led._listeners) == 1
    assert p.quota_check("bulk") is None
    assert p.quota_check("unknown-tenant") is None  # no quota configured
    led.charge_tokens("llm", "bulk", prompt=20, generated=15)
    eta = p.quota_check("bulk")  # tokens: 5 - 35 = -30 → ~3s at 10/s
    assert eta == pytest.approx(3.0, abs=0.1)
    led.charge_chip_seconds("llm", "bulk", 10.0)  # chip: 2 - 10 = -8 → ~8s
    assert p.quota_check("bulk") == pytest.approx(8.0, abs=0.2)
    # the bucket gauge exports the live balance for policy tenants
    lvl = reg.get_sample_value("tpustack_qos_bucket_level_ratio",
                               {"tenant": "bulk", "dimension": "tokens"})
    assert lvl is not None and lvl < 0


# --------------------------------------------------------------- admission
def test_admission_quota_shed_uses_bucket_eta():
    reg = Registry()
    p = QosPolicy({"tenants": {"bulk": {"priority": "batch",
                                        "tokens_per_s": 2.0,
                                        "burst_tokens": 4.0}}},
                  registry=reg)
    rm = ResilienceManager("llm", reg, qos=p)
    try:
        assert rm.admission_check(priority="batch", tenant="bulk") is None
        p.on_ledger_charge("llm", "bulk", "tokens", 24.0)  # debt 20 → 10s
        resp = rm.admission_check(priority="batch", tenant="bulk")
        assert resp is not None and resp.status == 429
        ra = int(resp.headers["Retry-After"])
        assert ra == math.ceil(p._tenants["bulk"]
                               .buckets["tokens"].refill_eta_s()) or \
            abs(ra - 10) <= 1
        assert resp.headers["X-Shed-Reason"] == "quota"
        assert p.counters["quota_throttle"]["batch"] == 1
        assert reg.get_sample_value(
            "tpustack_qos_quota_throttle_total",
            {"server": "llm", "priority": "batch"}) == 1
        assert reg.get_sample_value(
            "tpustack_requests_shed_total",
            {"server": "llm", "reason": "quota"}) == 1
    finally:
        rm.close()


def test_admission_batch_sheds_before_interactive():
    """SLO-aware shedding: at a queue depth past the batch wall but
    under the full cap, batch 429s while interactive still admits."""
    reg = Registry()
    p = QosPolicy({}, registry=reg)  # default batch_shed_ratio 0.5
    depth = {"v": 0}
    rm = ResilienceManager("llm", reg, qos=p, queue_depth=lambda: depth["v"],
                           env={"TPUSTACK_MAX_QUEUE_DEPTH": "8"})
    try:
        depth["v"] = 4  # >= batch wall (4), < full cap (8)
        shed = rm.admission_check(priority="batch", tenant="t")
        assert shed is not None and shed.status == 429
        assert rm.admission_check(priority="interactive", tenant="t") is None
        assert p.counters["shed"] == {"batch": 1}
        depth["v"] = 8  # the full cap sheds everyone
        assert rm.admission_check(priority="interactive",
                                  tenant="t").status == 429
        assert p.counters["shed"] == {"batch": 1, "interactive": 1}
        assert reg.get_sample_value(
            "tpustack_qos_shed_total",
            {"server": "llm", "priority": "batch"}) == 1
    finally:
        rm.close()


def test_admission_unchanged_without_qos():
    """qos=None (TPUSTACK_QOS=0): no quota arm, one depth wall for
    every priority — the pre-QoS admission check."""
    depth = {"v": 4}
    rm = ResilienceManager("llm", Registry(), queue_depth=lambda: depth["v"],
                           env={"TPUSTACK_MAX_QUEUE_DEPTH": "8"})
    try:
        assert rm.qos is None
        assert rm.admission_check(priority="batch", tenant="bulk") is None
        depth["v"] = 8
        assert rm.admission_check(priority="batch").status == 429
    finally:
        rm.close()


# --------------------------------------------- engine: priority scheduling
def test_llm_server_priority_dequeue_and_hint(gen, monkeypatch):
    """The engine's refill pops interactive entries first (FIFO within a
    class); with QoS off the pop is byte-for-byte the FIFO popleft."""
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer, _PendingCompletion

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="t", max_batch=4, registry=Registry())
    assert server.qos is not None  # TPUSTACK_QOS defaults on

    def pend(tag, priority):
        r = _PendingCompletion([1, 2], 4, GREEDY, None)
        r.priority = priority
        r.ids = [tag]
        return r

    server._queue.extend([pend(1, "batch"), pend(2, "interactive"),
                          pend(3, "batch"), pend(4, "interactive")])
    assert server._interactive_waiting()
    assert [server._pop_queued().ids[0] for _ in range(4)] == [2, 4, 1, 3]
    assert not server._interactive_waiting()
    # QoS off → strict FIFO
    server.qos = None
    server._queue.extend([pend(1, "batch"), pend(2, "interactive")])
    assert [server._pop_queued().ids[0] for _ in range(2)] == [1, 2]


# ------------------------------------------ engine: preemption parity bar
@pytest.mark.parametrize("spec", [None, SpecConfig(tokens=3)],
                         ids=["plain", "spec"])
def test_preempt_resume_greedy_byte_identical(gen, spec):
    """ACCEPTANCE: a batch request preempted at a wave boundary and
    resumed through the paged prefix warm start returns greedy output
    byte-identical to an uninterrupted solo run — no prefill work lost,
    no pool blocks leaked — while the interactive request that caused
    the preemption is served immediately and also matches solo."""
    pb, nb = [5, 6, 7, 8], 14
    pi, ni = [9, 10, 11], 6
    solo_b = gen.generate_fused(pb, max_new_tokens=nb, sample=GREEDY,
                                stop_tokens=(), chunk=4)[0]
    solo_i = gen.generate_fused(pi, max_new_tokens=ni, sample=GREEDY,
                                stop_tokens=(), chunk=4)[0]
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    results = {}
    trigger = {"armed": False}
    state = {"fed_b": False, "fed_i": False}
    preempts = []

    def on_b_tokens(toks):
        got = results.setdefault("b_tokens", [])
        got.extend(toks)
        if len(got) >= 2:
            trigger["armed"] = True  # the interactive request "arrives"

    breq = SlotRequest(ids=pb, max_new=nb, sample=GREEDY,
                       on_tokens=on_b_tokens,
                       on_done=lambda t, s: results.__setitem__("b", (t, s)),
                       tenant="bulk", priority="batch")
    ireq = SlotRequest(ids=pi, max_new=ni, sample=GREEDY,
                       on_done=lambda t, s: results.__setitem__("i", (t, s)),
                       tenant="alice", priority="interactive")

    def feed():
        if not state["fed_b"]:
            state["fed_b"] = True
            return breq
        if trigger["armed"] and not state["fed_i"]:
            state["fed_i"] = True
            return ireq
        return None

    engine = ContinuousEngine(
        gen, slots=1, chunk=4, stop_tokens=(), paged=rt, spec=spec,
        preempt_hint=lambda: trigger["armed"] and not state["fed_i"],
        on_preempt=preempts.append)
    stats = engine.run(feed)

    assert stats["preempted"] == 1, "the preemption never fired"
    assert preempts == ["bulk"]
    # BYTE-IDENTITY: both rows match their uninterrupted solo runs
    assert results["i"][0] == solo_i
    assert results["b"][0] == solo_b
    # the batch row's stats report the ORIGINAL request shape + the park
    bstats = results["b"][1]
    assert bstats["preempted"] == 1
    assert bstats["prompt_tokens"] == len(pb)
    assert bstats["generated_tokens"] == len(solo_b) == nb
    # streamed tokens: prior occupancy + resumed continuation, no gaps or
    # repeats (the parked entry re-delivers nothing)
    assert results["b_tokens"] == solo_b
    # pool leak-free: every block (retained refs included) returned
    assert rt.pool.n_free == free0


def test_preempt_conservation_and_flight_records(gen):
    """test_accounting's chip-second conservation invariant holds with a
    preempted slot: per-tenant chip-seconds still sum to the waves' wall
    time, the preempted slot's tenant is billed for BOTH occupancies,
    and the flight ring carries the preempt record + priority splits."""
    from tpustack.obs import accounting
    from tpustack.obs import flight as obs_flight

    led = accounting.TenantLedger(Registry(), cardinality=8)
    rec = obs_flight.FlightRecorder("qos-conservation", capacity=512)
    rt = make_runtime(gen)
    trigger = {"armed": False}
    state = {"fed_b": False, "fed_i": False}
    results = {}

    def on_b_tokens(toks):
        got = results.setdefault("bt", [])
        got.extend(toks)
        if len(got) >= 2:
            trigger["armed"] = True

    breq = SlotRequest(ids=[5, 6, 7], max_new=12, sample=GREEDY,
                       on_tokens=on_b_tokens, tenant="bulk",
                       priority="batch")
    ireq = SlotRequest(ids=[9, 10], max_new=5, sample=GREEDY,
                       tenant="alice", priority="interactive")

    def feed():
        if not state["fed_b"]:
            state["fed_b"] = True
            return breq
        if trigger["armed"] and not state["fed_i"]:
            state["fed_i"] = True
            return ireq
        return None

    engine = ContinuousEngine(
        gen, slots=1, chunk=4, stop_tokens=(), paged=rt, flight=rec,
        ledger=led,
        preempt_hint=lambda: trigger["armed"] and not state["fed_i"])
    stats = engine.run(feed)
    assert stats["preempted"] == 1

    recent = rec.recent()
    assert any(r["kind"] == "preempt" and r["priority"] == "batch"
               and r["tenant"] == "bulk" for r in recent)
    waves = [r for r in recent if r["kind"] in ("wave", "verify")]
    # every occupied wave carries its priority split
    for r in waves:
        if r["occupancy"]:
            assert r.get("priorities"), r
            assert sum(r["priorities"].values()) == r["occupancy"]
    billed = [r for r in waves if r.get("wave_s") and r.get("tenants")]
    busy = sum(r["wave_s"] for r in billed)
    snap = led.snapshot()["tenants"]
    attributed = sum(t["chip_seconds"] for t in snap.values())
    assert attributed == pytest.approx(busy, rel=0.01)
    # both occupancies billed: bulk decoded before AND after the park
    assert snap["bulk"]["chip_seconds"] > 0
    assert snap["alice"]["chip_seconds"] > 0


def test_parked_entry_released_on_cancel(gen):
    """A parked request whose client goes away releases its retained
    blocks when the engine tries to resume it — no leak, no crash."""
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    trigger = {"armed": False}
    state = {"fed_b": False, "fed_i": False}
    cancelled = {"v": False}
    results = {}

    def on_b_tokens(toks):
        got = results.setdefault("bt", [])
        got.extend(toks)
        if len(got) >= 2:
            trigger["armed"] = True

    breq = SlotRequest(ids=[5, 6, 7], max_new=12, sample=GREEDY,
                       on_tokens=on_b_tokens,
                       on_done=lambda t, s: results.__setitem__("b", (t, s)),
                       cancelled=lambda: cancelled["v"], priority="batch")
    ireq = SlotRequest(ids=[9, 10], max_new=4, sample=GREEDY,
                       on_done=lambda t, s: results.__setitem__("i", (t, s)),
                       priority="interactive")

    def feed():
        if not state["fed_b"]:
            state["fed_b"] = True
            return breq
        if trigger["armed"] and not state["fed_i"]:
            state["fed_i"] = True
            cancelled["v"] = True  # the batch client dies while parked
            return ireq
        return None

    engine = ContinuousEngine(
        gen, slots=1, chunk=4, stop_tokens=(), paged=rt,
        preempt_hint=lambda: trigger["armed"] and not state["fed_i"])
    stats = engine.run(feed)
    assert stats["preempted"] == 1
    assert results["i"][0]  # interactive served
    assert results["b"][0] is None  # parked entry reported, never resumed
    assert rt.pool.n_free == free0  # retained blocks released


# ------------------------------------------------- HTTP: quota + /debug
def test_llm_http_quota_429_and_debug_buckets(gen, monkeypatch):
    """End to end over HTTP: an in-quota request completes and its
    measured cost drives the bucket into debt; the next request 429s
    with the tenant's refill ETA and X-Shed-Reason: quota; and
    /debug/tenants serves the live bucket state."""
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    monkeypatch.setenv("TPUSTACK_QOS_POLICY", json.dumps({
        "tenants": {"bulk": {"priority": "batch", "tokens_per_s": 1.0,
                             "burst_tokens": 4.0}}}))
    reg = Registry()
    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="t", max_batch=2, registry=reg)

    async def scenario():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r1 = await client.post(
                "/completion",
                json={"prompt": "hello", "n_predict": 8, "temperature": 0},
                headers={"X-Tenant-Id": "bulk"})
            assert r1.status == 200
            body1 = await r1.json()
            r2 = await client.post(
                "/completion",
                json={"prompt": "again", "n_predict": 8, "temperature": 0},
                headers={"X-Tenant-Id": "bulk"})
            assert r2.status == 429
            assert r2.headers["X-Shed-Reason"] == "quota"
            ra = int(r2.headers["Retry-After"])
            body2 = await r2.json()
            # an unconfigured tenant is untouched by bulk's debt
            r3 = await client.post(
                "/completion",
                json={"prompt": "fine", "n_predict": 4, "temperature": 0},
                headers={"X-Tenant-Id": "alice"})
            assert r3.status == 200
            dbg = await (await client.get("/debug/tenants")).json()
            return body1, body2, ra, dbg
        finally:
            await client.close()

    body1, body2, ra, dbg = _run(scenario())
    spent = body1["tokens_evaluated"] + body1["tokens_predicted"]
    # Retry-After IS the bucket's refill ETA: (spent - burst) / rate,
    # ceil'd — tenant-specific, not the global p50 x depth heuristic
    assert abs(ra - math.ceil(spent - 4.0)) <= 1
    assert body2.get("reason") == "quota"
    q = dbg["qos"]
    assert q["enabled"] and "bulk" in q["tenants"]
    tok = q["tenants"]["bulk"]["buckets"]["tokens"]
    assert tok["level"] < 0 and tok["refill_eta_s"] > 0
    assert q["counters"]["quota_throttle"] == {"batch": 1}


# --------------------------------------------------- the =0 bisection path
def test_qos_off_is_byte_identical(gen):
    """TPUSTACK_QOS=0 subprocess vs the default QoS-on in-process server:
    identical greedy bytes, qos absent from every layer, X-Priority
    ignored, and no qos series minted."""
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="t", max_batch=2, registry=Registry())
    assert server.qos is not None  # defaults ON

    async def reference():
        from aiohttp.test_utils import TestClient, TestServer

        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post(
                "/completion",
                json={"prompt": "hello world", "n_predict": 12,
                      "temperature": 0},
                headers={"X-Priority": "batch"})
            assert r.status == 200
            return (await r.json())["content"]
        finally:
            await client.close()

    expected = _run(reference())

    code = """
import os
os.environ["TPUSTACK_QOS"] = "0"
import asyncio, json
import jax.numpy as jnp
from tpustack.obs import Registry
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_generate import Generator
from tpustack.models.text_tokenizer import ByteTokenizer
from tpustack.serving.llm_server import LLMServer
reg = Registry()
server = LLMServer(generator=Generator(LlamaConfig.tiny(max_seq=64),
                                       dtype=jnp.float32, seed=3),
                   tokenizer=ByteTokenizer(512), model_name="t",
                   max_batch=2, registry=reg)
assert server.qos is None
assert server.resilience.qos is None

async def go():
    from aiohttp.test_utils import TestClient, TestServer
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        r = await client.post(
            "/completion",
            json={"prompt": "hello world", "n_predict": 12,
                  "temperature": 0},
            headers={"X-Priority": "batch"})
        assert r.status == 200
        return (await r.json())["content"]
    finally:
        await client.close()

content = asyncio.new_event_loop().run_until_complete(go())
# X-Priority was ignored: no priority resolved, no qos series minted
assert "tpustack_qos_requests_total{" not in reg.render()
print("CONTENT:" + json.dumps(content))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_QOS="0",
               TPUSTACK_SANITIZE="0")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("CONTENT:"))
    assert json.loads(line[len("CONTENT:"):]) == expected


def test_current_priority_contextvar_default():
    assert qos_mod.current_priority.get() is None
    assert qos_mod.PRIORITIES == ("interactive", "batch")
