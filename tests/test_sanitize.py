"""tpusan — the runtime sanitizer suite's own tests.

The acceptance bar: seed ONE violation of each check class — off-lock
guarded write, AB/BA lock inversion, forced recompile over budget, leaked
KV block on cancel, unclosed span (+ leaked thread) — and assert each is
caught with an actionable report; prove the ``TPUSTACK_SANITIZE=0`` path
leaves hot paths untouched; prove report mode counts the catalog metric
instead of crashing; and prove the instrumented engine still produces
byte-identical output (tier-1 runs the WHOLE suite under the sanitizer
via the pytest plugin, so every existing parity test doubles as evidence;
the explicit checks here are the sanitizer-specific ones).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpustack import sanitize  # noqa: E402
from tpustack.obs.metrics import Registry  # noqa: E402
from tpustack.obs.trace import Tracer  # noqa: E402
from tpustack.sanitize import (SanitizerViolation, TrackedLock,  # noqa: E402
                               locks as san_locks)
from tpustack.serving.kv_pool import (KVBlockPool,  # noqa: E402
                                      PagedKVRuntime, PagedPrefixCache)

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(autouse=True)
def _sanitize_on():
    """Every test here runs with the sanitizer raising (the plugin already
    set that up for tier-1; make the suite self-sufficient standalone) and
    with a fresh lock-order graph (edges recorded by other tests must not
    leak into the inversion fixtures)."""
    sanitize.activate(mode="raise")
    san_locks._reset_graph()
    yield
    sanitize.activate(mode="raise")


def test_pytest_plugin_enabled_sanitizer_for_this_run():
    """The tier-1 acceptance bar: the plugin defaulted TPUSTACK_SANITIZE=1
    for the whole run (explicit =0 in the caller's env is the bisection
    escape hatch and skips this assert)."""
    val = os.environ.get("TPUSTACK_SANITIZE")
    if val == "0":
        pytest.skip("explicit TPUSTACK_SANITIZE=0 bisection run")
    assert val == "1"
    assert os.environ.get("TPUSTACK_SANITIZE_MODE", "raise") == "raise"


# ------------------------------------------------------ guarded-by (writes)
def test_off_lock_guarded_write_raises_at_faulting_line():
    from tpustack.serving.resilience import ResilienceManager

    rm = ResilienceManager("llm", Registry())
    try:
        with pytest.raises(SanitizerViolation) as ei:
            rm._inflight = 7  # the seeded violation: write without _lock
        msg = str(ei.value)
        assert "guarded_by" in msg and "_inflight" in msg
        assert "_lock" in msg  # actionable: names the lock to take
        with rm._lock:
            rm._inflight = 7  # the fix the report prescribes
        assert rm._inflight == 7  # writes-only: lock-free read allowed
    finally:
        rm.close()


def test_off_lock_container_mutation_raises():
    pool = KVBlockPool(8, 4)
    with pytest.raises(SanitizerViolation) as ei:
        pool._free.append(99)  # deque mutation without the pool lock
    assert "_free" in str(ei.value) and "append" in str(ei.value)
    # the production paths (lock held inside alloc/decref) stay clean
    ids = pool.alloc_tokens(8)
    assert pool.decref(ids) == 2


def test_assert_held_checkpoint():
    lock = TrackedLock(name="test.lock")
    with pytest.raises(SanitizerViolation):
        sanitize.assert_held(lock, "flush")
    with lock:
        sanitize.assert_held(lock, "flush")  # held: no violation


def test_guarded_enforcement_covers_engine_fetch_marks():
    """The satellite audit made concrete: the engine's `_fetch_marks`
    guard (the PR-7 fetch-mark path) is now enforced at runtime — an
    off-lock rebind of the marks list raises."""
    pytest.importorskip("jax")
    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_continuous import ContinuousEngine
    from tpustack.models.llm_generate import Generator

    gen = Generator(LlamaConfig.tiny(max_seq=64))
    eng = ContinuousEngine(gen, slots=2, chunk=4)
    with pytest.raises(SanitizerViolation):
        eng._fetch_marks = []
    with eng._marks_lock:
        eng._fetch_marks = [(0.0, 0, 0)]
    with eng._marks_lock:
        assert len(eng._fetch_marks) == 1


# ------------------------------------------------------------- lock order
def test_ab_ba_inversion_reports_cycle_with_both_stacks():
    a = TrackedLock(name="pool._lock")
    b = TrackedLock(name="trie._lock")
    with a:
        with b:
            pass  # records pool -> trie
    with pytest.raises(SanitizerViolation) as ei:
        with b:
            with a:  # the seeded inversion
                pass
    msg = str(ei.value)
    assert "lock_order" in msg
    assert "pool._lock" in msg and "trie._lock" in msg
    # both stacks in the report: this acquisition AND the recorded order
    assert "this acquisition" in msg and "recorded" in msg
    assert "test_sanitize.py" in msg  # the stacks point at real lines


def test_inversion_reports_once_in_report_mode():
    """An inverted pair on a per-request path must report ONCE, not once
    per acquire — report mode would otherwise drown the production log."""
    sanitize.activate(mode="report")
    a = TrackedLock(name="A1")
    b = TrackedLock(name="B1")
    with a:
        with b:
            pass
    for _ in range(3):  # the same inversion, three times
        with b:
            with a:
                pass
    inversions = [v for v in sanitize.violations_seen()
                  if "lock_order" in v and "A1" in v and "B1" in v]
    assert len(inversions) == 1


def test_trylock_does_not_seed_order_edges():
    """A non-blocking/timed acquire is the deadlock-AVOIDANCE idiom (it
    backs off instead of waiting) — it must not record an ordering edge
    that later flags the legitimate blocking reverse order."""
    a = TrackedLock(name="A3")
    b = TrackedLock(name="B3")
    with a:
        assert b.acquire(blocking=False)  # trylock under a: NOT an edge
        b.release()
    with b:
        with a:  # blocking reverse order: silent, no recorded A3->B3
            pass


def test_consistent_order_is_silent_and_reentrant_rlock_ok():
    a = TrackedLock(name="A2")
    b = TrackedLock(name="B2")
    for _ in range(3):
        with a:
            with b:
                pass
    r = TrackedLock(threading.RLock(), name="R")
    with r:
        with r:  # reentrant: no self-edge, no deadlock report
            assert r.held_by_current()
    assert not r.held_by_current()


def test_async_lock_ownership(event_loop=None):
    import asyncio

    from tpustack.sanitize import TrackedAsyncLock

    lock = TrackedAsyncLock(name="sd._lock")

    async def main():
        assert not lock.held_by_current()
        async with lock:
            assert lock.held_by_current()
        assert not lock.held_by_current()

    asyncio.new_event_loop().run_until_complete(main())


# -------------------------------------------------------------- recompile
def test_forced_recompile_over_budget_is_caught():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    watch = sanitize.CompileWatch()

    @jax.jit
    def f(x):
        return x * 2

    watch.watch("decode", f, budget=1)
    f(jnp.ones(3))
    watch.check("wave boundary")  # cold compile within budget
    assert watch.compiles("decode") == 1
    f(jnp.ones(4))
    f(jnp.ones(5))  # shape-driven retraces past the budget
    with pytest.raises(SanitizerViolation) as ei:
        watch.check("wave boundary")
    msg = str(ei.value)
    assert "recompile" in msg and "decode" in msg and "budget" in msg
    assert "static_argnums" in msg  # actionable: what to inspect


def test_engine_declares_decode_budgets():
    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_continuous import ContinuousEngine
    from tpustack.models.llm_generate import Generator

    gen = Generator(LlamaConfig.tiny(max_seq=64))
    eng = ContinuousEngine(gen, slots=2, chunk=4)
    assert eng._san is not None
    stats = eng._san.stats()
    assert "_decode_scan_paged" in stats
    eng._sanitize_wave()  # fresh engine: nothing compiled, no violation


# ---------------------------------------------------------------- KV leaks
def _runtime(n_blocks=16, block=4, max_seq=64, cache=True):
    pool = KVBlockPool(n_blocks, block)
    trie = PagedPrefixCache(pool) if cache else None
    return PagedKVRuntime(None, pool, max_seq, trie)


def test_leaked_kv_block_on_cancel_is_caught_at_quiesce():
    rt = _runtime()
    # a cancelled request's blocks, never decref'd by anyone (the seeded
    # leak: the failure path dropped the release)
    leaked = rt.pool.alloc_tokens(8)
    with pytest.raises(SanitizerViolation) as ei:
        sanitize.check_kv_quiesce(rt, where="engine drain")
    msg = str(ei.value)
    assert "kv_leak" in msg and "never decref" in msg
    assert "engine drain" in msg
    rt.pool.decref(leaked)
    sanitize.check_kv_quiesce(rt, where="engine drain")  # clean now


def test_quiesce_accounts_cache_resident_and_external_blocks():
    rt = _runtime()
    ids = list(range(100, 108))  # two full blocks of prompt tokens
    blocks = rt.pool.alloc_tokens(8)
    rt.cache.insert(ids, blocks)  # cache takes its own reference
    rt.pool.decref(blocks)  # the slot retires
    sanitize.check_kv_quiesce(rt, where="drain")  # resident == used: clean
    ext = rt.pool.alloc_tokens(4)  # a queued request's pre-allocation
    sanitize.check_kv_quiesce(rt, external_refs=1, where="drain")
    with pytest.raises(SanitizerViolation):
        sanitize.check_kv_quiesce(rt, external_refs=0, where="drain")
    rt.pool.decref(ext)


def test_conservation_catches_double_free_and_refcount_drift():
    pool = KVBlockPool(8, 4)
    ids = pool.alloc_tokens(8)
    sanitize.check_kv_conservation(pool, "wave")  # healthy
    with pool._lock:
        pool._free.append(ids[0])  # free while still referenced
    with pytest.raises(SanitizerViolation) as ei:
        sanitize.check_kv_conservation(pool, "wave")
    assert "free and" in str(ei.value) and "referenced" in str(ei.value)


def test_burst_cancel_leaves_pool_leak_free():
    """End-to-end negative: the engine's real cancel path releases every
    block — quiesce check green after a burst with mid-flight cancels."""
    pytest.importorskip("jax")
    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_continuous import (ContinuousEngine,
                                                SlotRequest)
    from tpustack.models.llm_generate import Generator, SampleConfig

    cfg = LlamaConfig.tiny(max_seq=64)
    gen = Generator(cfg)
    rt = PagedKVRuntime.build(cfg, 2, block=8, pool_blocks=32,
                              prefix_cache=True)
    eng = ContinuousEngine(gen, slots=2, chunk=4, paged=rt)
    cancelled = {"n": 0}

    def make(i):
        def is_cancelled():
            if i % 2 == 0 and cancelled["n"] < 2:
                cancelled["n"] += 1
                return True
            return False
        return SlotRequest(ids=[1 + i, 2, 3], max_new=6,
                           sample=SampleConfig(greedy=True),
                           cancelled=is_cancelled)

    reqs = [make(i) for i in range(4)]
    eng.run(lambda: reqs.pop(0) if reqs else None)
    sanitize.check_kv_quiesce(rt, where="post-run")  # no leak


# ----------------------------------------------------- span / thread leaks
def test_unclosed_span_is_caught_with_names():
    t = Tracer(max_recent=4)
    span = t.start_span("wave")
    with pytest.raises(SanitizerViolation) as ei:
        sanitize.check_span_leaks(t, where="pytest teardown")
    msg = str(ei.value)
    assert "span_leak" in msg and "wave" in msg and ".end()" in msg
    span.end()
    assert sanitize.check_span_leaks(t) == []


def test_leaked_nondaemon_thread_is_caught():
    ev = threading.Event()
    th = threading.Thread(target=ev.wait, name="tpusan-leaked-worker",
                          daemon=False)
    th.start()
    try:
        with pytest.raises(SanitizerViolation) as ei:
            sanitize.check_thread_leaks(where="pytest teardown")
        assert "tpusan-leaked-worker" in str(ei.value)
    finally:
        ev.set()
        th.join()
    assert sanitize.check_thread_leaks() == []


def test_teardown_checks_collect_instead_of_raising(monkeypatch):
    """The pytest-teardown sweep reports (list) whatever the mode — a leak
    at session end must fail the session with a readable list, not die on
    the first raise."""
    from tpustack.obs import trace as obs_trace

    t = Tracer(max_recent=4)
    monkeypatch.setattr(obs_trace, "TRACER", t)
    span = t.start_span("orphan")
    reports = sanitize.teardown_checks()
    assert len(reports) == 1 and "orphan" in reports[0]
    assert sanitize.mode() == "raise"  # sweep restored the mode
    span.end()
    assert sanitize.teardown_checks() == []


# ------------------------------------------------------------ report mode
def test_report_mode_counts_metric_and_never_raises():
    sanitize.activate(mode="report")
    from tpustack.obs import catalog as obs_catalog
    from tpustack.obs import metrics as obs_metrics

    counter = obs_catalog.build(None)[
        "tpustack_sanitizer_violations_total"].labels(check="kv_leak")
    before = counter.value
    rt = _runtime()
    leaked = rt.pool.alloc_tokens(4)
    sanitize.check_kv_quiesce(rt, where="prod drain")  # logs, no raise
    assert counter.value == before + 1
    assert any("kv_leak" in v for v in sanitize.violations_seen())
    rt.pool.decref(leaked)
    # exposition includes the family (scrapeable in production)
    text = obs_metrics.REGISTRY.render()
    assert "tpustack_sanitizer_violations_total" in text


# -------------------------------------------------- the =0 bisection path
def test_sanitize_off_is_uninstrumented():
    """TPUSTACK_SANITIZE=0 must keep hot paths byte-for-byte unchanged: a
    fresh process with the knob off instruments nothing — raw locks, raw
    containers, no descriptors consulted, no compile watch."""
    code = """
import os
os.environ["TPUSTACK_SANITIZE"] = "0"
import collections, threading
from tpustack import sanitize
assert not sanitize.enabled()
from tpustack.obs.metrics import Registry
from tpustack.serving.resilience import ResilienceManager
from tpustack.serving.kv_pool import KVBlockPool
rm = ResilienceManager("llm", Registry())
rm._inflight = 3  # no descriptor, no violation
assert type(rm._lock) is type(threading.Lock())
assert type(rm.__dict__["_service_times"]) is collections.deque
pool = KVBlockPool(8, 4)
assert type(pool.__dict__["_free"]) is collections.deque
pool._free.append(99); pool._free.pop()  # raw deque, no checks
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine
from tpustack.models.llm_generate import Generator
eng = ContinuousEngine(Generator(LlamaConfig.tiny(max_seq=64)), slots=2)
assert eng._san is None
assert "_fetch_marks" not in vars(type(eng))  # no descriptor installed
rm.close()
print("UNINSTRUMENTED-OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=240,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "UNINSTRUMENTED-OK" in proc.stdout


def test_instrumented_engine_output_identical_to_plain():
    """Greedy output through the instrumented engine (sanitize on) equals
    the uninstrumented reference tier-1 has always asserted — the
    enforcement layer observes, never perturbs."""
    pytest.importorskip("jax")
    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_continuous import (ContinuousEngine,
                                                SlotRequest)
    from tpustack.models.llm_generate import Generator, SampleConfig

    gen = Generator(LlamaConfig.tiny(max_seq=64))
    ref, _ = gen.generate([5, 6, 7], max_new_tokens=8,
                          sample=SampleConfig(greedy=True))

    outs = {}

    def run_engine():
        eng = ContinuousEngine(gen, slots=2, chunk=4)
        reqs = [SlotRequest(ids=[5, 6, 7], max_new=8,
                            sample=SampleConfig(greedy=True),
                            on_done=lambda toks, st: outs.update(t=toks))]
        eng.run(lambda: reqs.pop(0) if reqs else None)
        return outs["t"]

    assert run_engine() == list(ref)
