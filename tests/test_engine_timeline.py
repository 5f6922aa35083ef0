"""The engine's timeline (ISSUE 26): host phase timers on every wave and
admission, the admission's timeline on the ``prefill`` record, stable device
names for every program and scope, and the phases on the profiler's host
plane.  Counts and structure only — no rate is read on the CPU.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpustack.models.llm_continuous import (ContinuousEngine,  # noqa: E402
                                            SlotRequest)
from tpustack.models.llm_generate import Generator, SampleConfig  # noqa: E402
from tpustack.obs.flight import FlightRecorder, PhaseClock  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore")

GREEDY = SampleConfig(greedy=True)
#: every phase the engine may charge; a record's host_s has no other key
PHASES = {"admit", "dispatch", "fetch_wait", "resolve", "resolve_wait",
          "consume", "stream", "record", "gc", "park", "draft", "verify",
          "verify_wait", "other"}
SCOPES = ("attn_qkv", "attn_core", "attn_out", "kv_write", "kv_read", "mlp",
          "lm_head", "sample", "norm", "embed")


@pytest.fixture(scope="module")
def gen():
    from tpustack.models.llama import LlamaConfig

    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def _paged(gen):
    from tpustack.serving.kv_pool import PagedKVRuntime

    return PagedKVRuntime.build(gen.cfg, 2, block=8, pool_blocks=32)


def _spec():
    from tpustack.serving.speculative import SpecConfig

    return SpecConfig(tokens=3)


def _run(gen, prompts, max_new=9, delay_s=0.0, **engine_kw):
    """Run ``prompts`` through a 2-slot engine under a recorder.  ``feed``
    sleeps ``delay_s`` before it hands a request out: the wait a request
    spends queued at the server."""
    rec = FlightRecorder("eng", capacity=1024)
    eng = ContinuousEngine(gen, slots=2, chunk=4, flight=rec, **engine_kw)
    t_enq = time.time()
    q = [SlotRequest(ids=list(p), max_new=max_new, sample=GREEDY,
                     t_enqueue=t_enq) for p in prompts]

    def feed():
        if not q:
            return None
        if delay_s:
            time.sleep(delay_s)
        return q.pop(0)

    stats = eng.run(feed)
    return rec.recent(), stats


ENGINES = {
    "plain": lambda gen: {},
    "plain_paged": lambda gen: {"paged": _paged(gen)},
    "spec": lambda gen: {"spec": _spec()},
    "spec_paged": lambda gen: {"spec": _spec(), "paged": _paged(gen)},
}
#: repetitive, so that prompt lookup drafts and verify waves run
REPETITIVE = [7, 11, 13, 7, 11, 13, 7, 11, 13, 7, 11]


# ------------------------------------------------------ (a) the flight record
def test_phase_clock_is_exclusive_and_closes():
    clock = PhaseClock()
    t0 = time.perf_counter()
    with clock.phase("resolve"):
        time.sleep(0.01)
        with clock.phase("resolve_wait"):
            time.sleep(0.02)
    nested = time.perf_counter() - t0
    time.sleep(0.005)  # no phase open: other
    with clock.phase("consume"):
        got = clock.take()  # an open phase is charged up to now
        whole = time.perf_counter() - t0
    assert set(got) == {"resolve", "resolve_wait", "consume", "other"}
    assert got["resolve_wait"] >= 0.019 and got["resolve"] >= 0.009
    # exclusive: the inner wait is not charged to its parent as well
    assert got["resolve"] + got["resolve_wait"] == pytest.approx(
        nested, abs=2e-3)
    assert got["other"] >= 0.004
    assert sum(got.values()) == pytest.approx(whole, abs=2e-3)
    rest = clock.take()  # the next interval starts at the take
    assert set(rest) <= {"consume", "other"}
    assert sum(rest.values()) < 0.005


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_wave_records_carry_host_seconds_that_close(gen, engine):
    recs, stats = _run(gen, [REPETITIVE, REPETITIVE[:7], [5, 6, 7]],
                       max_new=16, **ENGINES[engine](gen))
    waves = [r for r in recs if r["kind"] in ("wave", "verify")]
    assert waves
    if engine.startswith("spec") and stats.get("spec_dispatches"):
        assert any(r["kind"] == "verify" for r in waves)
    timed = 0
    for r in waves:
        host = r["host_s"]
        assert host and set(host) <= PHASES, host
        assert all(v >= 0 for v in host.values())
        wait = "verify_wait" if r["kind"] == "verify" else "fetch_wait"
        assert wait in host
        if r["wave_s"] is None:
            continue  # a run's first record: no interval to hold it to
        timed += 1
        # the phases, with `other`, are the whole interval: within 2% of
        # wave_s (and the rounding of a dozen 6-digit values)
        assert sum(host.values()) == pytest.approx(r["wave_s"], rel=0.02,
                                                   abs=5e-5), r
    assert timed >= 1
    # a plain engine never charges the speculative phases
    if not engine.startswith("spec"):
        assert not any({"draft", "verify", "verify_wait"} & set(r["host_s"])
                       for r in waves)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_prefill_records_carry_the_admission_timeline(gen, engine):
    prompts = [[5, 6, 7], [5, 6, 7, 8, 9], REPETITIVE]
    delay = 0.05
    recs, stats = _run(gen, prompts, delay_s=delay, **ENGINES[engine](gen))
    pre = [r for r in recs if r["kind"] == "prefill"]
    assert sum(r["rows"] for r in pre) == len(prompts)
    seen = []
    for r in pre:
        n = r["rows"]
        assert len(r["queue_s"]) == len(r["admit_s"]) == n
        assert len(r["prompt_lens"]) == n
        assert r["prefill_s"] >= 0
        # one padded bucket for the group, no smaller than its longest row:
        # ``bucket`` is the positions computed a row — ``chunks`` chunks of
        # the compiled ``program_bucket`` where the admission walks it (PR
        # 34: above Generator.ADMIT_CHUNK), all of it in one shot here
        assert r["bucket"] >= max(r["prompt_lens"])
        assert r["chunks"] == 1 and r["program_bucket"] == r["bucket"]
        assert r["prompt_tokens"] == sum(r["prompt_lens"])
        # every request waited at least the delay feed() imposed on it
        assert all(q >= delay * 0.99 for q in r["queue_s"]), r
        assert all(a >= 0 for a in r["admit_s"])
        seen += r["prompt_lens"]
    assert sorted(seen) == sorted(len(p) for p in prompts)
    # the k-th request handed out waited for k delays
    assert max(q for r in pre for q in r["queue_s"]) >= 3 * delay * 0.99


def test_requests_without_an_enqueue_time_read_none(gen):
    rec = FlightRecorder("eng", capacity=64)
    eng = ContinuousEngine(gen, slots=2, chunk=4, flight=rec)
    q = [SlotRequest(ids=[5, 6, 7], max_new=3, sample=GREEDY)]
    eng.run(lambda: q.pop(0) if q else None)
    pre = [r for r in rec.recent() if r["kind"] == "prefill"]
    assert pre and pre[0]["queue_s"] == [None]  # never a made-up 0
    assert pre[0]["admit_s"][0] is not None


def test_a_preempted_rows_resume_carries_no_admission_timeline(gen):
    # a batch row is parked for an interactive one and resumed: the resume
    # is admitted again (a prefill record of its own), but its first token
    # is long out — no queue_s, no admit_s, so no reader counts it as a
    # time to first token; the row's stats keep the wait before its FIRST slot
    rec = FlightRecorder("eng", capacity=512)
    armed, fed, done = {"v": False}, [], {}

    def on_tokens(toks):
        armed["v"] = True  # the interactive request "arrives"

    t_enq = time.time() - 0.25
    batch = SlotRequest(ids=[5, 6, 7, 8], max_new=14, sample=GREEDY,
                        on_tokens=on_tokens, priority="batch",
                        on_done=lambda t, st: done.__setitem__("b", st),
                        t_enqueue=t_enq)
    inter = SlotRequest(ids=[9, 10, 11], max_new=6, sample=GREEDY,
                        priority="interactive", t_enqueue=t_enq)

    def feed():
        if not fed:
            fed.append(batch)
            return batch
        if armed["v"] and len(fed) == 1:
            fed.append(inter)
            return inter
        return None

    eng = ContinuousEngine(
        gen, slots=1, chunk=4, stop_tokens=(), paged=_paged(gen), flight=rec,
        preempt_hint=lambda: armed["v"] and len(fed) == 1)
    stats = eng.run(feed)
    assert stats["preempted"] == 1
    pre = [r for r in rec.recent() if r["kind"] == "prefill"]
    assert len(pre) == 3  # batch, interactive, the batch row's resume
    timed = [r for r in pre if r["queue_s"] != [None]]
    assert len(timed) == 2
    assert all(r["queue_s"][0] >= 0.25 and r["admit_s"][0] >= 0
               for r in timed)
    resume = [r for r in pre if r["queue_s"] == [None]]
    assert len(resume) == 1 and resume[0]["admit_s"] == [None]
    assert resume[0]["cached_tokens"] > 0  # the warm start, not a prompt
    assert done["b"]["preempted"] == 1
    assert done["b"]["queue_s"] == pytest.approx(batch.t_handed - t_enq)


def test_an_engine_without_a_recorder_runs_no_clock(gen):
    # nothing would read it: take() is only called for a flight record
    eng = ContinuousEngine(gen, slots=2, chunk=4)
    assert eng._clock is None
    q = [SlotRequest(ids=[5, 6, 7], max_new=5, sample=GREEDY)]
    assert eng.run(lambda: q.pop(0) if q else None)["requests"] == 1


def test_the_server_reads_one_queue_wait(gen):
    # enqueue -> handed out by feed() is stamped once: the flight record's
    # queue_s and the queue_wait phase histogram hold the same seconds
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs.metrics import Registry
    from tpustack.serving.llm_server import LLMServer

    reg = Registry()
    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="t", max_batch=2, registry=reg)

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post("/completion", json={
                "prompt": "hi", "n_predict": 3, "temperature": 0})
            assert r.status == 200
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())
    pre = [r for r in server.flight.recent() if r["kind"] == "prefill"]
    assert len(pre) == 1 and pre[0]["queue_s"][0] is not None
    observed = reg.get_sample_value(
        "tpustack_request_phase_latency_seconds_sum",
        {"server": "llm", "phase": "queue_wait"})
    assert observed == pytest.approx(pre[0]["queue_s"][0], abs=2e-6)


@pytest.mark.parametrize("engine", ["plain", "plain_paged"])
def test_ctx_tokens_is_prompt_plus_generated_of_the_rows(gen, engine):
    # two rows admitted together, no stop token: wave k is fetched with
    # each row holding its first token and 4 more per earlier wave
    prompts = [[5, 6, 7], [5, 6, 7, 8, 9]]
    recs, _ = _run(gen, prompts, max_new=9, **ENGINES[engine](gen))
    waves = [r for r in recs if r["kind"] == "wave"]
    assert [r["tokens"] for r in waves] == [8, 8]
    assert [r["ctx_tokens"] for r in waves] == [
        (3 + 1) + (5 + 1), (3 + 5) + (5 + 5)]


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_records_say_how_long_a_dispatch_ran_and_what_an_admission_waited_behind(
        gen, engine):
    """PR 31's two fields, by name: ``cut`` on every ``wave`` record, beside
    ``weight_passes`` = the steps that ran; ``behind_steps`` on every
    ``prefill`` record.  A ``verify`` record carries no ``cut``."""
    recs, stats = _run(gen, [REPETITIVE, REPETITIVE[:7], [5, 6, 7]],
                       max_new=11, min_steps=1, **ENGINES[engine](gen))
    waves = [r for r in recs if r["kind"] == "wave"]
    assert waves and all(
        r["cut"] in ("full", "row_end", "seating") for r in waves)
    assert all(1 <= r["weight_passes"] <= 4 for r in waves)
    assert all((r["cut"] == "full") == (r["weight_passes"] == 4)
               for r in waves)
    assert all("cut" not in r and r["weight_passes"] == 1
               for r in recs if r["kind"] == "verify")
    assert sum(r["weight_passes"] for r in recs
               if r["kind"] in ("wave", "verify")) == \
        stats["decode_weight_passes"]
    pre = [r for r in recs if r["kind"] == "prefill"]
    assert pre and all(isinstance(r["behind_steps"], int)
                       and 0 <= r["behind_steps"] <= 2 * 4 for r in pre)
    assert pre[0]["behind_steps"] == 0  # an idle engine's first admission


def test_ctx_tokens_under_speculation_counts_what_was_delivered(gen):
    delivered = {"n": 0}
    rec = FlightRecorder("eng", capacity=256)
    eng = ContinuousEngine(gen, slots=2, chunk=4, flight=rec, spec=_spec())
    q = [SlotRequest(ids=list(REPETITIVE), max_new=24, sample=GREEDY,
                     on_tokens=lambda t: delivered.__setitem__(
                         "n", delivered["n"] + len(t)))]
    eng.run(lambda: q.pop(0) if q else None)
    waves = [r for r in rec.recent() if r["kind"] in ("wave", "verify")]
    # one row: a wave is fetched holding the prompt and all earlier tokens
    before = 1  # the admission-sampled first token
    for r in waves:
        assert r["ctx_tokens"] == len(REPETITIVE) + before, r
        before += r["tokens"]
    assert before == delivered["n"]


# ------------------------------------------------- (b) names on the device
def _lowered_text(traced):
    return traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def _has_scope(text, name):
    # a whole component of an operation's name stack (inside a scan's body
    # the stack starts at the scope: "sample/cond")
    return re.search(r'["/]' + name + r'[/"]', text) is not None


@pytest.fixture(scope="module")
def paged_programs(gen):
    """The served path's programs, lowered for TPU at the tiny preset:
    ``{program name: lowered text}``."""
    from tpustack.models.llama import init_kv_caches, init_kv_pool

    cfg = dataclasses.replace(gen.cfg, kv_quant="int8")
    g = Generator(cfg, dtype=jnp.float32, seed=3)
    sds = jax.ShapeDtypeStruct
    B, blk, n_blocks, n, bucket, K = 2, 8, 17, 2, 16, 3
    nb = cfg.max_seq // blk
    pool = jax.eval_shape(
        lambda: init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32))
    caches = jax.eval_shape(
        lambda: init_kv_caches(cfg, n, dtype=jnp.float32))

    def i32(*s):
        return sds(s, jnp.int32)

    def f32(*s):
        return sds(s, jnp.float32)

    keys = sds((B, 2), jnp.uint32)
    flags = sds((B,), jnp.bool_)
    slot_state = (i32(B), i32(B), i32(B, 1), f32(B), i32(B), flags, keys)
    row = (f32(n), i32(n), sds((n,), jnp.bool_))
    P = g.params
    traced = {
        "_decode_scan_paged": Generator._decode_scan_paged.trace(
            g, P, i32(B, 1), i32(B), i32(B), pool, i32(B, nb), keys,
            f32(B), i32(B), flags, 4, i32(), flash=False),
        "_spec_verify_paged": Generator._spec_verify_paged.trace(
            g, P, i32(B, 1), i32(B, K), i32(B), i32(B), i32(B), pool,
            i32(B, nb), keys, f32(B), i32(B), flags, K, flash=True),
        "_admit_fused_paged": Generator._admit_fused_paged.trace(
            g, P, i32(n, bucket), pool, i32(n, nb), i32(n), i32(n), i32(n),
            sds((n,), jnp.uint32), *slot_state, *row),
        "_admit_prefix_paged": Generator._admit_prefix_paged.trace(
            g, P, i32(1, bucket), pool, i32(1, nb), i32(), i32(1), i32(1),
            i32(1), sds((1,), jnp.uint32), *slot_state, f32(1), i32(1),
            sds((1,), jnp.bool_)),
        "_prefill": Generator._prefill.trace(
            g, P, i32(n, bucket), i32(n), caches),
        "_prefill_chunk": Generator._prefill_chunk.trace(
            g, P, i32(n, bucket), i32(), i32(n), caches),
    }
    # the same admission program of a bucket above the generator's chunk
    # (PR 34): it walks the bucket, 4 chunks of 8 here
    walk = Generator(cfg, params=P, dtype=jnp.float32)
    walk.ADMIT_CHUNK = 8
    traced["_admit_fused_paged/walk"] = Generator._admit_fused_paged.trace(
        walk, P, i32(n, 32), pool, i32(n, nb), i32(n), i32(n), i32(n),
        sds((n,), jnp.uint32), *slot_state, *row)
    return {name: _lowered_text(t) for name, t in traced.items()}


#: the jitted programs the served path runs keep their function names: the
#: trace's ``XLA Modules`` line shows them as ``jit_<name>(...)``
PINNED_PROGRAMS = ("_decode_scan_paged", "_admit_fused_paged",
                   "_admit_prefix_paged", "_spec_verify_paged", "_prefill",
                   "_prefill_chunk")


@pytest.mark.parametrize("program", PINNED_PROGRAMS)
def test_program_names_are_pinned(paged_programs, program):
    assert re.search(r"module @jit_" + program + r"\b",
                     paged_programs[program])


@pytest.mark.parametrize("scope", SCOPES)
def test_decode_program_names_every_scope(paged_programs, scope):
    assert _has_scope(paged_programs["_decode_scan_paged"], scope)


@pytest.mark.parametrize("program", ["_admit_fused_paged",
                                     "_admit_fused_paged/walk",
                                     "_admit_prefix_paged",
                                     "_spec_verify_paged"])
def test_admission_and_verify_programs_name_the_scopes(paged_programs,
                                                       program):
    text = paged_programs[program]
    want = set(SCOPES) - {"kv_read"}
    if program == "_admit_prefix_paged":
        want.add("kv_read")  # the warm start gathers the hit row's line
    if program == "_admit_fused_paged/walk":
        # a chunk reads the row line it attends (an int8 one dequantised),
        # through the k-streaming kernel, under the program's own name
        want.add("kv_read")
        assert "flash_kstream" in text and "stablehlo.while" in text
        assert re.search(r"module @jit__admit_fused_paged\b", text)
    missing = [s for s in sorted(want) if not _has_scope(text, s)]
    assert not missing, missing
    if program == "_spec_verify_paged":
        assert "paged_attention" in text  # flash=True: the kernel, by name


# ------------------------------------ (a2) where consume and collections go
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_consume_splits_into_stream_and_record(gen, engine):
    recs, _ = _run(gen, [REPETITIVE, REPETITIVE[:7], [5, 6, 7]],
                   max_new=16, **ENGINES[engine](gen))
    waves = [r for r in recs if r["kind"] in ("wave", "verify")]
    assert len(waves) >= 2
    for r in waves:
        # every wave hands its tokens on and is recorded: both phases are
        # in every interval, beside what is left of consume
        assert {"stream", "record"} <= set(r["host_s"]), r["host_s"]
        if r["wave_s"] is not None:
            assert sum(r["host_s"].values()) == pytest.approx(
                r["wave_s"], rel=0.02, abs=5e-5), r


@pytest.mark.parametrize("where", ["admit", "wait"])
def test_a_collection_on_the_engine_thread_is_charged_to_gc(
        gen, where, monkeypatch):
    """A full collection forced inside the admission phase, or inside the
    fetch of a dispatch (a wait), lands in ``host_s.gc``, and the phases
    still add up to ``wave_s``."""
    import gc

    pauses = []

    def collect():
        t0 = time.perf_counter()
        gc.collect()
        pauses.append(time.perf_counter() - t0)

    rec = FlightRecorder("eng", capacity=1024)
    eng = ContinuousEngine(gen, slots=2, chunk=4, flight=rec,
                           paged=_paged(gen))
    q = [SlotRequest(ids=list(p), max_new=16, sample=GREEDY)
         for p in (REPETITIVE, [5, 6, 7], [8, 9, 10, 11])]
    handed = []

    def feed():
        if not q:
            return None
        handed.append(1)
        if where == "admit" and len(handed) == 3:  # waves are running
            collect()
        return q.pop(0)

    if where == "wait":
        real, calls = jax.device_get, []

        def device_get(x):
            calls.append(1)
            if len(calls) == 6:
                collect()
            return real(x)

        monkeypatch.setattr(jax, "device_get", device_get)
    eng.run(feed)
    assert len(pauses) == 1
    waves = [r for r in rec.recent()
             if r["kind"] == "wave" and r["wave_s"] is not None]
    hit = [r for r in waves if r["host_s"].get("gc", 0.0)
           >= 0.9 * pauses[0]]
    assert hit, (pauses, [r["host_s"] for r in waves])
    for r in waves:
        assert sum(r["host_s"].values()) == pytest.approx(
            r["wave_s"], rel=0.02, abs=5e-5), r


def test_the_gc_hook_lives_while_the_engine_runs(gen):
    import gc

    from tpustack.obs import flight

    seen = []
    rec = FlightRecorder("eng", capacity=64)
    eng = ContinuousEngine(gen, slots=2, chunk=4, flight=rec)
    q = [SlotRequest(ids=[5, 6, 7], max_new=5, sample=GREEDY)]

    def feed():
        seen.append(flight._on_gc in gc.callbacks)
        return q.pop(0) if q else None

    eng.run(feed)
    assert seen and all(seen)
    assert flight._on_gc not in gc.callbacks

    def broken():
        raise RuntimeError("feed failed")

    with pytest.raises(RuntimeError):
        eng.run(broken)
    assert flight._on_gc not in gc.callbacks  # a failed run removes it too


def test_a_collection_elsewhere_is_not_charged_to_the_engine_clock():
    import gc
    import threading

    from tpustack.obs import flight

    clock = PhaseClock()
    flight.gc_attach(clock)
    was_on = gc.isenabled()
    gc.disable()  # no collection of this thread's own but the forced one
    try:
        with clock.phase("fetch_wait"):
            t = threading.Thread(target=gc.collect)
            t.start()
            t.join(timeout=60)
        assert not t.is_alive()
        elsewhere = clock.take()
        with clock.phase("fetch_wait"):
            gc.collect()
        here = clock.take()
    finally:
        if was_on:
            gc.enable()
        flight.gc_detach()
    assert "gc" not in elsewhere and elsewhere["fetch_wait"] > 0
    # the pause inside a wait is the gc phase's, not the wait's
    assert here["gc"] > here["fetch_wait"]
    assert flight._on_gc not in gc.callbacks


def test_fetch_marks_keep_the_first_and_the_latest(gen):
    """Two marks, not one a wave: the rate and the Retry-After estimate
    read what they read from the whole list."""
    from tpustack.models.llm_continuous import _Slot

    eng = ContinuousEngine(gen, slots=2, chunk=2, min_steps=2,
                           flight=FlightRecorder("eng", capacity=1024))
    every, sizes = [], []
    mark = eng._mark_fetch

    def tracked(slots):
        mark(slots)
        with eng._marks_lock:
            every.append(eng._fetch_marks[-1])
            sizes.append(len(eng._fetch_marks))

    eng._mark_fetch = tracked
    q = [SlotRequest(ids=list(p), max_new=24, sample=GREEDY)
         for p in ([5, 6, 7], [8, 9], [10, 11, 12, 13], [14])]
    stats = eng.run(lambda: q.pop(0) if q else None)
    assert len(every) >= 10 and max(sizes) == 2
    with eng._marks_lock:
        assert eng._fetch_marks == [every[0], every[-1]]
    (t0, c0, _), (t1, c1, _) = every[0], every[-1]
    assert stats["steady_tokens_per_s"] == pytest.approx(
        (c1 - c0) / (t1 - t0))
    s = _Slot()
    s.req = SlotRequest(ids=[1], max_new=100, sample=GREEDY)
    s.budget, s.out, s.blocks, s.stride_ema = 100, [0], [1, 2, 3], 2.0
    eng._slots_view = [s]
    two = eng.projected_block_release_s(3)
    with eng._marks_lock:
        eng._fetch_marks = list(every)
    assert eng.projected_block_release_s(3) == pytest.approx(two)


# --------------------------------------------- (c) the profiler's host plane
def test_a_capture_holds_the_engine_phases_on_a_host_line(gen, tmp_path):
    from jax.profiler import ProfileData

    _run(gen, [[5, 6, 7]], max_new=5)  # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        recs, _ = _run(gen, [[5, 6, 7], [5, 6, 7, 8]], max_new=13)
    assert sum(r["kind"] == "wave" for r in recs) >= 3
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine/"):
                    names[e.name] = names.get(e.name, 0) + 1
    assert names.get("engine/fetch_wait", 0) >= 3, names
    assert names.get("engine/admit", 0) >= 1, names
    assert {n.split("/", 1)[1] for n in names} <= PHASES


def test_a_capture_holds_collections_on_the_engine_line(gen, tmp_path):
    import gc

    from jax.profiler import ProfileData

    _run(gen, [[5, 6, 7]], max_new=5)  # compile outside the capture
    q = [SlotRequest(ids=[5, 6, 7], max_new=9, sample=GREEDY)]

    def feed():
        if not q:
            return None
        gc.collect()  # inside engine/admit
        return q.pop(0)

    eng = ContinuousEngine(gen, slots=2, chunk=4,
                           flight=FlightRecorder("eng", capacity=64))
    with jax.profiler.trace(str(tmp_path)):
        eng.run(feed)
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = [[e for e in line.events]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    engine = [evs for evs in lines
              if any(e.name.startswith("engine/") for e in evs)]
    assert len(engine) == 1
    admits = [e for e in engine[0] if e.name == "engine/admit"]
    full = [e for e in engine[0] if e.name == "host/gc2"]
    assert full, sorted({e.name for e in engine[0]})
    # the forced collection is nested in the admission that ran it
    assert any(a.start_ns <= g.start_ns and g.end_ns <= a.end_ns
               for g in full for a in admits)


# ------------------------------- (d) layer kinds: routed experts and windows
MOE_SCOPES = ("moe_router", "moe_experts", "moe_shared", "moe_combine")
MOE_FIELDS = ("moe_layer_calls", "moe_pairs", "moe_experts_touched",
              "moe_max_expert_tokens")


@pytest.fixture(scope="module")
def moe_gen():
    from tpustack.models.llama import LlamaConfig

    return Generator(LlamaConfig.tiny_moe(max_seq=64), dtype=jnp.float32,
                     seed=5)


@pytest.mark.parametrize("engine", ["plain_paged", "spec_paged"])
def test_engine_serves_layer_kinds_with_rows_joining_and_leaving(
        moe_gen, engine):
    """Five requests of different lengths through two slots: rows join and
    leave a running batch of a model with window and full attention and
    routed experts; in-place pool reads and the gather say the same tokens,
    and every record carries the routed-expert counters (``wave``/
    ``verify`` also the window-cut context) within what they can be."""
    prompts = [REPETITIVE, REPETITIVE[:7], [5, 6, 7], [9] * 12, [3, 4]]
    outs = {}
    for flash in (True, False):
        res = {}
        rec = FlightRecorder("eng", capacity=1024)
        eng = ContinuousEngine(moe_gen, slots=2, chunk=4, flight=rec,
                               paged_flash=flash,
                               **ENGINES[engine](moe_gen))
        q = [SlotRequest(ids=list(p), max_new=6 + 3 * i, sample=GREEDY,
                         on_done=lambda t, s, i=i: res.__setitem__(i, t))
             for i, p in enumerate(prompts)]
        eng.run(lambda: q.pop(0) if q else None)
        outs[flash] = res
        recs = rec.recent()
    assert outs[True] == outs[False]
    assert [len(outs[True][i]) for i in range(5)] == [6, 9, 12, 15, 18]
    sparse, held, window = 3, 4, 8
    for r in recs:
        if r["kind"] not in ("wave", "verify", "prefill"):
            continue
        assert all(isinstance(r.get(f), int) for f in MOE_FIELDS), r
        passes = {"wave": 4, "verify": 1, "prefill": 1}[r["kind"]]
        calls = r["moe_layer_calls"]
        assert calls == sparse * passes
        assert 0 < r["moe_experts_touched"] <= held * calls
        assert r["moe_experts_touched"] <= r["moe_pairs"]
        # the fullest expert of a call holds at least the call's mean
        assert (r["moe_pairs"] / held <= r["moe_max_expert_tokens"]
                <= r["moe_pairs"])
        if r["kind"] != "prefill":
            assert 0 < r["ctx_tokens_window"] <= min(
                r["ctx_tokens"], 2 * window)
    kinds = {r["kind"] for r in recs}
    assert {"wave", "prefill"} <= kinds


def test_a_dense_model_records_no_layer_kind_fields(gen):
    recs, _ = _run(gen, [[5, 6, 7]], max_new=6, paged=_paged(gen))
    for r in recs:
        assert not any(k.startswith("moe_") for k in r), r
        assert "ctx_tokens_window" not in r


@pytest.fixture(scope="module")
def moe_programs(moe_gen):
    """``paged_programs`` for the model with every layer kind."""
    from tpustack.models.llama import init_kv_pool

    cfg = dataclasses.replace(moe_gen.cfg, kv_quant="int8")
    g = Generator(cfg, dtype=jnp.float32, seed=3)
    sds = jax.ShapeDtypeStruct
    B, blk, n_blocks, n, bucket = 2, 8, 17, 2, 16
    nb = cfg.max_seq // blk
    pool = jax.eval_shape(
        lambda: init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32))
    i32 = lambda *s: sds(s, jnp.int32)
    f32 = lambda *s: sds(s, jnp.float32)
    keys = sds((B, 2), jnp.uint32)
    flags = sds((B,), jnp.bool_)
    slot_state = (i32(B), i32(B), i32(B, 1), f32(B), i32(B), flags, keys)
    row = (f32(n), i32(n), sds((n,), jnp.bool_))
    traced = {
        "_decode_scan_paged": Generator._decode_scan_paged.trace(
            g, g.params, i32(B, 1), i32(B), i32(B), pool, i32(B, nb), keys,
            f32(B), i32(B), flags, 4, i32(), flash=True),
        "_admit_fused_paged": Generator._admit_fused_paged.trace(
            g, g.params, i32(n, bucket), pool, i32(n, nb), i32(n), i32(n),
            i32(n), sds((n,), jnp.uint32), *slot_state, *row),
    }
    return {name: _lowered_text(t) for name, t in traced.items()}


@pytest.mark.parametrize("program", ["_decode_scan_paged",
                                     "_admit_fused_paged"])
@pytest.mark.parametrize("scope", MOE_SCOPES + ("mlp", "attn_core"))
def test_layer_kind_programs_name_the_expert_scopes(moe_programs, program,
                                                    scope):
    assert _has_scope(moe_programs[program], scope)


@pytest.mark.parametrize("program,kernels", [
    ("_decode_scan_paged", ("moe_gmm", "paged_attention")),
    ("_admit_fused_paged", ("moe_gmm",))])
def test_layer_kind_programs_name_their_kernels(moe_programs, program,
                                                kernels):
    for kernel in kernels:
        assert kernel in moe_programs[program]
