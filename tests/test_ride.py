"""A lone admission rides the decode dispatches (``ContinuousEngine``'s
ride, ``Generator._ride_scan_paged``): while rows decode, a single cold
request whose prompt fits the ride line goes through the decode steps one
segment of its prompt (``Generator.RIDE_SEGMENT`` tokens, whole pool blocks)
a step, and joins the decode rows after the last one.  Bars here:

- the riding row's greedy continuation is the solo path's, and the rows
  it rode past decode as if it had not;
- ONE program for every prompt length and every segment offset;
- groups, long prompts and an idle engine keep their admission programs;
- a cancelled ride gives its blocks back;
- a routed-expert model with window layers rides too.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpustack import sanitize
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.obs.flight import FlightRecorder
from tpustack.serving.kv_pool import PagedKVRuntime

GREEDY = SampleConfig(greedy=True)
BLOCK = 64
SEGMENT = Generator.RIDE_SEGMENT  # two blocks: four segments a 512 line


@pytest.fixture(scope="module")
def gen():
    # a context long enough for a 512-token prompt: the ride line is the
    # admission chunk (512), four segments of two 64-token blocks
    return Generator(LlamaConfig.tiny(max_seq=1024), dtype=jnp.float32,
                     seed=5)


def _prompt(n, seed=0):
    return [int(x) for x in np.random.default_rng(seed).integers(3, 500, n)]


def _engine(gen, slots=2, chunk=4, min_steps=2, **kw):
    rec = FlightRecorder("eng", capacity=4096)
    paged = PagedKVRuntime.build(gen.cfg, slots, block=BLOCK,
                                 dtype=gen.cache_dtype)
    eng = ContinuousEngine(gen, slots=slots, chunk=chunk, flight=rec,
                           paged=paged, min_steps=min_steps, **kw)
    return eng, rec


def _serve(eng, first, later, after=3):
    """``first`` requests fed at once, then ``later`` together ``after``
    polls on, while the first decode.  Returns each request's tokens."""
    outs = {}

    def req(key, ids, n, **kw):
        return SlotRequest(ids=ids, max_new=n, sample=GREEDY,
                           on_done=lambda t, s: outs.__setitem__(key, t),
                           **kw)

    queue = [req(k, ids, n) for k, (ids, n) in first.items()]
    polls = [0]

    def feed():
        polls[0] += 1
        if polls[0] == after:
            queue.extend(req(k, ids, n) for k, (ids, n) in later.items())
        return queue.pop(0) if queue else None

    eng.run(feed)
    return outs


def _solo(gen, ids, n):
    return gen.generate_fused(ids, max_new_tokens=n, sample=GREEDY,
                              chunk=4)[0]


def _kind(rec, kind):
    return [r for r in rec.recent() if r["kind"] == kind]


@pytest.mark.parametrize("n", [1, 63, 64, 65, 128, 129, 512])
def test_a_lone_request_rides_and_decodes_as_the_solo_path(gen, n):
    """A request fed alone while a row decodes rides: its ``prefill``
    record says ``ride`` 1 and ``chunks`` = its segments, waves that carried
    them say how many prompt tokens; its greedy tokens are the solo path's,
    and so are those of the row it rode past."""
    eng, rec = _engine(gen)
    a, b = _prompt(20, 1), _prompt(n, 2)
    outs = _serve(eng, {"a": (a, 40)}, {"b": (b, 9)})
    ride = [r for r in _kind(rec, "prefill") if r.get("ride")]
    assert len(ride) == 1
    segs = -(-n // SEGMENT)
    assert (ride[0]["chunks"], ride[0]["bucket"], ride[0]["program_bucket"],
            ride[0]["prompt_lens"]) == (segs, segs * SEGMENT, 512, [n])
    assert all(k in ride[0] for k in ("queue_s", "admit_s", "prefill_s",
                                      "cached_tokens", "behind_steps"))
    carried = [r["ride_tokens"] for r in _kind(rec, "wave")
               if r.get("ride_tokens")]
    assert sum(carried) == n
    assert outs["b"] == _solo(gen, b, 9)
    assert outs["a"] == _solo(gen, a, 40)


def test_one_ride_program_for_every_length_and_offset(gen):
    """Prompt lengths 1 … 512 and segments at every offset, spread over
    dispatches of one, two and three steps: the ride program is traced
    once for the engine's shape."""
    capacity = 5   # a capacity no other test of this module compiles
    before = Generator._ride_scan_paged._cache_size()
    for m in (1, 2, 3):
        for n in (1, 63, 64, 65, 200, 512):
            eng, rec = _engine(gen, chunk=capacity, min_steps=m)
            _serve(eng, {"a": (_prompt(8, n), 60)}, {"b": (_prompt(n), 3)})
            assert [r.get("ride") for r in _kind(rec, "prefill")] == [None, 1]
    assert Generator._ride_scan_paged._cache_size() - before == 1


def test_two_waiting_requests_take_the_group_admission(gen):
    """Two requests of one bucket fed together while a row decodes are
    admitted as one group by the admission program; nothing rides."""
    eng, rec = _engine(gen, slots=3)
    b, c = _prompt(40, 3), _prompt(50, 4)
    outs = _serve(eng, {"a": (_prompt(10, 5), 30)},
                  {"b": (b, 6), "c": (c, 6)})
    pre = _kind(rec, "prefill")
    assert [r["rows"] for r in pre] == [1, 2]
    assert not any(r.get("ride") for r in pre)
    assert not any(r.get("ride_tokens") for r in _kind(rec, "wave"))
    assert outs["b"] == _solo(gen, b, 6) and outs["c"] == _solo(gen, c, 6)


def test_an_idle_engine_and_a_long_prompt_keep_the_admission_program():
    """A lone request on an idle engine is admitted by the 1-row program; a
    lone prompt longer than the ride line walks its bucket in the
    admission program as before (``chunks`` counted by the walk)."""
    g = Generator(LlamaConfig.tiny(max_seq=256), dtype=jnp.float32, seed=6)
    g.ADMIT_CHUNK = 64          # the instance's: before the first trace
    eng, rec = _engine(g)
    long = _prompt(100, 7)
    outs = _serve(eng, {"a": (_prompt(12, 8), 40)}, {"b": (long, 5)})
    pre = _kind(rec, "prefill")
    assert [(r.get("ride"), r["chunks"], r["program_bucket"])
            for r in pre] == [(None, 1, 16), (None, 2, 128)]
    assert outs["b"] == _solo(g, long, 5)


def test_a_request_cancelled_mid_ride_gives_its_blocks_back(gen):
    """A rider cancelled after its first segment's dispatch stops riding:
    it is answered, its blocks return to the pool, and the sanitizer's
    conservation check holds at every wave boundary."""
    eng, rec = _engine(gen, chunk=1, min_steps=1)  # a ride of 4 dispatches
    done = {}
    state = {"polls": 0}

    def cancelled():
        return sum(1 for r in _kind(rec, "wave") if r.get("ride_tokens")) >= 1

    queue = [SlotRequest(ids=_prompt(16, 9), max_new=60, sample=GREEDY,
                         on_done=lambda t, s: done.__setitem__("a", t))]

    def feed():
        state["polls"] += 1
        if state["polls"] == 3:
            queue.append(SlotRequest(
                ids=_prompt(500, 10), max_new=20, sample=GREEDY,
                cancelled=cancelled,
                on_done=lambda t, s: done.__setitem__("b", t)))
        return queue.pop(0) if queue else None

    seen = sanitize.violations_seen()  # earlier tests' in this process
    eng.run(feed)
    assert done["b"] == [] and len(done["a"]) == 60
    carried = sum(r.get("ride_tokens", 0) for r in _kind(rec, "wave"))
    assert 0 < carried < 500
    assert not any(r.get("ride") for r in _kind(rec, "prefill"))
    assert eng.paged.pool.n_used == 0
    sanitize.check_kv_conservation(eng.paged.pool, where="after the run")
    assert sanitize.violations_seen() == seen


def test_a_routed_expert_model_with_window_layers_rides():
    """The tiny preset with every layer kind: the segment goes through the
    router with the decode rows, obeys the window, and the rider's tokens
    are the solo path's; the ride's counters are its segments' own."""
    g = Generator(LlamaConfig.tiny_moe(max_seq=256), dtype=jnp.float32,
                  seed=7)
    eng, rec = _engine(g)
    a, b = _prompt(24, 11), _prompt(200, 12)
    outs = _serve(eng, {"a": (a, 40)}, {"b": (b, 8)})
    ride = [r for r in _kind(rec, "prefill") if r.get("ride")]
    assert len(ride) == 1 and ride[0]["chunks"] == 2
    # 200 true and 56 padded positions through 3 sparse layers, top-2 of
    # 8 experts of which 4 are held: some of those pairs land here
    assert 0 < ride[0]["moe_pairs"] <= 256 * 3 * 2
    assert ride[0]["moe_layer_calls"] == 3
    assert outs["b"] == _solo(g, b, 8)
    assert outs["a"] == _solo(g, a, 40)
