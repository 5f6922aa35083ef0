"""Speculative decoding on the continuous engine — the drafters, the
verify step, the acceptance throttle, and the HTTP surface.

The ISSUE's acceptance bars: greedy outputs byte-identical speculation on
vs off across solo / engine / HTTP, own and handed-in pool, int8 KV, and with
mid-stream cancellation in the mix; the plain path byte-for-byte
unchanged at ``TPUSTACK_SPEC_TOKENS=0``; rejected draft KV never lands
(paged block accounting stays capacity-true — the leak bar lives in
test_kv_pool.py); Retry-After projection uses the live per-slot stride
EMA; and the ``bench_llm --speculative --tiny`` smoke shows acceptance
> 0 with more tokens per weight pass than plain decode on repetitive
traffic."""

import asyncio
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.serving.kv_pool import PagedKVRuntime, eta_until_blocks
from tpustack.serving.speculative import (DraftModelDrafter,
                                          PromptLookupDrafter, SpecConfig)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SampleConfig(greedy=True)


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def make_runtime(gen, capacity_blocks=32, block=8, cache=True):
    return PagedKVRuntime.build(gen.cfg, 2, block=block,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32, prefix_cache=cache)


def _run(engine, requests):
    results = {}
    queue = [SlotRequest(on_done=(lambda t, s, i=i:
                                  results.__setitem__(i, (t, s))), **r)
             for i, r in enumerate(requests)]
    stats = engine.run(lambda: queue.pop(0) if queue else None)
    return results, stats


# ------------------------------------------------------------- the drafter
def test_drafter_no_match_returns_empty():
    d = PromptLookupDrafter()
    assert d.draft([1, 2, 3, 4, 5], 4) == []      # all tokens distinct
    assert d.draft([], 4) == []
    assert d.draft([7], 4) == []                   # too short to match
    assert d.draft([5, 6, 5, 6], 0) == []          # k=0 never proposes


def test_drafter_proposes_cycle_continuation():
    d = PromptLookupDrafter()
    hist = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    # last 2-gram [1, 2] matched at the cycle → continuation [3, 4, 1, 2]
    assert d.draft(hist, 4) == [3, 4, 1, 2]
    assert d.draft(hist, 2) == [3, 4]


def test_drafter_match_at_prompt_generated_boundary():
    """A match STRADDLING the prompt/generated boundary is legal — the
    drafter sees one flat history, exactly what the engine hands it."""
    d = PromptLookupDrafter()
    prompt = [9, 9, 7, 8]
    generated = [5, 7, 8, 5]
    # suffix [8, 5] occurs once earlier: prompt[-1]=8 + generated[0]=5 —
    # a boundary-straddling window; continuation starts inside generated
    assert d.draft(prompt + generated, 3) == [7, 8, 5]


def test_drafter_prefers_full_continuation_over_stub():
    """Within one n-gram length, the most recent match with k continuation
    tokens wins over a more recent stub-only match (a cycle's nearest
    occurrence sits right before the suffix and would draft 1 token)."""
    d = PromptLookupDrafter()
    hist = [5, 5, 5, 5, 5, 5]
    # every window matches; a full 3-token continuation exists further back
    assert d.draft(hist, 3) == [5, 5, 5]


def test_drafter_short_continuation_stub():
    d = PromptLookupDrafter(ngram_max=2)
    hist = [1, 2, 9, 1, 2]
    # only match for [1, 2] has a single continuation token (9) — a stub
    # draft is still a draft
    assert d.draft(hist, 4) == [9, 1, 2]  # falls back to idx[0], 3 avail


def test_drafter_k_longer_than_history_tail():
    d = PromptLookupDrafter()
    hist = [3, 4, 3, 4]
    out = d.draft(hist, 16)  # k >> history: proposal truncates, never pads
    assert 1 <= len(out) <= 16
    assert out[0] == 3


def test_draft_model_drafter_self_draft_is_greedy(gen):
    """Drafting with the TARGET model proposes exactly its own greedy
    continuation — the 100%-acceptance identity that pins the verify."""
    hist = [5, 6, 7, 8]
    d = DraftModelDrafter(gen)
    solo = gen.generate(hist, max_new_tokens=4, sample=GREEDY)[0]
    assert d.draft(hist, 4) == solo
    assert d.draft([], 4) == [] and d.draft(hist, 0) == []


# ----------------------------------------------- engine greedy identity
def test_engine_spec_matches_solo_dense_and_paged(gen):
    """The tentpole bar: greedy outputs byte-identical to the solo path
    with speculation on, over the engine's own pool and a handed-in one
    with a prefix cache, including slot reuse and mixed lengths.
    Prompts are cyclic so the drafter genuinely proposes (and the tiny
    model's generated tail cycles, so drafts genuinely get accepted)."""
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6], [9, 10, 9, 10, 9, 10], [20],
               [30 + (i % 3) for i in range(12)], [40, 41]]
    reqs = [{"ids": p, "max_new": 16, "sample": GREEDY} for p in prompts]
    solo = [gen.generate_fused(p, max_new_tokens=16, sample=GREEDY,
                               stop_tokens=(2,), chunk=4)[0] for p in prompts]
    spec = lambda: SpecConfig(tokens=4)
    own, st = _run(ContinuousEngine(gen, slots=2, chunk=4,
                                    stop_tokens=(2,), spec=spec()), reqs)
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    paged, stp = _run(ContinuousEngine(gen, slots=2, chunk=4,
                                       stop_tokens=(2,), paged=rt,
                                       spec=spec()), reqs)
    for i, s in enumerate(solo):
        assert own[i][0] == s, f"own-pool spec row {i} diverged from solo"
        assert paged[i][0] == s, f"paged spec row {i} diverged from solo"
    # the sweep genuinely speculated, and the twins dispatched identically
    assert st["spec_dispatches"] > 0 and st["spec_accepted_tokens"] > 0
    assert stp["spec_dispatches"] == st["spec_dispatches"]
    assert stp["spec_accepted_tokens"] == st["spec_accepted_tokens"]
    assert rt.pool.n_free == free0  # rejected/accepted KV leaked nothing


def test_engine_spec_int8_kv_parity():
    """Verify scatter covers the int8 K/V + per-vector scale layout."""
    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64), kv_quant="int8")
    g = Generator(cfg, dtype=jnp.float32, seed=3)
    prompts = [[5, 6, 5, 6, 5, 6], [9, 10, 11, 9, 10, 11]]
    solo = [g.generate_fused(p, max_new_tokens=10, sample=GREEDY, chunk=4)[0]
            for p in prompts]
    reqs = [{"ids": p, "max_new": 10, "sample": GREEDY} for p in prompts]
    own, _ = _run(ContinuousEngine(g, slots=2, chunk=4,
                                   spec=SpecConfig(tokens=4)), reqs)
    paged, _ = _run(ContinuousEngine(g, slots=2, chunk=4,
                                     paged=make_runtime(g),
                                     spec=SpecConfig(tokens=4)), reqs)
    for i, s in enumerate(solo):
        assert own[i][0] == s and paged[i][0] == s


def test_engine_spec_stop_token_inside_accepted_run(gen):
    """A stop token inside an accepted draft run ends the row exactly
    there — emission truncates mid-verify and the slot retires."""

    class StopDrafter:
        def draft(self, history, k):
            # propose the model's own next tokens with a stop spliced in —
            # verify accepts what agrees; the engine must cut at the stop
            out, _ = gen.generate(list(history), max_new_tokens=k,
                                  sample=GREEDY)
            return out[:k]

    prompts = [[5, 6, 7, 5, 6, 7]]
    free_run = gen.generate_fused(prompts[0], max_new_tokens=20,
                                  sample=GREEDY, chunk=4)[0]
    # stop on a token whose FIRST occurrence is a few steps in, so the
    # planted stop genuinely lands inside an accepted multi-token run
    pos, stop = next((p, t) for p, t in enumerate(free_run)
                     if p >= 2 and t not in free_run[:p])
    solo = gen.generate_fused(prompts[0], max_new_tokens=20, sample=GREEDY,
                              stop_tokens=(stop,), chunk=4)[0]
    assert len(solo) == pos + 1  # sanity: it stops at the planted stop
    res, _ = _run(ContinuousEngine(gen, slots=1, chunk=4,
                                   stop_tokens=(stop,),
                                   spec=SpecConfig(tokens=6,
                                                   drafter=StopDrafter())),
                  [{"ids": prompts[0], "max_new": 20, "sample": GREEDY}])
    assert res[0][0] == solo


def test_engine_spec_draft_model_full_acceptance(gen):
    """Drafting with the target model itself: every draft token agrees
    with greedy argmax, so acceptance is 100% and strides hit k+1."""
    reqs = [{"ids": [5, 6, 7], "max_new": 17, "sample": GREEDY}]
    solo = gen.generate_fused([5, 6, 7], max_new_tokens=17, sample=GREEDY,
                              chunk=4)[0]
    eng = ContinuousEngine(
        gen, slots=1, chunk=4,
        spec=SpecConfig(tokens=4, drafter=DraftModelDrafter(gen)))
    res, st = _run(eng, reqs)
    assert res[0][0] == solo
    assert st["spec_acceptance"] == 1.0
    assert st["spec_dispatches"] >= 3
    assert st["tokens_per_weight_pass"] > 1.0


def test_engine_spec_budget_clamp_k_longer_than_remaining(gen):
    """Draft length clamps to the remaining budget: a 4-token draft
    against a 2-token budget may emit at most budget tokens."""
    eng = ContinuousEngine(
        gen, slots=1, chunk=4,
        spec=SpecConfig(tokens=4, drafter=DraftModelDrafter(gen)))
    res, _ = _run(eng, [{"ids": [5, 6, 7, 5, 6, 7], "max_new": 2,
                         "sample": GREEDY}])
    solo = gen.generate_fused([5, 6, 7, 5, 6, 7], max_new_tokens=2,
                              sample=GREEDY, chunk=4)[0]
    assert res[0][0] == solo and len(res[0][0]) == 2


def test_engine_spec_adversarial_drafter_throttles_to_plain(gen):
    """A drafter that is always wrong must cost bounded verify work: the
    acceptance EMA throttles the slot to plain decode (with occasional
    1-token probes), and outputs stay exact."""

    class WrongDrafter:
        calls = 0

        def draft(self, history, k):
            WrongDrafter.calls += 1
            nxt = gen.generate(list(history), max_new_tokens=1,
                               sample=GREEDY)[0][0]
            wrong = (nxt + 1) % gen.cfg.vocab_size or 1
            return [wrong] * k

    solo = gen.generate_fused([5, 6, 7], max_new_tokens=40, sample=GREEDY,
                              chunk=4)[0]
    eng = ContinuousEngine(
        gen, slots=1, chunk=4,
        spec=SpecConfig(tokens=4, drafter=WrongDrafter(), probe_every=8))
    res, st = _run(eng, [{"ids": [5, 6, 7], "max_new": 40,
                          "sample": GREEDY}])
    assert res[0][0] == solo
    assert st["spec_accepted_tokens"] == 0
    # EMA throttle: after the initial burst (ema 1.0 → under 1/8 in ~7
    # dispatches) drafting stops except probes — far fewer verify
    # dispatches than the 39 decode steps a per-step drafter would burn
    assert st["spec_dispatches"] <= 12
    assert st["decode_weight_passes"] >= 39  # plain decode floor intact


def test_engine_spec_seeded_sampling_deterministic(gen):
    """Sampled rows under speculation: rejection sampling rides the
    per-slot PRNG chain, so a seeded request reproduces exactly (same
    seed → same tokens, own pool == handed-in pool) and mixes safely with greedy
    peers (who stay byte-exact)."""
    seeded = {"ids": [5, 6, 5, 6, 5, 6], "max_new": 8, "seed": 99,
              "sample": SampleConfig(temperature=1.2, top_k=8)}
    peer = {"ids": [9, 10, 9, 10], "max_new": 8, "sample": GREEDY}
    spec = lambda: SpecConfig(tokens=4, drafter=DraftModelDrafter(gen))
    a, _ = _run(ContinuousEngine(gen, slots=2, chunk=4, spec=spec()),
                [seeded, peer])
    b, _ = _run(ContinuousEngine(gen, slots=2, chunk=4, spec=spec()),
                [seeded, peer])
    c, _ = _run(ContinuousEngine(gen, slots=2, chunk=4,
                                 paged=make_runtime(gen), spec=spec()),
                [seeded, peer])
    assert a[0][0] == b[0][0] == c[0][0]
    assert len(a[0][0]) == 8
    assert all(0 <= t < gen.cfg.vocab_size for t in a[0][0])
    solo_peer = gen.generate_fused([9, 10, 9, 10], max_new_tokens=8,
                                   sample=GREEDY, chunk=4)[0]
    assert a[1][0] == solo_peer  # greedy peer exact next to a sampled row


def test_engine_spec_per_request_opt_out(gen):
    """``speculative=False`` rows never draft; peers still may."""
    reqs = [{"ids": [5, 6, 5, 6, 5, 6], "max_new": 12, "sample": GREEDY,
             "speculative": False}]
    eng = ContinuousEngine(gen, slots=1, chunk=4,
                           spec=SpecConfig(tokens=4,
                                           drafter=DraftModelDrafter(gen)))
    res, st = _run(eng, reqs)
    assert st["spec_dispatches"] == 0 and st["spec_drafted_tokens"] == 0
    solo = gen.generate_fused([5, 6, 5, 6, 5, 6], max_new_tokens=12,
                              sample=GREEDY, chunk=4)[0]
    assert res[0][0] == solo


def test_engine_spec_mid_stream_cancellation(gen):
    """A row cancelled mid-speculation retires at the wave boundary; its
    peer's greedy output is unperturbed and (paged) nothing leaks."""
    cancel = {"on": False}
    seen = []

    def on_toks(t):
        seen.extend(t)
        if len(seen) >= 4:
            cancel["on"] = True

    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    results = {}
    q = [SlotRequest(ids=[5, 6, 5, 6], max_new=30, sample=GREEDY,
                     on_done=lambda t, s: results.__setitem__("keep", t)),
         SlotRequest(ids=[9, 10, 9, 10], max_new=30, sample=GREEDY,
                     on_tokens=on_toks, cancelled=lambda: cancel["on"],
                     on_done=lambda t, s: results.__setitem__("cxl", t))]
    ContinuousEngine(gen, slots=2, chunk=4, paged=rt,
                     spec=SpecConfig(tokens=4)).run(
        lambda: q.pop(0) if q else None)
    solo = gen.generate_fused([5, 6, 5, 6], max_new_tokens=30,
                              sample=GREEDY, chunk=4)[0]
    assert results["keep"] == solo
    assert len(results["cxl"]) < 30  # actually cancelled early
    assert rt.pool.n_free == free0   # cancelled row released its blocks


def test_engine_spec_off_is_spec_none(gen):
    """SpecConfig(tokens=0) — the TPUSTACK_SPEC_TOKENS=0 contract — is
    the plain engine: no drafter built, the plain run loop runs."""
    eng = ContinuousEngine(gen, slots=2, chunk=4,
                           spec=SpecConfig(tokens=0))
    assert eng.spec is None and eng._drafter is None
    res, st = _run(eng, [{"ids": [5, 6, 7], "max_new": 6,
                          "sample": GREEDY}])
    assert "spec_dispatches" not in st
    solo = gen.generate_fused([5, 6, 7], max_new_tokens=6, sample=GREEDY,
                              chunk=4)[0]
    assert res[0][0] == solo


# -------------------------------------------- Retry-After stride projection
def test_eta_until_blocks_walks_finish_order():
    assert eta_until_blocks([(4.0, 2), (1.0, 3)], 3) == 1.0
    assert eta_until_blocks([(4.0, 2), (1.0, 3)], 4) == 4.0
    assert eta_until_blocks([(4.0, 2), (1.0, 3)], 99) == 4.0  # best effort
    assert eta_until_blocks([], 5) == 1.0


def test_projected_release_uses_per_slot_stride_ema(gen):
    """The satellite bar: a slot speculation is advancing k+1 tokens per
    wave projects (k+1)x sooner than a one-token-per-wave assumption —
    Retry-After must not overestimate under speculation."""
    from tpustack.models.llm_continuous import _Slot

    eng = ContinuousEngine(gen, slots=2, chunk=4, paged=make_runtime(gen),
                           spec=SpecConfig(tokens=4))
    slow, fast = _Slot(), _Slot()
    for s, stride in ((slow, 1.0), (fast, 5.0)):
        s.req = SlotRequest(ids=[1], max_new=100, sample=GREEDY)
        s.budget, s.out = 100, [0]
        s.blocks = [1, 2, 3]
        s.stride_ema = stride
    eng._slots_view = [slow]
    with eng._marks_lock:  # the runtime sanitizer enforces the guard
        eng._fetch_marks = [(0.0, 0, 0), (10.0, 100, 10)]  # 1 wave/s
    eta_slow = eng.projected_block_release_s(3)
    eng._slots_view = [fast]
    eta_fast = eng.projected_block_release_s(3)
    # same remaining budget, 5x the stride → 5x sooner
    assert eta_fast == pytest.approx(eta_slow / 5.0)
    # and with no marks at all, the fallback rate still answers
    with eng._marks_lock:
        eng._fetch_marks = []
    assert eng.projected_block_release_s(3) > 0


# ------------------------------------------------------------- HTTP surface
def _server(gen, **kw):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs import Registry
    from tpustack.serving.llm_server import LLMServer

    reg = kw.pop("registry", None) or Registry()
    return LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                     max_batch=4, registry=reg, **kw), reg


def _post_all(server, payloads):
    from aiohttp.test_utils import TestClient, TestServer

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            outs = []
            for body in payloads:
                r = await client.post("/completion", json=body)
                assert r.status == 200, await r.text()
                outs.append((await r.json())["content"])
            props = await (await client.get("/props")).json()
            metrics = await (await client.get("/metrics")).text()
            return outs, props, metrics
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(scenario())


def test_server_spec_onoff_parity_and_props(gen):
    """HTTP bar: greedy completions byte-identical spec on vs off; /props
    reports live speculation stats; the catalog metrics export."""
    bodies = [{"prompt": "abcabcabcabcabcabcabcabc", "n_predict": 16,
               "temperature": 0} for _ in range(3)]
    on, reg = _server(gen, spec=SpecConfig(tokens=4))
    outs_on, props_on, metrics = _post_all(on, bodies)
    off, _ = _server(gen, spec=None)
    outs_off, props_off, _ = _post_all(off, bodies)
    assert outs_on == outs_off
    sp = props_on["speculative"]
    assert sp["enabled"] and sp["tokens"] == 4
    assert sp["drafter"] == "prompt_lookup"
    assert sp["drafted_tokens"] > 0
    assert sp["accepted_tokens"] <= sp["drafted_tokens"]
    assert props_off["speculative"]["enabled"] is False
    for name in ("tpustack_llm_spec_drafted_tokens_total",
                 "tpustack_llm_spec_accepted_tokens_total",
                 "tpustack_llm_spec_acceptance_ratio",
                 "tpustack_llm_spec_accepted_length_tokens"):
        assert name in metrics
    assert reg.get_sample_value(
        "tpustack_llm_spec_drafted_tokens_total") == sp["drafted_tokens"]


def test_server_spec_body_opt_out(gen):
    """Body ``speculative: false`` keeps the request on plain decode
    (no drafted tokens) with identical output."""
    body = {"prompt": "xyzxyzxyzxyzxyzxyz", "n_predict": 12,
            "temperature": 0}
    on, _ = _server(gen, spec=SpecConfig(tokens=4))
    base, _, _ = _post_all(on, [body])
    opt, reg = _server(gen, spec=SpecConfig(tokens=4))
    outs, props, _ = _post_all(opt, [dict(body, speculative=False)])
    assert outs == base
    assert props["speculative"]["drafted_tokens"] == 0


def test_server_spec_stream_parity(gen):
    """SSE streaming under speculation: chunked deliveries reassemble to
    the non-streamed (and spec-off) content."""
    from aiohttp.test_utils import TestClient, TestServer

    body = {"prompt": "abcabcabcabcabcabc", "n_predict": 12,
            "temperature": 0}
    off, _ = _server(gen, spec=None)
    base, _, _ = _post_all(off, [body])
    server, _ = _server(gen, spec=SpecConfig(tokens=4))

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.post("/completion",
                                  json=dict(body, stream=True))
            assert r.status == 200
            text = ""
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    payload = json.loads(line[6:])
                    text += payload.get("content", "")
            return text
        finally:
            await client.close()

    streamed = asyncio.new_event_loop().run_until_complete(scenario())
    assert streamed == base[0]


def test_build_spec_env_knobs(gen, monkeypatch):
    from tpustack.serving.llm_server import LLMServer

    monkeypatch.setenv("TPUSTACK_SPEC_TOKENS", "0")
    assert LLMServer._build_spec(gen) is None
    monkeypatch.setenv("TPUSTACK_SPEC_TOKENS", "6")
    monkeypatch.setenv("TPUSTACK_SPEC_NGRAM", "2")
    sc = LLMServer._build_spec(gen)
    assert sc.tokens == 6 and sc.ngram_max == 2 and sc.drafter is None
    monkeypatch.setenv("TPUSTACK_SPEC_DRAFT", "tiny")
    sc = LLMServer._build_spec(gen)
    assert type(sc.drafter).__name__ == "DraftModelDrafter"
    monkeypatch.setenv("TPUSTACK_SPEC_DRAFT", "nonsense")
    with pytest.raises(ValueError):
        LLMServer._build_spec(gen)


def test_engine_spec_span_events(gen):
    """Satellite bar: each verify dispatch lands a `spec` event with
    drafted/accepted on the request's wave span."""
    from tpustack.obs.trace import Tracer

    tracer = Tracer()
    root = tracer.start_span("POST /completion")
    eng = ContinuousEngine(
        gen, slots=1, chunk=4, tracer=tracer,
        spec=SpecConfig(tokens=4, drafter=DraftModelDrafter(gen)))
    res = {}
    q = [SlotRequest(ids=[5, 6, 7], max_new=12, sample=GREEDY,
                     span_ctx=root.context,
                     on_done=lambda t, s: res.__setitem__(0, (t, s)))]
    eng.run(lambda: q.pop(0) if q else None)
    root.end()
    rec = tracer.get(root.context.trace_id)
    waves = [s for s in rec["spans"] if s["name"] == "wave"]
    assert waves, rec["spans"]
    spec_events = [e for s in waves for e in s.get("events", [])
                   if e.get("name") == "spec"]
    assert spec_events, waves
    for e in spec_events:
        assert e["drafted"] >= 1 and 0 <= e["accepted"] <= e["drafted"]


# ------------------------------------------------------------- bench smoke
def test_bench_speculative_tiny_smoke_cli():
    """Shell ``tools/bench_llm.py --speculative --tiny`` — the
    CPU-runnable proof behind the acceptance bar: acceptance > 0 and
    strictly more tokens per weight pass than plain decode on repetitive
    traffic, greedy outputs identical spec on vs off in every cell."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--speculative", "--tiny"],
        capture_output=True, text=True, timeout=420,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["outputs_identical"] is True
    assert out["acceptance_rate"] > 0
    assert (out["tokens_per_weight_pass_on"]
            > out["tokens_per_weight_pass_off"])
    cells = {(c["traffic"], c["batch"]) for c in out["sweep"]}
    assert ("repetitive", 1) in cells and ("random", 1) in cells
