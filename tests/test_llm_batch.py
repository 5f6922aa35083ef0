"""Batched (slot-parallel) LLM decode — ``Generator.generate_batch``.

The reference's llama.cpp server exposes parallel slots (``--parallel``);
here B requests share each weight-streaming decode step.  Correctness bar:
a row decoded in a batch must match the same prompt decoded alone (greedy),
regardless of which other rows ride along — per-row RoPE positions and
attention masks make batch composition invisible.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_generate import Generator, SampleConfig

GREEDY = SampleConfig(greedy=True)


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


@pytest.mark.slow
def test_batch_matches_single_greedy_mixed_lengths(gen):
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [20]]
    outs, stats = gen.generate_batch(prompts, 8, [GREEDY] * 3, seed=0)
    assert stats["batch"] == 3
    for p, o in zip(prompts, outs):
        # single-request path buckets each prompt separately; rows see their
        # true RoPE positions either way, so tokens must agree exactly
        solo, _ = gen.generate(p, max_new_tokens=8, sample=GREEDY, seed=0)
        assert o == solo, f"batch row diverged for prompt {p}"


def test_batch_row_independent_of_peers(gen):
    """A row's output must not depend on what else is in the batch."""
    target = [5, 6, 7, 8]
    a, _ = gen.generate_batch([target, [30, 31]], 6, [GREEDY] * 2, seed=0)
    b, _ = gen.generate_batch([target, [40, 41, 42, 43, 44, 45, 46]], 6,
                              [GREEDY] * 2, seed=0)
    assert a[0] == b[0]


def test_batch_per_row_max_and_stop(gen):
    prompts = [[5, 6], [7, 8]]
    outs, _ = gen.generate_batch(prompts, [3, 6], [GREEDY] * 2, seed=0)
    assert len(outs[0]) == 3 and len(outs[1]) == 6
    # stop token truncates only the row it appears in; the expected prefix
    # runs through the FIRST occurrence (the greedy chain may repeat tokens,
    # so solo[2] can also appear earlier in the sequence)
    solo, _ = gen.generate([5, 6], max_new_tokens=6, sample=GREEDY, seed=0)
    stop = solo[2]
    outs2, _ = gen.generate_batch(prompts, 6, [GREEDY] * 2, seed=0,
                                  stop_tokens=(stop,))
    assert outs2[0] == solo[:solo.index(stop) + 1]
    assert len(outs2[1]) <= 6


def test_batch_mixed_sampling_configs(gen):
    """Greedy and temperature rows coexist; the greedy row stays exact."""
    prompts = [[5, 6, 7], [5, 6, 7]]
    cfgs = [GREEDY, SampleConfig(temperature=1.5, top_k=8)]
    outs, _ = gen.generate_batch(prompts, 6, cfgs, seed=1)
    solo, _ = gen.generate([5, 6, 7], max_new_tokens=6, sample=GREEDY, seed=1)
    assert outs[0] == solo
    assert all(0 <= t < gen.cfg.vocab_size for t in outs[1])


def test_batch_on_row_done_fires_early(gen):
    """A short row's completion callback fires before the long row's, with
    that row's final tokens — the server unblocks short requests without
    waiting for the slowest batch peer."""
    order = []
    outs, _ = gen.generate_batch(
        [[5, 6], [7, 8]], [2, 20], [GREEDY] * 2, seed=0, chunk=4,
        on_row_done=lambda i, toks, st: order.append((i, toks, st)))
    assert [i for i, _, _ in order] == [0, 1]  # short row first
    by_row = {i: toks for i, toks, _ in order}
    assert by_row[0] == outs[0] and by_row[1] == outs[1]
    stats0 = order[0][2]
    assert stats0["generated_tokens"] == 2 and stats0["batch"] == 2


def test_batch_on_chunk_streaming_hook(gen):
    blocks = []
    outs, _ = gen.generate_batch([[5, 6], [7, 8]], 7, [GREEDY] * 2, seed=0,
                                 chunk=3, on_chunk=lambda b: blocks.append(b))
    assert blocks and all(b.shape[0] == 2 for b in blocks)
    assert blocks[0].shape == (2, 1)  # first call: the prefill-sampled tokens
    # the hook sees EVERY token of each row, first included (rows may carry
    # post-stop garbage the host discarded; prefix must match)
    streamed = np.concatenate(blocks, axis=1)
    for i in range(2):
        assert list(streamed[i][:len(outs[i])]) == outs[i]


@pytest.mark.slow
def test_batch_decodes_to_full_capacity_via_tail_steps():
    """When the remaining cache tail is shorter than a chunk, the batched
    decoder finishes on the single-step path (no per-tail-length recompiles)
    and still matches the solo decoder token-for-token."""
    g = Generator(LlamaConfig.tiny(max_seq=32), dtype=jnp.float32, seed=3)
    prompt = list(range(5, 15))  # bucket 16 → capacity 16
    outs, _ = g.generate_batch([prompt], 999, [GREEDY], seed=0, chunk=6)
    assert len(outs[0]) == 16  # 1 prefill token + 15 decode steps
    solo, _ = g.generate(prompt, max_new_tokens=999, sample=GREEDY, seed=0)
    assert outs[0] == solo[:16]


def test_batch_capacity_guard(gen):
    with pytest.raises(ValueError, match="exceeds ctx"):
        gen.generate_batch([list(range(5, 64))], 8, [GREEDY], seed=0)
    with pytest.raises(ValueError, match="SampleConfig"):
        gen.generate_batch([[5]], 8, [GREEDY, GREEDY], seed=0)


def test_server_micro_batches_concurrent_completions(gen):
    """N concurrent non-streaming greedy requests ride the continuous engine
    (slot decode dispatches, no solo path), and each gets the same answer
    the solo path gives."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    tok = ByteTokenizer(512)
    server = LLMServer(generator=gen, tokenizer=tok, model_name="tiny-test",
                       max_batch=4)
    calls = {"batch": 0, "solo": 0}
    real_paged, real_fused = gen._decode_scan_paged, gen.generate_fused
    real_ride = gen._ride_scan_paged

    def spy(real):  # the engine's decode programs, a ride on one or not
        def spied(*a, **kw):
            calls["batch"] += 1
            return real(*a, **kw)
        return spied

    def spy_fused(*a, **kw):
        calls["solo"] += 1
        return real_fused(*a, **kw)

    gen._decode_scan_paged, gen.generate_fused = spy(real_paged), spy_fused
    gen._ride_scan_paged = spy(real_ride)
    prompts = ["alpha", "bee", "gamma!"]

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            posts = [client.post("/completion", json={
                "prompt": p, "n_predict": 5, "temperature": 0})
                for p in prompts]
            rs = await asyncio.gather(*posts)
            return [await r.json() for r in rs]
        finally:
            await client.close()

    try:
        results = asyncio.new_event_loop().run_until_complete(scenario())
    finally:
        gen._decode_scan_paged, gen.generate_fused = real_paged, real_fused
        gen._ride_scan_paged = real_ride

    assert calls["batch"] >= 1 and calls["solo"] == 0, calls
    for p, r in zip(prompts, results):
        assert r["stop"] is True and r["tokens_evaluated"] == len(tok.encode(p))
        solo, _ = gen.generate_fused(
            tok.encode(p), max_new_tokens=5,
            sample=SampleConfig(greedy=True), seed=0,
            stop_tokens=(tok.eos_id,))
        if solo and solo[-1] == tok.eos_id:
            solo = solo[:-1]
        assert r["content"] == tok.decode(solo)


def test_server_batched_streaming_coalesces(gen):
    """Two concurrent SSE streams (greedy, unseeded) ride ONE batched decode
    and each stream reproduces its solo content."""
    import asyncio
    import json as _json

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    tok = ByteTokenizer(512)
    server = LLMServer(generator=gen, tokenizer=tok, model_name="tiny-test",
                       max_batch=4)
    calls = {"batch": 0, "solo": 0}
    real_paged, real_solo = gen._decode_scan_paged, gen.generate

    def spy_paged(*a, **kw):  # the engine's decode program
        calls["batch"] += 1
        return real_paged(*a, **kw)

    def spy_solo(*a, **kw):
        calls["solo"] += 1
        return real_solo(*a, **kw)

    gen._decode_scan_paged, gen.generate = spy_paged, spy_solo
    prompts = ["stream one", "stream two!"]

    async def read_stream(client, prompt):
        r = await client.post("/completion", json={
            "prompt": prompt, "n_predict": 6, "temperature": 0,
            "stream": True})
        assert r.status == 200
        text, final = "", None
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = _json.loads(line[6:])
            if payload.get("stop"):
                final = payload
            else:
                text += payload.get("content", "")
        return text, final

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            return await asyncio.gather(
                *(read_stream(client, p) for p in prompts))
        finally:
            await client.close()

    try:
        results = asyncio.new_event_loop().run_until_complete(scenario())
    finally:
        gen._decode_scan_paged, gen.generate = real_paged, real_solo

    assert calls["batch"] >= 1 and calls["solo"] == 0, calls
    for p, (text, final) in zip(prompts, results):
        solo, _ = gen.generate_fused(
            tok.encode(p), max_new_tokens=6, sample=SampleConfig(greedy=True),
            seed=0, stop_tokens=(tok.eos_id,))
        if solo and solo[-1] == tok.eos_id:
            solo = solo[:-1]
        assert text == tok.decode(solo), (p, text)
        assert final is not None and final["tokens_predicted"] <= 6


def test_server_negative_seed_is_random_not_fatal(gen):
    """r5 review: llama.cpp clients routinely send seed=-1 ("random").
    It must behave as an unseeded request — and an out-of-range seed must
    never escape as an OverflowError that fails every batched peer."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test", max_batch=4)

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            outs = []
            for seed in (-1, 2**40):  # llama.cpp "random" + out-of-range
                r = await client.post("/completion", json={
                    "prompt": "hello", "n_predict": 4, "seed": seed,
                    "temperature": 0.9})
                assert r.status == 200, await r.text()
                outs.append(await r.json())
            return outs
        finally:
            await client.close()

    for j in asyncio.new_event_loop().run_until_complete(scenario()):
        assert j["tokens_predicted"] <= 4


def test_server_seeded_sampling_batches_and_reproduces(gen):
    """r5: seeded non-greedy requests go through the continuous engine
    (per-slot PRNG streams make them admission-timing independent) — the
    r4 solo carve-out is gone, and the same (prompt, seed) posted twice
    returns identical content even with a concurrent peer in the batch."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test", max_batch=4)
    real_solo = gen.generate_fused
    gen.generate_fused = lambda *a, **kw: (_ for _ in ()).throw(
        AssertionError("seeded request must ride the continuous engine"))

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            seeded = {"prompt": "hello", "n_predict": 6, "seed": 7,
                      "temperature": 0.9}
            # run 1: alone; run 2: alongside a greedy peer — content must
            # not change with batch composition
            r1 = await client.post("/completion", json=seeded)
            assert r1.status == 200
            j1 = await r1.json()
            peer = client.post("/completion", json={
                "prompt": "peer request", "n_predict": 12, "temperature": 0})
            again = client.post("/completion", json=seeded)
            rp, r2 = await asyncio.gather(peer, again)
            assert rp.status == 200 and r2.status == 200
            return j1, await r2.json()
        finally:
            await client.close()

    try:
        j1, j2 = asyncio.new_event_loop().run_until_complete(scenario())
    finally:
        gen.generate_fused = real_solo
    assert j1["tokens_predicted"] <= 6
    assert j1["content"] == j2["content"], (
        "seeded output changed with batch composition")


@pytest.mark.slow
def test_chunked_prefill_matches_single_shot():
    """Long prompts prefill in PREFILL_CHUNK windows attending the cache
    prefix (streaming flash kernel, traced offset).  Forcing a tiny chunk on
    the tiny model must reproduce the single-shot path token-for-token."""
    g = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
    prompt = list(range(5, 45))  # bucket 64
    ref, _ = g.generate(prompt, max_new_tokens=6, sample=GREEDY, seed=0)
    g.PREFILL_CHUNK = 16  # bucket 64 % 16 == 0 → four whole chunks
    out, _ = g.generate(prompt, max_new_tokens=6, sample=GREEDY, seed=0)
    assert out == ref
    # r5: a bucket that is NOT a chunk multiple (max_seq-capped buckets)
    # is padded to whole chunks inside the program, tokens and cache lines
    # alike (PR 34; a per-chunk host loop before) — it must produce the
    # same tokens as the whole-chunk walk and the single shot
    g.PREFILL_CHUNK = 24  # 64 % 24 != 0 → padded to 72, three chunks
    out_loop, _ = g.generate(prompt, max_new_tokens=6, sample=GREEDY, seed=0)
    assert out_loop == ref


@pytest.mark.slow
def test_chunked_prefill_batch_short_row_peaks_early():
    """In a chunked batch, a row much shorter than the bucket takes its
    first-token logits from an EARLY chunk, not the last one."""
    g = Generator(LlamaConfig.tiny(max_seq=128), dtype=jnp.float32, seed=3)
    long_p = list(range(5, 45))   # drives bucket to 64
    short_p = [7, 8, 9]           # last token in chunk 0
    ref_long, _ = g.generate_batch([long_p], 5, [GREEDY], seed=0)
    ref_short, _ = g.generate(short_p, max_new_tokens=5, sample=GREEDY, seed=0)
    g.PREFILL_CHUNK = 16
    outs, _ = g.generate_batch([long_p, short_p], 5, [GREEDY] * 2, seed=0)
    assert outs[0] == ref_long[0]
    assert outs[1] == ref_short[:len(outs[1])] and len(outs[1]) == 5


@pytest.mark.slow
def test_batch_quantized_generator():
    qgen = Generator(dataclasses.replace(LlamaConfig.tiny(max_seq=64),
                                         quant="int8"),
                     dtype=jnp.float32, seed=3)
    outs, stats = qgen.generate_batch([[5, 6, 7], [9, 10]], 5, [GREEDY] * 2,
                                      seed=0)
    solo, _ = qgen.generate([5, 6, 7], max_new_tokens=5, sample=GREEDY, seed=0)
    assert outs[0] == solo


def test_server_seed_coercion_and_rejection(gen):
    """ADVICE r5: JSON clients round-trip integer seeds as floats (7.0) —
    those must coerce to int and reproduce, while non-numeric seeds get a
    400 instead of silently going random (losing the reproducibility the
    client asked for)."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test", max_batch=4)

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            outs = []
            for seed in (7, 7.0):  # int and its JSON-float spelling
                r = await client.post("/completion", json={
                    "prompt": "hello", "n_predict": 4, "seed": seed,
                    "temperature": 0.9})
                assert r.status == 200, await r.text()
                outs.append((await r.json())["content"])
            assert outs[0] == outs[1], "seed 7.0 must behave as seed 7"
            for bad in ("abc", 7.5, True):
                r = await client.post("/completion", json={
                    "prompt": "hello", "n_predict": 4, "seed": bad})
                assert r.status == 400, (bad, await r.text())
                assert "seed" in (await r.json())["error"]
            # the OpenAI surface rejects identically
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "seed": "abc"})
            assert r.status == 400
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())
