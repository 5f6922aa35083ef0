"""LLM generation engine + server + weight converter tests (tiny, CPU)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.llama import LlamaConfig, LlamaModel
from tpustack.models.llama_weights import (
    convert_llama_state_dict,
    make_fake_hf_llama_state_dict,
    our_path_to_hf_key,
)
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.models.text_tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32)


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer(512)
    ids = tok.encode("hello, TPU! ünïcødé")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "hello, TPU! ünïcødé"


def test_llama_key_mapping():
    assert (our_path_to_hf_key(("layers_0", "self_attn", "q_proj", "kernel"))
            == "model.layers.0.self_attn.q_proj.weight")
    assert our_path_to_hf_key(("embed_tokens", "embedding")) == "model.embed_tokens.weight"
    assert our_path_to_hf_key(("norm", "scale")) == "model.norm.weight"
    assert our_path_to_hf_key(("lm_head", "kernel")) == "lm_head.weight"
    assert (our_path_to_hf_key(("layers_1", "input_layernorm", "scale"))
            == "model.layers.1.input_layernorm.weight")


@pytest.mark.slow
def test_llama_weights_roundtrip():
    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg, dtype=jnp.float32)
    tmpl = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    hf = make_fake_hf_llama_state_dict(tmpl)
    ours = convert_llama_state_dict(tmpl, hf, dtype=jnp.float32)
    a = jax.tree_util.tree_leaves(tmpl)
    b = jax.tree_util.tree_leaves(ours)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.shape == y.shape
    # value check: q_proj kernel is the transpose of the HF tensor
    np.testing.assert_array_equal(
        np.asarray(ours["layers_0"]["self_attn"]["q_proj"]["kernel"]),
        hf["model.layers.0.self_attn.q_proj.weight"].T)


def test_generate_greedy_deterministic(gen):
    ids = [1] + [10, 20, 30]
    out1, stats = gen.generate(ids, max_new_tokens=8,
                               sample=SampleConfig(greedy=True))
    out2, _ = gen.generate(ids, max_new_tokens=8, sample=SampleConfig(greedy=True))
    assert out1 == out2
    assert len(out1) == 8
    assert stats["generated_tokens"] == 8
    assert stats["tokens_per_s"] > 0


def test_generate_seeded_sampling_deterministic(gen):
    ids = [1, 5, 6]
    out1, _ = gen.generate(ids, max_new_tokens=6, seed=7)
    out2, _ = gen.generate(ids, max_new_tokens=6, seed=7)
    out3, _ = gen.generate(ids, max_new_tokens=6, seed=8)
    assert out1 == out2
    assert out1 != out3 or True  # different seed usually differs; no hard guarantee


@pytest.mark.slow
def test_generate_matches_full_forward_greedy(gen):
    """KV-cache decode must agree with running the full sequence each step."""
    cfg = gen.cfg
    model = gen.model
    ids = [1, 40, 41, 42]
    out, _ = gen.generate(ids, max_new_tokens=4, sample=SampleConfig(greedy=True))
    seq = list(ids)
    for _ in range(4):
        logits, _ = model.apply({"params": gen.params},
                                jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        seq.append(nxt)
    assert out == seq[len(ids):]


def test_generate_respects_ctx_limit(gen):
    ids = list(range(1, 60))
    out, stats = gen.generate(ids, max_new_tokens=100)
    assert stats["prompt_tokens"] + len(out) <= gen.cfg.max_seq


def test_llm_server_endpoints(gen):
    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test")

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.get("/health")
            assert r.status == 200 and (await r.json()) == {"status": "ok"}

            r = await client.get("/props")
            j = await r.json()
            assert j["n_ctx"] == 64
            # the device as JAX reports it, not a literal
            assert j["backend"] == {"platform": "cpu", "kind": "cpu",
                                    "count": len(jax.devices())}

            r = await client.post("/tokenize", json={"content": "hi"})
            toks = (await r.json())["tokens"]
            r = await client.post("/detokenize", json={"tokens": toks})
            assert (await r.json())["content"] == "hi"

            r = await client.post("/completion", json={
                "prompt": "hello", "n_predict": 4, "seed": 3})
            j = await r.json()
            assert r.status == 200
            assert j["model"] == "tiny-test" and j["stop"] is True
            assert j["tokens_predicted"] <= 4
            assert "predicted_per_second" in j["timings"]

            r = await client.post("/completion", json={"prompt": ""})
            assert r.status == 400

            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hey"}],
                "max_tokens": 4, "seed": 1})
            j = await r.json()
            assert r.status == 200
            assert j["object"] == "chat.completion"
            assert j["choices"][0]["finish_reason"] in ("stop", "length")
            assert j["usage"]["completion_tokens"] <= 4

            r = await client.post("/v1/chat/completions", json={"messages": []})
            assert r.status == 400
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_llm_server_streaming(gen):
    """SSE streaming: llama.cpp-style /completion chunks and OpenAI
    chat.completion.chunk events ending in [DONE]."""
    from aiohttp.test_utils import TestClient, TestServer

    from tpustack.serving.llm_server import LLMServer

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test")

    def parse_sse(raw: str):
        events = []
        for block in raw.split("\n\n"):
            if block.startswith("data: "):
                events.append(block[len("data: "):])
        return events

    async def scenario():
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            # llama.cpp format: {"content", "stop": false} ... final stop:true
            r = await client.post("/completion", json={
                "prompt": "hello", "n_predict": 4, "seed": 3, "stream": True})
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            events = [__import__("json").loads(e)
                      for e in parse_sse(await r.text())]
            assert len(events) >= 2
            assert all(ev["stop"] is False for ev in events[:-1])
            final = events[-1]
            assert final["stop"] is True
            assert final["tokens_predicted"] <= 4
            assert "predicted_per_second" in final["timings"]
            # streamed deltas concatenate to the non-streamed completion
            r2 = await client.post("/completion", json={
                "prompt": "hello", "n_predict": 4, "seed": 3})
            j2 = await r2.json()
            assert "".join(ev["content"] for ev in events[:-1]) == j2["content"]

            # OpenAI format: role chunk, content chunks, finish chunk, [DONE]
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hey"}],
                "max_tokens": 4, "seed": 1, "stream": True})
            assert r.status == 200
            raw_events = parse_sse(await r.text())
            assert raw_events[-1] == "[DONE]"
            chunks = [__import__("json").loads(e) for e in raw_events[:-1]]
            assert all(c["object"] == "chat.completion.chunk" for c in chunks)
            assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
            assert chunks[-1]["choices"][0]["finish_reason"] in ("stop", "length")
            assert all(c["id"] == chunks[0]["id"] for c in chunks)

            # over-long prompt fails as plain JSON 400, not a broken stream
            r = await client.post("/completion", json={
                "prompt": "x" * 500, "n_predict": 4, "stream": True})
            assert r.status == 400
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(scenario())


def test_stream_disconnect_cancels_worker_and_lock_outlives_handler(gen):
    """A dead client's generate worker is (a) told to stop via the on_token
    cancel hook and (b) the generation lock is held by an independent task
    until the worker thread exits, even if the handler awaiting it is
    cancelled (the one-generation-at-a-time invariant)."""
    import threading

    from tpustack.serving.llm_server import LLMServer, _Cancelled

    server = LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                       model_name="tiny-test")

    # (a) the cancel hook aborts generation mid-flight
    seen = []
    cancel = threading.Event()

    def on_token(t):
        seen.append(t)
        if len(seen) >= 2:
            cancel.set()
        if cancel.is_set():
            raise _Cancelled()

    with pytest.raises(_Cancelled):
        gen.generate(ByteTokenizer(512).encode("hi"), max_new_tokens=32,
                     sample=SampleConfig(greedy=True), seed=0,
                     on_token=on_token)
    assert len(seen) == 2  # stopped right after the cancel, not after 32

    # (b) _run_on_device: cancelling the awaiting handler does NOT release
    # the lock until the worker finishes; the next request then proceeds
    async def scenario():
        release = threading.Event()
        started = threading.Event()

        def slow_worker():
            started.set()
            release.wait(timeout=10)
            return "done"

        handler = asyncio.ensure_future(server._run_on_device(slow_worker))
        await asyncio.sleep(0.05)
        assert started.is_set()
        handler.cancel()  # simulated client teardown mid-generation
        with pytest.raises(asyncio.CancelledError):
            await handler
        assert server._lock.locked()  # device still accounted for
        nxt = asyncio.ensure_future(server._run_on_device(lambda: "next"))
        await asyncio.sleep(0.05)
        assert not nxt.done()  # queued behind the detached worker
        release.set()
        assert await nxt == "next"

    asyncio.new_event_loop().run_until_complete(scenario())


def test_generate_fused_matches_loop_greedy(gen):
    """The scan-based fused decoder must reproduce the per-token loop
    exactly under greedy decoding (same split chain, same sampling)."""
    tok = ByteTokenizer(512)
    ids = tok.encode("fused?")
    loop_out, loop_stats = gen.generate(
        ids, max_new_tokens=24, sample=SampleConfig(greedy=True), seed=5)
    fused_out, fused_stats = gen.generate_fused(
        ids, max_new_tokens=24, sample=SampleConfig(greedy=True), seed=5,
        chunk=8)
    assert fused_out == loop_out
    assert fused_stats["prompt_tokens"] == loop_stats["prompt_tokens"]

    # stop-token handling at chunk granularity: truncate at first stop
    stop = loop_out[4]
    fused_stop, _ = gen.generate_fused(
        ids, max_new_tokens=24, sample=SampleConfig(greedy=True), seed=5,
        stop_tokens=(stop,), chunk=8)
    assert fused_stop == loop_out[:5]

    # sampled path: deterministic per seed, valid ids
    s1, _ = gen.generate_fused(ids, max_new_tokens=12,
                               sample=SampleConfig(temperature=0.9), seed=3)
    s2, _ = gen.generate_fused(ids, max_new_tokens=12,
                               sample=SampleConfig(temperature=0.9), seed=3)
    assert s1 == s2 and all(0 <= t < 512 for t in s1)


def test_generate_fused_edge_cases(gen):
    out, stats = gen.generate_fused([1, 2, 3], max_new_tokens=0)
    assert out == [] and stats["generated_tokens"] == 0
    with pytest.raises(ValueError, match="chunk"):
        gen.generate_fused([1, 2, 3], chunk=0)
    # fixed-size chunks: an uneven max_new_tokens still only ever compiles
    # the full-chunk signature (plus the cache-edge clamp)
    out, _ = gen.generate_fused([1, 2, 3], max_new_tokens=11,
                                sample=SampleConfig(greedy=True), seed=1,
                                chunk=8)
    ref, _ = gen.generate([1, 2, 3], max_new_tokens=11,
                          sample=SampleConfig(greedy=True), seed=1)
    assert out == ref


@pytest.mark.parametrize("preset,kinds", [("tiny", 1), ("tiny_moe", 3)])
def test_each_layer_kind_is_traced_once_a_program(preset, kinds,
                                                  monkeypatch):
    """Applied, ``LlamaModel`` runs a layer through its kind's jitted,
    inlined block: a lowered serving program traces the block once a
    ``LayerSpec`` (tiny: 2 layers of 1 kind; tiny_moe: 4 layers of 3),
    however many layers call it."""
    from tpustack.models import llama
    from tpustack.models.llama import init_kv_caches

    traced = []
    real = llama.LlamaBlock.apply

    def counted(block, *a, **kw):
        traced.append(block.spec)
        return real(block, *a, **kw)

    cfg = getattr(LlamaConfig, preset)(max_seq=96)
    m = LlamaModel(cfg, dtype=jnp.float32)
    params = jax.eval_shape(lambda: m.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    caches = jax.eval_shape(lambda: init_kv_caches(cfg, 2, jnp.float32))
    llama._layer_program.cache_clear()
    monkeypatch.setattr(llama.LlamaBlock, "apply", counted)
    jax.jit(lambda p, t, c: m.apply({"params": p}, t, None, c, 0, None,
                                    mutable=["moe_stats"])).lower(
        params, jax.ShapeDtypeStruct((2, 32), jnp.int32), caches)
    assert len(cfg.layer_specs) > kinds == len(set(cfg.layer_specs))
    assert sorted(map(repr, traced)) == sorted(map(repr,
                                                   set(cfg.layer_specs)))
