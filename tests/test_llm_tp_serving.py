"""Tensor-parallel SERVING paths (ISSUE 10 tentpole): the continuous
engine — the block pool it builds itself and one handed in, int8 KV, and
the speculative verify — run GSPMD-partitioned over a tp mesh with the KV
substrate sharded on the head axis, and greedy outputs stay BYTE-IDENTICAL
to the unsharded engine across all of it.  Plus: the pool tensors are
provably head-axis-sharded (per-chip HBM = total/tp), the kv-pool leak
check and sanitizer quiesce pass under tp, the HTTP surface serves the
same bytes through a tp server, the LLM_SHARD_KV=0 bisection keeps
compiler-placed caches, the new lint_manifests chip-arithmetic rule fires
on drift, and the ``bench_llm --tp`` smoke runs green on the forced-8-
device CPU backend."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.parallel import build_mesh
from tpustack.serving.kv_pool import PagedKVRuntime
from tpustack.serving.speculative import SpecConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SampleConfig(greedy=True)
BLOCK = 8

PROMPTS = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [20],
           [30 + i for i in range(12)], [40, 41]]


@pytest.fixture(scope="module")
def ref():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def _tp_gen(ref, tp, kv_quant=None, shard_kv=True):
    cfg = dataclasses.replace(ref.cfg, kv_quant=kv_quant)
    mesh = build_mesh((1, 1, tp, 1), devices=jax.devices()[:tp])
    return Generator(cfg, params=jax.device_get(ref.params),
                     dtype=jnp.float32, mesh=mesh, shard_kv=shard_kv)


def _runtime(gen, capacity_blocks=32, cache=True):
    return PagedKVRuntime.build(gen.cfg, 2, block=BLOCK,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32, mesh=gen.kv_mesh,
                                prefix_cache=cache)


def _run(engine, requests):
    results = {}
    queue = [SlotRequest(ids=r["ids"], max_new=r["max_new"],
                         sample=r.get("sample", GREEDY), seed=r.get("seed"),
                         on_done=(lambda t, s, i=i:
                                  results.__setitem__(i, (t, s))))
             for i, r in enumerate(requests)]
    stats = engine.run(lambda: queue.pop(0) if queue else None)
    return results, stats


# --------------------------------------------------------- engine parity
@pytest.mark.parametrize("tp", [2, pytest.param(4, marks=pytest.mark.slow),
                                pytest.param(8, marks=pytest.mark.slow)])
def test_engine_tp_matches_unsharded_dense_and_paged(ref, tp):
    """THE acceptance bar: the continuous engine over a tp mesh emits the
    unsharded engine's exact greedy bytes — on the pool it builds itself
    AND on one handed in — including slot reuse, mixed lengths, and a seeded
    sampled row (per-slot PRNG streams are sharding-independent)."""
    tpg = _tp_gen(ref, tp)
    reqs = [{"ids": p, "max_new": 8} for p in PROMPTS]
    reqs.append({"ids": [45, 46, 47, 48], "max_new": 6, "seed": 77,
                 "sample": SampleConfig(temperature=1.1, top_k=8)})
    base, _ = _run(ContinuousEngine(ref, slots=2, chunk=4,
                                    stop_tokens=(2,)), reqs)
    own, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4,
                                   stop_tokens=(2,)), reqs)
    rt = _runtime(tpg)
    free0 = rt.pool.n_free
    paged, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4, stop_tokens=(2,),
                                     paged=rt), reqs)
    for i in range(len(reqs)):
        assert own[i][0] == base[i][0], f"tp own-pool row {i} diverged"
        assert paged[i][0] == base[i][0], f"tp paged row {i} diverged"
    # leak check under tp: everything still held is cache-resident (the
    # prefix trie's own refs); evicting it returns the pool to pristine
    rt.cache.clear()
    assert rt.pool.n_free == free0


def test_engine_tp_int8_kv_matches_unsharded(ref):
    """int8 KV under tp: the [.., kvh] scale arrays shard consistently
    with the head-sharded int8 K/V and greedy bytes are unchanged."""
    cfg8 = dataclasses.replace(ref.cfg, kv_quant="int8")
    solo = Generator(cfg8, params=jax.device_get(ref.params),
                     dtype=jnp.float32)
    tpg = _tp_gen(ref, 2, kv_quant="int8")
    reqs = [{"ids": p, "max_new": 8} for p in PROMPTS[:3]]
    base, _ = _run(ContinuousEngine(solo, slots=2, chunk=4), reqs)
    own, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4), reqs)
    paged, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4,
                                     paged=_runtime(tpg)), reqs)
    for i in range(len(reqs)):
        assert own[i][0] == base[i][0]
        assert paged[i][0] == base[i][0]


def test_engine_tp_speculative_matches_unsharded(ref):
    """Speculative verify under tp: drafts scored by the mesh-partitioned
    one-pass verify accept exactly what the unsharded spec-off engine
    would have produced — own pool and handed-in pool."""
    # repetitive prompts so prompt-lookup actually drafts
    pat = [7, 11, 13, 5]
    prompts = [[pat[j % 4] + i for j in range(16)] for i in range(3)]
    reqs = [{"ids": p, "max_new": 12} for p in prompts]
    base, _ = _run(ContinuousEngine(ref, slots=2, chunk=4), reqs)
    tpg = _tp_gen(ref, 2)
    spec = lambda: SpecConfig(tokens=3)
    own, ds = _run(ContinuousEngine(tpg, slots=2, chunk=4, spec=spec()),
                   reqs)
    rt = _runtime(tpg)
    paged, ps = _run(ContinuousEngine(tpg, slots=2, chunk=4, spec=spec(),
                                      paged=rt), reqs)
    for i in range(len(reqs)):
        assert own[i][0] == base[i][0], f"tp spec own-pool row {i} diverged"
        assert paged[i][0] == base[i][0], f"tp spec paged row {i} diverged"
    assert ds["spec_drafted_tokens"] > 0, "spec never drafted under tp"
    assert ps["spec_drafted_tokens"] > 0


def test_engine_tp_shard_kv_off_bisection(ref):
    """LLM_SHARD_KV=0 (shard_kv=False): compute stays mesh-partitioned but
    the caches are compiler-placed (kv_mesh None) — outputs unchanged,
    pool tensors unsharded (per-shard == total bytes)."""
    tpg = _tp_gen(ref, 2, shard_kv=False)
    assert tpg.mesh is not None and tpg.kv_mesh is None
    rt = _runtime(tpg)
    assert rt.kv_shards == 1 and rt.per_shard_bytes == rt.pool_bytes
    reqs = [{"ids": p, "max_new": 6} for p in PROMPTS[:2]]
    base, _ = _run(ContinuousEngine(ref, slots=2, chunk=4), reqs)
    off, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4, paged=rt), reqs)
    for i in range(len(reqs)):
        assert off[i][0] == base[i][0]


# ----------------------------------------------- substrate actually shards
def test_pool_tensors_head_axis_sharded(ref):
    """The paged pool under tp=2 is REALLY sharded: every pool tensor's
    sharding spec names tp on the kv-head axis and the runtime's per-shard
    accounting reports exactly half the pool bytes per chip."""
    from jax.sharding import NamedSharding

    tpg = _tp_gen(ref, 2)
    rt = _runtime(tpg, cache=False)
    assert rt.kv_shards == 2
    assert rt.per_shard_bytes * 2 == rt.pool_bytes
    for layer in rt.arrays:
        for name, x in layer.items():
            assert isinstance(x.sharding, NamedSharding), name
            flat = [a for entry in x.sharding.spec if entry
                    for a in ((entry,) if isinstance(entry, str) else entry)]
            assert flat == ["tp"], (name, x.sharding.spec)
            # head axis: index 2 both for [N, blk, kvh, hd] and [N, blk, kvh]
            assert tuple(x.sharding.spec)[2] == "tp", name
    st = rt.stats()
    assert st["kv_shards"] == 2 and st["per_shard_bytes"] * 2 == st["pool_bytes"]


def test_tp_indivisible_kv_heads_replicate(ref):
    """GQA guard: tiny has 2 kv heads, so tp=4 cannot split the head axis
    — the substrate replicates (correctness over HBM split) instead of
    crashing, and the engine still matches unsharded."""
    from tpustack.parallel.sharding import can_shard_kv_heads

    tpg = _tp_gen(ref, 4)
    assert not can_shard_kv_heads(tpg.kv_mesh, tpg.cfg.n_kv_heads)
    rt = _runtime(tpg, cache=False)
    assert rt.kv_shards == 1
    reqs = [{"ids": PROMPTS[0], "max_new": 6}]
    base, _ = _run(ContinuousEngine(ref, slots=2, chunk=4), reqs)
    got, _ = _run(ContinuousEngine(tpg, slots=2, chunk=4, paged=rt), reqs)
    assert got[0][0] == base[0][0]


# ------------------------------------------------- sanitizer quiesce + leak
def test_kv_quiesce_passes_sharded(ref):
    """The tpusan kv-leak check must hold on a SHARDED pool: after a busy
    period with prefix-cache inserts and a cancelled request, every used
    block is cache-resident at refcount exactly 1."""
    from tpustack import sanitize

    tpg = _tp_gen(ref, 2)
    rt = _runtime(tpg)
    shared = list(range(5, 5 + 16))
    results = {}

    def req(i, cancelled=False):
        ids = shared + [50 + i]
        m = rt.cache.match(ids)
        prefix = (m.length, m.block_ids) if m.length else None
        return SlotRequest(
            ids=ids, max_new=6, sample=GREEDY, prefix=prefix,
            cancelled=(lambda: True) if cancelled else (lambda: False),
            on_prefill_blocks=lambda bids, ids=list(ids): rt.cache.insert(
                ids, bids),
            on_done=lambda t, s, i=i: results.__setitem__(i, t))

    queue = [req(0), req(1), req(2, cancelled=True)]
    ContinuousEngine(tpg, slots=2, chunk=4, paged=rt).run(
        lambda: queue.pop(0) if queue else None)
    assert results[0] and results[1]
    # raises on any leaked reference; passing sharded IS the assertion
    sanitize.check_kv_quiesce(rt, where="tp quiesce test")
    rt.cache.clear()
    assert rt.pool.n_used == 0


# ----------------------------------------------------------- HTTP surface
def _server(gen, **kw):
    from tpustack.models.text_tokenizer import ByteTokenizer
    from tpustack.obs import Registry
    from tpustack.serving.llm_server import LLMServer

    return LLMServer(generator=gen, tokenizer=ByteTokenizer(512),
                     max_batch=4, registry=Registry(), **kw)


def _post_all(server, payloads):
    import asyncio

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


def test_http_tp_parity_props_and_gauges(ref):
    """The HTTP bar: a tp=2 server (paged default engine over the sharded
    pool) serves byte-identical completions to the unsharded server, and
    reports the mesh shape + per-chip HBM on /props and the new mesh
    gauges on /metrics."""
    prompts = [{"prompt": "tensor parallel serving " + t, "n_predict": 6,
                "temperature": 0} for t in ("q1", "q2", "q1")]
    base_outs, base_props, _ = _post_all(_server(ref), prompts)
    tpg = _tp_gen(ref, 2)
    outs, props, metrics = _post_all(_server(tpg), prompts)
    assert outs == base_outs
    assert base_props["mesh"]["enabled"] is False
    mesh = props["mesh"]
    assert mesh["enabled"] and mesh["tp"] == 2 and mesh["devices"] == 2
    assert mesh["kv_head_sharded"] is True
    assert mesh["axes"]["tp"] == 2
    # per-chip bills: weights strictly below the unsharded total; KV half
    assert (mesh["weights_per_chip_bytes"]
            < base_props["mesh"]["weights_per_chip_bytes"])
    assert mesh["kv_per_chip_bytes"] * 2 == props["paged_kv"]["pool_bytes"]
    assert props["paged_kv"]["kv_shards"] == 2
    assert 'tpustack_mesh_axis_chips{server="llm",axis="tp"} 2' in metrics
    assert "tpustack_llm_weights_per_chip_bytes" in metrics
    assert "tpustack_llm_tp_collective_bytes" in metrics


def test_server_env_70b_requires_tp(monkeypatch):
    """LLM_PRESET=llama2_70b without LLM_TP must fail at startup with a
    clear error, not OOM mid-load."""
    monkeypatch.setenv("LLM_PRESET", "llama2_70b")
    monkeypatch.delenv("LLM_TP", raising=False)
    from tpustack.serving.llm_server import _build_generator

    with pytest.raises(ValueError, match="LLM_TP"):
        _build_generator()


def test_server_env_tp_exceeding_devices_is_clear_error(monkeypatch):
    monkeypatch.setenv("LLM_PRESET", "tiny")
    monkeypatch.setenv("LLM_TP", "64")
    from tpustack.serving.llm_server import _build_generator

    with pytest.raises(ValueError, match="google.com/tpu"):
        _build_generator()


# ------------------------------------------------- manifest chip arithmetic
def _lint_manifest(tmp_path, text):
    from tools.tpulint.checker_manifests import lint

    d = tmp_path / "cluster-config"
    d.mkdir(exist_ok=True)
    (d / "w.yaml").write_text(text)
    return lint(root=d)


_DEPLOY_TMPL = """
apiVersion: apps/v1
kind: Deployment
metadata: {{name: x, namespace: llm}}
spec:
  template:
    spec:
      terminationGracePeriodSeconds: 30
      containers:
        - name: server
          command: [python, -m, tpustack.serving.llm_server]
          readinessProbe: {{httpGet: {{path: /readyz, port: 8080}}}}
          livenessProbe: {{httpGet: {{path: /healthz, port: 8080}}}}
          env: [{env}]
          resources:
            requests: {{cpu: "1", memory: 1Gi}}
            limits: {{cpu: "1", memory: 1Gi, "google.com/tpu": {tpu}}}
"""


def test_lint_tpu_request_must_match_parallelism(tmp_path):
    """The new rule: google.com/tpu == LLM_TP/SD15_DP product (per host),
    both directions — the 1-chip-manifest-vs-tp-comment drift class."""
    # tp=8 on a 1-chip pod: fires
    errs = _lint_manifest(tmp_path, _DEPLOY_TMPL.format(
        env='{name: LLM_TP, value: "8"}', tpu=1))
    assert any("google.com/tpu: 1" in e and "want 8" in e for e in errs), errs
    # 8 chips with no parallelism env on a serving container: fires
    errs = _lint_manifest(tmp_path, _DEPLOY_TMPL.format(env="", tpu=8))
    assert any("declares no" in e for e in errs), errs
    # consistent: clean
    assert not _lint_manifest(tmp_path, _DEPLOY_TMPL.format(
        env='{name: LLM_TP, value: "8"}', tpu=8))
    # multi-host: global product divides across NUM_PROCESSES
    assert not _lint_manifest(tmp_path, _DEPLOY_TMPL.format(
        env='{name: LLM_TP, value: "16"}, {name: NUM_PROCESSES, value: "2"}',
        tpu=8))
    errs = _lint_manifest(tmp_path, _DEPLOY_TMPL.format(
        env='{name: LLM_TP, value: "16"}, {name: NUM_PROCESSES, value: "2"}',
        tpu=16))
    assert any("want 8" in e for e in errs), errs


def test_repo_manifests_pass_chip_arithmetic():
    from tools.tpulint.checker_manifests import lint

    assert lint() == []


# --------------------------------------------------------- multihost driver
def test_multihost_driver_single_process(monkeypatch, capsys, tmp_path):
    """The JobSet entrypoint degrades to a one-host batch serving run
    without the DCN env (the CPU-tier proof; the 2-process DCN leg rides
    the slow tier with test_distributed_bootstrap)."""
    prompts = tmp_path / "prompts.txt"
    prompts.write_text("hello multihost\nsecond prompt\n")
    for k, v in {"LLM_PRESET": "tiny", "LLM_CTX": "64", "LLM_TP": "2",
                 "LLM_MAX_BATCH": "2", "LLM_MULTIHOST_NEW_TOKENS": "4",
                 "LLM_MULTIHOST_PROMPTS": str(prompts)}.items():
        monkeypatch.setenv(k, v)
    for k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID",
              "MODEL_DIR"):
        monkeypatch.delenv(k, raising=False)
    from tpustack.serving import llm_multihost

    assert llm_multihost.run() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["requests"] == 2 and out["tp"] == 2
    assert all(r["generated_tokens"] <= 4 for r in out["results"])


# ------------------------------------------------------------- bench smoke
def test_bench_tp_tiny_smoke():
    """Shell ``tools/bench_llm.py --tp 2 --tiny`` — the CPU-runnable
    tensor-parallel sweep tier-1 keeps green: outputs identical tp on/off
    on the block pool and the per-chip weight bill strictly below the
    unsharded total."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--tp", "2", "--tiny"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=8"),
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["outputs_identical"] is True
    assert out["tp_ways"] == 2
    sweep = {c["mode"]: c for c in out["sweep"]}
    assert set(sweep) == {"paged"}
    for cell in sweep.values():
        assert (cell["tp_on"]["weights_per_chip_bytes"]
                < cell["tp_off"]["weights_per_chip_bytes"])
    assert (sweep["paged"]["tp_on"]["kv_per_chip_bytes"] * 2
            == sweep["paged"]["tp_off"]["kv_per_chip_bytes"])
