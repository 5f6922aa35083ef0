"""Tests of the benchmark's own yardstick.  Run by hand or in a rehearsal:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are outside ``tests/``: the repository's tier-1 does not collect them.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import loadgen  # noqa: E402
from benchmark.readers import arith, flight, trace  # noqa: E402

CELL = "qwen25-7b-int8.chat-c8"


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def tiny_cfg(name="qwen25-7b-int8"):
    return dict(load("configs", name + ".json"),
                **load("tests", "rehearsal.json"))


# ------------------------------------------------------------ (a) the trace
def test_trace_reducer_on_written_events():
    # two overlapping ops, a gap, a named kernel twice: busy is the union
    dev = {"/device:TPU:0": [("fusion.1", 0.0, 100.0),
                             ("paged_attention.3", 50.0, 100.0),
                             ("fusion.2", 400.0, 100.0),
                             ("paged_attention.3", 600.0, 50.0)]}
    assert trace.busy_ns(dev["/device:TPU:0"]) == 300.0
    busy = trace.device_busy(dev, window_s=1e-6)
    assert busy == {"busy_s": pytest.approx(300e-9), "window_s": 1e-6}
    assert trace.idle_share({"device_busy": busy}) == pytest.approx(70.0)
    seconds, calls = trace.kernel_seconds(dev, "paged_attention")
    assert (seconds, calls) == (pytest.approx(150e-9), 2)
    assert trace.top_ops(dev) == [["fusion", pytest.approx(200e-9)],
                                  ["paged_attention", pytest.approx(150e-9)]]
    gaps = dict(map(tuple, trace.idle_gaps(dev)))
    assert gaps["before fusion"] == pytest.approx(250e-9)
    # nothing to read is nothing, never 0
    assert trace.device_busy({}, 1.0) is None
    assert trace.idle_share({}) is None
    assert arith.paged_attention_roofline(
        {"peaks": None, "devices": dev, "trace_span": [0, 1]}) is None


def test_trace_extract_on_a_recorded_xplane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    assert any("/host:CPU" in row for row in trace.describe(path))
    if jax.default_backend() == "cpu":
        # no device plane on the CPU: no device metric can be read
        assert trace.extract(path) == {}


# ------------------------------------------------------- (b) load generator
def test_schedule_is_reproducible_and_seed_only_reorders():
    traffic = load("workloads", CELL + ".json")["traffic"]
    a = loadgen.client_schedules(traffic, 7)
    assert a == loadgen.client_schedules(traffic, 7)
    b = loadgen.client_schedules(traffic, 2 ** 31 + 11)
    sizes = lambda s: sorted((r["prompt_tokens"], r["n_predict"])
                             for c in s for r in c)
    assert a != b and sizes(a) == sizes(b)
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    assert all(lo <= r["prompt_tokens"] <= hi for c in a for r in c)
    body, ids = loadgen.request_body(traffic, 7, a[0][1], 1000)
    again, _ = loadgen.request_body(traffic, 7, a[0][1], 1000)
    assert body == again and len(ids) == a[0][1]["prompt_tokens"] - 1
    greedy = [r for c in a for r in c if r["greedy"]]
    assert len(greedy) * traffic["greedy_every"] == traffic["pool"]


def test_percentile_and_window_reduction():
    assert loadgen.percentile([], 90) is None
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3
    assert loadgen.percentile(list(range(11)), 90) == pytest.approx(9.0)
    vals = [3.0, 1.0, 2.0, 10.0]
    assert loadgen.percentile(vals, 90) == pytest.approx(
        float(np.percentile(vals, 90)))
    rec = lambda t_done, n, ok=True: {
        "status": 200 if ok else 500, "error": None, "tokens": n,
        "prompt_tokens": 100, "t_send": t_done - 2.0,
        "t_first": t_done - 1.5, "t_last": t_done - 0.5, "t_done": t_done,
        "chunks": [[t_done - 1.5, 1], [t_done - 0.5, n - 1]]}
    out = loadgen.reduce_window({"window": [10.0, 20.0], "records": [
        rec(12.0, 11), rec(15.0, 21), rec(19.0, 5, ok=False),
        rec(21.0, 99)]})
    assert (out["attempted"], out["failed"]) == (3, 1)
    # tokens count where they arrive: the request that ends after the
    # window gives its first chunk (and its prompt), the failed one nothing
    assert out["output_tokens_per_s"] == pytest.approx((11 + 21 + 1) / 10)
    assert out["prompt_tokens_per_s"] == pytest.approx(30.0)
    # the failed request enters the tail as a miss of the whole window
    assert out["ttft_p90_ms"] > 500.0 and out["tpot_p50_ms"] == 100.0


def test_histogram_quantile_between_scrapes():
    text = lambda a, b, c: "\n".join(
        f'm_bucket{{server="llm",phase="queue_wait",le="{le}"}} {v}'
        for le, v in (("0.1", a), ("0.5", b), ("+Inf", c)))
    before = flight.parse_histogram(text(5, 5, 5), "m", {"phase": "queue_wait"})
    after = flight.parse_histogram(text(5, 15, 15), "m",
                                   {"phase": "queue_wait"})
    assert flight.histogram_quantile(before, after, 0.9) == pytest.approx(
        0.1 + 0.4 * 0.9)
    assert flight.histogram_quantile(before, before, 0.9) is None


def test_arithmetic_counts_true_lengths():
    cfg = load("configs", "qwen25-7b-int8.json")
    el = arith.matmul_elements(cfg)
    assert el["layers"] + el["head"] == pytest.approx(7.07e9, rel=0.01)
    assert arith.kv_bytes_per_token(cfg) == 2 * 4 * (128 + 4) * 28
    rec = {"status": 200, "tokens": 101, "prompt_tokens": 400,
           "t_first": 0.0, "t_last": 10.0}
    whole = arith.decode_kv_bytes(cfg, [rec], 0.0, 10.0, chunk=0)
    assert whole == pytest.approx(100 * 450 * arith.kv_bytes_per_token(cfg))
    half = arith.decode_kv_bytes(cfg, [rec], 0.0, 5.0, chunk=0)
    assert half == pytest.approx(50 * 425 * arith.kv_bytes_per_token(cfg))


# ------------------------------------------------ (c) the plain reference
def test_reference_matches_the_program_prefill():
    """Full forward, no cache, float32: the reference and
    ``tpustack.models.llama`` agree to rounding, int8 and bf16 weights."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights as W
    from benchmark.reference import dense_gqa
    from benchmark.runners import llm_http
    from tpustack.models.llama import LlamaConfig, LlamaModel

    for name in ("qwen25-7b-int8", "qwen25-1p5b-bf16"):
        cfg = tiny_cfg(name)
        w = W.Weights(cfg, seed=5)
        lc = LlamaConfig(
            vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            ffn_dim=cfg["intermediate_size"], max_seq=cfg["ctx"],
            rope_theta=cfg["rope_theta"], rms_eps=cfg["rms_norm_eps"],
            qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
            quant="int8" if cfg["weights"] == "int8" else None)
        tokens = np.random.RandomState(0).randint(3, 512, (2, 24))
        with jax.default_matmul_precision("highest"):
            got, _ = LlamaModel(lc, dtype=jnp.float32).apply(
                {"params": llm_http.program_params(w)}, jnp.asarray(tokens))
        want = dense_gqa.logits_at(cfg, w, tokens,
                                   [np.arange(24), np.arange(24)])
        for b in range(2):
            np.testing.assert_allclose(np.asarray(got[b]),
                                       np.asarray(want[b]), atol=2e-4)


def test_served_path_agrees_and_the_control_does_not():
    """Prefill then decode through the paged pool, over HTTP, against the
    reference; then the control — the reference one precision down — has to
    read above the limit the rehearsal holds the program to."""
    from benchmark import idtok
    from benchmark import weights as W
    from benchmark.reference import dense_gqa
    from benchmark.runners import llm_http

    cfg = tiny_cfg()
    limit = load("workloads", CELL + ".json")["check"]["rehearsal"][
        "served_gap_limit"]
    w = W.Weights(cfg, seed=9)
    host = llm_http.Hosted(llm_http.build_server(cfg, w))
    try:
        ids = [[3 + (7 * j + 13 * r) % 500 for j in range(40 + r)]
               for r in range(3)]
        recs = loadgen.burst(host.url, [
            {"prompt": idtok.render_ids(p), "n_predict": 40, "stream": True,
             "temperature": 0.0, "speculative": False} for p in ids])
    finally:
        host.close()
    assert all(r["status"] == 200 and len(r["tokens"]) >= 39 for r in recs)
    seqs = [([idtok.BOS_ID] + p, r["tokens"]) for p, r in zip(ids, recs)]
    lower = dense_gqa.LOWER[cfg["weights"]]
    gaps = dense_gqa.served_gaps(cfg, w, seqs, lower=lower)
    served = max(float(g.max()) for g in gaps["served"])
    control = max(float(g.max()) for g in gaps["control"])
    assert served <= limit < control, (served, limit, control)


# ------------------------------------------------------- (d) a whole run
def run_rehearsal(monkeypatch, capsys, seed=21):
    from benchmark import run as bench_run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", "5", "--trace", "1",
                           "--cpu-rehearsal"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal_runs_end_to_end_and_reports_no_device_metric(
        monkeypatch, capsys):
    line = run_rehearsal(monkeypatch, capsys)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    counted = {m["name"] for m in bench["per_layer"]
               if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counted
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0


def alter_tokens(monkeypatch):
    """A token altered where it is produced: the serving process streams
    another id than the engine chose."""
    from benchmark import idtok

    monkeypatch.setattr(
        idtok.IdTokenizer, "decode",
        lambda self, ids: "".join(
            f"{int(i) + 1 if int(i) % 3 == 0 else int(i)} " for i in ids))


def perturb_weights(monkeypatch):
    """The program serves another model than the seed's."""
    from benchmark.runners import llm_http

    real = llm_http.program_params

    def broken(weights):
        tree = real(weights)
        proj = tree["layers_0"]["mlp"]["down_proj"]
        key = "scale" if "scale" in proj else "kernel"
        proj[key] = proj[key] * 3
        return tree

    monkeypatch.setattr(llm_http, "program_params", broken)


@pytest.mark.parametrize("fault", [alter_tokens, perturb_weights])
def test_a_broken_timed_path_reads_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    line = run_rehearsal(monkeypatch, capsys, seed=22)
    assert line["correct"] is False
    gap = line["checks"]["served_gap"]
    assert gap["value"] > gap["limit"]


def test_no_accelerator_no_result():
    """Without a TPU (JAX held to the CPU but not named on purpose for a
    rehearsal) the run exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
