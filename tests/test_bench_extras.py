"""bench.py driver-artifact shape: the LLM/Wan extras folded into the one
JSON line must keep their schema; a tool that fails becomes an error
record in the line — and a non-zero exit of the run, so a cell that went
missing cannot pass for a measurement."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.bench_schema import (LLM_EXTRA_KEEP, META_KEYS,  # noqa: E402
                                WAN_KEEP, check_meta, prune)


def load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_llm_extras_schema(monkeypatch):
    bench = load_bench()
    calls = []

    def fake_run(cmd, capture_output, text, timeout):
        calls.append(cmd)
        payload = {"metric": "m", "value": 1.0, "unit": "tok/s",
                   "steady_decode_tokens_per_sec": 2.0,
                   "prefill_tokens_per_sec": 3.0, "roofline_pct": 4.0,
                   "prefill_roofline_pct": 5.0,
                   # the continuous run's flight-recorder aggregates: the
                   # artifact must record utilization, not just throughput
                   "flight": {"mean_occupancy": 7.5, "spec_acceptance": 0.6,
                              "tokens_per_weight_pass": 2.1,
                              "live_mfu": None, "live_hbm_util": None,
                              "device_kind": None},
                   # the replay extra's artifact keys ride the same keep
                   # list into the driver artifact
                   "schedule_sha": "abc123", "offered_rps": 5.0,
                   "goodput_rps": 4.5, "goodput_ratio": 0.9,
                   "shed": 2, "deadline": 1, "errors": 3,
                   "tenants": {"interactive": {"offered": 10}},
                   # QoS split: per-priority outcome table + the server's
                   # qos counters ride the replay cell too
                   "priorities": {"batch": {"shed": 2}},
                   "server_qos": {"counters": {"shed": {"batch": 2}}},
                   # host-tier + chunked-prefill cells: off/on comparison
                   # tables and the tier's conservation ledger ride the
                   # same keep list
                   "tier_off": {"prefix_hit_ratio": 0.1},
                   "tier_on": {"prefix_hit_ratio": 0.6},
                   "host_tier": {"spilled_total": 23, "restored_total": 14},
                   "ttft_p99_speedup": 1.4,
                   "chunk_off": {"prefill_chunks": 0},
                   "chunk_on": {"prefill_chunks": 3},
                   "prefill_chunk_tokens": 512,
                   # KV working-set observatory snapshots: the paged
                   # bench's per-pool profiler view and the replay
                   # server's /debug/kvcache ride the same keep list
                   "kvprof": {"working_set_blocks": 12.0,
                              "counterfactual_hit_ratio": {"2x": 0.8}},
                   "server_kvcache": {"enabled": True,
                                      "working_set_blocks": 9.0},
                   # L7 router view when the replay drove through
                   # tpustack.serving.router (--url at the router)
                   "server_router": {
                       "requests": {"ok": 50},
                       "failovers": {"connect_error": 1},
                       "affinity": {"hit": 22, "hit_ratio": 0.85}},
                   # elastic capacity controller view when the replay ran
                   # with --autoscaler-url (desired/actual + events)
                   "server_autoscaler": {
                       "desired": 2, "actual": 2, "converged": True,
                       "events": [{"direction": "up", "reason": "load"}]},
                   # provenance + exact-counter signature (PR 13): every
                   # tool artifact carries them and the driver keeps them
                   "meta": {"schema_version": 1, "git_sha": "cafe",
                            "device_kind": "cpu", "backend": "cpu",
                            "ts": 1.0, "knobs": {}},
                   "signature": {"engine.generated_tokens": 64},
                   "ignored_key": "must not leak into the artifact"}
        return subprocess.CompletedProcess(cmd, 0,
                                           stdout=json.dumps(payload) + "\n",
                                           stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._llm_extras(8)
    assert set(out) == {"continuous_e2e", "prefill_8k", "shared_prefix",
                        "paged", "speculative", "host_tier",
                        "chunked_prefill", "tp", "replay"}
    for sub in out.values():
        assert sub["value"] == 1.0
        assert sub["steady_decode_tokens_per_sec"] == 2.0
        assert "ignored_key" not in sub
    # the flight aggregates ride the continuous cell into the artifact
    assert out["continuous_e2e"]["flight"]["mean_occupancy"] == 7.5
    assert out["continuous_e2e"]["flight"]["spec_acceptance"] == 0.6
    # the shared meta block and the perf signature ride EVERY cell (the
    # keep-list is tools/bench_schema.LLM_EXTRA_KEEP — one module, shared
    # with bench.py, so this test and the driver cannot drift)
    for sub in out.values():
        assert check_meta(sub["meta"]) == []
        assert sub["signature"] == {"engine.generated_tokens": 64}
    # the replay cell keeps the open-loop goodput/percentile keys
    assert out["replay"]["goodput_ratio"] == 0.9
    assert out["replay"]["schedule_sha"] == "abc123"
    assert out["replay"]["errors"] == 3
    assert out["replay"]["tenants"]["interactive"]["offered"] == 10
    # the per-priority split + server qos counters ride the replay cell
    assert out["replay"]["priorities"]["batch"]["shed"] == 2
    assert out["replay"]["server_qos"]["counters"]["shed"]["batch"] == 2
    # the kvprof snapshots (paged pool view + replay server view) are kept
    assert out["paged"]["kvprof"]["working_set_blocks"] == 12.0
    assert out["paged"]["kvprof"]["counterfactual_hit_ratio"]["2x"] == 0.8
    assert out["replay"]["server_kvcache"]["working_set_blocks"] == 9.0
    # the router's health/failover/affinity view rides the replay cell
    assert out["replay"]["server_router"]["affinity"]["hit_ratio"] == 0.85
    assert out["replay"]["server_router"]["failovers"]["connect_error"] == 1
    # ...and so does the capacity controller's convergence evidence
    assert out["replay"]["server_autoscaler"]["converged"] is True
    assert out["replay"]["server_autoscaler"]["events"][0]["reason"] == "load"
    # the host-tier ledger + off/on tables ride the host_tier cell, the
    # chunk tables ride chunked_prefill
    assert out["host_tier"]["host_tier"]["spilled_total"] == 23
    assert out["host_tier"]["tier_on"]["prefix_hit_ratio"] == 0.6
    assert out["host_tier"]["ttft_p99_speedup"] == 1.4
    assert out["chunked_prefill"]["chunk_on"]["prefill_chunks"] == 3
    assert out["chunked_prefill"]["prefill_chunk_tokens"] == 512
    # the bench replay scenario is mixed-priority (one tenant per class)
    assert any(":interactive" in " ".join(c) and ":batch" in " ".join(c)
               for c in calls)
    # the seven tool invocations: batch-8 continuous + the 8k prefill
    # + the shared-prefix (prefix KV cache) + the paged-KV sweep + the
    # speculative-decoding sweep + the tensor-parallel sweep + the
    # open-loop trace replay
    assert any("--continuous" in c for c in calls)
    assert any("8192" in c for c in calls)
    assert any("--shared-prefix" in c for c in calls)
    assert any("--paged" in c for c in calls)
    assert any("--speculative" in c for c in calls)
    assert any("--tp" in c for c in calls)
    assert any("--self-host" in c for c in calls)


def test_wan_extras_schema(monkeypatch):
    bench = load_bench()

    def fake_run(cmd, capture_output, text, timeout):
        payload = {"metric": "w", "value": 600.0, "unit": "videos/hour/chip",
                   "seconds_per_video": 6.0, "mfu": 0.65,
                   "meta": {"schema_version": 1, "git_sha": None,
                            "device_kind": "cpu", "backend": "cpu",
                            "ts": 2.0, "knobs": {}},
                   "extra": "drop me"}
        return subprocess.CompletedProcess(cmd, 0,
                                           stdout=json.dumps(payload) + "\n",
                                           stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._wan_extras()
    assert out["mfu"] == 0.65 and out["seconds_per_video"] == 6.0
    assert check_meta(out["meta"]) == []
    assert "extra" not in out


def test_extras_degrade_on_tool_failure(monkeypatch):
    """A crashing tool yields {'error': ...}, never an exception — the
    rest of the line stays readable (main() turns the record into a
    non-zero exit: test_failed_child_fails_the_run)."""
    bench = load_bench()

    def fake_run(cmd, capture_output, text, timeout):
        raise subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._llm_extras(8)
    assert "error" in out["continuous_e2e"] and "error" in out["prefill_8k"]
    assert "error" in out["shared_prefix"] and "error" in out["paged"]
    assert "error" in out["speculative"] and "error" in out["replay"]
    wan = bench._wan_extras()
    assert "error" in wan


def test_run_tool_nonzero_exit_is_error_record(monkeypatch):
    """A tool that exits nonzero after printing a stale JSON-
    looking line must be recorded as an error (with the stderr tail), not
    trusted as a measurement."""
    bench = load_bench()

    def fake_run(cmd, capture_output, text, timeout):
        return subprocess.CompletedProcess(
            cmd, 3, stdout=json.dumps({"metric": "stale", "value": 1}) + "\n",
            stderr="Traceback ...\nRuntimeError: device fell over")

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._run_tool("t", ["tools/bench_llm.py"])
    assert out["error"] == "exit code 3"
    assert "device fell over" in out["stderr_tail"]
    assert "metric" not in out and "value" not in out


def test_tp_cell_is_skipped_below_eight_devices(monkeypatch):
    """The tp=8 sweep needs eight chips: on a smaller host the cell says so
    instead of launching a child that can only fail."""
    bench = load_bench()
    calls = []

    def fake_run(cmd, capture_output, text, timeout):
        calls.append(cmd)
        return subprocess.CompletedProcess(
            cmd, 0, stdout=json.dumps({"value": 1.0}) + "\n", stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = bench._llm_extras(1)
    assert "skipped" in out["tp"] and "error" not in out["tp"]
    assert not any("--tp" in c for c in calls)


def _fake_children(fail_substr):
    """subprocess.run stand-in: every child prints a valid artifact, except
    the one whose command contains ``fail_substr``, which exits 3."""
    def fake_run(cmd, capture_output, text, timeout):
        if fail_substr and any(fail_substr in c for c in cmd):
            return subprocess.CompletedProcess(cmd, 3, stdout="",
                                               stderr="boom")
        payload = {"metric": "m", "value": 1.0, "unit": "u",
                   "device": {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1},
                   "content_check": "pass", "families": {}}
        return subprocess.CompletedProcess(
            cmd, 0, stdout=json.dumps(payload) + "\n", stderr="")
    return fake_run


def test_failed_child_fails_the_run(monkeypatch, capsys):
    """ISSUE 21 item 4: an extra that fails, a content check that errors,
    or a failed SD measurement each make bench.py exit non-zero (the line
    is still printed, with the error record, unless SD itself failed)."""
    bench = load_bench()
    monkeypatch.setattr(subprocess, "run", _fake_children(None))
    assert bench.main([]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["content_check"] == "pass"
    assert "skipped" in line["llm"]["tp"]          # one device reported

    monkeypatch.setattr(subprocess, "run", _fake_children("--speculative"))
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["llm"]["speculative"]["error"] == "exit code 3"

    monkeypatch.setattr(subprocess, "run", _fake_children("verify_hw.py"))
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["content_check"].startswith("error:")

    monkeypatch.setattr(subprocess, "run", _fake_children("--phase"))
    assert bench.main([]) == 1
    assert capsys.readouterr().out.strip() == ""   # no result printed


def test_bench_small_exits_nonzero_when_its_child_fails():
    """The real CLI, no fakes: ``bench.py --small`` runs the SD measurement
    as a child; a dp mesh wider than the host makes that child fail, and
    the parent must exit non-zero without printing a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--small",
         "--dp", "64"], env=env, capture_output=True, text=True,
        timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "exited" in proc.stderr


def test_meta_contract_matches_producer():
    """tools/bench_schema.META_KEYS IS the shape perfsig.artifact_meta
    produces — the schema test and the one sanctioned producer cannot
    drift (and every bench tool stamps through that producer)."""
    from tpustack.obs import perfsig

    meta = perfsig.artifact_meta(0.0)
    assert set(meta) == set(META_KEYS)
    assert check_meta(meta) == []


def test_prune_is_keeplist_projection():
    rec = {k: i for i, k in enumerate(LLM_EXTRA_KEEP[:3])}
    rec["stray"] = "x"
    assert prune(rec, LLM_EXTRA_KEEP) == {k: rec[k]
                                          for k in LLM_EXTRA_KEEP[:3]}
    assert prune({}, WAN_KEEP) == {}
