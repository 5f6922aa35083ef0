"""Perf sentinel: deterministic signatures and the noise-aware bench
regression gate.

Two layers under test, mirroring the subsystem:

- ``tpustack.obs.perfsig``: signature assembly (dotted int counters),
  the shared ``meta`` provenance block, exact-diff semantics, the forced
  CompileWatch and its ``tpustack_recompiles_total`` export, baseline
  info gauges;
- ``tools/perf_gate.py``: fire/clean minimal pairs for the comparator
  (seeded counter regression → gating rows naming the offender;
  wall-clock jitter inside tolerance → clean), the ``--update-baselines``
  round-trip, and the REAL gate: ``--tiny`` scenario subsets shelled as
  subprocesses, clean on the unmodified tree and nonzero (naming the
  regressed metric) when the prefix cache is deliberately disabled via
  ``TPUSTACK_PREFIX_CACHE=0``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import perf_gate  # noqa: E402
from tools.bench_schema import (LLM_EXTRA_KEEP, META_KEYS,  # noqa: E402
                                WAN_KEEP, check_meta)
from tpustack.obs import perfsig  # noqa: E402


# --------------------------------------------------------------- perfsig
def test_signature_assembly_is_flat_dotted_ints():
    # sum_engine_stats shares ENGINE_COUNTERS with engine_signature, so a
    # counter added to the tuple gates in single- and multi-run modes alike
    summed = perfsig.sum_engine_stats([
        {"requests": 2, "generated_tokens": 48, "decode_weight_passes": 24,
         "tokens_per_s": 61.7},
        {"requests": 2, "generated_tokens": 48,
         "decode_weight_passes": 24}])
    sig = perfsig.signature(
        engine=summed,
        prefix_cache={"hits": 5, "misses": 1, "evictions": 0,
                      "cached_tokens_served": 160, "inserted_tokens": 128,
                      "entries": 8, "hit_rate": 0.83},
        flight={"waves": 7, "tokens": 90, "spec_drafted": 0,
                "spec_accepted": 0, "tokens_per_s": 9.9},
        extra={"outputs_identical": True,
               "kv_pool.allocated_blocks_total": 40})
    assert sig["engine.generated_tokens"] == 96
    assert sig["engine.decode_weight_passes"] == 48
    assert sig["kv_pool.allocated_blocks_total"] == 40
    assert sig["prefix_cache.cached_tokens_served"] == 160
    assert sig["flight.waves"] == 7
    assert sig["outputs_identical"] == 1
    # ratios/rates never enter the signature — ints only, exactly equal
    assert all(isinstance(v, int) for v in sig.values())
    assert "engine.tokens_per_s" not in sig and "flight.tokens_per_s" not in sig
    assert list(sig) == sorted(sig)


def test_diff_signatures_fire_and_clean():
    base = {"engine.generated_tokens": 96, "recompiles._decode_scan": 1}
    assert perfsig.diff_signatures(base, dict(base)) == []
    rows = perfsig.diff_signatures(
        base, {"engine.generated_tokens": 80, "prefix_cache.hits": 5})
    by_key = {r["key"]: r for r in rows}
    assert by_key["engine.generated_tokens"]["status"] == "mismatch"
    assert by_key["engine.generated_tokens"]["fresh"] == 80
    assert by_key["recompiles._decode_scan"]["status"] == "missing"
    assert by_key["prefix_cache.hits"]["status"] == "new"


def test_artifact_meta_shape_and_knob_snapshot(monkeypatch):
    monkeypatch.setenv("TPUSTACK_SPEC_TOKENS", "6")
    monkeypatch.delenv("TPUSTACK_KV_BLOCK", raising=False)
    meta = perfsig.artifact_meta(1234.5)
    assert check_meta(meta) == []
    assert set(META_KEYS) <= set(meta)
    assert meta["schema_version"] == perfsig.SCHEMA_VERSION
    assert meta["ts"] == 1234.5
    # snapshot records overridden knobs only (defaults are code, already
    # pinned by the git sha) and never undeclared names
    assert meta["knobs"].get("TPUSTACK_SPEC_TOKENS") == "6"
    assert "TPUSTACK_KV_BLOCK" not in meta["knobs"]


class _FakeJit:
    """Stands in for a PjitFunction: exposes ``_cache_size``."""

    def __init__(self):
        self.size = 0

    def _cache_size(self):
        return self.size


def test_compile_watch_force_and_recompile_counter():
    from tpustack import sanitize
    from tpustack.obs import catalog as obs_catalog

    fake = _FakeJit()
    watch = sanitize.CompileWatch()
    # force=True baselines even if the sanitizer env is off (the bench
    # measures recompiles as data, not violations)
    watch.watch("_fake_entry", fake, budget=99, force=True)
    fake.size = 3
    sig = perfsig.recompile_signature(watch)
    assert sig == {"recompiles._fake_entry": 3}
    if not sanitize.enabled():
        pytest.skip("check()-path export needs the sanitizer enabled "
                    "(tier-1 runs with it on)")
    child = obs_catalog.build(None)["tpustack_recompiles_total"].labels(
        entry_point="_fake_entry")
    before = child.value
    watch.check(where="test")
    assert child.value == before + 3  # growth exported once...
    watch.check(where="test")
    assert child.value == before + 3  # ...not re-counted per check
    fake.size = 5
    watch.check(where="test")
    assert child.value == before + 5  # later growth lands as the delta


def test_export_baseline_gauges_reads_committed_store():
    from tpustack.obs.metrics import Registry

    reg = Registry()
    n = perfsig.export_baseline_gauges(reg)
    committed = perfsig.load_baselines()
    assert n == len(committed) >= 5  # the tiny tier ships ≥5 scenarios
    text = reg.render()
    assert 'scenario="llm_prefix_tiny"' in text
    assert "tpustack_bench_baseline_entries" in text
    # every info series carries the ratchet sha from the baseline meta
    assert 'git_sha=""' not in text


def test_export_baseline_gauges_missing_store_is_zero(tmp_path):
    from tpustack.obs.metrics import Registry

    reg = Registry()
    assert perfsig.export_baseline_gauges(
        reg, path=str(tmp_path / "nope")) == 0


# ------------------------------------------------------- gate comparator
def _rec(sig, wallclock=None, kind="cpu"):
    return {"scenario": "s", "meta": {"device_kind": kind,
                                      "schema_version": 1},
            "signature": dict(sig), "wallclock": dict(wallclock or {})}


def test_compare_clean_within_wallclock_jitter():
    """Wall-clock jitter inside tolerance → clean (no gating rows)."""
    base = _rec({"engine.generated_tokens": 96},
                {"value": {"value": 100.0, "direction": "higher"}})
    fresh = _rec({"engine.generated_tokens": 96},
                 {"value": {"value": 88.0, "direction": "higher"}})  # -12%
    rows = perf_gate.compare(base, fresh, tolerance=0.35,
                             gate_wallclock=True)
    assert not [r for r in rows if r["gating"]
                and r["status"] in perf_gate._GATING_STATUSES]
    assert [r for r in rows if r["kind"] == "wallclock"][0]["status"] == "ok"


def test_compare_seeded_counter_regression_names_the_row():
    base = _rec({"engine.decode_weight_passes": 48,
                 "recompiles._decode_scan_paged": 1})
    fresh = _rec({"engine.decode_weight_passes": 56,
                  "recompiles._decode_scan_paged": 1})
    rows = perf_gate.compare(base, fresh, tolerance=0.35,
                             gate_wallclock=True)
    bad = [r for r in rows if r["gating"]
           and r["status"] in perf_gate._GATING_STATUSES]
    assert len(bad) == 1
    assert bad[0]["key"] == "engine.decode_weight_passes"
    assert bad[0]["baseline"] == 48 and bad[0]["fresh"] == 56


def test_compare_wallclock_direction_and_gating():
    # throughput DOWN past tolerance: regression when gating, info not
    base = _rec({}, {"tps": {"value": 100.0, "direction": "higher"},
                     "ttft": {"value": 10.0, "direction": "lower"}})
    fresh = _rec({}, {"tps": {"value": 50.0, "direction": "higher"},
                      "ttft": {"value": 4.0, "direction": "lower"}})
    rows = {r["key"]: r for r in perf_gate.compare(
        base, fresh, tolerance=0.35, gate_wallclock=True)}
    assert rows["tps"]["status"] == "regressed" and rows["tps"]["gating"]
    assert rows["ttft"]["status"] == "improved"  # lower latency never fails
    rows = {r["key"]: r for r in perf_gate.compare(
        base, fresh, tolerance=0.35, gate_wallclock=False)}
    assert rows["tps"]["status"] == "regressed_info"
    assert not rows["tps"]["gating"]
    # latency UP past tolerance regresses under "lower"
    fresh2 = _rec({}, {"tps": {"value": 99.0, "direction": "higher"},
                       "ttft": {"value": 20.0, "direction": "lower"}})
    rows = {r["key"]: r for r in perf_gate.compare(
        base, fresh2, tolerance=0.35, gate_wallclock=True)}
    assert rows["ttft"]["status"] == "regressed"


def test_update_baselines_roundtrip(tmp_path, monkeypatch):
    """--update-baselines writes a record the very next compare run reads
    back clean; a tampered fresh signature then fails naming the row."""
    canned = {"scenario": "llm_prefix_tiny",
              "meta": perfsig.artifact_meta(1.0),
              "signature": {"prefix.on.prefill_tokens_skipped": 128,
                            "recompiles._decode_scan": 1},
              "signature_stable": True,
              "wallclock": {"cache_on.ttft_p50_ms":
                            {"value": 5.0, "direction": "lower"}},
              "artifact": {}}
    calls = {"n": 0}

    def fake_run(sc, repeats, extra_env, log=print):
        calls["n"] += 1
        rec = json.loads(json.dumps(canned))
        rec["signature_stable"] = True
        if extra_env.get("BREAK"):
            rec["signature"]["prefix.on.prefill_tokens_skipped"] = 0
        return rec

    monkeypatch.setattr(perf_gate, "run_scenario", fake_run)
    args = ["--tiny", "--scenarios", "llm_prefix_tiny",
            "--baselines", str(tmp_path)]
    assert perf_gate.main(args + ["--update-baselines"]) == 0
    stored = json.load(open(tmp_path / "llm_prefix_tiny.json"))
    assert stored["signature"] == canned["signature"]
    assert check_meta(stored["meta"]) == []
    assert perf_gate.main(args) == 0  # round-trip: clean against itself
    assert perf_gate.main(args + ["--env", "BREAK=1"]) == 1
    assert calls["n"] == 3


def test_gate_scenario_crash_degrades_to_error_row(tmp_path, monkeypatch):
    """A dead scenario subprocess fails the gate but neither kills it nor
    loses the --out delta report (the CI failure artifact)."""

    def boom(sc, repeats, extra_env, log=print):
        raise RuntimeError("tool died")

    monkeypatch.setattr(perf_gate, "run_scenario", boom)
    out = tmp_path / "delta.json"
    rc = perf_gate.main(["--tiny", "--scenarios", "llm_prefix_tiny",
                         "--baselines", str(tmp_path),
                         "--out", str(out)])
    assert rc == 1
    rep = json.load(open(out))
    assert "tool died" in rep["scenarios"]["llm_prefix_tiny"]["error"]
    assert rep["failed"] is True


def test_gate_missing_baseline_fails(tmp_path, monkeypatch):
    monkeypatch.setattr(
        perf_gate, "run_scenario",
        lambda sc, repeats, extra_env, log=print: {
            "scenario": sc.name, "meta": {}, "signature": {},
            "signature_stable": True, "wallclock": {}, "artifact": {}})
    rc = perf_gate.main(["--tiny", "--scenarios", "llm_prefix_tiny",
                         "--baselines", str(tmp_path / "empty")])
    assert rc == 1


# ------------------------------------------------------ gate end-to-end
def _shell_gate(extra, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
         "--tiny", "--repeats", "1"] + extra,
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)


def test_gate_tiny_subset_clean_on_unmodified_tree():
    """The real thing, CPU-sized: two tiny scenarios against the
    committed baselines must pass clean (exact signatures, wall-clock
    informational in --tiny)."""
    proc = _shell_gate(["--scenarios",
                        "llm_continuous_tiny,llm_prefix_tiny"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_gate_tiny_injected_prefix_cache_off_fails_named():
    """Deliberately disabling the prefix cache (TPUSTACK_PREFIX_CACHE=0
    through the gate's env passthrough) must exit nonzero naming the
    regressed signature rows."""
    proc = _shell_gate(["--scenarios", "llm_prefix_tiny",
                        "--env", "TPUSTACK_PREFIX_CACHE=0"])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "prefix.on.prefill_tokens_skipped" in proc.stdout
    assert "REGRESSION" in proc.stdout


@pytest.mark.slow
def test_gate_tiny_full_clean():
    """Every committed tiny scenario (incl. the SD small path) passes
    clean on an unmodified tree."""
    proc = _shell_gate([], timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ----------------------------------------------- bench artifact schema
def test_bench_schema_keep_lists_carry_provenance():
    for keep in (LLM_EXTRA_KEEP, WAN_KEEP):
        assert "meta" in keep and "signature" in keep
    assert check_meta({"bogus": 1})  # missing keys reported
    assert check_meta("not a dict") == ["meta is not an object"]
