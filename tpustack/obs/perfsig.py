"""Deterministic perf signatures: the machine-exact half of the perf gate.

Five bench rounds of wins (56% SD MFU, 625 tok/s/chip continuous batch-8,
paged KV, speculation, tp=8) are wall-clock numbers — and wall clocks need
the right hardware, warm caches, and a quiet machine to reproduce.  But
*how* those numbers were achieved is counted, not timed, by
instrumentation the stack already carries:

- decode **weight passes** and tokens-per-weight-pass (the bandwidth-
  amortisation figure) from the continuous engine / flight recorder;
- **recompile counts** per jitted entry point from
  :class:`tpustack.sanitize.CompileWatch` (a serving path that silently
  retraces is a multi-second stall per occurrence);
- paged-KV **block alloc/free totals** from :class:`KVBlockPool`;
- prefix-cache **computed-vs-skipped prompt tokens** (the prefill FLOPs
  the radix cache removes);
- speculative **drafted/accepted totals** (the verify win).

Those counters are bit-reproducible on CPU for the tiny bench shapes —
a regression in any of them (one more dispatch per wave, a retrace per
request, a cache that stopped hitting) is caught EXACTLY by CI with no
timers involved.  This module assembles them into a flat ``signature``
dict (dotted keys, integer values) embedded in every bench artifact, and
provides the shared ``meta`` provenance block (git sha, device kind,
knob-registry snapshot, schema version) every artifact is stamped with.

``tools/bench_llm.py`` builds signatures from its live runs,
``tools/perf_gate.py`` compares them against the committed baselines
under ``bench/baselines/`` — both import THIS module, so the arithmetic
cannot drift between the producer and the judge (the
``llm_wave_arith``/roofline discipline applied to counters).

:func:`export_baseline_gauges` closes the loop at serving time: the
committed baseline set is exported as ``tpustack_bench_baseline_*`` info
gauges, so a scrape shows which baseline a live server is being held to.
"""

from __future__ import annotations

import json
import os
import subprocess
from typing import Dict, List, Mapping, Optional

from tpustack.utils import knobs

__all__ = [
    "SCHEMA_VERSION", "ENTRY_POINTS", "ENGINE_COUNTERS", "git_sha",
    "knob_snapshot", "artifact_meta", "compile_watch", "engine_signature",
    "sum_engine_stats", "prefix_cache_signature", "recompile_signature",
    "flight_signature", "signature", "diff_signatures", "baseline_dir",
    "load_baselines", "export_baseline_gauges",
]

#: bump when the meta/signature layout changes shape (the gate refuses to
#: compare artifacts across schema versions instead of misreading them)
SCHEMA_VERSION = 1

#: the jitted entry points whose trace caches must stop growing in steady
#: state: the engine set the sanitizer CompileWatch budgets
#: (llm_continuous.ContinuousEngine.__init__) plus the solo/static-batch
#: decode programs the bench's non-engine paths run.  A forced watch on an
#: entry a scenario never compiles reports 0 — and a committed 0 is
#: signature too (that path STARTING to compile is the regression)
ENTRY_POINTS = ("_decode_scan_paged", "_spec_verify_paged",
                "_decode_scan", "_decode_scan_batch")


# ------------------------------------------------------------- provenance
def git_sha(root: Optional[str] = None) -> Optional[str]:
    """HEAD sha of the repo containing this file (or ``root``); None when
    git is unavailable — provenance is best-effort, never a crash."""
    if root is None:
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def knob_snapshot(env: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """The knob-registry slice of the environment: every DECLARED knob
    that is explicitly set, name → raw value.  Defaults are omitted (they
    are code, versioned by the git sha) — what matters for reproducing a
    measurement is what the caller overrode."""
    src = os.environ if env is None else env
    return {name: src[name] for name in sorted(knobs.REGISTRY)
            if name in src}


def artifact_meta(ts: float, env: Optional[Mapping[str, str]] = None,
                  extra: Optional[Dict] = None) -> Dict:
    """The shared provenance block every bench artifact carries
    (``bench.py``, ``bench_llm``, ``bench_wan`` — one helper, one shape).
    ``ts`` is passed by the caller (the measurement's own wall clock);
    device kind/backend degrade to "" off-device rather than failing a
    CPU run."""
    kind, backend = "", ""
    try:
        import jax

        backend = jax.default_backend()
        kind = getattr(jax.devices()[0], "device_kind", "")
    except Exception:
        pass
    meta = {
        "schema_version": SCHEMA_VERSION,
        "git_sha": git_sha(),
        "device_kind": kind,
        "backend": backend,
        "ts": round(float(ts), 3),
        "knobs": knob_snapshot(env),
    }
    if extra:
        meta.update(extra)
    return meta


# --------------------------------------------------------- counter sources
def compile_watch(gen):
    """A :class:`tpustack.sanitize.CompileWatch` force-baselined on the
    serving entry points of ``gen``'s class — active regardless of
    ``TPUSTACK_SANITIZE`` (the bench measures recompiles as data, not as
    violations).  Create it BEFORE the first dispatch so the cold
    compiles are counted too: a deterministic workload compiles a
    deterministic number of traces, and one extra is exactly the
    mid-traffic retrace the signature exists to catch."""
    from tpustack.sanitize import CompileWatch

    watch = CompileWatch()
    cls = type(gen)
    for name in ENTRY_POINTS:
        watch.watch(name, cls.__dict__.get(name), budget=0, force=True)
    return watch


def _ints(prefix: str, src: Mapping, keys) -> Dict[str, int]:
    return {f"{prefix}.{k}": int(src[k]) for k in keys
            if src.get(k) is not None}


#: the exact counters taken from a :meth:`ContinuousEngine.run` stats
#: dict — ONE tuple shared by :func:`engine_signature` and
#: :func:`sum_engine_stats`, so a counter added here gates everywhere
ENGINE_COUNTERS = ("requests", "generated_tokens", "decode_weight_passes",
                   "spec_drafted_tokens", "spec_accepted_tokens",
                   "spec_dispatches")


def engine_signature(stats: Mapping) -> Dict[str, int]:
    """Exact counters from a :meth:`ContinuousEngine.run` stats dict."""
    return _ints("engine", stats, ENGINE_COUNTERS)


def sum_engine_stats(runs) -> Dict[str, int]:
    """:data:`ENGINE_COUNTERS` summed over several ``run()`` stats dicts
    (a bench repeating a deterministic fleet keeps ONE signature for the
    whole measurement)."""
    out: Dict[str, int] = {}
    for st in runs:
        for k in ENGINE_COUNTERS:
            if st.get(k) is not None:
                out[k] = out.get(k, 0) + int(st[k])
    return out


def prefix_cache_signature(stats: Mapping,
                           prefix: str = "prefix_cache") -> Dict[str, int]:
    """Exact counters from a :class:`PrefixCache`/:class:`PagedPrefixCache`
    stats dict — hits/misses/served tokens are the cache-effectiveness
    signature (``cached_tokens_served`` falling is prefill FLOPs coming
    back)."""
    return _ints(prefix, stats,
                 ("hits", "misses", "evictions", "cached_tokens_served",
                  "inserted_tokens", "entries"))


def recompile_signature(watch) -> Dict[str, int]:
    """Traces compiled per watched entry point since the watch baseline
    (:func:`compile_watch`).  Includes zeros: "this path compiled nothing"
    is signature too — a baseline row of 0 turning 1 names the entry
    point that started retracing."""
    return {f"recompiles.{name}": int(s["compiles"])
            for name, s in sorted(watch.stats().items())}


def flight_signature(agg: Mapping) -> Dict[str, int]:
    """Exact counters from a :class:`FlightRecorder` aggregates dict:
    wave/dispatch structure (how the tokens were delivered, not how fast)."""
    return _ints("flight", agg,
                 ("waves", "tokens", "spec_drafted", "spec_accepted"))


def signature(*, engine: Optional[Mapping] = None,
              prefix_cache: Optional[Mapping] = None, watch=None,
              flight: Optional[Mapping] = None,
              extra: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Assemble one flat signature dict from whichever sources the bench
    scenario has.  Keys are dotted (``engine.generated_tokens``,
    ``recompiles._decode_scan_paged``), values are plain ints — the gate
    compares with ``==`` and nothing else.  Pool/allocator counters go
    through ``extra`` (the paged bench keys them per footprint)."""
    sig: Dict[str, int] = {}
    if engine is not None:
        sig.update(engine_signature(engine))
    if prefix_cache is not None:
        sig.update(prefix_cache_signature(prefix_cache))
    if watch is not None:
        sig.update(recompile_signature(watch))
    if flight is not None:
        sig.update(flight_signature(flight))
    if extra:
        sig.update({k: int(v) for k, v in extra.items()})
    return dict(sorted(sig.items()))


# --------------------------------------------------------------- comparing
def diff_signatures(baseline: Mapping[str, int],
                    fresh: Mapping[str, int]) -> List[Dict]:
    """Every way two signatures disagree, as rows the gate prints:
    ``mismatch`` (both have the key, values differ — the exact-perf
    regression), ``missing`` (baseline counter the fresh run no longer
    produces) and ``new`` (fresh counter with no committed expectation).
    All three are gate failures — missing/new mean the signature schema
    drifted, and the sanctioned answer is ``--update-baselines``, not a
    silent pass."""
    rows: List[Dict] = []
    for key in sorted(set(baseline) | set(fresh)):
        if key not in fresh:
            rows.append({"key": key, "baseline": baseline[key],
                         "fresh": None, "status": "missing"})
        elif key not in baseline:
            rows.append({"key": key, "baseline": None,
                         "fresh": fresh[key], "status": "new"})
        elif int(baseline[key]) != int(fresh[key]):
            rows.append({"key": key, "baseline": int(baseline[key]),
                         "fresh": int(fresh[key]), "status": "mismatch"})
    return rows


# --------------------------------------------------------- baseline export
def baseline_dir(root: Optional[str] = None) -> str:
    """The committed baseline store: ``TPUSTACK_BENCH_BASELINES`` when
    set, else ``<repo>/bench/baselines``."""
    configured = knobs.get_str("TPUSTACK_BENCH_BASELINES")
    if configured:
        return configured
    if root is None:
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "bench", "baselines")


def load_baselines(path: Optional[str] = None) -> Dict[str, Dict]:
    """Every committed baseline, scenario name → record (recursive over
    the tier subdirs: ``tiny/`` for the CPU CI set, hardware tiers
    beside it).  Unreadable files are skipped — one corrupt baseline
    must not hide the rest."""
    path = path or baseline_dir()
    out: Dict[str, Dict] = {}
    if not os.path.isdir(path):
        return out
    for dirpath, _, names in sorted(os.walk(path)):
        for name in sorted(names):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(dirpath, name)) as f:
                    rec = json.load(f)
                out[rec.get("scenario", name[:-5])] = rec
            except Exception:
                continue
    return out


def export_baseline_gauges(registry=None, path: Optional[str] = None) -> int:
    """Export the committed baseline set as scrape-visible info gauges:
    ``tpustack_bench_baseline_info{scenario, git_sha}`` = 1 per baseline
    and ``tpustack_bench_baseline_entries`` = how many are loaded — so
    "which perf bar is this live server held to" reads off ``/metrics``
    instead of off a checkout.  Best-effort: a server must boot with no
    baseline dir (returns 0)."""
    from tpustack.obs import catalog as obs_catalog

    metrics = obs_catalog.build(registry)
    try:
        baselines = load_baselines(path)
    except Exception:
        baselines = {}
    for scenario, rec in sorted(baselines.items()):
        sha = (rec.get("meta") or {}).get("git_sha") or ""
        metrics["tpustack_bench_baseline_info"].labels(
            scenario=scenario, git_sha=sha).set(1)
    metrics["tpustack_bench_baseline_entries"].set(len(baselines))
    return len(baselines)
