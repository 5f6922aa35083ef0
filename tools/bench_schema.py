"""The bench-artifact schema, declared once.

``bench.py`` folds each tool's one-line JSON artifact into the driver
artifact through a keep-list (the tools print rich records; the driver
keeps the cells the trajectory/gate layers read).  Before this module the
keep-list lived in ``bench.py`` and its expectations lived separately in
``tests/test_bench_extras.py`` — two copies that could drift.  Both now
import THIS module; a key added here is kept by the driver AND required
by the schema test in the same edit.

Also the home of the shared ``meta`` contract: every bench artifact
(``bench.py``, ``tools/bench_llm.py``, ``tools/bench_wan.py``) carries a
``meta`` block built by :func:`tpustack.obs.perfsig.artifact_meta` —
:data:`META_KEYS` is what a valid block must contain, and
:func:`check_meta` is the one validator the tests and the gate share.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

#: every key a bench-artifact ``meta`` block must carry
#: (tpustack.obs.perfsig.artifact_meta is the only sanctioned producer)
META_KEYS = ("schema_version", "git_sha", "device_kind", "backend", "ts",
             "knobs")

#: keys bench.py keeps from each LLM-extra tool artifact (one list for
#: every cell: continuous_e2e / prefill_8k / shared_prefix / paged /
#: speculative / tp / replay — a tool key absent from a given mode is
#: simply not kept for that cell)
LLM_EXTRA_KEEP = (
    "metric", "value", "unit", "steady_decode_tokens_per_sec",
    "prefill_tokens_per_sec", "roofline_pct", "prefill_roofline_pct",
    "cache_on", "cache_off", "ttft_p50_speedup", "outputs_identical",
    "dense_slot_cap", "sweep", "leak_check_ok",
    # paged mode: which decode-attention body served the sweep (gather vs
    # the in-place paged-flash kernel) + the per-step KV bytes both ways
    "kernel", "roofline",
    # host-tier mode: the off/on comparison tables, the tier's spill/
    # restore/expire ledger, and the p99 speedup the tier bought; chunked-
    # prefill mode reuses outputs_identical/leak_check_ok plus its own
    # off/on tables
    "tier_off", "tier_on", "host_tier", "ttft_p99_speedup",
    "chunk_off", "chunk_on", "prefill_chunk_tokens",
    "acceptance_rate", "tokens_per_weight_pass_on",
    "tokens_per_weight_pass_off", "speedup_batch1",
    "tp_ways", "weights_per_chip_bytes", "kv_per_chip_bytes",
    "flight", "error", "skipped",
    # replay artifact keys: offered vs achieved goodput + the per-tenant
    # AND per-priority-class percentile/outcome tables + the schedule
    # digest (same seed = same offered load across driver rounds) + the
    # self-hosted server's qos counter view (shed/preempt/quota_throttle
    # by priority — the "shed lands on batch first" evidence)
    "seed", "schedule_sha", "offered_rps", "goodput_rps",
    "goodput_ratio", "shed", "deadline", "errors", "tenants",
    "priorities", "server_qos",
    # KV working-set observatory (tpustack.obs.kvprof): the paged bench's
    # per-pool snapshot and the replay's server-side /debug/kvcache view
    # (miss-ratio curve, working set, block lifetimes, Retry-After
    # calibration) — the sizing evidence ROADMAP item 4 reads
    "kvprof", "server_kvcache",
    # L7 router view when --url pointed at tpustack.serving.router:
    # backend health/circuit states, failover + affinity counters — the
    # scale-out evidence chaos_serving's goodput bar is judged with
    "server_router",
    # elastic capacity controller view when --autoscaler-url was given:
    # desired/actual, policy decisions and scale events recorded while
    # the replay's load was offered
    "server_autoscaler",
    # provenance + the machine-exact perf signature (tpustack.obs.perfsig)
    # ride each cell into the driver artifact: BENCH_r*.json rounds carry
    # the exact counters the perf gate ratchets on, per measurement
    "meta", "signature",
)

#: keys bench.py keeps from the Wan tool artifact
WAN_KEEP = ("metric", "value", "unit", "seconds_per_video", "mfu", "error",
            "meta", "signature")


def prune(record: Mapping, keep: Sequence[str]) -> Dict:
    """The driver's keep-list filter: the kept subset, order of ``keep``."""
    return {k: record[k] for k in keep if k in record}


def get_path(record, path):
    """Walk a nested artifact by dotted string (``"cache_on.ttft_p50_ms"``)
    or key sequence; None when any hop is absent/non-dict (the perf gate's
    wall-clock paths)."""
    if isinstance(path, str):
        path = path.split(".")
    cur = record
    for part in path:
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check_meta(meta) -> List[str]:
    """Problems with an artifact ``meta`` block (empty list = valid)."""
    if not isinstance(meta, dict):
        return ["meta is not an object"]
    problems = [f"meta missing key {k!r}" for k in META_KEYS if k not in meta]
    if not isinstance(meta.get("knobs", {}), dict):
        problems.append("meta.knobs is not an object")
    return problems
