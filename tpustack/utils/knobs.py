"""Typed registry of every ``TPUSTACK_*``/``LLM_*`` environment knob.

The stack is configured the way the reference app is — k8s env vars — but
by PR 7 those had grown into ~40 ad-hoc ``os.environ`` reads scattered over
a dozen modules, each with its own parsing idiom, no central list of what
exists, and no doc an operator could trust.  This module is the single
source of truth:

- every knob is **declared** once (:data:`REGISTRY`): name, type, default,
  one-line doc;
- every knob is **read** through the typed accessors here
  (:func:`get_str` / :func:`get_int` / :func:`get_float` / :func:`get_bool`),
  which validate against the declaration — reading an undeclared name or
  with the wrong type raises immediately instead of silently drifting;
- the operator table in ``docs/CONFIG.md`` is **generated** from the
  registry (``python -m tools.tpulint --list-knobs``), and
  ``tools/tpulint``'s config-discipline rules (TPL401/TPL402) cross-check
  code ↔ registry ↔ docs both ways, exactly like ``lint_metrics`` does for
  the metric catalog.

Accessors take an optional ``env`` mapping (default ``os.environ``) so
components constructed with injected env dicts (``FaultInjector``,
``Tracer``, the resilience manager — a test-isolation contract) keep
working unchanged.

Parsing semantics, shared by every knob (this replaces the per-site
idioms):

- int/float: unset or blank → default; otherwise ``int()``/``float()``
  with a ``ValueError`` naming the knob on garbage;
- bool: unset or blank → default (a manifest stub with ``value: ""``
  must not silently flip a default-on feature off); ``1/true/yes/on`` →
  True; ``0/false/no/off`` → False; anything else raises (a typo'd flag
  must not silently pick a side);
- str: unset → default, no further parsing.

This module is dependency-free (stdlib only) and imported by
``tpustack.utils.logging`` — it must never import anything from tpustack.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterable, Mapping, Optional

_TRUTHY = frozenset(("1", "true", "yes", "on"))
_FALSY = frozenset(("0", "false", "no", "off"))


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    type: type  # str | int | float | bool
    default: object
    doc: str

    @property
    def type_name(self) -> str:
        return self.type.__name__

    def default_str(self) -> str:
        """Rendering used by the generated doc table (and checked against
        it by tpulint's TPL402)."""
        if self.type is str:
            return f'"{self.default}"'
        return str(self.default)


REGISTRY: Dict[str, Knob] = {}


def _declare(name: str, type_: type, default, doc: str) -> None:
    if name in REGISTRY:
        raise ValueError(f"duplicate knob declaration {name}")
    if type_ not in (str, int, float, bool):
        raise TypeError(f"{name}: unsupported knob type {type_!r}")
    if not isinstance(default, type_):
        raise TypeError(f"{name}: default {default!r} is not {type_.__name__}")
    REGISTRY[name] = Knob(name, type_, default, doc)


# --------------------------------------------------------------------- model
_declare("LLM_PRESET", str, "qwen25_7b",
         "Model preset served by llm_server (qwen25_7b | llama2_7b | "
         "llama2_70b | k_exaone_236b_ep8 | tiny | tiny_moe).")
_declare("LLM_CTX", int, 4096,
         "Context window in tokens (llama.cpp --ctx-size parity).")
_declare("LLM_QUANT", str, "",
         "Weight quantisation: 'int8' for weight-only int8 serving, "
         "empty for bf16.")
_declare("LLM_KV_QUANT", str, "",
         "KV-cache quantisation: 'int8' halves KV HBM and decode traffic, "
         "empty for the compute dtype.")
_declare("LLM_TP", int, 0,
         "Tensor-parallel ways: GSPMD-shard the model over N chips "
         "(0/1 = single chip).  The manifest's google.com/tpu request "
         "must equal the LLM_TP/dp product (lint_manifests enforces it).")
_declare("LLM_SHARD_KV", bool, True,
         "Under LLM_TP, place serving KV caches and the paged block pool "
         "head-axis-sharded over the tp mesh (per-chip KV HBM = total/tp); "
         "0 bisects back to compiler-placed caches.")
_declare("LLM_MULTIHOST_PROMPTS", str, "",
         "llm_multihost driver: path to a prompts file (one per line); "
         "empty serves a synthetic fleet.")
_declare("LLM_MULTIHOST_NEW_TOKENS", int, 128,
         "llm_multihost driver: tokens generated per prompt.")
_declare("LLM_TOKENIZER_DIR", str, "",
         "Directory holding the HF tokenizer files; empty falls back to "
         "the byte-fallback BPE baked into the repo.")
_declare("LLM_MAX_BATCH", int, 8,
         "Continuous-batching slot count (llama.cpp --parallel analog); "
         "1 disables batching (solo path).")
_declare("LLM_CHUNK", int, 32,
         "Decode tokens per fused dispatch on the solo path.")
_declare("LLM_ENGINE_CHUNK", int, 0,
         "Override for the continuous engine's chunk: the most steps a "
         "decode dispatch runs (the coarsest admission + SSE cadence; the "
         "engine runs fewer where its lanes say so); 0 = default "
         "min(LLM_CHUNK, 16).")
_declare("LLM_BATCH_WINDOW_MS", float, 0.0,
         "Legacy pre-continuous batching window; accepted, unused.")

# ----------------------------------------------------------------- KV cache
_declare("TPUSTACK_PAGED_FLASH", str, "auto",
         "Paged-flash decode attention: read KV pool blocks in place via "
         "the scalar-prefetch Pallas kernel (fused speculative verify "
         "included) instead of gathering a dense per-slot copy each "
         "chunk.  'auto' = on for real TPU backends, off on CPU/"
         "interpret and under a tp mesh; 0 bisects to the gather path "
         "(greedy outputs identical).")
_declare("TPUSTACK_KV_BLOCK", int, 0,
         "KV block size in tokens; 0 = min(64, max(8, ctx/8)) snapped to "
         "divide ctx.")
_declare("TPUSTACK_KV_POOL_BLOCKS", int, 0,
         "Allocatable pool size in blocks; 0 = LLM_MAX_BATCH x ctx / "
         "block.")
_declare("TPUSTACK_PREFIX_CACHE", bool, True,
         "Cross-request prefix KV cache (the engine's refcounted block "
         "trie; the host radix store on the LLM_MAX_BATCH=1 solo route).")
_declare("TPUSTACK_PREFIX_CACHE_MB", float, 512.0,
         "Resident host-byte cap for the solo route's host prefix cache.")
_declare("TPUSTACK_PREFIX_CACHE_CHUNK", int, 256,
         "Snap granularity in tokens for the solo route's host prefix "
         "cache.")
_declare("TPUSTACK_KV_HOST_TIER_MB", float, 0.0,
         "Host-RAM second tier for the paged prefix cache: evicted "
         "refcount-0 prefix blocks spill device->host into an LRU arena "
         "of this many megabytes instead of dying, and a warm match "
         "restores them pool-side in one dispatch (no prefill FLOPs).  "
         "0 is the bisection flag — no tier constructs, eviction and "
         "match are byte-for-byte the tier-free paths.")
_declare("TPUSTACK_KV_HOST_TIER_CROSSOVER", bool, True,
         "Restore-vs-recompute crossover guard for the host KV tier: "
         "when on (default), a warm host-tier match only restores if the "
         "measured per-block copy cost undercuts the measured per-block "
         "prefill cost (otherwise recompute wins and the chain is left "
         "resident).  0 restores unconditionally — for tiny/CPU shapes "
         "where both EMAs are dispatch noise (CI smokes, bench tiny "
         "presets); HBM-scale deployments keep the guard.")
_declare("TPUSTACK_PREFILL_CHUNK_TOKENS", int, 0,
         "Chunked prefill for the paged continuous engine: a prompt "
         "whose uncached remainder exceeds this many tokens prefills in "
         "block-aligned chunks of (at most) this size, parking between "
         "chunks so decode waves of other slots interleave — long "
         "prompts stop monopolising the device.  Admission still "
         "charges the full block footprint up front.  0 disables "
         "(bisection: admission is byte-for-byte the monolithic "
         "prefill).")

# -------------------------------------------------------------- speculative
_declare("TPUSTACK_SPEC_TOKENS", int, 4,
         "Draft tokens per speculative verify step on the continuous "
         "engine; 0 disables (bisection: the wave loop is byte-for-byte "
         "the spec-free engine).")
_declare("TPUSTACK_SPEC_NGRAM", int, 3,
         "Max n-gram length for the prompt-lookup drafter.")
_declare("TPUSTACK_SPEC_DRAFT", str, "",
         "Draft-model preset (tiny | llama2_7b | qwen25_7b); empty keeps "
         "the n-gram prompt-lookup drafter.")
_declare("TPUSTACK_SPEC_DRAFT_DIR", str, "",
         "Safetensors dir for the draft model; empty = random weights "
         "(rehearsal-grade).")

# --------------------------------------------------------------- resilience
_declare("TPUSTACK_DRAIN_TIMEOUT_S", float, 30.0,
         "Max seconds to wait for in-flight work after SIGTERM before "
         "exiting.")
_declare("TPUSTACK_DRAIN_LINGER_S", float, 0.0,
         "Accept-and-poll servers: keep the read surface alive this long "
         "after the last prompt publishes so pollers can fetch results.")
_declare("TPUSTACK_REQUEST_TIMEOUT_S", float, 600.0,
         "Default per-request deadline in seconds (0 disables; request "
         "body timeout_s overrides).")
_declare("TPUSTACK_MAX_QUEUE_DEPTH", int, 64,
         "Waiting-work cap before shedding with 429 + Retry-After "
         "(0 disables).")
_declare("TPUSTACK_WATCHDOG_S", float, 0.0,
         "No-progress seconds before liveness flips 503 (0 disables; set "
         "above the worst cold-compile dispatch).")

# ------------------------------------------------------------------- router
_declare("TPUSTACK_ROUTER_BACKENDS", str, "",
         "Replica set for the L7 router: comma list of base URLs "
         "(http://host:port), @/path/to/file (one URL per line, "
         "hot-reloaded on mtime change), or dns://host:port (A records "
         "re-resolved each health tick).  Empty is the bisection flag — "
         "no router constructs.")
_declare("TPUSTACK_ROUTER_HEALTH_INTERVAL_S", float, 2.0,
         "Seconds between active /readyz polls of every backend (also "
         "the file/DNS re-resolution cadence).")
_declare("TPUSTACK_ROUTER_EJECT_AFTER", int, 3,
         "Consecutive passive failures (connect error / timeout / 5xx) "
         "before a backend is ejected from the healthy set (circuit "
         "opens).")
_declare("TPUSTACK_ROUTER_HALF_OPEN_S", float, 5.0,
         "Seconds an ejected backend stays open before a half-open "
         "/readyz probe may re-admit it.")
_declare("TPUSTACK_ROUTER_RETRY_BUDGET", int, 2,
         "Max failover attempts per request beyond the first try "
         "(connect errors and spillable sheds only; quota sheds never "
         "spill).")
_declare("TPUSTACK_ROUTER_RETRY_JITTER_S", float, 0.05,
         "Upper bound of the uniform jitter slept before each failover "
         "attempt (decorrelates retry stampedes after an ejection).")
_declare("TPUSTACK_ROUTER_AFFINITY_CHUNK", int, 256,
         "Prompt-prefix alignment in characters for the rendezvous "
         "affinity key — mirror of the replicas' prefix-cache chunking "
         "so one replica keeps a given prefix hot.")
_declare("TPUSTACK_ROUTER_AFFINITY_KEYS", int, 4096,
         "LRU capacity of the router's affinity table (prefix-key -> "
         "last backend), used only for hit/cold-move accounting.")
_declare("TPUSTACK_ROUTER_UPSTREAM_TIMEOUT_S", float, 600.0,
         "Total per-attempt upstream timeout in seconds (covers connect "
         "+ full response; streaming responses are exempt after the "
         "first byte).")

# -------------------------------------------------------------- autoscaler
_declare("TPUSTACK_ADMIN_TOKEN", str, "",
         "Shared secret for the authenticated admin surface (POST "
         "/admin/drain).  Empty disables the surface entirely — every "
         "request 403s, so an unconfigured fleet exposes nothing.")
_declare("TPUSTACK_AUTOSCALER_ROUTER_URL", str, "",
         "Base URL of the L7 router the autoscaler scrapes for fleet "
         "state (/debug/router).  Empty is the bisection flag — no "
         "autoscaler constructs.")
_declare("TPUSTACK_AUTOSCALER_MIN", int, 1,
         "Replica floor.  Never below 1: scale-to-zero would empty the "
         "healthy set and turn the next request into a cold-boot timeout.")
_declare("TPUSTACK_AUTOSCALER_MAX", int, 4,
         "Replica ceiling (chips are finite; the policy clamps here "
         "before the executor ever sees the desire).")
_declare("TPUSTACK_AUTOSCALER_TARGET_LOAD", float, 3.0,
         "Target work units (in-flight + queued requests) per replica — "
         "the set-point of the utilization controller.")
_declare("TPUSTACK_AUTOSCALER_HYSTERESIS", float, 0.25,
         "Dead-band half-width as a fraction of the target: scale up "
         "above target*(1+h), down only below (n-1)*target*(1-h).")
_declare("TPUSTACK_AUTOSCALER_INTERVAL_S", float, 2.0,
         "Seconds between control-loop ticks (scrape -> decide -> "
         "execute).")
_declare("TPUSTACK_AUTOSCALER_UP_COOLDOWN_S", float, 5.0,
         "Minimum seconds between consecutive scale-UP events (fast: a "
         "surge should add capacity within seconds).")
_declare("TPUSTACK_AUTOSCALER_DOWN_COOLDOWN_S", float, 60.0,
         "Minimum seconds after ANY scale event before a scale-DOWN "
         "(slow: giving back a warm KV cache must never be hasty).")
_declare("TPUSTACK_AUTOSCALER_DOWN_STABLE_TICKS", int, 3,
         "Consecutive below-band ticks required before a scale-down "
         "fires (flap suppression on top of the cooldowns).")
_declare("TPUSTACK_AUTOSCALER_KV_FREE_MIN", float, 0.05,
         "KV-pool free-block ratio under which the fleet is memory-"
         "pressured and a scale-up fires regardless of request load.")
_declare("TPUSTACK_AUTOSCALER_DRAIN_TIMEOUT_S", float, 120.0,
         "Scale-down choreography: max seconds to wait for a drained "
         "victim's in-flight work before terminating it anyway.")
_declare("TPUSTACK_AUTOSCALER_REGISTRY_FILE", str, "",
         "Local executor: path of the router's @file registry the "
         "executor rewrites (selects LocalSubprocessExecutor when set).")
_declare("TPUSTACK_AUTOSCALER_SPAWN_CMD", str, "",
         "Local executor: replica spawn command template; '{port}' is "
         "substituted (shlex-split).")
_declare("TPUSTACK_AUTOSCALER_K8S_DEPLOYMENT", str, "",
         "Kubernetes executor: Deployment name whose scale subresource "
         "is patched (selects KubernetesExecutor when set).")
_declare("TPUSTACK_AUTOSCALER_K8S_NAMESPACE", str, "llm",
         "Kubernetes executor: namespace of the managed Deployment (the "
         "RBAC Role grants deployments/scale patch here only).")

# -------------------------------------------------------------- watchtower
_declare("TPUSTACK_WATCHTOWER_ROUTER_URL", str, "",
         "Base URL of the L7 router the watchtower discovers the fleet "
         "from (/debug/router) and stitches traces through.  Empty is "
         "the bisection flag — no watchtower constructs.")
_declare("TPUSTACK_WATCHTOWER_AUTOSCALER_URL", str, "",
         "Base URL of the autoscaler's debug surface; when set, its "
         "decisions (unhealthy_floor holds) join the incident evidence "
         "and can trigger bundles.  Empty skips the autoscaler scrape.")
_declare("TPUSTACK_WATCHTOWER_INTERVAL_S", float, 5.0,
         "Seconds between watchtower ticks (scrape fleet -> evaluate "
         "burn rates -> capture incident bundles).")
_declare("TPUSTACK_WATCHTOWER_INCIDENT_DIR", str, "",
         "Directory of the bounded on-disk incident-bundle ring.  Empty "
         "keeps bundles in memory only (still served on "
         "/debug/incidents, lost with the process).")
_declare("TPUSTACK_WATCHTOWER_INCIDENT_KEEP", int, 16,
         "Ring bound: newest bundles kept in memory and on disk; older "
         "incident-*.json artifacts are pruned on every capture.")
_declare("TPUSTACK_WATCHTOWER_INCIDENT_COOLDOWN_S", float, 60.0,
         "Minimum seconds between incident captures — one fleet event "
         "(an ejection storm, a flapping breaker) yields one bundle, "
         "not one per tick.")
_declare("TPUSTACK_WATCHTOWER_TRACES_PER_BUNDLE", int, 5,
         "How many slowest/errored stitched traces a bundle snapshots "
         "(K in the incident-forensics runbook).")
_declare("TPUSTACK_WATCHTOWER_WINDOW_SCALE", float, 1.0,
         "Multiplier on the canonical burn-rate alert windows "
         "(5m/1h fast page, 30m/6h slow ticket).  1.0 in production; "
         "tests and chaos drills shrink it so alerts resolve within a "
         "drill.")

# ------------------------------------------------------------ fault injection
_declare("TPUSTACK_FAULT_SLOW_PREFILL_S", float, 0.0,
         "Sleep injected before every device dispatch (deterministic "
         "fault).")
_declare("TPUSTACK_FAULT_DEVICE_ERROR_NTH", int, 0,
         "The Nth dispatch raises a one-shot transient device error.")
_declare("TPUSTACK_FAULT_HANG_NTH", int, 0,
         "The Nth dispatch hangs for TPUSTACK_FAULT_HANG_S.")
_declare("TPUSTACK_FAULT_HANG_S", float, 3600.0,
         "Hang duration for the injected dispatch hang.")
_declare("TPUSTACK_FAULT_SIGTERM_AFTER", int, 0,
         "Begin drain after the Nth completed wave (mid-request SIGTERM).")
_declare("TPUSTACK_FAULT_TRAIN_KILL_STEP", int, 0,
         "Training chaos: real SIGTERM to the trainer at this exact step "
         "boundary (0 disables).")
_declare("TPUSTACK_FAULT_TRAIN_CORRUPT_CKPT", int, 0,
         "Training chaos: corrupt the checkpoint written at this step "
         "(restore must quarantine + fall back).")

# ------------------------------------------------------------ observability
_declare("TPUSTACK_LOG_FORMAT", str, "text",
         "Log line format: 'text' (kubectl-logs friendly) or 'json' "
         "(one object per line).")
_declare("TPUSTACK_LOG_LEVEL", str, "INFO",
         "Root log level for the tpustack logger tree.")
_declare("TPUSTACK_METRICS_PORT", int, 0,
         "Stdlib /metrics sidecar port for batch/train jobs (0 disables).")
_declare("TPUSTACK_TRACE_BUFFER", int, 128,
         "Recent-traces ring buffer size in the in-process trace store.")
_declare("TPUSTACK_TRACE_SLOW_S", float, 5.0,
         "Traces at or above this duration are always kept (survive the "
         "ring buffer's churn).")
_declare("TPUSTACK_FLIGHT_RECORDS", int, 4096,
         "Flight-recorder ring capacity: per-dispatch engine records "
         "retained for /debug/flight and post-mortem dumps (an LLM decode "
         "dispatch is 1 to LLM_ENGINE_CHUNK steps: up to a hundred records "
         "a second).")
_declare("TPUSTACK_FLIGHT_DUMP_DIR", str, "/tmp/tpustack-flight",
         "Directory for flight-recorder JSON dumps (watchdog fire, SIGTERM "
         "drain, fatal engine error, sanitizer violation); empty disables "
         "dumping.")
_declare("TPUSTACK_FLIGHT_WINDOW_S", float, 60.0,
         "Aggregation window for the live roofline/occupancy gauges "
         "computed from the flight recorder at scrape time.")
_declare("TPUSTACK_PROFILE_DIR", str, "/tmp/tpustack-profile",
         "Base directory for on-demand POST /profile xplane captures "
         "(the SD server's legacy SD15_TRACE_DIR overrides it there).")
_declare("TPUSTACK_TENANT_CARDINALITY", int, 32,
         "Max distinct tenant label values on tenant-labelled metrics; "
         "tenants beyond the first N collapse into the 'other' overflow "
         "bucket (bounds scrape cardinality under hostile tenant ids).")
_declare("TPUSTACK_TENANT_DEFAULT", str, "anonymous",
         "Tenant charged for requests that carry no X-Tenant-Id header "
         "and no body 'tenant' field.")
_declare("TPUSTACK_REPLAY_URL", str, "",
         "Default target URL for tools/replay.py (the in-cluster replay "
         "Job sets it); empty = the tool's --url default.")
_declare("TPUSTACK_KVPROF_RATE", float, 0.1,
         "Spatial sampling rate for the KV working-set profiler "
         "(tpustack.obs.kvprof): fraction of the token-chunk key space "
         "whose reuse distances feed the online miss-ratio curve; 0 is "
         "the bisection flag — no profiler constructs, no hooks attach, "
         "the serving path is byte-identical.")
_declare("TPUSTACK_KVPROF_WARM_S", float, 30.0,
         "Warm-eviction window: a prefix-cache entry evicted within this "
         "many seconds of its last hit counts as evicted-warm (an "
         "avoidable eviction) rather than evicted-cold.")

# --------------------------------------------------------------------- QoS
_declare("TPUSTACK_QOS", bool, True,
         "Multi-tenant QoS layer (tpustack.serving.qos): priority classes "
         "at admission/scheduling, per-tenant token-bucket quotas, and "
         "SLO-aware shedding; 0 is the bisection flag — the admission "
         "path and engine outputs are byte-for-byte the QoS-free stack.")
_declare("TPUSTACK_QOS_POLICY", str, "",
         "QoS policy: inline JSON (starts with '{') or a path to a JSON "
         "file — per-tenant priority defaults and token-bucket quotas "
         "(docs/QOS.md documents the schema); empty = priorities only, "
         "no quotas.")
_declare("TPUSTACK_BENCH_BASELINES", str, "",
         "Committed perf-baseline store read by tools/perf_gate.py and "
         "exported as tpustack_bench_baseline_* gauges at server start; "
         "empty = <repo>/bench/baselines.")

# ---------------------------------------------------------------- sanitizers
_declare("TPUSTACK_SANITIZE", bool, False,
         "Runtime sanitizer suite (tpustack.sanitize): guarded-by "
         "enforcement, lock-order detection, recompile budgets, KV/span/"
         "thread leak checks.  The tier-1 pytest plugin turns it on for "
         "the whole suite; production keeps it off (zero overhead).")
_declare("TPUSTACK_SANITIZE_MODE", str, "report",
         "What a sanitizer violation does: 'raise' (tests — fail at the "
         "faulting line) or 'report' (production — count "
         "tpustack_sanitizer_violations_total and log, never crash).")

# ------------------------------------------------------------------ runtime
_declare("TPUSTACK_NO_NATIVE", bool, False,
         "Skip building/loading the native (C) helpers; pure-python "
         "fallbacks serve instead.")


# ------------------------------------------------------------------ readers
def _knob(name: str, expect: type) -> Knob:
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(
            f"unknown knob {name!r}: declare it in tpustack/utils/knobs.py "
            "(tpulint TPL402 enforces registry <-> code <-> docs agreement)")
    if knob.type is not expect:
        raise TypeError(f"knob {name} is declared {knob.type_name}, "
                        f"read as {expect.__name__}")
    return knob


def get_str(name: str, env: Optional[Mapping[str, str]] = None) -> str:
    knob = _knob(name, str)
    val = (os.environ if env is None else env).get(name)
    return knob.default if val is None else val


def get_int(name: str, env: Optional[Mapping[str, str]] = None) -> int:
    knob = _knob(name, int)
    val = (os.environ if env is None else env).get(name)
    if val is None or not val.strip():
        return knob.default
    try:
        return int(val)
    except ValueError:
        raise ValueError(f"{name}={val!r} is not an integer")


def get_float(name: str, env: Optional[Mapping[str, str]] = None) -> float:
    knob = _knob(name, float)
    val = (os.environ if env is None else env).get(name)
    if val is None or not val.strip():
        return knob.default
    try:
        return float(val)
    except ValueError:
        raise ValueError(f"{name}={val!r} is not a number")


def get_bool(name: str, env: Optional[Mapping[str, str]] = None) -> bool:
    knob = _knob(name, bool)
    val = (os.environ if env is None else env).get(name)
    if val is None or not val.strip():
        return knob.default
    low = val.strip().lower()
    if low in _TRUTHY:
        return True
    if low in _FALSY:
        return False
    raise ValueError(f"{name}={val!r} is not a boolean "
                     "(want 1/true/yes/on or 0/false/no/off)")


# ---------------------------------------------------------------- rendering
def knobs(prefix: str = "") -> Iterable[Knob]:
    """Declared knobs, sorted by name, optionally prefix-filtered."""
    return [REGISTRY[n] for n in sorted(REGISTRY) if n.startswith(prefix)]


def markdown_table() -> str:
    """The operator table docs/CONFIG.md embeds — regenerate it with
    ``python -m tools.tpulint --list-knobs`` whenever the registry changes
    (tpulint TPL402 fails when the two drift)."""
    lines = ["| Knob | Type | Default | Description |",
             "|------|------|---------|-------------|"]
    for k in knobs():
        # GFM splits cells on raw '|' even inside code spans — escape the
        # free-text column so docs like "(a | b | c)" stay one cell
        doc = k.doc.replace("|", "\\|")
        lines.append(f"| `{k.name}` | {k.type_name} | `{k.default_str()}` "
                     f"| {doc} |")
    return "\n".join(lines)
