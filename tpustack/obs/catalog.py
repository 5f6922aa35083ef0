"""The metric catalog: every metric the stack exports, declared in one place.

Central declaration buys three things: the three servers share families
(same name → same family object in the default registry) instead of
drifting; ``tools/lint_metrics.py`` can enforce the naming contract
(``tpustack_*``, snake_case, unit-suffixed, counters ``_total``) on the
catalog instead of grepping call sites; and ``docs/OBSERVABILITY.md``'s
table has a source of truth.

Add new metrics HERE, then take them from the dict ``build()`` returns —
ad-hoc ``registry.counter(...)`` calls in serving code will work (the
registry is get-or-create) but escape the lint, so don't.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

from tpustack.obs.metrics import REGISTRY, Registry

#: batch-size style buckets: micro-batchers cap out at small powers of two
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
#: token-count buckets for prompt/generation length histograms
TOKEN_BUCKETS = (1, 8, 32, 128, 512, 2048, 8192, 32768)
#: checkpoint-commit buckets: tiny CI saves are ms, a sharded 7B on a PVC
#: can take minutes
SAVE_BUCKETS = (0.1, 0.5, 2.0, 10.0, 30.0, 120.0, 600.0)


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    type: str  # counter | gauge | histogram
    help: str
    labels: Tuple[str, ...] = ()
    unit: str = ""  # trailing unit token, checked by tools/lint_metrics.py
    buckets: Optional[Tuple[float, ...]] = None  # histograms only


CATALOG: Tuple[MetricSpec, ...] = (
    # ---- HTTP surface (all three servers; server ∈ llm|sd|graph) ----
    MetricSpec("tpustack_http_requests_total", "counter",
               "HTTP requests served, by endpoint and status code.",
               ("server", "endpoint", "status"), unit="total"),
    MetricSpec("tpustack_http_request_latency_seconds", "histogram",
               "End-to-end HTTP request latency (ingress to last byte).",
               ("server", "endpoint"), unit="seconds"),
    MetricSpec("tpustack_http_in_flight_requests", "gauge",
               "Requests currently being handled.",
               ("server",), unit="requests"),
    MetricSpec("tpustack_request_phase_latency_seconds", "histogram",
               "Per-phase request latency: llm queue_wait/prefill/decode/"
               "detokenize; sd queue_wait/batch_build/denoise_vae/"
               "png_encode (denoise+VAE are ONE fused XLA program, not "
               "separable); graph node_<Class> execute spans.",
               ("server", "phase"), unit="seconds"),

    # ---- LLM server (continuous batching engine) ----
    MetricSpec("tpustack_llm_queue_depth", "gauge",
               "Completions parked in the admission queue (not yet in a "
               "slot).", unit="depth"),
    MetricSpec("tpustack_llm_running_requests", "gauge",
               "Requests admitted to engine slots and still decoding.",
               unit="requests"),
    MetricSpec("tpustack_llm_prompt_tokens_total", "counter",
               "Prompt tokens prefilled.", unit="total"),
    MetricSpec("tpustack_llm_generated_tokens_total", "counter",
               "Tokens generated (decode output).", unit="total"),
    MetricSpec("tpustack_llm_requests_rejected_total", "counter",
               "Requests rejected at admission, by reason.",
               ("reason",), unit="total"),
    MetricSpec("tpustack_llm_batch_occupancy_slots", "histogram",
               "Requests served per continuous-engine busy period.",
               buckets=BATCH_BUCKETS, unit="slots"),
    MetricSpec("tpustack_llm_prompt_length_tokens", "histogram",
               "Prompt length distribution.",
               buckets=TOKEN_BUCKETS, unit="tokens"),

    # ---- LLM prefix KV cache (cross-request radix reuse) ----
    MetricSpec("tpustack_llm_prefix_cache_lookups_total", "counter",
               "Prefix-cache lookups, by result (hit|miss).  A hit means "
               "at least one chunk of the prompt's KV was reused.",
               ("result",), unit="total"),
    MetricSpec("tpustack_llm_prefix_cache_evictions_total", "counter",
               "Cached chunks evicted under capacity pressure (LRU "
               "leaves).", unit="total"),
    MetricSpec("tpustack_llm_prefix_cached_tokens", "histogram",
               "Prompt tokens served from the prefix cache per request "
               "(prefill FLOPs skipped; 0 on a miss).",
               buckets=TOKEN_BUCKETS, unit="tokens"),
    MetricSpec("tpustack_llm_prefix_cache_bytes", "gauge",
               "Resident bytes of cached KV segments (host RAM).",
               unit="bytes"),
    MetricSpec("tpustack_llm_prefix_cache_entries", "gauge",
               "Chunk nodes resident in the radix store.", unit="entries"),

    # ---- LLM paged KV pool (block-table substrate, kv_pool.py) ----
    MetricSpec("tpustack_llm_kv_free_blocks", "gauge",
               "Free blocks in the paged KV pool — what capacity-true "
               "admission checks against (plus evictable cached blocks).",
               unit="blocks"),
    MetricSpec("tpustack_llm_kv_used_blocks", "gauge",
               "Pool blocks held by live slots and/or the refcounted "
               "prefix cache.", unit="blocks"),
    MetricSpec("tpustack_llm_kv_copy_avoided_tokens_total", "counter",
               "Prompt-KV tokens served by block POINTER sharing instead "
               "of a host store's copies: prefix hits (restore host→HBM "
               "avoided) plus cache inserts (extract HBM→host avoided).  "
               "Zero with the cache cold.",
               unit="total"),
    MetricSpec("tpustack_llm_kv_block_fragmentation_ratio", "gauge",
               "Reserved-but-unfillable token slack in used blocks "
               "(block-size rounding): 0 = tight fit, rises with larger "
               "TPUSTACK_KV_BLOCK against short requests.", unit="ratio"),

    # ---- LLM host KV tier (kv_host_tier.py: refcount-0 prefix blocks
    # spill device→host at eviction instead of dying; a warm match
    # restores them with ONE fused host→HBM dispatch.  All series absent
    # at TPUSTACK_KV_HOST_TIER_MB=0 — the tier's bisection contract.
    # Conservation invariant the sanitizer asserts at quiesce:
    # spilled == restored + expired + resident_blocks) ----
    MetricSpec("tpustack_llm_kv_host_spilled_blocks_total", "counter",
               "Prefix blocks copied device→host at eviction time (the "
               "block's HBM is freed; its bytes live on in the host "
               "arena).", unit="total"),
    MetricSpec("tpustack_llm_kv_host_restored_blocks_total", "counter",
               "Host-tier blocks copied back into fresh pool blocks on a "
               "warm prefix match — each one is a block of prefill FLOPs "
               "the engine did NOT pay for.", unit="total"),
    MetricSpec("tpustack_llm_kv_host_expired_blocks_total", "counter",
               "Host-tier blocks dropped under the arena's byte cap (LRU) "
               "or retired with their trie subtree — their next reuse is "
               "a full recompute.", unit="total"),
    MetricSpec("tpustack_llm_kv_host_resident_bytes", "gauge",
               "Bytes resident in the host KV arena (≤ "
               "TPUSTACK_KV_HOST_TIER_MB).", unit="bytes"),

    # ---- LLM chunked prefill (long prompts split into block-aligned
    # chunks at wave boundaries; absent at TPUSTACK_PREFILL_CHUNK_TOKENS=0)
    MetricSpec("tpustack_llm_prefill_chunks_total", "counter",
               "Non-final chunked-prefill dispatches (each parks its slot "
               "again instead of monopolising the wave — decode latency "
               "for seated rows stays bounded by the chunk size).",
               unit="total"),

    # ---- KV working-set observatory (tpustack.obs.kvprof; SHARDS-style
    # sampled stack distances over prefix-chunk keys.  Gauges refresh at
    # scrape time via the profiler's collector; histograms observe at
    # event time.  All series absent at TPUSTACK_KVPROF_RATE=0 — the
    # profiler's bisection contract) ----
    MetricSpec("tpustack_llm_kv_working_set_blocks", "gauge",
               "Estimated prefix working-set size in pool blocks (distinct "
               "sampled chunks / sampling rate) — the number ROADMAP item "
               "4 sizes the host KV tier against.", unit="blocks"),
    MetricSpec("tpustack_llm_kv_counterfactual_hit_ratio", "gauge",
               "Online miss-ratio curve: predicted prefix hit rate IF the "
               "pool were capacity x {0.5x|1x|2x|4x} — the 1x point "
               "tracks the measured hit rate (CI-asserted), the others "
               "answer what more/less HBM would buy.",
               ("capacity",), unit="ratio"),
    MetricSpec("tpustack_llm_kv_block_lifetime_seconds", "histogram",
               "Alloc→release age of pool blocks by release outcome "
               "(retired | evicted_warm | evicted_cold | spilled | "
               "died_queued | other) — how long KV actually lives, and "
               "why it dies.  'spilled' frees the HBM but keeps the bytes "
               "in the host tier.",
               ("outcome",), buckets=SAVE_BUCKETS, unit="seconds"),
    MetricSpec("tpustack_llm_kv_eviction_age_seconds", "histogram",
               "Seconds since last hit for evicted prefix-cache entries "
               "(low = the LRU is churning entries still in use).",
               buckets=SAVE_BUCKETS, unit="seconds"),
    MetricSpec("tpustack_llm_kv_reuse_gap_seconds", "histogram",
               "Wall time between successive hits on the same cached "
               "prefix — the residency an entry needs to convert reuse "
               "into hits.", buckets=SAVE_BUCKETS, unit="seconds"),
    MetricSpec("tpustack_llm_kv_retry_after_error_seconds", "histogram",
               "Absolute error of the paged 429's projected block-release "
               "ETA vs the observed release wall — calibration of the "
               "Retry-After admission math.",
               buckets=(0.1, 0.5, 1.0, 2.0, 5.0, 15.0, 60.0),
               unit="seconds"),
    MetricSpec("tpustack_llm_prefix_evicted_warm_total", "counter",
               "Prefix-cache entries evicted within TPUSTACK_KVPROF_WARM_S "
               "of their last hit — avoidable evictions a bigger pool "
               "would have kept.", unit="total"),

    # ---- LLM speculative decoding (prompt-lookup / draft-model verify) ----
    MetricSpec("tpustack_llm_spec_drafted_tokens_total", "counter",
               "Draft tokens proposed to verify steps (prompt-lookup "
               "n-gram or draft-model).  Zero with TPUSTACK_SPEC_TOKENS=0 "
               "or when the acceptance throttle has every slot on plain "
               "decode.", unit="total"),
    MetricSpec("tpustack_llm_spec_accepted_tokens_total", "counter",
               "Draft tokens the verify step accepted (agreed with what "
               "the model would have produced).  Each accepted token is "
               "one decode weight-pass the engine did NOT pay for.",
               unit="total"),
    MetricSpec("tpustack_llm_spec_acceptance_ratio", "gauge",
               "Running accepted/drafted ratio since process start — the "
               "traffic-predictability signal the per-slot EMA throttle "
               "acts on (low ratio = drafting is wasted verify "
               "positions).", unit="ratio"),
    MetricSpec("tpustack_llm_spec_accepted_length_tokens", "histogram",
               "Accepted draft length per verify dispatch per slot (the "
               "slot advanced this + 1 tokens in one weight pass; 0 = "
               "the verify degenerated to a plain decode step).",
               buckets=(0, 1, 2, 3, 4, 6, 8, 16), unit="tokens"),

    # ---- engine flight recorder / live roofline (tpustack.obs.flight) ----
    MetricSpec("tpustack_llm_mfu_ratio", "gauge",
               "Live model-FLOP utilization of the serving engine over the "
               "flight-recorder window: delivered tokens/s x matmul FLOPs/"
               "token over the chip's bf16 peak.  Labelled by the matched "
               "device kind and OMITTED (sample-less) when the kind is "
               "unknown — never computed against the wrong wall "
               "(peaks.py contract).", ("device_kind",), unit="ratio"),
    MetricSpec("tpustack_llm_hbm_util_ratio", "gauge",
               "Live HBM-bandwidth utilization of decode over the flight "
               "window: weight passes/s x (weight stream + occupancy x "
               "per-slot KV read) over the HBM peak — decode's binding "
               "roofline, the \"how close to the hardware\" number the "
               "scale-out layer reads off a scrape.  Omitted on unknown "
               "device kinds.", ("device_kind",), unit="ratio"),
    MetricSpec("tpustack_sd_mfu_ratio", "gauge",
               "Live SD MFU over the flight window: summed pipeline FLOPs "
               "(XLA cost analysis per batch signature) over device-busy "
               "seconds against the bf16 peak — bench.py's MFU, computed "
               "from live traffic.  Omitted on unknown device kinds.",
               ("device_kind",), unit="ratio"),
    MetricSpec("tpustack_llm_wave_occupancy_slots", "gauge",
               "Mean live slots per engine wave over the flight window — "
               "decode streams the weights once per step regardless, so "
               "occupancy IS the decode-bandwidth amortisation factor.",
               unit="slots"),
    MetricSpec("tpustack_llm_spec_efficiency_tokens", "gauge",
               "Mean tokens delivered per decode weight pass over the "
               "flight window (plain decode = mean occupancy; speculation "
               "raises it by accepted drafts).  0 when the window holds "
               "no waves.", unit="tokens"),

    # ---- tenant cost accounting (tpustack.obs.accounting; the tenant
    # label is BOUNDED: first TPUSTACK_TENANT_CARDINALITY distinct
    # tenants + an 'other' overflow bucket.  Written ONLY through the
    # TenantLedger — tpulint TPL502 flags any other labels(tenant=...)
    # call site) ----
    MetricSpec("tpustack_tenant_prompt_tokens_total", "counter",
               "Prompt tokens prefilled, charged to the requesting tenant "
               "(X-Tenant-Id header / body tenant field).",
               ("server", "tenant"), unit="total"),
    MetricSpec("tpustack_tenant_generated_tokens_total", "counter",
               "Tokens generated for the tenant's completed requests.",
               ("server", "tenant"), unit="total"),
    MetricSpec("tpustack_tenant_chip_seconds_total", "counter",
               "Device wall seconds attributed to the tenant: each engine "
               "wave's wall time (the flight recorder's wave_s — live "
               "attribution and /debug/flight share the record) split "
               "across the slots it served; sd charges each fused batch's "
               "denoise+VAE seconds split across its riders; graph charges "
               "the finalize fetch per prompt (dispatch is async — its "
               "device wall lands in the fetch).  Per-tenant sums equal "
               "the engine's busy wall time — accounting, not estimation.",
               ("server", "tenant"), unit="total"),
    MetricSpec("tpustack_tenant_kv_block_seconds_total", "counter",
               "Paged-KV residency bill: pool blocks held x seconds held "
               "(allocation at admission to release at retire), per "
               "tenant.  The HBM a slow-rolling request occupies while "
               "others are shed.", ("tenant",), unit="total"),
    MetricSpec("tpustack_tenant_queue_seconds_total", "counter",
               "Admission-queue wall seconds the tenant's requests spent "
               "waiting (llm slot queue, sd batch window, graph worker "
               "queue).", ("server", "tenant"), unit="total"),
    MetricSpec("tpustack_tenant_requests_total", "counter",
               "Requests finished per tenant, by outcome (ok = completed "
               "in-deadline | shed = 429/503 backpressure or drain | "
               "deadline = 504 | error = 5xx | client_error = other 4xx, "
               "excluded from goodput).", ("server", "tenant", "outcome"),
               unit="total"),
    MetricSpec("tpustack_tenant_goodput_ratio", "gauge",
               "Lifetime goodput per tenant: ok / (ok + shed + deadline + "
               "error).  The number the QoS layer (quotas, priorities, "
               "SLO-aware shedding — ROADMAP item 5) will be judged by.",
               ("server", "tenant"), unit="ratio"),
    MetricSpec("tpustack_tenant_kv_working_set_blocks", "gauge",
               "Estimated prefix working-set blocks attributed to the "
               "tenant (sampled chunks owned by last toucher / rate) — "
               "tenant values partition the global working set, so the "
               "sum never exceeds tpustack_llm_kv_working_set_blocks.",
               ("tenant",), unit="blocks"),
    MetricSpec("tpustack_tenant_kv_hit_ratio", "gauge",
               "Per-tenant counterfactual prefix hit rate at {1x|2x} of "
               "current pool capacity, from the tenant's own sampled "
               "reuse distances — which tenant a host KV tier would "
               "actually help.", ("tenant", "capacity"), unit="ratio"),

    # ---- multi-tenant QoS (tpustack.serving.qos; priority ∈
    # interactive|batch.  The bucket gauge's tenant label is bounded by
    # construction: policy tenants are operator-declared config, never
    # client-minted) ----
    MetricSpec("tpustack_qos_shed_total", "counter",
               "Requests shed by the priority-aware backpressure wall: "
               "batch sheds at batch_shed_ratio of TPUSTACK_MAX_QUEUE_"
               "DEPTH, interactive at the full depth — under pressure "
               "batch eats the 429s first, by design.",
               ("server", "priority"), unit="total"),
    MetricSpec("tpustack_qos_preempt_total", "counter",
               "Engine slots preempted at a wave boundary so a waiting "
               "interactive request could run: the batch slot's state "
               "parks with its paged block refs retained and resumes via "
               "the prefix warm-start path (no prefill work lost).",
               ("priority",), unit="total"),
    MetricSpec("tpustack_qos_quota_throttle_total", "counter",
               "Requests 429'd because the tenant's token bucket (tokens/"
               "s or chip-seconds/s, TPUSTACK_QOS_POLICY) was in debt; "
               "Retry-After is that bucket's own refill ETA, not the "
               "global p50 heuristic.", ("server", "priority"),
               unit="total"),
    MetricSpec("tpustack_qos_requests_total", "counter",
               "Work requests finished per priority class, by outcome "
               "(same outcome taxonomy as tpustack_tenant_requests_total)"
               " — the numerator/denominator of the per-priority goodput "
               "recordings slo-rules.yaml alerts on (interactive only).",
               ("server", "priority", "outcome"), unit="total"),
    MetricSpec("tpustack_qos_queue_wait_seconds", "histogram",
               "Admission-queue wall time by priority class: llm engine "
               "queue (enqueue to slot pickup), sd micro-batch window "
               "(enqueue to fused dispatch), graph worker queue (submit "
               "to worker pickup) — the latency the interactive-first "
               "dequeue and wave-boundary preemption exist to bound.",
               ("server", "priority"), unit="seconds"),
    MetricSpec("tpustack_qos_bucket_level_ratio", "gauge",
               "Live token-bucket balance over burst per policy tenant "
               "and dimension (tokens|chip_seconds): 1 = full headroom, "
               "<= 0 = in debt (requests 429 until refill).  Tenant "
               "label bounded by the operator-declared policy, not "
               "client input.", ("tenant", "dimension"), unit="ratio"),

    # ---- serving mesh (tensor/data-parallel GSPMD serving) ----
    MetricSpec("tpustack_mesh_axis_chips", "gauge",
               "Serving-mesh axis sizes (dp/fsdp/tp/sp ways) of the "
               "process's device mesh; every axis 1 (or the series "
               "absent) means unsharded single-chip serving.",
               ("server", "axis"), unit="chips"),
    MetricSpec("tpustack_llm_weights_per_chip_bytes", "gauge",
               "Model weight bytes resident on ONE chip: total/tp for "
               "tp-sharded tensors, whole for replicated ones.  With "
               "tpustack_llm_kv_per_chip_bytes this is the serving HBM "
               "bill the 70B-over-v5e-8 sizing works from.", unit="bytes"),
    MetricSpec("tpustack_llm_kv_per_chip_bytes", "gauge",
               "Serving KV bytes resident on ONE chip: the paged pool's "
               "(or dense slot caches') largest single-device shard — "
               "pool/tp under head-axis sharding, the whole substrate "
               "unsharded (LLM_SHARD_KV=0 or no mesh).", unit="bytes"),
    MetricSpec("tpustack_llm_tp_collective_bytes", "gauge",
               "Estimated tensor-parallel all-reduce traffic per decoded "
               "token per chip (2 partial-sum reduces per layer x hidden "
               "dim x activation bytes x (tp-1)/tp) — the ICI bytes a "
               "decode step pays for running sharded; 0 unsharded.",
               unit="bytes"),

    # ---- SD server (signature-keyed micro-batcher) ----
    MetricSpec("tpustack_sd_queue_depth", "gauge",
               "Generate requests waiting in micro-batch groups.",
               unit="depth"),
    MetricSpec("tpustack_sd_batch_size_images", "histogram",
               "Real (un-padded) images per fused dispatch.",
               buckets=BATCH_BUCKETS, unit="images"),
    MetricSpec("tpustack_sd_padded_slots_total", "counter",
               "Pad rows added to reach canonical pow2/dp batch shapes — "
               "wasted device work.", unit="total"),
    MetricSpec("tpustack_sd_images_total", "counter",
               "Images generated (pad rows excluded).", unit="total"),

    # ---- graph (Wan video) server ----
    MetricSpec("tpustack_graph_queue_depth", "gauge",
               "Prompts queued for the worker (submitted, not dispatched).",
               unit="depth"),
    MetricSpec("tpustack_graph_prompts_total", "counter",
               "Prompt graphs finished, by outcome "
               "(success|error|rejected).", ("status",), unit="total"),
    MetricSpec("tpustack_graph_node_latency_seconds", "histogram",
               "Per-node execute time during graph resolution, by "
               "class_type.", ("node_class",), unit="seconds"),
    MetricSpec("tpustack_graph_batch_fallback_total", "counter",
               "Batched dispatches that failed (typically compile-time HBM "
               "OOM) and degraded to per-row serial dispatch.",
               unit="total"),

    # ---- resilience layer (tpustack.serving.resilience; all three servers) ----
    MetricSpec("tpustack_serving_drain_state", "gauge",
               "Lifecycle: 0 serving, 1 draining (SIGTERM received, "
               "finishing in-flight work), 2 drained (about to exit).",
               ("server",), unit="state"),
    MetricSpec("tpustack_requests_shed_total", "counter",
               "Work refused at admission, by reason (backpressure 429 | "
               "draining 503 | out_of_kv_blocks 429, llm paged mode).  "
               "All responses carry Retry-After.",
               ("server", "reason"), unit="total"),
    MetricSpec("tpustack_deadline_exceeded_total", "counter",
               "Requests cancelled at their deadline (504), by the phase "
               "they died in (queued|decode|denoise).",
               ("server", "phase"), unit="total"),
    MetricSpec("tpustack_watchdog_stalls_total", "counter",
               "Watchdog detections of in-flight work with no wave "
               "progress — each flips liveness so kubernetes restarts "
               "the pod.", ("server",), unit="total"),
    MetricSpec("tpustack_retry_after_seconds", "gauge",
               "Last Retry-After hint handed to a shed client: p50 "
               "service time scaled by queue depth over capacity.",
               ("server",), unit="seconds"),
    MetricSpec("tpustack_faults_injected_total", "counter",
               "Deterministic TPUSTACK_FAULT_* injections fired, by kind "
               "(serving: slow_prefill|device_error|dispatch_hang|sigterm; "
               "train, server=\"train\": kill_step|corrupt_ckpt).  "
               "Nonzero outside a chaos drill is a config bug.",
               ("server", "kind"), unit="total"),

    # ---- training resilience (tpustack.train.resilience; task ∈
    # resnet50|bert|llama2|sd15; scraped via the TPUSTACK_METRICS_PORT
    # sidecar the train-Job manifests wire up) ----
    MetricSpec("tpustack_train_steps_total", "counter",
               "Optimizer steps completed.", ("task",), unit="total"),
    MetricSpec("tpustack_train_heartbeat_seconds", "gauge",
               "Unix time of the last completed training step.  A Running "
               "pod whose heartbeat age keeps growing is the train-side "
               "hung-dispatch signal (Jobs have no liveness probe to "
               "flip).", ("task",), unit="seconds"),
    MetricSpec("tpustack_train_checkpoint_save_seconds", "histogram",
               "Background checkpoint write duration: async save start → "
               "last write into the committed step dir (saves are async — "
               "the step loop does not block on this).",
               ("task",), buckets=SAVE_BUCKETS, unit="seconds"),
    MetricSpec("tpustack_train_last_saved_step", "gauge",
               "Step number of the newest durable, manifest-verified "
               "checkpoint — what a restarted pod would resume from.",
               ("task",), unit="step"),
    MetricSpec("tpustack_train_restores_total", "counter",
               "Checkpoint restores at startup, by outcome (ok = newest "
               "step verified; fallback = an older step after "
               "quarantining corrupt newer ones).",
               ("task", "outcome"), unit="total"),
    MetricSpec("tpustack_train_emergency_saves_total", "counter",
               "SIGTERM-triggered emergency checkpoints flushed before "
               "the resumable exit (code 42).", ("task",), unit="total"),
    MetricSpec("tpustack_train_checkpoints_quarantined_total", "counter",
               "Checkpoints that failed integrity verification, renamed "
               "to <step>.corrupt and skipped at restore.  Nonzero means "
               "storage corrupted data in flight — see the runbook in "
               "docs/RESILIENCE.md.", ("task",), unit="total"),

    # ---- distributed tracing (tpustack.obs.trace; /debug/traces store) ----
    MetricSpec("tpustack_traces_captured_total", "counter",
               "Traces finalized into the in-process store, by kind (ok | "
               "slow = past TPUSTACK_TRACE_SLOW_S, always kept | error = "
               "a span errored, always kept | incomplete = spans never "
               "ended, evicted from the live table).", ("kind",),
               unit="total"),

    # ---- runtime sanitizers (tpustack.sanitize; tpusan) ----
    MetricSpec("tpustack_sanitizer_violations_total", "counter",
               "Runtime sanitizer violations, by check (guarded_by | "
               "lock_order | recompile | kv_leak | span_leak | "
               "thread_leak).  Counted in BOTH modes; under "
               "TPUSTACK_SANITIZE_MODE=report (production) this counter "
               "is the only signal — any nonzero value is a real "
               "correctness bug caught live, not noise.",
               ("check",), unit="total"),
    MetricSpec("tpustack_recompiles_total", "counter",
               "XLA traces observed per watched serving entry point "
               "(CompileWatch cache growth, exported at wave-boundary "
               "checks).  The cold compiles land once at the first check; "
               "any later increment is MID-TRAFFIC retracing — a multi-"
               "second stall per occurrence that looks like a hung "
               "dispatch from outside.  Populated while the sanitizer is "
               "enabled (report mode in production suffices).",
               ("entry_point",), unit="total"),

    # ---- perf baselines (tpustack.obs.perfsig; bench/baselines/) ----
    MetricSpec("tpustack_bench_baseline_info", "gauge",
               "One series (value 1) per committed perf baseline loaded "
               "at startup, labelled with the scenario name and the git "
               "sha the baseline was last ratcheted at "
               "(tools/perf_gate.py --update-baselines) — the perf bar "
               "this live server is being held to.",
               ("scenario", "git_sha"), unit="info"),
    MetricSpec("tpustack_bench_baseline_entries", "gauge",
               "Committed perf baselines loaded from the bench/baselines "
               "store (0 = no baseline store shipped with this deploy).",
               unit="entries"),

    # ---- L7 router (tpustack.serving.router; constructed only when
    # TPUSTACK_ROUTER_BACKENDS is set) ----
    MetricSpec("tpustack_router_requests_total", "counter",
               "Requests proxied through the router, by final outcome "
               "(ok | shed = upstream 429/503 surfaced to the client | "
               "deadline = upstream 504 | client_error = relayed 4xx "
               "without a shed header (the request's fault, not the "
               "proxy's) | error = connect/5xx after the retry budget | "
               "no_backend = healthy set empty).",
               ("outcome",), unit="total"),
    MetricSpec("tpustack_router_failover_total", "counter",
               "Failover attempts to a next-preference replica, by the "
               "reason the first choice was abandoned (connect_error | "
               "timeout | http_5xx | out_of_kv_blocks | queue_depth | "
               "draining).  quota sheds never appear here — quota is "
               "policy, not capacity.", ("reason",), unit="total"),
    MetricSpec("tpustack_router_backend_healthy_state", "gauge",
               "1 while the backend is in the routable healthy set, 0 "
               "while its circuit is open (ejected) or half-open.  The "
               "series is removed when the backend leaves the registry "
               "(dns:// pod churn must not grow label cardinality).",
               ("backend",), unit="state"),
    MetricSpec("tpustack_router_backend_ejections_total", "counter",
               "Circuit-open events per backend (consecutive passive "
               "failures reached TPUSTACK_ROUTER_EJECT_AFTER, or the "
               "active /readyz poll failed).", ("backend",), unit="total"),
    MetricSpec("tpustack_router_affinity_total", "counter",
               "Affinity-table lookups, by result (hit = rendezvous "
               "choice matches the prefix's last backend | cold_move = "
               "the prefix moved replicas, its KV there is cold | new = "
               "first sighting of this prefix).", ("result",),
               unit="total"),
    MetricSpec("tpustack_router_affinity_hit_ratio", "gauge",
               "hit / (hit + cold_move) over the router's lifetime — "
               "the fraction of repeat prefixes that landed on the "
               "replica already holding their KV.  Drops after an "
               "ejection, recovers as rendezvous re-converges.",
               unit="ratio"),
    MetricSpec("tpustack_router_retry_budget_retries", "gauge",
               "Remaining failover budget of the most recent request "
               "that needed at least one failover (budget exhausted at "
               "0 — the client saw the last upstream error honestly).",
               unit="retries"),

    # ---- elastic capacity controller (tpustack.serving.autoscaler;
    # constructed only when TPUSTACK_AUTOSCALER_ROUTER_URL is set) ----
    MetricSpec("tpustack_autoscaler_desired_replicas", "gauge",
               "Replica count the damped policy currently wants (after "
               "hysteresis, cooldowns and min/max clamping).",
               unit="replicas"),
    MetricSpec("tpustack_autoscaler_actual_replicas", "gauge",
               "Replica count the executor reports as existing (local: "
               "live subprocesses; k8s: the Deployment scale "
               "subresource).  desired != actual means a scale event is "
               "in flight or stuck — see the runbook.", unit="replicas"),
    MetricSpec("tpustack_autoscaler_scale_events_total", "counter",
               "Executed scale events, by direction (up|down) and the "
               "policy reason that fired them (load | shed_pressure | "
               "kv_pressure | idle | bounds).", ("direction", "reason"),
               unit="total"),
    MetricSpec("tpustack_autoscaler_policy_decision_state", "gauge",
               "Raw per-tick policy desire before damping: +1 scale up, "
               "-1 scale down, 0 hold.  Oscillation here with no scale "
               "events means the hysteresis/cooldowns are doing their "
               "job; oscillating EVENTS mean they are mis-tuned.",
               unit="state"),
    MetricSpec("tpustack_autoscaler_drain_wait_seconds", "histogram",
               "Scale-down choreography: seconds from the victim's "
               "admin drain to its clean exit (in-flight work finished "
               "+ SIGTERM drain state machine ran).",
               buckets=(0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0),
               unit="seconds"),

    # ---- fleet watchtower (tpustack.serving.watchtower; constructed
    # only when TPUSTACK_WATCHTOWER_ROUTER_URL is set) ----
    MetricSpec("tpustack_watchtower_alert_active", "gauge",
               "1 while the multi-window burn-rate alert for this "
               "(severity, server, SLI kind) is firing — the burn "
               "exceeds the severity's threshold over BOTH its long and "
               "short windows (page: 14.4x over 1h AND 5m; ticket: 6x "
               "over 6h AND 30m) — else 0.  The live, in-stack twin of "
               "the slo-rules.yaml Prometheus alerts.",
               ("severity", "server", "kind"), unit="active"),
    MetricSpec("tpustack_watchtower_burn_rate_ratio", "gauge",
               "Error-budget burn rate over each alert window "
               "((1 - SLI) / (1 - SLO); 1.0 = burning exactly the "
               "budget).  Absent while a window has no traffic.",
               ("severity", "server", "kind", "window"), unit="ratio"),
    MetricSpec("tpustack_watchtower_fleet_targets", "gauge",
               "Scrape targets the watchtower currently tracks, by role "
               "(router | replica | autoscaler).  replica count dropping "
               "without an autoscaler decision is itself an incident "
               "signal.", ("role",), unit="targets"),
    MetricSpec("tpustack_watchtower_incidents_total", "counter",
               "Incident bundles captured, by trigger reason (alert | "
               "ejection | breaker | unhealthy_floor).  Bounded by the "
               "capture cooldown — a flapping fleet yields one bundle "
               "per cooldown window, not one per flap.",
               ("reason",), unit="total"),
    MetricSpec("tpustack_watchtower_scrape_errors_total", "counter",
               "Fleet scrape failures, by target role.  A burst here "
               "means the watchtower is partially blind — alert state "
               "degrades to whatever targets still answer.",
               ("role",), unit="total"),

    # ---- black-box prober (tools/probe.py, the prober CronJob sidecar) ----
    MetricSpec("tpustack_probe_attempts_total", "counter",
               "Prober checks run, by target (llm|sd|graph), check "
               "(healthz|readyz|inference) and outcome (ok|failed).",
               ("target", "check", "outcome"), unit="total"),
    MetricSpec("tpustack_probe_latency_seconds", "histogram",
               "Black-box check latency as a client sees it (DNS + TCP + "
               "serve), per target and check.",
               ("target", "check"), unit="seconds"),
    MetricSpec("tpustack_probe_up_state", "gauge",
               "1 when the target's most recent full probe round passed "
               "every check, else 0 — the outside-in availability signal "
               "the SLO burn-rate alerts cannot provide (a wedged server "
               "stops reporting its own error ratio).",
               ("target",), unit="state"),
    MetricSpec("tpustack_probe_last_success_seconds", "gauge",
               "Unix time of the target's last fully-green probe round; "
               "alert when now() minus this grows past the probe cadence.",
               ("target",), unit="seconds"),

    # ---- batch clients (scripts/batch_generate.py via the Job sidecar) ----
    MetricSpec("tpustack_batch_generate_requests_total", "counter",
               "batch_generate client requests, by outcome (ok|failed).",
               ("outcome",), unit="total"),

    # ---- device / runtime (scrape-time collectors, obs.device) ----
    MetricSpec("tpustack_device_hbm_used_bytes", "gauge",
               "HBM bytes in use, per device "
               "(jax.Device.memory_stats bytes_in_use).",
               ("device",), unit="bytes"),
    MetricSpec("tpustack_device_hbm_limit_bytes", "gauge",
               "HBM capacity, per device "
               "(jax.Device.memory_stats bytes_limit).",
               ("device",), unit="bytes"),
    MetricSpec("tpustack_compile_cache_entries", "gauge",
               "Compiled programs in the persistent XLA cache dir.",
               unit="entries"),
    MetricSpec("tpustack_compile_cache_bytes", "gauge",
               "Bytes on disk in the persistent XLA cache dir.",
               unit="bytes"),
    MetricSpec("tpustack_compile_cache_hits_total", "counter",
               "Persistent-cache hits observed via jax monitoring events "
               "(0 until the first cached compile; absent listener support "
               "leaves it 0).", unit="total"),
    MetricSpec("tpustack_process_start_time_seconds", "gauge",
               "Unix time the process imported tpustack.obs.",
               unit="seconds"),
)


def build(registry: Optional[Registry] = None) -> Dict[str, object]:
    """Instantiate (get-or-create) every catalog metric in ``registry``
    (default: the process-wide one); returns name → family."""
    registry = registry or REGISTRY
    out: Dict[str, object] = {}
    for spec in CATALOG:
        if spec.type == "counter":
            out[spec.name] = registry.counter(spec.name, spec.help, spec.labels)
        elif spec.type == "gauge":
            out[spec.name] = registry.gauge(spec.name, spec.help, spec.labels)
        elif spec.type == "histogram":
            out[spec.name] = registry.histogram(
                spec.name, spec.help, spec.labels, buckets=spec.buckets)
        else:
            raise ValueError(f"{spec.name}: unknown metric type {spec.type}")
    return out
