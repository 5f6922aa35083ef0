"""LLM HTTP server — TPU-native replacement for the reference's llama.cpp pod.

The reference runs ``ghcr.io/ggml-org/llama.cpp:server-cuda`` with
``llama-server -m qwen2.5-7b-q4k.gguf --ctx-size 4096 --n-gpu-layers 35`` on
:8080 (reference ``cluster-config/apps/llm/deployment.yaml:61-87``).  This
server keeps llama.cpp's HTTP surface so existing clients/Gateway routes work:

- ``GET  /health``              → ``{"status": "ok"}``
- ``POST /completion``          → llama.cpp-style {content, tokens_predicted,
                                  tokens_evaluated, timings, model, stop};
                                  ``"stream": true`` → SSE token chunks
- ``POST /tokenize``            → {tokens};  ``POST /detokenize`` → {content}
- ``POST /v1/chat/completions`` → OpenAI-compatible chat endpoint, incl.
                                  ``"stream": true`` chunk events + [DONE]
- ``GET  /props``               → minimal server properties

but the engine is this package's JAX prefill+KV-cache generator on TPU: bf16
whole-model on-chip (no GGUF quantisation, no ``--n-gpu-layers`` CPU split —
v5e HBM holds 7B), ctx 4096 parity via ``LLM_CTX`` env.

Env: ``LLM_PRESET`` (``qwen25_7b``|``llama2_7b``|``k_exaone_236b_ep8``|
``tiny``|``tiny_moe``), ``LLM_CTX``,
``LLM_TP`` (tensor-parallel ways: GSPMD-shards the model over N chips,
lifting the per-chip HBM ceiling),
``LLM_KV_QUANT`` (``int8`` → per-vector int8 KV cache: halves long-context
decode KV traffic and cache HBM),
``LLM_CHUNK`` (decode tokens per fused dispatch for the solo path, default
32; the continuous engine runs at ``min(LLM_CHUNK, 16)`` — its chunk is
also the admission/streaming cadence, so latency caps it;
``LLM_ENGINE_CHUNK`` overrides that cap for throughput-first serving:
chunk 32 measured ~4% more steady aggregate than 16),
``LLM_QUANT`` (``int8`` → weight-only quantised serving, the analog of the
reference's Q4_K_M GGUF but ~2x decode from halved HBM traffic),
``LLM_MAX_BATCH`` (continuous-batching slot count — llama.cpp
``--parallel`` analog; requests join/leave the running batch at chunk
boundaries; ``LLM_BATCH_WINDOW_MS`` is a legacy no-op),
the engine's KV pool (slots hold block tables into one HBM-resident
pool, admission is "enough free blocks for prompt + max_new", prefix
reuse is zero-copy refcounted block sharing, and out-of-blocks requests
get 429 with a Retry-After computed from projected block release):
``TPUSTACK_KV_BLOCK`` is the block size in tokens (default
``min(64, max(8, ctx / 8))``, snapped to divide ctx);
``TPUSTACK_KV_POOL_BLOCKS`` is the allocatable pool size in blocks
(default ``LLM_MAX_BATCH x ctx / block``; raise it and ``LLM_MAX_BATCH``
together to serve more concurrent requests from the same HBM when
typical contexts run short of ctx),
``TPUSTACK_SPEC_TOKENS`` (speculative decoding on the continuous engine,
ON by default at 4 draft tokens per verify step: a host-side n-gram
prompt-lookup drafter proposes continuations out of each request's own
prompt+generated history and ONE forward pass scores draft+1 positions,
accepting the longest prefix that agrees with what the model would have
produced — greedy outputs are byte-identical speculation on or off, and
sampled outputs keep the target distribution via rejection sampling.
``0`` disables (bisection flag: the plain wave loop is byte-for-byte the
spec-free engine); per-slot draft length auto-throttles on a rolling
acceptance EMA so unpredictable traffic degrades to plain decode, never
below it; per-request opt-out via body ``"speculative": false``;
``TPUSTACK_SPEC_NGRAM`` caps the lookup n-gram length (default 3);
``TPUSTACK_SPEC_DRAFT=<preset>`` swaps the drafter for a greedy draft
MODEL of that preset (``tiny``|``llama2_7b``|``qwen25_7b``; weights from
``TPUSTACK_SPEC_DRAFT_DIR`` or random — rehearsal-grade), reusing the
same verify program),
``TPUSTACK_PREFIX_CACHE`` (cross-request prefix KV cache — radix reuse of
finished prefill KV so chat requests sharing a system prompt skip its
prefill entirely; on by default, ``0`` disables.  The engine's store is
the pool's refcounted block trie (``tpustack.serving.kv_pool``) and a
hit is pointer sharing; the ``LLM_MAX_BATCH=1`` solo route keeps the
host-resident radix store, where ``TPUSTACK_PREFIX_CACHE_MB`` caps
resident host bytes, default 512, and ``TPUSTACK_PREFIX_CACHE_CHUNK`` is
the snap granularity in tokens, default 256; per-request opt-out via
``"cache_prompt": false`` in the body — llama.cpp's field name),
``MODEL_DIR`` (HF safetensors), ``LLM_TOKENIZER_DIR``, ``PORT`` (8080),
plus the shared resilience contract (``tpustack.serving.resilience``):
``TPUSTACK_DRAIN_TIMEOUT_S``, ``TPUSTACK_REQUEST_TIMEOUT_S`` (per-request
body override ``timeout_s``), ``TPUSTACK_MAX_QUEUE_DEPTH``,
``TPUSTACK_WATCHDOG_S`` and the ``TPUSTACK_FAULT_*`` injection knobs.
``GET /healthz`` (liveness + engine state) and ``GET /readyz`` (readiness,
503 while draining) carry the kubernetes probe contract; ``/health`` stays
for llama.cpp client parity.
"""

from __future__ import annotations

import asyncio
import hmac
import json
import os
import threading
import time
import uuid
from typing import Optional

from aiohttp import web

from tpustack import sanitize
from tpustack.obs import accounting as obs_accounting
from tpustack.obs import catalog as obs_catalog
from tpustack.obs import device as obs_device
from tpustack.obs import flight as obs_flight
from tpustack.obs import http as obs_http
from tpustack.obs import profile as obs_profile
from tpustack.obs import trace as obs_trace
from tpustack.serving import qos as qos_mod
from tpustack.serving.resilience import (DeadlineExceeded,
                                         InjectedDeviceError,
                                         ResilienceManager, shed_headers)
from tpustack.utils import get_logger, knobs

log = get_logger("serving.llm_server")


class _Cancelled(Exception):
    """Raised inside the generate loop (via on_token) to abandon a stream
    whose client went away — stops burning TPU on a dead connection."""


class OutOfKVBlocks(Exception):
    """Paged admission shortfall: the pool (even after evicting every
    unreferenced cached block) cannot cover the request right now.
    ``retry_after_s`` is capacity-true — computed from the projected
    block-release time of the in-flight requests, not a slot-count
    heuristic — and handlers surface it as 429 + Retry-After."""

    def __init__(self, retry_after_s: int):
        super().__init__(f"out of KV blocks; retry after {retry_after_s}s")
        self.retry_after_s = retry_after_s


def _or_default(value, default):
    return default if value is None else value


def _normalize_seed(seed):
    """llama.cpp request convention: a negative seed (clients routinely
    send -1) means "draw a random one" — map it to None so the engine
    picks a fresh seed.  An integral float coerces to int (JSON clients
    round-trip 7 as 7.0); anything else raises ValueError → a 400,
    instead of silently going random and losing the reproducibility the
    client asked for (ADVICE r5)."""
    if seed is None:
        return None
    if isinstance(seed, bool) or not isinstance(seed, (int, float)):
        raise ValueError(f"seed must be an integer, got {seed!r}")
    if isinstance(seed, float):
        if not seed.is_integer():
            raise ValueError(f"seed must be an integer, got {seed!r}")
        seed = int(seed)
    return seed if seed >= 0 else None


def _build_generator():
    import jax.numpy as jnp

    from tpustack.models.llama import LlamaConfig
    from tpustack.models.llm_generate import Generator
    from tpustack.models.text_tokenizer import load_text_tokenizer

    import dataclasses

    preset = knobs.get_str("LLM_PRESET")
    ctx = knobs.get_int("LLM_CTX")
    if preset == "tiny":
        cfg = LlamaConfig.tiny(max_seq=min(ctx, 128))
        dtype = jnp.float32
    elif preset == "tiny_moe":
        cfg = LlamaConfig.tiny_moe(max_seq=min(ctx, 128))
        dtype = jnp.float32
    elif preset == "k_exaone_236b_ep8":
        # one chip's share of the 8-chip deployment (share 0): layer kinds,
        # routed experts and window attention on the same served path
        cfg = dataclasses.replace(LlamaConfig.k_exaone_236b_ep8(),
                                  max_seq=ctx)
        dtype = jnp.bfloat16
    elif preset == "llama2_7b":
        cfg = dataclasses.replace(LlamaConfig.llama2_7b(), max_seq=ctx)
        dtype = jnp.bfloat16
    elif preset == "llama2_70b":
        # the 70B-class config the tp mesh exists for: int8 + tp=8 fits a
        # v5e-8 pod (see tests/test_llm_tp.py::test_70b_tp8_serving_hbm_math
        # for the per-chip arithmetic); serving it without LLM_TP would OOM
        # one chip, which _build-time validation below turns into a clear
        # startup error instead of an allocator crash mid-load
        cfg = dataclasses.replace(LlamaConfig.llama2_70b(), max_seq=ctx)
        dtype = jnp.bfloat16
    else:
        cfg = dataclasses.replace(LlamaConfig.qwen25_7b(), max_seq=ctx)
        dtype = jnp.bfloat16

    quant = knobs.get_str("LLM_QUANT").lower() or None
    if quant not in (None, "int8"):
        raise ValueError(f"LLM_QUANT={quant!r} unsupported (want int8)")
    kv_quant = knobs.get_str("LLM_KV_QUANT").lower() or None
    if kv_quant not in (None, "int8"):
        raise ValueError(f"LLM_KV_QUANT={kv_quant!r} unsupported (want int8)")
    cfg = dataclasses.replace(cfg, quant=quant, kv_quant=kv_quant)

    # LLM_TP=N: tensor-parallel serving over N chips (GSPMD over a tp mesh
    # axis) — the whole-model-per-chip ceiling lifts to N x HBM (70B-class
    # on a v5e-8 pod, the scale story llama.cpp's GPU/CPU split approximated)
    mesh = None
    tp = knobs.get_int("LLM_TP")
    if tp > 1:
        import jax

        devices = jax.devices()
        if len(devices) < tp:
            raise ValueError(
                f"LLM_TP={tp} but only {len(devices)} device(s) visible — "
                "the manifest's google.com/tpu request must equal the "
                "LLM_TP/dp product (tools/lint_manifests.py enforces it)")
        from tpustack.parallel import build_mesh

        mesh = build_mesh((1, 1, tp, 1), devices=devices[:tp])
    elif preset == "llama2_70b":
        raise ValueError("LLM_PRESET=llama2_70b needs LLM_TP>1: 70B does "
                         "not fit one chip's HBM (int8 + tp=8 fits v5e-8)")
    # LLM_SHARD_KV=0 bisects back to compiler-placed (unsharded) serving
    # caches while keeping the mesh-partitioned compute
    shard_kv = knobs.get_bool("LLM_SHARD_KV")

    model_dir = os.environ.get("MODEL_DIR", "")
    if model_dir:
        gen = Generator.from_checkpoint(cfg, model_dir, dtype=dtype,
                                        mesh=mesh, shard_kv=shard_kv)
    else:
        gen = Generator(cfg, dtype=dtype, mesh=mesh, shard_kv=shard_kv)
    tok = load_text_tokenizer(cfg.vocab_size)
    return gen, tok, preset


class _PendingCompletion:
    """One request parked in the micro-batch queue.

    ``stream_put``: optional callable — set for streaming requests; the
    batch loop feeds it each of the row's tokens as chunks complete (and
    ``None`` once the row is done), chunk-granular SSE.  ``seed``: sampling
    seed forwarded to the engine's per-slot PRNG stream (seeded output is
    admission-timing independent, so seeded requests batch like any
    other)."""

    __slots__ = ("ids", "n_predict", "sample", "future", "cancel",
                 "stream_put", "seed", "prefix", "phase", "span_ctx",
                 "queue_span", "kv_blocks", "on_prefill_blocks",
                 "speculative", "tenant", "t_enqueue", "t_handed",
                 "t_kv_alloc", "priority", "host_restore")

    def __init__(self, ids, n_predict, sample, future, stream_put=None,
                 seed=None, prefix=None, kv_blocks=None,
                 on_prefill_blocks=None, speculative=True, t_kv_alloc=None,
                 host_restore=None):
        self.ids = ids
        self.n_predict = n_predict
        self.sample = sample
        self.future = future
        self.cancel = threading.Event()
        self.stream_put = stream_put
        self.seed = seed
        # deadline reporting: "queued" until feed() hands the request to an
        # engine slot, "decode" after — the phase a 504 names
        self.phase = "queued"
        # pool hooks: a prefix hit's shared blocks (`prefix`), the fresh
        # blocks pre-allocated at HTTP admission (the capacity check IS
        # the allocation, so admission and the engine can never
        # disagree) and the zero-copy cache-insert callback.  While
        # phase == "queued" the SERVER owns the references (released if the
        # request dies in the queue); feed() handing it to a slot transfers
        # ownership to the engine.
        self.prefix = prefix
        self.kv_blocks = kv_blocks
        self.on_prefill_blocks = on_prefill_blocks
        # host-tier warm start: (restore block ids, claimed payloads) —
        # the restore ids also ride at the tail of prefix[1], so the
        # refcount lifecycle is the ordinary prefix one; the PAYLOADS are
        # this request's to deliver (or abandon back to the tier's
        # conservation ledger if it dies queued)
        self.host_restore = host_restore
        # per-request speculation opt-out (body `"speculative": false`)
        self.speculative = speculative
        # distributed tracing: the request's HTTP root-span context (engine
        # threads parent their prefill/wave spans under it) and the
        # queue_wait span, open from enqueue until feed() hands the request
        # to a slot
        self.span_ctx = None
        self.queue_span = None
        # tenant cost accounting: the tenant id (resolved by the obs
        # middleware, captured at enqueue like span_ctx — engine threads
        # don't see the contextvar), enqueue wall clock (queue-seconds
        # charge when feed() pops the request, which stamps ``t_handed``),
        # and the paged-admission allocation wall clock (KV-block-seconds
        # run from here)
        self.tenant = None
        self.t_enqueue = 0.0
        self.t_handed = None
        self.t_kv_alloc = t_kv_alloc
        # QoS priority class (resolved by the resilience middleware,
        # captured at enqueue like tenant/span_ctx); None with QoS off
        self.priority = None


class LLMServer:
    """llama.cpp-surface LLM server with CONTINUOUS batching.

    Concurrent completions decode in persistent slots
    (``tpustack.models.llm_continuous.ContinuousEngine``): a request
    arriving mid-generation joins the running batch at the next
    ``LLM_CHUNK``-token boundary (its prefill, written through the slot's
    block table into the KV pool, happens while the chain keeps flowing)
    and a finished row is answered and its slot freed immediately —
    llama.cpp's slot semantics (reference server
    ``--parallel``; deployment.yaml:67-84), not a collect-window batch.
    Decode streams the weights once per step regardless of how many slots
    are live, so aggregate tokens/s scales ~linearly with occupancy, and
    each row's context budget is its own ``max_seq - len(prompt)`` (no
    shared longest-peer bucket).

    EVERY request batches (llama.cpp parity): seeded non-greedy requests
    ride per-slot PRNG streams, so their output depends only on (prompt,
    seed) — never on admission timing or batch peers — and long prompts
    admit like any other (a slot's block table spans ``max_seq``;
    admission charges ``prompt + max_new`` pool blocks; admission prefills
    are bucket-grouped so a short prompt never pays a long peer's
    padding, and they overlap the running decode chain).  The
    one long-prompt cost that remains is physical: a K-token admission
    prefill occupies the chip for its duration, so in-flight peers see
    that as added latency — exactly llama.cpp's behavior on one GPU.  The
    solo path survives only for ``LLM_MAX_BATCH=1`` deployments.
    """

    #: sentinel: "build the prefix cache from the environment"
    _PREFIX_FROM_ENV = object()
    #: sentinel: "build the speculative-decoding config from the environment"
    _SPEC_FROM_ENV = object()

    def __init__(self, generator=None, tokenizer=None, model_name: str = "tpustack",
                 max_batch: Optional[int] = None,
                 batch_window_ms: Optional[float] = None,
                 registry=None, prefix_cache=_PREFIX_FROM_ENV, tracer=None,
                 paged=None, spec=_SPEC_FROM_ENV):
        # metrics registry: tests pass a fresh Registry for isolation; the
        # default is the process-wide one /metrics exposes
        self._registry = registry
        self.metrics = obs_catalog.build(registry)
        obs_device.install(registry)
        # distributed tracing: same isolation contract as the registry —
        # tests pass a fresh Tracer, production shares the process default
        self.tracer = tracer if tracer is not None else obs_trace.TRACER
        # tenant cost ledger (tpustack.obs.accounting): the process-wide
        # one on the default registry, a private one when a test injects
        # its own Registry — the same isolation contract as the tracer
        self.ledger = obs_accounting.for_registry(registry)
        # multi-tenant QoS (tpustack.serving.qos): priority classes at
        # admission + interactive-first scheduling + wave-boundary
        # preemption + per-tenant token-bucket quotas driven by the
        # ledger's measured charges.  None (TPUSTACK_QOS=0) keeps the
        # whole serving path byte-for-byte QoS-free.
        self.qos = qos_mod.QosPolicy.from_env(registry=registry)
        if self.qos is not None:
            self.ledger.add_listener(self.qos.on_ledger_charge)
        if generator is None:
            generator, tokenizer, model_name = _build_generator()
        self.gen = generator
        self.tok = tokenizer
        self.model_name = model_name
        self._lock = asyncio.Lock()
        self.max_batch = (knobs.get_int("LLM_MAX_BATCH")
                          if max_batch is None else max_batch)
        # the engine's KV store (tpustack.serving.kv_pool): one HBM block
        # pool + per-slot block tables, capacity-true admission,
        # refcounted zero-copy prefix sharing.  Tests pass a runtime; None
        # builds one from the environment.  ``paged is None`` afterwards
        # is the same fact as ``max_batch == 1``: the solo route runs no
        # engine and keeps the host-resident PrefixCache instead.
        if self.max_batch > 1:
            if (prefix_cache is not LLMServer._PREFIX_FROM_ENV
                    and prefix_cache is not None):
                raise ValueError(
                    "a host PrefixCache serves only the LLM_MAX_BATCH=1 "
                    "solo route; the engine's prefix cache is the pool's "
                    "block trie (TPUSTACK_PREFIX_CACHE)")
            if paged is None:
                paged = self._build_paged(self.gen, self.max_batch)
                if prefix_cache is None:
                    paged.cache = None  # caller asked for NO prefix cache:
                    # keep the pool, drop the block trie
            prefix_cache = None  # the block trie replaces the host store
        else:
            paged = None
        self.paged = paged
        # paged-flash verdict resolved ONCE at boot: a typo'd
        # TPUSTACK_PAGED_FLASH fails startup like every other knob typo,
        # not on the first work cycle's executor thread; engines and
        # /props both read this resolved value
        from tpustack.models.llm_generate import resolve_paged_flash

        self.paged_flash = (resolve_paged_flash(mesh=self.gen.mesh)
                            if paged is not None else False)
        # cross-request prefix KV cache of the solo route
        # (tpustack.serving.prefix_cache): tests pass an instance (tiny
        # chunk) or None (hard off); serving builds from
        # TPUSTACK_PREFIX_CACHE{,_MB,_CHUNK}, default ON — lookup/insert
        # are no-ops until a prompt spans a whole chunk
        if prefix_cache is LLMServer._PREFIX_FROM_ENV:
            prefix_cache = self._build_prefix_cache()
        self.prefix_cache = prefix_cache
        if prefix_cache is not None and prefix_cache._on_evict is None:
            prefix_cache._on_evict = (
                lambda n: self.metrics[
                    "tpustack_llm_prefix_cache_evictions_total"].inc(n))
        trie = paged.cache if paged is not None else None
        if trie is not None:
            if trie.on_evict is None:
                # same exported counter as the solo route's host store
                trie.on_evict = (
                    lambda n: self.metrics[
                        "tpustack_llm_prefix_cache_evictions_total"].inc(n))
            # warm-eviction visibility rides the unconditional last-hit
            # stamping (kv_pool) — counted whether or not the profiler is on
            trie.on_evict_warm = (
                lambda n: self.metrics[
                    "tpustack_llm_prefix_evicted_warm_total"].inc(n))
            tier = getattr(trie, "host_tier", None)
            if tier is not None and tier.metrics is None:
                # _build_paged is static (and tests hand-build runtimes):
                # the spill/restore/expire counters attach here, once the
                # server's metric set exists
                tier.metrics = self.metrics
        # KV working-set observatory (tpustack.obs.kvprof): SHARDS-sampled
        # online miss-ratio curve, block-lifetime telemetry, Retry-After
        # calibration — observer hooks on the pool/trie, gauges refreshed
        # by a scrape-time collector, served on GET /debug/kvcache.
        # TPUSTACK_KVPROF_RATE=0 constructs nothing and attaches nothing.
        self.kvprof = None
        if self.paged is not None:
            from tpustack.obs import kvprof as obs_kvprof
            from tpustack.obs.metrics import REGISTRY as _default_registry

            # resolve the registry the way every other component does —
            # a None here would leave the profiler metrics-free (the
            # bench/replay snapshot-only mode), silencing the scrape
            # gauges on a production boot
            self.kvprof = obs_kvprof.from_env(
                self.paged.pool, cache=self.paged.cache,
                registry=(registry if registry is not None
                          else _default_registry))
            if self.kvprof is not None:
                self.kvprof.ledger = self.ledger
        # speculative decoding (tpustack.serving.speculative.SpecConfig):
        # tests pass a SpecConfig (or None for hard off); serving builds
        # from TPUSTACK_SPEC_TOKENS & friends, default ON — the engine's
        # verify step keeps greedy outputs byte-identical, so this is a
        # perf knob, not a behavior change.  Engine-only: LLM_MAX_BATCH=1
        # solo deployments decode plain.
        if spec is LLMServer._SPEC_FROM_ENV:
            spec = self._build_spec(self.gen)
        self.spec_cfg = spec
        self._spec_drafted = 0
        self._spec_accepted = 0
        # live engine during a busy period — the projected-block-release
        # estimate behind 429 Retry-After reads it opportunistically
        # (reads are advisory; the write happens on the executor thread
        # that holds the device lock)
        self._engine = None  # guarded-by: _lock (writes)
        # legacy knob (pre-continuous window batching): accepted, unused
        self.batch_window_ms = (
            knobs.get_float("LLM_BATCH_WINDOW_MS")
            if batch_window_ms is None else batch_window_ms)
        # decode tokens per fused scan dispatch: larger chunks amortise the
        # per-dispatch tail (chunk 64 measured ~6% over 32 at 7B int8)
        self.chunk = max(1, knobs.get_int("LLM_CHUNK"))
        # the continuous engine's chunk is the CAPACITY of a decode
        # dispatch — the most steps one runs, so the coarsest admission +
        # SSE cadence; it defaults to min(LLM_CHUNK, 16).  How many steps a
        # dispatch does run the engine chooses itself, from its lanes and
        # its own clocks (ContinuousEngine._dispatch_len), so this is no
        # latency knob: LLM_ENGINE_CHUNK overrides the capacity.
        # 0/empty means "no override" (the LLM_BATCH_WINDOW_MS convention),
        # not a 1-token cadence
        override = knobs.get_int("LLM_ENGINE_CHUNK")
        self._engine_chunk_override = override if override > 0 else None
        import collections

        self._queue: "collections.deque" = collections.deque()
        self._wake: Optional[asyncio.Event] = None
        self._batch_task = None
        # solo requests queued on the device lock; the engine stops
        # admitting while > 0 so the FIFO-fair lock can hand over
        self._solo_waiting = 0
        self._profiling = False  # one POST /profile capture at a time
        # shared resilience layer: drain on SIGTERM, per-request deadlines,
        # 429 backpressure, hung-dispatch watchdog, TPUSTACK_FAULT_* hooks
        self.resilience = ResilienceManager(
            "llm", registry, concurrency=self.max_batch,
            queue_depth=lambda: len(self._queue) + self._solo_waiting,
            expected_service_s=2.0, qos=self.qos)
        # engine flight recorder (tpustack.obs.flight): one structured
        # record per engine dispatch, served on /debug/flight and
        # auto-dumped on watchdog fire / SIGTERM drain / fatal engine
        # error / sanitizer violation.  The scrape-time collector below
        # turns its windowed rates into the live roofline gauges.
        self.flight = obs_flight.register(obs_flight.FlightRecorder(
            "llm", meta={
                "model": model_name,
                "slots": self.max_batch,
                "chunk": self.engine_chunk,
                "paged_kv": self.paged is not None,
                "spec_tokens": (self.spec_cfg.tokens
                                if self.spec_cfg is not None else 0),
            }))
        # per-token FLOPs + per-pass HBM bytes from the served config —
        # the same arithmetic bench_llm reports offline, so the live
        # gauges and the bench can never disagree
        self._flight_arith = obs_flight.llm_wave_arith(
            self.gen.cfg, self.gen.params, self.gen.cache_dtype,
            rows=self.max_batch)
        self._flight_chips = self._mesh_props()["devices"]
        from tpustack.obs.metrics import REGISTRY

        (registry if registry is not None else REGISTRY).add_collector(
            self._flight_collector)
        if self.kvprof is not None:
            # working-set / counterfactual gauges are derived state:
            # computed when Prometheus asks, like the roofline gauges
            (registry if registry is not None else REGISTRY).add_collector(
                self.kvprof.collect)
        self._export_mesh_gauges()
        # committed perf baselines (bench/baselines) as info gauges: a
        # scrape shows which bench bar this server build is held to
        # (tools/perf_gate.py; tpustack.obs.perfsig)
        from tpustack.obs import perfsig

        perfsig.export_baseline_gauges(registry)
        sanitize.install_guards(self)

    def _flight_collector(self, registry) -> None:
        """Scrape-time roofline attribution: the flight window's delivered
        tokens/s and weight passes/s against the chip's peaks.  Occupancy
        and spec-efficiency gauges always; the MFU/HBM-utilization gauges
        only when the device kind is known (omitted, never faked — the
        peaks.py contract)."""
        from tpustack.utils import knobs as _knobs

        agg = self.flight.aggregates(
            _knobs.get_float("TPUSTACK_FLIGHT_WINDOW_S"))
        m = self.metrics
        kind, peaks = obs_flight.device_peaks_info()
        if not agg.get("waves"):
            # idle window: the truthful utilization is ~0, not the last
            # busy window's value frozen forever — clear instead of skip
            # (the MFU gauges only once they exist: kind must be known)
            m["tpustack_llm_wave_occupancy_slots"].set(0)
            m["tpustack_llm_spec_efficiency_tokens"].set(0)
            if peaks is not None and kind:
                m["tpustack_llm_mfu_ratio"].labels(device_kind=kind).set(0)
                m["tpustack_llm_hbm_util_ratio"].labels(
                    device_kind=kind).set(0)
            return
        if agg.get("mean_occupancy") is not None:
            m["tpustack_llm_wave_occupancy_slots"].set(agg["mean_occupancy"])
        if agg.get("tokens_per_weight_pass"):
            m["tpustack_llm_spec_efficiency_tokens"].set(
                agg["tokens_per_weight_pass"])
        util = obs_flight.llm_utilization(agg, self._flight_arith, peaks,
                                          chips=self._flight_chips)
        if util is not None and kind:
            m["tpustack_llm_mfu_ratio"].labels(device_kind=kind).set(
                util["mfu"])
            m["tpustack_llm_hbm_util_ratio"].labels(device_kind=kind).set(
                util["hbm_util"])

    # --------------------------------------------------- mesh accounting
    def _kv_per_chip_bytes(self) -> int:
        """Serving-KV bytes ONE chip holds: the pool's largest
        single-device shard, or (solo route) the one cache line's
        arithmetic equivalent — total cache bytes over the tp ways when
        the kv-head axis shards, whole otherwise."""
        if self.paged is not None:
            return self.paged.per_shard_bytes
        import jax.numpy as jnp

        from tpustack.parallel.sharding import can_shard_kv_heads

        c = self.gen.cfg
        elt = (1 if c.kv_quant == "int8"
               else jnp.dtype(self.gen.cache_dtype).itemsize)
        per_tok = c.n_layers * 2 * c.n_kv_heads * (
            c.head_dim * elt + (4 if c.kv_quant == "int8" else 0))
        total = self.max_batch * c.max_seq * per_tok
        if can_shard_kv_heads(self.gen.kv_mesh, c.n_kv_heads):
            total //= int(self.gen.kv_mesh.shape["tp"])
        return total

    def _mesh_props(self) -> dict:
        """Mesh shape + per-chip HBM bill for ``/props`` and the startup
        gauges — what an operator checks to confirm a google.com/tpu: 8
        pod is actually serving sharded."""
        import jax.numpy as jnp

        from tpustack.parallel.sharding import (can_shard_kv_heads,
                                                mesh_axis_sizes,
                                                tree_per_shard_bytes)

        axes = mesh_axis_sizes(self.gen.mesh)
        tp = axes.get("tp", 1)
        devices = 1
        for ways in axes.values():
            devices *= ways
        c = self.gen.cfg
        # estimated tp all-reduce bytes per decoded token per chip: two
        # partial-sum reduces per layer (o_proj + down_proj row-parallel
        # outputs) over the [1, dim] activation
        act_bytes = jnp.dtype(self.gen.cache_dtype).itemsize
        collective = (0 if tp <= 1 else
                      int(2 * c.n_layers * c.dim * act_bytes
                          * (tp - 1) / tp))
        return {
            "enabled": self.gen.mesh is not None,
            "axes": axes,
            "devices": devices,
            "tp": tp,
            "kv_head_sharded": can_shard_kv_heads(self.gen.kv_mesh,
                                                  c.n_kv_heads),
            "weights_per_chip_bytes": tree_per_shard_bytes(self.gen.params),
            "kv_per_chip_bytes": self._kv_per_chip_bytes(),
            "tp_collective_bytes_per_token": collective,
        }

    def _export_mesh_gauges(self) -> None:
        from tpustack.parallel.sharding import export_mesh_axis_gauges

        info = self._mesh_props()
        m = self.metrics
        export_mesh_axis_gauges(m, "llm", self.gen.mesh)
        m["tpustack_llm_weights_per_chip_bytes"].set(
            info["weights_per_chip_bytes"])
        m["tpustack_llm_kv_per_chip_bytes"].set(info["kv_per_chip_bytes"])
        m["tpustack_llm_tp_collective_bytes"].set(
            info["tp_collective_bytes_per_token"])

    @staticmethod
    def _build_prefix_cache():
        from tpustack.serving.prefix_cache import PrefixCache

        if not knobs.get_bool("TPUSTACK_PREFIX_CACHE"):
            return None
        # registry owns the defaults; an explicit 0 stays 0 (the store
        # then clamps capacity to its 1-byte floor)
        mb = knobs.get_float("TPUSTACK_PREFIX_CACHE_MB")
        chunk = knobs.get_int("TPUSTACK_PREFIX_CACHE_CHUNK")
        return PrefixCache(chunk_tokens=chunk,
                           capacity_bytes=max(1, int(mb * 1024 * 1024)))

    @staticmethod
    def _build_paged(gen, max_batch: int):
        """The engine's KV pool, sized from the environment
        (``TPUSTACK_KV_BLOCK``, ``TPUSTACK_KV_POOL_BLOCKS``,
        ``TPUSTACK_PREFIX_CACHE``, ``TPUSTACK_KV_HOST_TIER_MB``); None for
        an ``LLM_MAX_BATCH=1`` solo deployment, which runs no engine."""
        if max_batch < 2:
            return None
        from tpustack.serving.kv_pool import PagedKVRuntime

        return PagedKVRuntime.build(
            gen.cfg, max_batch,
            block=knobs.get_int("TPUSTACK_KV_BLOCK"),
            pool_blocks=knobs.get_int("TPUSTACK_KV_POOL_BLOCKS"),
            dtype=gen.cache_dtype, mesh=gen.kv_mesh,
            prefix_cache=knobs.get_bool("TPUSTACK_PREFIX_CACHE"),
            host_tier_mb=knobs.get_float("TPUSTACK_KV_HOST_TIER_MB"))

    @staticmethod
    def _build_spec(gen):
        """Speculative-decoding config from the environment (default ON:
        4-token prompt-lookup drafting).  ``TPUSTACK_SPEC_TOKENS=0`` is
        the bisection flag — the engine's wave loop is then byte-for-byte
        the spec-free one.  ``TPUSTACK_SPEC_DRAFT=<preset>`` builds a
        draft-model drafter (weights from ``TPUSTACK_SPEC_DRAFT_DIR``, or
        random — the verify step owns correctness either way)."""
        from tpustack.serving.speculative import SpecConfig

        k = knobs.get_int("TPUSTACK_SPEC_TOKENS")
        if k <= 0:
            return None
        ngram = max(1, knobs.get_int("TPUSTACK_SPEC_NGRAM"))
        drafter = None
        preset = knobs.get_str("TPUSTACK_SPEC_DRAFT").strip()
        if preset:
            drafter = LLMServer._build_draft_drafter(gen, preset)
        return SpecConfig(tokens=k, ngram_max=ngram, drafter=drafter)

    @staticmethod
    def _build_draft_drafter(gen, preset: str):
        import dataclasses as _dc

        import jax.numpy as jnp

        from tpustack.models.llama import LlamaConfig
        from tpustack.models.llm_generate import Generator
        from tpustack.serving.speculative import DraftModelDrafter

        presets = ("tiny", "llama2_7b", "qwen25_7b")
        if preset not in presets:
            raise ValueError(f"TPUSTACK_SPEC_DRAFT={preset!r}: unknown "
                             f"preset (want one of {presets})")
        cfg = (LlamaConfig.tiny(max_seq=gen.cfg.max_seq)
               if preset == "tiny" else _dc.replace(
                   getattr(LlamaConfig, preset)(), max_seq=gen.cfg.max_seq))
        dtype = jnp.float32 if preset == "tiny" else jnp.bfloat16
        model_dir = knobs.get_str("TPUSTACK_SPEC_DRAFT_DIR")
        if model_dir:
            draft_gen = Generator.from_checkpoint(cfg, model_dir,
                                                  dtype=dtype)
        else:
            draft_gen = Generator(cfg, dtype=dtype)
        log.info("speculative draft model: %s (%s)", preset,
                 model_dir or "random weights")
        return DraftModelDrafter(draft_gen)

    def _note_spec(self, drafted: int, accepted: int) -> None:
        """Per-verify-dispatch speculation accounting (engine thread):
        counters, the per-dispatch accepted-length histogram, and the
        running acceptance-ratio gauge."""
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        m = self.metrics
        m["tpustack_llm_spec_drafted_tokens_total"].inc(drafted)
        m["tpustack_llm_spec_accepted_tokens_total"].inc(accepted)
        m["tpustack_llm_spec_accepted_length_tokens"].observe(accepted)
        m["tpustack_llm_spec_acceptance_ratio"].set(
            self._spec_accepted / self._spec_drafted
            if self._spec_drafted else 0.0)

    # ---------------------------------------------------- paged admission
    def _paged_gauges(self) -> None:
        p = self.paged.pool
        self.metrics["tpustack_llm_kv_free_blocks"].set(p.n_free)
        self.metrics["tpustack_llm_kv_used_blocks"].set(p.n_used)
        self.metrics["tpustack_llm_kv_block_fragmentation_ratio"].set(
            p.fragmentation())

    def _paged_retry_after(self, shortfall_blocks: int) -> int:
        """Capacity-true Retry-After: seconds until the in-flight
        requests' projected block releases cover the shortfall (engine
        fetch-mark decode rate x remaining budgets), clamped to [1, 120].
        Falls back to the resilience layer's p50-service heuristic when no
        engine run is live to estimate from."""
        import math

        eng = self._engine
        ra = None
        if eng is not None:
            try:
                ra = eng.projected_block_release_s(shortfall_blocks)
            except Exception:
                # the p50 fallback below still answers the client, but a
                # broken estimator must not fail silently forever
                # (tpulint TPL301 caught exactly that here)
                log.debug("projected block-release estimate failed; "
                          "falling back to p50 Retry-After", exc_info=True)
                ra = None
        if ra is None:
            return self.resilience.retry_after_s()
        clamped = min(max(1, math.ceil(ra)), 120)
        self.metrics["tpustack_retry_after_seconds"].labels(
            server="llm").set(clamped)
        if self.kvprof is not None:
            # calibration: arm the RAW estimate (not the clamp) against
            # the observed release wall — the 429's admission math is
            # what item 4's host tier reuses, so IT is what's measured
            self.kvprof.note_retry_after(shortfall_blocks, float(ra))
        return clamped

    def _paged_admit(self, ids, n_predict: int, cache_prompt: bool):
        """Admission + prefix hooks for the paged engine, in ONE step: the
        capacity check IS the allocation.  A prefix hit increfs the shared
        blocks (zero-copy — counted in the copy-avoided total) and only
        the uncached remainder allocates fresh blocks; a shortfall first
        evicts unreferenced cached blocks (LRU), then raises
        :class:`OutOfKVBlocks` with the projected-release Retry-After.
        Returns ``(prefix, kv_blocks, on_prefill_blocks)`` for the
        SlotRequest."""
        from tpustack.serving.kv_pool import OutOfBlocks

        rt = self.paged
        prefix = None
        host_restore = None
        if rt.cache is not None and cache_prompt:
            m = rt.cache.match(ids)
            hit = bool(m.length or m.host_payloads)
            self.metrics["tpustack_llm_prefix_cache_lookups_total"].labels(
                result="hit" if hit else "miss").inc()
            if m.length:
                self.metrics[
                    "tpustack_llm_kv_copy_avoided_tokens_total"].inc(
                    m.length)
                prefix = (m.length, m.block_ids)
            host_tokens = 0
            if m.host_payloads:
                # host-tier warm start: seat the claimed payloads in fresh
                # pool blocks riding the PREFIX refcount lifecycle (the
                # engine fuses the host→HBM copy with the warm start).  A
                # full pool downgrades to the HBM hit alone — abandon()
                # keeps the tier's conservation ledger exact
                tier = rt.cache.host_tier
                n_host = len(m.host_payloads)
                try:
                    rt.ensure_free(n_host)
                    restore_ids = rt.pool.alloc_tokens(n_host * rt.block)
                except OutOfBlocks:
                    tier.abandon(n_host)
                else:
                    prefix = (m.length + n_host * rt.block,
                              m.block_ids + list(restore_ids))
                    host_restore = (restore_ids, m.host_payloads)
                    host_tokens = n_host * rt.block
            self.metrics["tpustack_llm_prefix_cached_tokens"].observe(
                m.length + host_tokens)
            span = obs_trace.current_span.get()
            if span is not None:
                extra = ({"host_restored_tokens": host_tokens}
                         if host_tokens else {})  # tier off: event shape
                span.add_event("prefix_cache",  # identical to pre-tier
                               result="hit" if hit else "miss",
                               cached_tokens=m.length, **extra)
        n_shared = len(prefix[1]) if prefix else 0
        fresh_tokens = (rt.need_tokens(len(ids), max(0, n_predict))
                        - n_shared * rt.block)
        need_fresh = rt.pool.blocks_for(fresh_tokens)
        if n_shared + need_fresh > rt.pool.capacity_blocks:
            if prefix:
                rt.pool.decref(prefix[1])
            if host_restore:
                # claimed payloads die unwritten: restored → expired
                rt.cache.host_tier.abandon(len(host_restore[1]))
            raise ValueError(
                f"request needs {n_shared + need_fresh} KV blocks; the "
                f"pool holds {rt.pool.capacity_blocks} "
                f"(TPUSTACK_KV_POOL_BLOCKS)")
        try:
            rt.ensure_free(need_fresh)
            kv_blocks = rt.pool.alloc_tokens(fresh_tokens)
        except OutOfBlocks:
            if prefix:
                rt.pool.decref(prefix[1])
            if host_restore:
                rt.cache.host_tier.abandon(len(host_restore[1]))
            self.metrics["tpustack_requests_shed_total"].labels(
                server="llm", reason="out_of_kv_blocks").inc()
            shortfall = need_fresh - rt.pool.n_free
            raise OutOfKVBlocks(self._paged_retry_after(shortfall)) from None
        on_insert = None
        if (rt.cache is not None and cache_prompt
                and (len(ids) // rt.block > n_shared
                     or host_restore is not None)):
            # host_restore forces the insert even with zero fresh full
            # blocks: it is what RE-PROMOTES the claimed stubs onto their
            # freshly-seated pool blocks (skipping it would free them at
            # retire and strand the trie path)
            ids_copy = list(ids)

            def on_insert(bids):
                new_toks = rt.cache.insert(ids_copy, bids)
                if new_toks:
                    # dense inserts copied these tokens' KV device→host;
                    # recording block ids moves zero bytes
                    self.metrics[
                        "tpustack_llm_kv_copy_avoided_tokens_total"].inc(
                        new_toks)
        self._paged_gauges()
        return prefix, kv_blocks, on_insert, host_restore

    def _paged_release(self, r: "_PendingCompletion") -> None:
        """Release a QUEUED request's pool references (pre-allocated fresh
        blocks + prefix-hit refs).  No-op once feed() handed the request
        to a slot — from then on the engine owns the references and
        releases them at retire (or in its failure path)."""
        if r.phase != "queued":
            return
        ids = list(r.kv_blocks or [])
        if r.prefix:
            ids += list(r.prefix[1])
        r.kv_blocks, r.prefix = None, None
        if r.host_restore is not None:
            # died queued before the engine seated the payloads: their
            # restore blocks free with the prefix refs above; the claims
            # go back to the tier's ledger as expired
            tier = getattr(self.paged.cache, "host_tier", None)
            if tier is not None:
                tier.abandon(len(r.host_restore[1]))
            r.host_restore = None
        if ids:
            if r.tenant is not None and r.t_kv_alloc:
                # the request died queued but its blocks were resident
                # the whole time — the residency bill is real either way
                self.ledger.charge_kv_block_seconds(
                    r.tenant,
                    len(ids) * max(0.0, time.time() - r.t_kv_alloc))
            self.paged.pool.decref(ids, outcome="died_queued")
            self._paged_gauges()

    def _prefix_lookup(self, ids, allow: bool = True):
        """Per-request prefix-cache policy: longest cached prefix (hit →
        restore + suffix-only prefill) and, when the prompt extends past
        what's cached, an extract range + insert callback so THIS request's
        prefill populates the cache for the next one.  Returns
        ``(prefix, kv_extract, on_prefill_kv)`` — all None when the cache
        is off, the request opted out, or the prompt is shorter than one
        chunk."""
        pc = self.prefix_cache
        if pc is None or not allow:
            return None, None, None
        m = pc.match(ids)
        self.metrics["tpustack_llm_prefix_cache_lookups_total"].labels(
            result="hit" if m.length else "miss").inc()
        self.metrics["tpustack_llm_prefix_cached_tokens"].observe(m.length)
        span = obs_trace.current_span.get()
        if span is not None:  # hit/miss as a span annotation: the trace
            span.add_event("prefix_cache",  # answers "why was THIS prefill
                           result="hit" if m.length else "miss",  # short"
                           cached_tokens=m.length)
        prefix = (m.length, m.kv, m.key) if m.length else None
        upto = pc.snap(len(ids))
        if upto <= m.length:
            return prefix, None, None
        start, ids_copy = m.length, list(ids)

        def on_kv(kv):
            pc.insert(ids_copy, start, kv)
            self.metrics["tpustack_llm_prefix_cache_bytes"].set(pc.bytes)
            self.metrics["tpustack_llm_prefix_cache_entries"].set(pc.entries)

        return prefix, (start, upto), on_kv

    @property
    def engine_chunk(self) -> int:
        """Resolved at engine-construction time so ``self.chunk`` overrides
        (tests tune it for tiny admission cadences) keep taking effect."""
        if self._engine_chunk_override is not None:
            return self._engine_chunk_override
        return max(1, min(self.chunk, 16))

    async def _run_on_device(self, fn, cancel: Optional[threading.Event] = None):
        """Run blocking ``fn`` in the executor under the generation lock, in
        a task INDEPENDENT of the calling handler: if the handler is torn
        down (client disconnect, shutdown), the lock is still held until the
        worker thread actually exits — one generation at a time, always.

        ``cancel`` is set when the awaiting handler dies, so (a) a request
        still QUEUED on the lock is dropped before any device work starts,
        and (b) a running ``fn`` that polls the event (via its on_token
        hook) aborts at the next token instead of generating for nobody."""
        loop = asyncio.get_running_loop()
        started = False

        async def locked():
            nonlocal started
            async with self._lock:
                if cancel is not None and cancel.is_set():
                    raise _Cancelled()  # caller died while we were queued
                started = True
                return await loop.run_in_executor(None, fn)

        task = asyncio.ensure_future(locked())
        # if we get cancelled below, the task runs on detached; swallow its
        # result/exception so it never logs "exception was never retrieved"
        task.add_done_callback(lambda t: t.cancelled() or t.exception())
        try:
            return await asyncio.shield(task)
        except BaseException:
            if cancel is not None:
                cancel.set()
            if not started:
                task.cancel()  # never touched the device — safe to kill
            raise

    # ------------------------------------------------- slot micro-batching
    def _batchable(self) -> bool:
        """All requests batch: per-slot PRNG streams make seeded sampling
        admission-timing independent, and per-slot cache lines give every
        prompt its own full-context budget — the r4 per-request carve-outs
        (seeded sampling, prompts > ctx/2) are gone, so this no longer
        inspects the request.  Solo only when batching is disabled
        outright (``LLM_MAX_BATCH=1``)."""
        return self.max_batch > 1

    async def _enqueue_raw(self, req: _PendingCompletion) -> None:
        # runs in the handler's context: capture the request's root span so
        # the engine thread (no contextvar inheritance) can parent its
        # prefill/wave spans, and open queue_wait — closed by feed() when
        # the request gets a slot
        parent = obs_trace.current_span.get()
        if parent is not None:
            req.span_ctx = parent.context
            req.queue_span = self.tracer.start_span("queue_wait",
                                                    parent=parent)
        req.tenant = obs_accounting.current_tenant.get()
        req.priority = (qos_mod.current_priority.get()
                        if self.qos is not None else None)
        req.t_enqueue = time.time()
        if self._wake is None:
            self._wake = asyncio.Event()
        if self._batch_task is None or self._batch_task.done():
            self._batch_task = asyncio.create_task(self._batch_loop())
        # deque append is atomic — the engine thread polls this queue
        # directly at chunk boundaries (continuous admission), no window
        self._queue.append(req)
        self.metrics["tpustack_llm_queue_depth"].set(len(self._queue))
        self._wake.set()

    def _request_hooks(self, ids, n_predict: int, cache_prompt: bool) -> dict:
        """Per-request KV-cache wiring: the engine's admission (allocation
        IS admission; may raise :class:`OutOfKVBlocks` or ValueError) as
        _PendingCompletion kwargs, or the solo route's host prefix-cache
        lookup as ``generate``/``generate_fused`` kwargs."""
        if self.paged is not None:
            prefix, kv_blocks, on_insert, host_restore = self._paged_admit(
                ids, n_predict, cache_prompt)
            return {"prefix": prefix, "kv_blocks": kv_blocks,
                    "on_prefill_blocks": on_insert,
                    "host_restore": host_restore,
                    # admission IS allocation: KV-block-seconds run from
                    # this wall clock, queued time included
                    "t_kv_alloc": time.time()}
        p, e, cb = self._prefix_lookup(ids, cache_prompt)
        return {"prefix": p, "kv_extract": e, "on_prefill_kv": cb}

    async def _enqueue_completion(self, ids, n_predict, sample, seed=None,
                                  hooks=None, deadline_s=None,
                                  speculative=True):
        loop = asyncio.get_running_loop()
        req = _PendingCompletion(ids, n_predict, sample, loop.create_future(),
                                 seed=seed, speculative=speculative,
                                 **(hooks or {}))
        await self._enqueue_raw(req)
        try:
            return await asyncio.wait_for(req.future, deadline_s)
        except asyncio.TimeoutError:
            # deadline: the cancel event frees the slot at the engine's next
            # chunk boundary (the existing cancelled() poll); report the
            # phase the request died in
            req.cancel.set()
            raise DeadlineExceeded(req.phase) from None
        except asyncio.CancelledError:
            req.cancel.set()  # dropped if still queued; batch notices if all die
            raise

    def _slot_request(self, r: _PendingCompletion, loop):
        """Adapt a parked request into a ContinuousEngine SlotRequest."""
        from tpustack.models.llm_continuous import SlotRequest

        eos = self.tok.eos_id

        def on_tokens(toks):
            if r.stream_put is None:
                return
            for t in toks:  # engine already enforced budget/stop
                if t != eos:
                    r.stream_put(t)

        def on_done(tokens, row_stats):
            self.metrics["tpustack_llm_running_requests"].dec()
            self._paged_gauges()  # the engine freed the slot's blocks
            # before calling us
            if tokens is None:  # admission-time validation failure
                self.metrics["tpustack_llm_requests_rejected_total"].labels(
                    reason="admission").inc()
                exc = ValueError(row_stats.get("error", "bad request"))
                loop.call_soon_threadsafe(
                    lambda: r.future.done() or r.future.set_exception(exc))
            else:
                loop.call_soon_threadsafe(
                    lambda: r.future.done()
                    or r.future.set_result((tokens, row_stats)))
            if r.stream_put is not None:
                r.stream_put(None)  # end-of-stream sentinel

        return SlotRequest(ids=r.ids, max_new=r.n_predict, sample=r.sample,
                           on_tokens=on_tokens, on_done=on_done,
                           cancelled=r.cancel.is_set, seed=r.seed,
                           prefix=r.prefix, span_ctx=r.span_ctx,
                           kv_blocks=r.kv_blocks,
                           on_prefill_blocks=r.on_prefill_blocks,
                           speculative=r.speculative, tenant=r.tenant,
                           t_kv_alloc=r.t_kv_alloc, priority=r.priority,
                           host_restore=r.host_restore,
                           t_enqueue=r.t_enqueue or None,
                           t_handed=r.t_handed)

    # -------------------------------------------------- QoS queue helpers
    def _pop_queued(self) -> "_PendingCompletion":
        """(engine thread) Next queued request by priority: the first
        interactive entry when QoS is on (FIFO within each class), else
        strict FIFO — byte-for-byte the pre-QoS ``popleft`` with the
        policy off.  Index-based scan, not iteration: the event loop
        appends concurrently and deque iteration raises on mutation."""
        if self.qos is not None:
            try:
                for idx in range(len(self._queue)):
                    if self._queue[idx].priority == qos_mod.INTERACTIVE:
                        r = self._queue[idx]
                        del self._queue[idx]
                        return r
            except IndexError:
                pass  # racing an append — fall through to FIFO
        return self._queue.popleft()

    def _interactive_waiting(self) -> bool:
        """(engine thread) The engine's preemption hint: an interactive
        request is waiting in the queue.  Racy by design — a stale answer
        costs one spurious park or one wave of extra wait, never
        correctness."""
        if self._solo_waiting > 0:
            # feed() refuses ALL admissions while a solo request queues
            # on the device lock — a park now could not seat the
            # interactive request, it would only thrash park/resume at
            # every wave boundary until the solo run got its turn
            return False
        try:
            for idx in range(len(self._queue)):
                r = self._queue[idx]
                if r.priority == qos_mod.INTERACTIVE and \
                        not r.cancel.is_set():
                    return True
        except IndexError:
            pass
        return False

    def _note_preempt(self, tenant) -> None:
        """(engine thread) A batch slot was parked for an interactive
        request — count it (the engine already wrote the flight
        record)."""
        self.qos.note_preempt(qos_mod.BATCH)

    async def _batch_loop(self):
        """Run the continuous engine whenever requests are queued: the
        engine holds the device lock for the duration of a busy period,
        admitting new arrivals at chunk boundaries and answering each row
        the moment it finishes; it returns when all slots drain."""
        from tpustack.models.llm_continuous import ContinuousEngine

        loop = asyncio.get_running_loop()
        while True:
            await self._wake.wait()
            self._wake.clear()
            if not self._queue:
                continue

            handed = []

            def work():
                engine = ContinuousEngine(
                    self.gen, slots=self.max_batch,
                    chunk=self.engine_chunk,
                    stop_tokens=(self.tok.eos_id,),
                    on_progress=self.resilience.progress,
                    tracer=self.tracer, paged=self.paged,
                    paged_flash=self.paged_flash,
                    spec=self.spec_cfg, on_spec=self._note_spec,
                    flight=self.flight, ledger=self.ledger,
                    queue_depth=lambda: len(self._queue),
                    # QoS scheduling: the hint tells the engine an
                    # interactive request is waiting (it then parks a
                    # batch slot at the wave boundary); None with QoS
                    # off keeps the engine byte-for-byte preemption-free
                    preempt_hint=(self._interactive_waiting
                                  if self.qos is not None else None),
                    on_preempt=(self._note_preempt
                                if self.qos is not None else None))
                # work() runs on the executor thread WHILE _run_on_device
                # holds self._lock — the guard is real, just lexically
                # invisible to the AST walk
                self._engine = engine  # tpulint: disable=TPL201

                def feed():
                    if self._solo_waiting > 0:
                        # a solo request (seeded / over-long prompt) is
                        # queued on the device lock: stop admitting so the
                        # engine drains and the (FIFO-fair) lock hands over
                        # — sustained batchable traffic must not starve it
                        return None
                    while self._queue:
                        r = self._pop_queued()
                        self.metrics["tpustack_llm_queue_depth"].set(
                            len(self._queue))
                        r.t_handed = time.time()  # THE queue wait's end:
                        # the flight record's queue_s, the row's stat and
                        # the queue_wait phase histogram read this stamp too
                        if r.t_enqueue:  # queue-seconds to the tenant,
                            # cancelled and admitted alike — both waited
                            wait_s = r.t_handed - r.t_enqueue
                            self.ledger.charge_queue_seconds(
                                "llm", r.tenant, wait_s)
                            if self.qos is not None:
                                self.qos.observe_queue_wait(
                                    "llm", r.priority, wait_s)
                        if r.cancel.is_set():
                            if r.queue_span is not None:
                                r.queue_span.set_attribute("cancelled", True)
                                r.queue_span.end(status="error")
                            self._paged_release(r)  # died queued: give the
                            continue  # blocks back; waiter already gone
                        handed.append(r)
                        r.phase = "decode"  # now owns a slot (504 phase)
                        if r.queue_span is not None:
                            r.queue_span.end()
                        self.metrics["tpustack_llm_running_requests"].inc()
                        return self._slot_request(r, loop)
                    return None

                return engine.run(feed)

            def fail(exc):
                # a failed engine run must strand neither its admitted
                # waiters (handed, futures not yet resolved) nor the queue
                while self._queue:
                    handed.append(self._queue.popleft())
                for r in handed:
                    if r.queue_span is not None:
                        r.queue_span.end(status="error")  # idempotent
                    # still-queued requests hold pool references the engine
                    # never saw (phase gate makes this a no-op for rows the
                    # engine's own failure path already released)
                    self._paged_release(r)
                    if not r.future.done():
                        r.future.set_exception(exc)
                    if r.stream_put is not None:
                        r.stream_put(None)

            try:
                stats = await self._run_on_device(work)
            except asyncio.CancelledError:
                fail(RuntimeError("server shutting down"))
                raise
            except Exception as e:
                fail(e)
                continue
            finally:
                # the run is over, nothing is decoding — self-heal the gauge
                # even when the engine died mid-run (on_done never fired for
                # some handed rows)
                self.metrics["tpustack_llm_running_requests"].set(0)
                if self._queue:
                    # engine yielded with work left (solo preemption):
                    # re-enter after the lock's FIFO queue services it
                    self._wake.set()
            self._sanitize_quiesce()
            if stats.get("prefill_chunks"):
                self.metrics["tpustack_llm_prefill_chunks_total"].inc(
                    stats["prefill_chunks"])
            if stats["requests"]:
                self.metrics["tpustack_llm_batch_occupancy_slots"].observe(
                    stats["requests"])
                log.info("continuous run: %d requests, %d gen tok, "
                         "%.1f tok/s aggregate", stats["requests"],
                         stats["generated_tokens"], stats["tokens_per_s"])

    def _sanitize_quiesce(self) -> None:
        """Runtime-sanitizer KV accounting at engine drain (no-op unless
        TPUSTACK_SANITIZE): with nothing queued and no open work request
        (a TRUE quiesce — a stream handler between paged admission and
        enqueue legitimately holds unaccounted blocks), every used pool
        block must belong to the prefix cache at refcount exactly 1.
        Anything else is a leaked slot reference: capacity gone until
        restart."""
        if (not sanitize.enabled() or self._queue
                or self.resilience._inflight or self._solo_waiting):
            return
        sanitize.check_kv_quiesce(self.paged, where="llm engine drain")

    async def _complete_routed(self, prompt: str, n_predict: int,
                               temperature: float, top_k: int, seed,
                               cache_prompt: bool = True, deadline_s=None,
                               speculative: bool = True):
        """(content, stats, stopped_eos) via the micro-batcher when eligible,
        else the solo device path.  Raises ValueError for bad requests and
        DeadlineExceeded past ``deadline_s``."""
        from tpustack.models.llm_generate import SampleConfig

        ids = self.tok.encode(prompt)
        if not ids:  # reject here, not inside a batch where peers would 400
            self.metrics["tpustack_llm_requests_rejected_total"].labels(
                reason="empty_prompt").inc()
            raise ValueError("empty prompt")
        hooks = self._request_hooks(ids, n_predict, cache_prompt)
        prefix_hooks = (hooks.get("prefix"), hooks.get("kv_extract"),
                        hooks.get("on_prefill_kv"))
        t_start = time.perf_counter()
        if not self._batchable():
            cancel = threading.Event()
            started = {"v": False}  # device work began (vs queued on lock)

            def solo_fn():
                started["v"] = True
                return self._solo_complete(ids, n_predict, temperature,
                                           top_k, seed, cancel, prefix_hooks)

            self._solo_waiting += 1  # engine yields the lock at its next
            try:                     # chunk boundary (FIFO-fair handover)
                content, stats, stopped_eos = await asyncio.wait_for(
                    self._run_on_device(solo_fn, cancel), deadline_s)
            except asyncio.TimeoutError:
                # wait_for already cancelled the awaiting task, which set
                # ``cancel`` (via _run_on_device's teardown path) so the
                # worker stops at its next chunk and the device lock frees
                raise DeadlineExceeded(
                    "decode" if started["v"] else "queued") from None
            finally:
                self._solo_waiting -= 1
            self._observe_done(len(ids), stats, time.perf_counter() - t_start)
            return content, stats, stopped_eos
        sample = SampleConfig(temperature=temperature, top_k=top_k,
                              greedy=temperature <= 0)
        out_ids, stats = await self._enqueue_completion(
            ids, n_predict, sample, seed=seed, hooks=hooks,
            deadline_s=deadline_s, speculative=speculative)
        if out_ids and out_ids[-1] == self.tok.eos_id:
            out_ids = out_ids[:-1]
            stopped_eos = True
        else:
            stopped_eos = False
        # the continuous engine reports true PER-ROW stats (each row has its
        # own admit→retire wall time and token counts) — no shared-batch
        # reconstruction needed
        stats = dict(stats)
        t_detok = time.perf_counter()
        with self.tracer.span_if_active("detokenize"):
            content = self.tok.decode(out_ids)
        stats["detokenize_s"] = time.perf_counter() - t_detok
        self._observe_done(len(ids), stats, time.perf_counter() - t_start)
        return content, stats, stopped_eos

    # ------------------------------------------------------------ helpers
    def _observe_done(self, n_prompt: int, stats: dict, total_s: float) -> None:
        """Fold one finished completion into the metric families: token
        counters, prompt-length histogram, and the phase breakdown
        (queue_wait is the engine's own ``queue_s``, enqueue -> handed out
        by feed(); a solo completion has no queue, so there it is the wall
        time its device phases don't account for — the wait for the device
        lock, event-loop overhead)."""
        from tpustack.obs import Trace

        m = self.metrics
        m["tpustack_llm_prompt_tokens_total"].inc(stats.get("prompt_tokens", 0))
        m["tpustack_llm_generated_tokens_total"].inc(
            stats.get("generated_tokens", 0))
        m["tpustack_llm_prompt_length_tokens"].observe(n_prompt)
        # tenant token accounting: _observe_done runs in the handler's
        # context (solo, batched, and streamed paths alike), so the
        # middleware's contextvar is live here — ONE charge point per
        # completed request
        self.ledger.charge_tokens(
            "llm", obs_accounting.current_tenant.get(),
            prompt=stats.get("prompt_tokens", 0),
            generated=stats.get("generated_tokens", 0))
        prefill = stats.get("prefill_s", 0.0)
        decode = stats.get("decode_s", 0.0)
        detok = stats.get("detokenize_s", 0.0)
        queue = stats.get("queue_s")
        if queue is None:
            queue = max(0.0, total_s - prefill - decode - detok)
        tr = Trace()
        tr.add("queue_wait", queue)
        tr.add("prefill", prefill)
        tr.add("decode", decode)
        tr.add("detokenize", detok)
        tr.observe_into(m["tpustack_request_phase_latency_seconds"],
                        server="llm")

    def _final_payload(self, stats, stopped_eos: bool, content: str) -> dict:
        """llama.cpp-shaped result body, shared by the non-streamed response
        and the terminal SSE event so the two can never drift apart."""
        return {
            "content": content,
            "model": self.model_name,
            "stop": True,
            "stopped_eos": stopped_eos,
            "stopped_limit": not stopped_eos,
            "tokens_evaluated": stats["prompt_tokens"],
            "tokens_predicted": stats["generated_tokens"],
            "timings": {
                "prompt_n": stats["prompt_tokens"],
                "prompt_ms": stats["prefill_s"] * 1e3,
                "predicted_n": stats["generated_tokens"],
                "predicted_ms": stats["decode_s"] * 1e3,
                "predicted_per_second": stats["tokens_per_s"],
            },
        }

    def _solo_complete(self, ids, n_predict, temperature, top_k, seed,
                       cancel, prefix_hooks):
        """Solo worker (executor thread): report the dispatch progress point
        (watchdog beat + fault hooks) then run the fused solo path."""
        self.resilience.progress("prefill")
        try:
            return self._complete(ids, n_predict, temperature, top_k,
                                  seed, False, cancel, prefix_hooks)
        finally:
            self.resilience.progress("wave")

    def _complete(self, ids, n_predict: int, temperature: float,
                  top_k: int, seed: Optional[int], greedy: bool,
                  cancel: Optional[threading.Event] = None,
                  prefix_hooks=(None, None, None)):
        """Non-streaming solo path: fused scan decode (chunk of tokens per
        device dispatch — the throughput path; a dead client is noticed
        between chunks).  Output matches the streaming per-token path
        token-for-token (same split chain, tested).  Takes pre-encoded ids
        (the router already tokenised to decide batchability)."""
        from tpustack.models.llm_generate import SampleConfig

        def chunk_check():
            # polled once per fused chunk: a long-but-healthy solo run must
            # keep beating the watchdog (the batched engine beats per wave)
            self.resilience.beat()
            return False if cancel is None else cancel.is_set()

        out_ids, stats = self.gen.generate_fused(
            ids, max_new_tokens=n_predict,
            sample=SampleConfig(temperature=temperature, top_k=top_k,
                                greedy=greedy or temperature <= 0),
            seed=seed, stop_tokens=(self.tok.eos_id,),
            chunk=self.chunk,
            cancel_check=chunk_check,
            prefix=prefix_hooks[0], kv_extract=prefix_hooks[1],
            on_prefill_kv=prefix_hooks[2])
        if out_ids and out_ids[-1] == self.tok.eos_id:
            out_ids = out_ids[:-1]
            stopped_eos = True
        else:
            stopped_eos = False
        t_detok = time.perf_counter()
        content = self.tok.decode(out_ids)
        stats = dict(stats)
        stats["detokenize_s"] = time.perf_counter() - t_detok
        return content, stats, stopped_eos

    async def _stream(self, request: web.Request, prompt: str, n_predict: int,
                      temperature: float, top_k: int, seed, fmt: str,
                      cache_prompt: bool = True, deadline_s=None,
                      speculative: bool = True):
        """SSE streaming shared by /completion (llama.cpp chunk shape) and
        /v1/chat/completions (OpenAI ``chat.completion.chunk`` + ``[DONE]``).

        The blocking generate loop runs in the executor; its ``on_token``
        callback feeds an asyncio queue.  Text deltas are computed by decoding
        the accumulated ids and emitting the suffix, so multi-byte/BPE pieces
        never split mid-character.
        """
        from tpustack.models.llm_generate import SampleConfig

        ids = self.tok.encode(prompt)
        if len(ids) >= self.gen.cfg.max_seq:  # fail as JSON before SSE starts
            msg = f"prompt ({len(ids)}) exceeds ctx {self.gen.cfg.max_seq}"
            if fmt == "openai":
                return web.json_response({"error": {"message": msg}}, status=400)
            return web.json_response({"error": msg}, status=400)
        try:
            # paged admission allocates HERE — any 429/400 must go out as
            # JSON with real status codes, before the SSE headers flush
            hooks = self._request_hooks(ids, n_predict, cache_prompt)
        except OutOfKVBlocks as e:
            payload = ({"error": {"message": str(e)}} if fmt == "openai"
                       else {"error": str(e)})
            return web.json_response(
                payload, status=429,
                headers=shed_headers("out_of_kv_blocks", e.retry_after_s))
        except ValueError as e:
            payload = ({"error": {"message": str(e)}} if fmt == "openai"
                       else {"error": str(e)})
            return web.json_response(payload, status=400)

        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        prefix_hooks = (hooks.get("prefix"), hooks.get("kv_extract"),
                        hooks.get("on_prefill_kv"))
        batched = self._batchable()
        if batched:
            # concurrent streams coalesce into ONE batched decode; tokens
            # arrive per fused chunk (coarser cadence than the solo path's
            # per-token hook, but N streams share each weight pass).  Built
            # BEFORE the SSE headers flush: the request object is what owns
            # the paged admission's pool references until it is enqueued.
            req = _PendingCompletion(
                ids, n_predict,
                SampleConfig(temperature=temperature, top_k=top_k,
                             greedy=temperature <= 0),
                loop.create_future(),
                stream_put=lambda t: loop.call_soon_threadsafe(q.put_nowait, t),
                seed=seed, speculative=speculative, **hooks)
            cancel = req.cancel

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            # the obs middleware's post-handler setdefault is too late for a
            # prepared StreamResponse — stamp the rid before headers flush
            "X-Request-Id": request.get("request_id", "-"),
        })
        try:
            await resp.prepare(request)
        except BaseException:
            # client died before the stream existed (prepare raised, or the
            # handler task was cancelled at this await): the request was
            # never enqueued, so nothing downstream will ever release its
            # paged admission blocks — do it here or they leak forever
            if batched:
                self._paged_release(req)
            raise

        async def send(payload) -> None:
            # bounded write: a stalled-but-connected reader (TCP zero window)
            # must not wedge this handler forever
            await asyncio.wait_for(
                resp.write(b"data: " + json.dumps(payload).encode() + b"\n\n"),
                timeout=60)

        if not batched:
            cancel = threading.Event()

            def on_token(t):
                self.resilience.beat()  # per-token progress (solo stream)
                loop.call_soon_threadsafe(q.put_nowait, t)
                if cancel.is_set():
                    raise _Cancelled()  # aborts generate in the worker thread

            def worker():
                try:
                    if cancel.is_set():  # client died while we were queued:
                        raise _Cancelled()  # skip the whole prefill
                    self.resilience.progress("prefill")
                    return self.gen.generate(
                        ids, max_new_tokens=n_predict,
                        sample=SampleConfig(temperature=temperature,
                                            top_k=top_k,
                                            greedy=temperature <= 0),
                        seed=seed, stop_tokens=(self.tok.eos_id,),
                        on_token=on_token,
                        prefix=prefix_hooks[0], kv_extract=prefix_hooks[1],
                        on_prefill_kv=prefix_hooks[2])
                finally:
                    loop.call_soon_threadsafe(q.put_nowait, None)  # EOS

        chat_id = f"chatcmpl-{uuid.uuid4().hex[:12]}"
        created = int(time.time())

        def chat_chunk(delta, finish=None):
            return {"id": chat_id, "object": "chat.completion.chunk",
                    "created": created, "model": self.model_name,
                    "choices": [{"index": 0, "delta": delta,
                                 "finish_reason": finish}]}

        # incremental detokenisation (the vLLM/TGI sliding-window recipe):
        # decode a window that keeps a few tokens of context so BPE/
        # sentencepiece spacing renders as it would in the full text, and
        # hold back while the window ends in U+FFFD (incomplete multi-byte)
        gen_ids = []
        prefix_off = read_off = 0

        def next_delta() -> str:
            nonlocal prefix_off, read_off
            prev = self.tok.decode(gen_ids[prefix_off:read_off])
            text = self.tok.decode(gen_ids[prefix_off:])
            if len(text) <= len(prev):
                return ""
            if text.endswith("�"):
                # hold back a trailing U+FFFD (incomplete multi-byte) —
                # unless the window has stalled so long (genuinely invalid
                # byte stream) that holding would grow it unboundedly
                if len(gen_ids) - read_off <= 16:
                    return ""
                # forced flush: the U+FFFD is emitted, so drop the pending
                # bytes from future windows entirely — keeping them as
                # context would let a later token re-render them and make
                # the next delta's prefix arithmetic drop GOOD characters
                prefix_off = read_off = len(gen_ids)
                return text[len(prev):]
            prefix_off = max(read_off - 4, 0)
            read_off = len(gen_ids)
            return text[len(prev):]

        t0 = time.time()

        if batched:
            await self._enqueue_raw(req)
            locked_task = req.future
            # mirror the solo task's guard: if the handler dies before
            # awaiting (client disconnect) a later batch failure must not
            # log "exception was never retrieved"
            locked_task.add_done_callback(
                lambda f: f.cancelled() or f.exception())
        else:
            self._solo_waiting += 1  # released when the solo run finishes
            locked_task = asyncio.ensure_future(
                self._run_on_device(worker, cancel))
            locked_task.add_done_callback(
                lambda t: t.cancelled() or t.exception())
            locked_task.add_done_callback(
                lambda t: setattr(self, "_solo_waiting",
                                  self._solo_waiting - 1))
        t_deadline = (loop.time() + deadline_s) if deadline_s else None
        try:
            if fmt == "openai":
                await send(chat_chunk({"role": "assistant", "content": ""}))
            while True:
                if t_deadline is None:
                    tok = await q.get()
                else:
                    # per-request deadline mid-stream: a 504 status is no
                    # longer possible (headers flushed), so the timeout
                    # surfaces as a terminal error event below.  Converted
                    # HERE so send()'s own 60s stalled-reader write timeout
                    # keeps falling through to the cancel-and-raise path
                    # instead of masquerading as a deadline
                    try:
                        tok = await asyncio.wait_for(
                            q.get(), max(t_deadline - loop.time(), 0.001))
                    except asyncio.TimeoutError:
                        # batched requests track queued-vs-decode; the solo
                        # worker starts immediately, so it is decoding
                        raise DeadlineExceeded(
                            req.phase if batched else "decode") from None
                if tok is None:
                    break
                if tok == self.tok.eos_id:
                    continue
                gen_ids.append(tok)
                delta = next_delta()
                if not delta:
                    continue
                if fmt == "openai":
                    await send(chat_chunk({"content": delta}))
                else:
                    await send({"content": delta, "stop": False})
            try:
                out_ids, stats = await locked_task
            except (ValueError, InjectedDeviceError) as e:
                # stream already started: surface the error as a final event
                # (the 200 headers flushed long ago — tell the tenant
                # outcome accounting what actually happened)
                request["tenant_outcome"] = "error"
                if fmt == "openai":
                    await send(chat_chunk({}, finish="error") | {
                        "error": {"message": str(e)}})
                else:
                    await send({"content": "", "stop": True, "error": str(e)})
                await resp.write_eof()
                return resp
        except DeadlineExceeded as e:
            # the cancel event frees the engine slot at the next chunk
            cancel.set()
            self.resilience.note_deadline(e.phase)
            # the SSE response stays HTTP 200 (headers long flushed) —
            # override so the tenant goodput accounting records the
            # deadline instead of a phantom success
            request["tenant_outcome"] = "deadline"
            msg = str(e)
            if fmt == "openai":
                await send(chat_chunk({}, finish="error") | {
                    "error": {"message": msg}})
            else:
                await send({"content": "", "stop": True, "error": msg})
            await resp.write_eof()
            return resp
        except BaseException:
            # client gone / write timed out / handler cancelled: tell the
            # worker to stop at its next token; _run_on_device keeps holding
            # the lock until the worker actually exits, so the device stays
            # accounted for without any orphan bookkeeping here
            cancel.set()
            raise

        # flush anything held back (trailing bytes that never completed)
        tail = self.tok.decode(gen_ids[prefix_off:])[
            len(self.tok.decode(gen_ids[prefix_off:read_off])):]
        if tail:
            if fmt == "openai":
                await send(chat_chunk({"content": tail}))
            else:
                await send({"content": tail, "stop": False})

        self._observe_done(len(ids), stats, time.time() - t0)
        stopped_eos = bool(out_ids) and out_ids[-1] == self.tok.eos_id
        if fmt == "openai":
            await send(chat_chunk({}, finish="stop" if stopped_eos else "length"))
            await resp.write(b"data: [DONE]\n\n")
        else:
            await send(self._final_payload(stats, stopped_eos, content=""))
        log.info("stream %s: %d prompt tok, %d gen tok, %.2fs", fmt,
                 stats["prompt_tokens"], stats["generated_tokens"],
                 time.time() - t0)
        await resp.write_eof()
        return resp

    # ----------------------------------------------------------- handlers
    async def health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    async def healthz(self, request: web.Request) -> web.Response:
        """Liveness + engine state: 503 only when the watchdog declared a
        hung dispatch (kubernetes then restarts the pod).  Draining pods
        stay live — they are finishing in-flight work on purpose."""
        status, payload = self.resilience.health_payload(extra={"engine": {
            "model": self.model_name,
            "slots": self.max_batch,
            "chunk": self.engine_chunk,
            "queue_depth": len(self._queue),
            "solo_waiting": self._solo_waiting,
            "prefix_cache": (self.prefix_cache is not None
                             or (self.paged is not None
                                 and self.paged.cache is not None)),
            "paged_kv": self.paged is not None,
        }})
        return web.json_response(payload, status=status,
                                 headers=self.resilience.health_headers(status))

    async def readyz(self, request: web.Request) -> web.Response:
        """Readiness: 503 from the moment drain begins, so the endpoint
        leaves Service rotation while in-flight completions finish."""
        status, payload = self.resilience.ready_payload()
        return web.json_response(payload, status=status,
                                 headers=self.resilience.ready_headers(status))

    async def admin_drain(self, request: web.Request) -> web.Response:
        """Authenticated reversible drain (``POST /admin/drain``).

        The autoscaler's scale-down choreography calls this FIRST: the
        flip makes ``/readyz`` 503 with ``X-Shed-Reason: draining``, the
        router ejects the replica authoritatively within one health tick,
        in-flight work finishes, and only then is the process signalled.
        Body ``{"undrain": true}`` reverses it (an operator aborting a
        scale-down, or a drill restoring the fleet).

        Auth: ``X-Admin-Token`` must equal ``TPUSTACK_ADMIN_TOKEN``; an
        empty knob disables the surface (403 always) so an unconfigured
        replica exposes no unauthenticated drain lever."""
        expected = knobs.get_str("TPUSTACK_ADMIN_TOKEN")
        presented = request.headers.get("X-Admin-Token", "")
        if not expected or not hmac.compare_digest(presented, expected):
            self._reject("admin_forbidden")
            return web.json_response(
                {"error": "forbidden", "detail": "missing or bad "
                 "X-Admin-Token (or TPUSTACK_ADMIN_TOKEN unset)"},
                status=403)
        try:
            body = await request.json()
        except Exception as exc:
            # an empty/absent body is a plain drain request
            log.debug("admin drain: unparseable body treated as {}: %s", exc)
            body = {}
        undrain = bool(isinstance(body, dict) and body.get("undrain"))
        if undrain:
            changed = self.resilience.admin_undrain()
        else:
            changed = self.resilience.admin_drain()
        status, ready = self.resilience.ready_payload()
        return web.json_response({
            "ok": True,
            "action": "undrain" if undrain else "drain",
            "changed": changed,
            "draining": self.resilience.draining,
            "state": self.resilience.state_name,
            "readyz_status": status,
            "inflight": self.resilience.inflight,
        })

    async def props(self, request: web.Request) -> web.Response:
        """Server properties + live KV-cache config/stats, so operators can
        verify the serving substrate (paged pool size/block/utilization,
        prefix-cache hit rate, dense-fallback flag) without scraping
        ``/metrics``."""
        from tpustack.utils import device_info

        pc = self.prefix_cache
        payload = {
            "model": self.model_name,
            "n_ctx": self.gen.cfg.max_seq,
            # what jax.devices()[0] reports — never a literal
            "backend": device_info(),
            "prefix_cache": pc.stats() if pc is not None
            else {"enabled": False},
        }
        if self.paged is not None:
            rt = self.paged
            payload["paged_kv"] = dict(
                rt.stats(), enabled=True, dense_fallback=False,
                # which decode-attention body the engines run (the
                # TPUSTACK_PAGED_FLASH verdict resolved at boot)
                kernel=("paged_flash" if self.paged_flash else "gather"))
            payload["prefix_cache"] = (rt.cache.stats()
                                       if rt.cache is not None
                                       else {"enabled": False})
        else:
            payload["paged_kv"] = {"enabled": False, "dense_fallback": True}
        payload["mesh"] = self._mesh_props()
        sc = self.spec_cfg
        enabled = sc is not None and self._batchable()
        payload["speculative"] = {
            "enabled": enabled,
            "tokens": sc.tokens if enabled else 0,
            "drafter": ((type(sc.drafter).__name__ if sc.drafter is not None
                         else "prompt_lookup") if enabled else None),
            "drafted_tokens": self._spec_drafted,
            "accepted_tokens": self._spec_accepted,
            "acceptance_ratio": (self._spec_accepted / self._spec_drafted
                                 if self._spec_drafted else 0.0),
        }
        return web.json_response(payload)

    def _reject(self, reason: str) -> None:
        self.metrics["tpustack_llm_requests_rejected_total"].labels(
            reason=reason).inc()

    async def profile(self, request: web.Request) -> web.Response:
        """Capture an XLA/TPU profile (xplane) of whatever the engine is
        serving for the next ``seconds`` (body ``{"seconds": n}``, default
        3, at most 60).  No device lock and no request of its own: the
        capture is the live traffic — the engine thread's ``engine/<phase>``
        host events beside the device's programs and kernels, on one
        clock.  One capture at a time (409 while another runs)."""
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            body = {}
        if body is None:
            body = {}
        seconds = body.get("seconds", 3) if isinstance(body, dict) else None
        if isinstance(seconds, bool) or not isinstance(seconds, (int, float)) \
                or not 0 < seconds <= 60:
            return web.json_response(
                {"detail": "body must be a JSON object; seconds a number "
                           "in (0, 60]"}, status=422)
        if self._profiling:
            return web.json_response(
                {"detail": "a capture is already running"}, status=409)
        self._profiling = True
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, lambda: obs_profile.capture(
                    obs_profile.base_dir("llm"),
                    lambda: time.sleep(seconds)))
        finally:
            self._profiling = False
        return web.json_response(dict(out, seconds=seconds))

    async def completion(self, request: web.Request) -> web.Response:
        try:
            body = await obs_http.request_json(request)
        except json.JSONDecodeError:
            self._reject("invalid_json")
            return web.json_response({"error": "invalid json"}, status=400)
        prompt = body.get("prompt", "")
        if not isinstance(prompt, str) or not prompt:
            self._reject("empty_prompt")
            return web.json_response({"error": "prompt is required"}, status=400)
        try:  # explicit None checks — 0 is a meaningful value (greedy temp)
            n_predict = int(_or_default(body.get("n_predict"), 128))
            temperature = float(_or_default(body.get("temperature"), 0.8))
            top_k = int(_or_default(body.get("top_k"), 40))
            deadline_s = self.resilience.deadline(body.get("timeout_s"))
            seed = _normalize_seed(body.get("seed"))
        except (TypeError, ValueError) as e:
            self._reject("bad_parameter")
            return web.json_response({"error": f"invalid parameter: {e}"}, status=400)
        if n_predict < 0:  # llama.cpp: -1 means "until EOS / context limit"
            n_predict = self.gen.cfg.max_seq
        # llama.cpp's prompt-cache field: absent/true → use the prefix KV
        # cache (when server-enabled); explicit false → this request neither
        # reuses nor populates it
        cache_prompt = bool(_or_default(body.get("cache_prompt"), True))
        # per-request speculation opt-out (greedy outputs identical either
        # way; a debugging/bisection knob, mirroring cache_prompt)
        speculative = bool(_or_default(body.get("speculative"), True))
        if body.get("stream"):
            return await self._stream(request, prompt, n_predict, temperature,
                                      top_k, seed, fmt="llamacpp",
                                      cache_prompt=cache_prompt,
                                      deadline_s=deadline_s,
                                      speculative=speculative)

        t0 = time.time()
        try:
            content, stats, stopped_eos = await self._complete_routed(
                prompt, n_predict, temperature, top_k, seed,
                cache_prompt=cache_prompt, deadline_s=deadline_s,
                speculative=speculative)
        except ValueError as e:  # e.g. prompt longer than the context window
            return web.json_response({"error": str(e)}, status=400)
        except OutOfKVBlocks as e:
            return web.json_response(
                {"error": str(e)}, status=429,
                headers=shed_headers("out_of_kv_blocks", e.retry_after_s))
        except DeadlineExceeded as e:
            self.resilience.note_deadline(e.phase)
            return web.json_response({"error": str(e), "phase": e.phase},
                                     status=504,
                                     headers=shed_headers("deadline"))
        except InjectedDeviceError as e:
            return self.resilience.transient_error_response(e)
        log.info("completion: %d prompt tok, %d gen tok, %.2fs",
                 stats["prompt_tokens"], stats["generated_tokens"], time.time() - t0)
        return web.json_response(self._final_payload(stats, stopped_eos, content))

    async def tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        ids = self.tok.encode(str(body.get("content", "")), add_bos=False)
        return web.json_response({"tokens": ids})

    async def detokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        return web.json_response({"content": self.tok.decode(body.get("tokens", []))})

    async def chat_completions(self, request: web.Request) -> web.Response:
        try:
            body = await obs_http.request_json(request)
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid json"}, status=400)
        messages = body.get("messages", [])
        if not messages:
            return web.json_response(
                {"error": {"message": "messages required"}}, status=400)
        # simple generic chat template (no model-specific tokens baked in)
        parts = [f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages]
        prompt = "\n".join(parts) + "\nassistant:"
        try:
            n_predict = int(_or_default(body.get("max_tokens"), 128))
            temperature = float(_or_default(body.get("temperature"), 0.8))
            deadline_s = self.resilience.deadline(body.get("timeout_s"))
            seed = _normalize_seed(body.get("seed"))
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": f"invalid parameter: {e}"}}, status=400)
        cache_prompt = bool(_or_default(body.get("cache_prompt"), True))
        speculative = bool(_or_default(body.get("speculative"), True))
        if body.get("stream"):
            return await self._stream(request, prompt, n_predict, temperature,
                                      40, seed,
                                      fmt="openai", cache_prompt=cache_prompt,
                                      deadline_s=deadline_s,
                                      speculative=speculative)

        try:
            content, stats, stopped_eos = await self._complete_routed(
                prompt, n_predict, temperature, 40, seed,
                cache_prompt=cache_prompt, deadline_s=deadline_s,
                speculative=speculative)
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        except OutOfKVBlocks as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=429,
                headers=shed_headers("out_of_kv_blocks", e.retry_after_s))
        except DeadlineExceeded as e:
            self.resilience.note_deadline(e.phase)
            return web.json_response(
                {"error": {"message": str(e)}, "phase": e.phase}, status=504,
                headers=shed_headers("deadline"))
        except InjectedDeviceError as e:
            return self.resilience.transient_error_response(e)
        return web.json_response({
            "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [{
                "index": 0,
                "message": {"role": "assistant", "content": content},
                "finish_reason": "stop" if stopped_eos else "length",
            }],
            "usage": {
                "prompt_tokens": stats["prompt_tokens"],
                "completion_tokens": stats["generated_tokens"],
                "total_tokens": stats["prompt_tokens"] + stats["generated_tokens"],
            },
        })

    def build_app(self) -> web.Application:
        work = {"/completion", "/v1/chat/completions"}
        app = web.Application(
            middlewares=[obs_http.instrument("llm", self._registry,
                                             tracer=self.tracer,
                                             ledger=self.ledger,
                                             work_endpoints=work),
                         self.resilience.middleware(work)])
        obs_http.add_debug_trace_routes(app, self.tracer)
        obs_http.add_debug_flight_routes(app, self.flight)
        obs_http.add_debug_tenant_routes(app, self.ledger, qos=self.qos,
                                         kvprof=self.kvprof)
        obs_http.add_debug_kvcache_routes(app, self.kvprof)
        app.router.add_get("/health", self.health)
        app.router.add_get("/healthz", self.healthz)
        app.router.add_get("/readyz", self.readyz)
        app.router.add_get("/props", self.props)
        app.router.add_get("/metrics",
                           obs_http.make_metrics_handler(self._registry))
        app.router.add_post("/profile", self.profile)
        # deliberately NOT in the work set: the drain lever must keep
        # working while admission is shedding (that is its whole point)
        app.router.add_post("/admin/drain", self.admin_drain)
        app.router.add_post("/completion", self.completion)
        app.router.add_post("/tokenize", self.tokenize)
        app.router.add_post("/detokenize", self.detokenize)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        return app


def main() -> None:
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR or <repo>/.cache/xla
    port = int(os.environ.get("PORT", "8080"))
    server = LLMServer()
    # our SIGTERM handler drains (readiness 503, in-flight work finishes,
    # exit 0); handle_signals=False keeps aiohttp's own immediate-stop
    # SIGTERM handler from racing it
    server.resilience.install_signal_handlers()
    web.run_app(server.build_app(), port=port, access_log=None,
                handle_signals=False)


if __name__ == "__main__":
    main()
