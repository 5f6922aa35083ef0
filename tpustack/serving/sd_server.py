"""SD1.5 REST API server — TPU-native port of the reference sd15-api app.

Byte-compatible with the reference's FastAPI app (reference
``cluster-config/apps/sd15-api/configmap.yaml:16-121``) so
``scripts/batch_generate.py`` works unchanged:

- ``GET /healthz``  → ``{"ok": true}``                (configmap.yaml:60-62)
- ``GET /``         → HTML preview of the last image  (configmap.yaml:64-78)
- ``GET /last``     → last PNG or 404                 (configmap.yaml:80-84)
- ``POST /generate``→ PNG + ``X-Gen-Time: <sec>s``    (configmap.yaml:86-121)
  body {prompt, steps=30, guidance_scale=7.5, seed, width=512, height=512};
  400 on missing/empty prompt.

Implementation differences, all TPU-motivated: aiohttp instead of
FastAPI/uvicorn (no ASGI dependency in the base image), the model is this
package's jitted JAX pipeline instead of torch/diffusers, and there is no
autocast/attention-slicing/VAE-offload — bf16 and 16 GB HBM make them moot
(cf. configmap.yaml:42-45).  Batch DISPATCH is serialised with a lock (cf.
the reference's ``_LAST_LOCK``, configmap.yaml:38-39) so program order stays
deterministic, but the device→host image transfer happens outside it: batch
k+1's compute overlaps batch k's transfer (JAX async dispatch — measured
+32% steady-state throughput, docs/PERF.md).  ``/profile`` drains in-flight
batches before tracing so captures stay clean.  Concurrent requests with
the same (steps, guidance, size) signature are **micro-batched** into one
fused program — and, with ``SD15_DP=N``, data-parallel across the pod's N
chips via GSPMD (the reference's only scale story was one-GPU-per-pod).

Env flags (mirroring the reference's env contract, deployment.yaml:43-53):
``MODEL_DIR`` (diffusers safetensors snapshot; random weights if unset),
``SD15_PRESET`` (``sd15``|``tiny``), ``PORT``, ``SD15_TOKENIZER_DIR``,
``SD15_DP`` (dp mesh size), ``SD15_BATCH_WINDOW_MS`` (batch collection
window, default 15), ``SD15_MAX_BATCH`` (default dp×fsdp or 1), plus the
shared resilience contract (``tpustack.serving.resilience``):
``TPUSTACK_DRAIN_TIMEOUT_S``, ``TPUSTACK_REQUEST_TIMEOUT_S`` (per-request
body override ``timeout_s``), ``TPUSTACK_MAX_QUEUE_DEPTH``,
``TPUSTACK_WATCHDOG_S`` and the ``TPUSTACK_FAULT_*`` injection knobs.
``GET /readyz`` is the readiness endpoint (503 while draining);
``/healthz`` stays the liveness endpoint and now reports drain/watchdog
state alongside the reference's ``ok`` field.
"""

from __future__ import annotations

import asyncio
import base64
import dataclasses
import os
import time
from typing import Dict, Optional

import numpy as np
from aiohttp import web
from pydantic import BaseModel, ValidationError

from tpustack import runtime, sanitize
from tpustack.obs import accounting as obs_accounting
from tpustack.obs import catalog as obs_catalog
from tpustack.obs import device as obs_device
from tpustack.obs import flight as obs_flight
from tpustack.obs import http as obs_http
from tpustack.obs import profile as obs_profile
from tpustack.obs import trace as obs_trace
from tpustack.serving.resilience import (DeadlineExceeded,
                                         InjectedDeviceError,
                                         ResilienceManager, shed_headers)
from tpustack.utils import get_logger
from tpustack.utils.image import array_to_png

log = get_logger("serving.sd_server")


class GenReq(BaseModel):
    """Request schema — field-for-field the reference's GenReq
    (configmap.yaml:52-58), plus negative_prompt as a superset."""

    prompt: str
    steps: Optional[int] = 30
    guidance_scale: Optional[float] = 7.5
    seed: Optional[int] = None
    width: Optional[int] = 512
    height: Optional[int] = 512
    negative_prompt: Optional[str] = ""
    # per-request deadline override (seconds); None → the server default
    # TPUSTACK_REQUEST_TIMEOUT_S, 0 disables for this request
    timeout_s: Optional[float] = None


@dataclasses.dataclass
class _PendingReq:
    prompt: str
    negative: str
    seed: Optional[int]
    future: asyncio.Future
    t_enqueue: float = 0.0  # perf_counter at admission → queue_wait phase
    # distributed tracing: the request's HTTP root-span context (the batch
    # task serves many requests, so each rider's spans are written from the
    # shared batch timings against its own parent) + admission wall clock
    span_ctx: Optional[object] = None
    t_enqueue_unix: float = 0.0
    # tenant cost accounting: resolved by the obs middleware, carried
    # explicitly — the batch task serves many riders, each charged its
    # share of the fused dispatch
    tenant: Optional[str] = None
    # QoS priority class (resolved by the resilience middleware into the
    # current_priority contextvar), carried across the window/batch
    # boundary for the per-priority queue-wait recording
    priority: Optional[str] = None


class SDServer:
    def __init__(self, pipeline=None, mesh=None, batch_window_ms: float = None,
                 max_batch: int = None, registry=None, tracer=None):
        self._registry = registry
        self.metrics = obs_catalog.build(registry)
        obs_device.install(registry)
        # committed perf baselines as info gauges (which bench bar this
        # server build is held to — tools/perf_gate.py, obs.perfsig)
        from tpustack.obs import perfsig

        perfsig.export_baseline_gauges(registry)
        self.tracer = tracer if tracer is not None else obs_trace.TRACER
        # tenant cost ledger: process-wide on the default registry, private
        # per injected test Registry (the tracer's isolation contract)
        self.ledger = obs_accounting.for_registry(registry)
        # multi-tenant QoS (tpustack.serving.qos): priority resolution +
        # quota/priority-aware admission via the resilience middleware;
        # measured ledger charges drive the quota buckets.  None
        # (TPUSTACK_QOS=0) keeps admission byte-for-byte QoS-free.
        from tpustack.serving import qos as qos_mod

        self.qos = qos_mod.QosPolicy.from_env(registry=registry)
        if self.qos is not None:
            self.ledger.add_listener(self.qos.on_ledger_charge)
        if pipeline is None:
            pipeline = self._pipeline_from_env()
        self.pipe = pipeline
        self.mesh = mesh if mesh is not None else self._mesh_from_env()
        self._last_image: Optional[bytes] = None
        self._lock = asyncio.Lock()
        # device arrays dispatched but not yet fetched — /profile drains
        # these before tracing so a capture never interleaves with an
        # earlier batch still computing/transferring.  Mutations hold the
        # dispatch lock so /profile's drain snapshot can never see a
        # half-applied update (tpulint TPL201)
        self._inflight: list = []  # guarded-by: _lock
        # ---- dynamic micro-batcher (TPU-native: one fused program serves
        # many queued requests at once; the reference serialised requests on
        # its single GPU, configmap.yaml:38-39) ----
        if batch_window_ms is None:
            batch_window_ms = float(os.environ.get("SD15_BATCH_WINDOW_MS", "15"))
        if max_batch is None:
            max_batch = int(os.environ.get("SD15_MAX_BATCH", "0") or 0)
        if not max_batch:
            max_batch = self._mesh_data_size() or 1
        # invariants that keep _padded_size ≤ max_batch (the operator's HBM
        # cap must never be exceeded by pow2 padding): round a non-pow2 cap
        # down, and raise it to dp×fsdp (padding reaches that regardless)
        pow2 = 1
        while pow2 * 2 <= max_batch:
            pow2 *= 2
        if pow2 != max_batch:
            log.warning("SD15_MAX_BATCH=%d is not a power of two; using %d "
                        "(batches pad to pow2 signatures)", max_batch, pow2)
            max_batch = pow2
        n_data = self._mesh_data_size()
        if n_data and max_batch % n_data:
            # below dp×fsdp (padding reaches that regardless) or not a
            # multiple of it (padding would overshoot the cap): round up
            rounded = max_batch + (-max_batch) % n_data
            log.warning("SD15_MAX_BATCH=%d not a multiple of mesh dp×fsdp=%d;"
                        " using %d", max_batch, n_data, rounded)
            max_batch = rounded
        self.batch_window_s = batch_window_ms / 1e3
        self.max_batch = max_batch
        # shape-key → (group id, [_PendingReq]); the id lets a window flusher
        # detect that "its" group was already drained by a full-batch flush,
        # so a stale timer never shrinks the NEXT group's window
        self._pending: Dict[tuple, tuple] = {}
        self._group_seq = 0
        # shared resilience layer: drain on SIGTERM, per-request deadlines,
        # 429 backpressure, hung-dispatch watchdog, TPUSTACK_FAULT_* hooks.
        # queue depth is the manager default (in-flight work requests beyond
        # max_batch capacity): a request leaves the window groups the moment
        # it is dispatched, so group size alone under-counts waiting work
        self.resilience = ResilienceManager("sd", registry,
                                            concurrency=self.max_batch,
                                            expected_service_s=5.0,
                                            qos=self.qos)
        # mesh-shape gauges: operators confirm a google.com/tpu: N pod is
        # actually fanning batches out dp-ways (SD15_DP) from /metrics
        from tpustack.parallel.sharding import export_mesh_axis_gauges

        export_mesh_axis_gauges(self.metrics, "sd", self.mesh)
        # engine flight recorder: one record per fused batch (window size,
        # riders, denoise/encode split, pipeline FLOPs), on /debug/flight
        # and auto-dumped by the resilience/sanitizer post-mortem hooks;
        # the collector turns the window into the live SD MFU gauge
        self.flight = obs_flight.register(obs_flight.FlightRecorder(
            "sd", meta={"max_batch": self.max_batch,
                        "dp": self._mesh_data_size() or 1}))
        self._flops_cache: Dict[tuple, Optional[float]] = {}
        from tpustack.obs.metrics import REGISTRY

        (registry if registry is not None else REGISTRY).add_collector(
            self._flight_collector)
        sanitize.install_guards(self)

    def _signature_flops(self, steps: int, width: int, height: int,
                         batch_size: int) -> Optional[float]:
        """Pipeline FLOPs for one compiled batch signature (XLA cost
        analysis — the number bench.py's MFU divides).  Cached per
        signature; None (and the MFU gauge omitted) when the pipeline
        cannot cost itself (stub pipes, cost analysis unavailable)."""
        key = (steps, width, height, batch_size)
        if key not in self._flops_cache:
            try:
                self._flops_cache[key] = float(self.pipe.pipeline_flops(
                    steps=steps, width=width, height=height,
                    batch_size=batch_size))
            except Exception:
                log.debug("pipeline FLOPs unavailable for signature %s — "
                          "sd MFU gauge will be omitted", key,
                          exc_info=True)
                self._flops_cache[key] = None
        return self._flops_cache[key]

    def _flight_collector(self, registry) -> None:
        """Scrape-time live-MFU attribution: summed batch FLOPs over
        device-busy seconds in the flight window against the bf16 peak —
        omitted (never faked) when the device kind is unknown."""
        from tpustack.utils import knobs as _knobs

        agg = self.flight.aggregates(
            _knobs.get_float("TPUSTACK_FLIGHT_WINDOW_S"))
        kind, peaks = obs_flight.device_peaks_info()
        if peaks is None or not kind:
            return  # unknown device kind: the gauge stays omitted
        util = obs_flight.sd_utilization(agg, peaks,
                                         chips=self._mesh_data_size() or 1)
        # an idle (or uncosted) window is ~0 utilization — clear the gauge
        # rather than freezing the last busy window's value forever
        self.metrics["tpustack_sd_mfu_ratio"].labels(device_kind=kind).set(
            util["mfu"] if util is not None else 0)

    @staticmethod
    def _pipeline_from_env():
        from tpustack.models.sd15 import SD15Config, SD15Pipeline

        preset = os.environ.get("SD15_PRESET", "sd15")
        cfg = SD15Config.tiny() if preset == "tiny" else SD15Config.sd15()
        pipe = SD15Pipeline(cfg)
        model_dir = os.environ.get("MODEL_DIR", "")
        if model_dir:
            from tpustack.models.sd15.weights import load_sd15_safetensors

            pipe.params = load_sd15_safetensors(model_dir, cfg, pipe.params)
            log.info("Loaded weights from %s", model_dir)
        return pipe

    def _mesh_data_size(self) -> int:
        """Number of data-parallel ways on the mesh (dp×fsdp), or 0 if none."""
        from tpustack.parallel import data_parallel_size

        return data_parallel_size(self.mesh)

    @staticmethod
    def _mesh_from_env():
        """``SD15_DP=N`` → dp mesh over the pod's N chips (v5e-8 Deployment:
        one server process, batch requests data-parallel across all chips —
        the reference could only scale by adding pods, SURVEY.md §2.10)."""
        dp = int(os.environ.get("SD15_DP", "0") or 0)
        if dp <= 1:
            return None
        import jax

        from tpustack.parallel import build_mesh

        # dp may be smaller than the pod's visible chip count — use a subset
        return build_mesh((dp, 1, 1, 1), devices=jax.devices()[:dp])

    # ------------------------------------------------------------ handlers
    async def healthz(self, request: web.Request) -> web.Response:
        """Liveness + server state (503 only on a watchdog-declared hang;
        the ``ok`` field keeps the reference configmap's response shape)."""
        status, payload = self.resilience.health_payload(extra={
            "max_batch": self.max_batch,
            "batch_window_ms": self.batch_window_s * 1e3,
            "dp": self._mesh_data_size() or 1,
            "png_encoder": runtime.encoder(),
        })
        return web.json_response(payload, status=status,
                                 headers=self.resilience.health_headers(status))

    async def readyz(self, request: web.Request) -> web.Response:
        status, payload = self.resilience.ready_payload()
        return web.json_response(payload, status=status,
                                 headers=self.resilience.ready_headers(status))

    async def index(self, request: web.Request) -> web.Response:
        if self._last_image is None:
            return web.Response(
                text="<h1>SD1.5 TPU API</h1><p>No image generated yet. "
                     "POST /generate to create one.</p>",
                content_type="text/html")
        preview = base64.b64encode(self._last_image).decode("ascii")
        html = f"""
        <html>
          <head><title>SD1.5 TPU Demo</title></head>
          <body style="background:#0b0b0f;color:#f0f0f0;font-family:sans-serif;">
            <h1>Latest image</h1>
            <img src="data:image/png;base64,{preview}" alt="latest image"
                 style="max-width:90vw;height:auto;border:3px solid #333;border-radius:8px;" />
            <p>POST <code>/generate</code> with a prompt to update this preview.</p>
          </body>
        </html>
        """
        return web.Response(text=html, content_type="text/html")

    async def last(self, request: web.Request) -> web.Response:
        if self._last_image is None:
            return web.json_response({"detail": "No image generated yet"}, status=404)
        return web.Response(body=self._last_image, content_type="image/png")

    async def generate(self, request: web.Request) -> web.Response:
        try:
            req = GenReq.model_validate(await obs_http.request_json(request))
        except (ValidationError, ValueError) as e:
            return web.json_response({"detail": str(e)}, status=422)
        if not req.prompt or not req.prompt.strip():
            return web.json_response({"detail": "prompt is required"}, status=400)

        # explicit None checks — 0.0 guidance (CFG off) is a legitimate value
        steps = 30 if req.steps is None else req.steps
        guidance = 7.5 if req.guidance_scale is None else req.guidance_scale
        width = 512 if req.width is None else req.width
        height = 512 if req.height is None else req.height

        try:
            deadline_s = self.resilience.deadline(req.timeout_s)
        except (TypeError, ValueError) as e:
            return web.json_response({"detail": f"bad timeout_s: {e}"},
                                     status=422)
        t0 = time.time()
        log.info(
            "Generating prompt='%s' steps=%s guidance=%.2f seed=%s size=%sx%s",
            req.prompt, steps, guidance,
            req.seed if req.seed is not None else "auto", width, height)

        key = (steps, float(guidance), width, height)
        from tpustack.serving import qos as qos_mod

        parent = obs_trace.current_span.get()
        pending = _PendingReq(req.prompt, req.negative_prompt or "",
                              req.seed,
                              asyncio.get_running_loop().create_future(),
                              t_enqueue=time.perf_counter(),
                              span_ctx=parent.context if parent else None,
                              t_enqueue_unix=time.time(),
                              tenant=obs_accounting.current_tenant.get(),
                              priority=(qos_mod.current_priority.get()
                                        if self.qos is not None else None))
        try:
            img = await asyncio.wait_for(self._enqueue(key, pending),
                                         deadline_s)
        except ValueError as e:  # e.g. size not a multiple of the UNet factor
            return web.json_response({"detail": str(e)}, status=400)
        except asyncio.TimeoutError:
            # still waiting in its window group → pull it out so the batch
            # never pays for it (phase=queued); already dispatched → the
            # fused program runs to completion but nobody waits (the engine
            # "slot" was a batch row, freed when the batch resolves)
            phase = "queued" if self._abandon(key, pending) else "denoise"
            self.resilience.note_deadline(phase)
            return web.json_response(
                {"detail": f"request deadline exceeded (phase={phase})",
                 "phase": phase}, status=504,
                headers=shed_headers("deadline"))
        except InjectedDeviceError as e:
            return self.resilience.transient_error_response(e)
        from tpustack.obs import Trace

        tr = Trace(request_id=request.get("request_id"))
        with tr.span("png_encode"), \
                self.tracer.span_if_active("png_encode"):
            png = array_to_png(img)
        tr.observe_into(self.metrics["tpustack_request_phase_latency_seconds"],
                        server="sd")
        self.metrics["tpustack_sd_images_total"].inc()
        latency = time.time() - t0
        log.info("Completed generation in %.2fs", latency)
        self._last_image = png
        return web.Response(body=png, content_type="image/png",
                            headers={"X-Gen-Time": f"{latency:.2f}s"})

    # ------------------------------------------------------- micro-batcher
    async def _enqueue(self, key: tuple, req: _PendingReq):
        """Queue one request; concurrent requests with the same compiled
        signature (steps, guidance, size) ride the same fused program.

        The first request in a group starts a flusher task that waits
        ``batch_window_s`` for company, then drains up to ``max_batch``
        requests into one ``pipe.generate`` call; a group hitting
        ``max_batch`` flushes immediately.  On a mesh the batch is padded to
        a multiple of dp×fsdp so GSPMD can split it.
        """
        if key not in self._pending:
            self._group_seq += 1
            self._pending[key] = (self._group_seq, [])
        gid, group = self._pending[key]
        group.append(req)
        self._set_queue_depth()
        if len(group) == self.max_batch:  # == not >=: one flusher per group
            asyncio.ensure_future(self._flush(key, gid, wait=False))
        elif len(group) == 1:
            asyncio.ensure_future(self._flush(key, gid, wait=self.max_batch > 1))
        return await req.future

    def _abandon(self, key: tuple, req: _PendingReq) -> bool:
        """Remove a deadline-expired request from its window group (True if
        it was still queued).  Runs on the event loop with no awaits, so it
        cannot interleave with a flusher draining the same group."""
        entry = self._pending.get(key)
        if entry is None or req not in entry[1]:
            return False
        entry[1].remove(req)
        if not entry[1]:
            self._pending.pop(key, None)
        self._set_queue_depth()
        return True

    async def _flush(self, key: tuple, gid: int, wait: bool) -> None:
        if wait:
            await asyncio.sleep(self.batch_window_s)  # collection window
        async with self._lock:
            entry = self._pending.get(key)
            if entry is None or entry[0] != gid:
                return  # this group was already drained; don't touch the next
            _, group = entry
            batch, rest = group[:self.max_batch], group[self.max_batch:]
            if rest:
                self._group_seq += 1
                self._pending[key] = (self._group_seq, rest)
                asyncio.ensure_future(self._flush(key, self._group_seq, wait=False))
            else:
                self._pending.pop(key, None)
            self._set_queue_depth()
        # OUTSIDE the bookkeeping lock: batches pipeline — while batch k's
        # images stream device→host, batch k+1's program is already queued
        # on the chip (generate_async dispatches without blocking)
        await self._run_batch(key, batch)

    def _set_queue_depth(self) -> None:
        self.metrics["tpustack_sd_queue_depth"].set(
            sum(len(g) for _, g in self._pending.values()))

    def _padded_size(self, n: int) -> int:
        """Canonical batch size: next power of two (so at most log2(max_batch)
        compiled signatures ever exist, instead of one per concurrency level),
        rounded up to a multiple of dp×fsdp so GSPMD can split it."""
        size = 1
        while size < n:
            size *= 2
        n_data = self._mesh_data_size()
        if n_data:
            size = max(size, n_data)
            size += (-size) % n_data
        # __init__ rounds max_batch to a pow2 multiple of dp×fsdp, so the
        # clamp keeps both invariants: never exceed the cap, stay splittable
        return min(size, self.max_batch)

    async def _run_batch(self, key: tuple, batch: list) -> None:
        from tpustack.obs import Trace

        steps, guidance, width, height = key
        tr = Trace()  # phase spans for this fused dispatch
        t_build = time.perf_counter()
        t_build_unix = time.time()
        prompts = [r.prompt for r in batch]
        negs = [r.negative for r in batch]
        seeds = [r.seed for r in batch]
        mesh = self.mesh
        pad = self._padded_size(len(batch)) - len(batch)
        prompts += prompts[-1:] * pad  # pad to a canonical compiled signature
        negs += negs[-1:] * pad
        seeds += [0] * pad
        self.metrics["tpustack_sd_batch_size_images"].observe(len(batch))
        if pad:
            self.metrics["tpustack_sd_padded_slots_total"].inc(pad)
        for r in batch:  # admission → dispatch: the window + lock wait
            if r.t_enqueue:
                wait_s = time.perf_counter() - r.t_enqueue
                tr.add("queue_wait", wait_s)
                self.ledger.charge_queue_seconds("sd", r.tenant, wait_s)
                if self.qos is not None:
                    self.qos.observe_queue_wait("sd", r.priority, wait_s)
        if len(batch) > 1 or pad:
            log.info("Micro-batch: %d requests (+%d pad) in one program (dp=%s)",
                     len(batch), pad, self._mesh_data_size() or 1)
        try:
            loop = asyncio.get_running_loop()
            # dispatch under the lock (host-side, returns immediately via JAX
            # async dispatch — keeps program order deterministic), fetch
            # outside it so the next batch's compute overlaps this transfer
            def dispatch():
                # progress point on the executor thread (a fault-injected
                # sleep/hang must never block the event loop): watchdog
                # beat + TPUSTACK_FAULT_* hooks, then the async dispatch
                self.resilience.progress("prefill")
                return self.pipe.generate_async(
                    prompts, steps=steps, guidance_scale=guidance,
                    seed=seeds, width=width, height=height,
                    negative_prompt=negs, mesh=mesh)

            async with self._lock:
                dev_imgs = await loop.run_in_executor(None, dispatch)
                self._inflight.append(dev_imgs)
            # batch_build: list assembly + the host-side trace/dispatch of
            # the fused program (returns before the device finishes)
            build_s = time.perf_counter() - t_build
            tr.add("batch_build", build_s)
            t_denoise = time.perf_counter()
            try:
                # device wall time: the CFG denoise loop AND the VAE decode
                # are ONE fused XLA program here, so they are one phase
                with tr.span("denoise_vae"):
                    imgs = await loop.run_in_executor(
                        None, lambda: np.asarray(dev_imgs))
            finally:
                # remove by identity: list.remove uses ==, which on jax.Array
                # raises "truth value is ambiguous" whenever two batches
                # overlap and ours is no longer at index 0
                async with self._lock:
                    self._inflight[:] = [a for a in self._inflight
                                         if a is not dev_imgs]
        except Exception as e:
            for r in batch:
                if not r.future.done():
                    r.future.set_exception(e)
            return
        # flush the phase spans only for batches that served images — a
        # failed dispatch must not skew the latency histograms
        tr.observe_into(self.metrics["tpustack_request_phase_latency_seconds"],
                        server="sd")
        # distributed tracing: one fused program served every rider, so each
        # request's batch_build/denoise spans carry the SHARED batch timing
        # (explicit wall clocks — this task is not any rider's context)
        denoise_s = time.perf_counter() - t_denoise
        # flight record: one per fused dispatch — the SD engine's wave.
        # The riders' tenant split rides the record and the chip-seconds
        # charge reads it back, so /debug/flight and /debug/tenants hold
        # the same numbers (the llm engine's charge_flight_wave contract)
        tenants: Dict[str, int] = {}
        for r in batch:
            if r.tenant is not None:
                tenants[r.tenant] = tenants.get(r.tenant, 0) + 1
        rec = dict(
            batch=len(batch), pad=pad, steps=steps,
            width=width, height=height,
            build_s=round(build_s, 6), denoise_vae_s=round(denoise_s, 6),
            flops=self._signature_flops(steps, width, height,
                                        len(batch) + pad))
        if tenants:
            rec["tenants"] = tenants
        self.flight.record("batch", **rec)
        self.ledger.charge_flight_wave("sd", rec,
                                       seconds_key="denoise_vae_s")
        for r in batch:
            if r.span_ctx is None:
                continue
            self.tracer.add_span(
                "queue_wait", r.span_ctx, r.t_enqueue_unix,
                max(0.0, t_build_unix - r.t_enqueue_unix))
            self.tracer.add_span(
                "batch_build", r.span_ctx, t_build_unix, build_s,
                attrs={"batch": len(batch), "pad": pad,
                       "dp": self._mesh_data_size() or 1})
            self.tracer.add_span(
                "denoise_vae", r.span_ctx, t_build_unix + build_s, denoise_s,
                attrs={"steps": steps, "width": width, "height": height})
        # batch boundary: watchdog beat + injected mid-request SIGTERM point
        self.resilience.progress("wave")
        for i, r in enumerate(batch):
            if not r.future.done():
                r.future.set_result(imgs[i])

    async def profile(self, request: web.Request) -> web.Response:
        """Capture an XLA/TPU profile (xplane) around one small generate.

        Observability beyond the reference's wall-clock-only `X-Gen-Time`
        (SURVEY.md §5 "Tracing/profiling: none... JAX profiler/xplane is
        optional extra").  ``POST /profile {steps?, width?, height?}`` →
        {trace_dir, files, gen_time_s}; view with xprof/tensorboard or
        ``tools/xprof_summary.py``.  The capture mechanics live in
        ``tpustack.obs.profile``, shared with llm_server/graph_server;
        this handler keeps the SD-specific drain-snapshot dance."""
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            body = {}
        try:
            f = obs_profile.parse_int_fields(
                body, {"steps": 4, "width": 512, "height": 512})
        except ValueError as e:
            return web.json_response({"detail": str(e)}, status=422)
        base = obs_profile.base_dir("sd", os.environ.get("SD15_TRACE_DIR"))
        async with self._lock:
            # quiesce: dispatches are blocked by the lock, but a previous
            # batch may still be computing/transferring — wait it out so
            # the capture contains only the profiled run
            import jax as _jax

            for arr in list(self._inflight):
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda a=arr: _jax.block_until_ready(a))

            def run():
                self.pipe.generate("profile capture", steps=f["steps"],
                                   width=f["width"], height=f["height"],
                                   seed=0)

            try:
                out = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: obs_profile.capture(base, run))
            except ValueError as e:
                return web.json_response({"detail": str(e)}, status=400)
        return web.json_response(out)

    # ---------------------------------------------------------------- app
    def build_app(self) -> web.Application:
        work = {"/generate"}
        app = web.Application(
            client_max_size=1 << 20,
            middlewares=[obs_http.instrument("sd", self._registry,
                                             tracer=self.tracer,
                                             ledger=self.ledger,
                                             work_endpoints=work),
                         self.resilience.middleware(work)])
        obs_http.add_debug_trace_routes(app, self.tracer)
        obs_http.add_debug_flight_routes(app, self.flight)
        obs_http.add_debug_tenant_routes(app, self.ledger, qos=self.qos)
        app.router.add_get("/healthz", self.healthz)
        app.router.add_get("/readyz", self.readyz)
        app.router.add_get("/", self.index)
        app.router.add_get("/last", self.last)
        app.router.add_get("/metrics",
                           obs_http.make_metrics_handler(self._registry))
        app.router.add_post("/generate", self.generate)
        app.router.add_post("/profile", self.profile)
        return app


def main() -> None:
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR or <repo>/.cache/xla
    # build/load the native PNG encoder before serving (never inside a
    # request) and say which encoder this process ended up with
    log.info("PNG encoder: %s", runtime.encoder())
    port = int(os.environ.get("PORT", "8000"))
    server = SDServer()
    if os.environ.get("SD15_WARMUP", "1") not in ("0", "false"):
        tiny = os.environ.get("SD15_PRESET", "sd15") == "tiny"
        kw = dict(steps=2, width=64, height=64) if tiny else {}
        # compile every canonical batch signature the micro-batcher can emit
        # (pow2s up to max_batch; one size when a mesh pads everything to it)
        # BEFORE readiness — a request must never stall on a cold jit
        sizes = sorted({server._padded_size(n)
                        for n in range(1, server.max_batch + 1)})
        for size in sizes:
            log.info("Warming up (compiling %s batch=%d, dp=%s)...",
                     kw or "default 512x512x30", size,
                     server._mesh_data_size() or 1)
            secs = server.pipe.warmup(batch_size=size, mesh=server.mesh, **kw)
            log.info("Warmup batch=%d done in %.1fs", size, secs)
    # SIGTERM → graceful drain (readiness 503, in-flight batches finish,
    # exit 0); aiohttp's own immediate-stop handler must not race it
    server.resilience.install_signal_handlers()
    web.run_app(server.build_app(), port=port, access_log=None,
                handle_signals=False)


if __name__ == "__main__":
    main()
