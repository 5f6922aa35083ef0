"""ComfyUI-compatible node-graph server for the Wan T2V family.

The reference's video path drives a ComfyUI server that its repo never ships —
the client targets a ``wan-video-gen`` deployment that does not exist in its
manifests (reference ``generate_wan_t2v.py:320``, SURVEY.md §2.6).  This
module closes that gap TPU-natively: the same HTTP API surface the reference
client speaks, executing node graphs on this package's jitted Wan pipeline.

API (exactly what ``generate_wan_t2v.py`` uses):

- ``GET  /queue``                 → {"queue_running": [...], "queue_pending": [...]}
- ``GET  /object_info``           → node schemas incl. loader file options
  (client preflight, reference ``generate_wan_t2v.py:204-221``)
- ``POST /prompt``                → {"prompt_id": ...}; body {prompt, client_id}
- ``GET  /history/{prompt_id}``   → {id: {status, outputs}} once known
- ``GET  /view?filename=&subfolder=&type=`` → output file bytes

Node set: UNETLoader, CLIPLoader, VAELoader, EmptyHunyuanLatentVideo,
CLIPTextEncode, KSampler, VAEDecode, SaveImage, SaveAnimatedWEBP and —
when an ``ffmpeg`` binary is present (the serving image installs one; dev
images may not) — SaveWEBM.

TPU twist: the graph is a *serving* abstraction, not a compute schedule.
``KSampler`` returns a symbolic sampling spec; ``VAEDecode`` triggers the
single fused XLA program (UMT5 → CFG flow-matching loop → causal-3D-VAE
decode) from ``WanPipeline``.  Intermediate latents never round-trip to the
host, which is precisely what a node-per-op executor cannot avoid.
Graphs wired outside this shape are rejected with a clear error.

Resilience (``tpustack.serving.resilience``): SIGTERM drains — /prompt
refuses with 503 + Retry-After while the worker publishes every accepted
prompt, then the process exits 0; ``TPUSTACK_MAX_QUEUE_DEPTH`` sheds with
429; a queued prompt past its deadline (``TPUSTACK_REQUEST_TIMEOUT_S`` or
body ``timeout_s``) is answered through /history instead of wasting a
dispatch; ``TPUSTACK_WATCHDOG_S`` flips ``/healthz`` (liveness) when a
dispatch hangs; ``GET /readyz`` is the readiness probe endpoint.
"""

from __future__ import annotations

import asyncio
import json
import os
import queue
import re
import shutil
import subprocess
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from aiohttp import web

from tpustack import runtime, sanitize
from tpustack.obs import Trace
from tpustack.obs import accounting as obs_accounting
from tpustack.obs import catalog as obs_catalog
from tpustack.obs import device as obs_device
from tpustack.obs import flight as obs_flight
from tpustack.obs import http as obs_http
from tpustack.obs import profile as obs_profile
from tpustack.obs import trace as obs_trace
from tpustack.serving.resilience import ResilienceManager, shed_headers
from tpustack.utils import get_logger
from tpustack.utils.image import array_to_png

log = get_logger("serving.graph_server")

# canonical checkpoint filenames (what the reference client preflights for,
# reference generate_wan_t2v.py:347-349)
CANONICAL_UNET = "wan2.1_t2v_1.3B_bf16.safetensors"
CANONICAL_CLIP = "umt5_xxl_fp16.safetensors"
CANONICAL_VAE = "wan_2.1_vae.safetensors"

_SAMPLERS = ["uni_pc", "uni_pc_bh2", "euler", "heun", "dpmpp_2m"]
_SCHEDULERS = ["simple", "normal"]


def _ffmpeg() -> Optional[str]:
    return shutil.which("ffmpeg")


# --------------------------------------------------------------------- values
@dataclass(frozen=True)
class Conditioning:
    text: str


@dataclass(frozen=True)
class LatentSpec:
    width: int
    height: int
    frames: int
    batch_size: int


@dataclass(frozen=True)
class SampleSpec:
    latent: LatentSpec
    positive: Conditioning
    negative: Conditioning
    seed: int
    steps: int
    cfg: float
    sampler_name: str
    denoise: float


@dataclass
class Frames:
    """[F, H, W, 3] uint8 — a jax device array until the first ``numpy()``
    (VAEDecode dispatches asynchronously; save nodes fetch at write time, so
    the worker can overlap one prompt's fetch with the next one's compute).

    Under the worker's queue-batching, ``array`` is late-bound: VAEDecode
    returns an empty Frames and the worker fills it (a row of one batched
    dispatch) before any deferred save runs; ``error`` carries a failed
    dispatch to the save node that would have consumed it."""

    array: Any = None
    error: Any = None
    n_frames: Optional[int] = None  # known at plan time for late-bound frames

    @property
    def frame_count(self) -> int:
        if self.array is not None:
            return int(self.array.shape[0])
        if self.n_frames is None:
            raise GraphError("frame count unknown before dispatch (server bug)")
        return self.n_frames

    def numpy(self) -> np.ndarray:
        if self.error is not None:
            raise GraphError(f"sampling failed: {self.error}")
        if self.array is None:
            raise GraphError("frames were never dispatched (server bug)")
        if not isinstance(self.array, np.ndarray):
            self.array = np.asarray(self.array)
        return self.array


#: max summed pixel-frames (B * frames * H * W) per BATCHED dispatch —
#: shared by the worker's _dispatch_plan and the hookless execute path.
#: Measured on one v5e: batching wins where per-dispatch overhead dominates
#: (64x64x5f pair: 1.3-1.4x cheaper than 2x serial) but the denoise is
#: COMPUTE-bound at larger shapes, where fusing buys nothing and XLA
#: schedules the doubled batch slightly worse (256x256x9f pair: 0.9x) —
#: and a full-size 512x320x16f pair does not even fit HBM (B=2 wants
#: 17.06 GB of 15.75).  Default admits only the overhead-dominated small
#: shapes; env override for experimentation.
PIXEL_BUDGET = int(os.environ.get("WAN_BATCH_PIXEL_BUDGET", "150000"))


class _ConcatFrames(Frames):
    """ComfyUI batched-latent semantics: a ``batch_size`` B latent decodes
    to the B videos stacked along the frame axis (ComfyUI's IMAGE batch),
    so SaveAnimatedWEBP writes one B*F-frame animation and SaveImage writes
    B*F stills.  Each row is its own late-bound :class:`Frames` (its own
    seed, its own lane of a batched dispatch) — rows are row-equal to solo
    runs of (seed + row index); the concat is deferred to first fetch."""

    def __init__(self, rows):
        super().__init__(n_frames=sum(r.frame_count for r in rows))
        self.rows = rows

    def numpy(self) -> np.ndarray:
        if self.array is None:
            errs = [r.error for r in self.rows if r.error is not None]
            if errs:
                raise GraphError(f"sampling failed: {errs[0]}")
            self.array = np.concatenate([r.numpy() for r in self.rows],
                                        axis=0)
        return self.array


@dataclass
class OutputFile:
    filename: str
    subfolder: str = ""
    type: str = "output"
    kind: str = "images"  # history key: images | videos

    def as_history(self) -> Dict[str, str]:
        return {"filename": self.filename, "subfolder": self.subfolder,
                "type": self.type}


# --------------------------------------------------------------------- runtime
def _text_quant(preset: str) -> Optional[str]:
    """Resolve ``WAN_TEXT_QUANT``: serving default is the weight-only int8
    umt5-xxl text tower (5.7 GB instead of 11.4 bf16 / 22.8 f32 — a
    full-precision tower does not even COMPILE beside the DiT on a 16 GB
    chip: XLA reports 30.9 GB HBM for the f32 build).  An empty/unset env
    keeps the default; explicit ``none``/``off`` opts out (multi-chip
    setups).  Called at server startup too, so a typo fails the pod at
    deploy time instead of erroring every /prompt."""
    raw = os.environ.get("WAN_TEXT_QUANT", "").strip().lower()
    if raw in ("none", "off"):
        return None
    if raw == "":
        return None if preset == "tiny" else "int8"
    if raw != "int8":
        raise ValueError(f"WAN_TEXT_QUANT={raw!r} unsupported (int8|none)")
    return raw


class WanRuntime:
    """Owns the (lazily built) pipeline + models/output directories."""

    def __init__(self, models_dir: Optional[str] = None,
                 output_dir: Optional[str] = None, pipeline=None):
        self.models_dir = models_dir or os.environ.get("WAN_MODELS_DIR", "/models")
        self.output_dir = output_dir or os.environ.get("WAN_OUTPUT_DIR",
                                                       "/tmp/wan-outputs")
        os.makedirs(self.output_dir, exist_ok=True)
        self._pipeline = pipeline  # guarded-by: _lock
        self._lock = threading.Lock()
        sanitize.install_guards(self)

    # ---- model discovery (ComfyUI directory layout)
    def _list(self, sub: str, canonical: str) -> List[str]:
        names = []
        d = os.path.join(self.models_dir, sub)
        if os.path.isdir(d):
            names = sorted(f for f in os.listdir(d)
                           if f.endswith((".safetensors", ".sft", ".pt")))
        if not names and self._allow_random():
            # zero-egress / random-weights mode still advertises the canonical
            # names so the reference client's preflight passes
            names = [canonical]
        return names

    @staticmethod
    def _allow_random() -> bool:
        return os.environ.get("WAN_ALLOW_RANDOM", "1") not in ("0", "false")

    def unet_names(self) -> List[str]:
        return self._list("diffusion_models", CANONICAL_UNET)

    def clip_names(self) -> List[str]:
        return self._list("text_encoders", CANONICAL_CLIP)

    def vae_names(self) -> List[str]:
        return self._list("vae", CANONICAL_VAE)

    def pipeline(self):
        with self._lock:
            if self._pipeline is None:
                from tpustack.models.wan import WanConfig, WanPipeline

                import dataclasses

                preset = os.environ.get("WAN_PRESET", "wan_1_3b")
                cfg = (WanConfig.tiny() if preset == "tiny"
                       else WanConfig.wan_1_3b())
                tq = _text_quant(preset)
                if tq:
                    cfg = dataclasses.replace(
                        cfg, text=dataclasses.replace(cfg.text, quant=tq))
                log.info("Building Wan pipeline (preset=%s, text_quant=%s)...",
                         preset, tq)
                pipe = WanPipeline(cfg)
                unets, clips = self.unet_names(), self.clip_names()
                vaes = self.vae_names()
                have_real = os.path.isdir(
                    os.path.join(self.models_dir, "diffusion_models"))
                if have_real and unets and clips:
                    # real checkpoints on the PVC → map them in (DiT + UMT5 +
                    # VAE); any mismatch raises rather than silently serving
                    # noise — there is no partial-load mode
                    from tpustack.models.wan.weights import load_wan_safetensors

                    pipe.params = load_wan_safetensors(
                        self.models_dir, cfg, pipe.params,
                        unet_name=unets[0], clip_name=clips[0],
                        vae_name=vaes[0] if vaes else CANONICAL_VAE)
                elif not self._allow_random():
                    raise RuntimeError(
                        f"no Wan checkpoints under {self.models_dir} and "
                        "WAN_ALLOW_RANDOM=0 — refusing to serve random weights")
                self._pipeline = pipe
            return self._pipeline


# ----------------------------------------------------------------- graph exec
class GraphError(ValueError):
    pass


class GraphExecutor:
    """Topologically executes a ComfyUI-style ``{id: {class_type, inputs}}``
    graph.  Node functions are methods ``node_<ClassType>``."""

    def __init__(self, runtime: WanRuntime, registry=None, tracer=None,
                 flight=None):
        self.rt = runtime
        self.metrics = obs_catalog.build(registry)
        self.tracer = tracer if tracer is not None else obs_trace.TRACER
        # flight recorder (tpustack.obs.flight): one record per resolved
        # node during graph execution; None keeps resolution record-free
        self.flight = flight
        self._counter_lock = threading.Lock()
        self._counter = self._scan_counter()  # guarded-by: _counter_lock
        sanitize.install_guards(self)

    def _scan_counter(self) -> int:
        """Resume numbering after the max existing ``*_NNNNN_.*`` output so
        restarts and concurrent prefixes never overwrite earlier files."""
        best = 0
        try:
            for name in os.listdir(self.rt.output_dir):
                m = re.search(r"_(\d{5,})_\.\w+$", name)
                if m:
                    best = max(best, int(m.group(1)))
        except OSError:
            pass
        return best

    def _next_counter(self) -> int:
        with self._counter_lock:
            self._counter += 1
            return self._counter

    # -- node implementations ------------------------------------------------
    def node_UNETLoader(self, inputs, _ctx):
        name = inputs.get("unet_name")
        if name not in self.rt.unet_names():
            raise GraphError(f"UNET not found: {name}")
        return (("unet", name),)

    def node_CLIPLoader(self, inputs, _ctx):
        name = inputs.get("clip_name")
        if name not in self.rt.clip_names():
            raise GraphError(f"CLIP not found: {name}")
        return (("clip", name),)

    def node_VAELoader(self, inputs, _ctx):
        name = inputs.get("vae_name")
        if name not in self.rt.vae_names():
            raise GraphError(f"VAE not found: {name}")
        return (("vae", name),)

    def node_CLIPTextEncode(self, inputs, _ctx):
        return (Conditioning(text=str(inputs.get("text", ""))),)

    def node_EmptyHunyuanLatentVideo(self, inputs, _ctx):
        return (LatentSpec(width=int(inputs.get("width", 512)),
                           height=int(inputs.get("height", 320)),
                           frames=int(inputs.get("length", 16)),
                           batch_size=int(inputs.get("batch_size", 1))),)

    def node_KSampler(self, inputs, _ctx):
        latent = inputs.get("latent_image")
        pos, neg = inputs.get("positive"), inputs.get("negative")
        if not isinstance(latent, LatentSpec):
            raise GraphError("KSampler latent_image must come from "
                             "EmptyHunyuanLatentVideo")
        if not isinstance(pos, Conditioning) or not isinstance(neg, Conditioning):
            raise GraphError("KSampler positive/negative must come from "
                             "CLIPTextEncode")
        denoise = float(inputs.get("denoise", 1.0))
        if denoise != 1.0:
            raise GraphError("partial denoise (img2vid) not supported yet")
        if not 1 <= latent.batch_size <= 16:
            raise GraphError(
                f"batch_size {latent.batch_size} out of range [1, 16]")
        return (SampleSpec(latent=latent, positive=pos, negative=neg,
                           seed=int(inputs.get("seed", 0)),
                           steps=int(inputs.get("steps", 25)),
                           cfg=float(inputs.get("cfg", 6.0)),
                           sampler_name=str(inputs.get("sampler_name", "uni_pc")),
                           denoise=denoise),)

    @staticmethod
    def _expand_rows(spec: SampleSpec) -> List[SampleSpec]:
        """A ``batch_size`` B KSampler spec is B independent rows with seeds
        ``seed + i`` — each row-equal to a solo graph at that seed (the
        documented batch convention; the pipeline's ``generate_many_async``
        builds per-item noise, so fused rows reproduce solo runs exactly)."""
        import dataclasses as _dc

        if spec.latent.batch_size == 1:
            return [spec]
        solo_latent = _dc.replace(spec.latent, batch_size=1)
        return [_dc.replace(spec, latent=solo_latent, seed=spec.seed + i)
                for i in range(spec.latent.batch_size)]

    def node_VAEDecode(self, inputs, ctx):
        spec = inputs.get("samples")
        if not isinstance(spec, SampleSpec):
            raise GraphError("VAEDecode samples must come from KSampler")
        rows = self._expand_rows(spec)
        hook = ctx.get("sample_hook")
        if hook is not None:
            # worker queue-batching: record each row's spec, return
            # late-bound Frames the worker fills from batched dispatches
            frames = [hook(r) for r in rows]
            return (frames[0] if len(frames) == 1
                    else _ConcatFrames(frames),)
        pipe = self.rt.pipeline()
        t0 = time.time()
        log.info("Sampling%s: %dx%d f=%d steps=%d cfg=%.1f sampler=%s "
                 "seed=%d", f" BATCH of {len(rows)}" if len(rows) > 1 else "",
                 spec.latent.width, spec.latent.height, spec.latent.frames,
                 spec.steps, spec.cfg, spec.sampler_name, spec.seed)
        # the same pixel-frame budget the worker's _dispatch_plan applies:
        # a full-size (512x320x16f) pair wants ~17 GB of HBM fused, so rows
        # chunk to at most max_b per dispatch (weights still stream once
        # per chunk; rows stay solo-equal either way)
        per = max(1, pipe.pixel_frame_count(spec.latent.frames)) \
            * spec.latent.height * spec.latent.width
        max_b = max(1, PIXEL_BUDGET // per)
        def dispatch(chunk):
            if len(chunk) == 1:
                vid_dev = pipe.generate_async(
                    chunk[0].positive.text,
                    negative_prompt=chunk[0].negative.text,
                    frames=spec.latent.frames, steps=spec.steps,
                    guidance_scale=spec.cfg, seed=chunk[0].seed,
                    width=spec.latent.width, height=spec.latent.height,
                    sampler=spec.sampler_name)
            else:
                vid_dev = pipe.generate_many_async(
                    [{"prompt": r.positive.text,
                      "negative_prompt": r.negative.text, "seed": r.seed}
                     for r in chunk],
                    frames=spec.latent.frames, steps=spec.steps,
                    guidance_scale=spec.cfg, width=spec.latent.width,
                    height=spec.latent.height, sampler=spec.sampler_name)
            return [Frames(array=vid_dev[i]) for i in range(len(chunk))]

        out = []
        for lo in range(0, len(rows), max_b):
            chunk = rows[lo:lo + max_b]
            try:
                out.extend(dispatch(chunk))
            except Exception as e:  # noqa: BLE001 — same policy as the
                # worker's _dispatch_one: a batched build failure (e.g.
                # compile-time HBM OOM at a shape an overridden pixel
                # budget admitted) degrades to per-row serial dispatches,
                # not a failed graph
                if len(chunk) == 1:
                    raise
                log.warning("hookless batched dispatch of %d failed (%s); "
                            "serving rows serially", len(chunk), e)
                self.metrics["tpustack_graph_batch_fallback_total"].inc()
                for r in chunk:
                    out.extend(dispatch([r]))
        log.info("Dispatched %d row(s) in %d chunk(s) in %.2fs (async; "
                 "save nodes fetch)", len(out),
                 (len(rows) + max_b - 1) // max_b, time.time() - t0)
        return (out[0] if len(out) == 1 else _ConcatFrames(out),)

    # -- save nodes
    def _out_path(self, prefix: str, ext: str, counter: int) -> Tuple[str, str]:
        safe = re.sub(r"[^A-Za-z0-9_.-]", "_", prefix) or "out"
        name = f"{safe}_{counter:05d}_.{ext}"
        return name, os.path.join(self.rt.output_dir, name)

    def node_SaveImage(self, inputs, ctx):
        frames = inputs.get("images")
        if not isinstance(frames, Frames):
            raise GraphError("SaveImage images must come from VAEDecode")
        prefix = str(inputs.get("filename_prefix", "out"))
        # filenames/counters assigned NOW (deterministic ordering across the
        # graph); pixel fetch + encode + write deferred so the worker can
        # overlap them with the next prompt's device compute
        names_paths = [self._out_path(prefix, "png", self._next_counter())
                       for _ in range(frames.frame_count)]

        def write():
            for frame, (_, path) in zip(frames.numpy(), names_paths):
                with open(path, "wb") as f:
                    f.write(array_to_png(frame))

        ctx.setdefault("deferred", []).append(write)
        return ([OutputFile(filename=name, kind="images")
                 for name, _ in names_paths],)

    def node_SaveAnimatedWEBP(self, inputs, ctx):
        frames = inputs.get("images")
        if not isinstance(frames, Frames):
            raise GraphError("SaveAnimatedWEBP images must come from VAEDecode")
        from PIL import Image

        fps = float(inputs.get("fps", 16))
        quality = int(inputs.get("quality", 90))
        lossless = bool(inputs.get("lossless", False))
        name, path = self._out_path(str(inputs.get("filename_prefix", "out")),
                                    "webp", self._next_counter())

        def write():
            imgs = [Image.fromarray(f) for f in frames.numpy()]
            imgs[0].save(path, format="WEBP", save_all=True,
                         append_images=imgs[1:],
                         duration=max(1, int(round(1000.0 / fps))), loop=0,
                         quality=quality, lossless=lossless)

        ctx.setdefault("deferred", []).append(write)
        return ([OutputFile(filename=name, kind="images")],)

    def node_SaveWEBM(self, inputs, ctx):
        frames = inputs.get("images")
        if not isinstance(frames, Frames):
            raise GraphError("SaveWEBM images must come from VAEDecode")
        exe = _ffmpeg()
        if exe is None:
            raise GraphError("SaveWEBM requires an ffmpeg binary in the image")
        fps = float(inputs.get("fps", 24))
        crf = int(inputs.get("crf", 32))
        codec = str(inputs.get("codec", "vp9"))
        name, path = self._out_path(str(inputs.get("filename_prefix", "out")),
                                    "webm", self._next_counter())

        def write():
            arr = frames.numpy()
            cmd = [exe, "-y", "-f", "rawvideo", "-pix_fmt", "rgb24",
                   "-s", f"{arr.shape[2]}x{arr.shape[1]}", "-r", str(fps),
                   "-i", "-", "-c:v", "libvpx-vp9" if codec == "vp9" else codec,
                   "-crf", str(crf), "-b:v", "0", "-pix_fmt", "yuv420p", path]
            proc = subprocess.run(cmd, input=arr.tobytes(),
                                  capture_output=True, check=False)
            if proc.returncode != 0:
                raise GraphError(
                    f"ffmpeg failed: {proc.stderr[-500:].decode(errors='replace')}")

        ctx.setdefault("deferred", []).append(write)
        return ([OutputFile(filename=name, kind="videos")],)

    # -- schema for /object_info --------------------------------------------
    def object_info(self) -> Dict[str, Any]:
        def req(**kw):
            return {"input": {"required": kw}}

        info = {
            "UNETLoader": req(unet_name=[self.rt.unet_names()],
                              weight_dtype=[["default", "fp8_e4m3fn"]]),
            "CLIPLoader": req(clip_name=[self.rt.clip_names()],
                              type=[["wan", "stable_diffusion"]],
                              device=[["default", "cpu"]]),
            "VAELoader": req(vae_name=[self.rt.vae_names()]),
            "CLIPTextEncode": req(text=["STRING"], clip=["CLIP"]),
            "EmptyHunyuanLatentVideo": req(width=["INT"], height=["INT"],
                                           length=["INT"], batch_size=["INT"]),
            "KSampler": req(model=["MODEL"], positive=["CONDITIONING"],
                            negative=["CONDITIONING"], latent_image=["LATENT"],
                            seed=["INT"], steps=["INT"], cfg=["FLOAT"],
                            sampler_name=[_SAMPLERS], scheduler=[_SCHEDULERS],
                            denoise=["FLOAT"]),
            "VAEDecode": req(samples=["LATENT"], vae=["VAE"]),
            "SaveImage": req(images=["IMAGE"], filename_prefix=["STRING"]),
            "SaveAnimatedWEBP": req(images=["IMAGE"], filename_prefix=["STRING"],
                                    fps=["FLOAT"], lossless=["BOOLEAN"],
                                    quality=["INT"], method=[["default"]]),
        }
        if _ffmpeg() is not None:
            info["SaveWEBM"] = req(images=["IMAGE"], filename_prefix=["STRING"],
                                   codec=[["vp9"]], fps=["FLOAT"], crf=["INT"])
        return info

    # -- execution -----------------------------------------------------------
    def execute(self, graph: Dict[str, Any], sample_hook=None,
                trace_parent=None):
        """Run a graph; returns ``(outputs, finish)``.

        ``outputs`` is the ComfyUI-style dict keyed by node id — complete,
        with final filenames.  Device compute is DISPATCHED but the files
        are not on disk until ``finish()`` runs (it fetches the video from
        the device and executes the save nodes' deferred writes); the worker
        calls it after dispatching the NEXT prompt, so one prompt's
        device→host transfer + encode overlaps the next one's compute.

        ``sample_hook(spec) -> Frames``: when given, VAEDecode records its
        SampleSpec through it instead of dispatching — the worker batches
        compatible specs from several queued graphs into one device program.

        ``trace_parent``: the prompt's trace span (worker thread — no
        contextvar); when set, each node's execute gets its own child span
        so a trace shows where graph RESOLUTION spent its time (VAEDecode
        under the worker's sample hook is plan-only here — device time
        lands in the ``finalize`` span's fetch).
        """
        for nid, node in graph.items():
            if not isinstance(node, dict):
                raise GraphError(f"node {nid} must be an object, got "
                                 f"{type(node).__name__}")
            ct = node.get("class_type")
            if not hasattr(self, f"node_{ct}"):
                raise GraphError(f"unknown node class_type {ct!r} (node {nid})")
            if ct == "SaveWEBM" and _ffmpeg() is None:
                raise GraphError("SaveWEBM requires an ffmpeg binary in the image")

        results: Dict[str, Tuple] = {}
        ctx = {} if sample_hook is None else {"sample_hook": sample_hook}
        outputs: Dict[str, Dict[str, List[Dict]]] = {}

        def resolve(nid: str, stack: Tuple[str, ...]) -> Tuple:
            if nid in results:
                return results[nid]
            if nid in stack:
                raise GraphError(f"cycle through node {nid}")
            node = graph.get(nid)
            if node is None:
                raise GraphError(f"edge to missing node {nid}")
            inputs = {}
            for key, val in (node.get("inputs") or {}).items():
                if (isinstance(val, list) and len(val) == 2
                        and isinstance(val[0], str) and isinstance(val[1], int)):
                    src = resolve(val[0], stack + (nid,))
                    if val[1] >= len(src):
                        raise GraphError(f"node {val[0]} has no output {val[1]}")
                    inputs[key] = src[val[1]]
                else:
                    inputs[key] = val
            fn = getattr(self, f"node_{node['class_type']}")
            t0 = time.perf_counter()
            node_span = (self.tracer.start_span(
                f"node_{node['class_type']}", parent=trace_parent,
                attrs={"node_id": nid}) if trace_parent is not None else None)
            try:
                out = fn(inputs, ctx)
            except BaseException as e:
                if node_span is not None:
                    node_span.set_attribute("error", str(e))
                    node_span.end(status="error")
                raise
            if node_span is not None:
                node_span.end()
            # per-node execute span; note under the worker's sample hook
            # VAEDecode is plan-only here — its device time shows up as the
            # dispatch/finalize phases, not in this histogram
            dt = time.perf_counter() - t0
            self.metrics["tpustack_graph_node_latency_seconds"].labels(
                node_class=node["class_type"]).observe(dt)
            if self.flight is not None:
                self.flight.record("node", class_type=node["class_type"],
                                   node_id=nid, seconds=round(dt, 6))
            results[nid] = out
            if out and isinstance(out[0], list) and out[0] and isinstance(out[0][0], OutputFile):
                by_kind: Dict[str, List[Dict]] = {}
                for f in out[0]:
                    by_kind.setdefault(f.kind, []).append(f.as_history())
                outputs[nid] = by_kind
            return out

        for nid in sorted(graph, key=lambda s: (len(s), s)):
            resolve(nid, ())
        deferred = ctx.get("deferred", [])

        def finish():
            for write in deferred:
                write()

        return outputs, finish


# -------------------------------------------------------------------- server
@dataclass
class HistoryEntry:
    prompt_id: str
    client_id: str
    completed: bool = False
    status_str: str = "pending"
    messages: List[str] = field(default_factory=list)
    outputs: Dict[str, Any] = field(default_factory=dict)
    # tenant cost accounting: set once at submit (before the entry is
    # shared), read by the worker at plan/finalize — the graph analog of
    # SlotRequest.tenant
    tenant: Optional[str] = None
    # QoS priority class, same capture point: the worker counts the
    # prompt's per-priority outcome at its publish/refuse points (the
    # accept-and-poll analog of the middleware's status-derived count)
    priority: Optional[str] = None

    def as_json(self) -> Dict[str, Any]:
        return {"status": {"completed": self.completed,
                           "status_str": self.status_str,
                           "messages": list(self.messages)},
                "outputs": self.outputs}


class GraphServer:
    """aiohttp app + one background worker thread (one chip, one queue —
    same serialisation stance as the sd15 server).

    The worker pipelines consecutive prompts: prompt k+1's device compute is
    dispatched BEFORE prompt k's deferred saves run, so k's >1 s video
    fetch + encode overlaps k+1's sampling (the same one-in-flight pattern
    as the SD15 micro-batcher; +~15% back-to-back video throughput)."""

    def __init__(self, runtime: Optional[WanRuntime] = None, registry=None,
                 tracer=None):
        self.rt = runtime or WanRuntime()
        self._registry = registry
        self.metrics = obs_catalog.build(registry)
        obs_device.install(registry)
        self.tracer = tracer if tracer is not None else obs_trace.TRACER
        # tenant cost ledger: process-wide on the default registry, private
        # per injected test Registry (the tracer's isolation contract)
        self.ledger = obs_accounting.for_registry(registry)
        # multi-tenant QoS (tpustack.serving.qos): priority resolution +
        # quota/priority-aware admission via the resilience middleware;
        # outcome counts land at the worker's publish/refuse points
        # (accept-and-poll: the HTTP status can't carry the verdict)
        from tpustack.serving import qos as qos_mod

        self.qos = qos_mod.QosPolicy.from_env(registry=registry)
        if self.qos is not None:
            self.ledger.add_listener(self.qos.on_ledger_charge)
        # engine flight recorder: per-node records from graph resolution
        # plus per-dispatch/finalize records from the worker, served on
        # /debug/flight and dumped by the resilience post-mortem hooks
        self.flight = obs_flight.register(obs_flight.FlightRecorder(
            "graph", meta={"max_batch": int(os.environ.get("WAN_MAX_BATCH",
                                                           "4"))}))
        self.executor = GraphExecutor(self.rt, registry=registry,
                                      tracer=self.tracer,
                                      flight=self.flight)
        self._queue: "queue.Queue[Optional[str]]" = queue.Queue()
        # event-loop handlers and the worker thread share every dict below;
        # all of them ride self._lock (tpulint TPL201 enforces the
        # annotations — dict ops are GIL-atomic individually, but the
        # worker's pop-check-update sequences are not)
        self._pending: Dict[str, Dict] = {}  # guarded-by: _lock
        # accept-and-poll tracing: /prompt returns in ~1ms while the worker
        # runs minutes, so the HTTP root span ends long before the work —
        # each accepted prompt opens a "prompt" child span here, ended by
        # the worker at publish; the tracer holds the trace open until then
        self._prompt_spans: Dict[str, obs_trace.Span] = {}  # guarded-by: _lock
        self._history: Dict[str, HistoryEntry] = {}  # guarded-by: _lock
        self._running: List[str] = []  # guarded-by: _lock
        self._no_batch: set = set()  # signatures whose batched build failed
        # (worker-thread private: written and read only from _work's paths)
        self._lock = threading.Lock()
        self.max_batch = max(1, int(os.environ.get("WAN_MAX_BATCH", "4")))
        # per-prompt absolute deadlines (monotonic); the worker refuses to
        # start a prompt past its deadline (phase=queued) — there is no
        # long-lived HTTP request to 504, so the verdict lands in /history
        self._deadline_at: Dict[str, float] = {}  # guarded-by: _lock
        # shared resilience layer: drain on SIGTERM, queued-prompt
        # deadlines, 429 backpressure, hung-dispatch watchdog, TPUSTACK_
        # FAULT_* hooks.  /prompt answers immediately, so drain must wait
        # on the worker's accepted-but-unfinished prompts, not on open
        # HTTP requests
        # observe_http=False: /prompt answers in ~1ms while the prompt runs
        # minutes — Retry-After must come from real submit→publish times,
        # fed in _finalize, or shed clients would be told to retry in ~1s
        self.resilience = ResilienceManager(
            "graph", registry, concurrency=self.max_batch,
            queue_depth=self._queue.qsize,
            extra_busy=self._graph_busy, observe_http=False,
            expected_service_s=60.0, qos=self.qos)  # video prompts run minutes, and the
        # cold-start seed must say so before the first publish is observed
        self._t_submit: Dict[str, float] = {}  # guarded-by: _lock
        # serialises device dispatch against an in-progress /profile
        # capture: the worker's _dispatch_one and the profile handler both
        # hold it, so a prompt accepted AFTER the profile's busy-check
        # blocks until the capture ends instead of racing into it
        self._profile_lock = threading.RLock()  # RLock: the serial
        # fallback path re-enters _dispatch_one per member
        sanitize.install_guards(self)
        self._worker = threading.Thread(target=self._work, daemon=True,
                                        name="wan-graph-worker")
        self._worker.start()

    def _graph_busy(self) -> bool:
        """Accepted work the drain loop must wait for: queued, planned, or
        dispatched-but-unpublished prompts."""
        with self._lock:
            if self._running or self._pending:
                return True
        return not self._queue.empty()

    # ---- worker
    def _work(self):
        """Queue loop with BATCHED dispatch: up to ``WAN_MAX_BATCH`` queued
        prompts are planned together (graphs resolve with a sample hook, no
        device work), their compatible SampleSpecs fuse into ONE batched
        device program (CFG text encode + the whole denoise loop + VAE
        decode stream the weights once for all of them), and the previous
        wave's deferred saves run while the new wave computes.  If an
        upcoming dispatch signature is COLD (a multi-minute full-size XLA
        build), the previous wave is published FIRST so finished prompts
        never sit unpublished behind a compile (ADVICE r3)."""
        max_batch = self.max_batch
        in_flight: List[Tuple] = []  # (pid, entry, outputs, finish)
        stop = False
        while not stop:
            if in_flight:
                # opportunistic: only keep the previous wave pending if more
                # work is already queued to overlap with
                try:
                    pid = self._queue.get_nowait()
                except queue.Empty:
                    for f in in_flight:
                        self._finalize(*f)
                    in_flight = []
                    continue
            else:
                pid = self._queue.get()
            if pid is None:
                break
            pids = [pid]
            while len(pids) < max_batch:
                try:
                    nxt = self._queue.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                pids.append(nxt)
            self.metrics["tpustack_graph_queue_depth"].set(self._queue.qsize())

            # plan every graph (cheap — device work deferred to the hook)
            plans = []  # (pid, entry, outputs, finish, specs, pspan)
            for pid in pids:
                with self._lock:
                    graph = self._pending.pop(pid, None)
                    self._running.append(pid)
                    entry = self._history[pid]
                    pspan = self._prompt_spans.pop(pid, None)
                    # same lock as submit's writes: popping outside it
                    # could interleave with a submit still stamping the
                    # deadline (tpulint TPL201 found the original unlocked
                    # pops here)
                    deadline = self._deadline_at.pop(pid, None)
                    t_submit = self._t_submit.get(pid)
                if t_submit is not None:
                    # queue-seconds: submit → worker pickup (charged
                    # outside the lock — the ledger has its own)
                    wait_s = time.monotonic() - t_submit
                    self.ledger.charge_queue_seconds(
                        "graph", entry.tenant, wait_s)
                    if self.qos is not None:
                        self.qos.observe_queue_wait(
                            "graph", entry.priority, wait_s)
                if deadline is not None and time.monotonic() > deadline:
                    # expired while queued: refuse to start it (its device
                    # work would be wasted), publish the verdict in history
                    self.resilience.note_deadline("queued")
                    self.metrics["tpustack_graph_prompts_total"].labels(
                        status="error").inc()
                    self.ledger.note_outcome("graph", entry.tenant,
                                             "deadline")
                    self._note_qos_outcome(entry, "deadline")
                    if pspan is not None:
                        pspan.add_event("deadline_exceeded", phase="queued")
                        pspan.end(status="error")
                    with self._lock:
                        self._t_submit.pop(pid, None)
                        entry.status_str = "error"
                        entry.messages.append(
                            "DeadlineExceeded: request deadline exceeded "
                            "(phase=queued)")
                        entry.completed = True
                        self._running.remove(pid)
                    continue
                specs: List[Tuple[SampleSpec, Frames]] = []

                def hook(spec, specs=specs):
                    pipe = self.rt.pipeline()
                    fr = Frames(n_frames=pipe.pixel_frame_count(
                        spec.latent.frames))
                    specs.append((spec, fr))
                    return fr

                try:
                    outputs, finish = self.executor.execute(
                        graph, sample_hook=hook, trace_parent=pspan)
                except Exception as e:  # noqa: BLE001 — via /history
                    log.exception("prompt %s failed", pid)
                    self.metrics["tpustack_graph_prompts_total"].labels(
                        status="error").inc()
                    self.ledger.note_outcome("graph", entry.tenant, "error")
                    self._note_qos_outcome(entry, "error")
                    if pspan is not None:
                        pspan.set_attribute("error",
                                            f"{type(e).__name__}: {e}")
                        pspan.end(status="error")
                    with self._lock:
                        self._t_submit.pop(pid, None)
                        entry.status_str = "error"
                        entry.messages.append(f"{type(e).__name__}: {e}")
                        entry.completed = True
                        self._running.remove(pid)
                    continue
                plans.append((pid, entry, outputs, finish, specs, pspan))

            plan = self._dispatch_plan(self._group_specs(plans))
            if in_flight and self._any_cold(plan):
                for f in in_flight:  # publish before blocking on a compile
                    self._finalize(*f)
                in_flight = []
            for key, chunk in plan:
                self._dispatch_one(key, chunk)
                # prompt-wave boundary (worker thread): watchdog beat +
                # the injected mid-request SIGTERM point
                self.resilience.progress("wave")
            for f in in_flight:
                self._finalize(*f)
            in_flight = [(pid, entry, outputs, finish, pspan)
                         for pid, entry, outputs, finish, _, pspan in plans]
        for f in in_flight:
            self._finalize(*f)

    @staticmethod
    def _spec_key(spec: SampleSpec):
        l = spec.latent
        return (l.width, l.height, l.frames, spec.steps, spec.cfg,
                spec.sampler_name)

    def _group_specs(self, plans):
        groups: Dict[Tuple, List[Tuple[SampleSpec, Frames]]] = {}
        for _, _, _, _, specs, _ in plans:
            for spec, fr in specs:
                groups.setdefault(self._spec_key(spec), []).append((spec, fr))
        return groups

    def _dispatch_plan(self, groups):
        """Split groups into the ACTUAL dispatch chunks (pixel budget +
        known-unbatchable signatures) so cold-compile checks judge the
        batch sizes that will really run, not the pre-split group size."""
        plan = []
        if not groups:
            # a wave of device-free graphs (text-encode-only probes) must
            # not force the multi-minute pipeline build
            return plan
        pipe = self.rt.pipeline()
        for key, members in groups.items():
            width, height, frames_n = key[0], key[1], key[2]
            # budget against DECODED pixel-frames (16 requested -> 13
            # decoded under the floor convention), the pixels that
            # actually hit HBM — not the requested count
            per = max(1, pipe.pixel_frame_count(frames_n)) * height * width
            max_b = max(1, PIXEL_BUDGET // per)
            if key in self._no_batch:
                max_b = 1
            for lo in range(0, len(members), max_b):
                plan.append((key, members[lo:lo + max_b]))
        return plan

    def _any_cold(self, plan) -> bool:
        if not plan:
            return False
        pipe = self.rt.pipeline()
        return any(not pipe.is_warm(
            batch_size=len(chunk), frames=key[2], steps=key[3],
            width=key[0], height=key[1], sampler=key[5])
            for key, chunk in plan)


    def _dispatch_one(self, key, members) -> None:
        # mutually exclusive with an in-progress /profile capture: a
        # prompt accepted after the profile's busy-check waits here
        # instead of leaking foreign device work into the xplane
        with self._profile_lock:
            self._dispatch_one_inner(key, members)

    def _dispatch_one_inner(self, key, members) -> None:
        width, height, frames_n, steps, cfg, sampler = key
        pipe = self.rt.pipeline()
        t0 = time.perf_counter()
        try:
            # pre-dispatch progress point (worker thread): watchdog beat +
            # TPUSTACK_FAULT_* slow-prefill / device-error / hang hooks; an
            # injected error rides the existing dispatch-failure paths
            self.resilience.progress("prefill")
            if len(members) == 1:
                spec = members[0][0]
                log.info("Sampling: %dx%d f=%d steps=%d cfg=%.1f "
                         "sampler=%s seed=%d", width, height, frames_n,
                         steps, cfg, sampler, spec.seed)
                vid = pipe.generate_async(
                    spec.positive.text,
                    negative_prompt=spec.negative.text, frames=frames_n,
                    steps=steps, guidance_scale=cfg, seed=spec.seed,
                    width=width, height=height, sampler=sampler)
            else:
                log.info("Sampling BATCH of %d: %dx%d f=%d steps=%d "
                         "cfg=%.1f sampler=%s", len(members), width,
                         height, frames_n, steps, cfg, sampler)
                vid = pipe.generate_many_async(
                    [{"prompt": s.positive.text,
                      "negative_prompt": s.negative.text,
                      "seed": s.seed} for s, _ in members],
                    frames=frames_n, steps=steps, guidance_scale=cfg,
                    width=width, height=height, sampler=sampler)
        except Exception as e:  # noqa: BLE001
            if len(members) > 1:
                # batched build failed (typically compile-time HBM OOM at a
                # shape the pixel budget admitted): remember, serve serially
                log.warning("batched dispatch of %d failed (%s); falling "
                            "back to serial for this signature",
                            len(members), e)
                self.metrics["tpustack_graph_batch_fallback_total"].inc()
                self._no_batch.add(key)
                for m in members:
                    self._dispatch_one(key, [m])
                return
            log.exception("dispatch failed")
            for _, fr in members:
                fr.error = e
            return
        # Frame-convention guard OUTSIDE the try: a drift between the
        # pipeline's decode and the server's planned Frames is deterministic
        # — routing it through the batched-build-failure path would
        # blacklist the signature and re-run every member serially at full
        # generation cost, each failing identically.  (Shape metadata is
        # available without blocking the async dispatch.)
        if int(vid.shape[1]) != members[0][1].n_frames:
            err = GraphError(
                f"decoded frame count {int(vid.shape[1])} != planned "
                f"{members[0][1].n_frames} — frame-convention drift "
                "between pipeline and server")
            log.error("%s", err)
            for _, fr in members:
                fr.error = err
            return
        for i, (_, fr) in enumerate(members):
            fr.array = vid[i]
        # host-side dispatch span (async: device compute continues after it;
        # the device wall time lands in the finalize span's fetch)
        dispatch_s = time.perf_counter() - t0
        tr = Trace()
        tr.add("dispatch", dispatch_s)
        tr.observe_into(self.metrics["tpustack_request_phase_latency_seconds"],
                        server="graph")
        self.flight.record(
            "dispatch", batch=len(members), width=width, height=height,
            frames=frames_n, steps=steps, sampler=sampler,
            dispatch_s=round(dispatch_s, 6),
            queue_depth=self._queue.qsize())

    def _note_qos_outcome(self, entry: HistoryEntry, outcome: str) -> None:
        """Per-priority goodput count at the worker's publish/refuse
        points — the accept-and-poll analog of the middleware's
        status-derived count (the /prompt 200 said nothing about whether
        the work succeeded).  No-op with QoS off (no priority resolved)."""
        if self.qos is None or entry.priority is None:
            return
        self.metrics["tpustack_qos_requests_total"].labels(
            server="graph", priority=entry.priority, outcome=outcome).inc()

    def _finalize(self, pid, entry, outputs, finish, pspan=None):
        """Run deferred saves (fetch + encode + write) and publish."""
        self.resilience.beat()  # publishing is progress too
        tr = Trace()
        fspan = (self.tracer.start_span("finalize", parent=pspan)
                 if pspan is not None else None)
        t_fin = time.perf_counter()
        try:
            with tr.span("finalize"):
                finish()
            if fspan is not None:
                fspan.end()
            finalize_s = time.perf_counter() - t_fin
            self.flight.record("finalize", prompt_id=pid, status="success",
                               finalize_s=round(finalize_s, 6))
            # tenant attribution: the prompt's device wall time lands in
            # this finalize fetch (dispatch was async) — charge it, and
            # the goodput outcome, to the submitting tenant
            self.ledger.charge_chip_seconds("graph", entry.tenant,
                                            finalize_s)
            self.ledger.note_outcome("graph", entry.tenant, "ok")
            self._note_qos_outcome(entry, "ok")
            tr.observe_into(
                self.metrics["tpustack_request_phase_latency_seconds"],
                server="graph")
            with self._lock:  # status_str before completed: pollers treat
                entry.outputs = outputs       # completed+non-success as failure
                entry.status_str = "success"
                entry.completed = True
                t_submit = self._t_submit.pop(pid, None)
            if pspan is not None:
                pspan.end()  # publishes the trace (last open span)
            self.metrics["tpustack_graph_prompts_total"].labels(
                status="success").inc()
            # the Retry-After basis: true submit→publish wall time
            if t_submit is not None:
                self.resilience.observe_service_time(
                    time.monotonic() - t_submit)
        except Exception as e:  # noqa: BLE001 — surfaced via /history
            log.exception("prompt %s failed", pid)
            self.flight.record("finalize", prompt_id=pid, status="error",
                               error=f"{type(e).__name__}: {e}",
                               finalize_s=round(
                                   time.perf_counter() - t_fin, 6))
            self.ledger.note_outcome("graph", entry.tenant, "error")
            self._note_qos_outcome(entry, "error")
            if fspan is not None:
                fspan.end(status="error")
            if pspan is not None:
                pspan.set_attribute("error", f"{type(e).__name__}: {e}")
                pspan.end(status="error")
            self.metrics["tpustack_graph_prompts_total"].labels(
                status="error").inc()
            with self._lock:
                entry.status_str = "error"
                entry.messages.append(f"{type(e).__name__}: {e}")
                entry.completed = True
        finally:
            with self._lock:
                self._t_submit.pop(pid, None)  # error paths must not leak
                if pid in self._running:
                    self._running.remove(pid)
        return None

    def shutdown(self):
        self._queue.put(None)
        self.resilience.close()

    # ---- handlers
    async def queue_state(self, request: web.Request) -> web.Response:
        with self._lock:
            running = [[i, pid] for i, pid in enumerate(self._running)]
            pending = [[0, pid] for pid in self._pending]
        return web.json_response({"queue_running": running,
                                  "queue_pending": pending})

    async def object_info(self, request: web.Request) -> web.Response:
        return web.json_response(self.executor.object_info())

    async def submit(self, request: web.Request) -> web.Response:
        try:
            body = await obs_http.request_json(request)
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        graph = body.get("prompt")
        rejected = self.metrics["tpustack_graph_prompts_total"]
        if not isinstance(graph, dict) or not graph:
            rejected.labels(status="rejected").inc()
            return web.json_response({"error": "missing prompt graph"}, status=400)
        for nid, node in graph.items():
            if not isinstance(node, dict):
                rejected.labels(status="rejected").inc()
                return web.json_response(
                    {"error": f"node {nid} must be an object"}, status=400)
            ct = node.get("class_type")
            if not hasattr(self.executor, f"node_{ct}"):
                rejected.labels(status="rejected").inc()
                return web.json_response(
                    {"error": f"unknown node class_type {ct!r} (node {nid})"},
                    status=400)
        try:
            deadline_s = self.resilience.deadline(body.get("timeout_s"))
        except (TypeError, ValueError) as e:
            rejected.labels(status="rejected").inc()
            return web.json_response({"error": f"bad timeout_s: {e}"},
                                     status=400)
        pid = str(uuid.uuid4())
        entry = HistoryEntry(prompt_id=pid,
                             client_id=str(body.get("client_id", "")),
                             tenant=obs_accounting.current_tenant.get(),
                             priority=request.get("priority"))
        parent = obs_trace.current_span.get()
        with self._lock:
            self._history[pid] = entry
            self._pending[pid] = graph
            if parent is not None:
                # deliberately NOT ended here: the worker ends it at
                # publish, so the client's trace id covers the accepted
                # prompt's whole submit→publish lifetime even though this
                # HTTP request answers in ~1ms
                self._prompt_spans[pid] = self.tracer.start_span(
                    "prompt", parent=parent, attrs={"prompt_id": pid})
            # deadline/submit stamps ride the same lock as the worker's
            # pops: the worker is concurrently popping OTHER prompts out
            # of these dicts while this handler inserts
            if deadline_s is not None:
                self._deadline_at[pid] = time.monotonic() + deadline_s
            self._t_submit[pid] = time.monotonic()
            number = len(self._history)
        self._queue.put(pid)
        self.metrics["tpustack_graph_queue_depth"].set(self._queue.qsize())
        return web.json_response({"prompt_id": pid, "number": number})

    async def history(self, request: web.Request) -> web.Response:
        pid = request.match_info["prompt_id"]
        with self._lock:  # serialise under the lock — the worker mutates entries
            entry = self._history.get(pid)
            payload = {} if entry is None else {pid: entry.as_json()}
        return web.json_response(payload)

    async def view(self, request: web.Request) -> web.Response:
        filename = request.query.get("filename", "")
        subfolder = request.query.get("subfolder", "")
        base = os.path.realpath(self.rt.output_dir)
        path = os.path.realpath(os.path.join(base, subfolder, filename))
        # keep /view inside the output dir (the reference trusts ComfyUI here)
        if not path.startswith(base + os.sep) or not os.path.isfile(path):
            return web.json_response({"error": "not found"}, status=404)
        # FileResponse streams from disk without blocking the event loop
        return web.FileResponse(path)

    async def healthz(self, request: web.Request) -> web.Response:
        """Liveness + worker state (503 only on a watchdog-declared hang)."""
        with self._lock:
            running, pending = len(self._running), len(self._pending)
        status, payload = self.resilience.health_payload(extra={
            "worker_alive": self._worker.is_alive(),
            "running": running,
            "pending": pending,
            "png_encoder": runtime.encoder(),
        })
        return web.json_response(payload, status=status,
                                 headers=self.resilience.health_headers(status))

    async def readyz(self, request: web.Request) -> web.Response:
        status, payload = self.resilience.ready_payload()
        return web.json_response(payload, status=status,
                                 headers=self.resilience.ready_headers(status))

    async def profile(self, request: web.Request) -> web.Response:
        """Capture an XLA/TPU profile (xplane) around one graph execution
        — the SD server's ``POST /profile`` contract on the graph surface
        (``tpustack.obs.profile``).  Body: ``{prompt?: <graph>}``; the
        default graph is a symbolic text-encode (cheap smoke) — POST a
        real KSampler graph to capture the denoise.  Refuses with 409
        while the worker holds accepted prompts: a capture must contain
        only the profiled run, and this server's device work is
        serialised by the worker, not a lock."""
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            body = {}
        if body is not None and not isinstance(body, dict):
            return web.json_response({"detail": "body must be a JSON "
                                      "object"}, status=422)
        graph = (body or {}).get("prompt") or {
            "1": {"class_type": "CLIPTextEncode",
                  "inputs": {"text": "profile capture"}}}
        if not isinstance(graph, dict) or not graph:
            return web.json_response({"detail": "prompt must be a node "
                                      "graph"}, status=422)
        for nid, node in graph.items():
            ct = node.get("class_type") if isinstance(node, dict) else None
            if not hasattr(self.executor, f"node_{ct}"):
                return web.json_response(
                    {"detail": f"unknown node class_type {ct!r} "
                               f"(node {nid})"}, status=400)
        def run():
            self.resilience.beat()  # a cold pipeline build inside the
            # capture must not trip the watchdog
            outputs, finish = self.executor.execute(graph)
            finish()

        def capture_exclusive():
            # hold the dispatch lock for the WHOLE capture and re-check
            # busy under it: a /prompt accepted after the handler's check
            # blocks at _dispatch_one instead of racing its device work
            # into this xplane
            with self._profile_lock:
                if self._graph_busy():
                    return None
                return obs_profile.capture(obs_profile.base_dir("graph"),
                                           run)

        if self._graph_busy():
            return web.json_response(
                {"detail": "worker busy — retry when accepted prompts "
                           "have published"}, status=409,
                headers=shed_headers("busy",
                                     self.resilience.retry_after_s()))
        try:
            out = await asyncio.get_running_loop().run_in_executor(
                None, capture_exclusive)
        except GraphError as e:
            return web.json_response({"detail": str(e)}, status=400)
        if out is None:  # lost the race to an accepted prompt
            return web.json_response(
                {"detail": "worker busy — retry when accepted prompts "
                           "have published"}, status=409,
                headers=shed_headers("busy",
                                     self.resilience.retry_after_s()))
        return web.json_response(out)

    def build_app(self) -> web.Application:
        # outcome_accounting="refusals": /prompt is accept-and-poll (it
        # 200s in ~1ms regardless of how the prompt later fares), so
        # per-tenant ok/error/deadline outcomes are counted at the
        # worker's publish/refuse points — but shed (429/503) and
        # rejected (4xx) requests never reach the worker, so the
        # middleware still counts the non-ok statuses
        work = {"/prompt"}
        app = web.Application(
            client_max_size=4 << 20,
            middlewares=[obs_http.instrument("graph", self._registry,
                                             tracer=self.tracer,
                                             ledger=self.ledger,
                                             work_endpoints=work,
                                             outcome_accounting="refusals"),
                         self.resilience.middleware(work)])
        obs_http.add_debug_trace_routes(app, self.tracer)
        obs_http.add_debug_flight_routes(app, self.flight)
        obs_http.add_debug_tenant_routes(app, self.ledger, qos=self.qos)
        app.router.add_get("/queue", self.queue_state)
        app.router.add_get("/object_info", self.object_info)
        app.router.add_get("/metrics",
                           obs_http.make_metrics_handler(self._registry))
        app.router.add_post("/profile", self.profile)
        app.router.add_post("/prompt", self.submit)
        app.router.add_get("/history/{prompt_id}", self.history)
        app.router.add_get("/view", self.view)
        app.router.add_get("/healthz", self.healthz)
        app.router.add_get("/readyz", self.readyz)
        return app


def main() -> None:
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    # honours JAX_COMPILATION_CACHE_DIR (the Deployment contract); dev-box
    # fallback to <repo>/.cache/xla — without it every server start pays
    # the full multi-minute Wan compile
    enable_compile_cache()
    # build/load the native PNG encoder before serving (never inside a
    # request) and say which encoder this process ended up with
    log.info("PNG encoder: %s", runtime.encoder())
    _text_quant(os.environ.get("WAN_PRESET", "wan_1_3b"))  # fail fast on typo
    port = int(os.environ.get("PORT", "8181"))
    server = GraphServer()
    log.info("Wan graph server on :%d (models=%s, outputs=%s)",
             port, server.rt.models_dir, server.rt.output_dir)
    # SIGTERM → graceful drain: stop accepting /prompt (503), let the
    # worker publish every accepted prompt, exit 0 within the drain budget
    server.resilience.install_signal_handlers()
    web.run_app(server.build_app(), port=port, access_log=None,
                handle_signals=False)


if __name__ == "__main__":
    main()
