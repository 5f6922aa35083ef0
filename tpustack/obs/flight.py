"""Engine flight recorder + live roofline attribution.

The request-level observability layers (metrics, traces, SLOs) answer
"how are requests doing"; this module answers "what was the ENGINE doing"
— the question every post-mortem starts with when the watchdog fires or a
wave stalls, and the question the scale-out layer asks live ("how close
to the hardware are we") before adding a replica.

Two halves, one data structure:

- :class:`FlightRecorder` — a dependency-free, lock-cheap ring buffer
  (``TPUSTACK_FLIGHT_RECORDS``, default 4096) that each serving engine
  feeds ONE structured host-side record per dispatch: the LLM continuous
  engine per wave (slot occupancy, tokens emitted, spec drafted/accepted,
  stride, kv-pool free/used/fragmentation, queue depth, wave wall time,
  trace id of the slowest in-flight request), SD per fused batch (window
  size, riders, denoise/encode split), graph per resolved node.  The
  ring is exposed as ``GET /debug/flight`` (recent records + windowed
  aggregates) on all three servers and the metrics sidecar, and
  **auto-dumped to a JSON artifact** (``TPUSTACK_FLIGHT_DUMP_DIR``) on
  watchdog fire, SIGTERM drain, fatal engine error, and sanitizer
  violation — so "what were the last 512 things the engine did" survives
  the pod.

- **Live roofline attribution** — per-token model FLOPs and per-step HBM
  bytes computed from the model config/params (:func:`llm_wave_arith`,
  the SAME arithmetic ``tools/bench_llm.py`` reports offline) divided by
  :func:`tpustack.utils.peaks.device_peaks`, applied to the recorder's
  windowed rates: ``tpustack_llm_mfu_ratio``,
  ``tpustack_llm_hbm_util_ratio``, ``tpustack_sd_mfu_ratio`` (all
  labelled by ``device_kind`` and OMITTED, never faked, when the device
  kind is unknown — the peaks.py contract), plus the always-available
  ``tpustack_llm_wave_occupancy_slots`` and
  ``tpustack_llm_spec_efficiency_tokens`` gauges.

Everything here is host-side bookkeeping over values the engines already
hold at their fetch boundaries — recording a wave costs one dict build
and one deque append under an uncontended lock, and NEVER syncs the
device.  Dumps are best-effort by construction: a full disk or an
unwritable dir logs and returns None instead of taking the server down.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from tpustack.utils import knobs

__all__ = [
    "FlightRecorder", "PhaseClock", "gc_attach", "gc_detach", "register",
    "recorders", "dump_all", "snapshot_all",
    "device_peaks_info", "llm_wave_arith", "llm_utilization",
    "sd_utilization",
]

#: every live recorder in the process, weakly held — ``dump_all`` (the
#: watchdog / drain / sanitizer post-mortem hook) walks these; a recorder
#: dies with its server, so a test's dead servers never dump
_RECORDERS: "weakref.WeakSet[FlightRecorder]" = weakref.WeakSet()
_REG_LOCK = threading.Lock()
#: process-global dump counter: several recorders may share a server name
#: (tests, multi-engine processes) and dump in the same event — filenames
#: must never collide and overwrite one another's post-mortem
_DUMP_SEQ = [0]


class FlightRecorder:
    """Ring buffer of per-dispatch engine records for ONE server.

    ``meta`` is static context stamped into every snapshot/dump (model
    name, slot count, chunk — whatever makes the artifact readable on
    its own).  Records are plain JSON-able dicts; ``record`` stamps a
    monotonically increasing ``seq`` and a wall-clock ``ts``.
    """

    def __init__(self, server: str, capacity: Optional[int] = None,
                 meta: Optional[Dict] = None):
        if capacity is None:
            capacity = knobs.get_int("TPUSTACK_FLIGHT_RECORDS")
        self.server = server
        self.capacity = max(1, int(capacity))
        self.meta: Dict = dict(meta or {})
        # ring/seq mutations all hold _lock (engine threads feed while
        # handlers snapshot); kept out of the sanitizer registry — the
        # recorder is itself part of the post-mortem path and must stay
        # side-effect-free under a raising sanitizer
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._seq = 0
        self._dumps = 0

    # ------------------------------------------------------------- feeding
    def record(self, kind: str, **fields) -> Dict:
        """Append one record.  Cheap and lock-bounded — safe from engine
        threads at wave cadence."""
        rec = {"kind": kind, "ts": time.time()}
        rec.update(fields)
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._ring.append(rec)
        return rec

    # ------------------------------------------------------------- reading
    def recent(self, n: Optional[int] = None) -> List[Dict]:
        """Newest-last copy of the ring (the last ``n`` when given)."""
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-max(0, int(n)):]

    def last(self) -> Optional[Dict]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def aggregates(self, window_s: Optional[float] = None) -> Dict:
        """Windowed aggregates over the ring (``window_s`` None = all
        retained records).  Per-kind counts always; engine-shape rollups
        (wave rates, occupancy, spec efficiency, SD batch rates) when the
        matching records exist.  Rates use the first→last record span, so
        they read as "over the recent window", idle gaps included."""
        records = self.recent()
        if window_s:
            cutoff = time.time() - float(window_s)
            records = [r for r in records if r["ts"] >= cutoff]
        out: Dict = {"records": len(records), "window_s": window_s,
                     "kinds": {}}
        for r in records:
            out["kinds"][r["kind"]] = out["kinds"].get(r["kind"], 0) + 1
        waves = [r for r in records if r["kind"] in ("wave", "verify")]
        if waves:
            span = waves[-1]["ts"] - waves[0]["ts"]
            tokens = sum(r.get("tokens", 0) for r in waves)
            passes = sum(r.get("weight_passes", 0) for r in waves)
            drafted = sum(r.get("drafted", 0) for r in waves)
            accepted = sum(r.get("accepted", 0) for r in waves)
            occ = [r["occupancy"] for r in waves if "occupancy" in r]
            wave_s = [r["wave_s"] for r in waves
                      if r.get("wave_s") is not None]
            out.update({
                "waves": len(waves),
                "tokens": tokens,
                "mean_occupancy": (sum(occ) / len(occ)) if occ else None,
                "tokens_per_s": tokens / span if span > 0 else None,
                "weight_passes_per_s": passes / span if span > 0 else None,
                "tokens_per_weight_pass": (tokens / passes if passes
                                           else None),
                "mean_wave_s": (sum(wave_s) / len(wave_s)) if wave_s
                else None,
                "spec_drafted": drafted,
                "spec_accepted": accepted,
                "spec_acceptance": accepted / drafted if drafted else None,
            })
            # per-tenant slot-occupancy rollup: summing each wave's
            # tenants map weighted by its wall time gives the same
            # chip-second split the tenant ledger charges (the records
            # ARE the ledger's source) — /debug/flight can answer "who
            # was on the chip this window" without the ledger
            tenant_s: Dict[str, float] = {}
            for r in waves:
                if r.get("tenants") and r.get("wave_s"):
                    occ = sum(r["tenants"].values())
                    for tenant, n in r["tenants"].items():
                        tenant_s[tenant] = (tenant_s.get(tenant, 0.0)
                                            + r["wave_s"] * n / occ)
            if tenant_s:
                out["tenant_chip_seconds"] = {
                    t: round(v, 6) for t, v in sorted(tenant_s.items())}
            lastw = waves[-1]
            for k in ("queue_depth", "kv_free", "kv_used",
                      "kv_fragmentation"):
                if k in lastw:
                    out[f"{k}_last"] = lastw[k]
            slow = [r for r in waves if r.get("slowest_trace_id")]
            if slow:
                out["slowest_trace_id"] = slow[-1]["slowest_trace_id"]
                out["slowest_age_s"] = slow[-1].get("slowest_age_s")
        prefills = [r for r in records if r["kind"] == "prefill"]
        if prefills:
            ts = [r["prefill_s"] for r in prefills if "prefill_s" in r]
            out["prefills"] = len(prefills)
            out["mean_prefill_s"] = (sum(ts) / len(ts)) if ts else None
        batches = [r for r in records if r["kind"] == "batch"]
        if batches:
            span = batches[-1]["ts"] - batches[0]["ts"]
            images = sum(r.get("batch", 0) for r in batches)
            denoise = sum(r.get("denoise_vae_s", 0.0) for r in batches)
            # the FLOP-rate numerator and denominator must cover the SAME
            # batches: an uncostable signature (cost analysis failed →
            # flops None) contributes neither, or its busy seconds would
            # deflate the MFU below the true utilization
            costed = [r for r in batches if r.get("flops") is not None]
            flops = sum(r["flops"] for r in costed)
            costed_busy = sum(r.get("denoise_vae_s", 0.0) for r in costed)
            out.update({
                "batches": len(batches),
                "images": images,
                "images_per_s": images / span if span > 0 else None,
                "mean_batch": images / len(batches),
                "device_busy_s": denoise,
                "flops": flops if costed else None,
                "device_flops_per_s": (flops / costed_busy
                                       if costed and costed_busy > 0
                                       else None),
            })
        nodes = [r for r in records if r["kind"] == "node"]
        if nodes:
            per: Dict[str, Dict] = {}
            for r in nodes:
                c = per.setdefault(str(r.get("class_type")),
                                   {"count": 0, "seconds": 0.0})
                c["count"] += 1
                c["seconds"] += r.get("seconds", 0.0)
            out["nodes"] = per
        return out

    def snapshot(self, window_s: Optional[float] = None,
                 n: Optional[int] = None) -> Dict:
        """The ``GET /debug/flight`` payload: recent ring + aggregates."""
        return {
            "server": self.server,
            "capacity": self.capacity,
            "meta": dict(self.meta),
            "aggregates": self.aggregates(window_s),
            "records": self.recent(n),
        }

    # ------------------------------------------------------------- dumping
    def dump(self, reason: str, dump_dir: Optional[str] = None,
             ) -> Optional[str]:
        """Write the full snapshot to a JSON artifact; returns the path or
        None.  Best-effort by contract: a post-mortem writer must never be
        the thing that takes the server down, so every failure logs at
        warning and returns None."""
        try:
            d = dump_dir or knobs.get_str("TPUSTACK_FLIGHT_DUMP_DIR")
            if not d:
                return None
            os.makedirs(d, exist_ok=True)
            with _REG_LOCK:
                _DUMP_SEQ[0] += 1
                n = _DUMP_SEQ[0]
            with self._lock:
                self._dumps += 1
            safe = "".join(c if (c.isalnum() or c in "-_") else "_"
                           for c in reason)
            path = os.path.join(
                d, f"flight-{self.server}-{safe}-{os.getpid()}-{n}.json")
            payload = self.snapshot()
            payload["reason"] = reason
            payload["dumped_at"] = time.time()
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)  # pollers never see a half-written dump
            _log().warning("flight recorder dumped %d records to %s "
                           "(reason=%s)", len(payload["records"]), path,
                           reason)
            return path
        except Exception:
            _log().warning("flight dump failed (reason=%s)", reason,
                           exc_info=True)
            return None


class PhaseClock:
    """Where one thread's wall time goes between two flight records.

    ``with clock.phase("fetch_wait"):`` charges the enclosed wall time to
    that phase and puts an ``engine/fetch_wait`` event on the profiler's
    host plane (``jax.profiler.TraceAnnotation``), so a capture shows the
    same phases on the device trace's clock.  Phases nest: an inner phase
    pauses its parent, so the seconds are exclusive and add up.
    ``take()`` hands out ``{phase: seconds}`` since the previous ``take()``
    — an open phase is charged up to now — with whatever no phase covered
    as ``other``, and starts the next interval.  One ``perf_counter`` pair
    per phase; no lock (one thread owns a clock).

    A garbage collection on the owning thread (:func:`gc_attach`) pushes a
    ``gc`` phase from inside the collector, which may start at any
    allocation of a tracked object: between reading the time and storing
    it, ``_push``, ``_pop`` and ``take`` allocate none, so a pause is never
    charged twice.
    """

    __slots__ = ("_acc", "_stack", "_mark", "_annotate")

    #: the host plane's events are ``engine/<phase>``
    PREFIX = "engine/"

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.reset()

    def reset(self) -> None:
        self._acc: Dict[str, float] = {}
        self._stack: List[List] = []  # [name, running since] innermost last
        self._mark = time.perf_counter()

    def phase(self, name: str) -> "_Phase":
        return _Phase(self, name)

    def _charge(self, now: float) -> None:
        """Charge the innermost open phase up to ``now`` and restart it."""
        if self._stack:
            top = self._stack[-1]
            self._acc[top[0]] = self._acc.get(top[0], 0.0) + now - top[1]
            top[1] = now

    def _push(self, name: str) -> None:
        entry = [name, 0.0]  # allocated before the clock is read
        now = entry[1] = time.perf_counter()
        self._charge(now)  # the parent pauses
        self._stack.append(entry)

    def _pop(self) -> None:
        now = time.perf_counter()
        self._charge(now)
        self._stack.pop()
        if self._stack:
            self._stack[-1][1] = now  # the parent resumes

    def take(self) -> Dict[str, float]:
        fresh: Dict[str, float] = {}
        now = time.perf_counter()
        self._charge(now)
        acc, self._acc = self._acc, fresh
        mark, self._mark = self._mark, now
        out = {k: round(v, 6) for k, v in acc.items()}
        other = (now - mark) - sum(acc.values())
        if other > 0:
            out["other"] = round(other, 6)
        return out


class _Phase:
    __slots__ = ("_clock", "_name", "_ann")

    def __init__(self, clock: PhaseClock, name: str):
        self._clock, self._name = clock, name

    def __enter__(self):
        self._ann = self._clock._annotate(PhaseClock.PREFIX + self._name)
        self._ann.__enter__()
        self._clock._push(self._name)

    def __exit__(self, *exc):
        self._clock._pop()
        self._ann.__exit__(*exc)
        return False


# ------------------------------------------------ garbage collection pauses
# ``gc.callbacks`` is the process's, so is the table below of which thread's
# collections are charged to which engine's clock
#: the host plane's event of a collection of generation ``n``
_GC_NAMES = tuple(f"host/gc{n}" for n in range(3))
_GC_LOCK = threading.Lock()
#: thread ident -> the PhaseClock of the engine running on that thread
_GC_CLOCKS: Dict[int, PhaseClock] = {}
#: thread ident -> (annotation, clock charged) of the collection it is in
_GC_OPEN: Dict[int, Tuple] = {}
#: ``jax.profiler.TraceAnnotation``, bound when the hook is installed
_GC_ANNOTATE: List = [None]


def _on_gc(phase: str, info: Dict) -> None:
    """The ``gc.callbacks`` hook: a collection is a ``host/gc<generation>``
    event on the collecting thread's line of the profiler's host plane and,
    on an engine thread, a ``gc`` phase of its clock — which pauses the
    phase it interrupted, a wait included."""
    tid = threading.get_ident()
    if phase == "start":
        ann = _GC_ANNOTATE[0](_GC_NAMES[info["generation"]])
        ann.__enter__()
        clock = _GC_CLOCKS.get(tid)
        if clock is not None:
            clock._push("gc")
        _GC_OPEN[tid] = (ann, clock)
        return
    opened = _GC_OPEN.pop(tid, None)
    if opened is None:  # the hook came in during this collection
        return
    ann, clock = opened
    if clock is not None:
        clock._pop()
    ann.__exit__(None, None, None)


def gc_attach(clock: PhaseClock) -> None:
    """Charge this thread's garbage collections to ``clock`` (a ``gc``
    phase) until :func:`gc_detach`; the process's one ``gc.callbacks``
    hook is installed while any clock is attached, and puts every
    thread's collections on the profiler's host plane."""
    with _GC_LOCK:
        _GC_CLOCKS[threading.get_ident()] = clock
        _GC_ANNOTATE[0] = clock._annotate
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def gc_detach() -> None:
    """Undo this thread's :func:`gc_attach`; the hook goes with the last
    clock attached."""
    with _GC_LOCK:
        _GC_CLOCKS.pop(threading.get_ident(), None)
        if not _GC_CLOCKS and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def _log():
    from tpustack.utils import get_logger

    return get_logger("obs.flight")


# ------------------------------------------------------- process registry
def register(recorder: FlightRecorder) -> FlightRecorder:
    """Track ``recorder`` for process-wide post-mortem dumps
    (:func:`dump_all`) and the sidecar's ``/debug/flight``."""
    with _REG_LOCK:
        _RECORDERS.add(recorder)
    return recorder


def recorders() -> List[FlightRecorder]:
    with _REG_LOCK:
        return list(_RECORDERS)


def dump_all(reason: str) -> List[str]:
    """Dump every registered non-empty recorder (the watchdog / drain /
    sanitizer hook).  Empty recorders are skipped — a pod that never
    served a wave has nothing post-mortem-worthy to say."""
    paths = []
    for rec in recorders():
        if len(rec) == 0:
            continue
        p = rec.dump(reason)
        if p:
            paths.append(p)
    return paths


def snapshot_all(window_s: Optional[float] = None,
                 n: Optional[int] = 64) -> Dict:
    """Every registered recorder's snapshot — the metrics sidecar's
    ``/debug/flight`` payload (batch/train processes register theirs)."""
    return {"recorders": [rec.snapshot(window_s=window_s, n=n)
                          for rec in recorders()]}


# --------------------------------------------------- roofline attribution
def device_peaks_info() -> Tuple[str, Optional[Tuple[float, float]]]:
    """``(device_kind, (bf16 FLOP/s, HBM bytes/s) | None)`` for this
    process's first device.  None peaks (unknown kind, CPU dev box, jax
    absent) means callers must OMIT roofline gauges, not fake them."""
    try:
        import jax

        dev = jax.devices()[0]
    except Exception:
        return "", None
    from tpustack.utils.peaks import device_peaks

    return getattr(dev, "device_kind", ""), device_peaks(dev)


def llm_wave_arith(cfg, params, cache_dtype, rows: int = 1
                   ) -> Dict[str, float]:
    """Per-dispatch decode arithmetic from the llama config + param tree —
    the SAME accounting ``tools/bench_llm.py`` prints offline, shared so
    the live gauges and the bench can never disagree.  Counted by layer
    kind (``cfg.layer_specs``):

    - ``flops_per_token``: 2 FLOPs per matmul weight element a token
      multiplies — every dense kernel once; of a routed-expert stack
      ``[experts, in, out]`` the ``top_k / n_experts`` share a token's
      choices make of it (its expected experts among those held here);
    - ``weight_stream_bytes``: bytes one decode weight pass streams (the
      param tree minus embedding tables — decode gathers one row); of an
      expert stack the share a step of ``rows`` tokens is expected to
      touch, ``1 - (1 - top_k / n_experts) ** rows`` — held experts no
      token chose are not read;
    - ``kv_step_bytes_per_slot``: KV bytes one slot's attention reads per
      step (a full layer the whole static-shape cache line, a window
      layer its window; int8 cache = 1 B/element + one f32 scale per
      vector).
    """
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_leaves_with_path(params)

    def key_str(k):
        return str(getattr(k, "key", k))

    moe = getattr(cfg, "moe", None)
    chosen = moe.top_k / moe.n_experts if moe is not None else 1.0
    touched = 1.0 - (1.0 - chosen) ** max(1, rows)

    def is_stack(p, x):
        # an expert stack's leaves: [experts, in, out] and [experts, out]
        return moe is not None and any(
            key_str(k) in ("gate_proj", "up_proj", "down_proj")
            for k in p) and x.ndim == (3 if key_str(p[-1]) == "kernel"
                                       else 2)

    weight_stream_bytes = sum(
        x.nbytes * (touched if is_stack(p, x) else 1.0) for p, x in flat
        if not any("embed" in key_str(k) for k in p))
    flops_per_token = 2 * sum(
        x.size * (chosen if is_stack(p, x) else 1.0) for p, x in flat
        if key_str(p[-1]) == "kernel")
    kv_elt = 1 if cfg.kv_quant == "int8" else jnp.dtype(cache_dtype).itemsize
    kv_positions = sum(min(cfg.max_seq, sp.window or cfg.max_seq)
                       for sp in cfg.layer_specs)
    kv_step_bytes_per_slot = (
        2 * kv_positions * cfg.n_kv_heads
        * (cfg.head_dim * kv_elt + (4 if cfg.kv_quant == "int8" else 0)))
    return {
        "flops_per_token": float(flops_per_token),
        "weight_stream_bytes": float(weight_stream_bytes),
        "kv_step_bytes_per_slot": float(kv_step_bytes_per_slot),
    }


def llm_utilization(agg: Dict, arith: Dict,
                    peaks: Optional[Tuple[float, float]],
                    chips: int = 1) -> Optional[Dict[str, float]]:
    """Live MFU + HBM utilization from a recorder's wave aggregates.

    ``mfu`` = delivered tokens/s × matmul FLOPs/token over the bf16 peak;
    ``hbm_util`` = weight passes/s × (weight stream + mean-occupancy ×
    per-slot KV read) over the HBM peak — decode's roofline is the HBM
    one, so ``hbm_util`` is the "how close to the hardware" number and
    ``mfu`` is the honest (low) FLOP side.  ``chips`` divides the work
    across a tp mesh (each chip streams 1/tp of the bytes against its own
    peak).  None when the window holds no rate (idle, or a single wave).
    """
    if peaks is None:
        return None
    tps = agg.get("tokens_per_s")
    pps = agg.get("weight_passes_per_s")
    occ = agg.get("mean_occupancy")
    if not tps or not pps or occ is None:
        return None
    chips = max(1, int(chips))
    mfu = tps * arith["flops_per_token"] / (peaks[0] * chips)
    step_bytes = (arith["weight_stream_bytes"]
                  + occ * arith["kv_step_bytes_per_slot"])
    hbm = pps * step_bytes / (peaks[1] * chips)
    return {"mfu": mfu, "hbm_util": hbm}


def sd_utilization(agg: Dict, peaks: Optional[Tuple[float, float]],
                   chips: int = 1) -> Optional[Dict[str, float]]:
    """Live SD MFU from batch aggregates: summed pipeline FLOPs over
    summed device-busy seconds against the bf16 peak — the same number
    ``bench.py`` computes from XLA cost analysis at saturation.  None
    when the window has no costed batches (or peaks are unknown)."""
    if peaks is None:
        return None
    fps = agg.get("device_flops_per_s")
    if not fps:
        return None
    return {"mfu": fps / (peaks[0] * max(1, int(chips)))}
