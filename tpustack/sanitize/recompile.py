"""Recompile sanitizer: jitted entry points must not retrace mid-serving.

An XLA recompile on the serving path is a multi-second (CPU) to
multi-minute (TPU) stall that looks exactly like a hung dispatch from the
outside — the watchdog may even restart the pod for it.  The engine's
entry points are all shape-static by design (``_decode_scan_paged`` and
friends trace once per (B, chunk, dtype) configuration), so in steady
state their trace caches must stop growing.  This module makes that a
checked contract:

- :class:`CompileWatch` snapshots each watched jit wrapper's trace-cache
  size (``PjitFunction._cache_size()``) at registration and, at every
  ``check()`` (the engine calls it at wave boundaries and at drain),
  reports a violation when the cache grew past the declared budget.
- Budgets are *growth* budgets per watch lifetime — an engine declares
  "this busy period may compile each decode/verify program at most N
  times" (N=the cold compile + one slack), so the first run's cold
  compiles pass and a per-wave retrace trips by wave budget+1.

``_cache_size`` is jax-internal but stable across the versions this repo
has seen; when absent the watch degrades to a no-op (documented — the
sanitizer must never invent failures on a jax upgrade).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple


def cache_size(jit_fn) -> Optional[int]:
    """Trace-cache entry count of a jit wrapper, or None when this jax
    build doesn't expose it."""
    fn = getattr(jit_fn, "__func__", jit_fn)  # unwrap bound methods
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


class CompileWatch:
    """Per-engine recompile budget tracker.

    ``watch(name, jit_fn, budget)`` baselines the entry point;
    ``check(where)`` reports every watched entry whose cache grew more
    than its budget since the baseline.  All methods are cheap no-ops
    when the sanitizer is disabled, so engines can construct one
    unconditionally."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (jit_fn, budget, baseline size)
        self._watched: Dict[str, Tuple[object, int, int]] = {}
        self._reported: set = set()
        # growth already exported to tpustack_recompiles_total per entry
        # point — check() increments by the delta, so the counter tracks
        # every observed retrace, not just budget violations
        self._exported: Dict[str, int] = {}

    def watch(self, name: str, jit_fn, budget: int = 1,
              force: bool = False) -> None:
        """Baseline ``jit_fn``'s trace cache.  ``force`` watches even with
        the sanitizer disabled — the bench signature path
        (``tpustack.obs.perfsig``) measures recompiles as DATA, while the
        serving engines keep the enabled() gate so the =0 hot path stays
        uninstrumented."""
        from tpustack import sanitize

        if (not force and not sanitize.enabled()) or jit_fn is None:
            return
        base = cache_size(jit_fn)
        if base is None:
            return  # this jax build doesn't expose cache sizes
        with self._lock:
            self._watched[name] = (jit_fn, max(0, budget), base)

    def compiles(self, name: str) -> Optional[int]:
        """Traces compiled for ``name`` since its baseline (None when not
        watched)."""
        with self._lock:
            entry = self._watched.get(name)
        if entry is None:
            return None
        fn, _, base = entry
        size = cache_size(fn)
        return None if size is None else max(0, size - base)

    def check(self, where: str = "") -> None:
        """Report every watched entry point over its budget.  Each entry
        reports at most once per watch (the violation would otherwise
        re-fire every wave in report mode and drown the log)."""
        from tpustack import sanitize

        if not sanitize.enabled():
            return
        with self._lock:
            snapshot = dict(self._watched)
        for name, (fn, budget, base) in snapshot.items():
            size = cache_size(fn)
            if size is None:
                continue
            grown = size - base
            self._export(name, grown)
            if grown > budget and name not in self._reported:
                self._reported.add(name)
                sanitize.violation(
                    "recompile",
                    f"{name} compiled {grown} new trace(s) "
                    f"{f'by {where} ' if where else ''}against a budget of "
                    f"{budget} — a steady-state serving entry point is "
                    "retracing (varying Python scalar? shape drift? "
                    "dtype flip?).  Inspect static_argnums and the "
                    "argument shapes; raise the budget only for a real "
                    "new configuration")

    def _export(self, name: str, grown: int) -> None:
        """Count every observed trace into
        ``tpustack_recompiles_total{entry_point}`` (growth since the last
        check) — the cold compiles land once at the first wave boundary,
        then any increment is a mid-traffic retrace, visible on /metrics
        without waiting for the budget to trip.  Best-effort: metrics must
        never take the checker down."""
        with self._lock:
            delta = grown - self._exported.get(name, 0)
            if delta <= 0:
                return
            self._exported[name] = grown
        try:
            from tpustack.obs import catalog as obs_catalog

            obs_catalog.build(None)["tpustack_recompiles_total"].labels(
                entry_point=name).inc(delta)
        except Exception:
            pass

    def stats(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            snapshot = dict(self._watched)
        out: Dict[str, Dict[str, int]] = {}
        for name, (fn, budget, base) in snapshot.items():
            size = cache_size(fn)
            if size is not None:
                out[name] = {"budget": budget,
                             "compiles": max(0, size - base)}
        return out
