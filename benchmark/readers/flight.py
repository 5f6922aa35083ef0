"""Readers of what the server counts itself: ``/debug/flight`` wave records,
the ``/metrics`` phase histograms, and the process's compile events."""

from __future__ import annotations

import re
from typing import Dict, List, Optional


def wave_occupancy(ctx, kinds=("wave", "verify"), **_):
    """Mean live slots per decode wave over the slots, in the window."""
    w0, w1 = ctx["window"]
    waves = [r for r in ctx.get("flight_records", [])
             if w0 <= r.get("ts", 0) <= w1 and r.get("slots")
             and r.get("kind") in kinds]
    if not waves:
        return None
    return 100.0 * sum(r["occupancy"] / r["slots"] for r in waves) / len(waves)


def parse_histogram(text: str, name: str, labels: Dict[str, str]
                    ) -> Dict[float, float]:
    """``{upper bound: cumulative count}`` of one Prometheus histogram."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(name + "_bucket{"):
            continue
        head, _, value = line.rpartition(" ")
        found = dict(re.findall(r'(\w+)="([^"]*)"', head))
        if all(found.get(k) == v for k, v in labels.items()):
            le = found["le"]
            out[float("inf") if le == "+Inf" else float(le)] = float(value)
    return out


def histogram_quantile(before: Dict[float, float], after: Dict[float, float],
                       q: float) -> Optional[float]:
    """Quantile of what was observed between two scrapes, interpolated
    inside its bucket (Prometheus' own arithmetic)."""
    bounds = sorted(after)
    counts = [after[b] - before.get(b, 0.0) for b in bounds]
    if not bounds or counts[-1] <= 0:
        return None
    rank = q * counts[-1]
    lo_bound, lo_count = 0.0, 0.0
    for b, c in zip(bounds, counts):
        if c >= rank:
            if b == float("inf"):
                return lo_bound
            if c == lo_count:
                return b
            return lo_bound + (b - lo_bound) * (rank - lo_count) / (
                c - lo_count)
        lo_bound, lo_count = b, c
    return bounds[-1]


def phase_quantile_ms(ctx, phase="queue_wait", q=0.9,
                      metric="tpustack_request_phase_latency_seconds", **_):
    labels = {"phase": phase}
    before = parse_histogram(ctx.get("metrics_before", ""), metric, labels)
    after = parse_histogram(ctx.get("metrics_after", ""), metric, labels)
    value = histogram_quantile(before, after, q)
    return None if value is None else value * 1e3


def window_compiles(ctx, **_):
    """Programs traced or compiled between the window's bounds."""
    w0, w1 = ctx["window"]
    return float(sum(1 for e in ctx.get("compile_events", [])
                     if w0 <= e["t"] <= w1))
