"""What the host was doing while the chip idled, on the device trace's clock.

The engine's phases are ``engine/<phase>`` events on the profiler's host
plane (``tpustack.obs.flight.PhaseClock``), and every garbage collection
while an engine runs is a ``host/gc<generation>`` event on the collecting
thread's line (``tpustack.obs.flight.gc_attach``).  Only those events share
the device's clock: a flight record's ``ts`` is the wall clock, and a
trace's times count from the profile's own start.

``host_spans`` turns an ``.xplane.pb`` into ``(line, name, start_ns,
duration_ns)`` of those events; the engine's line is a line that holds
``engine/`` events.  The readers below take ``ctx["devices"]`` (``trace.
extract``), ``ctx["host_spans"]``, ``ctx["device_busy"]`` and the flight
records, so that the arithmetic can be checked on lists written by hand.

The idle time of the first device, within the window ``trace.idle_share``
uses (the gaps between its operations, and the window less its span: the
edges), is charged nanosecond by nanosecond to one class:

1. ``host``: a ``host/gc*`` span is open on any line, or an engine line is
   inside a phase other than the three waits (the innermost phase counts);
2. ``unseen``: everything else — the engine waiting while the chip idles,
   and time before the first or after the last engine event.

So ``host`` + ``unseen`` is ``idle_share``.  Each reader returns None —
never 0 — where there is nothing to read: no device plane (a CPU
rehearsal), no engine line, or a program without the phase or the spans.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.readers import phases

Span = Tuple[str, str, float, float]
Interval = Tuple[float, float]

HOST_PREFIX = "/host:"
ENGINE = "engine/"
GC = "host/gc"
#: the phases that put a program on the device, so that a gap can end no
#: earlier than the start of one: the decode dispatches, admissions, the
#: verify dispatch, and ``park`` (``_flush_park``'s slot update and the
#: constant operands it makes, each a program of its own)
LAUNCHES = ("dispatch", "admit", "verify", "park")


def host_spans(path: str) -> List[Span]:
    """``[(line, name, start_ns, duration_ns)]`` of the ``engine/*`` and
    ``host/gc*`` events on the host planes of an ``.xplane.pb``.  Lines
    are named ``<plane>#<index>:<thread>``: threads share names."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            key = f"{plane.name}#{i}:{line.name}"
            for e in line.events:
                if e.name.startswith((ENGINE, GC)):
                    out.append((key, e.name, float(e.start_ns),
                                float(e.duration_ns)))
    return out


# --------------------------------------------------------------- intervals
def union(intervals) -> List[Interval]:
    """Sorted, disjoint cover of ``(start, stop)`` intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(x: Sequence[Interval], y: Sequence[Interval]) -> float:
    """Length of the intersection of two disjoint sorted covers."""
    total, i, j = 0.0, 0, 0
    while i < len(x) and j < len(y):
        lo, hi = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if hi > lo:
            total += hi - lo
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return total


def engine_lines(spans: Sequence[Span]) -> Dict[str, List[Span]]:
    """``{line: its spans}`` of the lines that hold ``engine/`` events."""
    lines = {s[0] for s in spans if s[1].startswith(ENGINE)}
    out: Dict[str, List[Span]] = {k: [] for k in lines}
    for s in spans:
        if s[0] in out:
            out[s[0]].append(s)
    return out


def innermost(spans: Sequence[Span]) -> List[Tuple[str, float, float]]:
    """``(phase, start, stop)`` of one line's ``engine/`` events, each
    instant charged to the innermost open one (phases nest on a thread)."""
    out: List[Tuple[str, float, float]] = []
    stack: List[List] = []  # [phase, stop], innermost last
    cursor = float("-inf")

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            name, stop = stack.pop()
            if stop > cursor:
                out.append((name, cursor, stop))
            cursor = max(cursor, stop)

    for _, name, start, dur in sorted(
            (s for s in spans if s[1].startswith(ENGINE)),
            key=lambda s: (s[2], -s[3])):
        close_until(start)
        if stack and start > cursor:
            out.append((stack[-1][0], cursor, start))
        stack.append([name[len(ENGINE):], start + dur])
        cursor = max(cursor, start)
    close_until(float("inf"))
    return out


def host_busy(spans: Sequence[Span]) -> List[Interval]:
    """When the host held the chip back: a collection on any line, or an
    engine line in a phase other than a wait."""
    busy = [(s[2], s[2] + s[3]) for s in spans if s[1].startswith(GC)]
    for line in engine_lines(spans).values():
        busy += [(a, b) for name, a, b in innermost(line)
                 if name not in phases.WAITS]
    return union(busy)


# ------------------------------------------------------------- idle time
def idle_intervals(devices, window_s: float) -> List[Interval]:
    """The first device's idle time inside the window, which lies on the
    profile's clock ``window_s`` long from the profile's start (0) where
    that holds the device's span, else moved just enough to."""
    events = next(iter(devices.values()))
    busy = union((s, s + d) for _, s, d in events)
    w = window_s * 1e9
    if not busy:
        return [(0.0, w)]
    d0, d1 = busy[0][0], busy[-1][1]
    t0 = min(d0, max(0.0, d1 - w))
    idle, at = [], t0
    for a, b in busy:
        if a > at:
            idle.append((at, a))
        at = max(at, b)
    if t0 + w > at:
        idle.append((at, t0 + w))
    return idle


def _has_engine(ctx) -> bool:
    return bool(ctx.get("devices") and ctx.get("device_busy")
                and any(s[1].startswith(ENGINE)
                        for s in ctx.get("host_spans") or ()))


def idle_attribution(ctx) -> Optional[Dict[str, float]]:
    """``{window_ns, idle_ns, host_ns, unseen_ns}`` of the traced window."""
    if not _has_engine(ctx):
        return None
    window_s = ctx["device_busy"]["window_s"]
    idle = idle_intervals(ctx["devices"], window_s)
    host = overlap(idle, host_busy(ctx["host_spans"]))
    total = length(idle)
    return {"window_ns": window_s * 1e9, "idle_ns": total, "host_ns": host,
            "unseen_ns": total - host}


def idle_by_host(ctx) -> Optional[Dict[str, float]]:
    """The idle seconds by what the host was doing: ``gc`` (a collection on
    any line), else the engine's innermost phase, else ``none`` (no engine
    event open: the engine was between runs, or the edge of the trace)."""
    if not _has_engine(ctx):
        return None
    spans = ctx["host_spans"]
    idle = idle_intervals(ctx["devices"], ctx["device_busy"]["window_s"])
    gc = union((s[2], s[2] + s[3]) for s in spans if s[1].startswith(GC))
    out = {"gc": overlap(idle, gc)}
    left = _minus(idle, gc)
    for line in engine_lines(spans).values():
        for name, a, b in innermost(line):
            t = overlap(left, [(a, b)])
            if t > 0:
                out[name] = out.get(name, 0.0) + t
    out["none"] = length(idle) - sum(out.values())
    return {k: v / 1e9 for k, v in out.items() if v > 0}


def _minus(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """``x`` less ``y``: two disjoint sorted covers."""
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > a:
                out.append((a, y[k][0]))
            a = max(a, y[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def gc_by_phase(ctx) -> Optional[Dict[str, float]]:
    """Seconds of ``host/gc*`` spans by the engine phase each interrupted
    (the innermost one open at its start on its own line), ``-`` off the
    engine's lines: what lands under a wait is the pause the parent's
    ``host_s`` charged to that wait."""
    spans = ctx.get("host_spans") or ()
    gcs = [s for s in spans if s[1].startswith(GC)]
    if not gcs or not any(s[1].startswith(ENGINE) for s in spans):
        return None
    lines = {k: innermost(v) for k, v in engine_lines(spans).items()}
    out: Dict[str, float] = {}
    for line, _, start, dur in gcs:
        phase = "-"
        for name, a, b in lines.get(line, ()):
            if a <= start < b:
                phase = name
                break
        out[phase] = out.get(phase, 0.0) + dur / 1e9
    return out


def clock_check(ctx, min_gap_ns: float = 1e6) -> Optional[Dict[str, float]]:
    """The host cannot be later than the program it launched: every gap
    between the first device's operations longer than ``min_gap_ns`` must
    end after the start of an engine phase that launches (``LAUNCHES``).
    ``violations`` counts those that do not, of which ``before_capture``
    end before the engine's first event in the capture (a span open when
    the profiler started is not recorded, so the launch that ended such a
    gap may lie before the capture); ``median_lag_ms`` is from the latest
    launch to the gap's end."""
    if not _has_engine(ctx):
        return None
    engine = [s for s in ctx["host_spans"] if s[1].startswith(ENGINE)]
    starts = sorted(s[2] for s in engine if s[1][len(ENGINE):] in LAUNCHES)
    first = min(s[2] for s in engine)
    lags, late = [], []
    for a, b in gaps(ctx["devices"], min_gap_ns):
        k = bisect.bisect_right(starts, b)
        if k == 0:
            late.append(b)
        else:
            lags.append(b - starts[k - 1])
    return {"gaps": len(lags) + len(late), "violations": len(late),
            "before_capture": sum(1 for b in late if b <= first),
            "median_lag_ms": (statistics.median(lags) / 1e6 if lags
                              else None)}


def gaps(devices, min_gap_ns: float = 0.0) -> List[Interval]:
    """``(start, stop)`` of the first device's gaps between operations
    longer than ``min_gap_ns``."""
    busy = union((s, s + d) for _, s, d in next(iter(devices.values())))
    return [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])
            if b - a > min_gap_ns]


# ------------------------------------------------------- per-layer readers
def device_idle_host_share(ctx, **_) -> Optional[float]:
    """Share of the traced window in which the chip idled while the host
    was busy: a collection, or the engine in a phase other than a wait."""
    a = idle_attribution(ctx)
    return None if a is None else 100.0 * a["host_ns"] / a["window_ns"]


def device_idle_unseen_share(ctx, **_) -> Optional[float]:
    """Share of the traced window in which the chip idled and the program
    names nothing the host was doing."""
    a = idle_attribution(ctx)
    return None if a is None else 100.0 * a["unseen_ns"] / a["window_ns"]


def gc_pause_ms_per_s(ctx, **_) -> Optional[float]:
    """Milliseconds of garbage collection, summed over the threads, per
    second of the trace."""
    if not _has_engine(ctx):
        return None
    gcs = [s[3] for s in ctx["host_spans"] if s[1].startswith(GC)]
    a, b = ctx["trace_span"]
    if not gcs or b <= a:
        return None
    return sum(gcs) / 1e6 / (b - a)


def engine_phase_share(ctx, phase: str = "record", **_) -> Optional[float]:
    """Share of the engine thread's time spent in ``phase``: its seconds in
    ``host_s`` over ``wave_s``, by ``phases.engine_host_share``'s rules."""
    waves = [r for r in phases.in_window(ctx, phases.WAVES)
             if r.get("wave_s") and isinstance(r.get("host_s"), dict)]
    if not any(phase in r["host_s"] for r in waves):
        return None
    total = sum(r["wave_s"] for r in waves)
    return 100.0 * sum(r["host_s"].get(phase, 0.0) for r in waves) / total
