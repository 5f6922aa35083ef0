"""From the profiler's trace to numbers: the device's busy time, a named
kernel's time, the operations that took most of it.

``extract`` turns an ``.xplane.pb`` into plain lists — per device plane, the
``(name, start_ns, duration_ns)`` of every event on its operations line — so
that the arithmetic below can be checked on a list written by hand.  A device
plane is one named ``/device:TPU:<n>``; its operations are on the line
``XLA Ops``.  A trace with no such plane (a CPU rehearsal) has no device
metric: every reader returns None, never 0.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(path: str) -> Dict[str, List[Event]]:
    """``{device plane: [(name, start_ns, duration_ns), ...]}``."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
    return out


def describe(path: str, limit: int = 3) -> List[str]:
    """Planes and lines of a trace, for a look by hand."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"{plane.name} | {line.name} | {len(events)} | " +
                        "; ".join(e.name[:60] for e in events[:limit]))
            if plane.name.startswith(DEVICE_PREFIX) and line.name == OPS_LINE:
                by: Dict[str, List[float]] = {}
                for e in events:
                    row = by.setdefault(short_name(e.name), [0.0, 0, ""])
                    row[0] += e.duration_ns
                    row[1] += 1
                    row[2] = e.name[:160]
                for n, (d, c, full) in sorted(by.items(),
                                              key=lambda kv: -kv[1][0])[:40]:
                    rows.append(f"    {d / 1e9:9.4f}s x{c:6d} {n} :: {full}")
    return rows


def busy_ns(events: List[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def span_ns(devices: Dict[str, List[Event]]) -> float:
    """First start to last end over all devices: the traced window as the
    devices saw it."""
    starts = [e[1] for ev in devices.values() for e in ev]
    stops = [e[1] + e[2] for ev in devices.values() for e in ev]
    return (max(stops) - min(starts)) if starts else 0.0


def device_busy(devices: Dict[str, List[Event]], window_s: float
                ) -> Optional[Dict[str, float]]:
    """``busy_s`` averaged over the chips used, beside ``window_s``."""
    if not devices or window_s <= 0:
        return None
    busy = [busy_ns(ev) / 1e9 for ev in devices.values()]
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s}


def kernel_seconds(devices: Dict[str, List[Event]], name: str
                   ) -> Tuple[float, int]:
    """Summed device time and call count of events whose name has ``name``
    in it, per chip (the mean over chips)."""
    if not devices:
        return 0.0, 0
    durations = [d for ev in devices.values() for n, _, d in ev
                 if name in short_name(n)]
    return sum(durations) / 1e9 / len(devices), len(durations) // len(devices)


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a Pallas
    kernel keeps the name it was given (``paged_attention.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


#: operations that only enclose others (a scan's loop, a branch, a call):
#: their time is their children's, which are listed themselves
ENCLOSING = ("while", "conditional", "call")


def top_ops(devices: Dict[str, List[Event]], k: int = 10) -> List[List]:
    """Device seconds by operation, the numbered copies of one kind summed
    (``fusion.5107`` and ``fusion.5119`` are both ``fusion``; a layer's 28
    ``paged_attention.N`` are one kernel)."""
    by: Dict[str, float] = {}
    for ev in devices.values():
        for n, _, d in ev:
            n = short_name(n).split(".")[0]
            if n in ENCLOSING:
                continue
            by[n] = by.get(n, 0.0) + d
    n_dev = max(1, len(devices))
    return [[n, d / 1e9 / n_dev] for n, d in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(devices: Dict[str, List[Event]], k: int = 10) -> List[List]:
    """Idle time of the first device, summed by the operation that ended
    each gap.  (By what the host was doing needs spans inside the program.)"""
    if not devices:
        return []
    events = sorted(next(iter(devices.values())), key=lambda e: e[1])
    by: Dict[str, float] = {}
    end = None
    for n, start, dur in events:
        if end is not None and start > end:
            n = "before " + short_name(n).split(".")[0]
            by[n] = by.get(n, 0.0) + (start - end)
        end = max(end or 0.0, start + dur)
    return [[n, d / 1e9] for n, d in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


# ------------------------------------------------------- per-layer readers
def idle_share(ctx, **_):
    """Share of the traced window in which no operation ran on the chip."""
    dev = ctx.get("device_busy")
    if not dev:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
