#!/usr/bin/env python3
"""A cell's traced windows read by ``benchmark/readers/host.py``: the idle
device time split into ``host`` and ``unseen``, by what the host was doing,
the clock check, the garbage-collection pauses, beside the per-layer metrics
the cell reports.

    python benchmark/tools/host_attribution.py --workload <cell> --seeds 3 --seconds 20 [--untraced]

One set-up serves every seed (the runner's ``Session.reseed``).  A window is
traced as a benchmark run traces it (the runner's own profiler options); its
host planes are read before the runner's ``reduce_trace`` removes the trace.
``--untraced`` first serves the first seed without the profiler, and sets the
engine's mean ``host_s`` a wave over the middle seconds of that window beside
the traced window's: what tracing costs the host.  One JSON line a window goes
to ``chiprun_out/host_attribution.<cell>.jsonl``.  Not part of a benchmark
run.
"""

from __future__ import annotations

import argparse
import bisect
import importlib
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def host_seconds(records: List[Dict], span) -> Dict:
    """Mean seconds a wave, by phase, of the wave records in ``span``."""
    from benchmark.readers import phases

    waves = [r for r in phases.in_window(
        {"span": span, "flight_records": records}, phases.WAVES, "span")
        if r.get("wave_s") and isinstance(r.get("host_s"), dict)]
    if not waves:
        return {"waves": 0}
    by: Dict[str, float] = {}
    for r in waves:
        for k, v in r["host_s"].items():
            by[k] = by.get(k, 0.0) + v
    n = len(waves)
    return {"waves": n,
            "wave_s": sum(r["wave_s"] for r in waves) / n,
            "host_work_s": sum(v for k, v in by.items()
                               if k not in phases.WAITS) / n,
            "by_phase_s": {k: v / n for k, v in sorted(by.items())}}


def gap_table(ctx, min_gap_ns: float = 1e6) -> List[Dict]:
    """Each gap between the device's operations longer than ``min_gap_ns``,
    in ms on the profile's clock: the operation that ended it, the engine's
    innermost phase at its start and at its end (``-``: none open), and
    the engine's first event in the capture."""
    from benchmark.readers import host, trace

    if not ctx.get("devices") or not ctx.get("host_spans"):
        return []
    phases = [seg for line in host.engine_lines(ctx["host_spans"]).values()
              for seg in host.innermost(line)]
    first = min((s[2] for s in ctx["host_spans"]
                 if s[1].startswith(host.ENGINE)), default=None)
    ops = sorted(next(iter(ctx["devices"].values())), key=lambda e: e[1])
    starts = [e[1] for e in ops]

    def phase_at(t):
        return next((n for n, a, b in phases if a <= t < b), "-")

    out = []
    for a, b in host.gaps(ctx["devices"], min_gap_ns):
        op = ops[min(len(ops) - 1, bisect.bisect_left(starts, b))][0]
        out.append({"start_ms": a / 1e6, "ms": (b - a) / 1e6,
                    "ended_by": trace.short_name(op),
                    "phase_at_start": phase_at(a), "phase_at_end": phase_at(b),
                    "first_engine_event_ms": (None if first is None
                                              else first / 1e6)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147390000)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--untraced", action="store_true")
    ap.add_argument("--describe-trace", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as R

    bench, cell, cfg, workload, full_ctx = R.load_cell(args.workload,
                                                       args.cpu_rehearsal)
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    enable_compile_cache()
    import jax

    from benchmark import peaks
    from benchmark.readers import host, trace
    from benchmark.runners import llm_http

    dev = jax.devices()[0]
    chip_peaks = (None if dev.platform == "cpu"
                  else peaks.peaks_for(dev.device_kind))
    runner = importlib.import_module(f"benchmark.runners.{cfg['runner']}")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out_dir = os.path.join(ROOT, ".cache", "benchmark",
                           cell["name"] + ".host")
    job = {"root": ROOT, "cfg": cfg, "workload": workload, "seed": seeds[0],
           "rehearsal": args.cpu_rehearsal, "full_ctx": full_ctx,
           "chips": cell["chips"], "out_dir": out_dir}
    if args.cpu_rehearsal and hasattr(runner, "rehearsal_cfg"):
        job["cfg"] = runner.rehearsal_cfg(job)
    cells_of_e2e = {m["name"]: [w["name"] for w in bench["workloads"]
                                if R.reports(m, w["name"], {})]
                    for m in bench["end_to_end"]}
    kind = ("decode" if cell["name"] in cells_of_e2e["output_tokens_per_s"]
            else "prefill")
    trace_s = float(workload.get("trace_s", 3))
    lines = []
    session = runner.Session(job)
    try:
        untraced = None
        if args.untraced:
            ctx = session.window(seeds[0], args.seconds, False)
            snap = json.loads(session.host.get("/debug/flight?n=100000"))
            mid = sum(ctx["window"]) / 2
            untraced = host_seconds(snap.get("records", []),
                                    [mid - trace_s / 2, mid + trace_s / 2])
        for i, seed in enumerate(seeds):
            if i:
                session.reseed(seed)
            ctx = session.window(seed, args.seconds, True)
            path = trace.find_xplane(ctx["trace_dir"])
            spans = host.host_spans(path) if path else []
            describe = None
            if args.describe_trace and i == 0:
                describe = os.path.join(ROOT, "chiprun_out",
                                        f"trace_lines.{cell['name']}.txt")
            llm_http.reduce_trace(ctx, describe)
            ctx.update(host_spans=spans, peaks=chip_peaks)
            metrics = {}
            for m in bench["per_layer"]:
                if not R.reports(m, cell["name"], cells_of_e2e):
                    continue
                spec = R.load_json(ROOT, "benchmark", "metrics",
                                   m["name"] + ".json")
                reader = importlib.import_module(
                    f"benchmark.readers.{spec['reader']}")
                metrics[m["name"]] = getattr(reader, spec["function"])(
                    ctx, **(spec.get("args") or {}))
            metrics.update({
                f"device_idle_host_share.{kind}":
                    host.device_idle_host_share(ctx),
                f"device_idle_unseen_share.{kind}":
                    host.device_idle_unseen_share(ctx),
                "gc_pause_ms_per_s": host.gc_pause_ms_per_s(ctx),
                "engine_record_share.decode":
                    host.engine_phase_share(ctx, phase="record"),
                "engine_stream_share.decode":
                    host.engine_phase_share(ctx, phase="stream")})
            events: Dict[str, int] = {}
            for _, name, _, _ in spans:
                events[name] = events.get(name, 0) + 1
            line = {
                "workload": cell["name"], "seed": seed,
                "idle_share": trace.idle_share(ctx),
                "metrics": metrics,
                "attribution": host.idle_attribution(ctx),
                "idle_by_host_s": host.idle_by_host(ctx),
                "gc_by_phase_s": host.gc_by_phase(ctx),
                "clock_check": host.clock_check(ctx),
                "gaps_over_1ms": gap_table(ctx),
                "host_events": dict(sorted(events.items())),
                "engine_lines": len(host.engine_lines(spans)),
                "trace_span_s": ctx["trace_span"][1] - ctx["trace_span"][0],
                "device_busy": ctx.get("device_busy"),
                "host_s_traced": host_seconds(ctx.get("flight_records", []),
                                              ctx["trace_span"])}
            if i == 0 and untraced is not None:
                line["host_s_untraced"] = untraced
            lines.append(line)
            print(json.dumps(line), flush=True)
    finally:
        session.close()
    out = os.path.join(ROOT, "chiprun_out",
                       f"host_attribution.{cell['name']}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
