#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell or one per-layer
metric is a file found by its name: ``configs/<config>.json`` (which names
its ``runner``), ``workloads/<cell>.json`` (the traffic, the warm-up, the
limits of the comparison), ``metrics/<metric>.json`` (which names its
``reader`` and ``function``).  This file has no branch on any of those names.

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: every number that was compared, beside its limit (also the last
lines of standard error).  Without a TPU — or with fewer chips than the cell
asks for — it exits non-zero and prints no result.

``--cpu-rehearsal`` (with ``JAX_PLATFORMS=cpu``) drives the same control flow
at the sizes of ``tests/rehearsal.json``; its line says platform ``cpu`` and
carries counts only, never a time, a rate or a share of a peak.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, rehearsal: bool):
    """``BENCHMARK.json``, the cell's entry, its configuration as it is run
    (at the rehearsal's sizes with ``rehearsal``), its workload file, and the
    configuration's full context length."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], name, "workload")
    config = find(bench["configs"], cell["config"], "config")
    cfg = load_json(ROOT, config["file"])
    workload = load_json(HERE, "workloads", cell["name"] + ".json")
    full_ctx = cfg["ctx"]
    if rehearsal:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise SystemExit("--cpu-rehearsal needs JAX_PLATFORMS=cpu")
        cfg = dict(cfg, **load_json(HERE, "tests", "rehearsal.json"))
    return bench, cell, cfg, workload, full_ctx


def reports(metric, cell: str, cells_of_e2e) -> bool:
    """Whether ``metric`` is reported in ``cell``: its ``workloads`` key, or
    (a per-layer metric without one) every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return cell in cells_of_e2e[metric["moves"]]
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--describe-trace", action="store_true",
                    help="also write the trace's planes and lines to "
                    "chiprun_out/ (a look by hand)")
    args = ap.parse_args(argv)

    bench, cell, cfg, workload, full_ctx = load_cell(args.workload,
                                                     args.cpu_rehearsal)

    # the program's guard first (backend must be tpu unless JAX_PLATFORMS
    # names cpu), then this cell's own: platform and chip count
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.cpu_rehearsal and platform != "tpu":
        raise SystemExit(f"run.py: platform is {platform!r}, not 'tpu'")
    if not args.cpu_rehearsal and len(devices) < cell["chips"]:
        raise SystemExit(f"run.py: {len(devices)} chip(s), the cell asks "
                         f"for {cell['chips']}")
    cache_dir = enable_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else a
    # fixed directory inside the checkout (.cache/xla)

    from benchmark import peaks

    chip_peaks = None if platform == "cpu" else peaks.peaks_for(
        devices[0].device_kind)

    out_dir = os.path.join(ROOT, ".cache", "benchmark", cell["name"])
    runner = importlib.import_module(f"benchmark.runners.{cfg['runner']}")
    res = runner.run({
        "root": ROOT, "cfg": cfg, "workload": workload, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "rehearsal": args.cpu_rehearsal, "full_ctx": full_ctx,
        "chips": cell["chips"], "t_start": T_START,
        "t_import": time.time() - T_START, "cache_dir": cache_dir,
        "out_dir": out_dir, "describe_trace": args.describe_trace})

    cells_of_e2e = {m["name"]: [w["name"] for w in bench["workloads"]
                                if reports(m, w["name"], {})]
                    for m in bench["end_to_end"]}
    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if not reports(m, cell["name"], cells_of_e2e):
                continue
            value = res["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ctx = dict(res["ctx"], peaks=chip_peaks)
        for m in bench["per_layer"]:
            if not reports(m, cell["name"], cells_of_e2e):
                continue
            spec = load_json(HERE, "metrics", m["name"] + ".json")
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = getattr(reader, spec["function"])(
                ctx, **(spec.get("args") or {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.cpu_rehearsal:
        # counts only: a time or a rate off the CPU is no measurement
        counted = {m["name"] for m in bench["per_layer"]
                   if m["source"] == "program_counter"}
        print(json.dumps({"rehearsal_not_a_measurement": metrics}),
              file=sys.stderr)
        metrics = {k: v for k, v in metrics.items() if k in counted}

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace and not args.cpu_rehearsal:
        device.update(res["device_extra"])
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and res["breakdown"] and not args.cpu_rehearsal:
        line["breakdown"] = res["breakdown"]
    line["checks"] = res["checks"]
    sys.stdout.flush()
    for name, c in res["checks"].items():
        print(f"check {name}: {json.dumps(c)}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
