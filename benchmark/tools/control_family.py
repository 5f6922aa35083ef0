#!/usr/bin/env python3
"""``tools/control.py`` for a configuration whose runner and reference are
named in its file (``runner``, ``reference``): the two numbers a cell's
``served_gap`` limit stands between — what sound runs of the program read
over many seeds, and what the reference one precision down (its ``LOWER``)
reads at the same positions.

    python benchmark/tools/control_family.py --workload <cell> --seeds 12 --seconds 20

One set-up serves every seed (the runner's ``Session.reseed``); the table
goes to ``chiprun_out/``.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--controls", type=int, default=4,
                    help="how many of the seeds also read the control")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as R

    _, cell, cfg, workload, full_ctx = R.load_cell(args.workload,
                                                   args.cpu_rehearsal)
    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    enable_compile_cache()

    from benchmark import idtok
    from benchmark.runners import llm_http

    runner = importlib.import_module(f"benchmark.runners.{cfg['runner']}")
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    job = {"root": ROOT, "cfg": cfg, "workload": workload, "seed": seeds[0],
           "rehearsal": args.cpu_rehearsal, "full_ctx": full_ctx,
           "chips": cell["chips"],
           "out_dir": os.path.join(ROOT, ".cache", "benchmark",
                                   cell["name"] + ".control")}
    if args.cpu_rehearsal and hasattr(runner, "rehearsal_cfg"):
        cfg = job["cfg"] = runner.rehearsal_cfg(job)
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    session = runner.Session(job)
    make_weights = type(session.weights)
    samples = {}
    try:
        for i, seed in enumerate(seeds):
            if i:
                session.reseed(seed)
            ctx = session.window(seed, args.seconds, False)
            result = {"window": ctx["window"], "records": ctx["records"]}
            picked = llm_http.pick_sample(
                result, seed, int(workload["check"].get("requests", 4)))
            samples[seed] = [([idtok.BOS_ID] + r["prompt_ids"], r["tokens"])
                             for r in picked]
    finally:
        session.close()

    lower = reference.LOWER[cfg["weights"]]
    rows = []
    for i, seed in enumerate(seeds):
        seqs = samples[seed]
        if not seqs:
            rows.append({"seed": seed, "requests": 0})
            continue
        t0 = time.time()
        gaps = reference.served_gaps(
            cfg, make_weights(cfg, seed), seqs,
            lower=lower if i < args.controls else None)
        row = {"seed": seed, "requests": len(seqs),
               "tokens": int(sum(len(g) for g in gaps["served"])),
               "served_gap": float(max(g.max() for g in gaps["served"])),
               "served_nonzero": int(sum((g > 0).sum()
                                         for g in gaps["served"])),
               "seconds": round(time.time() - t0, 1)}
        if "control" in gaps:
            row["control_gap"] = float(max(g.max() for g in gaps["control"]))
            row["control_nonzero"] = int(sum((g > 0).sum()
                                             for g in gaps["control"]))
        rows.append(row)
        print(json.dumps(row), flush=True)
    served = [r["served_gap"] for r in rows if "served_gap" in r]
    control = [r["control_gap"] for r in rows if "control_gap" in r]
    summary = {"workload": cell["name"], "control": lower,
               "lower_reading": max(served) if served else None,
               "upper_reading": min(control) if control else None,
               "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{cell['name']}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
