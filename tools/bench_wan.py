#!/usr/bin/env python3
"""Wan2.1-class T2V benchmark: seconds per video at the reference shape.

The reference's T2V workload is Wan2.1 1.3B bf16, 512x320, 16 frames, 25
steps, cfg 6.0, via an out-of-band ComfyUI server
(``/root/reference/cluster-config/apps/llm/scripts/generate_wan_t2v.py:305-349``).
This measures the same shape on the TPU-native pipeline: one fused program
for the 25-step CFG flow-matching denoise loop + 3D-VAE decode.

Default: the FULL umt5-xxl-shape text tower, weight-only int8
(``UMT5Config(quant="int8")`` — ~5.7 GB instead of 11.4 GB bf16, fitting
beside the DiT on one 16 GB chip; the serving configuration).  ``--toy-text``
swaps in a miniature tower to isolate the DiT+VAE number.

One process per chip: the content check (``tools/verify_hw.py``, whose hw
phase is a child that needs the chip) runs BEFORE this process initialises
a backend; a check that does not pass makes the run exit non-zero.

Prints ONE JSON line: {"metric", "value", "unit", "seconds_per_video"}.
The repo headline (driver-run) stays bench.py's SD15 number.
"""

from __future__ import annotations

import argparse
import os
import dataclasses
import json
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=25)
    p.add_argument("--frames", type=int, default=16)
    p.add_argument("--width", type=int, default=512)
    p.add_argument("--height", type=int, default=320)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--warmup", type=int, default=2,
                   help="minimum untimed pipelined intervals; warmup "
                        "continues until two consecutive intervals agree "
                        "(shared discipline with bench.py)")
    p.add_argument("--small", action="store_true", help="tiny smoke shape")
    p.add_argument("--no-content-check", action="store_true",
                   help="skip the tools/verify_hw.py wan-family content "
                        "verification folded into the result")
    p.add_argument("--toy-text", action="store_true",
                   help="miniature text tower instead of the int8 umt5-xxl "
                        "shape (isolates the DiT+VAE number)")
    args = p.parse_args()
    t_bench = time.time()
    log = lambda *a: print(*a, file=sys.stderr, flush=True)

    content_check = None
    if not args.small and not args.no_content_check:
        # bench.py-style gating: the Wan number only counts if the chip
        # provably computes the right frames (wan family: 3-file export→
        # reload→denoise+mapped-VAE parity; flash family incl. the S=8320
        # d=128 case this very workload's DiT runs).  FIRST, while this
        # process is still off jax: afterwards it holds the chip.
        import bench

        content_check = bench._content_check(families="wan,flash",
                                             workdir="verify_hw_wan")
        if content_check != "pass":
            log(f"[bench_wan] content check: {content_check}")
            return 1

    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    import jax

    from tpustack.models.wan.config import UMT5Config, WanConfig
    from tpustack.models.wan.pipeline import WanPipeline

    log(f"[bench_wan] compile cache: {enable_compile_cache()}")
    log(f"[bench_wan] backend={jax.default_backend()}")

    if args.small:
        cfg = WanConfig.tiny()
        args.width, args.height, args.frames = 64, 64, 5
        args.steps = min(args.steps, 4)
    elif args.toy_text:
        cfg = WanConfig.wan_1_3b()
        # miniature text tower; the DiT's text_proj input width follows it
        cfg = dataclasses.replace(
            cfg,
            text=UMT5Config(vocab_size=512, dim=64, ffn_dim=128, num_heads=4,
                            head_dim=16, num_layers=2, max_length=512),
            dit=dataclasses.replace(cfg.dit, text_dim=64))
    else:
        cfg = WanConfig.wan_1_3b()
        # full umt5-xxl shape, weight-only int8 (random int8 init — timing
        # is weight-value-independent; real checkpoints quantise at load)
        cfg = dataclasses.replace(
            cfg, text=dataclasses.replace(cfg.text, quant="int8"))

    t0 = time.time()
    pipe = WanPipeline(cfg)
    log(f"[bench_wan] init {time.time() - t0:.1f}s")

    import numpy as np

    gen = lambda seed: pipe.generate_async(
        "a panda riding a motorbike through a neon city",
        steps=args.steps, frames=args.frames, width=args.width,
        height=args.height, seed=seed)

    t0 = time.time()
    np.asarray(gen(0))
    log(f"[bench_wan] compile+first {time.time() - t0:.1f}s")

    # Steady-state serving regime: one video always in flight, so video k's
    # >1 s uint8 device→host transfer overlaps video k+1's compute — the
    # SAME measurement loop as bench.py's SD15 number (adaptive warm-until-
    # steady, then median of the recorded intervals).
    from tpustack.utils.benchmark import pipelined_intervals

    times = pipelined_intervals(
        gen, repeats=args.repeats, warmup_min=args.warmup, warm_tol=0.05,
        log=lambda s: log(f"[bench_wan] {s}"), unit="video")

    sec = statistics.median(times)

    mfu = None
    from tpustack.utils.peaks import measurement_peaks

    peaks = measurement_peaks(jax.devices()[0])
    peak = peaks[0] if peaks else None
    if peak:
        try:
            flops = pipe.pipeline_flops(steps=args.steps, frames=args.frames,
                                        width=args.width, height=args.height)
            mfu = flops / sec / peak
            log(f"[bench_wan] {flops / 1e12:.1f} TFLOP/video → "
                f"{flops / sec / 1e12:.1f} TFLOP/s ({100 * mfu:.1f}% of "
                f"bf16 peak)")
        except Exception as e:
            log(f"[bench_wan] cost analysis unavailable: {e!r}")

    from tpustack.obs import perfsig

    result = {
        "metric": f"wan21_1.3b_{args.width}x{args.height}x{args.frames}f_"
                  f"{args.steps}step_videos_per_hour_per_chip",
        "value": round(3600.0 / sec, 2),
        "unit": "videos/hour/chip",
        "seconds_per_video": round(sec, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "meta": perfsig.artifact_meta(t_bench),
    }
    if content_check is not None:
        result["content_check"] = content_check
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
