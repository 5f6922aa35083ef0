#!/usr/bin/env python
"""Headline benchmark: SD1.5 text→image samples/sec/chip.

This is the reference stack's baseline-metric workload (BASELINE.json:
"batch_generate.py samples/sec/chip"): Stable Diffusion 1.5 at 512x512,
30 denoise steps, guidance 7.5 — the exact serving shape of the reference's
sd15-api (``cluster-config/apps/sd15-api/configmap.yaml:52-58``,
``scripts/batch_generate.py``), generated by the TPU-native pipeline
(one fused XLA program: CLIP → CFG denoise loop → VAE decode).

The reference publishes no numbers (BASELINE.md); ``vs_baseline`` is computed
against a documented estimate of the reference's own hardware on this exact
workload: a GTX 1070 runs SD1.5 fp16 512x512 at roughly 1.1 s/denoise-step
⇒ ~33 s/image ⇒ 0.030 samples/s.

Measurement discipline: the XLA persistent compilation cache
is enabled (kills the ~2-minute cold start after the first ever run), the
timed region is preceded by compile + warmup runs, and the reported value is
the MEDIAN of ``--repeats`` (default 5) with the IQR logged to stderr so
run-to-run drift is visible.  ``mfu`` reports achieved model FLOP/s over the
chip's bf16 peak, with FLOPs taken from XLA's own cost analysis of the
compiled program.

One process per chip: a chip belongs to the process that initialised a
JAX backend on it, so this file's parent never imports jax.  It runs every
measurement — the SD one included (``--phase sd``) — as a child, one after
another, and folds their one-line artifacts together.  A child that fails,
or a content check that does not pass, is recorded in the line AND makes
the run exit non-zero.

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GTX1070_EST_SAMPLES_PER_S = 0.030  # documented estimate, see module docstring

log = lambda *a: print(*a, file=sys.stderr, flush=True)


def _parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--width", type=int, default=512)
    p.add_argument("--height", type=int, default=512)
    p.add_argument("--batch", type=int, default=8,
                   help="images per fused program; 8 is the serving default "
                        "(micro-batcher + batch-generate Job --concurrency)")
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--warmup", type=int, default=2,
                   help="minimum untimed pipelined intervals before the "
                        "measured ones; warmup continues past this until two "
                        "consecutive intervals agree within --warm-tol")
    p.add_argument("--warmup-max", type=int, default=8,
                   help="hard cap on adaptive warmup intervals")
    p.add_argument("--warm-tol", type=float, default=0.04,
                   help="relative gap between consecutive warmup intervals "
                        "considered steady-state")
    p.add_argument("--window", type=int, default=2,
                   help="batches per measured interval: each recorded "
                        "sample averages this many back-to-back batches "
                        "(throughput over a longer window — what a serving "
                        "deployment experiences)")
    p.add_argument("--small", action="store_true",
                   help="tiny model/shape smoke run (not the headline number)")
    p.add_argument("--no-content-check", action="store_true",
                   help="skip the tools/verify_hw.py content verification "
                        "(train→export→serve parity on the real chip)")
    p.add_argument("--no-extras", action="store_true",
                   help="skip the LLM/Wan driver-witness measurements "
                        "folded into the JSON line (the driver artifact "
                        "records the full perf surface, not just SD15)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel mesh size (v5e-8: 8); batch must divide")
    p.add_argument("--phase", choices=["sd"],
                   help="internal: run one measurement in-process")
    return p.parse_args(argv)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = _parse_args(argv)
    if args.phase == "sd":
        return _measure_sd(args)

    # parent: stays off jax — the chip is each child's in turn
    sd = _run_tool("sd15", ["bench.py", *argv, "--phase", "sd"],
                   timeout=3600)
    if "error" in sd:
        log("[bench] the SD measurement failed; nothing to report")
        return 1
    result = sd
    if args.small and not args.no_content_check:
        log("[bench] --small: skipping content check (run tools/verify_hw.py "
            "directly for the hardware content proof)")
    elif not args.no_content_check:
        result["content_check"] = _content_check()
    if not args.small and not args.no_extras:
        result["llm"] = _llm_extras(sd["device"]["count"])
        result["wan"] = _wan_extras()
    print(json.dumps(result))
    cells = {**result.get("llm", {}), "wan": result.get("wan", {})}
    failed = [name for name, cell in cells.items() if "error" in cell]
    if result.get("content_check", "pass") != "pass":
        failed.append("content_check")
    if failed:
        log(f"[bench] FAILED: {', '.join(failed)}")
    return 1 if failed else 0


def _measure_sd(args) -> int:
    """The SD1.5 measurement (a child of ``main``: the one place in this
    file that touches jax)."""
    t_bench = time.time()

    from tpustack.utils import (device_info, enable_compile_cache,
                                require_accelerator)

    require_accelerator()
    import jax

    from tpustack.models.sd15 import SD15Config, SD15Pipeline

    # the 512x512/30-step program costs ~2 min to compile cold; cached
    # reruns skip it
    log(f"[bench] compile cache: {enable_compile_cache()}")
    log(f"[bench] backend={jax.default_backend()} devices={jax.devices()}")

    mesh = None
    if args.dp > 1:
        from tpustack.parallel import build_mesh

        mesh = build_mesh((args.dp, 1, 1, 1), devices=jax.devices()[:args.dp])
        args.batch += (-args.batch) % args.dp  # round up to a dp multiple
        log(f"[bench] dp mesh over {args.dp} devices, batch {args.batch}")

    if args.small:
        cfg = SD15Config.tiny()
        args.width = args.height = 64
        args.steps = min(args.steps, 4)
    else:
        cfg = SD15Config.sd15()

    t0 = time.time()
    pipe = SD15Pipeline(cfg)
    log(f"[bench] init {time.time() - t0:.1f}s")

    kw = dict(steps=args.steps, width=args.width, height=args.height,
              batch_size=args.batch, mesh=mesh)
    prompt = "a photo of an astronaut riding a horse on mars"
    gen = lambda seed: pipe.generate(prompt, seed=seed, **kw)

    t0 = time.time()
    gen(0)
    log(f"[bench] compile+first run {time.time() - t0:.1f}s")

    # Steady-state throughput, exactly like the serving micro-batcher under
    # load: one batch always in flight, so batch k's device→host image
    # transfer (and host work) overlaps batch k+1's compute; warmup runs in
    # the SAME pipelined regime (r2's first measured interval was 19% high
    # because warmup went through the blocking generate() path).  Shared
    # discipline with tools/bench_wan.py.
    from tpustack.utils.benchmark import pipelined_intervals

    times = pipelined_intervals(
        lambda seed: pipe.generate_async(prompt, seed=seed, **kw),
        repeats=args.repeats, window=args.window, warmup_min=args.warmup,
        warmup_max=args.warmup_max, warm_tol=args.warm_tol,
        log=lambda s: log(f"[bench] {s}"), unit="batch")

    # percentiles via the serving stack's own histogram type (tpustack.obs)
    # — the same estimator the /metrics latency histograms use, exact here
    # because every repeat is retained
    from tpustack.obs.metrics import Histogram

    hist = Histogram("tpustack_bench_batch_latency_seconds",
                     "per-interval batch latency", sample_cap=len(times))
    for t in times:
        hist.observe(t)
    sec_per_batch = hist.percentile(50)
    pcts = {f"p{q}": round(hist.percentile(q), 4) for q in (50, 90, 99)}
    if len(times) >= 4:
        iqr = hist.percentile(75) - hist.percentile(25)
        spread_pct = 100.0 * iqr / sec_per_batch
        log(f"[bench] median {sec_per_batch:.3f}s  IQR {iqr:.3f}s "
            f"({spread_pct:.1f}%)  min {min(times):.3f}s  max {max(times):.3f}s")
    n_chips = args.dp if mesh is not None else 1
    samples_per_sec_per_chip = args.batch / sec_per_batch / n_chips

    from tpustack.utils.peaks import measurement_peaks

    mfu = flops_per_batch = None
    peaks = measurement_peaks(jax.devices()[0])
    peak = peaks[0] if peaks else None
    try:
        # loop-aware component sum (cost_analysis counts a fori_loop body
        # once); flops here are GLOBAL for the whole batch, so per-chip MFU
        # divides by all chips' peak
        flops_per_batch = pipe.pipeline_flops(
            steps=args.steps, width=args.width, height=args.height,
            batch_size=args.batch)
    except Exception as e:
        log(f"[bench] cost analysis unavailable: {e!r}")
    if flops_per_batch and peak:
        mfu = flops_per_batch / sec_per_batch / (peak * n_chips)
        log(f"[bench] {flops_per_batch / 1e12:.1f} TFLOP/batch → "
            f"{flops_per_batch / sec_per_batch / 1e12 / n_chips:.1f} "
            f"TFLOP/s/chip ({100 * mfu:.1f}% of bf16 peak)")

    # shared provenance block (tpustack.obs.perfsig.artifact_meta — the
    # one helper bench.py/bench_llm/bench_wan all stamp with): git sha,
    # device kind, knob-registry snapshot, schema version, run timestamp
    from tpustack.obs import perfsig

    result = {
        "metric": f"sd15_{args.width}x{args.height}_{args.steps}step_batch{args.batch}"
                  "_samples_per_sec_per_chip",
        "meta": perfsig.artifact_meta(t_bench),
        "device": device_info(),
        "value": round(samples_per_sec_per_chip, 4),
        "unit": "samples/s/chip",
        "vs_baseline": round(samples_per_sec_per_chip / GTX1070_EST_SAMPLES_PER_S, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "iqr_pct": round(spread_pct, 1) if len(times) >= 4 else None,
        # per-interval latency percentiles over the repeat runs, from the
        # obs histogram (exact: all repeats retained)
        "batch_latency_percentiles_s": pcts,
    }
    print(json.dumps(result))
    return 0


def _run_tool(name: str, cmd: list, timeout: int = 1500):
    """Run a measurement as a child, return its final-stdout-line JSON (the
    tools' one-line contract) or an {"error": ...} record.  The record keeps
    the rest of the line readable; ``main`` turns any such record into a
    non-zero exit."""
    full = [sys.executable, os.path.join(ROOT, *cmd[0].split("/"))] + cmd[1:]
    log(f"[bench] {name}: " + " ".join(cmd))
    t0 = time.time()
    try:
        proc = subprocess.run(full, capture_output=True, text=True,
                              timeout=timeout)
        sys.stderr.write(proc.stderr or "")  # the child's own progress log
        if proc.returncode != 0:
            # a tool that died after printing a stale/partial JSON-looking
            # line must NOT be recorded as a valid measurement
            tail = (proc.stderr or "").strip()[-400:]
            log(f"[bench] {name} exited {proc.returncode} after "
                f"{time.time() - t0:.0f}s")
            return {"error": f"exit code {proc.returncode}",
                    "stderr_tail": tail}
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        out = json.loads(line)
        log(f"[bench] {name} done in {time.time() - t0:.0f}s: "
            f"{out.get('value')} {out.get('unit')}")
        return out
    except Exception as e:
        log(f"[bench] {name} errored after {time.time() - t0:.0f}s: {e!r}")
        return {"error": str(e)}


def _llm_extras(n_devices: int) -> dict:
    """Driver-witnessed LLM serving numbers: the CONTINUOUS
    engine's end-to-end batch-8 decode at the Deployment's exact env
    (Qwen-7B shape, int8 weights + int8 KV, ``LLM_CTX=4096`` — the
    reference's ``--ctx-size`` parity; ctx 2048 numbers in PERF.md run
    ~9% higher from the halved per-step KV read), and one 8k prefill."""
    common = ["--preset", "qwen25_7b", "--quant", "int8",
              "--kv-quant", "int8"]
    decode = _run_tool("llm continuous batch-8", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--prompt-tokens", "128", "--new-tokens", "256",
        "--batch", "8", "--continuous", "--repeats", "3"])
    prefill = _run_tool("llm 8k prefill", [
        "tools/bench_llm.py", *common, "--ctx", "8448",
        "--prompt-tokens", "8192", "--new-tokens", "16", "--repeats", "2"])
    # prefix KV cache under chat-shaped traffic: 8 requests sharing a
    # 1k-token system prompt — reports prefill skipped + TTFT on/off
    shared = _run_tool("llm shared-prefix cache", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--prompt-tokens", "1024", "--new-tokens", "32",
        "--shared-prefix", "--requests", "8"])
    # paged KV pool: concurrency sweep at 1k/4k footprints inside a ctx-4k
    # engine — admitted concurrency paged-vs-dense at the same HBM budget
    paged = _run_tool("llm paged-kv sweep", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--new-tokens", "64", "--paged", "--dense-slots", "8",
        "--req-ctx", "1024,2048,4096"])
    # speculative decoding: spec on/off at batch 1/4/8 over repetitive vs
    # random traffic — acceptance rate, tokens per weight pass, tok/s
    spec = _run_tool("llm speculative sweep", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--prompt-tokens", "512", "--new-tokens", "256", "--speculative"])
    # tensor-parallel serving: the continuous engine unsharded vs over a
    # tp=8 mesh (dense + paged), parity-asserted, with the per-chip HBM
    # bill — a cell only a host with eight chips has
    if n_devices >= 8:
        tp = _run_tool("llm tp=8 sweep", [
            "tools/bench_llm.py", *common, "--ctx", "4096",
            "--prompt-tokens", "512", "--new-tokens", "128",
            "--batch", "8", "--tp", "8"])
    else:
        tp = {"skipped": f"needs 8 devices, this host has {n_devices}"}
    # host-KV-tier sweep: Zipf-revisited document preambles against a
    # pool ~1/3 of the working set, tier off vs on — prefix hit ratio,
    # TTFT, and the spill/restore ledger (identity-asserted)
    host_tier = _run_tool("llm host-tier sweep", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--prompt-tokens", "1024", "--new-tokens", "32",
        "--host-tier", "--docs", "8", "--requests", "16"])
    # chunked prefill: a long prompt sliced into decode-wave-aligned
    # chunks next to short peers — short-request TTFT off vs on, chunk
    # dispatches pinned (identity-asserted)
    chunked = _run_tool("llm chunked prefill", [
        "tools/bench_llm.py", *common, "--ctx", "4096",
        "--new-tokens", "64", "--chunked-prefill",
        "--prefill-chunk-tokens", "512"])
    # open-loop trace replay: bursty mixed-PRIORITY load against a self-
    # hosted env-configured server — per-tenant AND per-priority goodput
    # + latency percentiles under REALISTIC arrivals, not the closed-
    # loop sweeps above (tools/replay.py; the artifact records offered
    # vs goodput split by class, plus the server's qos counters)
    replay = _run_tool("llm open-loop replay", [
        "tools/replay.py", "--self-host", "--duration", "20",
        "--tenants", "interactive:4:interactive,batch:1:batch",
        "--burstiness", "2.0", "--seed", "1"])
    # ONE keep-list, shared with tests/test_bench_extras.py via
    # tools/bench_schema.py — the driver's pruning and the schema test
    # cannot drift apart
    from tools.bench_schema import LLM_EXTRA_KEEP, prune

    return {
        "continuous_e2e": prune(decode, LLM_EXTRA_KEEP),
        "prefill_8k": prune(prefill, LLM_EXTRA_KEEP),
        "shared_prefix": prune(shared, LLM_EXTRA_KEEP),
        "paged": prune(paged, LLM_EXTRA_KEEP),
        "speculative": prune(spec, LLM_EXTRA_KEEP),
        "host_tier": prune(host_tier, LLM_EXTRA_KEEP),
        "chunked_prefill": prune(chunked, LLM_EXTRA_KEEP),
        "tp": prune(tp, LLM_EXTRA_KEEP),
        "replay": prune(replay, LLM_EXTRA_KEEP),
    }


def _wan_extras() -> dict:
    """Driver-witnessed Wan T2V number: the reference shape
    512x320x16f/25-step (``generate_wan_t2v.py:305-308``).  Content gating
    for the wan family already ran in _content_check, so the duplicate
    check is skipped here."""
    wan = _run_tool("wan 512x320x16f", [
        "tools/bench_wan.py", "--repeats", "3", "--no-content-check"])
    from tools.bench_schema import WAN_KEEP, prune

    return prune(wan, WAN_KEEP)


def _content_check(families: str = "", workdir: str = "verify_hw") -> str:
    """Hardware content verification: run ``tools/verify_hw.py`` — tiny
    real-weight train→export→reload→generate parity (SD15 + LLM + Wan) and
    the Pallas kernel vectors, computed on the REAL chip against a CPU
    reference — and return its verdict (``pass`` / ``fail`` / ``error:
    …``; anything but ``pass`` fails the run).  verify_hw's own parent
    stays off jax, so this is safe to call from one that does too — and
    only from one: a process that has initialised a backend holds the
    chip its hw phase needs.  The workdir persists under .cache/ so the
    CPU reference phase is reused across bench runs.  ``families`` narrows
    the check (tools/bench_wan.py gates on its own families with a
    separate workdir so the two caches don't invalidate each other's
    fingerprint)."""
    cmd = [sys.executable, os.path.join(ROOT, "tools", "verify_hw.py"),
           "--workdir", os.path.join(ROOT, ".cache", workdir)]
    if families:
        cmd += ["--families", families]
    log("[bench] content check: " + " ".join(cmd[1:]))
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=1200)
        if not proc.stdout.strip():
            raise RuntimeError(
                f"verify_hw produced no verdict (rc={proc.returncode}); "
                f"stderr tail: {proc.stderr.strip()[-400:]}")
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
        log(f"[bench] content check {verdict['content_check']} in "
            f"{time.time() - t0:.0f}s (families: "
            f"{', '.join(k + '=' + ('ok' if v['pass'] else 'FAIL') for k, v in verdict['families'].items())})")
        return verdict["content_check"]
    except Exception as e:
        log(f"[bench] content check errored: {e!r}")
        return f"error: {e}"


if __name__ == "__main__":
    sys.exit(main())
