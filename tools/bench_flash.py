#!/usr/bin/env python3
"""Microbenchmark the Pallas flash kernel at the serving hot shapes.

Round-4 tuning driver (VERDICT r3 #4/#5): the streaming kernel re-streams
the K/V panel once per q-block, so its HBM traffic scales with
``(Sq/block_q) * Sk`` — block sizes are the lever.  Shapes:

- ``wan``: Wan 1.3B DiT self-attention, B=2 (CFG) x 12 heads, S=8320, D=128,
  non-causal (reference shape ``generate_wan_t2v.py:305-312``).
- ``prefill``: Qwen-7B chunked prefill, one 8192-token chunk attending a
  17408-slot cache causally at offset (GQA 28q/4kv).
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import statistics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


#: the Deployment's decode shape (cluster-config/apps/llm/deployment.yaml):
#: 8 slots, Qwen2.5-7B GQA 28q/4kv x 128, int8 pool of 512+1 64-token
#: blocks, ctx 4096
PAGED_DEPLOYMENT = dict(b=8, s=1, h=28, hkv=4, d=128, blk=64, nb=64)
PAGED_SWEEP_CTX = (256, 512, 1024, 2048, 4096)
PAGED_SWEEP_LIVE = (1, 4, 8)
#: ms per layer-call of the (b, nb)-grid kernel this sweep replaced, from
#: the records (PERF.md §6, PR 27): PR 21's probe and PR 26's two traced
#: cells — (about ctx, live rows) → ms
PAGED_OLD_MS = {(4096, 8): 0.48, (2900, 4): 0.19, (450, 7.3): 0.122}


def _device_events(trace_dir: str):
    """``(name, start_ns, duration_ns)`` of every device operation in the
    newest trace under ``trace_dir`` — read as the benchmark's rooflines
    read it (``benchmark/readers/trace.py``)."""
    from benchmark.readers import trace

    devices = trace.extract(trace.find_xplane(trace_dir))
    return [e for events in devices.values() for e in events]


def paged_sweep(partial_fn, log, *, calls: int = 64, trace_dir=None):
    """Device time per ``paged_attention`` call over context x live rows at
    the Deployment's shape, int8 pool — the table ROADMAP S4 asked for.
    ``partial_fn`` is ``paged_attention_partial`` (a parameter so one sweep
    can time two trees' kernels).  Each cell runs ``calls`` chained calls
    in one program, the pool and tables loop-invariant as in a decode
    chunk, under the profiler; the time is the median of the kernel's own
    device events, the roofline share the cell's K/V + scale bytes over
    the chip's HBM bandwidth (``peaks.py``) over that."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.readers import trace
    from tpustack.ops.pallas.flash_attention import paged_scale_rows
    from tpustack.utils.peaks import measurement_peaks

    hbm_bytes_per_s = measurement_peaks(jax.devices()[0])[1]
    sh = PAGED_DEPLOYMENT
    b, h, hkv, d, blk, nb = (sh[k] for k in ("b", "h", "hkv", "d", "blk",
                                             "nb"))
    n_pool = b * nb + 1
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.bfloat16)
    # the pool as it rests (llama.init_kv_pool): heads folded into lanes,
    # scales token-minor; their lane rows made once, as a decode chunk does
    pk = jnp.asarray(rng.randint(-127, 128, (n_pool, blk, hkv * d)), jnp.int8)
    pv = jnp.asarray(rng.randint(-127, 128, (n_pool, blk, hkv * d)), jnp.int8)
    ks = jnp.asarray(rng.rand(n_pool, hkv * blk) * 0.02 + 1e-3, jnp.float32)
    vs = jnp.asarray(rng.rand(n_pool, hkv * blk) * 0.02 + 1e-3, jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pool)).reshape(b, nb),
                     jnp.int32)
    rows_kv = (paged_scale_rows(ks, bt, pk), paged_scale_rows(vs, bt, pv))

    @jax.jit
    def chain(q, lens):
        def step(qq, _):
            acc, m, l = partial_fn(qq, pk, pv, bt, lens, scale_rows=rows_kv)
            # the next call waits for this one; the values do not move
            return qq + (0 * acc[:, :, :, :1]).astype(qq.dtype), None
        return jax.lax.scan(step, q, None, length=calls)[0]

    rows = []
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="paged_sweep_")
    for ctx in PAGED_SWEEP_CTX:
        for live in PAGED_SWEEP_LIVE:
            lens = np.zeros(b, np.int32)
            lens[np.arange(live) * (b // live)] = ctx   # dead rows between
            lens = jnp.asarray(lens)
            chain(q, lens).block_until_ready()          # compile, warm
            cell_dir = os.path.join(trace_dir, f"ctx{ctx}_live{live}")
            with jax.profiler.trace(cell_dir):
                chain(q, lens).block_until_ready()
            secs = [dur / 1e9 for name, _, dur in _device_events(cell_dir)
                    if "paged_attention" in trace.short_name(name)]
            kv_bytes = ctx * live * hkv * (2 * d + 8)   # int8 K+V, f32 scales
            us = statistics.median(secs) * 1e6
            rows.append({
                "ctx": ctx, "live_rows": live, "calls": len(secs),
                "us_per_call": round(us, 2),
                "roofline_pct": round(
                    100 * kv_bytes / hbm_bytes_per_s / (us * 1e-6), 2)})
            log(f"[bench_flash] paged ctx {ctx:5d} live {live}: "
                f"{us:8.2f} us/call ({len(secs)} events), "
                f"{rows[-1]['roofline_pct']:.2f}% of the HBM roofline")
    return rows


def _paged_mode(args) -> int:
    """``--paged``: gather-vs-in-place paged decode attention.

    Two implementations of the same math — gather every table-mapped pool
    block into a dense ``[B, max_seq]`` view then run the masked XLA
    partial (what ``_pool_gather_body`` + ``dot_product_attention_partial``
    do per chunk), vs the Pallas kernel reading the pool blocks IN PLACE
    (``paged_attention_partial``).  Asserts the outputs agree and that the
    in-place path moves STRICTLY fewer HBM bytes per decode step
    (``paged_bytes_accounting`` — the same arithmetic ``bench_llm --paged``
    embeds in its roofline block).  On CPU the kernel runs in interpret
    mode and no time is reported (interpret wall clock proves nothing); on
    a TPU it adds the wall time of both at one ragged shape and
    ``paged_sweep``'s table of the kernel's device time per call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpustack.models.llama import pool_pages
    from tpustack.ops.attention import dot_product_attention_partial
    from tpustack.ops.pallas.flash_attention import (paged_attention_partial,
                                                     paged_bytes_accounting)

    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    on_tpu = jax.default_backend() == "tpu"
    if args.tiny or not on_tpu:
        # the CPU smoke shape (the tier-1 suite shells this): interpret-
        # mode kernel over a scrambled table, ragged lengths, GQA
        b, s, h, hkv, d, blk, nb = 4, 1, 4, 2, 16, 8, 8
        n_steps = 8
    else:
        b, s, h, hkv, d, blk, nb = (PAGED_DEPLOYMENT[k] for k in
                                    ("b", "s", "h", "hkv", "d", "blk", "nb"))
        n_steps = 16
    max_seq = blk * nb
    n_pool = b * nb + 1  # every slot fully backed + reserved block 0
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.randn(b, s, h, d), dt)
    pool_k = jnp.asarray(rng.randn(n_pool, blk, hkv, d), dt)
    pool_v = jnp.asarray(rng.randn(n_pool, blk, hkv, d), dt)
    # scrambled tables: valid prefix blocks are real allocations, the idle
    # tail points at the reserved block 0 (whose garbage must never leak)
    lens = np.asarray([max_seq * (i + 1) // b for i in range(b)], np.int32)
    lens[0] = 3  # one ragged mid-block row
    bt = np.zeros((b, nb), np.int32)
    alloc = rng.permutation(np.arange(1, n_pool))
    pos = 0
    for i in range(b):
        valid = -(-int(lens[i]) // blk)
        bt[i, :valid] = alloc[pos:pos + valid]
        pos += valid
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)

    def dense_view(x):
        g = jnp.take(x, bt.reshape(-1), axis=0)
        return g.reshape((b, nb * x.shape[1]) + x.shape[2:])

    def dense_partial(qq, k, v):
        mask = jnp.arange(max_seq)[None, None, :] < lens[:, None, None]
        return dot_product_attention_partial(
            qq, k, v, mask=jnp.broadcast_to(mask, (b, s, max_seq)))

    gather_partial = lambda qq: dense_partial(qq, dense_view(pool_k),
                                              dense_view(pool_v))

    rest_k, rest_v = pool_pages("k", pool_k), pool_pages("v", pool_v)
    inplace_partial = lambda qq: paged_attention_partial(
        qq, rest_k, rest_v, bt, lens)

    # (acc, m, l) compared as what they are for: the normalised output and
    # the two merge statistics (an unnormalised acc over 4k bf16 tokens
    # carries rounding of the order of an absolute tolerance)
    norm = lambda part: (part[0] / jnp.maximum(part[2][..., None], 1e-30),
                         part[1], part[2])
    ref = norm(jax.jit(gather_partial)(q))
    got = norm(jax.jit(inplace_partial)(q))
    ok = all(np.allclose(np.asarray(x), np.asarray(y), rtol=2e-2, atol=2e-2)
             for x, y in zip(got, ref))
    log(f"[bench_flash] paged in-place vs gather allclose: {ok}")

    esize = jnp.dtype(dt).itemsize
    mean_valid = float(np.mean([-(-int(x) // blk) for x in np.asarray(lens)]))
    bytes_acct = paged_bytes_accounting(
        n_valid_blocks=int(round(mean_valid)), blocks_per_seq=nb, block=blk,
        kvh=hkv, hd=d, esize=esize, scale_bytes=0, n_steps=n_steps)
    fewer = (bytes_acct["paged_flash_step_bytes"]
             < bytes_acct["gather_step_bytes"])
    log(f"[bench_flash] per-step bytes (mean slot): gather "
        f"{bytes_acct['gather_step_bytes']:.0f} vs in-place "
        f"{bytes_acct['paged_flash_step_bytes']:.0f} (fewer={fewer})")

    timing = sweep = None
    if on_tpu and not args.tiny:
        import tempfile

        from benchmark.readers import trace

        # device time per step of one n_steps decode chunk, all of it: the
        # gather path copies the dense view once a chunk and reads all of
        # it every step, the in-place path gathers its scale rows once
        def chunk(attend_of):
            def run(qq):
                attend = attend_of()
                def step(c, _):
                    acc = attend(c)[0]
                    return c + (0 * acc[:, :, :, :1]).astype(c.dtype), None
                return jax.lax.scan(step, qq, None, length=n_steps)[0]
            return jax.jit(run)

        def gather_chunk():
            k, v = dense_view(pool_k), dense_view(pool_v)
            return lambda qq: dense_partial(qq, k, v)

        timing = {}
        for name, fn in (("gather", chunk(gather_chunk)),
                         ("inplace", chunk(lambda: inplace_partial))):
            fn(q).block_until_ready()
            tdir = tempfile.mkdtemp(prefix=f"paged_{name}_")
            with jax.profiler.trace(tdir):
                fn(q).block_until_ready()
            us = trace.busy_ns(_device_events(tdir)) / 1e3 / n_steps
            timing[f"{name}_us_per_step"] = round(us, 2)
            log(f"[bench_flash] paged {name}: {us:.2f} us of device time a "
                f"step of a {n_steps}-step chunk")
        sweep = paged_sweep(paged_attention_partial, log)

    print(json.dumps({
        "shape": "paged", "batch": b, "heads": h, "kv_heads": hkv,
        "head_dim": d, "block": blk, "blocks_per_seq": nb,
        "interpret": not on_tpu, "outputs_allclose": bool(ok),
        "bytes_per_step": {k: round(v, 1) for k, v in bytes_acct.items()},
        "inplace_moves_fewer_bytes": bool(fewer), "timing": timing,
        "sweep": sweep,
        "old_ms_per_call": {f"ctx~{c},live~{n}": ms
                            for (c, n), ms in PAGED_OLD_MS.items()},
    }))
    # both properties gate: a wrong kernel or a bytes model that stopped
    # favoring in-place fails the smoke (tier-1 shells this)
    return 0 if (ok and fewer) else 1


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default="wan",
                   choices=["wan", "wan16f", "prefill"])
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--block-q", type=int, nargs="*", default=[128, 256, 512, 1024])
    p.add_argument("--block-k", type=int, nargs="*", default=[512, 1024])
    p.add_argument("--panel", action="store_true",
                   help="also try the panel kernel (raise panel_max_kv)")
    p.add_argument("--paged", action="store_true",
                   help="paged decode attention microbench: gather the "
                        "block table into a dense view vs the in-place "
                        "scalar-prefetch kernel (correctness + per-step "
                        "bytes always; timing on real TPU only)")
    p.add_argument("--tiny", action="store_true",
                   help="paged mode: force the CPU smoke shape")
    args = p.parse_args()
    from tpustack.utils import require_accelerator

    require_accelerator()
    if args.paged:
        return _paged_mode(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpustack.ops.pallas.flash_attention import flash_attention
    from tpustack.utils.benchmark import pipelined_intervals

    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    key = jax.random.PRNGKey(0)

    if args.shape == "wan":
        b, sq, h, d, hkv = 2, 8320, 12, 128, 12
        sk, causal, q_off, kv_len = sq, False, None, None
        flops = 4 * b * h * sq * sk * d
    elif args.shape == "wan16f":
        # the 512x320x16f serving hot shape: S=2560 — PANEL-kernel block_q
        # sweep (in-situ xprof r5: the panel runs ~132 TFLOP/s here at the
        # default block_q 128 while the surrounding matmuls do 172-192)
        b, sq, h, d, hkv = 2, 2560, 12, 128, 12
        sk, causal, q_off, kv_len = sq, False, None, None
        flops = 4 * b * h * sq * sk * d
    else:
        b, sq, h, d, hkv = 1, 8192, 28, 128, 4
        sk = 17408
        causal, q_off, kv_len = True, 8192, 16384
        # valid attention pairs: rows at 8192..16383 attend their prefix
        pairs = sum(q_off + i + 1 for i in range(sq))
        flops = 4 * b * h * d * pairs

    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), jnp.bfloat16)

    results = []
    if args.shape == "wan16f":
        # sweep the PANEL kernel's block_q (block_k unused there)
        combos = [(bq, 512, True) for bq in args.block_q]
    else:
        combos = [(bq, bk, False) for bq, bk in
                  itertools.product(args.block_q, args.block_k)]
        if args.panel and args.shape == "wan":
            combos.append((128, 512, True))

    # Chain kernel applications (out feeds the next q) inside one jit: a
    # ms-scale kernel timed one dispatch at a time measures the dispatch,
    # not the kernel.  The chain must total well past the per-dispatch
    # fixed cost or block-size effects vanish.  Start from a FLOPs guess
    # at 30 TFLOP/s and re-scale once from the first measured config so
    # every config runs >= ~400 ms.
    iters = max(8, int(0.4 / max(flops / 30e12, 1e-4)))

    for bq, bk, panel in combos:
        tag = "panel" if panel else f"bq{bq}_bk{bk}"
        try:
            # non-panel rows must FORCE the streaming kernel: with
            # PANEL_MAX_KV at 8704 the wan shape (S=8320, no q_offset)
            # would otherwise take the panel branch for every combo,
            # silently ignoring block_k and mislabelling the sweep.
            # Passing kv_len=sk (semantically a no-op) selects the
            # dynamic/streaming branch without touching block sizes.
            fn = functools.partial(
                flash_attention, causal=causal, block_q=bq, block_k=bk,
                q_offset=q_off,
                kv_len=(kv_len if panel or kv_len is not None else sk),
                panel_max_kv=(sk + 512 if panel else None))

            n_it = iters

            @functools.partial(jax.jit, static_argnums=(3,))
            def chained(q0, kk, vv, n):
                def body(i, acc):
                    return fn(acc, kk, vv).astype(q0.dtype)
                return jax.lax.fori_loop(0, n, body, q0).sum()

            def dispatch(seed):
                return chained(q, k, v, n_it)

            np.asarray(dispatch(0))  # compile
            times = pipelined_intervals(dispatch, repeats=args.repeats,
                                        warmup_min=1, warmup_max=4,
                                        unit="call")
            med = statistics.median(times) / n_it
            if med * n_it < 0.25:  # still dispatch-floored: rescale, re-run
                n_it = max(n_it, int(0.4 / med))
                iters = n_it  # persist for the remaining configs
                np.asarray(dispatch(0))
                times = pipelined_intervals(dispatch, repeats=args.repeats,
                                            warmup_min=1, warmup_max=4,
                                            unit="call")
                med = statistics.median(times) / n_it
            tf = flops / med / 1e12
            log(f"[{tag}] {med*1e3:.2f} ms  {tf:.1f} TFLOP/s")
            results.append({"config": tag, "ms": round(med * 1e3, 2),
                            "tflops": round(tf, 1)})
        except Exception as e:  # noqa: BLE001 - report and continue the sweep
            log(f"[{tag}] FAILED: {type(e).__name__}: {str(e)[:200]}")
            results.append({"config": tag, "error": str(e)[:120]})

    print(json.dumps({"shape": args.shape, "flops_G": round(flops / 1e9, 1),
                      "results": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
