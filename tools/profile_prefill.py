#!/usr/bin/env python3
"""Profile chunked prefill on the real chip: timing + xprof per-op table.

Round-4 companion to ``tools/bench_llm.py`` (VERDICT r3 #5: "give prefill
the decode treatment").  Runs the 7B serving config's ``_prefill_walk`` at a
dispatch-amortised size, times it device-honestly (block_until_ready), and
captures an xplane trace for ``tools/xprof_summary.py``.

Usage:
    python tools/profile_prefill.py --prompt-tokens 16384 --repeats 3 \
        --trace-dir /tmp/prefill-trace
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="qwen25_7b",
                   choices=["llama2_7b", "qwen25_7b", "tiny"])
    p.add_argument("--prompt-tokens", type=int, default=16384)
    p.add_argument("--quant", default="int8", choices=["int8", "none"])
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--trace-dir", default=None)
    args = p.parse_args()

    from tpustack.utils import enable_compile_cache, require_accelerator

    require_accelerator()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpustack.models.llama import LlamaConfig, LlamaModel, init_kv_caches
    from tpustack.models.llm_generate import Generator

    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    log(f"[profile_prefill] compile cache: {enable_compile_cache()}")
    log(f"[profile_prefill] backend={jax.default_backend()}")

    quant = None if args.quant == "none" else args.quant
    if args.preset == "tiny":
        cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=128), quant=quant)
        dtype = jnp.float32
        args.prompt_tokens = 64
    else:
        base = (LlamaConfig.llama2_7b() if args.preset == "llama2_7b"
                else LlamaConfig.qwen25_7b())
        # room for the prompt plus a little decode headroom
        cfg = dataclasses.replace(base, max_seq=args.prompt_tokens + 1024,
                                  quant=quant)
        dtype = jnp.bfloat16

    t0 = time.time()
    model = LlamaModel(cfg, dtype=dtype)
    tmpl = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree.map(
        lambda t: jnp.zeros(t.shape, t.dtype if t.dtype == jnp.int8 else dtype),
        tmpl)
    gen = Generator(cfg, params=params, dtype=dtype)
    if args.preset == "tiny":
        gen.PREFILL_CHUNK = 32  # two chunks of the 64-token smoke
    log(f"[profile_prefill] init {time.time() - t0:.1f}s")

    P = args.prompt_tokens
    tokens = np.arange(5, 5 + P, dtype=np.int32).reshape(1, P) % 1000
    length = jnp.asarray([P], jnp.int32)

    def dispatch(seed):
        # returns a small device array; the benchmark loop's np.asarray on
        # the PREVIOUS dispatch is the blocking fetch
        caches = init_kv_caches(cfg, 1, dtype=gen.cache_dtype)
        logits, caches = gen._prefill_walk(gen.params, jnp.asarray(tokens),
                                            length, caches)
        return logits.sum()

    t0 = time.time()
    np.asarray(dispatch(0))
    log(f"[profile_prefill] compile+first {time.time() - t0:.1f}s")

    from tpustack.utils.benchmark import pipelined_intervals

    times = pipelined_intervals(dispatch, repeats=args.repeats, log=log,
                                unit="prefill")

    if args.trace_dir:
        with jax.profiler.trace(args.trace_dir):
            np.asarray(dispatch(1))
        log(f"[profile_prefill] trace → {args.trace_dir}")

    med = statistics.median(times)

    # FLOPs accounting: matmul weights (2·params/token) + causal attention
    # (QK^T and P·V each 2·d_attn per (q,k) pair; causal halves the pairs)
    flat = jax.tree_util.tree_leaves_with_path(gen.params)
    leaf_name = lambda pth: str(pth[-1].key if hasattr(pth[-1], "key")
                                else pth[-1])
    matmul_flops = 2 * sum(x.size for pth, x in flat
                           if leaf_name(pth) == "kernel") * P
    d_attn = cfg.n_heads * cfg.head_dim
    attn_flops = cfg.n_layers * 4 * d_attn * (P * (P + 1) // 2)
    flops = matmul_flops + attn_flops
    # bytes: weights stream once per chunk; KV cache read grows per chunk
    n_chunks = max(1, (P + gen.PREFILL_CHUNK - 1) // gen.PREFILL_CHUNK)
    weight_bytes = sum(x.nbytes for pth, x in flat
                       if not any("embed" in str(getattr(k, "key", k))
                                  for k in pth)) * n_chunks
    kv_elt = 2
    kv_bytes = (cfg.n_layers * 2 * cfg.max_seq * cfg.n_kv_heads *
                cfg.head_dim * kv_elt) * n_chunks  # full static cache/chunk
    from tpustack.utils.peaks import measurement_peaks

    peak = measurement_peaks(jax.devices()[0])
    out = {
        "prompt_tokens": P,
        "chunks": n_chunks,
        "median_s": round(med, 3),
        "tok_per_s": round(P / med, 1),
        "flops_T": round(flops / 1e12, 2),
        "matmul_flops_T": round(matmul_flops / 1e12, 2),
        "attn_flops_T": round(attn_flops / 1e12, 2),
        "bytes_GB": round((weight_bytes + kv_bytes) / 1e9, 2),
    }
    if peak:  # None on a CPU rehearsal only; an unknown chip raised above
        t_min = max(flops / peak[0], (weight_bytes + kv_bytes) / peak[1])
        out.update({
            "t_min_s": round(t_min, 3),
            "roofline_pct": round(100 * t_min / med, 1),
            "mfu_pct": round(100 * flops / peak[0] / med, 1),
        })
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
