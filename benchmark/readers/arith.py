"""What the algorithm needs: operations and bytes from shapes and true
lengths, held against the chip's peaks.

The flops and weight arithmetic follows ``tpustack/obs/flight.py::
llm_wave_arith`` (2 flops per matmul weight element per token); the KV bytes
do not: they are counted from each request's true context, not from a whole
``max_seq`` line, because a paged kernel reads only the blocks in use.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.loadgen import n_tokens as _tokens


def model_dims(cfg: Dict) -> Dict:
    """Shapes from a Hugging Face style config dict."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kvh": kvh, "hd": hd,
            "ffn": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def matmul_elements(cfg: Dict) -> Dict[str, int]:
    """Weight elements a token multiplies: in the layers, and in the head."""
    m = model_dims(cfg)
    per_layer = (m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kvh"] * m["hd"]
                 + m["h"] * m["hd"] * m["d"] + 3 * m["d"] * m["ffn"])
    return {"layers": m["layers"] * per_layer, "head": m["d"] * m["vocab"]}


def request_flops(cfg: Dict, n_prompt: int, n_out: int) -> float:
    """Flops one request needs: every token through the layers, the head
    once per sampled token, attention over the true context of each token
    (QK^T and PV: 4 * context * head_dim per query head per layer)."""
    m = model_dims(cfg)
    el = matmul_elements(cfg)
    n = n_prompt + max(0, n_out - 1)       # tokens fed through the layers
    # sum of context lengths 1..n, causal
    ctx_sum = n * (n + 1) / 2
    attn = 4.0 * m["hd"] * m["h"] * m["layers"] * ctx_sum
    return 2.0 * el["layers"] * n + 2.0 * el["head"] * n_out + attn


def kv_bytes_per_token(cfg: Dict) -> float:
    """Bytes of K and V that one cached token holds, over all layers."""
    m = model_dims(cfg)
    per_vec = m["hd"] * (1 if cfg.get("kv") == "int8" else 2) + (
        4 if cfg.get("kv") == "int8" else 0)  # + one f32 scale per vector
    return 2.0 * m["kvh"] * per_vec * m["layers"]


def window_flops(cfg: Dict, records: List[Dict], w0: float, w1: float
                 ) -> float:
    """Flops of the work that reached a client inside the window: a
    request's prefill counts where its first token arrives, its decode steps
    in proportion to the tokens that arrive inside."""
    total = 0.0
    for r in records:
        n = _tokens(r)
        if r["status"] != 200 or r.get("error") or n < 1:
            continue
        whole = request_flops(cfg, r["prompt_tokens"], n)
        prefill = request_flops(cfg, r["prompt_tokens"], 1)
        if w0 <= r["t_first"] <= w1:
            total += prefill
        if n > 1:
            inside = sum(c for t, c in r["chunks"] if w0 <= t <= w1
                         and t > r["t_first"])
            total += (whole - prefill) * min(1.0, inside / (n - 1))
    return total


def model_mfu(ctx, **_):
    """The whole window's share of the chip's bf16 peak: flops the work of
    the window needed, over window seconds, over the peak.  True tokens
    only: padding and recomputation do not count."""
    peaks = ctx.get("peaks")
    if not peaks:
        return None
    w0, w1 = ctx["window"]
    flops = window_flops(ctx["cfg"], ctx["records"], w0, w1)
    if flops <= 0:
        return None
    return 100.0 * flops / (w1 - w0) / (peaks["flops_bf16"] * ctx["chips"])


def decode_kv_bytes(cfg: Dict, records: List[Dict], a: float, b: float,
                    chunk: int) -> float:
    """KV bytes the decode steps between ``a`` and ``b`` had to read: for
    each token a request produced in that span, the context the main cache
    held for it.  A token's time is interpolated between the request's first
    and last chunk; the kernel reads the cache as it stood at the start of
    the engine's chunk, so ``chunk`` tokens are taken off (a lower bound:
    the least bytes, hence the least time)."""
    per_tok = kv_bytes_per_token(cfg)
    total = 0.0
    for r in records:
        n = _tokens(r)
        if n < 2 or r.get("t_first") is None or r["status"] != 200:
            continue
        t0, t1 = r["t_first"], r["t_last"]
        if t1 <= a or t0 >= b or t1 <= t0:
            continue
        lo = max(0.0, (a - t0) / (t1 - t0)) * (n - 1)
        hi = min(1.0, (b - t0) / (t1 - t0)) * (n - 1)
        steps = hi - lo
        mean_ctx = r["prompt_tokens"] + max(0.0, (lo + hi) / 2 - chunk)
        total += steps * mean_ctx * per_tok
    return total


def paged_attention_roofline(ctx, kernel="paged_attention", **_):
    """Least time over measured time of the paged kernel in the traced span.
    Bandwidth-bound: least time is the KV bytes its calls needed over the
    chip's HBM bytes per second."""
    peaks, span = ctx.get("peaks"), ctx.get("trace_span")
    if not peaks or not span or not ctx.get("devices"):
        return None
    from benchmark.readers import trace

    seconds, calls = trace.kernel_seconds(ctx["devices"], kernel)
    if calls == 0 or seconds <= 0:
        return None
    need = decode_kv_bytes(ctx["cfg"], ctx["records"], span[0], span[1],
                           int(ctx.get("engine_chunk") or 16))
    if need <= 0:
        return None
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
