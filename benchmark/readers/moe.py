"""Readers for a model with routed-expert and window layers (K-EXAONE), and
the functions that count its operations and bytes.

From the flight records (``ctx["flight_records"]``) they read what the
engine counts of each dispatch, summed over the sparse layer-calls it made:
``moe_layer_calls``, ``moe_pairs`` (token-expert pairs that landed on held
experts), ``moe_experts_touched`` (held experts with at least one),
``moe_max_expert_tokens`` (the fullest held expert's pairs), on ``wave`` /
``verify`` / ``prefill`` records; and ``ctx_tokens_window`` (each advanced
row's context cut at the attention window) beside ``ctx_tokens`` on the
waves.  A program without these fields (the parent of the PR that added
them) gives every reader here nothing to read: each returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.loadgen import n_tokens as _tokens
from benchmark.readers import trace
from benchmark.readers.phases import WAVES, in_window
from benchmark.weights_exaone import dims

MOE_FIELDS = ("moe_layer_calls", "moe_pairs", "moe_experts_touched",
              "moe_max_expert_tokens")


# ------------------------------------------------ operations and bytes
def expert_elements(m: Dict) -> int:
    """Weight elements of one routed expert (gate, up, down)."""
    return 3 * m["d"] * m["eff"]


def expert_bytes(cfg: Dict) -> float:
    """Bytes ``moe_gmm`` reads of one expert it touches, over its three
    calls: the matrices and, served int8, a f32 scale per output channel."""
    m = dims(cfg)
    if cfg.get("weights") == "int8":
        return expert_elements(m) + 4.0 * (2 * m["eff"] + m["d"])
    return 2.0 * expert_elements(m)


def token_flops_outside_experts(cfg: Dict) -> Dict[str, float]:
    """Matmul flops one token needs outside the routed experts: in the
    layers (attention projections, the dense layer, the shared expert of
    every sparse layer), and in the head (its held rows)."""
    m = dims(cfg)
    attn = m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kvh"] * m["hd"] * 2
    n_sparse = sum(m["sparse"])
    layers = (m["layers"] * attn + (m["layers"] - n_sparse) * 3 * m["d"]
              * m["ffn"] + n_sparse * 3 * m["d"] * m["sff"])
    return {"layers": 2.0 * layers, "head": 2.0 * m["d"] * m["vocab"]}


def attention_flops(cfg: Dict, n: int) -> float:
    """QK^T and PV of a sequence of ``n`` tokens, by layer kind: a full
    layer's token at position ``p`` sees ``p + 1`` keys, a window layer's
    ``min(p + 1, window)``; 4 * head_dim flops per (query, key), per head."""
    m = dims(cfg)
    total = 0.0
    for w in m["windows"]:
        if w is None or n <= w:
            pairs = n * (n + 1) / 2
        else:
            pairs = w * (w + 1) / 2 + (n - w) * w
        total += 4.0 * m["hd"] * m["h"] * pairs
    return total


def kv_layer_bytes_per_token(cfg: Dict) -> float:
    """Bytes of K and V one cached token holds in ONE layer."""
    m = dims(cfg)
    int8 = cfg.get("kv") == "int8"
    return 2.0 * m["kvh"] * (m["hd"] * (1 if int8 else 2) + (4 if int8 else 0))


# ------------------------------------------------------- flight records
def moe_records(ctx, kinds, span="window") -> List[Dict]:
    """Records of ``kinds`` in ``ctx[span]`` that carry the counters."""
    return [r for r in in_window(ctx, kinds, span)
            if all(r.get(f) is not None for f in MOE_FIELDS)
            and r["moe_layer_calls"] > 0]


def experts_touched_share(ctx, **_) -> Optional[float]:
    """Of the held experts, the share a decode step's sparse layer had to
    read: experts touched over held experts x layer-calls."""
    waves = moe_records(ctx, WAVES)
    calls = sum(r["moe_layer_calls"] for r in waves)
    if calls <= 0:
        return None
    held = dims(ctx["cfg"])["held"]
    return 100.0 * sum(r["moe_experts_touched"] for r in waves) / (
        held * calls)


def load_imbalance(ctx, **_) -> Optional[float]:
    """The fullest held expert's pairs a layer-call over the mean touched
    expert's: 1 is even, and what lies above it is time the grouped product
    spends on one expert while the others have none left."""
    waves = moe_records(ctx, WAVES)
    calls = sum(r["moe_layer_calls"] for r in waves)
    touched = sum(r["moe_experts_touched"] for r in waves)
    pairs = sum(r["moe_pairs"] for r in waves)
    if calls <= 0 or touched <= 0 or pairs <= 0:
        return None
    return (sum(r["moe_max_expert_tokens"] for r in waves) / calls) / (
        pairs / touched)


def pairs_per_token_call(ctx) -> Optional[float]:
    """Pairs that landed here per token a sparse layer-call processed, over
    the window's dispatches: a wave's pass runs every slot, an admission
    every padded position of its rows."""
    pairs = processed = 0.0
    for r in moe_records(ctx, WAVES + ("prefill",)):
        if r["kind"] == "prefill":
            rows = (r.get("bucket") or 0) * (r.get("rows") or 0)
        else:
            rows = r.get("slots") or 0
        pairs += r["moe_pairs"]
        processed += rows * r["moe_layer_calls"]
    return pairs / processed if processed > 0 else None


# --------------------------------------------------- the whole step's share
def request_flops(cfg: Dict, per_token_routed: float, n_prompt: int,
                  n_out: int) -> float:
    """Flops one request needs: every token through the layers (its routed
    experts' part from the measured pairs a token), the head once per
    sampled token, attention by layer kind over its true context."""
    el = token_flops_outside_experts(cfg)
    n = n_prompt + max(0, n_out - 1)
    return ((el["layers"] + per_token_routed) * n + el["head"] * n_out
            + attention_flops(cfg, n))


def model_mfu(ctx, **_) -> Optional[float]:
    """The whole window's share of the chip's bf16 peak: flops that the
    tokens delivered inside the window needed — attention by layer kind,
    the dense layer, the shared expert, the held experts' part at the pairs
    a token the engine counted, the head's held rows — over window seconds,
    over the peak.  The accounting of ``arith.model_mfu`` (a prefill where
    its first token arrives, decode steps where their tokens arrive) with
    this family's arithmetic."""
    peaks = ctx.get("peaks")
    density = pairs_per_token_call(ctx)
    if not peaks or density is None:
        return None
    cfg = ctx["cfg"]
    m = dims(cfg)
    routed = 2.0 * expert_elements(m) * density * sum(m["sparse"])
    w0, w1 = ctx["window"]
    total = 0.0
    for r in ctx["records"]:
        n = _tokens(r)
        if r["status"] != 200 or r.get("error") or n < 1:
            continue
        whole = request_flops(cfg, routed, r["prompt_tokens"], n)
        prefill = request_flops(cfg, routed, r["prompt_tokens"], 1)
        if w0 <= r["t_first"] <= w1:
            total += prefill
        if n > 1:
            inside = sum(c for t, c in r["chunks"]
                         if w0 <= t <= w1 and t > r["t_first"])
            total += (whole - prefill) * min(1.0, inside / (n - 1))
    if total <= 0:
        return None
    return 100.0 * total / (w1 - w0) / (peaks["flops_bf16"] * ctx["chips"])


# ---------------------------------------------------------- the kernels
def gmm_roofline(ctx, kernel="moe_gmm", **_) -> Optional[float]:
    """Least time over measured time of the grouped product in the traced
    span.  Each dispatch recorded in the span is charged the larger of the
    bytes of the experts it touched over the chip's HBM bytes per second
    and the flops of its pairs over the bf16 peak: a lower bound of its
    work (activations, padding rows and dead tiles cost time and count for
    nothing), so the share cannot pass 100%."""
    peaks, span = ctx.get("peaks"), ctx.get("trace_span")
    if not peaks or not span or not ctx.get("devices"):
        return None
    seconds, calls = trace.kernel_seconds(ctx["devices"], kernel)
    records = moe_records(ctx, WAVES + ("prefill",), "trace_span")
    if calls == 0 or seconds <= 0 or not records:
        return None
    cfg = ctx["cfg"]
    per_expert, flops_pair = expert_bytes(cfg), 2.0 * expert_elements(
        dims(cfg))
    least = sum(max(r["moe_experts_touched"] * per_expert
                    / peaks["hbm_bytes_per_s"],
                    r["moe_pairs"] * flops_pair / peaks["flops_bf16"])
                for r in records)
    return 100.0 * least / seconds


def paged_kinds_roofline(ctx, kernel="paged_attention", **_
                         ) -> Optional[float]:
    """``phases.paged_ctx_roofline`` by layer kind: a full layer's call
    has to read the K and V of every live row's whole context
    (``ctx_tokens``), a window layer's only the last ``window`` positions
    of it (``ctx_tokens_window``)."""
    peaks, span = ctx.get("peaks"), ctx.get("trace_span")
    if not peaks or not span or not ctx.get("devices"):
        return None
    seconds, calls = trace.kernel_seconds(ctx["devices"], kernel)
    waves = [r for r in in_window(ctx, WAVES, "trace_span")
             if r.get("ctx_tokens") and r.get("weight_passes")
             and r.get("ctx_tokens_window") is not None]
    passes = sum(r["weight_passes"] for r in waves)
    if calls == 0 or seconds <= 0 or passes <= 0:
        return None
    cfg = ctx["cfg"]
    windows = dims(cfg)["windows"]
    n_window = sum(w is not None for w in windows)
    n_full = len(windows) - n_window
    per_pass = sum(r["weight_passes"] * (
        n_full * r["ctx_tokens"] + n_window * r["ctx_tokens_window"])
        for r in waves) / passes * kv_layer_bytes_per_token(cfg)
    need = calls / len(windows) * per_pass
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / seconds
