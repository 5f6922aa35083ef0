"""Readers of the engine's own timeline, as the flight recorder holds it
(``/debug/flight``, polled by the runner into ``ctx["flight_records"]``):

- a ``prefill`` record has, per admitted row, ``queue_s`` (queued at the
  server -> handed out to the engine) and ``admit_s`` (handed out -> the
  admission's dispatch), beside the group's ``prefill_s`` (that dispatch ->
  first token on the host), ``prompt_lens`` (true lengths) and ``bucket``
  (the padded length every row of the group was computed at);
- a ``wave``/``verify`` record has ``host_s`` (the engine thread's seconds
  by phase since the previous such record, adding up to ``wave_s``),
  ``tokens``, ``weight_passes`` and ``ctx_tokens`` (prompt + generated so
  far, summed over the rows the wave advanced).

A reader of host records takes those whose ``ts`` lies in the window; a
reader of the device trace takes the traced span's, so that device seconds
are held against the work of the same seconds.  Each returns None — never
0 — where the program wrote nothing to read (a program from before these
fields, an empty window).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

from benchmark.loadgen import percentile
from benchmark.readers import arith, trace

#: phases in which the engine thread only waits for the chip
WAITS = ("fetch_wait", "resolve_wait", "verify_wait")
WAVES = ("wave", "verify")


def in_window(ctx, kinds, span="window") -> List[Dict]:
    """Records of ``kinds`` whose ``ts`` lies in ``ctx[span]``."""
    w0, w1 = ctx[span]
    return [r for r in ctx.get("flight_records") or []
            if r.get("kind") in kinds and w0 <= r.get("ts", 0) <= w1]


def row_seconds(ctx, *fields) -> List[float]:
    """Per admitted row of the window's ``prefill`` records, the sum of
    ``fields``: a per-row list's entry, or a scalar of the whole group.  A
    row with any part missing is left out."""
    out = []
    for r in in_window(ctx, ("prefill",)):
        parts = [r.get(f) for f in fields]
        n = max((len(p) for p in parts if isinstance(p, list)), default=0)
        for i in range(n):
            vals = [p[i] if isinstance(p, list) else p for p in parts]
            if all(v is not None for v in vals):
                out.append(float(sum(vals)))
    return out


def rows_quantile_ms(ctx, fields=("queue_s",), q=90, **_) -> Optional[float]:
    """Exact ``q``-th percentile (the arithmetic of the end-to-end tails)
    over the rows admitted in the window, in milliseconds."""
    value = percentile(row_seconds(ctx, *fields), q)
    return None if value is None else value * 1e3


def engine_host_share(ctx, **_) -> Optional[float]:
    """Share of the engine thread's time that is host work and not waiting
    for the chip: every phase of ``host_s`` but the waits, over ``wave_s``.
    A run's first record has no interval (``wave_s`` null) and is left out."""
    waves = [r for r in in_window(ctx, WAVES)
             if r.get("wave_s") and isinstance(r.get("host_s"), dict)]
    total = sum(r["wave_s"] for r in waves)
    if total <= 0:
        return None
    work = sum(v for r in waves for k, v in r["host_s"].items()
               if k not in WAITS)
    return 100.0 * work / total


def tokens_per_weight_pass(ctx, **_) -> Optional[float]:
    """Tokens the decode waves delivered per pass over the weights."""
    waves = in_window(ctx, WAVES)
    passes = sum(r.get("weight_passes") or 0 for r in waves)
    if passes <= 0:
        return None
    return sum(r.get("tokens") or 0 for r in waves) / passes


def prefill_padding_share(ctx, **_) -> Optional[float]:
    """Share of the prefill's token positions that were padding: a group's
    rows are each computed at its ``bucket``, of which a row's prompt, less
    what the prefix cache held of it, is the part that was asked for."""
    asked = computed = 0
    for r in in_window(ctx, ("prefill",)):
        lens, bucket = r.get("prompt_lens"), r.get("bucket")
        if lens and bucket:
            asked += sum(lens) - (r.get("cached_tokens") or 0)
            computed += bucket * len(lens)
    if computed <= 0:
        return None
    return 100.0 * (1.0 - asked / computed)


#: ``%flash_panel.3 = bf16[56,4096,128]{2,1,0:T(8,128)(2,1)} custom-call(``:
#: the kernel's output, heads folded into the batch: [rows * heads, S, head]
KERNEL_OUT = re.compile(r" = \(?\w+\[(\d+),(\d+),(\d+)\]")


def causal_call_flops(event_name: str) -> Optional[float]:
    """Flops the least causal attention of a call's own shape needs, the
    shape read from the device event's name: QK^T and PV, 4 * head_dim per
    (query, key) pair, S(S+1)/2 pairs per folded head.  ``S`` is the
    admission's bucket, so padding counts: the kernel is charged what it
    was given (``prefill_padding_share`` says how much of it was asked
    for).  A call over a longer key range than its queries (a chunk, a
    warm start) does more; this is its floor."""
    m = KERNEL_OUT.search(event_name)
    if not m:
        return None
    bh, s, d = (int(g) for g in m.groups())
    return 4.0 * d * bh * s * (s + 1) / 2


def flash_prefill_roofline(ctx, kernels: Sequence[str] = ("flash_panel",
                                                          "flash_kstream"),
                           **_) -> Optional[float]:
    """Least time over measured time of the prefill attention kernels in the
    traced span.  Compute-bound, and from the device trace alone: every
    call's seconds are held against the flops of that call's own shape, so
    no host record of another span is paired with it."""
    peaks = ctx.get("peaks")
    if not peaks:
        return None
    flops = seconds = 0.0
    for events in (ctx.get("devices") or {}).values():
        for name, _, dur in events:
            if trace.short_name(name).split(".")[0] in kernels:
                need = causal_call_flops(name)
                if need is None:
                    return None  # a name with no shape: nothing to charge
                flops += need
                seconds += dur / 1e9
    if seconds <= 0:
        return None
    return 100.0 * (flops / peaks["flops_bf16"]) / seconds


def paged_ctx_roofline(ctx, kernel="paged_attention", **_) -> Optional[float]:
    """Least time over measured time of the paged decode kernel in the
    traced span.  Bandwidth-bound: a call (one layer of one step) has to
    read the K and V of every live row's context, which the engine counts
    where the rows are (``ctx_tokens``; a row's first token counts a step
    early, under 1%).  The traced span's calls are charged the mean call of
    the waves recorded in the same span, each wave weighted by its passes."""
    peaks, span = ctx.get("peaks"), ctx.get("trace_span")
    if not peaks or not span or not ctx.get("devices"):
        return None
    seconds, calls = trace.kernel_seconds(ctx["devices"], kernel)
    waves = [r for r in in_window(ctx, WAVES, "trace_span")
             if r.get("ctx_tokens") and r.get("weight_passes")]
    passes = sum(r["weight_passes"] for r in waves)
    if calls == 0 or seconds <= 0 or passes <= 0:
        return None
    layers = arith.model_dims(ctx["cfg"])["layers"]
    per_call = (sum(r["weight_passes"] * r["ctx_tokens"] for r in waves)
                / passes * arith.kv_bytes_per_token(ctx["cfg"]) / layers)
    return 100.0 * (calls * per_call / peaks["hbm_bytes_per_s"]) / seconds
