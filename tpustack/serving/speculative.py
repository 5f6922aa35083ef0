"""Speculative decoding — host-side drafters and config for the engine.

ROADMAP item 3: steady decode is bandwidth-bound (688 tok/s/chip,
BENCH_r05) — every plain decode step streams the full weight + KV working
set to emit one token per slot, so the only way materially faster at low
batch is to amortise that read over several tokens per step.  The
continuous engine does that with a verify step
(``llm_generate._spec_verify_paged``): score the last accepted
token plus up to ``SpecConfig.tokens`` host-proposed draft tokens in ONE
forward pass and keep the longest prefix the model agrees with (greedy:
argmax-identical; sampled: rejection-sampled, distribution-preserving).

This module is the HOST side only — where the draft tokens come from:

- :class:`PromptLookupDrafter` (the default; Saxena 2023 "prompt lookup
  decoding"): match the last n tokens of (prompt + generated history)
  against an earlier occurrence in that same history and propose the
  tokens that followed it.  No second model, no extra HBM — a perfect
  first fit for the chat/shared-prefix and retrieval-heavy traffic the
  radix prefix cache already targets (answers quote their context), and
  for the cycling tails greedy decode settles into.
- :class:`DraftModelDrafter` (optional, ``TPUSTACK_SPEC_DRAFT``): greedy
  k-token proposals from a separate small model.  Rehearsal-grade: it
  re-prefills the full history per proposal rather than keeping per-slot
  draft KV, so it trades drafting cost for simplicity; the verify step is
  identical either way, which is what makes the two paths swappable.

Correctness never depends on the drafter: a bad proposal costs wasted
verify positions, not wrong tokens — the engine's per-slot acceptance EMA
(``SpecConfig.ema_alpha``) throttles drafting down to zero on adversarial
traffic so the engine degrades to plain decode, never below it, and
probes again every ``probe_every`` waves.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from tpustack.utils import get_logger

log = get_logger("serving.speculative")


@dataclasses.dataclass
class SpecConfig:
    """Engine-side speculation knobs (``TPUSTACK_SPEC_*`` env analogs).

    ``tokens``: max draft tokens per verify dispatch (K; the compiled
    verify program scores K+1 positions).  ``ngram_max``/``ngram_min``:
    prompt-lookup match lengths, tried longest-first.  ``ema_alpha``:
    weight of the newest acceptance ratio in each slot's rolling EMA.
    ``probe_every``: waves between 1-token probes once a slot's EMA has
    throttled its drafting to zero.  ``drafter``: any object with
    ``draft(history, k) -> List[int]``; None builds the prompt-lookup
    default."""

    tokens: int = 4
    ngram_max: int = 3
    ngram_min: int = 1
    ema_alpha: float = 0.25
    probe_every: int = 8
    drafter: Optional[object] = None


class PromptLookupDrafter:
    """n-gram prompt lookup: propose the continuation of the most recent
    earlier occurrence of the history's final n-gram.

    Match lengths run ``ngram_max`` down to ``ngram_min`` (a longer match
    is stronger evidence the continuation repeats); within one length the
    winner is the MOST RECENT occurrence that still has ``k`` continuation
    tokens available (recency beats the prompt for cycling generations; a
    match butting against the end of history would only yield a stub
    draft, so full-continuation matches take precedence, falling back to
    whichever match offers the longest stub).  The trivial self-match
    (the suffix matching itself) is excluded, and only continuations with
    at least one token are proposed.  Pure host work on numpy —
    O(n·len(history)) per call, microseconds at serving context
    lengths."""

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1):
        if ngram_min < 1 or ngram_max < ngram_min:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"[{ngram_min}, {ngram_max}]")
        self.ngram_max = ngram_max
        self.ngram_min = ngram_min

    def draft(self, history: Sequence[int], k: int) -> List[int]:
        n_hist = len(history)
        if k <= 0 or n_hist < self.ngram_min + 1:
            return []
        arr = np.asarray(history, dtype=np.int64)
        for n in range(min(self.ngram_max, n_hist - 1),
                       self.ngram_min - 1, -1):
            pat = arr[-n:]
            m = n_hist - n  # candidate starts [0, m): start m IS the suffix
            eq = np.ones(m, dtype=bool)
            for j in range(n):
                eq &= arr[j:j + m] == pat[j]
            idx = np.flatnonzero(eq)
            if idx.size:
                full = idx[idx <= n_hist - n - k]  # k tokens available
                start = int(full[-1]) if full.size else int(idx[0])
                cont = arr[start + n:start + n + k]
                if cont.size:
                    return [int(x) for x in cont]
        return []


class DraftModelDrafter:
    """Greedy k-token proposals from a separate (small) draft generator.

    Rehearsal-grade by design: each call runs the draft model's own
    prefill over the (ctx-clipped) history plus k greedy decode steps —
    no per-slot draft KV is kept, so a proposal costs O(len(history))
    draft-model FLOPs.  That is the right trade while the draft model is
    tiny relative to the target (the verify step amortises the TARGET
    model's bandwidth, which is where the win lives); a chunked draft KV
    cache is the known follow-up if draft cost ever shows up on a
    profile.  The verify program is the same one prompt-lookup uses."""

    def __init__(self, gen, stop_tokens: Sequence[int] = ()):
        self.gen = gen
        self.stop_tokens = tuple(stop_tokens)

    def draft(self, history: Sequence[int], k: int) -> List[int]:
        from tpustack.models.llm_generate import SampleConfig

        if k <= 0 or not history:
            return []
        # clip to the DRAFT model's context (it may be smaller than the
        # target's); proposals from shifted positions are still just
        # proposals — the verify step owns correctness
        ctx = self.gen.cfg.max_seq
        hist = list(history)[-(max(1, ctx - k - 1)):]
        try:
            out, _ = self.gen.generate(
                hist, max_new_tokens=k, sample=SampleConfig(greedy=True),
                stop_tokens=self.stop_tokens)
        except ValueError:
            return []
        return [int(t) for t in out[:k]]
