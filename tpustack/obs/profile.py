"""Shared on-demand device profiling (jax.profiler xplane captures).

The SD server grew a ``POST /profile`` endpoint in round 3 — capture an
XLA/TPU profile around one small generate, return the xplane file list —
and it proved its worth (SURVEY.md §5: the reference stack had "Tracing/
profiling: none").  This module extracts the capture mechanics so every
serving surface (llm, sd, graph) offers the same endpoint instead of
each hand-rolling the mkdtemp/trace/glob dance:

- :func:`capture` — blocking: run a callable under ``jax.profiler.trace``
  into a fresh per-capture subdir, return ``{trace_dir, files,
  gen_time_s}``.  Each capture gets its own ``mkdtemp`` subdir so the
  response lists exactly this run's xplane files, never residue from
  earlier captures (unique even across restarts onto the same volume).
- :func:`parse_int_fields` — the shared "ints or 422" body validation.
- :func:`base_dir` — per-server capture root under
  ``TPUSTACK_PROFILE_DIR`` (the SD server keeps honouring its legacy
  ``SD15_TRACE_DIR`` on top).

The drain/quiesce dance stays server-specific by design: each server
holds whatever lock serialises ITS device work around the capture (sd
blocks its dispatch lock and drains in-flight batches; llm runs the
capture under the generation lock so the continuous engine and the
profiled run cannot interleave; graph refuses while the worker is busy).
View captures with ``tools/xprof_summary.py`` or tensorboard.
"""

from __future__ import annotations

import glob
import os
import tempfile
import time
from typing import Callable, Dict, Mapping, Optional

from tpustack.utils import knobs


def base_dir(server: str, override: Optional[str] = None) -> str:
    """Capture root for one server: ``override`` (a legacy env contract
    like SD15_TRACE_DIR) when set, else ``TPUSTACK_PROFILE_DIR/<server>``."""
    if override:
        return override
    return os.path.join(knobs.get_str("TPUSTACK_PROFILE_DIR"), server)


def parse_int_fields(body: object,
                     defaults: Mapping[str, int]) -> Dict[str, int]:
    """Validate a profile request body: must be a dict (or None), every
    known field an int-coercible scalar.  Raises ValueError with a
    client-readable message — handlers map it to 422."""
    if body is None:
        body = {}
    if not isinstance(body, dict):
        raise ValueError("body must be a JSON object")
    out: Dict[str, int] = {}
    for name, default in defaults.items():
        v = body.get(name)
        if v is None:
            out[name] = default
            continue
        try:
            out[name] = int(v)
        except (TypeError, ValueError):
            raise ValueError(f"bad parameter: {name}={v!r} is not an "
                             "integer") from None
    return out


def capture(base: str, run: Callable[[], object],
            prefix: str = "capture-") -> Dict[str, object]:
    """Run blocking ``run()`` under ``jax.profiler.trace`` into a fresh
    subdir of ``base``; returns the endpoint payload.  Callers invoke
    this from an executor thread.  The SD server holds its
    device-serialising lock around it, so its capture contains only the
    profiled run; the LLM server holds none and sleeps in ``run``, so its
    capture is whatever the engine serves meanwhile."""
    import jax

    os.makedirs(base, exist_ok=True)
    trace_dir = tempfile.mkdtemp(prefix=prefix, dir=base)
    t0 = time.time()
    with jax.profiler.trace(trace_dir):
        run()
    latency = time.time() - t0
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return {"trace_dir": trace_dir, "files": files,
            "gen_time_s": round(latency, 2)}
