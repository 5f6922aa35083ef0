"""Process start-up helpers: which device this process computes on, and
where its compiled programs are kept.

Env parsing lives in the typed knob registry (:mod:`tpustack.utils.knobs`,
docs/CONFIG.md).  The two helpers here read JAX's OWN variables instead
(``JAX_PLATFORMS``, ``JAX_COMPILATION_CACHE_DIR``) so the stack adds no
second spelling for something JAX already lets an operator place from
outside.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def require_accelerator() -> str:
    """The platform guard every entry point that computes calls first.

    This stack is written for a TPU: a server, trainer or benchmark that
    quietly came up on JAX's CPU fallback (driver missing, chip held by
    another process, wrong image) would look healthy and measure nothing.
    So the default backend must be ``tpu`` — or ``JAX_PLATFORMS`` must name
    ``cpu`` explicitly (the test tier, the chaos drills, a dev box), in
    which case the process runs there on purpose.  Anything else exits
    non-zero with the reason.  Returns the backend name."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        # JAX_PLATFORMS named a platform that failed to initialise
        raise SystemExit(f"tpustack: no usable JAX backend: {e}")
    named = [p.strip().lower()
             for p in os.environ.get("JAX_PLATFORMS", "").split(",")]
    if backend == "tpu" or "cpu" in named:
        return backend
    raise SystemExit(
        f"tpustack: JAX's default backend is {backend!r} "
        f"({jax.devices()}), not 'tpu'. Refusing to compute on a fallback "
        "device; set JAX_PLATFORMS=cpu to run on the CPU on purpose.")


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the default
    backend — what ``/props``, the benches and ``chip_smoke.py`` print so
    every result names the device it ran on."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache so a restarted pod (or
    the next process of a benchmark run) reuses every compiled program
    instead of paying the cold jit again.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set — the serving manifests
    point it at their PVC-backed volume, a harness at its own directory —
    that directory is the cache and no other is set in code.  Where it is
    not, the cache is ``<repo>/.cache/xla`` (gitignored): a FIXED path,
    because the directory is part of what a cache hit is keyed on.  A
    directory that cannot be created or written raises — a cache that
    silently is not there turns every restart into a cold start."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".cache", "xla")
    os.makedirs(cache, exist_ok=True)
    if not os.access(cache, os.W_OK | os.X_OK):
        raise PermissionError(f"compile cache dir {cache} is not writable")
    if jax.config.jax_compilation_cache_dir != cache:
        # jax read the variable at import; this covers "unset" and a
        # variable exported after jax was imported — same directory
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache
