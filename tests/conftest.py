"""Test harness: force an 8-virtual-device CPU backend BEFORE jax initialises.

Multi-chip TPU hardware is not available in CI; all sharding/mesh tests
run against 8 virtual CPU devices, the same validation path the driver uses
for ``__graft_entry__.dryrun_multichip``.  Kernels run in interpret mode
here; ``tests/test_pallas_lowering.py`` cross-lowers them for TPU as the
pre-check a chip run would otherwise pay for.

Opt-in hardware tier: ``TPUSTACK_TPU_TESTS=1`` keeps the TPU as the default
backend (with CPU available for references) and selects the ``tpu``-marked
tests — bf16-on-MXU numerics, the real (non-interpret) Pallas kernels,
on-chip content parity.  It runs where the chip is, through the chip tool:

    chiprun -- env TPUSTACK_TPU_TESTS=1 python -m pytest tests/ -m tpu -q
"""

import os
import sys

TPU_MODE = os.environ.get("TPUSTACK_TPU_TESTS") == "1"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# the chip is the default backend in the hardware tier; CPU stays registered
# so tests can compute references in-process via jax.default_device
os.environ["JAX_PLATFORMS"] = "tpu,cpu" if TPU_MODE else "cpu"

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file ran; the config
# update wins as long as no backend has been initialised
jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tpustack.utils import enable_compile_cache  # noqa: E402

# Both tiers pay real XLA compiles (tiny-model fixtures, and every
# subprocess drill re-compiles the programs the in-process fixtures just
# built); the persistent cache (JAX_COMPILATION_CACHE_DIR, else
# <repo>/.cache/xla — the same dir the servers use) makes them cross-process
# and cross-run hits.  Recompile signatures count python retraces, so cache
# hits change wall-clock only, never a perf signature.
enable_compile_cache()

import pytest  # noqa: E402

# Runtime sanitizers (tpustack.sanitize): the plugin defaults
# TPUSTACK_SANITIZE=1 + MODE=raise for the whole run — tier-1 IS the
# sanitizer-enabled run, per the acceptance bar of the tpusan PR.  An
# explicit TPUSTACK_SANITIZE=0 in the environment bisects back to the
# uninstrumented suite.
pytest_plugins = ("tpustack.sanitize.pytest_plugin",)


def pytest_configure(config):
    if TPU_MODE:
        # Hardware mode must never run the CPU suite against the real
        # backend (its sharding tests assume 8 virtual devices): an explicit
        # command-line -m narrows WITHIN the tpu tier; anything else —
        # including addopts' default "-m 'not slow'" — becomes plain "tpu".
        import shlex

        def has_m(args):
            return any(a == "-m" or (a.startswith("-m") and
                                     not a.startswith("--"))
                       for a in args)  # incl. the -mEXPR glued form

        # a marker expression is user-provided if it came from the command
        # line OR from PYTEST_ADDOPTS (parsed, not substring-matched — a
        # stray --maxfail must not count, and an explicit "-m 'not slow'"
        # must be honored even though it equals the ini default)
        cli_m = has_m(config.invocation_params.args)
        env_m = has_m(shlex.split(os.environ.get("PYTEST_ADDOPTS", "")))
        user = config.option.markexpr
        config.option.markexpr = (f"({user}) and tpu"
                                  if (cli_m or env_m) and user else "tpu")


def pytest_collection_modifyitems(config, items):
    if not TPU_MODE:
        skip = pytest.mark.skip(
            reason="needs TPUSTACK_TPU_TESTS=1 (opt-in real-hardware tier)")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip)


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8():
    from tpustack.parallel import build_mesh

    return build_mesh((2, 2, 2, 1))
