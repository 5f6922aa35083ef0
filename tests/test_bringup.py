"""Bring-up contracts (ISSUE 21): nothing passes without the device it
claims, the compile cache can be placed from outside, and ``chip_smoke.py``
fails closed.

All subprocess-level: the properties are about what a fresh interpreter does
with ``JAX_PLATFORMS`` / ``JAX_COMPILATION_CACHE_DIR``, which this process
(already pinned to the CPU by conftest) cannot observe on itself.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(args, env_set=None, env_unset=(), cwd=REPO, timeout=300):
    env = dict(os.environ, **(env_set or {}))
    for k in env_unset:
        env.pop(k, None)
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------- platform guard
def test_vectoradd_refuses_a_fallback_backend():
    """No TPU and JAX_PLATFORMS unset: JAX falls back to the CPU, and the
    device smoke must NOT pass there (it used to print Test PASSED on
    whatever backend it got)."""
    proc = run(["-m", "tpustack.ops.vectoradd"], env_unset=["JAX_PLATFORMS"])
    assert proc.returncode != 0
    assert "Test PASSED" not in proc.stdout
    assert "not 'tpu'" in proc.stderr


def test_vectoradd_runs_on_cpu_when_asked_to():
    proc = run(["-m", "tpustack.ops.vectoradd"],
               env_set={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip().splitlines()[-1] == "Test PASSED"
    assert "backend=cpu " in proc.stdout


def test_guard_reports_a_platform_that_cannot_initialise():
    """JAX_PLATFORMS=tpu on a host without one: JAX raises at backend
    init; the guard turns that into a clean non-zero exit with the
    reason."""
    proc = run(["-c", "from tpustack.utils import require_accelerator; "
                      "require_accelerator(); print('computed')"],
               env_set={"JAX_PLATFORMS": "tpu"})
    assert proc.returncode != 0
    assert "computed" not in proc.stdout
    assert "no usable JAX backend" in proc.stderr


# ---------------------------------------------------------- cache placement
_CACHE_PROBE = ("import jax; from tpustack.utils import enable_compile_cache;"
                " d = enable_compile_cache();"
                " assert d == jax.config.jax_compilation_cache_dir, d;"
                " print(d)")


def test_compile_cache_follows_jax_own_variable(tmp_path):
    """Set: that directory (created if need be) and no other."""
    want = str(tmp_path / "placed" / "xla")
    proc = run(["-c", _CACHE_PROBE],
               env_set={"JAX_PLATFORMS": "cpu",
                        "JAX_COMPILATION_CACHE_DIR": want})
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip().splitlines()[-1] == want
    assert os.path.isdir(want)


def test_compile_cache_defaults_to_fixed_path_in_the_checkout():
    proc = run(["-c", _CACHE_PROBE], env_set={"JAX_PLATFORMS": "cpu"},
               env_unset=["JAX_COMPILATION_CACHE_DIR"])
    assert proc.returncode == 0, proc.stderr[-500:]
    assert proc.stdout.strip().splitlines()[-1] == os.path.join(
        REPO, ".cache", "xla")


def test_compile_cache_raises_when_named_directory_is_unusable(tmp_path):
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    proc = run(["-c", _CACHE_PROBE],
               env_set={"JAX_PLATFORMS": "cpu",
                        "JAX_COMPILATION_CACHE_DIR": str(blocker / "xla")})
    assert proc.returncode != 0
    assert "Error" in proc.stderr and str(blocker) in proc.stderr


# --------------------------------------------------------------- chip_smoke
def _result_lines(stdout: str):
    """(result, observations): the LAST stdout line is the result the
    driver parses — exactly ``ok`` and ``device{platform,kind,count}``,
    nothing else; the line before it carries the observations."""
    lines = stdout.strip().splitlines()
    assert len(lines) == 2, f"stdout must be observations + result: {lines}"
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"}, result
    assert set(result["device"]) == {"platform", "kind", "count"}, result
    assert isinstance(result["device"]["platform"], str)
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    first = json.loads(lines[0])
    assert set(first) == {"observations"}, first
    return result, first["observations"]


def test_chip_smoke_cpu_rehearsal_passes_and_says_cpu():
    proc = run(["chip_smoke.py", "--cpu-rehearsal"],
               env_set={"JAX_PLATFORMS": "cpu"}, timeout=600)
    assert proc.returncode == 0, proc.stderr[-1500:]
    result, res = _result_lines(proc.stdout)
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= 1
    assert res["rehearsal"] is True
    assert res["kernel"] == "paged_flash" and res["waves"] >= 1
    assert res["prefix_cache_hits"] >= 1
    assert {"boot_to_ready", "first_request",
            "repeated_request"} <= set(res["seconds"])
    assert res["cache"]["dir"] == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".cache", "xla"))


def test_chip_smoke_fails_when_the_server_child_fails():
    proc = run(["chip_smoke.py", "--cpu-rehearsal", "--server-env",
                "LLM_QUANT=int7"], env_set={"JAX_PLATFORMS": "cpu"},
               timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""          # no result, no observations
    assert "LLM_QUANT='int7'" in proc.stderr  # the child's log tail


@pytest.mark.parametrize("platforms", ["cpu", None])
def test_chip_smoke_without_an_accelerator_prints_no_result(platforms):
    """The real command on a host with no TPU — whether JAX was pinned to
    the CPU or left to fall back — exits non-zero in phase A and prints
    nothing on stdout."""
    proc = run(["chip_smoke.py"],
               env_set={"JAX_PLATFORMS": platforms} if platforms else None,
               env_unset=() if platforms else ["JAX_PLATFORMS"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "phase A" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path),
               env_unset=["PYTHONPATH"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "phase A" in proc.stderr
