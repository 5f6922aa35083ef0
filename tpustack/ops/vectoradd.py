"""jax-vectoradd — the TPU analog of the CUDA vectorAdd smoke test.

The reference's canonical "does the accelerator path work" gate is the NVIDIA
``cuda-sample:vectoradd-cuda12.5.0-ubi8`` image run as a k8s Job: 50,000
elements, launched as 196 blocks x 256 threads, and the log must end with
"Test PASSED" (reference ``README.md:264-299``).  On TPU there is no kernel
launch geometry to print — XLA tiles the add onto the VPU — so the TPU gate is:
allocate on device, add under ``jit``, verify on host, print the same final
line so the k8s Job log-gate (``grep 'Test PASSED'``) carries over unchanged.

``cluster-config/jobs/jax-vectoradd.yaml`` runs exactly this module as
``python -m tpustack.ops.vectoradd``.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

# Same element count as the CUDA sample the reference runs (README.md:292-299).
NUM_ELEMENTS = 50_000


@jax.jit
def vector_add(a: jax.Array, b: jax.Array) -> jax.Array:
    return a + b


def vectoradd_selftest(n: int = NUM_ELEMENTS, seed: int = 0) -> bool:
    """Run the smoke test; returns True on PASS.

    Mirrors the CUDA sample's structure: fill two vectors, add on the
    accelerator, verify each element on the host within fp32 tolerance.
    """
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.uniform(k1, (n,), dtype=jnp.float32)
    b = jax.random.uniform(k2, (n,), dtype=jnp.float32)
    out = jax.device_get(vector_add(a, b))
    expect = jax.device_get(a) + jax.device_get(b)
    max_err = float(abs(out - expect).max())
    return max_err < 1e-5


def main() -> int:
    from tpustack.utils import require_accelerator

    # the device-plugin smoke must not pass on a node whose TPU is absent:
    # JAX would fall back to the CPU and the add would still verify
    backend = require_accelerator()
    print(f"[jax-vectoradd] backend={backend} devices={jax.devices()}")
    print(f"[jax-vectoradd] Vector addition of {NUM_ELEMENTS} elements")
    ok = vectoradd_selftest()
    if ok:
        print("Test PASSED")
        return 0
    print("Test FAILED")
    return 1


if __name__ == "__main__":
    sys.exit(main())
