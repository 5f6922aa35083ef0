"""Opt-in REAL-hardware tier: the CPU suite verifies content; these tests
verify the actual chip computes that same content — bf16-on-MXU numerics,
the real compiled (non-interpret) Pallas kernels (panel, k-streaming,
paged decode/verify), and full-precision exactness vs an in-process CPU
reference.

Run (through the chip tool — this sandbox has no accelerator):
    chiprun -- env TPUSTACK_TPU_TESTS=1 python -m pytest tests/ -m tpu -q

``tools/verify_hw.py`` is the superset (train→export→serve parity per
family); this tier is the fast loop over the same hardware properties.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def tpu_backend():
    # asked for the hardware tier and got no hardware: a failure, not a
    # skip — a green run must mean the chip computed something
    backend = jax.default_backend()
    assert backend == "tpu", (
        f"TPUSTACK_TPU_TESTS=1 but JAX's default backend is {backend!r}")
    return backend


def _cpu(f, *args):
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(f(*args))


def test_matmul_bf16_on_mxu_vs_cpu(tpu_backend):
    """bf16 MXU matmul within bf16 rounding of the CPU f32 reference."""
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (256, 512)))
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (512, 128)))
    ref = _cpu(lambda x, y: x @ y, a, b)
    got = np.asarray(jnp.asarray(a, jnp.bfloat16) @ jnp.asarray(b, jnp.bfloat16),
                     np.float32)
    # |error| ~ sqrt(K) * eps_bf16 * |a||b| ; K=512, eps=2^-8
    np.testing.assert_allclose(got, ref, atol=0.5, rtol=0.05)


def test_matmul_f32_highest_precision_exact_vs_cpu(tpu_backend):
    """With highest matmul precision the chip reproduces CPU f32 results to
    f32 rounding — the exactness anchor for the content proofs."""
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (128, 256)))
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (256, 64)))
    ref = _cpu(lambda x, y: x @ y, a, b)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jnp.asarray(a) @ jnp.asarray(b))
    np.testing.assert_allclose(got, ref, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_real_compile_matches_xla_on_chip(tpu_backend, causal):
    """The REAL compiled Pallas kernel (interpret=False on a tpu backend,
    tpustack/ops/pallas/flash_attention.py:207-208) vs XLA on the same chip;
    the CPU suite only ever runs this kernel in interpret mode."""
    from tpustack.ops.attention import dot_product_attention

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 256, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 32), jnp.float32)
    got = dot_product_attention(q, k, v, causal=causal, impl="flash")
    ref = dot_product_attention(q, k, v, causal=causal, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-2)


def test_flash_kernel_gqa_streaming_on_chip(tpu_backend):
    """GQA + k-streaming branch (online-softmax carry) on real hardware."""
    import tpustack.ops.pallas.flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                             panel_max_kv=256)  # 512 > 256 → streaming
    from tpustack.ops.attention import dot_product_attention

    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-2)


@pytest.mark.parametrize("name,s,int8", [
    ("decode_bf16", 1, False), ("decode_int8", 1, True),
    ("verify_k4_bf16", 5, False), ("verify_k4_int8", 5, True)])
def test_paged_kernel_real_compile_matches_xla_on_chip(tpu_backend, name, s,
                                                       int8):
    """The in-place paged decode kernel, compiled by Mosaic at the
    Qwen2.5-7B serving head shape (28/4 heads, d 128, 64-token blocks;
    S=1 decode and the S=5 k=4 verify, bf16 and int8 pool), vs the gather
    path's XLA math on the same chip (reserved block 0 is poisoned: it
    must never leak).  The CPU suite runs this kernel in interpret mode only."""
    from tools.verify_hw import THRESH, paged_outputs, paged_vectors

    vec = paged_vectors(s, int8, seed=300 + s + int8)
    got = paged_outputs(vec, jnp.bfloat16, kernel=True)
    ref = paged_outputs(vec, jnp.bfloat16, kernel=False)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref,
                               atol=THRESH["flash_vs_xla_on_chip_atol"])


def test_paged_kernel_tiny_preset_compiles_on_chip(tpu_backend):
    """``LLM_PRESET=tiny`` on a chip resolves to the same kernel: f32 pool,
    8-token blocks, head_dim 16 — heads sit at unaligned lane offsets."""
    from tools.verify_hw import THRESH, paged_outputs, paged_vectors

    tiny = dict(b=2, h=4, hkv=2, d=16, blk=8, nb=16, n_pool=33)
    vec = paged_vectors(5, False, seed=7, shape=tiny, lens=(61, 11))
    np.testing.assert_allclose(
        paged_outputs(vec, jnp.float32, kernel=True),
        paged_outputs(vec, jnp.float32, kernel=False),
        atol=THRESH["flash_vs_xla_on_chip_atol"])


@pytest.mark.parametrize("kvh,blk,kv", [
    (1, 8, "int8"), (4, 32, "int8"), (8, 32, "float")])
def test_pool_writers_spell_the_dense_cache_on_chip(tpu_backend, kvh, blk,
                                                    kv):
    """The paged pool's page writes (an admission's splice, a decode
    chunk's flush) then its gather, bit for bit against the dense cache on
    the chip's own gather and scatter — ``tests/test_pool_layout.py``'s
    cases, which the CPU suite runs on the CPU only."""
    import test_pool_layout as layout

    layout.test_admission_splice_spells_the_dense_cache(kvh, blk, kv)
    layout.test_chunk_flush_matches_the_dense_flush(kvh, blk, kv)


def test_sd15_tiny_unet_step_full_precision_vs_cpu(tpu_backend):
    """One UNet CFG forward at full precision: chip vs CPU within f32
    rounding — the per-op version of verify_hw's whole-pipeline proof."""
    from tpustack.models.sd15 import SD15Config
    from tpustack.models.sd15.unet import UNet2DCondition

    cfg = SD15Config.tiny()
    unet = UNet2DCondition(cfg.unet, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 8, cfg.unet.in_channels))
    t = jnp.array([3, 7], jnp.int32)
    ctx = jax.random.normal(
        jax.random.PRNGKey(7),
        (2, cfg.text.max_length, cfg.unet.cross_attention_dim))
    with jax.default_device(jax.devices("cpu")[0]):
        params = unet.init(jax.random.PRNGKey(8), x, t, ctx)["params"]
        ref = np.asarray(unet.apply({"params": params}, x, t, ctx))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(unet.apply({"params": jax.device_put(params)},
                                    jax.device_put(x), jax.device_put(t),
                                    jax.device_put(ctx)))
    np.testing.assert_allclose(got, ref, atol=2e-4)
