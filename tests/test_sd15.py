"""SD1.5 family tests on the tiny preset (CPU, fast)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.sd15 import SD15Config, SD15Pipeline
from tpustack.models.sd15.clip import CLIPTextEncoder
from tpustack.models.sd15.scheduler import add_noise, ddim_step, make_schedule
from tpustack.models.sd15.tokenizer import HashTokenizer
from tpustack.models.sd15.unet import UNet2DCondition
from tpustack.models.sd15.vae import VAEDecoder, VAEEncoder


@pytest.fixture(scope="module")
def tiny():
    return SD15Config.tiny()


@pytest.fixture(scope="module")
def pipe(tiny):
    return SD15Pipeline(tiny)


def test_clip_shapes(tiny):
    m = CLIPTextEncoder(tiny.text)
    ids = jnp.zeros((2, tiny.text.max_length), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), ids)["params"]
    out = m.apply({"params": params}, ids)
    assert out.shape == (2, tiny.text.max_length, tiny.text.hidden_size)


@pytest.mark.slow
def test_unet_shapes(tiny):
    m = UNet2DCondition(tiny.unet)
    x = jnp.zeros((1, 8, 8, 4))
    t = jnp.zeros((1,), jnp.int32)
    ctx = jnp.zeros((1, tiny.text.max_length, tiny.unet.cross_attention_dim))
    params = m.init(jax.random.PRNGKey(0), x, t, ctx)["params"]
    out = m.apply({"params": params}, x, t, ctx)
    assert out.shape == x.shape
    assert out.dtype == jnp.float32


@pytest.mark.slow
def test_vae_roundtrip_shapes(tiny):
    dec = VAEDecoder(tiny.vae)
    enc = VAEEncoder(tiny.vae)
    scale = 2 ** (len(tiny.vae.block_out_channels) - 1)
    z = jnp.zeros((1, 8, 8, tiny.vae.latent_channels))
    dp = dec.init(jax.random.PRNGKey(0), z)["params"]
    img = dec.apply({"params": dp}, z)
    assert img.shape == (1, 8 * scale, 8 * scale, 3)
    ep = enc.init(jax.random.PRNGKey(1), img)["params"]
    mean, logvar = enc.apply({"params": ep}, img)
    assert mean.shape == z.shape and logvar.shape == z.shape


def test_scheduler_endpoints():
    s = make_schedule(10)
    assert s.timesteps.shape == (10,)
    assert s.timesteps[0] == 900 and s.timesteps[-1] == 0
    # final step denoises to alpha_prev=1 (x0 estimate)
    assert float(s.alpha_prev[-1]) == 1.0
    # ddim with zero predicted noise just rescales toward x0
    x = jnp.ones((1, 4, 4, 4))
    out = ddim_step(jnp.int32(9), x, jnp.zeros_like(x), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x / jnp.sqrt(s.alpha_t[9])), rtol=1e-5)


def test_add_noise_limits():
    x0 = jnp.ones((1, 2, 2, 1))
    noise = jnp.full((1, 2, 2, 1), 2.0)
    near0 = add_noise(x0, noise, jnp.int32(0))
    near999 = add_noise(x0, noise, jnp.int32(999))
    assert abs(float(near0[0, 0, 0, 0]) - 1.0) < 0.1
    assert abs(float(near999[0, 0, 0, 0]) - 2.0) < 0.3


def test_hash_tokenizer_deterministic():
    tok = HashTokenizer(1000, 16)
    a = tok(["a photo of a panda", "a photo of a panda"])
    assert (a[0] == a[1]).all()
    assert a.shape == (2, 16)
    assert a[0, 0] == tok.bos
    b = tok(["different prompt"])
    assert not (a[0] == b[0]).all()


def test_host_key_data_matches_prngkey():
    """Host-built raw key data must be bit-identical to jax.random.PRNGKey
    (the fused program wraps it with wrap_key_data — any mismatch silently
    changes every seeded image)."""
    from tpustack.models.sd15.pipeline import _host_key_data

    seeds = (0, 1, 42, 2**31 - 1, 2**63 - 1, -1, -2**63)
    for seed in seeds:
        ours = _host_key_data([seed])[0]
        theirs = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
        np.testing.assert_array_equal(ours, theirs, err_msg=f"seed {seed}")

    # the x64 branch too (a deployment may enable it)
    with jax.enable_x64(True):
        for seed in seeds:
            ours = _host_key_data([seed])[0]
            theirs = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
            np.testing.assert_array_equal(ours, theirs,
                                          err_msg=f"x64 seed {seed}")


@pytest.mark.slow
def test_pipeline_generate_dp_mesh(pipe, mesh8):
    """DP generate over the 8-device mesh matches the unsharded program."""
    kw = dict(steps=2, seed=7, width=64, height=64, batch_size=8)
    ref, _ = pipe.generate("mesh test", **kw)
    img, _ = pipe.generate("mesh test", mesh=mesh8, **kw)
    assert img.shape == (8, 64, 64, 3)
    # same fused program partitioned by GSPMD: pixel-identical up to reduction
    # order; uint8 quantisation allows off-by-one
    assert np.abs(img.astype(int) - ref.astype(int)).max() <= 1

    with pytest.raises(ValueError, match="not divisible"):
        pipe.generate("mesh test", mesh=mesh8, steps=2, width=64, height=64,
                      batch_size=3)


def test_pipeline_generate_tiny(pipe):
    img, latency = pipe.generate("a tiny test", steps=2, seed=42, width=64, height=64)
    assert img.shape == (1, 64, 64, 3)
    assert img.dtype == np.uint8
    assert latency > 0
    # seeded determinism
    img2, _ = pipe.generate("a tiny test", steps=2, seed=42, width=64, height=64)
    np.testing.assert_array_equal(img, img2)
    # different seed → different image
    img3, _ = pipe.generate("a tiny test", steps=2, seed=43, width=64, height=64)
    assert (img != img3).any()
    # generate_async is the same program, fetched later (the serving/bench
    # pipelining path): identical bytes
    dev = pipe.generate_async("a tiny test", steps=2, seed=42, width=64,
                              height=64)
    np.testing.assert_array_equal(np.asarray(dev), img)


@pytest.mark.slow
def test_compiled_generate_aot_handle(pipe):
    """The AOT handle compiles the exact generate program and reports
    per-component analyses (pipeline_flops counts the fori_loop body per
    step, unlike raw cost_analysis on the fused program)."""
    compiled = pipe.compiled_generate(steps=2, width=64, height=64,
                                      batch_size=1)
    assert compiled.memory_analysis() is not None
    flops = pipe.pipeline_flops(steps=2, width=64, height=64, batch_size=1)
    assert flops > 0
    # more steps must cost strictly more, by exactly 2 extra UNet evals
    # (the raw fused-program count would be step-invariant); on the tiny
    # config the fixed text+VAE share dominates, so only assert linearity
    f4 = pipe.pipeline_flops(steps=4, width=64, height=64, batch_size=1)
    f6 = pipe.pipeline_flops(steps=6, width=64, height=64, batch_size=1)
    assert f4 > flops
    np.testing.assert_allclose(f6 - f4, f4 - flops, rtol=1e-6)
