"""SD1.5 text→image pipeline, compiled end-to-end for TPU.

TPU-first equivalent of diffusers' ``StableDiffusionPipeline.__call__`` as the
reference drives it (``cluster-config/apps/sd15-api/configmap.yaml:103-112``,
SURVEY.md §3.3: text encode → N× UNet denoise ← THE hot loop → VAE decode).

Differences from the torch reference, all deliberate:

- The **entire** generate path — CLIP encode, classifier-free-guidance denoise
  loop (``lax.fori_loop``), VAE decode, uint8 conversion — is one ``jit``
  program per (batch, steps, height, width) signature.  No host round-trips
  between steps, no autocast context: compute is bf16 by construction.
- CFG batches cond+uncond into a single UNet call (batch ``2B``) so the MXU
  sees one large matmul stream instead of two small ones.
- Seeding is ``jax.random.PRNGKey`` (reference: ``torch.Generator.manual_seed``,
  configmap.yaml:91-92) — deterministic per (seed, shape).
- Weights default to random init in the zero-egress dev environment; real
  ``runwayml/stable-diffusion-v1-5`` safetensors load through
  ``tpustack.models.sd15.weights.load_sd15_safetensors``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpustack.models.sd15.clip import CLIPTextEncoder
from tpustack.models.sd15.config import SD15Config
from tpustack.models.sd15.scheduler import Schedule, ddim_step, make_schedule
from tpustack.models.sd15.tokenizer import load_tokenizer
from tpustack.models.sd15.unet import UNet2DCondition
from tpustack.models.sd15.vae import VAEDecoder, VAEEncoder
from tpustack.utils import get_logger

log = get_logger("models.sd15.pipeline")


def _host_key_data(seeds) -> np.ndarray:
    """``[B, 2]`` uint32 threefry key data built host-side — bit-identical to
    ``jax.random.PRNGKey(seed)`` but with zero device dispatches (each eager
    PRNGKey/normal call is a host→device dispatch of its own).

    With x64 disabled (the default) PRNGKey truncates the seed to int32, so
    the key is ``[0, seed & 0xFFFFFFFF]``; with x64 on, the high word is the
    upper 32 seed bits (both branches verified bit-exact in tests/test_sd15.py).
    """
    x64 = jax.config.read("jax_enable_x64")
    out = np.empty((len(seeds), 2), np.uint32)
    for i, s in enumerate(seeds):
        if s is None:
            s = np.random.randint(0, 2**31)
        s &= (1 << 64) - 1 if x64 else (1 << 32) - 1  # PRNGKey's truncation
        out[i, 0] = (s >> 32) & 0xFFFFFFFF
        out[i, 1] = s & 0xFFFFFFFF
    return out


class SD15Pipeline:
    """Holds module defs + params and a cache of compiled generate programs."""

    def __init__(self, config: Optional[SD15Config] = None,
                 params: Optional[Dict[str, Any]] = None, seed: int = 0):
        self.config = config or SD15Config.sd15()
        dtype = self.config.compute_dtype
        self.text_encoder = CLIPTextEncoder(self.config.text, dtype=dtype)
        self.unet = UNet2DCondition(self.config.unet, dtype=dtype)
        self.vae_decoder = VAEDecoder(self.config.vae, dtype=dtype)
        self.vae_encoder = VAEEncoder(self.config.vae, dtype=dtype)
        self.tokenizer = load_tokenizer(self.config.text.vocab_size,
                                        self.config.text.max_length)
        self.params = params if params is not None else self._random_init(seed)
        # (mesh, source params, replicated device params) cache for DP generate
        self._mesh_params = None

    # ---------------------------------------------------------------- init
    def _random_init(self, seed: int) -> Dict[str, Any]:
        """Random weights (zero-egress default); architecture/shape-exact."""
        log.warning("Initialising SD1.5 with RANDOM weights (no checkpoint given)")
        c = self.config
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
        ids = jnp.zeros((1, c.text.max_length), jnp.int32)
        text = jax.jit(self.text_encoder.init)(k1, ids)["params"]
        ctx = jnp.zeros((1, c.text.max_length, c.unet.cross_attention_dim), jnp.float32)
        zl = jnp.zeros((1, 8, 8, c.unet.in_channels), jnp.float32)
        unet = jax.jit(self.unet.init)(k2, zl, jnp.zeros((1,), jnp.int32), ctx)["params"]
        zv = jnp.zeros((1, 8, 8, c.vae.latent_channels), jnp.float32)
        vae_d = jax.jit(self.vae_decoder.init)(k3, zv)["params"]
        img = jnp.zeros((1, 8 * c.vae_scale, 8 * c.vae_scale, 3), jnp.float32)
        vae_e = jax.jit(self.vae_encoder.init)(k4, img)["params"]
        return {"text_encoder": text, "unet": unet, "vae_decoder": vae_d,
                "vae_encoder": vae_e}

    # ------------------------------------------------------------ compiled fn
    @functools.partial(jax.jit, static_argnums=(0, 5, 6, 7, 9))
    def _generate(self, params, cond_ids, uncond_ids, keys, num_steps: int,
                  lat_h: int, lat_w: int, guidance_scale, n_data: int = 1):
        """One fused program: RNG → encode → CFG denoise loop → decode → uint8.

        ``keys`` is ``[B, 2]`` uint32 raw PRNG key data, built on the host —
        drawing the initial noise INSIDE the program saves two device
        dispatches per request (PRNGKey + normal): one program per
        request is the whole host-side cost.

        ``n_data``: dp×fsdp ways the batch is sharded under GSPMD — traced
        shapes are global, so the UNet's attention auto-dispatch needs it to
        judge per-chip work (same weights, different compiled schedule).
        """
        c = self.config
        unet = (self.unet if n_data <= 1 else UNet2DCondition(
            dataclasses.replace(c.unet, data_shards=n_data),
            dtype=c.compute_dtype))
        sched: Schedule = make_schedule(num_steps)

        noise = jax.vmap(lambda k: jax.random.normal(
            jax.random.wrap_key_data(k, impl="threefry2x32"),
            (lat_h, lat_w, c.unet.in_channels), jnp.float32))(keys)

        ids = jnp.concatenate([uncond_ids, cond_ids], axis=0)  # [2B, L]
        context = self.text_encoder.apply({"params": params["text_encoder"]}, ids)

        def body(i, x):
            t = jnp.broadcast_to(sched.timesteps[i], (x.shape[0] * 2,))
            eps = unet.apply(
                {"params": params["unet"]},
                jnp.concatenate([x, x], axis=0).astype(c.compute_dtype), t, context)
            eps_uncond, eps_cond = jnp.split(eps.astype(jnp.float32), 2, axis=0)
            eps = eps_uncond + guidance_scale * (eps_cond - eps_uncond)
            return ddim_step(i, x, eps, sched)

        x = noise * sched.init_noise_sigma
        x = jax.lax.fori_loop(0, num_steps, body, x)

        img = self.vae_decoder.apply(
            {"params": params["vae_decoder"]}, x / c.vae.scaling_factor)
        img = jnp.clip((img.astype(jnp.float32) + 1.0) * 127.5, 0.0, 255.0)
        return jnp.round(img).astype(jnp.uint8)

    # ---------------------------------------------------------------- public
    def generate(
        self,
        prompt,
        *,
        steps: int = 30,
        guidance_scale: float = 7.5,
        seed=None,
        width: int = 512,
        height: int = 512,
        negative_prompt="",
        batch_size: int = 1,
        mesh=None,
    ) -> Tuple[np.ndarray, float]:
        """Returns (``[B, H, W, 3]`` uint8 images, wall latency seconds).

        Matches the reference request schema {prompt, steps, guidance_scale,
        seed, width, height} (configmap.yaml:52-58); negative_prompt and
        batch_size are supersets.

        ``prompt``/``negative_prompt``/``seed`` may each be a sequence (one
        per image) — distinct requests batch into ONE fused program (the
        server's micro-batcher relies on this).  A scalar prompt is broadcast
        over ``batch_size``; a scalar seed expands to consecutive per-image
        seeds (seed, seed+1, …) so each image's noise depends only on its own
        seed.  The same (seed, batch shape) is exactly reproducible; across
        DIFFERENT batch shapes the compiled programs may differ in the last
        float bit, so images match only up to ±1 uint8 quantisation.

        ``mesh``: optional ``jax.sharding.Mesh`` — images are data-parallel
        over the ``dp``×``fsdp`` axes (params replicated; SD1.5 fits any
        chip), the TPU equivalent of the reference's "one GPU per pod, k8s
        spreads the Job" scale story (SURVEY.md §2.10) inside ONE program:
        XLA partitions the same fused generate over all chips, no NCCL/no
        per-pod orchestration.  ``batch_size`` must divide by dp*fsdp.
        """
        t0 = time.time()
        img = np.asarray(self.generate_async(
            prompt, steps=steps, guidance_scale=guidance_scale, seed=seed,
            width=width, height=height, negative_prompt=negative_prompt,
            batch_size=batch_size, mesh=mesh))
        return img, time.time() - t0

    def generate_async(
        self,
        prompt,
        *,
        steps: int = 30,
        guidance_scale: float = 7.5,
        seed=None,
        width: int = 512,
        height: int = 512,
        negative_prompt="",
        batch_size: int = 1,
        mesh=None,
    ):
        """``generate`` minus the device→host fetch: dispatches the fused
        program and returns the DEVICE array immediately (JAX async
        dispatch).  The caller overlaps the image transfer (``np.asarray``)
        — and any host work — with the next batch's compute; the serving
        micro-batcher and the bench use this to keep the chip busy
        back-to-back.
        """
        c = self.config
        # latents must survive the UNet's own down/up path cleanly
        factor = c.vae_scale * 2 ** (len(c.unet.block_out_channels) - 1)
        if width % factor or height % factor:
            raise ValueError(f"width/height must be multiples of {factor}")
        prompts = [prompt] * batch_size if isinstance(prompt, str) else list(prompt)
        negs = ([negative_prompt] * len(prompts) if isinstance(negative_prompt, str)
                else list(negative_prompt))
        seeds = seed if isinstance(seed, (list, tuple)) else [seed] * len(prompts)
        if not len(prompts) == len(negs) == len(seeds):
            raise ValueError(
                f"prompt/negative_prompt/seed lengths differ: "
                f"{len(prompts)}/{len(negs)}/{len(seeds)}")
        batch_size = len(prompts)
        cond = np.asarray(self.tokenizer(prompts))
        uncond = np.asarray(self.tokenizer(negs))
        if not isinstance(seed, (list, tuple)) and seed is not None:
            # scalar seed over a batch: consecutive per-image seeds (each
            # image's noise depends only on its own seed, independent of
            # batch position; see docstring for cross-batch-shape caveat)
            seeds = [seed + i for i in range(batch_size)]
        keys = _host_key_data(seeds)  # [B, 2] uint32, no device dispatch
        gen_args = self._prep_generate_args(cond, uncond, keys, steps, width,
                                            height, guidance_scale, mesh)
        return self._generate(*gen_args)

    def _prep_generate_args(self, cond, uncond, keys, steps, width, height,
                            guidance_scale, mesh):
        """The exact ``_generate`` argument tuple — single source for both
        the dispatch path (``generate``) and the AOT path
        (``compiled_generate``), so they can never drift apart."""
        c = self.config
        params, n_data = self.params, 1
        if mesh is not None:
            from tpustack.parallel import data_parallel_size

            n_data = data_parallel_size(mesh) or 1
            params, cond, uncond, keys = self._shard_for_mesh(
                mesh, cond, uncond, keys, n_data)
        return (params, cond, uncond, keys, int(steps),
                height // c.vae_scale, width // c.vae_scale,
                jnp.float32(guidance_scale), n_data)

    def _shard_for_mesh(self, mesh, cond, uncond, keys, n_data: int):
        """Replicate params on ``mesh`` (cached) and shard the batch inputs
        over dp×fsdp; the jitted ``_generate`` then compiles as one
        XLA-partitioned program across all mesh devices."""
        from jax.sharding import NamedSharding, PartitionSpec as PS

        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
        if keys.shape[0] % max(n_data, 1):
            raise ValueError(
                f"batch_size {keys.shape[0]} not divisible by mesh dp*fsdp={n_data}")
        batch_sharding = NamedSharding(mesh, PS(data_axes or None))
        cached = self._mesh_params
        # key on the source params object too: pipe.params may be reassigned
        # (e.g. weights loaded after a warmup) and must not serve stale HBM
        if cached is None or cached[0] is not mesh or cached[1] is not self.params:
            replicated = NamedSharding(mesh, PS())
            self._mesh_params = (mesh, self.params, jax.device_put(
                self.params, jax.tree.map(lambda _: replicated, self.params)))
        params = self._mesh_params[2]
        cond, uncond, keys = (jax.device_put(t, batch_sharding)
                               for t in (cond, uncond, keys))
        return params, cond, uncond, keys

    def warmup(self, **kw) -> float:
        """Compile the generate program for the given signature; returns seconds."""
        t0 = time.time()
        self.generate("warmup", seed=0, **kw)
        return time.time() - t0

    def compiled_generate(self, *, steps: int = 30, width: int = 512,
                          height: int = 512, guidance_scale: float = 7.5,
                          batch_size: int = 1, mesh=None):
        """AOT handle to the same fused program ``generate`` dispatches:
        lower + compile (served from the jit/persistent cache when already
        built) and return the ``jax.stages.Compiled`` — for
        ``memory_analysis()`` or HLO dumps.  NOT for MFU: ``cost_analysis``
        on this program counts the denoise ``fori_loop`` body once (~11x
        under-report at 30 steps) — use :meth:`pipeline_flops` instead.
        """
        c = self.config
        cond = np.zeros((batch_size, c.text.max_length), np.int32)
        uncond = np.zeros_like(cond)
        keys = np.zeros((batch_size, 2), np.uint32)
        gen_args = self._prep_generate_args(cond, uncond, keys, steps, width,
                                            height, guidance_scale, mesh)
        # .lower on the descriptor-bound jit does NOT prepend self — go
        # through the class attribute with self explicit (it's static arg 0)
        return type(self)._generate.lower(self, *gen_args).compile()

    def _component_flops(self, fn, *args) -> float:
        comp = jax.jit(fn).lower(*args).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca["flops"])

    def pipeline_flops(self, *, steps: int = 30, width: int = 512,
                       height: int = 512, batch_size: int = 1) -> float:
        """Model FLOPs of one ``generate`` batch (for MFU accounting).

        XLA's ``cost_analysis`` on the fused program counts the denoise
        ``fori_loop`` body ONCE whatever the trip count (measured: ~11x
        under-report at 30 steps), so sum per-component AOT analyses
        instead: ``steps × UNet(CFG 2B) + text(2B) + VAE decode(B)``.
        The component programs compile once and land in the persistent
        cache like everything else.
        """
        c = self.config
        lh, lw = height // c.vae_scale, width // c.vae_scale
        b2 = batch_size * 2  # CFG: cond+uncond ride one eval
        x = jnp.zeros((b2, lh, lw, c.unet.in_channels), c.compute_dtype)
        t = jnp.zeros((b2,), jnp.int32)
        ctx = jnp.zeros((b2, c.text.max_length, c.unet.cross_attention_dim),
                        jnp.float32)
        ids = jnp.zeros((b2, c.text.max_length), jnp.int32)
        z = jnp.zeros((batch_size, lh, lw, c.unet.in_channels), jnp.float32)
        f_unet = self._component_flops(
            lambda p, x, t, ctx: self.unet.apply({"params": p}, x, t, ctx),
            self.params["unet"], x, t, ctx)
        f_text = self._component_flops(
            lambda p, i: self.text_encoder.apply({"params": p}, i),
            self.params["text_encoder"], ids)
        f_vae = self._component_flops(
            lambda p, z: self.vae_decoder.apply({"params": p}, z),
            self.params["vae_decoder"], z)
        return steps * f_unet + f_text + f_vae
