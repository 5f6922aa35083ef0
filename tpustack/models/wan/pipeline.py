"""Wan T2V text→video pipeline, compiled end-to-end for TPU.

Executes the same graph the reference client builds for ComfyUI (reference
``generate_wan_t2v.py:36-103``: CLIPTextEncode ×2 → EmptyHunyuanLatentVideo →
KSampler → VAEDecode) as **one jitted XLA program** per
(batch, frames, steps, height, width, sampler) signature: UMT5 encode of
cond+uncond, CFG flow-matching denoise loop (``lax.fori_loop``), causal 3D VAE
decode, uint8 conversion.  No host round-trips between nodes — the node graph
is a serving-layer concept (``tpustack.serving.graph_server``), not a compute
boundary.

Frame counts follow ComfyUI's floor convention: requesting 16 frames yields
13 (= 1 + 4·⌊15/4⌋) — the reference behaves identically through its VAE.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpustack.models.wan.config import WanConfig
from tpustack.models.wan.dit import WanDiT
from tpustack.models.wan.scheduler import (FlowSchedule, canonical_sampler,
                                           euler_step, heun_step,
                                           make_flow_schedule)
from tpustack.models.wan.tokenizer import load_tokenizer
from tpustack.models.wan.umt5 import UMT5Encoder
from tpustack.models.wan.vae3d import VAE3DDecoder, VAE3DEncoder
from tpustack.models.wan.wanvae import (WanVAEDecoder, WanVAEDecoderStream,
                                        WanVAEEncoder, init_decode_caches)
from tpustack.utils import get_logger

log = get_logger("models.wan.pipeline")


class WanPipeline:
    """Holds module defs + params and a cache of compiled generate programs."""

    def __init__(self, config: Optional[WanConfig] = None,
                 params: Optional[Dict[str, Any]] = None, seed: int = 0):
        self.config = config or WanConfig.wan_1_3b()
        dtype = self.config.compute_dtype
        self.text_encoder = UMT5Encoder(self.config.text, dtype=dtype)
        self.dit = WanDiT(self.config.dit, dtype=dtype)
        if self.config.vae.arch == "wan":  # checkpoint-mapped Wan 2.1 arch
            self.vae_decoder = WanVAEDecoder(self.config.vae, dtype=dtype)
            self.vae_encoder = WanVAEEncoder(self.config.vae, dtype=dtype)
            # streaming twin (same param tree) for long-video decode
            self.vae_decoder_stream = WanVAEDecoderStream(self.config.vae,
                                                          dtype=dtype)
        else:  # "tpu": this package's own design (no checkpoint format)
            self.vae_decoder = VAE3DDecoder(self.config.vae, dtype=dtype)
            self.vae_encoder = VAE3DEncoder(self.config.vae, dtype=dtype)
        self.tokenizer = load_tokenizer(self.config.text.vocab_size,
                                        self.config.text.max_length)
        self.params = params if params is not None else self._random_init(seed)
        # shape signatures this process has already compiled+run — the graph
        # server consults this to decide whether a dispatch will block on a
        # (multi-minute, full-size) XLA build before piling more work behind it
        self._warm_keys = set()

    # ---------------------------------------------------------------- init
    def _random_init(self, seed: int) -> Dict[str, Any]:
        log.warning("Initialising Wan with RANDOM weights (no checkpoint given)")
        c = self.config
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
        ids = jnp.zeros((1, c.text.max_length), jnp.int32)
        text = jax.jit(self.text_encoder.init)(k1, ids)["params"]
        lat = jnp.zeros((1, 1, 4, 4, c.dit.in_channels), jnp.float32)
        ctx = jnp.zeros((1, c.text.max_length, c.dit.text_dim), jnp.float32)
        dit = jax.jit(self.dit.init)(k2, lat, jnp.zeros((1,), jnp.float32), ctx)["params"]
        z = jnp.zeros((1, 1, 4, 4, c.vae.z_channels), jnp.float32)
        vae_d = jax.jit(self.vae_decoder.init)(k3, z)["params"]
        px = jnp.zeros((1, 1, 4 * c.vae.spatial_scale, 4 * c.vae.spatial_scale, 3),
                       jnp.float32)
        vae_e = jax.jit(self.vae_encoder.init)(k4, px)["params"]
        return {"text_encoder": text, "dit": dit, "vae_decoder": vae_d,
                "vae_encoder": vae_e}

    # ------------------------------------------------------------ compiled fn
    def _denoise_body(self, params, ids, mask, noise, num_steps: int,
                      sampler: str, guidance_scale):
        """Traced denoise: text encode + CFG flow-matching loop → latents."""
        c = self.config
        sched: FlowSchedule = make_flow_schedule(num_steps, c.flow_shift)
        context = self.text_encoder.apply({"params": params["text_encoder"]},
                                          ids, mask)

        def velocity(x, t_scalar):
            t = jnp.broadcast_to(t_scalar, (x.shape[0] * 2,))
            v = self.dit.apply(
                {"params": params["dit"]},
                jnp.concatenate([x, x], axis=0).astype(c.compute_dtype),
                t, context)
            v_uncond, v_cond = jnp.split(v.astype(jnp.float32), 2, axis=0)
            return v_uncond + guidance_scale * (v_cond - v_uncond)

        def body(i, x):
            v = velocity(x, sched.timesteps[i])
            if sampler == "heun":
                x_pred = euler_step(i, x, v, sched)
                # endpoint velocity; at the final step σ_next = 0 ⇒ t_next = 0
                t_next = sched.sigmas[i + 1] * 1000.0
                v_next = velocity(x_pred, t_next)
                return heun_step(i, x, v, v_next, sched)
            return euler_step(i, x, v, sched)

        return jax.lax.fori_loop(0, num_steps, body, noise)

    @staticmethod
    def _to_uint8(frames):
        frames = jnp.clip((frames.astype(jnp.float32) + 1.0) * 127.5,
                          0.0, 255.0)
        return jnp.round(frames).astype(jnp.uint8)

    @functools.partial(jax.jit, static_argnums=(0, 5, 6))
    def _generate(self, params, ids, mask, noise, num_steps: int,
                  sampler: str, guidance_scale):
        """``ids``/``mask`` are ``[2B, L]`` — uncond rows then cond rows.
        One fused program: denoise + full-sequence VAE decode (the fast
        path; long videos use ``_generate_latents`` + streaming decode)."""
        c = self.config
        x = self._denoise_body(params, ids, mask, noise, num_steps, sampler,
                               guidance_scale)
        if c.vae.arch == "wan":  # decoder owns de-normalization + conv2
            frames = self.vae_decoder.apply({"params": params["vae_decoder"]}, x)
        else:
            frames = self.vae_decoder.apply(
                {"params": params["vae_decoder"]}, x / c.vae.scaling_factor)
        return self._to_uint8(frames)

    @functools.partial(jax.jit, static_argnums=(0, 5, 6))
    def _generate_latents(self, params, ids, mask, noise, num_steps: int,
                          sampler: str, guidance_scale):
        return self._denoise_body(params, ids, mask, noise, num_steps,
                                  sampler, guidance_scale)

    @functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=(3,))
    def _decode_stream_chunk(self, params, z_chunk, caches, first: bool):
        # caches donated: old and new history must not be live together —
        # the whole point of streaming is bounded decode memory
        frames, caches = self.vae_decoder_stream.apply(
            {"params": params["vae_decoder"]}, z_chunk, caches, first)
        return self._to_uint8(frames), caches

    #: stream the VAE decode (bounded memory) when the BATCH's decoded
    #: pixel-frame volume (B·F·H·W) exceeds this — the full-sequence
    #: decoder's activation maps scale with the whole batch: a 49-frame
    #: 512x320 video (8.0M px-frames) measured 23.9 GB > 16 GB HBM, while
    #: one 16-frame default row (2.1M) comfortably fits fused; two such
    #: rows (4.2M) stream
    STREAM_DECODE_PIXELS = int(os.environ.get("WAN_VAE_STREAM_PIXELS",
                                              str(3_000_000)))
    #: latent frames per streamed decode chunk.  2 is the measured default:
    #: a 49-frame 512x320 decode fits beside the full serving weights at
    #: chunk 2 on a 16 GB v5e; chunk 4's final-stage maps still OOM there
    STREAM_DECODE_CHUNK = int(os.environ.get("WAN_VAE_STREAM_CHUNK", "2"))

    def _use_stream_decode(self, noise_shape, height: int, width: int) -> bool:
        b, f_lat = noise_shape[0], noise_shape[1]
        if self.config.vae.arch != "wan" or f_lat < 2:
            return False
        # the fused decoder's activation maps scale with B*F*H*W, so the
        # threshold compares the WHOLE batch's decoded volume — N rows each
        # just under the solo threshold would otherwise OOM exactly like one
        # oversized row
        px = b * (1 + self.config.vae.temporal_scale * (f_lat - 1)) * height * width
        return px > self.STREAM_DECODE_PIXELS

    def _decode_streaming(self, x):
        """Host loop over latent-frame chunks of the streaming decoder —
        exact (per-conv 2-frame causal history), memory bounded by the
        chunk size.  Chunks dispatch async back-to-back; the concatenated
        uint8 video is returned as a device array like ``_generate``'s."""
        b, t = x.shape[0], x.shape[1]
        chunk = max(2, self.STREAM_DECODE_CHUNK)
        caches = init_decode_caches(self.config.vae, b, x.shape[2], x.shape[3],
                                    dtype=self.config.compute_dtype)
        outs = []
        lo = 0
        while lo < t:
            n = min(chunk, t - lo)
            if lo == 0 and n < 2:
                raise ValueError("streaming decode needs >= 2 latent frames")
            frames, caches = self._decode_stream_chunk(
                self.params, x[:, lo:lo + n], caches, lo == 0)
            outs.append(frames)
            lo += n
        return jnp.concatenate(outs, axis=1)

    # ---------------------------------------------------------------- public
    def generate(
        self,
        prompt: str,
        *,
        negative_prompt: str = "",
        frames: int = 16,
        steps: int = 25,
        guidance_scale: float = 6.0,
        seed: Optional[int] = None,
        width: int = 512,
        height: int = 320,
        sampler: str = "uni_pc",
        batch_size: int = 1,
    ) -> Tuple[np.ndarray, float]:
        """Returns (``[B, F, H, W, 3]`` uint8 frames, wall latency seconds).

        Defaults mirror the reference client (``generate_wan_t2v.py:305-312``):
        512x320, 16 frames, 25 steps, cfg 6.0, sampler uni_pc.
        """
        t0 = time.time()
        vid = self.generate_async(
            prompt, negative_prompt=negative_prompt, frames=frames,
            steps=steps, guidance_scale=guidance_scale, seed=seed,
            width=width, height=height, sampler=sampler,
            batch_size=batch_size)
        return np.asarray(vid), time.time() - t0

    def generate_async(self, prompt: str, *, negative_prompt: str = "",
                       frames: int = 16, steps: int = 25,
                       guidance_scale: float = 6.0,
                       seed: Optional[int] = None, width: int = 512,
                       height: int = 320, sampler: str = "uni_pc",
                       batch_size: int = 1):
        """Dispatch one generation and return the DEVICE array (JAX async
        dispatch) — ``np.asarray`` it to fetch.  The uint8 video transfer
        is host-visible latency, so serving/bench callers keep
        one video in flight and overlap the previous fetch with the next
        video's compute (same pattern as ``SD15Pipeline.generate_async``)."""
        lat_shape = self._lat_shape(frames, height, width)
        ids, mask = self.tokenizer([negative_prompt] * batch_size
                                   + [prompt] * batch_size)
        key = jax.random.PRNGKey(np.random.randint(0, 2**31) if seed is None
                                 else seed % (2**31))
        noise = jax.random.normal(key, (batch_size, *lat_shape), jnp.float32)
        out = self._run(jnp.asarray(ids), jnp.asarray(mask), noise,
                        int(steps), canonical_sampler(sampler),
                        jnp.float32(guidance_scale), height, width)
        self._warm_keys.add((batch_size, lat_shape, int(steps),
                             canonical_sampler(sampler)))
        return out

    def _run(self, ids, mask, noise, steps: int, sampler: str,
             guidance_scale, height: int, width: int):
        """Denoise + decode, choosing fused or streaming decode by the
        decoded pixel-frame volume (``_use_stream_decode``)."""
        if self._use_stream_decode(noise.shape, height, width):
            x = self._generate_latents(self.params, ids, mask, noise, steps,
                                       sampler, guidance_scale)
            return self._decode_streaming(x)
        return self._generate(self.params, ids, mask, noise, steps, sampler,
                              guidance_scale)

    def pixel_frame_count(self, frames: int) -> int:
        """Decoded frame count for a requested frame count (the ComfyUI
        floor convention) — THE definition; servers must not re-derive it."""
        ts = self.config.vae.temporal_scale
        lat_f = max(0, int(frames) - 1) // ts + 1
        return 1 + ts * (lat_f - 1)

    def signature_key(self, *, batch_size: int, frames: int, steps: int,
                      width: int, height: int, sampler: str):
        """The compiled-program signature of one ``_generate`` call."""
        return (batch_size, self._lat_shape(frames, height, width),
                int(steps), canonical_sampler(sampler))

    def is_warm(self, **kw) -> bool:
        return self.signature_key(**kw) in self._warm_keys

    def generate_many_async(self, items, *, frames: int = 16, steps: int = 25,
                            guidance_scale: float = 6.0, width: int = 512,
                            height: int = 320, sampler: str = "uni_pc"):
        """B independent singleton requests (own prompt/negative/seed each)
        fused batch-wide — the graph server's queue-depth>1 batching: CFG
        text encode and the whole denoise loop stream the weights once for
        all B in one device program; the VAE decode joins that program while
        the batch's decoded volume fits ``STREAM_DECODE_PIXELS``, else it
        runs as the chunked streaming decoder (still batched per chunk —
        B·F·H·W activation maps are exactly what the threshold bounds).
        Items sharing a seed+prompt reproduce ``generate_async``'s output
        row-for-row (same per-item noise construction).  Returns the device
        array ``[B, F, H, W, 3]``.

        ``items``: list of ``{"prompt", "negative_prompt", "seed"}``.
        """
        lat_shape = self._lat_shape(frames, height, width)
        ids, mask = self.tokenizer(
            [it.get("negative_prompt", "") for it in items]
            + [it["prompt"] for it in items])
        noise = jnp.concatenate([
            jax.random.normal(
                jax.random.PRNGKey(np.random.randint(0, 2**31)
                                   if it.get("seed") is None
                                   else it["seed"] % (2**31)),
                (1, *lat_shape), jnp.float32)
            for it in items])
        out = self._run(jnp.asarray(ids), jnp.asarray(mask), noise,
                        int(steps), canonical_sampler(sampler),
                        jnp.float32(guidance_scale), height, width)
        self._warm_keys.add((len(items), lat_shape, int(steps),
                             canonical_sampler(sampler)))
        return out

    def _lat_shape(self, frames: int, height: int, width: int):
        """Latent shape for a frame count (ComfyUI floor convention) —
        single source for ``generate`` and ``pipeline_flops``."""
        c = self.config
        ts = c.vae.temporal_scale
        lat_f = max(0, int(frames) - 1) // ts + 1
        return c.latent_shape(1 + (lat_f - 1) * ts, height, width)

    def pipeline_flops(self, *, steps: int = 25, frames: int = 16,
                       width: int = 512, height: int = 320,
                       batch_size: int = 1, sampler: str = "uni_pc") -> float:
        """Model FLOPs of one ``generate`` (MFU accounting): XLA's
        ``cost_analysis`` counts the denoise ``fori_loop`` body once, so sum
        per-component AOT analyses — ``text(2B) + steps × DiT(CFG 2B) +
        VAE decode(B)``.  Second-order samplers (heun — including uni_pc
        etc., which :func:`canonical_sampler` maps onto it, exactly as
        ``generate`` does) run the DiT twice per step."""
        c = self.config
        lat_shape = self._lat_shape(frames, height, width)
        b2 = batch_size * 2  # CFG batches uncond+cond through one DiT eval

        def cost(fn, *args):
            comp = jax.jit(fn).lower(*args).compile()
            ca = comp.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            return float(ca["flops"])

        ids = jnp.zeros((b2, c.text.max_length), jnp.int32)
        mask = jnp.ones((b2, c.text.max_length), jnp.int32)
        lat = jnp.zeros((b2, *lat_shape), c.compute_dtype)
        t = jnp.zeros((b2,), jnp.float32)
        ctx = jnp.zeros((b2, c.text.max_length, c.dit.text_dim),
                        c.compute_dtype)
        z = jnp.zeros((batch_size, *lat_shape), jnp.float32)
        f_text = cost(lambda p, i, m: self.text_encoder.apply(
            {"params": p}, i, m), self.params["text_encoder"], ids, mask)
        f_dit = cost(lambda p, x, t, cx: self.dit.apply(
            {"params": p}, x, t, cx), self.params["dit"], lat, t, ctx)
        f_vae = cost(lambda p, z: self.vae_decoder.apply({"params": p}, z),
                     self.params["vae_decoder"], z)
        per_step = (2 * f_dit if canonical_sampler(sampler) == "heun"
                    else f_dit)
        return f_text + steps * per_step + f_vae

    def warmup(self, **kw) -> float:
        t0 = time.time()
        self.generate("warmup", seed=0, **kw)
        return time.time() - t0
