"""Llama-2 / Qwen2-family decoder LM in Flax, TPU-first.

The reference serves Qwen2.5-7B-Instruct as a Q4_K_M GGUF through llama.cpp's
CUDA server with CPU offload (``--n-gpu-layers 35``, reference
``cluster-config/apps/llm/deployment.yaml:61-84``).  The TPU equivalent keeps
everything on-chip in bf16 — a v5e has 16 GB HBM, so a 7B model fits without
quantisation or layer offload — and is designed around XLA:

- Prefill is one big batched matmul pass (MXU-bound); decode is a
  static-shape single-token step with an in-place KV cache
  (``lax.dynamic_update_slice``), so both trace once.
- GQA (n_kv_heads < n_heads), RoPE, RMSNorm, SwiGLU — covering Llama-2
  (BASELINE config #5) and Qwen2.5 (the reference's served model; qkv bias,
  rope_theta=1e6) with one implementation.
- No data-dependent shapes: the cache is ``max_seq`` long; masking handles the
  valid prefix.  Sharding is applied externally via
  ``tpustack.parallel.sharding`` partition rules (megatron TP + FSDP).
- ``quant="int8"`` swaps every projection for weight-only int8
  (``tpustack.ops.quant``) — the TPU answer to the reference's Q4_K_M GGUF:
  decode streams half the weight bytes per token, so the HBM-bound decode
  nearly doubles.  Serving-only; training always runs bf16.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from tpustack.ops.attention import dot_product_attention


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer's kind.  ``window``: None attends the whole causal
    prefix, ``w`` only keys ``j`` with ``0 <= i - j < w``.  ``rope``: whether
    q/k carry the rotary position (some families leave their full layers
    without one).  ``ffn``: ``"dense"`` (SwiGLU of ``ffn_dim``) or
    ``"experts"`` (the routed-expert layer of ``LlamaConfig.moe``)."""
    window: Optional[int] = None
    rope: bool = True
    ffn: str = "dense"


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """The routed-expert layer (``tpustack.models.moe``): a router over all
    ``n_experts``, ``top_k`` a token, SwiGLU experts of ``expert_dim``, a
    shared expert of ``shared_dim`` every token passes.  ``held`` is
    ``(first, count)``: the experts this chip holds under expert
    parallelism — the layer routes over all of them and computes its own
    experts' part of the result."""
    n_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 1.0
    held: Tuple[int, int] = (0, 0)   # (0, 0): all of them

    @property
    def held_range(self) -> Tuple[int, int]:
        first, count = self.held
        return (first, count) if count else (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq: int = 4096          # reference parity: llama.cpp --ctx-size 4096
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    qkv_bias: bool = False       # True for Qwen2
    tie_embeddings: bool = False
    quant: Optional[str] = None  # None (bf16) | "int8" weight-only serving
    kv_quant: Optional[str] = None  # None (bf16 cache) | "int8": per-vector-
    # scaled int8 KV cache — halves decode KV traffic and cache HBM (the
    # dominant bytes term at long context: 1.9 GB/step at 32k on Qwen-7B)
    head_size: Optional[int] = None  # None: dim // n_heads
    # the model's spec beyond its widths: each layer's kind (None: n_layers
    # x full attention with rope, dense SwiGLU), where the norms sit
    # ("pre": x + f(norm(x)); "post": x + norm(f(x))), and whether q and k
    # are RMS-normed per head before the rotary embedding
    layers: Optional[Tuple[LayerSpec, ...]] = None
    norm_placement: str = "pre"
    qk_norm: bool = False
    moe: Optional[MoESpec] = None

    def __post_init__(self):
        if self.layers is not None and len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layer specs for "
                             f"n_layers={self.n_layers}")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement {self.norm_placement!r}")
        if self.moe is None and any(
                sp.ffn == "experts" for sp in self.layers or ()):
            raise ValueError("an 'experts' layer needs LlamaConfig.moe")

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.n_heads

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        return self.layers or (LayerSpec(),) * self.n_layers

    @classmethod
    def llama2_7b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def qwen25_7b(cls) -> "LlamaConfig":
        """Qwen2.5-7B-Instruct — the model the reference's llm app serves."""
        return cls(vocab_size=152064, dim=3584, n_layers=28, n_heads=28,
                   n_kv_heads=4, ffn_dim=18944, rope_theta=1_000_000.0,
                   qkv_bias=True, rms_eps=1e-6)

    @classmethod
    def llama2_70b(cls) -> "LlamaConfig":
        """Llama-2-70B (GQA 64/8): the shard-at-load TP-serving target —
        too big for one chip's HBM even at int8, sized for tp=8 on v5e-8
        (HBM math rehearsed in tests/test_llm_tp.py)."""
        return cls(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                   ffn_dim=28672)

    @classmethod
    def k_exaone_236b_ep8(cls, share: int = 0) -> "LlamaConfig":
        """K-EXAONE-236B-A23B (LGAI-EXAONE, ``exaone_moe``), one chip's
        share of an 8-chip deployment, layers 0-7 (two whole LLLG periods):
        routed experts 16 of 128 a chip (``share`` picks which), an eighth
        of the 153,600-row vocabulary, attention and the shared expert
        whole.  Window layers carry rope, full layers none; q/k RMS-normed
        per head; norms after each sublayer (the EXAONE 4.0 conventions).
        The multi-token-prediction layer is left out."""
        kinds = [LayerSpec(window=128), LayerSpec(window=128),
                 LayerSpec(window=128), LayerSpec(rope=False)] * 2
        layers = tuple(dataclasses.replace(k, ffn="experts" if i else "dense")
                       for i, k in enumerate(kinds))
        return cls(vocab_size=19200, dim=6144, n_layers=8, n_heads=64,
                   n_kv_heads=8, head_size=128, ffn_dim=18432,
                   rope_theta=1_000_000.0, rms_eps=1e-5, layers=layers,
                   norm_placement="post", qk_norm=True,
                   moe=MoESpec(n_experts=128, top_k=8, expert_dim=2048,
                               shared_dim=2048, routed_scale=2.5,
                               held=(16 * share, 16)))

    @classmethod
    def tiny_moe(cls, max_seq: int = 128, share: int = 0) -> "LlamaConfig":
        """Every layer kind at test size: window and full attention, rope
        and none, one dense then routed-expert layers (8 experts, 4 held),
        q/k norm, norms after the sublayers, head_dim != dim / n_heads."""
        w = LayerSpec(window=8, ffn="experts")
        layers = (LayerSpec(window=8), w, w,
                  LayerSpec(rope=False, ffn="experts"))
        return cls(vocab_size=512, dim=64, n_layers=4, n_heads=4,
                   n_kv_heads=2, head_size=32, ffn_dim=128, max_seq=max_seq,
                   layers=layers, norm_placement="post", qk_norm=True,
                   moe=MoESpec(n_experts=8, top_k=2, expert_dim=32,
                               shared_dim=32, routed_scale=2.5,
                               held=(4 * share, 4)))

    @classmethod
    def tiny(cls, max_seq: int = 128) -> "LlamaConfig":
        # vocab 512 ≥ 259 so the byte-level fallback tokenizer fits
        return cls(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                   ffn_dim=128, max_seq=max_seq)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        with jax.named_scope("norm"):
            xf = x.astype(jnp.float32)
            xf = xf * jax.lax.rsqrt(
                jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
            return (xf * scale).astype(self.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over ``[B, S, H, D]`` with ``positions [B, S]``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


KVCache = Dict[str, jax.Array]

#: the cache keys of a ride's line (``LlamaAttention._attend_ride``): the
#: riding row's dense line beside a decode step's buffers
RIDE_KEYS = ("rk", "rv", "rk_scale", "rv_scale")


def _per_head_shard(fn, mesh, cfg: LlamaConfig, n_scalars: int = 0):
    """``fn(q, k, v, *scalars) -> out`` (BSHD) made safe to trace under the
    serving tp mesh.  A Mosaic kernel cannot be GSPMD-partitioned ("wrap
    the call in a shard_map" — a tp=4 server on four v5e chips died on its
    first 1k-token prefill, PR 21), and attention is independent per head:
    so under a tp mesh ``fn`` runs per head shard, each chip seeing its own
    q heads and the kv heads they read (both counts must divide tp — they
    do wherever the KV substrate is head-sharded).  ``fn`` then judges
    ``impl="auto"`` on the per-chip shapes.  Returns ``(callable, ok)``:
    ``ok`` False means tp does not divide the heads and ``fn`` came back
    unwrapped — the caller must keep Pallas kernels out of it."""
    tp = (int(mesh.shape["tp"])
          if mesh is not None and "tp" in mesh.axis_names else 1)
    if tp == 1:
        return fn, True
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        return fn, False
    heads = PS(None, None, "tp", None)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(heads,) * 3 + (PS(),) * n_scalars,
                         out_specs=heads, check_vma=False), True


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    ring_mesh: Any = None  # Mesh → train-path attention rings K/V over "sp"
    tp_mesh: Any = None    # Mesh → serving kernels run per tp head shard
    spec: LayerSpec = LayerSpec()  # this layer's kind: window, rope

    def _ring_shapes_ok(self, b: int, s: int) -> bool:
        """Ring shard_map needs batch/seq/heads divisible by their mesh axes
        (init's tiny dummy input, for one, is not) — else dense fallback,
        which computes the same thing with generic GSPMD collectives."""
        m = self.ring_mesh
        n_data = int(np.prod([m.shape[a] for a in ("dp", "fsdp")
                              if a in m.axis_names]) or 1)
        tp = m.shape.get("tp", 1) if "tp" in m.axis_names else 1
        return (s % m.shape["sp"] == 0 and b % n_data == 0
                and self.cfg.n_heads % tp == 0)

    @nn.compact
    def __call__(self, x, positions, kv_cache: Optional[KVCache], cache_index,
                 attn_mask) -> Tuple[jax.Array, Optional[KVCache]]:
        from tpustack.ops.quant import make_dense

        c = self.cfg
        hd = c.head_dim
        # window layers see keys j with 0 <= i - j < window (i the query's
        # position); every branch below masks the same band, and the
        # kernels skip the blocks that lie wholly behind it
        window = self.spec.window
        dense = lambda feats, name, bias: make_dense(
            c.quant, feats, use_bias=bias, dtype=self.dtype, name=name)
        b, s, _ = x.shape
        # the jax.named_scope names below (attn_qkv, kv_write, kv_read,
        # attn_core, attn_out; norm, mlp, embed, lm_head in the modules
        # further down; sample in llm_generate) are what a device trace's
        # operations are summed by: a promise, like a kernel's name
        with jax.named_scope("attn_qkv"):
            q = dense(c.n_heads * hd, "q_proj", c.qkv_bias)(x).reshape(
                b, s, c.n_heads, hd)
            k = dense(c.n_kv_heads * hd, "k_proj", c.qkv_bias)(x).reshape(
                b, s, c.n_kv_heads, hd)
            v = dense(c.n_kv_heads * hd, "v_proj", c.qkv_bias)(x).reshape(
                b, s, c.n_kv_heads, hd)
            if c.qk_norm:
                q = RMSNorm(c.rms_eps, self.dtype, name="q_norm")(q)
                k = RMSNorm(c.rms_eps, self.dtype, name="k_norm")(k)
            if self.spec.rope:
                q = rope(q, positions, c.rope_theta)
                k = rope(k, positions, c.rope_theta)

        if kv_cache is not None and "ck" in kv_cache:
            if RIDE_KEYS[0] in kv_cache:
                out, new_cache = self._attend_ride(q, k, v, positions,
                                                   kv_cache, cache_index)
            else:
                out, new_cache = self._attend_chunk(q, k, v, positions,
                                                    kv_cache, *cache_index)
            with jax.named_scope("attn_out"):
                return dense(c.dim, "o_proj", False)(out), new_cache
        if kv_cache is not None:
            out, new_cache = self._attend_line(q, k, v, positions, kv_cache,
                                               cache_index, attn_mask)
        elif (self.ring_mesh is not None and attn_mask is None
                and "sp" in self.ring_mesh.axis_names
                and self.ring_mesh.shape["sp"] > 1
                and not self.is_initializing()
                and self._ring_shapes_ok(b, s)):
            # Sequence-parallel training: the sequence dim is GSPMD-sharded
            # over "sp"; ring attention keeps each chip's scores at
            # (S/sp)², rotating K/V shards over nearest-neighbor ICI with a
            # streaming-softmax merge (differentiable — lax.scan + ppermute)
            from tpustack.parallel.ring_attention import ring_attention

            if window is not None:
                raise NotImplementedError(
                    "ring attention has no window: train window layers "
                    "without an sp axis")
            new_cache = None
            with jax.named_scope("attn_core"):
                if c.n_kv_heads != c.n_heads:  # ring expects matched heads
                    rep = c.n_heads // c.n_kv_heads
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                out = ring_attention(q, k, v, mesh=self.ring_mesh, axis="sp",
                                     causal=True)
        else:
            new_cache = None
            # Deliberately impl="xla": this no-cache path is also the training
            # path, and the Pallas flash kernel has no VJP (ring attention
            # above covers sp-sharded training).  Serving prefill goes through
            # the masked KV-cache branch, so flash cannot apply there either
            # (kernel supports causal, not arbitrary masks).
            with jax.named_scope("attn_core"):
                out = dot_product_attention(q, k, v, causal=True,
                                            mask=attn_mask, window=window)
        out = out.reshape(b, s, c.n_heads * hd)
        with jax.named_scope("attn_out"):
            return dense(c.dim, "o_proj", False)(out), new_cache

    def _attend_ride(self, q, k, v, positions, kv_cache, cache_index):
        """A decode step that carries a segment of one row's prompt (the
        engine's ride, ``llm_generate._ride_scan_paged``): rows ``[0, B)``
        of ``q``/``k``/``v`` (``[B + S, 1, ...]``, out of one projection)
        are the B decode tokens and take the chunk-mode path; rows ``[B,
        B + S)`` are the segment's S tokens, a ``[1, S]`` run at positions
        ``roff + i`` that is written into the riding row's own cache line
        (``RIDE_KEYS``, a dense line as ``init_kv_caches`` lays one) at
        ``roff`` and attends it causally — in the line's own type, an int8
        line as quantised, as a warm start does.  ``cache_index`` is
        ``(cur0, t, roff)``.  Returns the ``[B + S, 1, heads · hd]``
        attention output and the new buffers and line."""
        cur0, t, roff = cache_index
        nb = cur0.shape[0]
        seg = lambda a: jnp.swapaxes(a[nb:], 0, 1)     # [S, 1, ..] → [1, S, ..]
        line = {key[1:]: kv_cache[key] for key in RIDE_KEYS if key in kv_cache}
        pos = seg(positions)
        mask = (jnp.arange(line["k"].shape[1])[None, None, None, :]
                <= pos[:, None, :, None])
        out_s, line = self._attend_line(seg(q), seg(k), seg(v), pos, line,
                                        roff, mask)
        out_d, new_cache = self._attend_chunk(
            q[:nb], k[:nb], v[:nb], positions[:nb],
            {key: val for key, val in kv_cache.items()
             if key not in RIDE_KEYS}, cur0, t)
        out = jnp.concatenate([out_d, jnp.swapaxes(
            out_s.reshape(1, -1, out_d.shape[-1]), 0, 1)], axis=0)
        new_cache.update({"r" + key: val for key, val in line.items()})
        return out, new_cache

    def _attend_chunk(self, q, k, v, positions, kv_cache, cur0, t):
        """Chunk-mode attention (the continuous decode step and the
        speculative verify segment): returns the ``[b, s, heads · hd]``
        output and the cache with this step's K/V in its buffers."""
        c = self.cfg
        hd = c.head_dim
        window = self.spec.window
        b, s = q.shape[:2]
        # CONTINUOUS-slot decode chunk (s == 1): every slot sits at its
        # OWN contiguous position cur0[i] + t.  The main cache is FROZEN
        # for the whole chunk — this step's K/V go into the small
        # chunk-local buffer ck/cv at the UNIFORM index t (a cheap
        # dynamic_update_slice), and attention is the exact streaming-
        # softmax merge of {main cache [0, cur0[i])} ∪ {chunk buffer
        # [0, t]}.  The engine flushes the buffer into the cache once
        # per chunk (per-row offsets).  This replaces the per-step
        # one-hot write-back (a full cache read+write pass per step:
        # fine at 4k, ~2x KV traffic for concurrent 32k decodes) with
        # one flush pass per chunk — write-back amortises by the chunk
        # length.  lax.scatter remains off the table (serialises on
        # TPU; 7x decode slowdown, measured).
        #
        # s > 1 is the SPECULATIVE VERIFY segment (llm_generate
        # ._spec_verify_*): s draft+carry tokens land at buffer indices
        # [t, t+s) in ONE weight pass, and query row j attends the same
        # {main cache [0, cur0[i])} set plus buffer [0, t+j] — the
        # in-segment causal generalisation of the single-token mask,
        # which it collapses to exactly at s == 1.
        #
        # The frozen main-cache view is either a dense per-slot line
        # (k/v keys) or the paged-flash IN-PLACE pool view (pk/pv, an
        # int8 pool's scale rows pk_rows/pv_rows, + block table bt,
        # TPUSTACK_PAGED_FLASH): same key set, same
        # masking semantics, different storage — see the partial
        # branch below
        paged_flash = "pk" in kv_cache
        quantized = "k_scale" in kv_cache or "pk_rows" in kv_cache
        cbuf_len = kv_cache["ck"].shape[1]
        with jax.named_scope("kv_write"):
            if quantized:
                # quantise at write — the buffer holds the SAME int8
                # values the main cache will, so flushing is a copy, not
                # a requant
                k_q, k_s = _quantize_kv(k)
                v_q, v_s = _quantize_kv(v)
                new_cache = dict(
                    kv_cache,
                    ck=jax.lax.dynamic_update_slice(
                        kv_cache["ck"], k_q, (0, t, 0, 0)),
                    cv=jax.lax.dynamic_update_slice(
                        kv_cache["cv"], v_q, (0, t, 0, 0)),
                    ck_scale=jax.lax.dynamic_update_slice(
                        kv_cache["ck_scale"], k_s, (0, t, 0)),
                    cv_scale=jax.lax.dynamic_update_slice(
                        kv_cache["cv_scale"], v_s, (0, t, 0)))
            else:
                new_cache = dict(
                    kv_cache,
                    ck=jax.lax.dynamic_update_slice(
                        kv_cache["ck"], k.astype(kv_cache["ck"].dtype),
                        (0, t, 0, 0)),
                    cv=jax.lax.dynamic_update_slice(
                        kv_cache["cv"], v.astype(kv_cache["cv"].dtype),
                        (0, t, 0, 0)))
        from tpustack.ops.attention import (dot_product_attention_partial,
                                            merge_attention_partials)

        with jax.named_scope("attn_core"):
            if s == 1:
                buf_mask = jnp.broadcast_to(
                    jnp.arange(cbuf_len)[None, None, :] <= t,
                    (b, 1, cbuf_len))
            else:
                # verify segment: per-query in-segment causal (see above)
                buf_mask = jnp.broadcast_to(
                    jnp.arange(cbuf_len)[None, None, :]
                    <= (t + jnp.arange(s))[None, :, None],
                    (b, s, cbuf_len))
            if window is not None:
                # buffer index u holds position cur0 + u
                buf_mask = buf_mask & (
                    (cur0[:, None] + jnp.arange(cbuf_len)[None, :])
                    [:, None, :] > positions[:, :, None] - window)
            if paged_flash:
                # read the KV pool blocks IN PLACE through the slot block
                # tables (scalar-prefetch Pallas kernel, per-row `cur0`
                # masking + int8 dequant in-kernel) — no dense
                # [B, max_seq] gather copy; every query row of a multi-
                # query verify attends the same [0, cur0) pool prefix, so
                # ONE kernel pass covers the whole segment and the in-
                # segment causal half stays in the buffer partial below
                from tpustack.ops.pallas.flash_attention import (
                    paged_attention_partial)

                part_main = paged_attention_partial(
                    q, kv_cache["pk"], kv_cache["pv"], kv_cache["bt"],
                    cur0, scale_rows=(
                        (kv_cache["pk_rows"], kv_cache["pv_rows"])
                        if quantized else None),
                    **({} if window is None else {
                        "window": window, "q_pos": positions[:, 0]}))
            else:
                main_pos = jnp.arange(kv_cache["k"].shape[1])
                main_mask = (main_pos[None, None, :]
                             < cur0[:, None, None])      # [B, 1, S]
                if window is not None:
                    main_mask = main_mask & (
                        main_pos[None, None, :]
                        > positions[:, :, None] - window)
                part_main = dot_product_attention_partial(
                    q, kv_cache["k"], kv_cache["v"], mask=main_mask,
                    k_scale=kv_cache.get("k_scale"),
                    v_scale=kv_cache.get("v_scale"))
            part_buf = dot_product_attention_partial(
                q, new_cache["ck"], new_cache["cv"], mask=buf_mask,
                k_scale=new_cache.get("ck_scale"),
                v_scale=new_cache.get("cv_scale"))
            out = merge_attention_partials(part_main, part_buf, self.dtype)
            out = out.reshape(b, s, c.n_heads * hd)
        return out, new_cache

    def _attend_line(self, q, k, v, positions, kv_cache, cache_index,
                     attn_mask):
        """Attention over a dense cache line (``init_kv_caches``' layout),
        this call's K/V written into it at ``cache_index`` first: a prefill
        from position 0 in-bucket, a chunk at a traced offset through the
        k-streaming kernel, else under ``attn_mask`` over the whole line.
        Returns the ``[b, s, heads, hd]`` output and the new line."""
        c = self.cfg
        window = self.spec.window
        s = q.shape[1]
        quantized = "k_scale" in kv_cache
        with jax.named_scope("kv_write"):
            if quantized:
                # int8 cache: quantise this call's K/V vectors as they are
                # written; reads below keep int8 as the attention matmul
                # operand and apply the scales outside the d-contraction
                k_q, k_s = _quantize_kv(k)
                v_q, v_s = _quantize_kv(v)
                k_all = jax.lax.dynamic_update_slice(
                    kv_cache["k"], k_q, (0, cache_index, 0, 0))
                v_all = jax.lax.dynamic_update_slice(
                    kv_cache["v"], v_q, (0, cache_index, 0, 0))
                ks_all = jax.lax.dynamic_update_slice(
                    kv_cache["k_scale"], k_s, (0, cache_index, 0))
                vs_all = jax.lax.dynamic_update_slice(
                    kv_cache["v_scale"], v_s, (0, cache_index, 0))
                new_cache = {"k": k_all, "k_scale": ks_all,
                             "v": v_all, "v_scale": vs_all}
            else:
                # static-shape cache update at cache_index (decode: s==1)
                k_all = jax.lax.dynamic_update_slice(
                    kv_cache["k"], k.astype(kv_cache["k"].dtype),
                    (0, cache_index, 0, 0))
                v_all = jax.lax.dynamic_update_slice(
                    kv_cache["v"], v.astype(kv_cache["v"].dtype),
                    (0, cache_index, 0, 0))
                ks_all = vs_all = None
                new_cache = {"k": k_all, "v": v_all}
        from_zero = isinstance(cache_index, int) and cache_index == 0
        if s > 1 and from_zero and attn_mask is None:
            # Prefill from position 0: attend IN-BUCKET, not over the
            # whole cache — scores are [P, P] instead of [P, max_seq]
            # (ctx/P× less attention work at serving shapes) and causal-
            # only, so the Pallas flash kernel applies to long prompts.
            # Padded tail positions only feed garbage to other padded
            # rows (causal) and to cache slots that decode masks/
            # overwrites; the engine reads logits at length-1 < P.
            # Chunked prefill (cache_index > 0 / traced, or an explicit
            # mask) must see the earlier cache, so it takes a full-cache
            # path below.
            with jax.named_scope("attn_core"):
                attend, sharded = _per_head_shard(
                    lambda q, k, v: dot_product_attention(
                        q, k, v, causal=True, impl="auto",
                        window=window),
                    self.tp_mesh, c)
                out = (attend(q, k, v) if sharded else
                       dot_product_attention(q, k, v, causal=True,
                                             window=window))
        elif s > 1 and attn_mask is None:
            # Chunked long-context prefill: this chunk's rows sit at
            # global positions cache_index + i and attend the whole
            # cache prefix causally via the k-streaming flash kernel
            # (traced offset/length — one compiled program serves every
            # chunk; GQA K/V stay unexpanded inside the kernel).  XLA
            # would need [s, max_seq] scores per head here.
            from tpustack.ops.pallas.flash_attention import flash_attention

            with jax.named_scope("kv_read"):
                if quantized:
                    # the kernel has no scale inputs: dequantise for this
                    # (per-chunk, compile-once) path — the decode step
                    # below is where the int8 bandwidth saving matters
                    k_in = (k_all.astype(self.dtype) *
                            ks_all[..., None].astype(self.dtype))
                    v_in = (v_all.astype(self.dtype) *
                            vs_all[..., None].astype(self.dtype))
                else:
                    k_in, v_in = k_all, v_all
            # (a tp that does not divide the heads leaves the kernel
            # unwrapped: Mosaic then refuses the partitioned program)
            with jax.named_scope("attn_core"):
                attend, _ = _per_head_shard(
                    lambda q, k, v, off: flash_attention(
                        q, k, v, causal=True, q_offset=off,
                        kv_len=off + s, window=window),
                    self.tp_mesh, c, n_scalars=1)
                out = attend(q, k_in, v_in,
                             jnp.asarray(cache_index, jnp.int32))
        else:
            if window is not None:
                band = (jnp.arange(k_all.shape[1])[None, None, None, :]
                        > positions[:, None, :, None] - window)
                attn_mask = (band if attn_mask is None
                             else attn_mask & band)
            with jax.named_scope("attn_core"):
                out = dot_product_attention(
                    q, k_all, v_all, mask=attn_mask, k_scale=ks_all,
                    v_scale=vs_all)
        return out, new_cache


class LlamaMLP(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    width: Optional[int] = None  # None: cfg.ffn_dim
    trace_name: str = "mlp"      # the jax.named_scope it runs under

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from tpustack.ops.quant import make_dense

        c = self.cfg
        width = self.width or c.ffn_dim
        dense = lambda feats, name: make_dense(
            c.quant, feats, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope(self.trace_name):
            gate = dense(width, "gate_proj")(x)
            up = dense(width, "up_proj")(x)
            return dense(c.dim, "down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    ring_mesh: Any = None
    tp_mesh: Any = None
    spec: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, positions, kv_cache, cache_index, attn_mask):
        c = self.cfg
        attn = LlamaAttention(c, self.dtype, self.ring_mesh, self.tp_mesh,
                              self.spec, name="self_attn")
        if self.spec.ffn == "experts":
            from tpustack.models.moe import MoEFeedForward

            ffn = MoEFeedForward(c, self.dtype, name="mlp")
        else:
            ffn = LlamaMLP(c, self.dtype, name="mlp")
        if self.spec.ffn == "experts" and kv_cache is not None and (
                RIDE_KEYS[0] in kv_cache):
            # a ride's decode rows and segment go through one router call;
            # their counters are kept apart (``MoEFeedForward``'s ``split``)
            experts, split = ffn, cache_index[0].shape[0]
            ffn = lambda h: experts(h, split=split)
        norm = lambda name: RMSNorm(c.rms_eps, self.dtype, name=name)
        if c.norm_placement == "pre":
            h, new_cache = attn(norm("input_layernorm")(x), positions,
                                kv_cache, cache_index, attn_mask)
            x = x + h
            x = x + ffn(norm("post_attention_layernorm")(x))
        else:
            h, new_cache = attn(x, positions, kv_cache, cache_index,
                                attn_mask)
            x = x + norm("post_attention_layernorm")(h)
            x = x + norm("post_feedforward_layernorm")(ffn(x))
        return x, new_cache


class LlamaModel(nn.Module):
    """``tokens [B,S] → logits [B,S,V]`` with optional per-layer KV caches.

    ``ring_mesh``: a ``jax.sharding.Mesh`` with an ``sp`` axis > 1 switches
    the (cache-less) training attention to ring sequence parallelism —
    params are unchanged, so the same checkpoint serves/rings freely.
    ``tp_mesh``: the serving tp mesh — the Pallas attention kernels then run
    per head shard (``_per_head_shard``).
    """

    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    ring_mesh: Any = None
    tp_mesh: Any = None

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None, cache_index=0,
                 attn_mask=None, logits_at=None, head_rows=None):
        """``logits_at``: optional ``[B]`` positions — compute logits ONLY at
        those sequence positions.  Long-context prefill must use this: full
        ``[B, S, vocab]`` f32 logits at 16k × Qwen's 152k vocab are ~10 GB,
        more than the lm_head needs to produce one next token.
        ``head_rows``: optional row indices — the head runs on those rows of
        the batch only (a ride's decode rows and its segment's last
        token).

        Applied (not initialised), each layer runs through its kind's
        ``_layer_program``: a block is traced once a program for every
        layer of one ``LayerSpec`` and inlined where each layer calls it,
        with that layer's own parameters."""
        c = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        if c.quant and not c.tie_embeddings:
            # int8 table frees ~0.5 GB of HBM on 150k-vocab models (gather +
            # rescale, no matmul); tied-embedding models keep bf16 so
            # ``embed.attend`` stays exact
            from tpustack.ops.quant import Int8Embed

            embed = Int8Embed(c.vocab_size, c.dim, dtype=self.dtype,
                              name="embed_tokens")
        else:
            embed = nn.Embed(c.vocab_size, c.dim, dtype=self.dtype,
                             name="embed_tokens")
        with jax.named_scope("embed"):
            x = embed(tokens)
        new_caches = [] if kv_caches is not None else None
        for i, spec in enumerate(c.layer_specs):
            cache_i = kv_caches[i] if kv_caches is not None else None
            if self.is_initializing():
                x, nc = LlamaBlock(c, self.dtype, self.ring_mesh,
                                   self.tp_mesh, spec, name=f"layers_{i}")(
                    x, positions, cache_i, cache_index, attn_mask)
            else:
                x, nc = self._apply_layer(i, spec, x, positions, cache_i,
                                          cache_index, attn_mask)
            if new_caches is not None:
                new_caches.append(nc)
        x = RMSNorm(c.rms_eps, self.dtype, name="norm")(x)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, logits_at[:, None, None].astype(jnp.int32), axis=1)  # [B,1,D]
        if head_rows is not None:
            x = jnp.take(x, head_rows, axis=0)
        from tpustack.ops.quant import make_dense

        with jax.named_scope("lm_head"):
            if c.tie_embeddings:
                logits = embed.attend(x.astype(jnp.float32))
            else:
                # int8 lm_head still matmuls in bf16 (x is bf16) but scales/
                # accumulates logits in f32, matching the bf16 path's out
                # dtype
                logits = make_dense(c.quant, c.vocab_size, use_bias=False,
                                    dtype=self.dtype, name="lm_head",
                                    out_dtype=jnp.float32)(
                    x if c.quant else x.astype(jnp.float32))
        return logits, new_caches

    def _apply_layer(self, i, spec, x, positions, cache, cache_index,
                     attn_mask):
        """Layer ``i`` through its kind's ``_layer_program``: array leaves
        of the cache, ``cache_index`` and ``attn_mask`` are its operands,
        any other leaf (a Python-int ``cache_index``) part of its key.
        Counters the block sows land where the block would have put them."""
        name = f"layers_{i}"
        mutable = tuple(col for col in ("moe_stats",)
                        if self.is_mutable_collection(col))
        leaves, tree = jax.tree.flatten((cache, cache_index, attn_mask))
        arrays = [v for v in leaves if isinstance(v, (jax.Array, np.ndarray))]
        fixed = tuple((j, v) for j, v in enumerate(leaves)
                      if not isinstance(v, (jax.Array, np.ndarray)))
        program = _layer_program(self.cfg, spec, self.dtype, self.ring_mesh,
                                 self.tp_mesh, mutable)
        (x, new_cache), sown = program(self.variables["params"][name], x,
                                       positions, arrays, tree, fixed)
        for col, val in sown.items():
            if val:
                self.put_variable(col, name, val)
        return x, new_cache


@functools.lru_cache(maxsize=None)
def _layer_program(cfg: LlamaConfig, spec: LayerSpec, dtype, ring_mesh,
                   tp_mesh, mutable: Tuple[str, ...]):
    """``LlamaBlock`` of one kind as ``jax.jit(..., inline=True)``: traced
    once for every layer of that kind that a program calls with the same
    shapes, and inlined into the caller, so the compiled program is what
    tracing each layer by itself gives.  ``(params, x, positions, arrays,
    tree, fixed) -> ((x, cache), sown)``: ``tree`` and ``fixed`` rebuild
    ``(cache, cache_index, attn_mask)`` from ``arrays``."""
    block = LlamaBlock(cfg, dtype, ring_mesh, tp_mesh, spec)

    def layer_block(params, x, positions, arrays, tree, fixed):
        leaves = list(arrays)
        for j, v in fixed:
            leaves.insert(j, v)
        cache, cache_index, attn_mask = jax.tree.unflatten(tree, leaves)
        args = (x, positions, cache, cache_index, attn_mask)
        if not mutable:
            return block.apply({"params": params}, *args), {}
        return block.apply({"params": params}, *args, mutable=list(mutable))

    return jax.jit(layer_block, static_argnums=(4, 5), inline=True)


def _shard_kv(caches, cfg: "LlamaConfig", mesh, pool: bool = False):
    """Serving-KV head-axis sharding (``parallel.sharding.shard_kv_tree``):
    host call sites pass the tp mesh so every cache/pool/buffer tensor
    lands split over its kv-head axis — the per-chip KV HBM bill divides
    by tp and decode's cache traffic stays chip-local.  ``mesh=None`` (and
    every in-graph/traced call, which never passes one) is byte-for-byte
    the unsharded layout, GSPMD propagation untouched."""
    if mesh is None:
        return caches
    from tpustack.parallel.sharding import shard_kv_tree

    return shard_kv_tree(caches, mesh, cfg.n_kv_heads, pool=pool)


def init_kv_caches(cfg: LlamaConfig, batch: int, dtype=jnp.bfloat16,
                   mesh=None, seq: Optional[int] = None):
    """Dense per-row cache lines of ``seq`` positions (default: the
    context, ``cfg.max_seq``)."""
    shape = (batch, seq or cfg.max_seq, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant == "int8":
        sshape = shape[:-1]  # one scale per cached K/V vector
        caches = [{"k": jnp.zeros(shape, jnp.int8),
                   "k_scale": jnp.zeros(sshape, jnp.float32),
                   "v": jnp.zeros(shape, jnp.int8),
                   "v_scale": jnp.zeros(sshape, jnp.float32)}
                  for _ in range(cfg.n_layers)]
    else:
        caches = [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                  for _ in range(cfg.n_layers)]
    return _shard_kv(caches, cfg, mesh)


def init_kv_pool(cfg: LlamaConfig, n_blocks: int, block: int,
                 dtype=jnp.bfloat16, mesh=None):
    """Per-layer PAGED KV pool tensors, AT REST in the layout their hot
    consumers take, so no compiled program re-lays them: K/V ``[n_blocks,
    block, kv_heads * head_dim]`` — a pool block is the ``[block, lanes]``
    slab ``paged_attention`` copies whole and a token is one lane row the
    scatter writes — and, when the cache is int8, per-vector scales
    TOKEN-MINOR and folded ``[n_blocks, kv_heads * block]`` — a block's
    page is one lane row, a head's ``block`` scales side by side (no view
    of a plane has a minor dimension of ``kv_heads``: under TPU tiling
    that is 4 live lanes of 128, and a minor dimension of ``block`` alone
    makes the chip lay the plane out blocks-minor).  ``pool_lines``,
    ``pool_pages`` and ``pool_rows`` convert between this and the dense
    cache's ``[..., tokens, kv_heads, head_dim]`` / ``[..., tokens,
    kv_heads]``.  The paged serving substrate
    (``tpustack.serving.kv_pool``): a sequence's cache line is a block
    table into these tensors instead of a private ``[max_seq]`` row, so
    HBM holds exactly the tokens in flight plus the refcounted prefix
    cache — not ``slots x max_seq`` regardless of use.  Block 0 is
    reserved (idle table entries point at it; nothing writes it)."""
    shape = (n_blocks, block, cfg.n_kv_heads * cfg.head_dim)
    if cfg.kv_quant == "int8":
        sshape = (n_blocks, cfg.n_kv_heads * block)
        pool = [{"k": jnp.zeros(shape, jnp.int8),
                 "k_scale": jnp.zeros(sshape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.int8),
                 "v_scale": jnp.zeros(sshape, jnp.float32)}
                for _ in range(cfg.n_layers)]
    else:
        pool = [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                for _ in range(cfg.n_layers)]
    return _shard_kv(pool, cfg, mesh, pool=True)


def is_scale_key(key: str) -> bool:
    """A per-vector scale plane of an int8 cache, pool or buffer."""
    return key.endswith("_scale")


def pool_lines(key: str, blocks: jax.Array, n_kv_heads: int) -> jax.Array:
    """Pool blocks ``[..., n, block, kvh * hd]`` (scales ``[..., n, kvh *
    block]``) → the dense cache line they spell: ``[..., n * block, kvh,
    hd]`` (scales ``[..., n * block, kvh]``)."""
    if is_scale_key(key):
        lead, n = blocks.shape[:-2], blocks.shape[-2]
        pages = blocks.reshape(lead + (n, n_kv_heads, -1))
        return jnp.swapaxes(pages, -1, -2).reshape(lead + (-1, n_kv_heads))
    lead, (n, blk) = blocks.shape[:-3], blocks.shape[-3:-1]
    return blocks.reshape(lead + (n * blk, n_kv_heads, -1))


def pool_pages(key: str, blocks: jax.Array) -> jax.Array:
    """Whole blocks in the dense order ``[n, block, kvh, hd]`` (scales
    ``[n, block, kvh]``) → the pages they rest as: ``pool_lines``'s
    inverse, for what builds a pool by hand (benches, tests, vectors)."""
    n, blk = blocks.shape[:2]
    if is_scale_key(key):
        return jnp.swapaxes(blocks, 1, 2).reshape(n, -1)
    return blocks.reshape(n, blk, -1)


def pool_rows(key: str, lines: jax.Array) -> jax.Array:
    """Dense cache values ``[..., tokens, kvh, hd]`` → the pool's token
    rows ``[..., tokens, kvh * hd]``; a scale's ``[..., tokens, kvh]`` is
    one already."""
    if is_scale_key(key):
        return lines
    return lines.reshape(lines.shape[:-2] + (-1,))


def init_chunk_bufs(cfg: LlamaConfig, batch: int, chunk: int,
                    dtype=jnp.bfloat16):
    """Per-layer chunk-local K/V buffers for the continuous decode scan
    (``ck``/``cv`` [+ scales when the cache is int8]): ``chunk`` positions
    written at the uniform step index while the main cache stays frozen,
    flushed into per-row cache lines once per chunk.  Mirrors the main
    cache's dtype/scale layout so a flush is a copy, never a requant."""
    shape = (batch, chunk, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant == "int8":
        sshape = shape[:-1]
        return [{"ck": jnp.zeros(shape, jnp.int8),
                 "ck_scale": jnp.zeros(sshape, jnp.float32),
                 "cv": jnp.zeros(shape, jnp.int8),
                 "cv_scale": jnp.zeros(sshape, jnp.float32)}
                for _ in range(cfg.n_layers)]
    return [{"ck": jnp.zeros(shape, dtype), "cv": jnp.zeros(shape, dtype)}
            for _ in range(cfg.n_layers)]


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-vector symmetric int8: ``[..., D] → (int8 [..., D], f32 [...])``."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    return (jnp.round(xf / scale[..., None]).astype(jnp.int8), scale)


def causal_lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross-entropy, mean over all positions (training ladder)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()
