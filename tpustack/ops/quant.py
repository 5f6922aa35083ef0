"""Weight-only quantisation for TPU serving (int8 per-channel).

Reference parity: the reference's llm app serves a **quantised** model —
Qwen2.5-7B Q4_K_M GGUF through llama.cpp (reference
``cluster-config/apps/llm/deployment.yaml:22-37,61-84``) — because a 6 GB
card cannot hold 7B in fp16.  A v5e chip holds 7B whole in bf16, so here
quantisation is a *throughput* feature, not a capacity workaround: decode is
HBM-bandwidth-bound (every generated token streams all weight bytes through
the MXU), so int8 weights halve bytes-per-token and nearly double decode
tokens/s.

TPU-first design:

- Weights live in HBM as ``int8`` with one fp32 scale per **output channel**
  (absmax/127, symmetric — llama.cpp's Q8_0 uses 32-wide blocks; per-channel
  is the XLA-friendly layout because the scale multiply fuses into the dot).
- The matmul runs in bf16: XLA fuses the ``int8 → bf16`` convert into the
  dot's operand read, so nothing bf16-sized is ever materialised in HBM.
  Activations stay bf16 (weight-only), which keeps quality near-lossless —
  measurably closer to fp16 than the reference's 4.5-bit Q4_K_M.
- Inference-only: ``Int8Dense`` parameters are not differentiable; training
  always runs bf16 and ``quantize_params`` converts a trained/loaded
  checkpoint in one pass (cf. GGUF conversion as an offline step).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

# Dense submodules that carry ~all weight bytes; norms/biases are negligible.
# Llama/Qwen projections (the default set — callers pass their own for other
# families; the Wan DiT/VAE also name modules "q"/"k"/"v"/"o", so the bare
# T5 names must NOT live in the default or a whole-pipeline quantise call
# would silently quantise attention projections never validated for int8).
QUANTIZABLE = frozenset({
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj", "lm_head",
})

# UMT5 encoder (Wan text tower): q/k/v/o attention + gated-GELU FFN
UMT5_QUANTIZABLE = frozenset({"q", "k", "v", "o", "wi_0", "wi_1", "wo"})

# default embedding-table dict key (quantised per ROW via quantize_rows);
# UMT5 callers pass {"embed"}
EMBED_KEYS = frozenset({"embed_tokens"})


class Int8Embed(nn.Module):
    """Drop-in ``nn.Embed`` with an int8 table + per-ROW (per-token) scale.

    The embedding is a gather, not a matmul — quantising it buys pure HBM
    capacity (e.g. 545 MB on Qwen2.5's 152k × 3584 table), which is what
    lets 32k-context prefill fit beside the model on a 16 GB chip.  The
    reference's Q4_K_M quantises its embedding table likewise.

    Scales are per vocabulary row, not per feature: a feature column's
    absmax over a 152k vocab is set by its single most extreme token, which
    would crush every other token's resolution in that feature; each row
    scaled by its own absmax keeps ~7 effective bits for every token.
    """

    num_embeddings: int
    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids: jax.Array) -> jax.Array:
        table = self.param("embedding", nn.initializers.zeros,
                           (self.num_embeddings, self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           (self.num_embeddings,), jnp.float32)
        # int8→f32, scale at full f32 precision, THEN cast to the compute
        # dtype — casting the scale to bf16 first would throw away half its
        # mantissa for no memory or compute saving (same single-rounding
        # policy as Int8Dense's f32-accumulate + f32-scale epilogue)
        rows = jnp.take(table, ids, axis=0).astype(jnp.float32)
        out = rows * jnp.take(scale, ids, axis=0)[..., None]
        return out.astype(self.dtype)


class Int8Dense(nn.Module):
    """Drop-in ``nn.Dense`` for weight-only int8 serving.

    Parameters: ``kernel`` int8 ``[in, out]``, ``scale`` fp32 ``[out]``,
    optional ``bias`` fp32 ``[out]`` — shapes chosen so
    ``quantize_params`` can map a bf16 Dense tree onto it 1:1.
    """

    features: int
    use_bias: bool = False
    dtype: Any = jnp.bfloat16
    out_dtype: Optional[Any] = None  # e.g. f32 for lm_head logits

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = self.param("kernel", nn.initializers.zeros,
                            (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        out_dtype = self.out_dtype or self.dtype
        # Accumulate in f32 on the MXU, apply the f32 scale (and bias) at
        # full precision, and round ONCE at the output cast — the epilogue
        # fuses into the matmul, so the f32 intermediate never hits HBM.
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        y = y * scale
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias
        return y.astype(out_dtype)


def make_dense(quant: Optional[str], features: int, *, use_bias: bool,
               dtype: Any, name: str, out_dtype: Optional[Any] = None):
    """Dense factory switched by config: ``None`` → bf16 ``nn.Dense``,
    ``"int8"`` → :class:`Int8Dense`."""
    if quant is None:
        return nn.Dense(features, use_bias=use_bias, name=name,
                        dtype=out_dtype or dtype)
    if quant == "int8":
        return Int8Dense(features, use_bias=use_bias, dtype=dtype,
                         name=name, out_dtype=out_dtype)
    raise ValueError(f"unknown quant mode {quant!r} (want None or 'int8')")


@jax.jit
def quantize_kernel(kernel: jax.Array) -> Dict[str, jax.Array]:
    """``[in, out]`` float kernel → {kernel: int8, scale: f32[out]}
    (symmetric absmax per output channel); a stack ``[experts, in, out]``
    → scale ``[experts, out]``, a channel of each expert its own.  Jitted
    so the fp32 intermediate never materialises in HBM — XLA fuses the
    convert into the absmax reduction and the rounding."""
    w = kernel.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=-2)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -127, 127).astype(
        jnp.int8)
    return {"kernel": q, "scale": scale.astype(jnp.float32)}


@jax.jit
def quantize_rows(table: jax.Array) -> Dict[str, jax.Array]:
    """``[V, D]`` embedding table → {embedding: int8, scale: f32[V]}
    (symmetric absmax per row — see Int8Embed for why not per feature)."""
    t = table.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(t), axis=1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(t / scale[:, None]), -127, 127).astype(jnp.int8)
    return {"embedding": q, "scale": scale.astype(jnp.float32)}


def quantize_params(params: Dict, names: frozenset = QUANTIZABLE,
                    quantize_embed: bool = True,
                    embed_keys: frozenset = EMBED_KEYS) -> Dict:
    """bf16 LLM param tree → int8 serving tree (module names in ``names``).

    The output matches what ``LlamaModel(cfg with quant='int8')`` initialises,
    so the quantised tree loads straight into the quantised model.  Runs once
    at server start (cf. the reference's offline GGUF conversion).

    **Consumes the input tree**: each bf16 kernel is popped before its int8
    replacement is created, so peak HBM is the full bf16 model plus ONE
    kernel — quantising a whole tree under one ``jit`` would instead hold
    bf16 + int8 trees simultaneously (~21 GB for 7B, an OOM on a 16 GB chip).
    """

    def walk(tree: Dict, under: Optional[str] = None) -> Dict:
        out = {}
        for k in list(tree.keys()):
            v = tree.pop(k)
            if (isinstance(v, dict) and k in names
                    and getattr(v.get("kernel"), "ndim", 0) in (2, 3)):
                kern = v.pop("kernel")
                q = dict(quantize_kernel(kern))
                del kern  # refcount → bf16 kernel freed before the next one
                q.update(v)  # carry bias etc. through
                out[k] = q
            elif (isinstance(v, dict) and k in embed_keys
                    and quantize_embed
                    and getattr(v.get("embedding"), "ndim", 0) == 2):
                emb = v.pop("embedding")
                q = dict(quantize_rows(emb))
                del emb
                q.update(v)
                out[k] = q
            elif isinstance(v, dict):
                out[k] = walk(v, k)
            else:
                out[k] = v
        return out

    return walk(dict(params))
