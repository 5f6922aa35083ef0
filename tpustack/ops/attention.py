"""Attention ops shared by the model families.

The reference never implements attention itself — it arrives prebuilt inside
diffusers (sd15-api) and llama.cpp (llm app).  Here it is a first-class op:
a plain XLA einsum path (lets XLA fuse softmax into the matmuls on the MXU)
plus an optional Pallas flash-attention kernel for long sequences
(``tpustack.ops.pallas.flash_attention``), selected by ``impl=``.

Shapes follow the TPU-friendly convention ``[batch, seq, heads, head_dim]``
(BSHD); matmuls contract over head_dim/seq which XLA tiles onto the MXU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def auto_impl(b: int, sq: int, h: int, sk: int, has_mask: bool,
              backend: str, data_shards: int = 1, d: int = 40) -> str:
    """The ``impl="auto"`` dispatch rule, separated for testability.

    Flash on TPU when the sequence is long enough that skipping the HBM
    round-trip of the ``[S, S]`` scores wins (≥1k tokens), short enough that
    the per-head K/V panel fits VMEM (≤8k), and batch·heads is small enough
    that the kernel's serialised grid still fills the MXU.  Measured on v5e,
    SD1.5 512² blocks: at D=40 flash is 2.5x faster at B*H=16, 1.4x at
    B*H=64, XLA ahead at B*H=128; at D=80 XLA is also ahead by B*H=128 —
    so the bound stays 64 below D=128.  At D=128 (Wan DiT) each grid step
    runs full-lane matmuls, so the bound doubles — enough to keep batched
    Wan generation (B*H≈72) on the kernel its docstring advertises.

    ``data_shards``: under GSPMD the traced ``b`` is the GLOBAL batch while
    each chip only runs ``b / data_shards`` of it — the crossover must be
    judged on the per-chip batch or DP serving would lose flash exactly
    where it wins.

    (Negative result, measured: unrolling multiple heads per kernel grid
    step to chase XLA at large B*H does not help — head_block=2 matched
    plain XLA and >=4 overflows the 16 MB VMEM scoped stack with full K/V
    panels per head.  Dispatching to XLA above the bound is the answer.)
    """
    from tpustack.ops.pallas.flash_attention import PANEL_MAX_KV

    per_chip_b = max(1, b // max(1, data_shards))
    bound = 128 if d >= 128 else 64
    # sk may be well below sq (DiT cross-attention to a 512-token text
    # panel): what flash avoids is the [Sq, Sk] fp32 scores HBM round-trip,
    # which scales with sq*sk — so the sk bound is only there to keep the
    # K/V panel DMA per grid step efficient, not to demand a long KV.
    # Measured in situ on v5e (Wan 1.3B full-size, xprof): the XLA path's
    # cross-attn score/value dots ran at 768-800 GB/s moving ~300 MB per
    # block-eval; the panel kernel's traffic is ~8x less.
    in_range = (1024 <= sq <= PANEL_MAX_KV and 256 <= sk <= PANEL_MAX_KV)
    # Beyond the panel ceiling XLA would materialise [Sq, Sk] scores
    # (tens of GB at 32k) — the k-streaming flash kernel is the only viable
    # path, whatever batch*heads is.
    long_ctx = sk > PANEL_MAX_KV
    return ("flash" if not has_mask and backend == "tpu"
            and (long_ctx or (in_range and per_chip_b * h <= bound))
            else "xla")


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "xla",
    data_shards: int = 1,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Scaled dot-product attention over BSHD tensors.

    Args:
      q: ``[B, Sq, H, D]``.
      k/v: ``[B, Sk, Hkv, D]`` — ``Hkv`` may divide ``H`` (GQA/MQA); kv heads
        are repeated to match.
      mask: optional boolean mask, ``[Sq, Sk]`` or ``[B|1, H|Hkv|1, Sq, Sk]``
        (3D is rejected as ambiguous between batch and head axes); True
        means *attend*.
      causal: apply a causal mask (decoder LMs).
      window: with ``causal``, a query at (bottom-right aligned) position
        ``i`` sees only keys ``j`` with ``i - j < window``.
      scale: defaults to ``1/sqrt(D)``.
      impl: ``"xla"`` (default), ``"flash"`` (Pallas kernel, TPU), or
        ``"auto"`` — flash on TPU for long sequences at small batch·heads
        (2.5x at SD1.5's 4k-token spatial attention, single image), XLA
        otherwise.
      k_scale/v_scale: optional ``[B, Sk, Hkv]`` per-vector dequantisation
        scales for an int8 KV cache (XLA impl only).  The int8 arrays stay
        the dot operands (XLA fuses the int8→compute convert into the
        operand read, so no bf16-sized cache ever materialises in HBM):
        ``k_scale`` factors out of the ``d``-contraction and is applied to
        the SCORES; ``v_scale`` rides the ``Sk``-contraction and folds into
        the softmax probabilities.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if window is not None and not causal:
        raise ValueError("window= needs causal=True")
    if mask is not None:
        mask = jnp.asarray(mask)
        # 3D masks are ambiguous ([B, Sq, Sk] vs [H, Sq, Sk]): broadcasting
        # against [B, H, Sq, Sk] would silently align the leading axis with
        # heads, so require the caller to disambiguate
        if mask.ndim not in (2, 4):
            raise ValueError(
                f"mask must be [Sq, Sk] or [B|1, H|Hkv|1, Sq, Sk]; a "
                f"{mask.ndim}D mask (shape {mask.shape}) is ambiguous — "
                "add explicit batch/head axes")

    if impl == "auto":
        impl = auto_impl(b, sq, h, k.shape[1], mask is not None,
                         jax.default_backend(), data_shards, d)
        if impl == "flash" and causal and sq > k.shape[1]:
            impl = "xla"  # flash rejects this shape (below); auto must not

    if k_scale is not None or v_scale is not None:
        if impl != "xla":
            raise NotImplementedError(
                "k_scale/v_scale (int8 KV cache) require impl='xla'; "
                "dequantise explicitly for the flash kernel")
        compute = q.dtype
        k = k.astype(compute)
        v = v.astype(compute)

    if impl == "flash":
        if mask is not None:
            raise NotImplementedError("flash impl supports causal=, not arbitrary mask=")
        from tpustack.ops.pallas.flash_attention import flash_attention

        # GQA is native in the kernel (K/V BlockSpec maps bh // group).
        # causal with sq != sk is BOTTOM-RIGHT aligned in the XLA path
        # (jnp.tril k=sk-sq: every q row sees its full K prefix); the kernel
        # judges causality against global q positions, so shift them by the
        # length difference to match (q_offset also routes to the streaming
        # kernel, the only one that takes an offset).
        if causal and sq > k.shape[1]:
            # bottom-right alignment has no meaning here (negative offset
            # would leave some q rows with zero valid keys, and the online
            # softmax would average garbage over K padding); the XLA path
            # keeps its degenerate-but-deterministic semantics instead
            raise ValueError(
                f"flash impl: causal with sq ({sq}) > sk ({k.shape[1]}) is "
                "not supported; use impl='xla'")
        q_off = k.shape[1] - sq if causal and k.shape[1] != sq else None
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               q_offset=q_off, window=window)
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}")

    if scale is None:
        scale = d ** -0.5
    sk = k.shape[1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                                     k=sk - sq - window)
        mask = causal_mask if mask is None else jnp.logical_and(mask, causal_mask)

    # [B, Sk, Hkv] scales → broadcastable over the score/prob layouts
    ks_b = (jnp.transpose(k_scale, (0, 2, 1))
            if k_scale is not None else None)  # [B, Hkv, Sk]
    vs_b = (jnp.transpose(v_scale, (0, 2, 1))
            if v_scale is not None else None)

    if hkv == h:
        # [B, H, Sq, Sk]; accumulate logits in fp32 for bf16 inputs.
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        if ks_b is not None:
            logits = logits * ks_b[:, :, None, :].astype(logits.dtype)
        logits = logits * jnp.asarray(scale, logits.dtype)
        if mask is not None:
            logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits, axis=-1)  # f32
        if vs_b is not None:
            # apply the f32 dequant scales BEFORE the downcast: scaling after
            # casting to bf16 would round the scales themselves and run the
            # multiply in bf16 — avoidable error on top of int8 quantisation
            probs = probs * vs_b[:, :, None, :]
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    # GQA contracts grouped queries against UNEXPANDED K/V — a ``jnp.repeat``
    # would materialise K/V at h/hkv× size in HBM, which on the KV-cache
    # decode step is the dominant bytes term (e.g. Qwen2.5 28q/4kv: 7× the
    # cache traffic; measured 2.6x batched decode from removing it).
    g = h // hkv
    q5 = q.reshape(b, sq, hkv, g, d)
    # [B, Hkv, G, Sq, Sk]
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                        preferred_element_type=jnp.float32)
    if ks_b is not None:
        logits = logits * ks_b[:, :, None, None, :].astype(logits.dtype)
    logits = logits * jnp.asarray(scale, logits.dtype)
    if mask is not None:
        # mask.ndim is 2 or 4 (validated above), so the head axis is exact
        if mask.ndim == 4 and mask.shape[-3] == h:
            # mask carries a full H heads axis → split it into (Hkv, G)
            mask = jnp.broadcast_to(mask, (b, h, sq, sk)).reshape(
                b, hkv, g, sq, sk)
        elif mask.ndim == 4:
            if mask.shape[-3] not in (1, hkv):
                raise ValueError(
                    f"mask head axis {mask.shape[-3]} matches neither "
                    f"H={h} nor Hkv={hkv} (nor 1)")
            # headless / per-kv-head masks broadcast over the group axis
            mask = mask[..., None, :, :]
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits, axis=-1)  # f32; scales applied pre-cast
    if vs_b is not None:
        probs = probs * vs_b[:, :, None, None, :]
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)


NEG_INF = -1e30


def dot_product_attention_partial(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: jax.Array,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Attention over a PARTIAL key set, returning the online-softmax carry
    instead of a normalised output: ``(acc [B,Sq,H,D] f32 unnormalised,
    m [B,Sq,H] f32 row max, l [B,Sq,H] f32 denominator)``.

    Two partials over disjoint key sets merge exactly into full attention
    via :func:`merge_attention_partials` — the same decomposition the flash
    kernels use across k-blocks, here at the XLA level so the continuous
    decode step can attend {frozen main cache} ∪ {chunk-local K/V buffer}
    without rewriting the whole cache every step (the one-hot write-back
    this replaces doubled decode KV traffic; see LlamaAttention).

    ``mask [B, Sq, Sk]`` (True = attend; required — a partial with no mask
    is just ``dot_product_attention``).  GQA K/V stay unexpanded like the
    main path.  A fully-masked row yields ``m = NEG_INF, l = 0, acc = 0``
    — merging handles it as long as the OTHER partial has a valid key
    (decode always attends its own freshly-written position).
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = d ** -0.5
    sk = k.shape[1]
    g = h // hkv
    if k_scale is not None or v_scale is not None:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    ks_b = (jnp.transpose(k_scale, (0, 2, 1))
            if k_scale is not None else None)  # [B, Hkv, Sk]
    vs_b = (jnp.transpose(v_scale, (0, 2, 1))
            if v_scale is not None else None)

    q5 = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                        preferred_element_type=jnp.float32)
    if ks_b is not None:
        logits = logits * ks_b[:, :, None, None, :].astype(logits.dtype)
    logits = logits * jnp.asarray(scale, logits.dtype)
    logits = jnp.where(mask[:, None, None, :, :], logits,
                       jnp.asarray(NEG_INF, logits.dtype))
    m = jnp.max(logits, axis=-1)                      # [B, Hkv, G, Sq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(logits <= NEG_INF, 0.0, p)          # all-masked row: l = 0
    l = jnp.sum(p, axis=-1)
    if vs_b is not None:
        p = p * vs_b[:, :, None, None, :]
    acc = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    to_bqh = lambda x: x.transpose(0, 3, 1, 2).reshape(b, sq, h)
    return acc.reshape(b, sq, h, d), to_bqh(m), to_bqh(l)


def merge_attention_partials(p1, p2, out_dtype) -> jax.Array:
    """Merge two :func:`dot_product_attention_partial` carries over disjoint
    key sets into the full attention output ``[B, Sq, H, D]``.

    Exact softmax decomposition: with the shared max ``m = max(m1, m2)``
    the rescaled exponentials equal the one-pass values, so the merge
    differs from single-pass attention only in summation order."""
    a1, m1, l1 = p1
    a2, m2, l2 = p2
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)[..., None]
    w2 = jnp.exp(m2 - m)[..., None]
    denom = l1[..., None] * w1 + l2[..., None] * w2
    return ((a1 * w1 + a2 * w2) /
            jnp.maximum(denom, 1e-30)).astype(out_dtype)
