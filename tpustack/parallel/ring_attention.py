"""Ring attention: exact attention over sequences sharded across the ``sp``
mesh axis.

The reference's only long-context story is llama.cpp's ``--ctx-size 4096``
flag on one GPU (reference ``cluster-config/apps/llm/deployment.yaml:67-68``;
SURVEY.md §5 "long-context/sequence parallelism: absent").  The TPU build
makes it structural: shard the sequence over ``sp``, keep Q local, and rotate
K/V shards around the ring with ``jax.lax.ppermute`` while accumulating
streaming-softmax statistics — compute on the current shard overlaps the
neighbour transfer, collectives ride nearest-neighbor ICI, and peak memory
per chip is O(S/sp · S/sp) instead of O(S²).

Implementation: ``shard_map`` over the mesh; per-step partial attention uses
log-sum-exp accumulation (the flash-attention recurrence, across devices
instead of across VMEM tiles).  Causal masking uses global positions derived
from each shard's ring index.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as PS

NEG_INF = -1e30


def _partial_attn(q, k, v, q_start, k_start, causal, scale):
    """Unnormalised attention of local Q against one K/V shard.

    Returns (out_unnorm [B,Sq,H,D], row_max [B,H,Sq], row_sumexp [B,H,Sq]).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_start + jnp.arange(sq)[:, None]
        k_pos = k_start + jnp.arange(sk)[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                               # [B,H,Sq]
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be 1)
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    s = jnp.sum(p, axis=-1)                                    # [B,H,Sq]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out, m_safe, s


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axes=("dp", "fsdp"),
    head_axis: Optional[str] = "tp",
) -> jax.Array:
    """Exact BSHD attention with the sequence dim sharded over ``axis``.

    q/k/v: ``[B, S, H, D]`` global arrays.  Returns ``[B, S, H, D]`` with the
    same sharding.  kv heads must equal q heads (repeat GQA heads first).

    ``batch_axes``/``head_axis`` describe how batch and heads are already
    sharded by the surrounding jit (megatron layout: batch over dp×fsdp,
    heads over tp) so the shard_map doesn't force a resharding gather; axes
    absent from ``mesh`` are dropped.  The ring loop is a ``lax.scan``, so
    the whole op is reverse-mode differentiable — this is the TRAINING path
    for sequence parallelism (ppermute has a transpose rule; the backward
    pass rotates gradients around the same ring).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n_shards = mesh.shape[axis]
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    h_axis = head_axis if head_axis in mesh.axis_names else None
    seq_spec = PS(b_axes, axis, h_axis, None)

    def local_fn(q_loc, k_loc, v_loc):
        # q_loc: [B/dp·fsdp, S/sp, H/tp, D] on every member of the ring
        idx = jax.lax.axis_index(axis)
        s_loc = q_loc.shape[1]
        q_start = idx * s_loc

        def body(carry, i):
            k_cur, v_cur, acc, m_run, s_run = carry
            # K/V shard currently held started life on ring position idx - i
            src = jax.lax.rem(idx - i + n_shards, n_shards)
            out_i, m_i, s_i = _partial_attn(
                q_loc, k_cur, v_cur, q_start, src * s_loc, causal, scale)
            # streaming-softmax merge (flash recurrence across devices)
            m_new = jnp.maximum(m_run, m_i)
            alpha = jnp.exp(m_run - m_new)                    # rescale old
            beta = jnp.exp(m_i - m_new)                       # rescale new
            acc = acc * alpha.transpose(0, 2, 1)[..., None] \
                + out_i * beta.transpose(0, 2, 1)[..., None]
            s_run = s_run * alpha + s_i * beta
            # rotate K/V to the next ring member (nearest-neighbor ICI)
            perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, acc, m_new, s_run), None

        b, sq, h, d = q_loc.shape
        acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
        m0 = jnp.full((b, h, sq), NEG_INF / 2, jnp.float32)
        s0 = jnp.zeros((b, h, sq), jnp.float32)
        (_, _, acc, _, s_run), _ = jax.lax.scan(
            body, (k_loc, v_loc, acc0, m0, s0), jnp.arange(n_shards))
        denom = jnp.maximum(s_run, 1e-30).transpose(0, 2, 1)[..., None]
        return (acc / denom).astype(q_loc.dtype)

    # replication checking is off (the accumulator maths is not
    # expressible to the checker)
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(seq_spec, seq_spec, seq_spec),
                   out_specs=seq_spec, check_vma=False)
    return fn(q, k, v)


def ring_attention_sharded(q, k, v, mesh: Mesh, **kw):
    """Convenience: place BSHD inputs with S over sp (batch/heads
    replicated — standalone use), run, return global."""
    from jax.sharding import NamedSharding

    spec = PS(None, "sp", None, None)
    place = lambda t: jax.device_put(t, NamedSharding(mesh, spec))
    kw.setdefault("batch_axes", ())
    kw.setdefault("head_axis", None)
    return ring_attention(place(q), place(k), place(v), mesh=mesh, **kw)
