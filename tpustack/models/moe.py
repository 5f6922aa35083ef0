"""The routed-expert feed-forward layer (``LayerSpec.ffn == "experts"``).

For a token ``x``: scores ``s = sigmoid(x . Wr)`` over ALL ``n_experts`` in
float32, the ``top_k`` largest of ``s + b`` (``b`` the score-correction bias
of auxiliary-loss-free routing) chosen, gates ``g_i = routed_scale * s_i /
sum(s_j over the chosen)``, and

    y = sum over chosen experts held here of g_i * E_i(x)  +  S(x)

with ``E_i`` and the shared expert ``S`` SwiGLU blocks.  The layer is told
which experts it holds (``MoESpec.held``, expert parallelism's share): it
routes over all of them, normalises the gates over all the chosen, and
computes its own experts' part.  On one chip there is no exchange, and
nothing here stands in for the other chips: what their experts would add is
simply not in ``y``.

The token-expert pairs that land here are sorted by expert into row tiles
(``plan_tiles``), each group padded to its own next tile; one grouped
product per projection (``ops.pallas.moe_gmm``) reads only the experts that
got a pair.  No capacity factor and no dropped pair: the row buffer is sized
for every pair landing here.  The same body runs a decode step and a
512-token admission; only ``tm``, from the static token count, differs.

Counters leave through the ``moe_stats`` collection (``sow``): per call
``[pairs, experts_touched, max_expert_tokens]`` (a call with ``split``: one
such row for each of its two parts); whoever applies the model with
``mutable=["moe_stats"]`` sums them (``llm_generate``).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpustack.ops.pallas.moe_gmm import moe_gmm


class TilePlan(NamedTuple):
    tile_expert: jax.Array   # [m_pad // tm] local expert of each row tile
    n_active: jax.Array      # scalar: row tiles that hold a pair
    row_token: jax.Array     # [m_pad] token each buffer row reads (0: none)
    pair_row: jax.Array      # [T, k] buffer row of each pair (0: not here)
    pair_here: jax.Array     # [T, k] bool: the pair's expert is held here
    counts: jax.Array        # [held] pairs per held expert


def row_tile(tokens: int, top_k: int, n_experts: int) -> int:
    """Rows of one tile, from the pairs an expert expects of ``tokens``
    tokens: a power of two between the bf16 sublane tile and 256 (where a
    panel's product outweighs its fetch)."""
    want = max(1, tokens * top_k // n_experts)
    return min(256, max(16, 1 << (want - 1).bit_length()))


def plan_tiles(local: jax.Array, held: int, tm: int) -> TilePlan:
    """``local [T, k]``: each token's chosen experts, counted from the first
    one held here (outside ``[0, held)``: another chip's).  Gathers only —
    no scatter (they serialise on the chip)."""
    t, k = local.shape
    p = t * k
    m_pad = -(-(t * min(k, held) + held * (tm - 1)) // tm) * tm
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(p).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)       # pairs by expert
    rank = jnp.argsort(order)                   # its inverse
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                     dtype=jnp.int32)
    ustart = jnp.cumsum(counts) - counts        # group starts, unpadded
    tiles = -(-counts // tm)
    tend = jnp.cumsum(tiles)
    pstart = (tend - tiles) * tm                # group starts in the buffer
    n_tiles = m_pad // tm
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles)[:, None] >= tend[None, :], axis=1,
                dtype=jnp.int32), held - 1)
    r = jnp.arange(m_pad)
    te = jnp.repeat(tile_expert, tm)
    pos = r - pstart[te]
    valid = (pos < counts[te]) & (r < tend[-1] * tm)
    src = order[jnp.clip(ustart[te] + pos, 0, p - 1)]
    keyc = jnp.minimum(key, held - 1)
    pair_row = jnp.where(here.reshape(p),
                         pstart[keyc] + rank - ustart[keyc], 0)
    return TilePlan(tile_expert, tend[-1], jnp.where(valid, src // k, 0),
                    pair_row.reshape(t, k), here, counts)


class ExpertStack(nn.Module):
    """``held`` matrices ``[in, features]`` as one stack, multiplied by row
    tile.  ``quant="int8"``: int8 with one f32 scale per expert and output
    channel (``ops.quant.quantize_kernel`` on the float twin's stack)."""

    held: int
    features: int
    quant: Optional[str] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, xs: jax.Array, plan: TilePlan, tm: int) -> jax.Array:
        shape = (self.held, xs.shape[-1], self.features)
        if self.quant == "int8":
            kernel = self.param("kernel", nn.initializers.zeros, shape,
                                jnp.int8)
            scale = self.param("scale", nn.initializers.ones,
                               (self.held, self.features), jnp.float32)
        else:
            kernel = self.param(
                "kernel", nn.initializers.lecun_normal(
                    in_axis=-2, out_axis=-1, batch_axis=(0,)), shape)
            scale = None
        return moe_gmm(xs.astype(self.dtype), kernel, scale,
                       plan.tile_expert, plan.n_active, tm=tm)


class MoEFeedForward(nn.Module):
    cfg: Any  # LlamaConfig (with .moe)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, split: Optional[int] = None
                 ) -> jax.Array:
        """``split``: the first ``split`` tokens are counted apart from the
        rest (a ride's decode rows and its segment, one router call):
        the counters are then ``[2, 3]``, one row for each part."""
        from tpustack.models.llama import LlamaMLP

        c, m = self.cfg, self.cfg.moe
        first, held = m.held_range
        b, s, d = x.shape
        t = b * s
        xt = x.reshape(t, d)
        tm = row_tile(t, m.top_k, m.n_experts)
        with jax.named_scope("moe_router"):
            # float32 all the way: a near-tie of s + b must fall the way the
            # reference's does
            wr = self.param("router", nn.initializers.normal(d ** -0.5),
                            (d, m.n_experts), jnp.float32)
            bias = self.param("score_bias", nn.initializers.zeros,
                              (m.n_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                xt.astype(jnp.float32), wr,
                precision=jax.lax.Precision.HIGHEST))
            _, chosen = jax.lax.top_k(scores + bias, m.top_k)     # [T, k]
            s_chosen = jnp.take_along_axis(scores, chosen, axis=1)
            gates = m.routed_scale * s_chosen / jnp.sum(
                s_chosen, axis=-1, keepdims=True)
            plan = plan_tiles(chosen - first, held, tm)
        with jax.named_scope("moe_experts"):
            stack = lambda feats, name: ExpertStack(
                held, feats, c.quant, self.dtype, name=name)
            xs = jnp.take(xt, plan.row_token, axis=0)
            h = nn.silu(stack(m.expert_dim, "gate_proj")(xs, plan, tm)) * (
                stack(m.expert_dim, "up_proj")(xs, plan, tm))
            rows = stack(d, "down_proj")(h, plan, tm)
        shared = LlamaMLP(c, self.dtype, width=m.shared_dim,
                          trace_name="moe_shared", name="shared")(x)
        with jax.named_scope("moe_combine"):
            picked = jnp.take(rows, plan.pair_row.reshape(-1), axis=0)
            picked = picked.reshape(t, m.top_k, d).astype(jnp.float32)
            # select, not multiply: a dead tile's rows may hold anything
            routed = jnp.sum(jnp.where(plan.pair_here[..., None],
                                       picked * gates[..., None], 0.0),
                             axis=1)
            out = routed + shared.reshape(t, d).astype(jnp.float32)
        stats = lambda n: jnp.stack([jnp.sum(n), jnp.sum(n > 0),
                                     jnp.max(n)]).astype(jnp.int32)
        if split is None:
            self.sow("moe_stats", "counts", stats(plan.counts))
        else:
            local = (chosen - first)[:split]
            head = jnp.sum(((local >= 0) & (local < held)).reshape(-1)[:, None]
                           & (local.reshape(-1)[:, None]
                              == jnp.arange(held)[None, :]), axis=0,
                           dtype=jnp.int32)
            self.sow("moe_stats", "counts", jnp.stack(
                [stats(head), stats(plan.counts - head)]))
        return out.astype(self.dtype).reshape(b, s, d)
