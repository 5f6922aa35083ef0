"""Plain reference: the ``exaone_moe`` decoder (K-EXAONE-236B-A23B), one
chip's share of it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no kernel, no cache, no batching, nothing imported
from the program.  Layer ``l`` of the kept layers, kinds from the
configuration's ``sliding_windows`` / ``mlp_layer_types``::

    q, k, v = x Wq, x Wk, x Wv                     (no biases)
    q, k    = rmsnorm_hd(q), rmsnorm_hd(k)         per head (assumed)
    q, k    = rope(q), rope(k)                     window layers only (assumed)
    a       = softmax(q k^T / sqrt(hd) + mask) v   key j visible to query i
                                                   iff 0 <= i - j (< window)
    x       = x + rmsnorm(a Wo)                    norm AFTER the sublayer
    x       = x + rmsnorm(ff(x))                   (assumed: EXAONE 4.0)

``ff`` of a dense layer is ``Wd (silu(Wg x) * Wu x)``.  Of a sparse layer:
``s = sigmoid(x Wr)`` over ALL routed experts, ``I`` = the ``top_k`` largest
of ``s + b``, gates ``g_i = routed_scale * s_i / sum_{j in I} s_j`` over all
the chosen, and ``y = sum_{i in I and held here} g_i E_i(x) + S(x)``: what
the experts of other chips would add is left out, here as in the program
(``model-configs`` guide, section 4), and ``y`` goes on to the next layer.
The head reads the rows of the vocabulary held here.

The multi-token-prediction layer is not here: next-token logits do not
depend on it.  ``served_gaps`` and its ``lower=`` control have the contract
of ``dense_gqa.served_gaps``, whose helpers this file imports.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_exaone as WX
from benchmark.reference.dense_gqa import (LOWER, lower_matrix, rmsnorm,
                                           rope)
from benchmark.weights import to_f32

__all__ = ["LOWER", "served_gaps", "logits_at", "hidden_states",
           "sparse_ff", "routing"]


def attention(x, w, *, h, kvh, hd, theta, eps, window):
    """One sequence ``x [T, D]``; ``window`` None is a full layer (no rope)."""
    t = x.shape[0]
    q = rmsnorm((x @ w["wq"]).reshape(t, h, hd), w["qn"], eps)
    k = rmsnorm((x @ w["wk"]).reshape(t, kvh, hd), w["kn"], eps)
    v = (x @ w["wv"]).reshape(t, kvh, hd)
    if window is not None:
        q, k = rope(q, theta), rope(k, theta)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (hd ** -0.5)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]      # i - j
    seen = back >= 0
    if window is not None:
        seen = seen & (back < window)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return att.reshape(t, h * hd) @ w["wo"]


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def routing(x, w, *, top_k, routed_scale):
    """``(chosen [T, k] global expert ids, gates [T, k])``."""
    s = jax.nn.sigmoid(x @ w["router"])
    _, chosen = jax.lax.top_k(s + w["bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, routed_scale * picked / picked.sum(-1, keepdims=True)


def sparse_ff(x, w, *, first, top_k, routed_scale):
    """The share's part of the layer: its held experts' (global ids
    ``first``...) weighted outputs, plus the shared expert.  One expert at
    a time over every token (a scan: one expert's program, not sixteen)."""
    chosen, gates = routing(x, w, top_k=top_k, routed_scale=routed_scale)

    def add_expert(y, expert):
        e, wg, wu, wd = expert
        g = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), axis=1)
        return y + g[:, None] * swiglu(x, wg, wu, wd), None

    held = w["e_gate"].shape[0]
    y, _ = jax.lax.scan(
        add_expert, swiglu(x, w["s_gate"], w["s_up"], w["s_down"]),
        (jnp.arange(held), w["e_gate"], w["e_up"], w["e_down"]))
    return y


def block(x, w, *, m, window, sparse):
    eps = m["eps"]
    a = attention(x, w, h=m["h"], kvh=m["kvh"], hd=m["hd"],
                  theta=m["theta"], eps=eps, window=window)
    x = x + rmsnorm(a, w["ln1"], eps)
    if sparse:
        y = sparse_ff(x, w, first=m["first"], top_k=m["top_k"],
                      routed_scale=m["routed_scale"])
    else:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"])
    return x + rmsnorm(y, w["ln2"], eps)


def _static(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, list)))


@functools.lru_cache(maxsize=None)
def _programs(static: tuple):
    m = dict(static)

    def run_block(x, w, window, sparse):
        with jax.default_matmul_precision("highest"):
            return block(x, w, m=m, window=window, sparse=sparse)

    def prep(w, kind):
        """Stored weights as the float32 values they stand for; with
        ``kind`` every matrix first stored one precision down."""
        out = {}
        for name, v in w.items():
            stack = name in WX.EXPERT
            if isinstance(v, dict):
                q, s = v["q"].astype(jnp.float32), v["s"]
                f = q * (s[:, None, :] if stack else s)
            else:
                f = v.astype(jnp.float32)
            if kind and name in WX.ATTN + WX.DENSE + WX.SHARED:
                f = lower_matrix(f, kind)
            elif kind and stack:
                f = jax.vmap(lambda e: lower_matrix(e, kind))(f)
            out[name] = f
        return out

    def logits(x, norm, head_t):
        with jax.default_matmul_precision("highest"):
            return rmsnorm(x, norm, m["eps"]) @ head_t

    return (jax.jit(run_block, static_argnums=(2, 3)),
            jax.jit(prep, static_argnums=1), jax.jit(logits))


def hidden_states(cfg: dict, weights: "WX.Weights", tokens: np.ndarray,
                  lower: Optional[str] = None) -> List[jax.Array]:
    """Final hidden state (before the last norm) of every position of every
    row of ``tokens [B, T]`` (rows padded on the right)."""
    m = WX.dims(cfg)
    run_block, prep, _ = _programs(_static(m))
    table = to_f32(weights.embed(), per_row=True)
    xs = [jnp.take(table, jnp.asarray(row), axis=0) for row in tokens]
    del table
    for i in range(weights.n_layers):
        w = prep(weights.layer(i), lower)
        xs = [run_block(x, w, m["windows"][i], m["sparse"][i]) for x in xs]
        del w
    return xs


def logits_at(cfg: dict, weights: "WX.Weights", tokens: np.ndarray,
              positions: Sequence[np.ndarray],
              lower: Optional[str] = None) -> List[jax.Array]:
    """Reference logits ``[len(positions[b]), V]`` for each row ``b``."""
    _, _, logits = _programs(_static(WX.dims(cfg)))
    xs = hidden_states(cfg, weights, tokens, lower)
    head_t = to_f32(weights.head())
    if lower:
        head_t = lower_matrix(head_t, lower)
    norm = weights.final_norm()
    return [logits(jnp.take(x, jnp.asarray(p), axis=0), norm, head_t)
            for x, p in zip(xs, positions)]


def served_gaps(cfg: dict, weights: "WX.Weights",
                seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
                lower: Optional[str] = None, pad_to: int = 128
                ) -> Dict[str, List[np.ndarray]]:
    """``dense_gqa.served_gaps`` for this family: per sequence, for each
    served token, how far its reference logit lies below the reference's
    best there; with ``lower`` also the control's reading."""
    longest = max(len(p) + len(s) for p, s in seqs)
    t = -(-longest // pad_to) * pad_to
    tokens = np.zeros((len(seqs), t), np.int32)
    positions, served = [], []
    for b, (p, s) in enumerate(seqs):
        ids = list(p) + list(s)
        tokens[b, :len(ids)] = ids
        positions.append(np.arange(len(p) - 1, len(ids) - 1))
        served.append(jnp.asarray(np.asarray(s, np.int32)))
    ref = logits_at(cfg, weights, tokens, positions)
    out = {"served": [np.asarray(
        r.max(axis=-1) - jnp.take_along_axis(r, s[:, None], axis=-1)[:, 0])
        for r, s in zip(ref, served)]}
    if lower:
        ctl = logits_at(cfg, weights, tokens, positions, lower)
        out["control"] = [np.asarray(
            r.max(axis=-1) - jnp.take_along_axis(
                r, jnp.argmax(c, axis=-1)[:, None], axis=-1)[:, 0])
            for r, c in zip(ref, ctl)]
    return out
