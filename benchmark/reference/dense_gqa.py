"""Plain reference: the dense GQA + SwiGLU decoder (Llama-2 / Qwen2 family).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision; no kernel, no cache, no batching, nothing imported
from the program.  It follows the published Qwen2 block::

    h = x + Wo . softmax(rope(Wq n1(x) + bq) . rope(Wk n1(x) + bk)^T / sqrt(hd)
                         + causal) . (Wv n1(x) + bv)
    y = h + Wdown . (silu(Wgate n2(h)) * Wup n2(h))

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotary embedding in
the half-split ("rotate_half") convention at ``rope_theta``, grouped-query
attention (each KV head serves ``h / kvh`` query heads) and an untied or tied
output matrix.  The model *is* its stored weights: int8 matrices stand for
``q * scale``, bfloat16 ones for their float32 values (`benchmark/weights`).

It runs a layer at a time and a row at a time, so a 7B model in float32 fits
beside nothing else on a 16 GB chip: the weights of one layer are made from
the seed, used for every row, and dropped.

``served_gaps`` is the comparison that decides ``correct`` for a served model:
for every token the system served greedily, how far that token's reference
logit lies below the reference's best at that position.  With ``lower=`` it
also reads the control: the same forward in the next precision down, and the
gap of the token *that* model puts first.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

#: the nearest precision below the one a configuration states
LOWER = {"float32": "bfloat16", "bfloat16": "fp8", "float16": "fp8",
         "int8": "int4", "fp8": "int4"}


def lower_matrix(w, kind: str):
    """``w`` (float32 ``[in, out]``) as ``kind`` would store it."""
    if kind == "bfloat16":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-30)
    if kind == "fp8":  # e4m3, scaled per output channel to its range
        s = absmax / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    levels = {"int8": 127.0, "int4": 7.0}[kind]
    s = absmax / levels
    return jnp.clip(jnp.round(w / s), -levels, levels) * s


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """``x [T, H, hd]`` at positions 0..T-1, half-split convention."""
    t, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, w, *, h, kvh, hd, theta, eps):
    """One decoder layer over one sequence ``x [T, D]``, causal."""
    t = x.shape[0]
    n = rmsnorm(x, w["ln1"], eps)
    q = rope((n @ w["wq"] + w["bq"]).reshape(t, h, hd), theta)
    k = rope((n @ w["wk"] + w["bk"]).reshape(t, kvh, hd), theta)
    v = (n @ w["wv"] + w["bv"]).reshape(t, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (hd ** -0.5)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(t, h * hd) @ w["wo"]
    n = rmsnorm(x, w["ln2"], eps)
    return x + (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) @ w["w_down"]


@functools.lru_cache(maxsize=None)
def _programs(h, kvh, hd, theta, eps):
    def run_block(x, w):
        with jax.default_matmul_precision("highest"):
            return block(x, w, h=h, kvh=kvh, hd=hd, theta=theta, eps=eps)

    def prep(w, kind):
        out = {k: W.to_f32(v) for k, v in w.items()}
        if kind:
            for name in W.MATRICES:
                out[name] = lower_matrix(out[name], kind)
        return out

    def logits(x, norm, head_t):
        with jax.default_matmul_precision("highest"):
            return rmsnorm(x, norm, eps) @ head_t

    return (jax.jit(run_block), jax.jit(prep, static_argnums=1),
            jax.jit(logits))


def _hp(cfg: dict):
    m = W.dims(cfg)
    return (m["h"], m["kvh"], m["hd"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]))


def hidden_states(cfg: dict, weights: "W.Weights", tokens: np.ndarray,
                  lower: Optional[str] = None) -> List[jax.Array]:
    """Final hidden state (before the last norm) of every position of every
    row of ``tokens [B, T]`` (rows padded on the right: causal attention
    keeps padding out of what comes before it).  With ``lower`` every matrix
    of every layer is first stored as that precision would store it."""
    run_block, prep, _ = _programs(*_hp(cfg))
    table = W.to_f32(weights.embed(), per_row=True)
    xs = [jnp.take(table, jnp.asarray(row), axis=0) for row in tokens]
    del table
    for i in range(weights.n_layers):
        w = prep(weights.layer(i), lower)
        xs = [run_block(x, w) for x in xs]
    return xs


def head_matrix(weights: "W.Weights", lower: Optional[str] = None):
    """``[D, V]`` float32: the output matrix, or the tied table transposed."""
    head = weights.head()
    m = (W.to_f32(head) if head is not None
         else W.to_f32(weights.embed(), per_row=True).T)
    return lower_matrix(m, lower) if lower else m


def logits_at(cfg: dict, weights: "W.Weights", tokens: np.ndarray,
              positions: Sequence[np.ndarray],
              lower: Optional[str] = None) -> List[jax.Array]:
    """Reference logits ``[len(positions[b]), V]`` for each row ``b``."""
    _, _, logits = _programs(*_hp(cfg))
    xs = hidden_states(cfg, weights, tokens, lower)
    head_t = head_matrix(weights, lower)
    norm = weights.final_norm()
    return [logits(jnp.take(x, jnp.asarray(p), axis=0), norm, head_t)
            for x, p in zip(xs, positions)]


def served_gaps(cfg: dict, weights: "W.Weights",
                seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
                lower: Optional[str] = None, pad_to: int = 128
                ) -> Dict[str, List[np.ndarray]]:
    """``seqs`` are (prompt ids as the model saw them, ids it then served).

    Returns ``{"served": [...]}``: per sequence, for each served token, how
    far its reference logit lies below the reference's best logit there (0
    where the system served the reference's own first choice).  With
    ``lower`` also ``{"control": [...]}``: the same for the token that the
    lower-precision forward puts first at each of those positions.
    """
    longest = max(len(p) + len(s) for p, s in seqs)
    t = -(-longest // pad_to) * pad_to  # few distinct shapes to compile
    tokens = np.zeros((len(seqs), t), np.int32)
    positions, served = [], []
    for b, (p, s) in enumerate(seqs):
        ids = list(p) + list(s)
        tokens[b, :len(ids)] = ids
        # the logits at position j predict token j + 1
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
