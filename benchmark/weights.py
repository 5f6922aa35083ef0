"""Seeded random weights of a dense GQA + SwiGLU decoder, in the type they
are served in.

The benchmark makes the weights, not the program: the runner hands them to
the system under test and the plain reference makes the same ones again,
layer by layer, from the same seed — so the reference takes nothing the
program has made.  One compiled program per kind of tensor group (a decoder
layer, the embedding, the head), called once per layer: nothing is made leaf
by leaf or on the host, and no float twin of the whole model ever exists.

Layout ("plain", not the program's): a layer is a dict of ``wq wk wv wo
w_gate w_up w_down`` (``[in, out]``), ``bq bk bv``, ``ln1 ln2``.  Under
``weights: "int8"`` every matrix is ``{"q": int8 [in, out], "s": f32 [out]}``
(symmetric absmax per output channel) and the embedding is ``{"q": int8
[V, D], "s": f32 [V]}`` (per row); otherwise matrices are bfloat16.

Distributions: matrices N(0, 1/fan_in), q/k/v biases N(0, 0.02^2), the
embedding N(0, 1/D), norm scales 1 — attention scores and logits (tied head
included) come out with unit spread, so neither softmax is degenerate and a
request that samples at temperature 1 does not fall into a cycle.

Keys use the ``rbg`` generator (the chip's own bit generator: a 7B model in
about a second, where threefry takes a quarter of a minute).  Its bits are
only promised to repeat for the same program on the same backend, which is
all that is asked: both users call the functions below in one process.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.readers.arith import model_dims as dims  # shapes of a config

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def base_key(seed: int):
    """Any whole number up to a little over 2**31: folded, not truncated."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


def quantize_cols(w):
    """``[in, out]`` float -> int8 + one f32 scale per output channel."""
    absmax = jnp.max(jnp.abs(w), axis=0)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return {"q": jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8),
            "s": s.astype(jnp.float32)}


def quantize_rows(t):
    """``[V, D]`` float -> int8 + one f32 scale per row."""
    absmax = jnp.max(jnp.abs(t), axis=1)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return {"q": jnp.clip(jnp.round(t / s[:, None]), -127, 127).astype(
        jnp.int8), "s": s.astype(jnp.float32)}


def _matrix(key, fan_in, fan_out, int8):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32) * (
        fan_in ** -0.5)
    return quantize_cols(w) if int8 else w.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _programs(d, h, kvh, hd, ffn, vocab, int8, tied):
    shapes = {"wq": (d, h * hd), "wk": (d, kvh * hd), "wv": (d, kvh * hd),
              "wo": (h * hd, d), "w_gate": (d, ffn), "w_up": (d, ffn),
              "w_down": (ffn, d)}
    bias_dt = jnp.float32 if int8 else jnp.bfloat16

    def layer(key):
        out = {}
        for j, name in enumerate(MATRICES):
            out[name] = _matrix(jax.random.fold_in(key, j), *shapes[name],
                                int8)
        for j, (name, n) in enumerate((("bq", h * hd), ("bk", kvh * hd),
                                       ("bv", kvh * hd))):
            out[name] = (0.02 * jax.random.normal(
                jax.random.fold_in(key, 16 + j), (n,), jnp.float32)
            ).astype(bias_dt)
        out["ln1"] = jnp.ones((d,), jnp.float32)
        out["ln2"] = jnp.ones((d,), jnp.float32)
        return out

    def embed(key):
        # 1/sqrt(D): a tied table is also the head, and logits of unit
        # spread want rows of unit length (the first norm rescales the input)
        t = jax.random.normal(key, (vocab, d), jnp.float32) * (d ** -0.5)
        # a tied table is also the head, which the program keeps unquantised
        return quantize_rows(t) if int8 and not tied else t.astype(
            jnp.bfloat16)

    def head(key):
        return _matrix(key, d, vocab, int8)

    return jax.jit(layer), jax.jit(embed), jax.jit(head)


class Weights:
    """The model's weights as functions of (config, seed)."""

    def __init__(self, cfg: dict, seed: int):
        m = dims(cfg)
        self.cfg = cfg
        self.n_layers = m["layers"]
        self.int8 = cfg.get("weights") == "int8"
        self.tied = bool(cfg.get("tie_word_embeddings"))
        self._layer, self._embed, self._head = _programs(
            m["d"], m["h"], m["kvh"], m["hd"], m["ffn"], m["vocab"],
            self.int8, self.tied)
        self._key = base_key(seed)

    def layer(self, i: int) -> dict:
        return self._layer(jax.random.fold_in(self._key, 100 + i))

    def embed(self):
        return self._embed(jax.random.fold_in(self._key, 1))

    def head(self):
        """The output matrix ``[D, V]``; None where the embedding is tied."""
        if self.tied:
            return None
        return self._head(jax.random.fold_in(self._key, 2))

    def final_norm(self):
        return jnp.ones((dims(self.cfg)["d"],), jnp.float32)


def to_f32(w, per_row: bool = False):
    """A stored matrix (scale per column) or embedding table (``per_row``)
    as the float32 values it stands for."""
    if isinstance(w, dict):
        q, s = w["q"].astype(jnp.float32), w["s"]
        return q * (s[:, None] if per_row else s)
    return w.astype(jnp.float32)
