"""Seeded random weights of an ``exaone_moe`` decoder (K-EXAONE), one chip's
share of it, in the type they are served in.

The same contract as ``benchmark/weights.py`` (whose helpers it imports): the
benchmark makes the weights, the runner hands them to the program, and the
plain reference makes them again from the same seed, a layer at a time.

A layer is a dict of ``wq wk wv wo`` (``[in, out]``), ``qn kn`` (the per-head
q/k norm scales), ``ln1 ln2`` (the norms after attention and after the
feed-forward), and either ``w_gate w_up w_down`` (the dense layer) or, for a
sparse layer, ``router`` (f32 ``[D, n_router]``), ``bias`` (f32
``[n_router]``, the score-correction bias), the held experts' stacks
``e_gate e_up e_down`` (``[held, in, out]``) and the shared expert
``s_gate s_up s_down``.  Under ``weights: "int8"`` a matrix is ``{"q", "s"}``
with a scale per output channel — a channel of each expert its own.

An expert's matrices are drawn from its GLOBAL id, so every share of the
deployment holds the same model; the stacks are made on the device by one
program a layer (no float stack of a whole layer ever exists beside the
served one: a projection at a time).

Distributions: matrices N(0, 1/fan_in), the router N(0, 1/D) in float32, the
bias N(0, 0.01^2), norm scales 1, embedding N(0, 1/D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import _matrix, base_key, quantize_rows

ATTN = ("wq", "wk", "wv", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERT = ("e_gate", "e_up", "e_down")
SHARED = ("s_gate", "s_up", "s_down")


def dims(cfg: dict) -> dict:
    """Shapes, kinds and the share from the configuration as it is run."""
    n = cfg["num_hidden_layers"]
    share = cfg.get("expert_share") or {"first": 0}
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "kvh": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "ffn": cfg["intermediate_size"], "eff": cfg["moe_intermediate_size"],
        "sff": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        "vocab": cfg["vocab_size"], "layers": n,
        "held": cfg["num_experts"], "first": int(share["first"]),
        "n_router": int(cfg.get("router_experts") or cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "windows": [int(w) or None for w in cfg["sliding_windows"][:n]],
        "sparse": [t == "sparse" for t in cfg["mlp_layer_types"][:n]],
    }


@functools.lru_cache(maxsize=None)
def _programs(d, h, kvh, hd, ffn, eff, sff, vocab, held, n_router, int8):
    attn_shapes = {"wq": (d, h * hd), "wk": (d, kvh * hd),
                   "wv": (d, kvh * hd), "wo": (h * hd, d)}

    def common(key):
        out = {name: _matrix(jax.random.fold_in(key, j), *attn_shapes[name],
                             int8) for j, name in enumerate(ATTN)}
        out["qn"] = jnp.ones((hd,), jnp.float32)
        out["kn"] = jnp.ones((hd,), jnp.float32)
        out["ln1"] = jnp.ones((d,), jnp.float32)
        out["ln2"] = jnp.ones((d,), jnp.float32)
        return out

    def swiglu(key, names, width, j0):
        shapes = ((d, width), (d, width), (width, d))
        return {name: _matrix(jax.random.fold_in(key, j0 + j), *shape, int8)
                for j, (name, shape) in enumerate(zip(names, shapes))}

    def dense_layer(key):
        return dict(common(key), **swiglu(key, DENSE, ffn, 8))

    def sparse_layer(key, first):
        out = dict(common(key), **swiglu(key, SHARED, sff, 8))
        out["router"] = jax.random.normal(
            jax.random.fold_in(key, 16), (d, n_router), jnp.float32
        ) * (d ** -0.5)
        out["bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(key, 17), (n_router,), jnp.float32)
        # one key an expert, from its global id
        ids = first + jnp.arange(held)
        for j, (name, shape) in enumerate(zip(
                EXPERT, ((d, eff), (d, eff), (eff, d)))):
            keys = jax.vmap(lambda e: jax.random.fold_in(
                jax.random.fold_in(key, 1000 + j), e))(ids)
            out[name] = jax.vmap(lambda k: _matrix(k, *shape, int8))(keys)
        return out

    def embed(key):
        t = jax.random.normal(key, (vocab, d), jnp.float32) * (d ** -0.5)
        return quantize_rows(t) if int8 else t.astype(jnp.bfloat16)

    def head(key):
        return _matrix(key, d, vocab, int8)

    return (jax.jit(dense_layer), jax.jit(sparse_layer), jax.jit(embed),
            jax.jit(head))


class Weights:
    """The share's weights as functions of (config, seed)."""

    def __init__(self, cfg: dict, seed: int):
        m = self.m = dims(cfg)
        self.cfg = cfg
        self.n_layers = m["layers"]
        self.int8 = cfg.get("weights") == "int8"
        self._dense, self._sparse, self._embed, self._head = _programs(
            m["d"], m["h"], m["kvh"], m["hd"], m["ffn"], m["eff"], m["sff"],
            m["vocab"], m["held"], m["n_router"], self.int8)
        self._key = base_key(seed)

    def layer(self, i: int, first: int = None) -> dict:
        """Layer ``i``; ``first``: another share's first expert (the share
        test), the configuration's own when None."""
        key = jax.random.fold_in(self._key, 100 + i)
        if not self.m["sparse"][i]:
            return self._dense(key)
        return self._sparse(key, jnp.int32(
            self.m["first"] if first is None else first))

    def embed(self):
        return self._embed(jax.random.fold_in(self._key, 1))

    def head(self):
        return self._head(jax.random.fold_in(self._key, 2))

    def final_norm(self):
        return jnp.ones((self.m["d"],), jnp.float32)
