"""Weight-only int8 quantisation (tpustack.ops.quant).

Reference parity: the reference's llm app serves a quantised model (Q4_K_M
GGUF via llama.cpp, ``cluster-config/apps/llm/deployment.yaml:22-37,61-84``);
here int8 is the serving-throughput analog.  Tests run the tiny config on the
virtual-CPU mesh, checking (a) the quantised tree loads straight into the
quantised model, (b) logits stay close to bf16, (c) the full generate path
runs end-to-end quantised.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.llama import LlamaConfig, LlamaModel
from tpustack.ops.quant import QUANTIZABLE, quantize_kernel, quantize_params


def test_quantize_kernel_roundtrip_error_small():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    q = quantize_kernel(w)
    assert q["kernel"].dtype == jnp.int8
    assert q["scale"].shape == (32,)
    deq = q["kernel"].astype(jnp.float32) * q["scale"]
    # symmetric absmax int8: max error is scale/2 per element
    err = jnp.abs(deq - w)
    assert float(err.max()) <= float(q["scale"].max()) / 2 + 1e-6
    # zero column must not divide by zero
    w0 = w.at[:, 3].set(0.0)
    q0 = quantize_kernel(w0)
    assert np.all(np.asarray(q0["kernel"][:, 3]) == 0)


def _tiny_params_and_tokens(quant=None):
    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64), quant=quant)
    model = LlamaModel(cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    return cfg, model, tokens


@pytest.mark.slow
def test_quantized_tree_matches_quant_model_init():
    cfg, model, tokens = _tiny_params_and_tokens()
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    qtree = quantize_params(params)

    qcfg, qmodel, _ = _tiny_params_and_tokens(quant="int8")
    tmpl = jax.eval_shape(
        lambda: qmodel.init(jax.random.PRNGKey(0), tokens))["params"]
    flat_q = jax.tree_util.tree_flatten_with_path(qtree)[0]
    flat_t = jax.tree_util.tree_flatten_with_path(tmpl)[0]
    assert [p for p, _ in flat_q] == [p for p, _ in flat_t]
    for (path, leaf), (_, t) in zip(flat_q, flat_t):
        assert leaf.shape == t.shape and leaf.dtype == t.dtype, path


def test_quantized_logits_close_to_bf16():
    cfg, model, tokens = _tiny_params_and_tokens()
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    ref_logits, _ = model.apply({"params": params}, tokens)

    qcfg, qmodel, _ = _tiny_params_and_tokens(quant="int8")
    qparams = quantize_params(params)  # consumes params
    q_logits, _ = qmodel.apply({"params": qparams}, tokens)

    ref = np.asarray(ref_logits, np.float32).ravel()
    got = np.asarray(q_logits, np.float32).ravel()
    cos = float(np.dot(ref, got) / (np.linalg.norm(ref) * np.linalg.norm(got)))
    assert cos > 0.99, f"quantised logits diverged: cosine {cos}"
    # greedy next-token agreement on most positions
    ref_arg = np.asarray(ref_logits).argmax(-1)
    got_arg = np.asarray(q_logits).argmax(-1)
    assert (ref_arg == got_arg).mean() > 0.9


def test_quantize_params_consumes_and_skips_non_target():
    cfg, model, tokens = _tiny_params_and_tokens()
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    emb_before = params["embed_tokens"]["embedding"]
    qtree = quantize_params(params)
    # embed table quantised too (int8 gather — pure HBM capacity win);
    # scales are per vocab ROW, not per feature (outlier-token robustness)
    assert qtree["embed_tokens"]["embedding"].dtype == jnp.int8
    assert qtree["embed_tokens"]["scale"].shape == (cfg.vocab_size,)
    # norms untouched
    assert "scale" in qtree["norm"] and qtree["norm"]["scale"].dtype != jnp.int8
    # every projection quantised
    attn = qtree["layers_0"]["self_attn"]
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        assert attn[name]["kernel"].dtype == jnp.int8, name
        assert attn[name]["scale"].dtype == jnp.float32
    # bf16 kernels were popped out of the input tree (freed for HBM headroom)
    assert "kernel" not in params["lm_head"]

    # tied-embedding configs keep the bf16 table (embed.attend path)
    tied_cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=32),
                                   tie_embeddings=True)
    tied = LlamaModel(tied_cfg, dtype=jnp.float32)
    tparams = tied.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    emb = tparams["embed_tokens"]["embedding"]
    ttree = quantize_params(tparams, quantize_embed=False)
    assert ttree["embed_tokens"]["embedding"] is emb


def test_random_int8_init_in_slices_equals_whole_tree_init(monkeypatch):
    """Random-weight int8 boot never holds the whole bf16 twin (15.2 GB for
    a 7B model — RESOURCE_EXHAUSTED on a 16 GB v5e): it initialises and
    quantises a slice of the tree at a time.  Every value must be the one
    the whole-tree init produces, whatever the slice size."""
    from tpustack.models.llm_generate import Generator

    cfg = dataclasses.replace(LlamaConfig.tiny(), quant="int8")
    twin = LlamaModel(dataclasses.replace(cfg, quant=None), dtype=jnp.float32)
    whole = quantize_params(jax.jit(twin.init)(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"])
    leaves = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for chunk_bytes in (Generator.RANDOM_INIT_CHUNK_BYTES, 40_000, 1):
        monkeypatch.setattr(Generator, "RANDOM_INIT_CHUNK_BYTES", chunk_bytes)
        got = Generator._random_quantized_params(cfg, jnp.float32, 3)
        assert [p for p, _ in leaves(got)] == [p for p, _ in leaves(whole)]
        for (_, a), (_, b) in zip(leaves(got), leaves(whole)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_generator_end_to_end_int8():
    from tpustack.models.llm_generate import Generator, SampleConfig

    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64), quant="int8")
    gen = Generator(cfg, dtype=jnp.float32, seed=0)
    out, stats = gen.generate([5, 6, 7], max_new_tokens=8,
                              sample=SampleConfig(greedy=True), seed=0)
    assert len(out) == 8 and all(0 <= t < cfg.vocab_size for t in out)
    # fused scan path agrees token-for-token under greedy
    out_f, _ = gen.generate_fused([5, 6, 7], max_new_tokens=8,
                                  sample=SampleConfig(greedy=True), seed=0,
                                  chunk=4)
    assert out_f == out


@pytest.mark.slow
def test_umt5_quantisation_close_to_float():
    """The Wan text tower quantises with the same machinery: tiny UMT5
    int8 output stays close to the float encoder's."""
    from tpustack.models.wan.config import UMT5Config
    from tpustack.models.wan.umt5 import UMT5Encoder

    cfg = UMT5Config(vocab_size=512, dim=32, ffn_dim=64, num_heads=2,
                     head_dim=16, num_layers=2, max_length=16)
    enc = UMT5Encoder(cfg, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 512)
    params = enc.init(jax.random.PRNGKey(1), ids)["params"]
    ref = enc.apply({"params": params}, ids)

    from tpustack.ops.quant import UMT5_QUANTIZABLE

    qcfg = dataclasses.replace(cfg, quant="int8")
    qenc = UMT5Encoder(qcfg, dtype=jnp.float32)
    qparams = quantize_params(params, names=UMT5_QUANTIZABLE,
                              embed_keys=frozenset({"embed"}))
    # quantised tree must drop straight into the quantised module
    tmpl = jax.eval_shape(
        lambda: qenc.init(jax.random.PRNGKey(1), ids))["params"]
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_flatten_with_path(qparams)[0],
            jax.tree_util.tree_flatten_with_path(tmpl)[0]):
        assert pa == pb and la.shape == lb.shape and la.dtype == lb.dtype
    got = qenc.apply({"params": qparams}, ids)

    a = np.asarray(ref, np.float32).ravel()
    b = np.asarray(got, np.float32).ravel()
    cos = float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9))
    assert cos > 0.99, f"UMT5 int8 diverged: cosine {cos}"


def test_qkv_bias_carried_through_quantisation():
    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=32), qkv_bias=True)
    model = LlamaModel(cfg, dtype=jnp.float32)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    bias = params["layers_0"]["self_attn"]["q_proj"]["bias"]
    qtree = quantize_params(params)
    q = qtree["layers_0"]["self_attn"]["q_proj"]
    assert set(q.keys()) == {"kernel", "scale", "bias"}
    assert q["bias"] is bias

    qcfg = dataclasses.replace(cfg, quant="int8")
    qmodel = LlamaModel(qcfg, dtype=jnp.float32)
    logits, _ = qmodel.apply({"params": qtree}, tokens)
    assert np.isfinite(np.asarray(logits)).all()
