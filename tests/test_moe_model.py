"""Layer kinds on the served path (ISSUE 28): window and full attention,
rope and none, a dense then routed-expert feed-forward layers, q/k norm,
norms after the sublayers — the program against the plain reference
(``benchmark/reference/exaone_moe.py``), the expert-parallel share against
the uncut layer, the grouped product against a per-expert loop, the window
in every kernel against a masked softmax.  Tiny sizes, CPU, seeded weights.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import weights_exaone as WX  # noqa: E402
from benchmark.reference import exaone_moe as REF  # noqa: E402
from benchmark.runners import llm_http_exaone as RUN  # noqa: E402
from tpustack.models.llama import LlamaModel, init_kv_pool  # noqa: E402
from tpustack.models.llm_generate import Generator  # noqa: E402
from tpustack.ops.attention import dot_product_attention  # noqa: E402
from tpustack.ops.pallas.flash_attention import (  # noqa: E402
    flash_attention, paged_attention_partial)
from tpustack.ops.pallas.moe_gmm import moe_gmm  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore")

WINDOW, BLOCK = 8, 8


def tiny_cfg(**over) -> dict:
    """A configuration dict of the benchmark's shape at test size: all four
    layer kinds (window+dense, window+sparse, window+sparse, full+sparse),
    8 routed experts of which 4 are held here, 2 a token."""
    cfg = {
        "name": "tiny-exaone", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "vocab_size": 512, "num_hidden_layers": 4, "num_experts": 4,
        "router_experts": 8, "num_experts_per_tok": 2,
        "expert_share": {"first": 0}, "routed_scaling_factor": 2.5,
        "rope_parameters": {"rope_theta": 1e6}, "rms_norm_eps": 1e-5,
        "sliding_windows": [WINDOW, WINDOW, WINDOW, 0],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
        "weights": "int8", "kv": None, "ctx": 64, "slots": 2,
    }
    return dict(cfg, **over)


# ------------------------------------------------- (1) program vs reference
def served_logits(cfg, weights, ids, n_prompt, chunk=4):
    """The program's logits for positions ``n_prompt - 1 ...`` of ``ids``,
    teacher-forced through the served path's own pieces: a bucketed prefill
    into row caches, the paged splice, then decode steps that read the pool
    in place (the paged kernel) beside the chunk buffers, each chunk's K/V
    scattered back through the block table."""
    from tpustack.models.llama import init_chunk_bufs, init_kv_caches

    lc = RUN.model_spec(cfg)
    gen = Generator(lc, params=RUN.program_params(weights), dtype=jnp.float32)
    apply = lambda *a: gen.model.apply({"params": gen.params}, *a)
    n_blocks = lc.max_seq // BLOCK
    pool = init_kv_pool(lc, n_blocks + 1, BLOCK, dtype=jnp.float32)
    bt = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None, :]
    bucket = 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n_prompt] = ids[:n_prompt]
    caches = init_kv_caches(lc, 1, dtype=jnp.float32)
    positions = jnp.arange(bucket)[None, :]
    logits, caches = apply(jnp.asarray(toks), positions, caches, 0, None,
                           jnp.asarray([n_prompt - 1]))
    out = [np.asarray(logits[0, 0])]
    pool = gen._insert_rows_paged(pool, bt, caches, jnp.int32(0), bucket,
                                  jnp.asarray([n_prompt], jnp.int32))
    cur = n_prompt
    keymap = {"k": "ck", "v": "cv", "k_scale": "ck_scale",
              "v_scale": "cv_scale"}
    while cur < len(ids):
        cur0 = jnp.asarray([cur], jnp.int32)
        bufs = init_chunk_bufs(lc, 1, chunk, dtype=jnp.float32)
        steps = min(chunk, len(ids) - cur)
        for t in range(steps):
            merged = [dict(v, **bf) for v, bf in zip(
                gen._pool_views(pool, bt), bufs)]
            logits, merged = apply(
                jnp.asarray([[ids[cur + t]]], jnp.int32),
                jnp.asarray([[cur + t]], jnp.int32), merged,
                (cur0, jnp.int32(t)), None)
            bufs = [{k: d[k] for k in bf} for d, bf in zip(merged, bufs)]
            out.append(np.asarray(logits[0, 0]))
        pos = cur0[:, None] + jnp.arange(chunk)[None, :]
        pool = gen._pool_scatter_body(pool, bt, bufs, keymap, cur0,
                                      pos < cur + steps)
        cur += steps
    return np.stack(out)


@pytest.mark.parametrize("kv,tol", [(None, 1e-4), ("int8", 0.25)],
                         ids=["kv_float", "kv_int8"])
def test_paged_prefill_then_decode_equals_the_reference(kv, tol):
    """Prefill 11 tokens (past one window and one pool block), decode 17
    more across two more block and window boundaries: every logit row
    equals the reference's full forward.  The tolerance: float32
    summation order reads 6e-6 to 7e-6 over three seeds; an int8 pool
    (K/V held to 1/254 of their largest element) 0.066 to 0.083; the same
    reference with int4 weights 2.4 to 2.8, outside three times either."""
    cfg = tiny_cfg(kv=kv)
    weights = WX.Weights(cfg, 7)
    rng = np.random.default_rng(3)
    ids = rng.integers(3, 512, size=28).tolist()
    n_prompt = 11
    got = served_logits(cfg, weights, ids, n_prompt)
    tokens = np.asarray([ids], np.int32)
    at = [np.arange(n_prompt - 1, len(ids))]
    want = np.asarray(REF.logits_at(cfg, weights, tokens, at)[0])
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) < tol
    control = np.asarray(REF.logits_at(cfg, weights, tokens, at,
                                       lower="int4")[0])
    assert np.max(np.abs(control - want)) > 3 * tol


def test_program_routes_as_the_reference_does():
    """The counters the layer sows are the reference's own routing: pairs
    on held experts, held experts touched, the fullest one's tokens."""
    cfg = tiny_cfg()
    weights = WX.Weights(cfg, 11)
    lc = RUN.model_spec(cfg)
    model = LlamaModel(lc, dtype=jnp.float32)
    ids = np.random.default_rng(5).integers(3, 512, size=(1, 24))
    _, extra = model.apply({"params": RUN.program_params(weights)},
                           jnp.asarray(ids), mutable=["moe_stats"])
    got = np.stack([np.asarray(extra["moe_stats"][f"layers_{i}"]["mlp"]
                               ["counts"][0]) for i in (1, 2, 3)])
    # the reference's hidden states layer by layer, and its routing there
    m = WX.dims(cfg)
    run_block, prep, _ = REF._programs(REF._static(m))
    x = jnp.take(REF.to_f32(weights.embed(), per_row=True),
                 jnp.asarray(ids[0]), axis=0)
    want = []
    for i in range(4):
        w = prep(weights.layer(i), None)
        if m["sparse"][i]:
            with jax.default_matmul_precision("highest"):
                a = REF.attention(x, w, h=m["h"], kvh=m["kvh"], hd=m["hd"],
                                  theta=m["theta"], eps=m["eps"],
                                  window=m["windows"][i])
                h = x + REF.rmsnorm(a, w["ln1"], m["eps"])
                chosen, _ = REF.routing(h, w, top_k=m["top_k"],
                                        routed_scale=m["routed_scale"])
            counts = np.bincount(np.asarray(chosen).ravel(), minlength=8)[:4]
            want.append([counts.sum(), (counts > 0).sum(), counts.max()])
        x = run_block(x, w, m["windows"][i], m["sparse"][i])
    assert got.tolist() == np.asarray(want).tolist()


# ------------------------------------------------------- (2) the share test
def test_all_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: the routed parts that the 8 shares of
    the deployment compute (2 of 16 experts each), with the shared expert —
    which every chip computes alike — counted once, add up to the uncut
    layer.  Program per share, reference uncut."""
    from tpustack.models.moe import MoEFeedForward

    shares, held = 8, 2
    cfg = tiny_cfg(num_experts=held, router_experts=shares * held,
                   num_experts_per_tok=4, weights=None)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    f32 = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)
    m = WX.dims(cfg)
    total, stacks = 0.0, {n: [] for n in WX.EXPERT}
    for r in range(shares):
        weights = WX.Weights(cfg, 13)
        w = f32(weights.layer(1, first=r * held))
        lc = RUN.model_spec(dict(cfg, expert_share={"first": r * held}))
        params = RUN.program_params(weights)["layers_1"]["mlp"]
        for k, n in zip(("gate_proj", "up_proj", "down_proj"), WX.EXPERT):
            params[k] = {"kernel": w[n]}
        out = MoEFeedForward(lc, jnp.float32).apply({"params": params}, x)
        with jax.default_matmul_precision("highest"):
            shared = REF.swiglu(x[0], w["s_gate"], w["s_up"], w["s_down"])
        total = total + (out[0] - shared)
        for n in WX.EXPERT:
            stacks[n].append(w[n])
    whole = dict(w, **{n: jnp.concatenate(v) for n, v in stacks.items()})
    with jax.default_matmul_precision("highest"):
        want = REF.sparse_ff(x[0], whole, first=0, top_k=m["top_k"],
                             routed_scale=m["routed_scale"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=2e-4)


# ------------------------------------------------------------ (3) moe_gmm
def _grouped(counts, tm):
    """Row tiles for per-expert ``counts``: each group padded to ``tm``."""
    tiles = [-(-c // tm) for c in counts]
    tile_expert = [e for e, n in enumerate(tiles) for _ in range(n)]
    return tile_expert, sum(tiles)


@pytest.mark.parametrize("counts", [(3, 0, 17, 5), (0, 0, 40, 0),
                                    (0, 0, 0, 0), (16, 16, 16, 16)],
                         ids=["ragged_one_empty", "one_gets_all", "none",
                              "even"])
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_moe_gmm_equals_a_per_expert_loop(counts, int8):
    tm, k, n, e = 16, 64, 32, len(counts)
    tile_expert, n_active = _grouped(counts, tm)
    m = (n_active + 3) * tm                     # dead tiles behind the live
    te = np.asarray(tile_expert + [e - 1] * (m // tm - n_active), np.int32)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((e, k, n)).astype(np.float32) / 8
    scale = None
    if int8:
        scale = np.abs(w).max(axis=1) / 127
        w = np.round(w / scale[:, None, :]).astype(np.int8)
    got = np.asarray(moe_gmm(jnp.asarray(x), jnp.asarray(w),
                             None if scale is None else jnp.asarray(scale),
                             jnp.asarray(te), n_active, tm=tm))
    for t in range(n_active):
        rows = slice(t * tm, (t + 1) * tm)
        wf = w[te[t]].astype(np.float32)
        if int8:
            wf = wf * scale[te[t]]
        np.testing.assert_allclose(got[rows], x[rows] @ wf, atol=1e-3)


# ----------------------------------------------------------- (4) the window
def _masked_softmax_attention(q, k, v, window, q_pos, kv_len=None):
    """XLA, float32: key ``j`` visible to the query at position ``p`` iff
    ``0 <= p - j < window`` (and ``j < kv_len``)."""
    sk = k.shape[1]
    j = jnp.arange(sk)[None, None, :]
    p = q_pos[:, :, None]
    mask = (j <= p) & (j > p - window)
    if kv_len is not None:
        mask = mask & (j < kv_len[:, None, None])
    return dot_product_attention(q, k, v, mask=mask[:, None])


@pytest.mark.parametrize("streaming", [False, True], ids=["panel", "kstream"])
@pytest.mark.parametrize("s", [96, 128, 400], ids=["below", "at", "above"])
def test_flash_window_equals_masked_softmax(s, streaming, monkeypatch):
    """Prefill kernels at lengths below, at and above the window (128):
    the panel kernel (which above it reads a slice of the panel) and the
    k-streaming one (which skips blocks wholly behind the band)."""
    b, h, hkv, d, window = 1, 4, 2, 32, 128
    kq, kk, kvk = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kvk, (b, s, hkv, d), jnp.float32)
    kw = dict(q_offset=0, kv_len=s, block_k=128) if streaming else {}
    got = flash_attention(q, k, v, causal=True, window=window, block_q=128,
                          **kw)
    want = _masked_softmax_attention(
        q, k, v, window, jnp.arange(s)[None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and the XLA path's own window argument says the same
    xla = dot_product_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("seg", [1, 3], ids=["decode", "verify"])
@pytest.mark.parametrize("ctx", [5, 8, 9, 41, 64],
                         ids=["below", "at", "one_over", "blocks_behind",
                              "full_table"])
def test_paged_window_starts_at_the_first_visible_block(ctx, seg):
    """The paged kernel on a window layer: equal to the masked softmax over
    the pool prefix, and it starts at the block that holds the oldest
    visible key — the blocks wholly behind the window are poisoned with NaN
    (a float pool: a fetched NaN would reach the sum through 0 * NaN)."""
    b, h, hkv, d, blk, nb = 2, 4, 2, 16, 8, 8
    rng = np.random.default_rng(ctx)
    pool_k = rng.standard_normal((b * nb + 1, blk, hkv, d)).astype(np.float32)
    pool_v = rng.standard_normal((b * nb + 1, blk, hkv, d)).astype(np.float32)
    bt = 1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    lens = np.asarray([ctx, max(1, ctx - 3)], np.int32)
    q_pos = lens + 2                       # a chunk step past the frontier
    q = jnp.asarray(rng.standard_normal((b, seg, h, d)), jnp.float32)
    dense_k = pool_k[bt].reshape(b, nb * blk, hkv, d)
    dense_v = pool_v[bt].reshape(b, nb * blk, hkv, d)
    pos = q_pos[:, None] + np.arange(seg)[None, :]
    want_acc, want_m, want_l = None, None, None
    from tpustack.ops.attention import dot_product_attention_partial

    j = np.arange(nb * blk)[None, None, :]
    mask = (j < lens[:, None, None]) & (j > pos[:, :, None] - WINDOW)
    want = dot_product_attention_partial(
        q, jnp.asarray(dense_k), jnp.asarray(dense_v), mask=jnp.asarray(mask))
    for row in range(b):
        first = max(0, int(q_pos[row]) - WINDOW + 1) // blk
        pool_k[bt[row, :first]] = np.nan
        pool_v[bt[row, :first]] = np.nan
    # (the kernel takes the pool as it rests: heads folded into lanes)
    got = paged_attention_partial(
        q, jnp.asarray(pool_k).reshape(-1, blk, hkv * d),
        jnp.asarray(pool_v).reshape(-1, blk, hkv * d), jnp.asarray(bt),
        jnp.asarray(lens), window=WINDOW, q_pos=jnp.asarray(q_pos))
    for g, w in zip(got, want):
        # rows whose window has left the pool: m = NEG_INF, l = acc = 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)
