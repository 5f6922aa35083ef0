"""The paged KV pool's layout at rest (PR 29) against the dense cache.

The pool rests as its hot consumers take it (``llama.init_kv_pool``): K/V
``[n_blocks, block, kvh*hd]``, int8 scales ``[n_blocks, kvh*block]``.  The
dense slot cache stays ``[B, max_seq, kvh, hd]`` / ``[B, max_seq, kvh]``.
Every traced writer and reader of the pool has to spell, through the block
tables, exactly what the dense twin holds — bit for bit, because greedy
outputs are byte-identical paged against dense — at every head count, with
a block under the sublane tile, for int8 and float pools.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.llama import (LlamaConfig, init_chunk_bufs,
                                   init_kv_caches, init_kv_pool, pool_lines,
                                   pool_pages)
from tpustack.models.llm_generate import Generator
from tpustack.ops.attention import dot_product_attention_partial
from tpustack.ops.pallas.flash_attention import (paged_attention_partial,
                                                 paged_scale_rows)

HD = 16
KEYMAP = {"k": "ck", "v": "cv", "k_scale": "ck_scale", "v_scale": "cv_scale"}


def _gen(kvh, kv, max_seq=64, layers=2):
    """A generator of the pool's shape alone: its traced bodies read the
    configuration, never the (absent) weights."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(max_seq=max_seq), n_layers=layers, n_heads=2 * kvh,
        n_kv_heads=kvh, head_size=HD, dim=2 * kvh * HD,
        kv_quant="int8" if kv == "int8" else None)
    return Generator(cfg, params={}, dtype=jnp.float32)


def _random_like(rng, tree):
    """Random values in every leaf, in its type (int8 over the full range,
    scales positive)."""
    def fill(x):
        if x.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, x.shape), jnp.int8)
        return jnp.asarray(rng.random(x.shape) + 0.01, x.dtype)
    return jax.tree.map(fill, tree)


def _tables(rng, rows, nb, n_blocks):
    """Shuffled, disjoint block tables over blocks 1..n_blocks-1."""
    perm = rng.permutation(np.arange(1, n_blocks))[:rows * nb]
    return jnp.asarray(perm.reshape(rows, nb), jnp.int32)


def _assert_trees_equal(got, want):
    for lg, lw in zip(got, want):
        assert lg.keys() == lw.keys()
        for k in lg:
            np.testing.assert_array_equal(np.asarray(lg[k]),
                                          np.asarray(lw[k]), err_msg=k)


CASES = [(kvh, blk, kv) for kvh in (1, 2, 4, 8) for blk in (8, 32)
         for kv in ("int8", "float")]
IDS = [f"kvh{k}-blk{b}-{kv}" for k, b, kv in CASES]


@pytest.mark.parametrize("kvh,blk,kv", CASES, ids=IDS)
def test_admission_splice_spells_the_dense_cache(kvh, blk, kv):
    """``_insert_span_body`` (the admission's write, from position 0 and
    from a mid-block base) then ``_pool_gather_body``: the rows' lines,
    bit for bit up to each row's limit, untouched past it."""
    rng = np.random.default_rng(kvh * 100 + blk)
    gen = _gen(kvh, kv)
    cfg, rows = gen.cfg, 3
    nb = cfg.max_seq // blk
    n_blocks = rows * nb + 1
    caches = _random_like(rng, init_kv_caches(cfg, rows, dtype=jnp.float32))
    pool = init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32)
    bt = _tables(rng, rows, nb, n_blocks)
    limits = jnp.asarray([cfg.max_seq, blk + 3, 2 * blk], jnp.int32)
    bucket = 32
    pool = jax.jit(gen._insert_span_body, static_argnums=(4,))(
        pool, bt, caches, jnp.int32(0), bucket, limits)
    # a second span from a base inside a block, as a warm suffix writes it
    base = blk + 5
    pool = jax.jit(gen._insert_span_body, static_argnums=(4,))(
        pool, bt, caches, jnp.int32(base), 16, limits)
    got = jax.jit(gen._pool_gather_body)(pool, bt)
    written = np.zeros((rows, cfg.max_seq), bool)
    pos = np.arange(cfg.max_seq)[None, :]
    lim = np.asarray(limits)[:, None]
    written |= (pos < bucket) & (pos < lim)
    written |= (pos >= base) & (pos < base + 16) & (pos < lim)
    for lg, lc in zip(got, caches):
        for k in lg:
            w = written.reshape(written.shape + (1,) * (lg[k].ndim - 2))
            np.testing.assert_array_equal(
                np.asarray(lg[k]), np.where(w, np.asarray(lc[k]), 0),
                err_msg=k)
    # the reserved block 0 is never written
    for layer in pool:
        for k, v in layer.items():
            assert not np.asarray(v[0]).any(), k


@pytest.mark.parametrize("kvh,blk,kv", CASES, ids=IDS)
def test_chunk_flush_matches_the_dense_flush(kvh, blk, kv):
    """``_pool_scatter_body`` (a decode chunk's and a verify's write:
    per-row frontiers anywhere in a block, a row cut short, a parked row)
    against ``_flush_chunk_bufs`` on the dense twin."""
    rng = np.random.default_rng(kvh * 100 + blk + 1)
    gen = _gen(kvh, kv)
    cfg, rows, steps = gen.cfg, 4, 6
    nb = cfg.max_seq // blk
    n_blocks = rows * nb + 1
    caches = _random_like(rng, init_kv_caches(cfg, rows, dtype=jnp.float32))
    bt = _tables(rng, rows, nb, n_blocks)
    pool = jax.jit(gen._insert_span_body, static_argnums=(4,))(
        init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32), bt, caches,
        jnp.int32(0), cfg.max_seq, jnp.full((rows,), cfg.max_seq, jnp.int32))
    bufs = _random_like(rng, init_chunk_bufs(cfg, rows, steps,
                                             dtype=jnp.float32))
    # a block's last slot, across a boundary, cut after 2 of 6, parked
    cur = jnp.asarray([blk - 1, 2 * blk - 3, 5, 17], jnp.int32)
    cur_end = cur + jnp.asarray([steps, steps, 2, 0], jnp.int32)
    valid = cur[:, None] + jnp.arange(steps)[None, :] < cur_end[:, None]
    # tables, frontiers and validity ride as ARGUMENTS, as the engine's do:
    # closed over as constants, XLA for the TPU folds the page ids and then
    # drops the whole scatter (nothing is written, at any shape: my chip
    # run, PR 29); with traced ids the same cases are equal on the chip
    pool = jax.jit(lambda p, b, t, c, v: gen._pool_scatter_body(
        p, t, b, KEYMAP, c, v))(pool, bufs, bt, cur, valid)
    want = jax.jit(gen._flush_chunk_bufs, static_argnums=(4,))(
        caches, bufs, cur, cur_end, steps)
    _assert_trees_equal(jax.jit(gen._pool_gather_body)(pool, bt), want)


@pytest.mark.parametrize("kv", ["int8", "float"])
def test_host_tier_park_then_restore_through_the_layout(kv):
    """A block parked by the host tier (``snapshot_block``: the block's
    page of every pool tensor, whatever its layout) and restored into
    OTHER block ids by ``_restore_blocks_paged`` spells the same tokens."""
    from tpustack.serving.kv_host_tier import HostKVTier, block_nbytes
    from tpustack.serving.kv_pool import KVBlockPool

    rng = np.random.default_rng(5)
    gen = _gen(4, kv)
    cfg, blk = gen.cfg, 8
    n_blocks = 17
    pool = _random_like(rng, init_kv_pool(cfg, n_blocks, blk,
                                          dtype=jnp.float32))
    per_token = cfg.n_kv_heads * (HD * (1 if kv == "int8" else 4)
                                  + (4 if kv == "int8" else 0))
    assert block_nbytes(pool) == cfg.n_layers * 2 * blk * per_token
    tier = HostKVTier(1 << 20, KVBlockPool(n_blocks, blk),
                      arrays_fn=lambda: pool)
    parked = [tier.snapshot_block(b) for b in (3, 9)]
    stacked = [{k: jnp.asarray(np.stack([p[li][k] for p in parked]))
                for k in parked[0][li]} for li in range(cfg.n_layers)]
    fresh = init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32)
    fresh = gen._restore_blocks_paged(fresh, jnp.asarray([12, 4], jnp.int32),
                                      stacked)
    line = lambda p, ids: jax.jit(gen._pool_gather_body)(
        p, jnp.asarray([ids], jnp.int32))
    _assert_trees_equal(line(fresh, [12, 4]), line(pool, [3, 9]))


@pytest.mark.parametrize("kvh", [1, 2, 4, 8])
def test_pool_lines_and_pages_are_inverses(kvh):
    rng = np.random.default_rng(kvh)
    dense = {"k": rng.integers(-127, 128, (5, 8, kvh, HD)).astype(np.int8),
             "k_scale": rng.random((5, 8, kvh)).astype(np.float32)}
    for key, x in dense.items():
        pages = pool_pages(key, jnp.asarray(x))
        assert pages.shape == ((5, 8, kvh * HD) if key == "k"
                               else (5, kvh * 8))
        np.testing.assert_array_equal(
            np.asarray(pool_lines(key, pages, kvh)),
            x.reshape((40,) + x.shape[2:]))


@pytest.mark.parametrize("window", [None, 12], ids=["full", "window"])
@pytest.mark.parametrize("kv", ["int8", "float"])
def test_kernel_reads_what_the_writers_wrote(kv, window):
    """Written by the admission splice, read in place by the paged kernel
    (scale rows made once, as a decode chunk makes them): the same partial
    as plain attention over the dense cache the splice was fed — a full
    layer and a window layer."""
    rng = np.random.default_rng(11)
    gen = _gen(2, kv, layers=1)
    cfg, rows, blk = gen.cfg, 2, 8
    nb = cfg.max_seq // blk
    n_blocks = rows * nb + 1
    caches = init_kv_caches(cfg, rows, dtype=jnp.float32)
    caches = jax.tree.map(
        lambda x: (jnp.asarray(rng.integers(-127, 128, x.shape), jnp.int8)
                   if x.dtype == jnp.int8 else
                   jnp.asarray(rng.standard_normal(x.shape) * 0.02 + (
                       0.03 if x.ndim == 3 else 0.0), x.dtype)), caches)
    if kv == "float":
        caches = jax.tree.map(lambda x: x * 50, caches)
    bt = _tables(rng, rows, nb, n_blocks)
    lens = jnp.asarray([cfg.max_seq - 7, blk + 3], jnp.int32)
    pool = jax.jit(gen._insert_span_body, static_argnums=(4,))(
        init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32), bt, caches,
        jnp.int32(0), cfg.max_seq, lens)[0]
    q = jnp.asarray(rng.standard_normal((rows, 1, cfg.n_heads, HD)),
                    jnp.float32)
    extra = ({} if kv == "float" else {"scale_rows": (
        paged_scale_rows(pool["k_scale"], bt, pool["k"]),
        paged_scale_rows(pool["v_scale"], bt, pool["v"]))})
    if window:
        extra.update(window=window, q_pos=lens)
    got = paged_attention_partial(q, pool["k"], pool["v"], bt, lens, **extra)
    pos = jnp.arange(cfg.max_seq)[None, None, :]
    mask = pos < lens[:, None, None]
    if window:
        mask = mask & (pos > lens[:, None, None] - window)
    c = caches[0]
    want = dot_product_attention_partial(
        q, c["k"], c["v"], mask=mask, k_scale=c.get("k_scale"),
        v_scale=c.get("v_scale"))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("kv", ["int8", "float"])
def test_tp_shards_the_folded_axes_in_whole_heads(kv):
    """Under a tp mesh the pool's folded axes split on head boundaries:
    chip ``i`` of 2 holds heads ``[2i, 2i+2)`` of 4 — their lanes of every
    K/V page, their runs of every scale page — and the byte bill halves."""
    from tpustack.parallel import build_mesh
    from tpustack.parallel.sharding import tree_bytes, tree_per_shard_bytes

    mesh = build_mesh((1, 1, 2, 1), devices=jax.devices()[:2])
    gen = _gen(4, kv, layers=1)
    cfg, blk, n_blocks = gen.cfg, 8, 9
    rng = np.random.default_rng(2)
    dense = {"k": rng.standard_normal((n_blocks, blk, 4, HD)).astype(
        np.float32)}
    if kv == "int8":
        dense = {"k": rng.integers(-127, 128, (n_blocks, blk, 4, HD)).astype(
            np.int8), "k_scale": rng.random((n_blocks, blk, 4)).astype(
                np.float32)}
    pool = init_kv_pool(cfg, n_blocks, blk, dtype=jnp.float32, mesh=mesh)
    assert tree_per_shard_bytes(pool) * 2 == tree_bytes(pool)
    for key, x in dense.items():
        placed = jax.device_put(pool_pages(key, jnp.asarray(x)),
                                pool[0][key].sharding)
        for i, shard in enumerate(sorted(
                placed.addressable_shards, key=lambda s: s.device.id)):
            heads = x[:, :, 2 * i:2 * i + 2]
            np.testing.assert_array_equal(
                np.asarray(shard.data), np.asarray(pool_pages(
                    key, jnp.asarray(heads))), err_msg=f"{key} chip {i}")
