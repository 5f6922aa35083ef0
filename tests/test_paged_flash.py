"""Paged-flash decode attention: the Pallas kernel that copies the KV pool
blocks a row has straight out of the pool, through scalar-prefetched block
tables (no dense gather copy), and its fused
multi-query speculative verify, knob-gated as ``TPUSTACK_PAGED_FLASH``.

The acceptance bars this file carries:

- **Kernel correctness** (interpret mode): block-table indirection over a
  scrambled pool (reserved block 0 poisoned — its garbage must never
  leak), ragged per-row ``cur`` masking including zero-length rows, int8
  dequant-in-kernel against the XLA partial's scale discipline, GQA head
  mapping, and the multi-query verify (k = 0..4) merged with the
  in-segment-causal buffer partial against a one-pass dense reference.
- **Engine byte-identity**: paged-flash vs gather greedy outputs
  identical across plain x int8-KV x speculative x seeded-sampling, and
  across a QoS preemption park + ``_admit_prefix_paged`` resume.
- **Bisection**: ``TPUSTACK_PAGED_FLASH=0`` resolves to the gather body
  (subprocess-proven) with identical outputs to ``=1``.
"""

import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpustack.models.llama import LlamaConfig, pool_pages
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import (Generator, SampleConfig,
                                          resolve_paged_flash)
from tpustack.ops.attention import (dot_product_attention,
                                    dot_product_attention_partial,
                                    merge_attention_partials)
from tpustack.ops.pallas.flash_attention import (PAGED_COMPUTE_TOKENS,
                                                 paged_attention_partial,
                                                 paged_bytes_accounting,
                                                 paged_flash_attention,
                                                 paged_pages_per_step,
                                                 paged_scale_rows)
from tpustack.serving.kv_pool import PagedKVRuntime
from tpustack.serving.speculative import SpecConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GREEDY = SampleConfig(greedy=True)


@pytest.fixture(scope="module")
def gen():
    return Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)


def make_runtime(gen, capacity_blocks=32, block=8):
    return PagedKVRuntime.build(gen.cfg, 2, block=block,
                                pool_blocks=capacity_blocks,
                                dtype=jnp.float32)


# ------------------------------------------------------------ kernel units
def _pool_setup(rng, *, b=3, hkv=2, d=16, blk=8, nb=6, n_pool=14,
                poison_block0=False, int8=False):
    """A scrambled paged layout: per-row tables over a shuffled pool,
    ragged lengths (one mid-block, one zero), idle tail entries at the
    reserved block 0."""
    max_seq = blk * nb
    if int8:
        pool_k = rng.randint(-127, 128, (n_pool, blk, hkv, d)).astype(np.int8)
        pool_v = rng.randint(-127, 128, (n_pool, blk, hkv, d)).astype(np.int8)
    else:
        pool_k = rng.randn(n_pool, blk, hkv, d).astype(np.float32)
        pool_v = rng.randn(n_pool, blk, hkv, d).astype(np.float32)
    if poison_block0:
        # the reserved block: idle table entries point here — huge values
        # must never reach any output: idle entries are not read at all
        pool_k[0] = 127 if int8 else 1e4
        pool_v[0] = 127 if int8 else 1e4
    lens = np.zeros(b, np.int32)
    lens[0] = max_seq          # full row
    if b > 1:
        lens[1] = blk + 3      # ragged mid-block row
    # row 2 (if present) stays 0: fresh/parked slot, no valid key
    bt = np.zeros((b, nb), np.int32)
    perm = rng.permutation(np.arange(1, n_pool))
    pos = 0
    for i in range(b):
        valid = -(-int(lens[i]) // blk)
        bt[i, :valid] = perm[pos:pos + valid]
        pos += valid
    return (jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(bt),
            jnp.asarray(lens), max_seq)


def _gather_view(x, bt):
    b, nb = bt.shape
    g = jnp.take(x, bt.reshape(-1), axis=0)
    return g.reshape((b, nb * x.shape[1]) + x.shape[2:])


def _at_rest(x):
    """A pool tensor in the dense order these tests build and their
    reference reads (K/V ``[n, blk, hkv, d]``, scales ``[n, blk, hkv]``) →
    the layout the pool rests in and the kernel takes."""
    return pool_pages("k" if x.ndim == 4 else "k_scale", x)


def _scale_rows(ks, vs, bt, pk):
    """Scale planes at rest → the lane rows the kernel reads."""
    return (paged_scale_rows(ks, bt, pk), paged_scale_rows(vs, bt, pk))


def _len_mask(lens, max_seq, s):
    return jnp.broadcast_to(
        jnp.arange(max_seq)[None, None, :] < lens[:, None, None],
        (lens.shape[0], s, max_seq))


def test_kernel_block_table_indirection_and_block0():
    """The kernel's table-mapped reads equal the dense gather reference,
    with the reserved block 0 poisoned: idle-tail garbage never leaks
    through the table walk + length mask."""
    rng = np.random.RandomState(0)
    pk, pv, bt, lens, max_seq = _pool_setup(rng, poison_block0=True)
    b = lens.shape[0]
    h, d = 4, pk.shape[-1]
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
    ref = dot_product_attention_partial(
        q, _gather_view(pk, bt), _gather_view(pv, bt),
        mask=_len_mask(lens, max_seq, 1))
    got = paged_attention_partial(q, _at_rest(pk), _at_rest(pv), bt, lens)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_kernel_ragged_cur_and_zero_length():
    """Per-row `cur` masking: mid-block frontiers clip inside a block;
    a zero-length row returns the empty partial (m=-inf, l=0, acc=0) and
    zeros from the normalised wrapper."""
    rng = np.random.RandomState(1)
    pk, pv, bt, lens, max_seq = _pool_setup(rng)
    b, h, d = lens.shape[0], 4, pk.shape[-1]
    assert int(lens[2]) == 0 and int(lens[1]) % int(pk.shape[1])
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
    acc, m, l = paged_attention_partial(q, _at_rest(pk), _at_rest(pv), bt,
                                        lens)
    assert float(jnp.max(jnp.abs(acc[2]))) == 0.0
    assert float(jnp.max(l[2])) == 0.0
    assert float(jnp.max(m[2])) <= -1e29
    out = paged_flash_attention(q, _at_rest(pk), _at_rest(pv), bt, lens)
    assert float(jnp.max(jnp.abs(out[2]))) == 0.0
    ref = dot_product_attention_partial(
        q, _gather_view(pk, bt), _gather_view(pv, bt),
        mask=_len_mask(lens, max_seq, 1))
    refn = np.asarray(ref[0]) / np.maximum(np.asarray(ref[2])[..., None],
                                           1e-30)
    np.testing.assert_allclose(np.asarray(out)[:2], refn[:2],
                               rtol=1e-5, atol=1e-5)


def test_kernel_int8_dequant_in_kernel():
    """int8 pool blocks + per-vector scales: the kernel's in-VMEM dequant
    (k_scale on the scores, v_scale on the probs after the denominator)
    matches the XLA partial's exact scale discipline."""
    rng = np.random.RandomState(2)
    pk, pv, bt, lens, max_seq = _pool_setup(rng, int8=True,
                                            poison_block0=True)
    n_pool, blk, hkv, d = pk.shape
    ks = jnp.asarray(rng.rand(n_pool, blk, hkv).astype(np.float32)
                     * 0.02 + 1e-3)
    vs = jnp.asarray(rng.rand(n_pool, blk, hkv).astype(np.float32)
                     * 0.02 + 1e-3)
    b, h = lens.shape[0], 4
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
    ref = dot_product_attention_partial(
        q, _gather_view(pk, bt), _gather_view(pv, bt),
        mask=_len_mask(lens, max_seq, 1),
        k_scale=_gather_view(ks, bt), v_scale=_gather_view(vs, bt))
    got = paged_attention_partial(
        q, _at_rest(pk), _at_rest(pv), bt, lens,
        scale_rows=_scale_rows(_at_rest(ks), _at_rest(vs), bt, _at_rest(pk)))
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("h,hkv", [(8, 2), (6, 6), (4, 1)])
def test_kernel_gqa_head_mapping(h, hkv):
    """GQA: q head i reads kv head i // (H/Hkv) — checked against the
    repeat-expanded dense reference (incl. MQA hkv=1 and matched heads)."""
    rng = np.random.RandomState(3)
    pk, pv, bt, lens, max_seq = _pool_setup(rng, hkv=hkv)
    b, d = lens.shape[0], pk.shape[-1]
    q = jnp.asarray(rng.randn(b, 1, h, d).astype(np.float32))
    kd, vd = _gather_view(pk, bt), _gather_view(pv, bt)
    rep = h // hkv
    ref = dot_product_attention_partial(
        q, kd, vd, mask=_len_mask(lens, max_seq, 1))
    ref_exp = dot_product_attention_partial(
        q, jnp.repeat(kd, rep, axis=2), jnp.repeat(vd, rep, axis=2),
        mask=_len_mask(lens, max_seq, 1))
    got = paged_attention_partial(q, _at_rest(pk), _at_rest(pv), bt, lens)
    for g, r, re in zip(got, ref, ref_exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(re),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [0, 1, 2, 3, 4])
def test_kernel_multi_query_verify_causal(k):
    """The fused verify decomposition for draft length k: ONE kernel pass
    over the pool prefix (all k+1 query rows attend [0, cur)) merged with
    the in-segment-causal buffer partial equals a one-pass dense
    reference over {pool prefix} ∪ {segment} with the full verify mask —
    k=0 collapses to the plain decode step."""
    rng = np.random.RandomState(4 + k)
    pk, pv, bt, lens, max_seq = _pool_setup(rng)
    b, h, d = lens.shape[0], 4, pk.shape[-1]
    hkv = pk.shape[2]
    s = k + 1
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    seg_k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32))
    seg_v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32))

    part_pool = paged_attention_partial(q, _at_rest(pk), _at_rest(pv), bt,
                                        lens)
    seg_causal = jnp.broadcast_to(
        jnp.arange(s)[None, None, :] <= jnp.arange(s)[None, :, None],
        (b, s, s))
    part_seg = dot_product_attention_partial(q, seg_k, seg_v,
                                             mask=seg_causal)
    merged = merge_attention_partials(part_pool, part_seg, jnp.float32)

    k_all = jnp.concatenate([_gather_view(pk, bt), seg_k], axis=1)
    v_all = jnp.concatenate([_gather_view(pv, bt), seg_v], axis=1)
    mask = jnp.concatenate(
        [_len_mask(lens, max_seq, s), seg_causal], axis=2)[:, None]
    ref = dot_product_attention(q, k_all, v_all, mask=mask)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------- compute blocks of several pages
#
# The kernel walks a row in compute blocks of `paged_pages_per_step` pool
# blocks and copies only the pool blocks a row has.  These shapes make the
# derived compute block smaller than the table and not a divisor of it
# (two compute blocks of 16 pages, the second 12 short), so lengths can sit
# on either kind of edge; every table entry past a row's frontier points
# at the reserved block 0 or at a poisoned block, alternately.

def _walk_shape(int8):
    """(blk, nb, pages): 512-token compute blocks over a 640-token table."""
    blk, nb = 32, 20
    pages = paged_pages_per_step(blk, nb, 2, 16,
                                 jnp.int8 if int8 else jnp.float32)
    assert pages * blk == PAGED_COMPUTE_TOKENS and 1 < pages < nb
    assert nb % pages
    return blk, nb, pages


def _walk_case(seed, lens, *, int8, hkv=2, g=2, s=1, d=16, poison="nan"):
    """A call's inputs, with the pool as the kernel gets it (poisoned) and
    as the reference gets it (the poison zeroed: masked columns multiply
    by it there)."""
    rng = np.random.RandomState(seed)
    blk, nb, _ = _walk_shape(int8)
    b = len(lens)
    lens = np.asarray(lens, np.int32)
    n_valid = [-(-int(x) // blk) for x in lens]
    n_pool = sum(n_valid) + 2
    bad = n_pool - 1                            # block 0 and this one
    shape = (n_pool, blk, hkv, d)
    if int8:
        pool = {"k": rng.randint(-127, 128, shape).astype(np.int8),
                "v": rng.randint(-127, 128, shape).astype(np.int8)}
        scales = {"k_scale": (rng.rand(*shape[:3]) * 0.02 + 1e-3
                              ).astype(np.float32),
                  "v_scale": (rng.rand(*shape[:3]) * 0.02 + 1e-3
                              ).astype(np.float32)}
    else:
        pool = {"k": rng.randn(*shape).astype(np.float32),
                "v": rng.randn(*shape).astype(np.float32)}
        scales = {}
    clean = {k: v.copy() for k, v in {**pool, **scales}.items()}
    for idx in (0, bad):
        for k in clean:
            clean[k][idx] = 0
        for k in pool:
            pool[k][idx] = 127 if int8 else (np.nan if poison == "nan"
                                             else 1e30)
        for k in scales:
            scales[k][idx] = np.nan if poison == "nan" else 1e30
    # shuffled, non-contiguous tables; the idle tail alternates 0 / bad
    perm, pos = rng.permutation(np.arange(1, bad)), 0
    bt = np.where(np.arange(nb)[None, :] % 2, bad, 0).astype(np.int32)
    bt = np.broadcast_to(bt, (b, nb)).copy()
    for i, n in enumerate(n_valid):
        bt[i, :n] = perm[pos:pos + n]
        pos += n
    q = rng.randn(b, s, hkv * g, d).astype(np.float32)
    to = lambda t: {k: jnp.asarray(v) for k, v in t.items()}
    return (jnp.asarray(q), to({**pool, **scales}), to(clean),
            jnp.asarray(bt), jnp.asarray(lens))


@functools.partial(jax.jit, static_argnames=("kernel",))
def _walk_partial(q, pool, bt, lens, kernel):
    scales = {k: pool[k] for k in ("k_scale", "v_scale") if k in pool}
    if kernel:
        rest = {k: _at_rest(v) for k, v in pool.items()}
        return paged_attention_partial(
            q, rest["k"], rest["v"], bt, lens,
            scale_rows=_scale_rows(rest["k_scale"], rest["v_scale"], bt,
                                   rest["k"]) if scales else None)
    max_seq = bt.shape[1] * pool["k"].shape[1]
    return dot_product_attention_partial(
        q, _gather_view(pool["k"], bt), _gather_view(pool["v"], bt),
        mask=_len_mask(lens, max_seq, q.shape[1]),
        **{k: _gather_view(v, bt) for k, v in scales.items()})


def _assert_walk_parity(q, pool, clean, bt, lens):
    got = _walk_partial(q, pool, bt, lens, kernel=True)
    ref = _walk_partial(q, clean, bt, lens, kernel=False)
    for g, r in zip(got, ref):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)
    return got


#: a row's length, from (pool block, compute block, table) sizes
WALK_EDGES = {
    "zero": lambda blk, T, full: 0,
    "one": lambda blk, T, full: 1,
    "block_edge_minus_1": lambda blk, T, full: 3 * blk - 1,
    "block_edge": lambda blk, T, full: 3 * blk,
    "block_edge_plus_1": lambda blk, T, full: 3 * blk + 1,
    "compute_block_edge_minus_1": lambda blk, T, full: T - 1,
    "compute_block_edge": lambda blk, T, full: T,
    "compute_block_edge_plus_1": lambda blk, T, full: T + 1,
    "whole_table": lambda blk, T, full: full,
}


@pytest.mark.parametrize("int8", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("edge", sorted(WALK_EDGES))
def test_kernel_walk_length_edges(edge, int8):
    """Parity with the gathered view at every edge a length can sit on:
    none, one, either side of a pool block's end and of a compute block's
    end, the whole table — beside a second row that the first hands its
    scratch half to."""
    blk, nb, pages = _walk_shape(int8)
    n = WALK_EDGES[edge](blk, pages * blk, nb * blk)
    acc, m, l = _assert_walk_parity(
        *_walk_case(10, [n, blk + 3], int8=int8))
    if n == 0:
        assert float(jnp.max(jnp.abs(acc[0]))) == 0.0
        assert float(jnp.max(l[0])) == 0.0
        assert float(jnp.max(m[0])) <= -1e29


@pytest.mark.parametrize("int8", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify_k4"])
def test_kernel_walk_mixed_rows_dead_between_live(s, int8):
    """One call, eight rows: dead rows first, between and last; live rows
    of one, two and a partial second compute block, so a row's first
    copies are started by the row before it, or by itself after a dead
    one, into either half of the scratch."""
    blk, nb, pages = _walk_shape(int8)
    T = pages * blk
    lens = [0, T + 1, 0, 5, nb * blk, 0, 0, blk]
    _assert_walk_parity(*_walk_case(20 + s, lens, int8=int8, s=s))
    _assert_walk_parity(*_walk_case(30 + s, lens[::-1], int8=int8, s=s))


@pytest.mark.parametrize("int8", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("hkv", [1, 2, 4])
def test_kernel_walk_kv_heads(hkv, int8):
    """Hkv 1 (a tp shard's slab), 2 and 4 heads folded into the lanes."""
    blk, nb, pages = _walk_shape(int8)
    lens = [pages * blk + blk + 1, 0, 2 * blk]
    _assert_walk_parity(*_walk_case(40 + hkv, lens, int8=int8, hkv=hkv))


@pytest.mark.parametrize("int8", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("poison", ["nan", "huge"])
def test_kernel_walk_never_reads_past_the_frontier(poison, int8):
    """Entries past the frontier point at block 0 and at a poisoned block
    (NaN, or 1e30 / ±127 with 1e30 scales): neither is fetched, and what a
    partial compute block leaves stale in the scratch reaches nothing."""
    blk, nb, pages = _walk_shape(int8)
    lens = [nb * blk, 1, pages * blk - blk - 1, blk + 1]
    _assert_walk_parity(
        *_walk_case(50, lens, int8=int8, s=5, poison=poison))


def test_pages_per_step_follows_the_shapes():
    """The compute block is derived, not set: 512 tokens at the
    Deployment's int8 and bf16 pools, the whole table when that is
    shorter, one pool block when a block is under the pool dtype's sublane
    tile, fewer pages when K/V would not fit the scratch budget."""
    assert paged_pages_per_step(64, 64, 4, 128, jnp.int8) == 8
    assert paged_pages_per_step(64, 64, 2, 128, jnp.bfloat16) == 8
    assert paged_pages_per_step(64, 4, 4, 128, jnp.int8) == 4
    assert paged_pages_per_step(8, 16, 2, 16, jnp.float32) == 16
    assert paged_pages_per_step(8, 16, 2, 16, jnp.int8) == 1
    assert paged_pages_per_step(64, 64, 64, 128, jnp.float32) == 1


MIB = 1 << 20


@pytest.mark.parametrize("pool_bytes,vmem,claim", [
    (513 * 64 * 512, 128 * MIB, 112 * MIB),        # 7B int8, as served
    (513 * 64 * 512 * 2, 128 * MIB, 112 * MIB),    # 7B, float pool
    (1061 * 64 * 1024, 128 * MIB, None),   # K-EXAONE: never staged by XLA
    (257 * 64 * 512, 128 * MIB, None),     # fits the 16 MiB a claim leaves
    (513 * 64 * 512, 16 * MIB, None),      # a v4 core: nothing to claim
    (513 * 64 * 512, None, None),          # off the TPU
], ids=["7b_int8", "7b_float", "k_exaone", "half_pool", "v4", "no_tpu"])
def test_vmem_claim_only_where_it_pays(pool_bytes, vmem, claim):
    """The paged call claims all of the core's VMEM but the default 16 MiB
    exactly where XLA would stage its pool tensors there and the claim can
    stop it: one pool tensor does not fit what the claim leaves, two fit
    what it takes.  The core's size comes from the device."""
    from tpustack.ops.pallas.flash_attention import (core_vmem_bytes,
                                                     paged_vmem_claim)

    assert paged_vmem_claim(pool_bytes, vmem) == claim
    assert core_vmem_bytes() is None       # this process runs on the CPU


def test_bytes_accounting_inplace_strictly_fewer():
    """The shared gather-vs-in-place bytes model: in place must move
    strictly fewer bytes per step at every occupancy, and the idle tail
    costs nothing: only the blocks a row has are copied."""
    for valid in (1, 4, 8):
        acct = paged_bytes_accounting(
            n_valid_blocks=valid, blocks_per_seq=8, block=16, kvh=2,
            hd=16, esize=2, scale_bytes=0, n_steps=8)
        assert (acct["paged_flash_step_bytes"]
                < acct["gather_step_bytes"]), acct
    full = paged_bytes_accounting(n_valid_blocks=8, blocks_per_seq=8,
                                  block=16, kvh=2, hd=16, esize=2,
                                  scale_bytes=0, n_steps=8)
    one = paged_bytes_accounting(n_valid_blocks=1, blocks_per_seq=8,
                                 block=16, kvh=2, hd=16, esize=2,
                                 scale_bytes=0, n_steps=8)
    assert one["paged_flash_step_bytes"] * 8 == full["paged_flash_step_bytes"]


# -------------------------------------------------------- engine parity
def _run_fleet(gen, *, flash, spec=None, seeded=False, n=4):
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    eng = ContinuousEngine(gen, slots=2, chunk=4, paged=rt,
                           paged_flash=flash, spec=spec)
    res = {}
    sample = (SampleConfig(greedy=False, temperature=0.9, top_k=8)
              if seeded else GREEDY)
    reqs = [SlotRequest(ids=[3 + i, 7, 11, 13 + i, 7, 11], max_new=12,
                        sample=sample, seed=42 + i if seeded else None,
                        on_done=lambda t, s, i=i: res.__setitem__(i, t))
            for i in range(n)]
    stats = eng.run(lambda: reqs.pop(0) if reqs else None)
    assert rt.pool.n_free == free0  # leak-free either body
    return res, stats


@pytest.mark.parametrize("kvq", [None, "int8"])
@pytest.mark.parametrize("mode", ["plain", "spec", "seeded"])
def test_engine_byte_identity_flash_vs_gather(kvq, mode):
    """ACCEPTANCE: greedy (and per-slot-seeded sampled) outputs are
    byte-identical paged-flash vs gather across plain decode,
    speculative verify, and int8 KV — the same traced scan/verify body
    reads the pool through the kernel instead of the gather copy."""
    import dataclasses

    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=64), kv_quant=kvq)
    g = Generator(cfg, dtype=jnp.float32, seed=3)
    kw = {"spec": SpecConfig(tokens=3) if mode == "spec" else None,
          "seeded": mode == "seeded"}
    res_g, st_g = _run_fleet(g, flash=False, **kw)
    res_f, st_f = _run_fleet(g, flash=True, **kw)
    assert res_g == res_f
    assert st_g["decode_kernel"] == "gather"
    assert st_f["decode_kernel"] == "paged_flash"
    # the copy-counter contract the perf gate pins: a flash engine never
    # dispatches the gather body (and vice versa)
    assert st_f["kernel_gather_dispatches"] == 0
    assert st_f["kernel_paged_flash_dispatches"] > 0
    assert st_g["kernel_paged_flash_dispatches"] == 0
    assert st_g["kernel_gather_dispatches"] > 0


def test_engine_preempt_resume_parity_flash(gen):
    """A QoS preemption park + `_admit_prefix_paged` warm-start resume
    under the paged-flash kernel still returns byte-identical greedy
    output vs the uninterrupted solo run (the warm start re-reads the
    retained blocks through the same in-place path)."""
    pb, nb = [5, 6, 7, 8], 14
    pi, ni = [9, 10, 11], 6
    solo_b = gen.generate_fused(pb, max_new_tokens=nb, sample=GREEDY,
                                stop_tokens=(), chunk=4)[0]
    solo_i = gen.generate_fused(pi, max_new_tokens=ni, sample=GREEDY,
                                stop_tokens=(), chunk=4)[0]
    rt = make_runtime(gen)
    free0 = rt.pool.n_free
    results = {}
    trigger = {"armed": False}
    state = {"fed_b": False, "fed_i": False}

    def on_b_tokens(toks):
        got = results.setdefault("b_tokens", [])
        got.extend(toks)
        if len(got) >= 2:
            trigger["armed"] = True

    breq = SlotRequest(ids=pb, max_new=nb, sample=GREEDY,
                       on_tokens=on_b_tokens,
                       on_done=lambda t, s: results.__setitem__("b", (t, s)),
                       tenant="bulk", priority="batch")
    ireq = SlotRequest(ids=pi, max_new=ni, sample=GREEDY,
                       on_done=lambda t, s: results.__setitem__("i", (t, s)),
                       tenant="alice", priority="interactive")

    def feed():
        if not state["fed_b"]:
            state["fed_b"] = True
            return breq
        if trigger["armed"] and not state["fed_i"]:
            state["fed_i"] = True
            return ireq
        return None

    engine = ContinuousEngine(
        gen, slots=1, chunk=4, stop_tokens=(), paged=rt, paged_flash=True,
        preempt_hint=lambda: trigger["armed"] and not state["fed_i"])
    stats = engine.run(feed)
    assert stats["preempted"] == 1
    assert results["i"][0] == solo_i
    assert results["b"][0] == solo_b
    assert results["b_tokens"] == solo_b
    assert rt.pool.n_free == free0


def test_flight_records_carry_kernel_tag(gen):
    """Every paged wave's flight record names the decode body that
    produced it — /debug/flight shows which kernel a live engine runs."""
    from tpustack.obs.flight import FlightRecorder

    rec = FlightRecorder("t-paged-flash", capacity=64)
    rt = make_runtime(gen)
    eng = ContinuousEngine(gen, slots=2, chunk=4, paged=rt,
                           paged_flash=True, flight=rec)
    reqs = [SlotRequest(ids=[3, 7, 11], max_new=8, sample=GREEDY)]
    eng.run(lambda: reqs.pop(0) if reqs else None)
    waves = [r for r in rec.recent() if r.get("kind") == "wave"]
    assert waves and all(r.get("kernel") == "paged_flash" for r in waves)


# ----------------------------------------------------- knob + bisection
def test_resolve_paged_flash_values(monkeypatch):
    monkeypatch.delenv("TPUSTACK_PAGED_FLASH", raising=False)
    # auto: off on the CPU backend the suite runs under
    assert resolve_paged_flash() is False
    monkeypatch.setenv("TPUSTACK_PAGED_FLASH", "1")
    assert resolve_paged_flash() is True
    # forcing on wins even under a mesh (the auto heuristic only)
    assert resolve_paged_flash(mesh=object()) is True
    monkeypatch.setenv("TPUSTACK_PAGED_FLASH", "0")
    assert resolve_paged_flash() is False
    monkeypatch.setenv("TPUSTACK_PAGED_FLASH", "sideways")
    with pytest.raises(ValueError, match="TPUSTACK_PAGED_FLASH"):
        resolve_paged_flash()


_BISECT = r"""
import json, sys
import jax.numpy as jnp
from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig

gen = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
eng = ContinuousEngine(gen, slots=2, chunk=4)  # own pool, knob-resolved
res = {}
reqs = [SlotRequest(ids=[3 + i, 7, 11, 13 + i], max_new=10,
                    sample=SampleConfig(greedy=True),
                    on_done=lambda t, s, i=i: res.__setitem__(i, t))
        for i in range(3)]
stats = eng.run(lambda: reqs.pop(0) if reqs else None)
print(json.dumps({"out": [res[i] for i in sorted(res)],
                  "kernel": stats["decode_kernel"]}))
"""


@pytest.mark.slow
def test_paged_flash_env_bisection_subprocess():
    """ACCEPTANCE: TPUSTACK_PAGED_FLASH=0 resolves a default-constructed
    paged engine onto the gather body and =1 onto the kernel — with
    byte-identical greedy outputs, subprocess-proven (fresh interpreter,
    only the env differs)."""
    outs = {}
    for flag in ("0", "1"):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   TPUSTACK_PAGED_FLASH=flag, TPUSTACK_SANITIZE="0")
        proc = subprocess.run([sys.executable, "-c", _BISECT], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-800:]
        outs[flag] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert outs["0"]["kernel"] == "gather"
    assert outs["1"]["kernel"] == "paged_flash"
    assert outs["0"]["out"] == outs["1"]["out"]


def test_bench_flash_paged_smoke():
    """The gather-vs-in-place microbench (interpret mode): outputs agree
    and the in-place path moves strictly fewer bytes — exit 0 is the
    assertion (tier-1 shells this the way the paged bench smoke is)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_flash.py"),
         "--paged", "--tiny"], env=env, capture_output=True, text=True,
        timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    art = json.loads(proc.stdout.strip().splitlines()[-1])
    assert art["outputs_allclose"] is True
    assert art["inplace_moves_fewer_bytes"] is True
    assert art["interpret"] is True


@pytest.mark.slow
def test_bench_llm_paged_flash_smoke():
    """bench_llm --paged --paged-flash --tiny: kernel tag + per-step KV
    bytes in the roofline block, outputs identical, and the signature's
    gather copy counter at ZERO (what the perf-gate scenario commits)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPUSTACK_SANITIZE="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_llm.py"),
         "--tiny", "--paged", "--paged-flash", "--requests", "4"],
        env=env, capture_output=True, text=True, timeout=590, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    art = json.loads(proc.stdout.strip().splitlines()[-1])
    assert art["kernel"] == "paged_flash"
    assert art["outputs_identical"] is True
    rl = art["roofline"]["per_slot_layer_step_bytes"]
    assert rl["paged_flash_step_bytes"] < rl["gather_step_bytes"]
    assert art["signature"]["kernel.gather_dispatches"] == 0
    assert art["signature"]["kernel.paged_flash_dispatches"] > 0
