"""A cold admission whose bucket is larger than ``Generator.ADMIT_CHUNK`` walks
its bucket in chunks inside the one fused admission program and stops at its
longest row's last chunk (PR 34).  Held here, on the CPU at test size, to the
single-shot admission of the same rows: the pool's pages, the first token's
logits, the activated slot state, the routed-expert counters, and what the
``prefill`` flight record says of it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.models.llama import LlamaConfig
from tpustack.models.llm_continuous import ContinuousEngine, SlotRequest
from tpustack.models.llm_generate import Generator, SampleConfig
from tpustack.obs.flight import FlightRecorder
from tpustack.serving.kv_pool import PagedKVRuntime

pytestmark = pytest.mark.filterwarnings("ignore")

GREEDY = SampleConfig(greedy=True)
C = 16            # the walk's chunk at test size
MAX_SEQ = 64      # a 40-token prompt: bucket 64, four chunks of capacity
BLOCK = 8
PRESETS = {"dense": LlamaConfig.tiny, "window_experts": LlamaConfig.tiny_moe}
#: prompt lengths a group: the longest ends in chunk 2 of 0..3 (so the last
#: chunk is never computed), the others in chunks 0 and 1
ROWS = {"one_row": (40,), "three_rows": (40, 5, 20)}


def _pair(preset: str, kv):
    """(single-shot, walking) generators of one model on the same weights."""
    cfg = dataclasses.replace(PRESETS[preset](max_seq=MAX_SEQ), kv_quant=kv)
    shot = Generator(cfg, dtype=jnp.float32, seed=3)
    walk = Generator(cfg, params=shot.params, dtype=jnp.float32)
    walk.ADMIT_CHUNK = C
    assert shot.ADMIT_CHUNK >= MAX_SEQ
    return shot, walk


def _prompts(lens):
    rng = np.random.default_rng(7)
    return [[int(t) for t in rng.integers(3, 500, n)] for n in lens]


def _padded(gen, prompts):
    """``(tokens [n, bucket], lengths [n])`` as the engine pads a group."""
    bucket = gen._bucket(max(map(len, prompts)))
    tokens = np.zeros((len(prompts), bucket), np.int32)
    for r, p in enumerate(prompts):
        tokens[r, :len(p)] = p
    return (jnp.asarray(tokens),
            jnp.asarray([len(p) for p in prompts], jnp.int32))


def _admit(gen, prompts, slots=4):
    """Run ``_admit_fused_paged`` by hand as the engine calls it: returns
    (dense lines of the admitted rows read back through their tables, the
    program's outputs)."""
    n = len(prompts)
    tokens, lengths = _padded(gen, prompts)
    rt = PagedKVRuntime.build(gen.cfg, slots, block=BLOCK, pool_blocks=64,
                              dtype=jnp.float32, prefix_cache=False)
    nb = gen.cfg.max_seq // BLOCK
    bt = np.zeros((n, nb), np.int32)
    for r, p in enumerate(prompts):
        ids = rt.pool.alloc_tokens(len(p) + 4)
        bt[r, :len(ids)] = ids
    row = lambda v, dt: jnp.full((n,), v, dt)
    st = lambda v, dt: jnp.full((slots,), v, dt)
    out = gen._admit_fused_paged(
        gen.params, tokens, rt.arrays, jnp.asarray(bt), lengths,
        lengths + 4, jnp.arange(n, dtype=jnp.int32) + 1,
        jnp.arange(n, dtype=jnp.uint32) + 11, st(0, jnp.int32),
        st(0, jnp.int32), jnp.zeros((slots, 1), jnp.int32),
        st(0.0, jnp.float32), st(0, jnp.int32), st(False, jnp.bool_),
        jnp.zeros((slots, 2), jnp.uint32), row(0.0, jnp.float32),
        row(0, jnp.int32), row(True, jnp.bool_))
    lines = gen._gather_rows_paged(out[0], jnp.asarray(bt))
    return jax.device_get(lines), jax.device_get(out[1:])


def _logits(gen, prompts, chunk=None):
    """First-token logits of ``prompts`` from the traced bodies the admission
    program is made of: the single shot (``chunk`` None) or the walk."""
    from tpustack.models.llama import init_kv_caches

    tokens, lengths = _padded(gen, prompts)
    n, bucket = tokens.shape
    caches = init_kv_caches(gen.cfg, n, dtype=jnp.float32, seq=bucket)

    @jax.jit
    def run(tokens, lengths, caches):
        if chunk is None:
            pos = jnp.broadcast_to(jnp.arange(bucket), (n, bucket))
            logits, _, _ = gen._apply_counted(
                gen.params, tokens, pos, caches, 0, None, lengths - 1)
            return logits[:, 0]
        return gen._prefill_walk_body(gen.params, tokens, lengths, caches,
                                      chunk)[0]

    return np.asarray(run(tokens, lengths, caches))


@pytest.mark.parametrize("kv", [None, "int8"], ids=["float_kv", "int8_kv"])
@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_walk_admission_equals_the_single_shot(preset, rows, kv):
    shot, walk = _pair(preset, kv)
    prompts = _prompts(ROWS[rows])
    lines_s, out_s = _admit(shot, prompts)
    lines_w, out_w = _admit(walk, prompts)

    # what the program says it ran: the single shot nothing, the walk the
    # longest row's chunks — 3 of the bucket's 4
    assert out_s[-1] is None
    assert int(out_w[-1]) == -(-max(ROWS[rows]) // C) == 3

    # the pool's pages, each row's own tokens (the rest of a page is
    # padding's K/V in one and zeros in the other: nothing reads it).  The
    # two run products of other shapes and attend through other kernels, so
    # a float line agrees to float32 rounding.  An int8 line is attended as
    # quantised by the walk and in flight by the single shot: layer 0 (K/V
    # of the embeddings alone) holds the same int8 values and scales.  A
    # later layer quantises OTHER vectors, not the same ones again — its
    # input carries layer 0's attention over a rounded line through an MLP
    # — so it is not within the one step a re-rounding would give: layer 1
    # reads 2.39 / 3.31 steps of its vectors' scales at most (dense: one
    # row / three), 3.03 / 3.03 (window + experts), scales within 1.5 /
    # 2.2%; held to 4 steps and 3%.  Past the first routed layer's output
    # a top-k that flips moves a token's whole hidden state (layers 2 and
    # 3 of that preset read 80 and 77 steps on these seeds; PERF.md §6, PR
    # 28: the chip's ``served_gap`` judges that), so the lines are held up
    # to that layer and the model by its logits below.
    specs = shot.cfg.layer_specs
    routed = [i for i, sp in enumerate(specs) if sp.ffn == "experts"]
    held = len(specs) if not (kv and routed) else routed[0] + 1
    for li, (ls, lw) in enumerate(zip(lines_s[:held], lines_w[:held])):
        for r, n in enumerate(ROWS[rows]):
            for key in ("k", "v"):
                a, b = ls[key][r, :n], lw[key][r, :n]
                if kv is None:
                    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
                    continue
                sa, sb = ls[key + "_scale"][r, :n], lw[key + "_scale"][r, :n]
                if li == 0:
                    np.testing.assert_array_equal(a, b)
                np.testing.assert_allclose(sa, sb, rtol=0.03 if li else 1e-5)
                gap = np.abs(a * sa[..., None] - b * sb[..., None])
                assert (gap <= 4 * np.maximum(sa, sb)[..., None]).all()

    # the first token's logits, a row that ends in an early chunk from that
    # chunk; with an int8 line to the noise of its quantisation (the units
    # are those of the chip's ``served_gap``, logits of unit spread: the
    # four cases read 0.023, 0.033, 0.077 and 0.104 at most)
    tol = dict(atol=0.15) if kv else dict(rtol=2e-4, atol=2e-4)
    want = _logits(shot, prompts)
    np.testing.assert_allclose(_logits(walk, prompts, C), want, **tol)

    # the first tokens and the activated slot state: firsts, then cur,
    # active, first, temp, topk, greedy, keys.  Greedy on a float line picks
    # the same token; on an int8 line one the single shot's logits hold
    # within that noise of their best (random weights leave near-ties)
    names = ("firsts", "cur", "active", "first", "temp", "topk", "greedy",
             "keys")
    for name, a, b in zip(names, out_s, out_w):
        if kv and name in ("firsts", "first"):
            picked = np.take_along_axis(
                want, np.asarray(out_w[0])[:, None], axis=1)[:, 0]
            assert (want.max(-1) - picked <= 0.15).all()
            continue
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert list(out_w[1][1:1 + len(prompts)]) == list(ROWS[rows])  # cur
    assert (out_w[3][1:1 + len(prompts), 0] == out_w[0]).all()

    # routed experts: the walk counts over the chunks it ran, the single
    # shot over the whole bucket — the same pairs on the rows' own tokens,
    # so no fewer than the true tokens' and no more than the single shot's
    if preset == "dense":
        assert out_s[-2] is None and out_w[-2] is None
    else:
        sparse = sum(sp.ffn == "experts" for sp in shot.cfg.layer_specs)
        pairs_s, pairs_w = int(out_s[-2][0]), int(out_w[-2][0])
        assert 0 < pairs_w <= pairs_s
        assert pairs_w <= len(prompts) * 3 * C * sparse * 2   # top-2


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_walk_over_the_whole_bucket_counts_the_single_shots_pairs(preset):
    """A longest row in the bucket's last chunk: every position the single
    shot computes the walk computes, and the routed-expert counters say so."""
    shot, walk = _pair(preset, None)
    prompts = _prompts((60, 9))
    _, out_s = _admit(shot, prompts)
    _, out_w = _admit(walk, prompts)
    assert int(out_w[-1]) == 4
    np.testing.assert_array_equal(out_s[0], out_w[0])
    if preset == "dense":
        assert out_w[-2] is None
    else:
        # pairs: equal; experts touched and the fullest expert's pairs are
        # sums over layer-calls, of which the walk makes four a layer
        assert int(out_w[-2][0]) == int(out_s[-2][0])
        assert int(out_w[-2][1]) >= int(out_s[-2][1])


def _serve(gen, prompts):
    """``prompts`` through a 4-slot engine of ``gen``: (its ``prefill`` flight
    records, each request's greedy tokens)."""
    rec = FlightRecorder("eng", capacity=256)
    rt = PagedKVRuntime.build(gen.cfg, 4, block=BLOCK, pool_blocks=64,
                              dtype=jnp.float32, mesh=gen.kv_mesh,
                              prefix_cache=False)
    done = {}
    q = [SlotRequest(ids=list(p), max_new=4, sample=GREEDY,
                     on_done=lambda t, s, i=i: done.__setitem__(i, t))
         for i, p in enumerate(prompts)]
    ContinuousEngine(gen, slots=4, chunk=4, paged=rt, flight=rec,
                     stop_tokens=()).run(lambda: q.pop(0) if q else None)
    return ([r for r in rec.recent() if r["kind"] == "prefill"],
            [done[i] for i in range(len(prompts))])


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_prefill_record_says_what_the_walk_computed(preset, rows):
    shot, walk = _pair(preset, None)
    prompts = _prompts(ROWS[rows])
    recs_s, toks_s = _serve(shot, prompts)
    recs_w, toks_w = _serve(walk, prompts)
    assert toks_w == toks_s        # the same greedy continuations
    sparse = sum(sp.ffn == "experts" for sp in shot.cfg.layer_specs)
    for recs, g in ((recs_s, shot), (recs_w, walk)):
        assert sum(r["rows"] for r in recs) == len(prompts)
        for r in recs:
            longest = max(r["prompt_lens"])
            assert r["program_bucket"] == g._bucket(longest)
            if g is walk and r["program_bucket"] > C:
                assert r["chunks"] == -(-longest // C)
                assert r["bucket"] == r["chunks"] * C
            else:
                assert r["chunks"] == 1
                assert r["bucket"] == r["program_bucket"]
            assert longest <= r["bucket"] <= r["program_bucket"]
            if sparse:
                # layer-calls stay sparse layers x 1: ``bucket`` carries the
                # positions, so rows x bucket x calls is what routed
                assert r["moe_layer_calls"] == sparse
                assert 0 < r["moe_pairs"] <= (r["rows"] * r["bucket"]
                                              * sparse * 2)
                # experts touched and the fullest expert's pairs are sums
                # over (layer, chunk) calls: 4 experts held, so at most
                # held x layer-calls x chunks, and the fullest of a call
                # holds at least its call's mean
                calls = sparse * r["chunks"]
                assert 0 < r["moe_experts_touched"] <= 4 * calls
                assert r["moe_experts_touched"] <= r["moe_pairs"]
                assert (r["moe_pairs"] / 4 <= r["moe_max_expert_tokens"]
                        <= r["moe_pairs"])
            else:
                assert "moe_pairs" not in r


def test_a_capped_bucket_walks_a_padded_line():
    """``_bucket`` caps at a ``max_seq`` that is no multiple of the chunk:
    the program pads its own tokens and row lines to whole chunks."""
    cfg = LlamaConfig.tiny(max_seq=56)
    shot = Generator(cfg, dtype=jnp.float32, seed=3)
    walk = Generator(cfg, params=shot.params, dtype=jnp.float32)
    walk.ADMIT_CHUNK = C
    prompts = _prompts((50, 33))
    assert walk._bucket(50) == 56
    lines_s, out_s = _admit(shot, prompts)
    lines_w, out_w = _admit(walk, prompts)
    assert int(out_w[-1]) == 4
    np.testing.assert_array_equal(out_s[0], out_w[0])
    for ls, lw in zip(lines_s, lines_w):
        for r, n in enumerate((50, 33)):
            np.testing.assert_allclose(ls["k"][r, :n], lw["k"][r, :n],
                                       rtol=2e-4, atol=2e-5)


def test_the_solo_route_pads_a_capped_bucket_to_whole_chunks():
    """``_prefill_walk`` (the solo route above PREFILL_CHUNK; a static batch
    never fills its context) pads a bucket capped at a ``max_seq`` that is no multiple
    of its chunk — tokens and caller-held cache lines alike — and hands the
    lines back at their own length: the single shot's greedy tokens."""
    g = Generator(LlamaConfig.tiny(max_seq=64), dtype=jnp.float32, seed=3)
    prompt = _prompts((40,))[0]
    ref, _ = g.generate(prompt, max_new_tokens=6, sample=GREEDY, seed=0)
    g.PREFILL_CHUNK = 24    # bucket 64 -> 72: three chunks, two of them run
    out, _ = g.generate(prompt, max_new_tokens=6, sample=GREEDY, seed=0)
    assert out == ref


def test_walk_under_a_tp_mesh_serves_the_unsharded_tokens():
    """The walk's k-streaming call runs per head shard under a ``tp`` mesh
    (``llama._per_head_shard``, as the prefix path's chunk loop does): the
    engine over two CPU devices emits the unsharded walk's greedy tokens."""
    from tpustack.parallel import build_mesh

    _, walk = _pair("dense", None)
    mesh = build_mesh((1, 1, 2, 1), devices=jax.devices()[:2])
    tp = Generator(walk.cfg, params=jax.device_get(walk.params),
                   dtype=jnp.float32, mesh=mesh)
    tp.ADMIT_CHUNK = C
    prompts = _prompts(ROWS["three_rows"])
    assert _serve(tp, prompts)[1] == _serve(walk, prompts)[1]
