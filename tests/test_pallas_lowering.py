"""Cross-lower every Pallas entry point for TPU from the CPU host, at the
shapes the servers run.

Interpret mode (what the rest of tier-1 runs the kernels in) skips the
Pallas→Mosaic lowering, so a BlockSpec the TPU compiler refuses — a block
whose last two dims are neither whole axes nor tile multiples — used to
surface only on a chip.  ``.trace(...).lower(lowering_platforms=("tpu",))``
runs that lowering on any host in well under a second per case.  It is the
pre-check before a chip run, not a replacement for one: Mosaic's own
passes (layout inference, VMEM limits) only run under libtpu — the
hardware tier (``tests/test_tpu_hw.py``) covers those.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustack.ops.pallas.flash_attention import (flash_attention,
                                                 paged_attention_partial,
                                                 paged_scale_rows)
from tpustack.ops.pallas.moe_gmm import moe_gmm


def _lower_for_tpu(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# (b, q heads, kv heads, head_dim, block, blocks/seq, pool blocks, q dtype):
# the Deployment's Qwen2.5-7B (8 slots, ctx 4096 / 64-token blocks, 512+1
# pool blocks) and the tiny preset the CPU servers boot
# ... and one head shard of the 7B pool under LLM_TP=4
# (``llama._per_head_shard``): a pool block is a [64, 1·128] slab
# ... and K-EXAONE's share (16 slots, 64 q / 8 kv heads), whose window
# layers run the same kernel with a first position a row
PAGED_MODELS = {
    "k_exaone_ep8": (16, 64, 8, 128, 64, 64, 1025, jnp.bfloat16),
    "qwen25_7b": (8, 28, 4, 128, 64, 64, 513, jnp.bfloat16),
    "qwen25_7b_tp4_shard": (8, 7, 1, 128, 64, 64, 513, jnp.bfloat16),
    "tiny": (2, 4, 2, 16, 8, 16, 33, jnp.float32),
}


def _paged_call(model, int8_pool, s, sharding=None, window=None):
    """``(fn, avals)`` of one paged kernel call at a served shape; with
    ``window`` a window layer's call (the last operand: its positions)."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    b, h, hkv, d, blk, nb, n_pool, qdt = PAGED_MODELS[model]
    # the pool as it rests (llama.init_kv_pool)
    pool = sds((n_pool, blk, hkv * d), jnp.int8 if int8_pool else qdt)
    scales = sds((n_pool, hkv * blk), jnp.float32) if int8_pool else None
    args = [sds((b, s, h, d), qdt), pool, pool,
            sds((b, nb), jnp.int32), sds((b,), jnp.int32), scales, scales,
            sds((b,), jnp.int32) if window else None]

    def fn(q, pk, pv, bt, lens, ks, vs, q_pos):
        rows = (None if ks is None else (paged_scale_rows(ks, bt, pk),
                                         paged_scale_rows(vs, bt, pv)))
        return paged_attention_partial(q, pk, pv, bt, lens, scale_rows=rows,
                                       interpret=False, window=window,
                                       q_pos=q_pos)

    return fn, args


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify_k4"])
@pytest.mark.parametrize("int8_pool", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("model", sorted(PAGED_MODELS))
def test_paged_attention_lowers_for_tpu(model, int8_pool, s):
    fn, args = _paged_call(model, int8_pool, s)
    _lower_for_tpu(fn, *args)


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described, not attached, v5e chip: the TPU compiler is installed
    on this host, so Mosaic's own passes (layout inference, the alignment
    of every copy's slice, VMEM) can refuse a kernel here instead of on
    the chip.  Made inside a test, never at import (one process per
    libtpu)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify_k4"])
@pytest.mark.parametrize("int8_pool", [False, True], ids=["pool", "int8pool"])
@pytest.mark.parametrize("model", sorted(PAGED_MODELS))
def test_paged_attention_compiles_for_v5e(one_v5e_chip, model, int8_pool, s):
    """The whole compile, Mosaic included: the kernel copies pool blocks
    itself, and Mosaic refuses a copy whose slice is not whole tiles (a
    ``[64, 4]`` scale page out of a ``pl.ANY`` operand was) only here."""
    fn, args = _paged_call(model, int8_pool, s, sharding=one_v5e_chip)
    _compile_for_described_chip(fn, args)


def _compile_for_described_chip(fn, args):
    from tpustack.utils.hlo_text import compile_program

    return compile_program(jax.jit(fn), args)


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify_k4"])
def test_paged_window_layer_compiles_for_v5e(one_v5e_chip, s):
    """A window layer's call at K-EXAONE's share: a third scalar-prefetch
    operand (each row's first position), a walk that starts mid-table."""
    fn, args = _paged_call("k_exaone_ep8", True, s, sharding=one_v5e_chip,
                           window=128)
    _compile_for_described_chip(fn, args)


# --------------------------------------------- the pool rests as it is read
@pytest.mark.parametrize("program,pool_share", [
    ("decode", 1), ("decode", 2), ("admit", 1)],
    ids=["decode", "decode_half_pool", "admit"])
@pytest.mark.parametrize("kv", ["int8", "float"])
@pytest.mark.parametrize("preset", ["k_exaone_236b_ep8", "qwen25_7b"])
def test_no_compiled_program_relays_the_pool(one_v5e_chip, preset, kv,
                                             program, pool_share):
    """The pool rests in the layout its consumers take (PR 29): compiled for
    a described v5e, neither a 16-step decode chunk (``flash=True``) nor a
    1-row 512-bucket admission holds, outside its scan and outside fusions,
    a ``copy`` / ``reshape`` / ``slice`` / ``pad`` whose result is shaped
    like the pool and reaches a quarter of a K/V pool tensor — at the pool
    the benchmark's two configurations serve (their KV shapes, 4 x 128 and
    8 x 128 heads, are what the layout has to fit; two layers of each at
    its true widths compile in 5-10 s) and at half of it.  On the tree
    before, every chunk re-tiled each K/V tensor whole for the kernel (a
    ``reshape``) and padded each scale plane's 4 or 8 heads to 128 lanes
    for the scatter (a ``copy`` 32 or 16 times the plane), and so did every
    admission: 3.2 GB a chunk at 28 layers.

    What XLA stages of the pool INSIDE the scan is the next test's."""
    from tpustack.utils.hlo_text import (SERVED_SLOTS, compile_program,
                                         pool_relayouts, serving_config,
                                         serving_program)

    cfg = serving_config(preset, 2, kv)
    slots, block = SERVED_SLOTS[preset], 64
    n_blocks = slots * (cfg.max_seq // block) // pool_share + 1
    text = compile_program(*serving_program(
        program, cfg, one_v5e_chip, rows=slots if program == "decode" else 1,
        pool_blocks=n_blocks, block=block)).as_text()
    assert "paged_attention" in text or program == "admit"
    tensor = (n_blocks * block * cfg.n_kv_heads * cfg.head_dim
              * (1 if kv == "int8" else 2))
    found = pool_relayouts(text, n_blocks, block, tensor // 4)
    assert not found, "\n".join(i.line[:200] for i in found[:6])


@pytest.mark.parametrize("preset,kv", [
    ("qwen25_7b", "int8"), ("qwen25_7b", "float"),
    ("k_exaone_236b_ep8", "int8")])
def test_no_decode_step_stages_the_pool(one_v5e_chip, preset, kv):
    """The pool stays where it rests while a chunk's steps run: compiled
    for a described v5e at 8 layers (XLA's memory-space assignment shows
    its choice from 8; at 2 it stages nothing), no step of the decode scan
    holds a copy of a whole pool tensor.  XLA takes the paged call to read
    its operands whole and, with VMEM to spare, stages 16-32 MiB pool
    tensors in VMEM ahead of every call (7 of 16 at this size without
    ``paged_vmem_claim``: 0.4 GB a step at 28 layers); the 7B pools are in
    that range, int8 and float, and the claim keeps them out; K-EXAONE's
    64 MiB tensors were never staged, and its calls claim nothing.  A
    pool tensor of 16 MiB or less (half the served pool) is out of the
    claim's reach and IS staged: ``paged_vmem_claim``'s docstring."""
    from tpustack.utils.hlo_text import (SERVED_SLOTS, compile_program,
                                         pool_relayouts, serving_config,
                                         serving_program)

    cfg = serving_config(preset, 8, kv)
    slots, block = SERVED_SLOTS[preset], 64
    n_blocks = slots * (cfg.max_seq // block) + 1
    text = compile_program(*serving_program(
        "decode", cfg, one_v5e_chip, rows=slots, pool_blocks=n_blocks,
        block=block)).as_text()
    tensor = (n_blocks * block * cfg.n_kv_heads * cfg.head_dim
              * (1 if kv == "int8" else 2))
    found = pool_relayouts(text, n_blocks, block, tensor // 4, in_scan=True)
    assert not found, "\n".join(i.line[:200] for i in found[:6])


# ------------------------------------- an admission's work follows its rows
@pytest.mark.parametrize("preset", ["k_exaone_236b_ep8", "qwen25_7b"])
def test_admission_above_a_chunk_walks_to_a_traced_bound(one_v5e_chip,
                                                         preset):
    """The admission program of a bucket above ``Generator.ADMIT_CHUNK`` (PR
    34), compiled for a described v5e — 4 rows of the 4,096 bucket, two
    layers at true widths: it holds ONE ``while`` (the walk over the
    bucket's chunks), whose trip count XLA does not know (the bound is an
    operand, the longest row's last chunk: no ``known_trip_count``, no
    constant in the condition), the k-streaming kernel inside it and the
    panel kernel nowhere, and no temporary of rows x bucket positions by
    the feed-forward width (1.2 GB at 28 layers' worth of reuse on the tree
    before): a chunk's ``[rows x chunk, ffn]`` is the largest."""
    from tpustack.models.llm_generate import Generator
    from tpustack.utils.hlo_text import (SERVED_SLOTS, compile_program,
                                         parse, serving_config,
                                         serving_program)

    cfg = serving_config(preset, 2)
    rows, bucket, block = 4, 4096, 64
    assert bucket > Generator.ADMIT_CHUNK
    n_blocks = SERVED_SLOTS[preset] * (cfg.max_seq // block) + 1
    text = compile_program(*serving_program(
        "admit", cfg, one_v5e_chip, rows=rows, pool_blocks=n_blocks,
        block=block, bucket=bucket)).as_text()
    instrs, _, _, _ = parse(text)
    whiles = [i for i in instrs if i.opcode == "while"]
    assert len(whiles) == 1, [i.line[:120] for i in whiles]
    assert "known_trip_count" not in whiles[0].line
    cond = re.search(r"condition=%?([\w.\-]+)", whiles[0].line).group(1)
    held = [i for i in instrs if i.comp == cond]
    assert held and not [i for i in held if i.opcode == "constant"], [
        i.line[:120] for i in held]
    assert "flash_kstream" in text and "flash_panel" not in text
    wide = max(cfg.ffn_dim, cfg.dim)
    chunk_rows = rows * Generator.ADMIT_CHUNK
    big = [i for i in instrs for dt, dims, _ in i.shapes
           if dims and dims[-1] >= wide and dt != "s8"
           and int(np.prod(dims[:-1])) > chunk_rows]
    assert not big, "\n".join(i.line[:160] for i in big[:4])
    assert any(dims and dims[-1] == cfg.ffn_dim
               and int(np.prod(dims[:-1])) == chunk_rows
               for i in instrs for _, dims, _ in i.shapes)


def test_admission_of_one_chunk_is_the_single_shot(one_v5e_chip):
    """A bucket of at most ``ADMIT_CHUNK`` keeps the single-shot body: no
    ``while``, no k-streaming call.  (That its compiled text is the parent's
    but for source lines, both served presets at every layer, is shown by
    hand from ``tools/hlo_where.py admit --layers 0 --dump``: CHANGES.md.)"""
    from tpustack.models.llm_generate import Generator
    from tpustack.utils.hlo_text import (compile_program, parse,
                                         serving_config, serving_program)

    cfg = serving_config("qwen25_7b", 2)
    text = compile_program(*serving_program(
        "admit", cfg, one_v5e_chip, rows=1, pool_blocks=513, block=64,
        bucket=Generator.ADMIT_CHUNK)).as_text()
    assert not [i for i in parse(text)[0] if i.opcode == "while"]
    assert "flash_kstream" not in text


# moe_gmm at K-EXAONE's share (16 held experts of [6144, 2048]): (tokens a
# call routes, K, N) — a decode step's 16 rows, admissions of 1 and 16 rows
# of the 512 bucket; gate/up and down
GMM_SHAPES = {
    "decode_gate_up": (16, 6144, 2048), "decode_down": (16, 2048, 6144),
    "admit1_gate_up": (512, 6144, 2048), "admit16_gate_up": (8192, 6144, 2048),
    "admit16_down": (8192, 2048, 6144),
}


def _gmm_call(shape, sharding=None):
    from tpustack.models.moe import row_tile

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    tokens, k, n = GMM_SHAPES[shape]
    held, top_k, n_experts = 16, 8, 128
    tm = row_tile(tokens, top_k, n_experts)
    m = -(-(tokens * min(top_k, held) + held * (tm - 1)) // tm) * tm
    args = [sds((m, k), jnp.bfloat16), sds((held, k, n), jnp.int8),
            sds((held, n), jnp.float32), sds((m // tm,), jnp.int32),
            sds((), jnp.int32)]
    return (lambda x, w, s, te, na: moe_gmm(x, w, s, te, na, tm=tm,
                                            interpret=False)), args


@pytest.mark.parametrize("shape", sorted(GMM_SHAPES))
def test_moe_gmm_compiles_for_v5e(one_v5e_chip, shape):
    """The grouped product, Mosaic included, at the served shapes: the
    panel of an expert, the row tile and the f32 sum have to fit the VMEM
    the call states."""
    fn, args = _gmm_call(shape, sharding=one_v5e_chip)
    _compile_for_described_chip(fn, args)


# panel kernel: (q tokens, k tokens, heads, head_dim)
PANEL_SHAPES = {
    "sd15_self_4096_d40": (4096, 4096, 8, 40),
    "wan_self_2560": (2560, 2560, 12, 128),
    "wan_self_8320": (8320, 8320, 12, 128),
    "wan_cross_2560x512": (2560, 512, 12, 128),
}


@pytest.mark.parametrize("shape", sorted(PANEL_SHAPES))
def test_panel_attention_lowers_for_tpu(shape):
    sq, sk, h, d = PANEL_SHAPES[shape]
    q = _sds((1, sq, h, d), jnp.bfloat16)
    kv = _sds((1, sk, h, d), jnp.bfloat16)
    _lower_for_tpu(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                   q, kv, kv)


def test_streaming_attention_lowers_for_tpu():
    """LLM chunked prefill: an 8k chunk over a 32k cache, GQA 28/4, traced
    offset and length (one compiled program serves every chunk)."""
    q = _sds((1, 8192, 28, 128), jnp.bfloat16)
    kv = _sds((1, 32768, 4, 128), jnp.bfloat16)
    scalar = _sds((), jnp.int32)

    def fn(q, k, v, off, n):
        return flash_attention(q, k, v, causal=True, q_offset=off, kv_len=n,
                               interpret=False)

    _lower_for_tpu(fn, q, kv, kv, scalar, scalar)


def _kernel_programs():
    """One small call of each Pallas kernel, by the name it has to carry."""
    q = _sds((1, 256, 4, 128), jnp.bfloat16)
    kv = _sds((1, 256, 2, 128), jnp.bfloat16)
    scalar = _sds((), jnp.int32)
    pool = _sds((33, 8, 2 * 128), jnp.bfloat16)
    return {
        "flash_panel": (lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), (q, kv, kv)),
        "flash_kstream": (lambda q, k, v, off, n: flash_attention(
            q, k, v, causal=True, q_offset=off, kv_len=n, interpret=False),
            (q, kv, kv, scalar, scalar)),
        "paged_attention": (lambda q, pk, pv, bt, lens:
                            paged_attention_partial(q, pk, pv, bt, lens,
                                                    interpret=False),
                            (_sds((2, 1, 4, 128), jnp.bfloat16), pool, pool,
                             _sds((2, 16), jnp.int32), _sds((2,), jnp.int32))),
        "moe_gmm": _gmm_call("decode_gate_up"),
    }


@pytest.mark.parametrize("window", [None, 128], ids=["causal", "window"])
@pytest.mark.parametrize("s", [512, 4096])
def test_prefill_kernels_lower_for_tpu_at_k_exaone_heads(s, window):
    """64 q / 8 kv heads x 128: a bucket's panel call and, from a traced
    offset, the streaming one; with the window the panel kernel slices its
    K/V panel at a 128-row boundary it computes from the grid index."""
    q = _sds((1, s, 64, 128), jnp.bfloat16)
    kv = _sds((1, s, 8, 128), jnp.bfloat16)
    scalar = _sds((), jnp.int32)
    _lower_for_tpu(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False), q, kv, kv)
    _lower_for_tpu(lambda q, k, v, off, n: flash_attention(
        q, k, v, causal=True, window=window, q_offset=off, kv_len=n,
        interpret=False), q, kv, kv, scalar, scalar)


@pytest.mark.parametrize("kernel", ["flash_panel", "flash_kstream",
                                    "paged_attention", "moe_gmm"])
def test_kernel_names_are_pinned(kernel):
    """A kernel's name is what a device trace shows and what the benchmark's
    rooflines sum by (``flash_prefill_roofline``, ``paged_attention_
    roofline``): a rename of the enclosing function must not move it."""
    fn, avals = _kernel_programs()[kernel]
    text = _lower_for_tpu(fn, *avals).as_text()
    assert "tpu_custom_call" in text
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [kernel]


def test_refused_block_shape_fails_on_cpu():
    """The check has teeth: a block of ONE kv head out of four (the shape
    the pre-repair paged kernel asked for) is refused by the lowering on
    this host, no chip needed."""
    from jax.experimental import pallas as pl

    def fn(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: o_ref.__setitem__(..., x_ref[...]),
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 64, 1, 128),
                                   lambda i: (0, 0, i, 0))],
            out_specs=pl.BlockSpec((1, 64, 1, 128), lambda i: (0, 0, i, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    with pytest.raises(ValueError, match="last two dimensions"):
        _lower_for_tpu(fn, _sds((2, 64, 4, 128), jnp.bfloat16))


# ------------------------------------------- kernels under the serving mesh
def test_serving_programs_lower_for_tpu_under_a_tp_mesh(monkeypatch):
    """A Mosaic kernel cannot be GSPMD-partitioned: traced as a TPU would
    trace them (``auto`` picks the flash kernels, interpret off), the tp
    server's admission prefill at a ≥1k bucket and its chunked long-prompt
    prefill must still lower — the kernels run per head shard.  On four
    v5e chips the un-wrapped program died with "Mosaic kernels cannot be
    automatically partitioned" (PR 21); this reproduces that on the CPU."""
    import dataclasses

    from tpustack.models.llama import LlamaConfig, init_kv_caches
    from tpustack.models.llm_generate import Generator
    from tpustack.parallel import build_mesh

    mesh = build_mesh((1, 1, 2, 1), devices=jax.devices()[:2])
    cfg = dataclasses.replace(LlamaConfig.tiny(max_seq=4096),
                              kv_quant="int8")
    gen = Generator(cfg, dtype=jnp.bfloat16, mesh=mesh)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    caches = jax.eval_shape(
        lambda: init_kv_caches(cfg, 1, dtype=jnp.bfloat16))
    tokens = _sds((1, 1024), jnp.int32)
    one = _sds((1,), jnp.int32)
    # prefill from 0 at a 1k bucket: in-bucket causal, auto → panel kernel
    lowered = Generator._prefill.trace(gen, gen.params, tokens, one,
                                       caches).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    # a later chunk of a long prompt: the k-streaming kernel, traced offset
    lowered = Generator._prefill_chunk.trace(
        gen, gen.params, tokens, _sds((), jnp.int32), one,
        caches).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_per_head_shard_matches_unsharded_attention():
    """The wrapper's numerics: the flash kernel run per tp head shard
    (interpret mode, GQA 4/2 heads over tp=2) equals plain XLA attention."""
    import numpy as np

    from tpustack.models.llama import LlamaConfig, _per_head_shard
    from tpustack.ops.attention import dot_product_attention
    from tpustack.parallel import build_mesh

    mesh = build_mesh((1, 1, 2, 1), devices=jax.devices()[:2])
    cfg = LlamaConfig.tiny()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, cfg.n_heads, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, cfg.n_kv_heads, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, cfg.n_kv_heads, 16), jnp.float32)
    fn, ok = _per_head_shard(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32,
                                        interpret=True), mesh, cfg)
    assert ok
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(q, k, v)),
                               np.asarray(ref), atol=2e-5)
    # heads that tp does not divide: handed back unwrapped, and says so
    import dataclasses

    odd = dataclasses.replace(cfg, n_kv_heads=1)
    assert _per_head_shard(lambda *a: None, mesh, odd)[1] is False
