"""Flash attention for TPU in Pallas.

The hot-op playbook from ``/opt/skills/guides/pallas_guide.md``: tile the
query sequence onto the grid, stream K/V through VMEM, never materialise the
``[S, S]`` score matrix in HBM.  XLA's fused attention is already strong at
SD1.5's 4k-token spatial attention; this kernel targets the places XLA's
generic fusion loses to a hand-tile — long single-device sequences (the
multi-device long-context path is ``tpustack.parallel.ring_attention``, which
uses its own per-shard partials) — and is exercised in interpret mode on CPU
in CI.

Layout contract: BSHD in, BSHD out (same as ``tpustack.ops.attention``).
Internally ``[B*H, S, D]`` with the q-sequence tiled at ``block_q`` rows per
grid step; the full per-head K/V panel lives in VMEM (fine to ~8k tokens at
D=128 bf16; ring attention keeps per-shard S small beyond that).

Constraints: D should be a multiple of 128 for peak MXU lane use (64 works,
down-tiled); q/k lengths must divide by the chosen block (the wrapper pads
and masks).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


LOG2E = 1.4426950408889634


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                 kv_len: int, block_q: int, window: Optional[int] = None):
    """One (batch*head, q-block) grid step: softmax(q·kᵀ)·v, fp32 accumulate.

    Inputs stay in their storage dtype (bf16 on TPU) through the two
    dot_generals — the MXU multiplies bf16 natively at full rate with fp32
    accumulation (``preferred_element_type``); upcasting to f32 first would
    halve matmul throughput for no extra accuracy in the product.  Softmax
    statistics are fp32.

    The kernel is VPU-bound at mid sizes (per score element: 512 MXU
    flops vs ~10 VPU ops, against the machine's ~50:1 MXU:VPU ratio), so
    the softmax phase economises VPU passes: the padding/causal mask —
    iota, compare, select: 3 full passes over the scores — is emitted only
    when the (static) shape actually has padding or causality, and exp goes
    through exp2 with log2(e) folded into the static scale (same math:
    exp(l·s - m) == exp2(l·s·log2e - m') with the max taken in the scaled
    domain; one fewer VPU multiply per element if exp lowers to scale+exp2).

    ``window`` (static, with ``causal``): row ``i`` sees keys ``j`` with
    ``i - j < window``.  The keys a q block can see span ``block_q +
    window - 1`` positions, so the step reads that slice of the panel
    (rounded out to 128-row tiles) and not the whole of it: what lies
    wholly behind the band costs no product and no softmax pass.
    """
    qi = pl.program_id(1)
    # fold the softmax scale (with log2e) into the q TILE, not the scores:
    # the tile is [block_q, D] (~16k elements) while the scores are
    # [block_q, S] (~20x more at serving shapes) — in a VPU-bound kernel
    # that one full score pass is measurable.  bf16 q x scalar rounds at
    # bf16 grain, the same order as the input rounding itself.
    q = q_ref[0] * jnp.asarray(scale * LOG2E, q_ref.dtype)  # [block_q, D]
    s_pad, col0 = k_ref.shape[1], 0
    if window is not None and block_q % 128 == 0:
        back = -(-(window - 1) // 128) * 128
        if block_q + back < s_pad:              # static
            col0 = pl.multiple_of(jnp.clip(qi * block_q - back, 0,
                                           s_pad - block_q - back), 128)
            s_pad = block_q + back
    if s_pad < k_ref.shape[1]:
        k = k_ref[0, pl.ds(col0, s_pad), :]     # [S_pad, D]
        v = v_ref[0, pl.ds(col0, s_pad), :]
    else:
        k = k_ref[0]
        v = v_ref[0]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    if causal or kv_len < k_ref.shape[1]:       # static: skip 3 VPU passes
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, s_pad), 1)
        valid = col < kv_len                    # mask K padding
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, s_pad), 0)
            valid = valid & (col <= row)
            if window is not None:
                valid = valid & (col > row - window)
        logits = jnp.where(valid, logits, NEG_INF)

    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp2(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)        # f32
    out = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32) / denom
    o_ref[0] = out.astype(o_ref.dtype)


def _attn_kernel_stream(q_ref, k_ref, v_ref, off_ref, len_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                        block_q: int, block_k: int, n_k: int,
                        window: Optional[int] = None):
    """One (batch*head, q-block, k-block) grid step with a running-softmax
    carry — the long-context kernel.  Unlike ``_attn_kernel`` the K/V panel
    never sits whole in VMEM: blocks of ``block_k`` stream through while
    fp32 scratch carries the online-softmax state (max ``m``, denominator
    ``l``, unnormalised accumulator ``acc``) across the innermost grid dim.
    TPU grid steps run sequentially per core, so the scratch persists from
    one k-block to the next; it is reset at ``ki == 0`` and the normalised
    output is written at the last k-block.  Sequence length is bounded by
    HBM, not VMEM.

    ``off_ref``/``len_ref`` are SMEM scalars: the q rows' global position
    offset (chunked prefill: a chunk at cache offset ``off`` attends the
    whole cache prefix) and the number of valid K tokens.  K-blocks past
    ``len`` or fully above the (offset) diagonal skip their compute (their
    DMA is still scheduled — see the wrapper docstring); with ``window``
    (static) so do the blocks wholly behind the band of the block's first
    row, and a block the band's edge crosses takes the masked branch.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    off = off_ref[0]
    kv_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col0 = ki * block_k
    # skip k-blocks past the valid length; causal: also those fully above
    # this q-block's diagonal
    needed = col0 < kv_len
    row0 = off + qi * block_q                   # first row's position
    if causal:
        needed = needed & (col0 <= row0 + block_q - 1)
    if window is not None:
        needed = needed & (col0 + block_k - 1 > row0 - window)

    def _accumulate(logits):
        """Online-softmax update of the (m, l, acc) carry from one block of
        scaled logits (log2e folded into the static scale; max/exp2 run in
        the scaled domain — same softmax, see the panel kernel docstring)."""
        v = v_ref[0]
        m_prev = m_ref[:, :1]                   # [block_q, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_cur)        # rescale of prior state
        p = jnp.exp2(logits - m_cur)
        l_ref[...] = jnp.broadcast_to(l_prev * alpha +
                                      jnp.sum(p, axis=-1, keepdims=True),
                                      l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    def _logits():
        # scale folded into the q tile (see _attn_kernel): one fewer full
        # VPU pass over every [block_q, block_k] score block
        q = q_ref[0] * jnp.asarray(scale * LOG2E, q_ref.dtype)
        k = k_ref[0]                            # [block_k, D]
        return jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    # The masking passes (iota, compare, select — 3 VPU passes over the
    # whole score block) are only needed on BOUNDARY blocks: those crossing
    # kv_len, or crossing this q-block's causal diagonal band.  Interior
    # blocks — the vast majority of a long prefill — take the unmasked
    # branch.  Exactly one branch executes per grid step; both update the
    # same carry.
    boundary = col0 + block_k > kv_len
    if causal:
        # fully-below-diagonal test against the STRICTEST row (row 0 of the
        # q block): every column valid for row 0 is valid for all rows
        boundary = boundary | (col0 + block_k - 1 > row0)
    if window is not None:
        # wholly inside the band only if the LAST row still sees col0
        boundary = boundary | (col0 <= row0 + block_q - 1 - window)

    @pl.when(needed & boundary)
    def _compute_masked():
        logits = _logits()
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + col0
        valid = col < kv_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            valid = valid & (col <= row + row0)
            if window is not None:
                valid = valid & (col > row + row0 - window)
        _accumulate(jnp.where(valid, logits, NEG_INF))

    @pl.when(needed & jnp.logical_not(boundary))
    def _compute_unmasked():
        _accumulate(_logits())

    @pl.when(ki == n_k - 1)
    def _finish():
        # l == 0 only for q rows whose every k column is masked (q padding
        # rows, or causal rows past kv_len) — their output is garbage the
        # wrapper slices off; avoid 0/0
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# beyond this many K tokens the full per-head K/V panel stops fitting VMEM
# (2 panels × 8.7k × 128 × 2B = 4.5 MB plus the [block_q, S] fp32
# scores/probs — ~14 MB peak at 8704) and the k-streaming kernel takes
# over.  Below it the panel kernel wins big: its K/V panel is DMA'd once
# per batch·head (the BlockSpec index is constant across q-blocks) while
# the streaming kernel re-fetches every k-block for every q-block.
# Measured on v5e at the Wan DiT shape (B·H=24, S=8320, D=128, bf16):
# panel 6.4 ms = 132 TFLOP/s vs best-streaming 8.1 ms — and 8704 is the
# largest 128-multiple whose panel program still compiles (block_q 256 at
# this S already overflows VMEM).  8320 > 8192 was exactly the Wan shape,
# which round 3 left on the streaming kernel at 48 TFLOP/s.
PANEL_MAX_KV = 8704


def _default_block_q(streaming: bool, kv_tokens: int, d: int) -> int:
    """Default q-block: streaming takes 1024 (HBM-traffic bound — see the
    wrapper docstring); the panel kernel takes 256 where that config is
    compile/VMEM-verified and 128 everywhere else.

    block_q 256 wins ~8% over 128 at serving shapes (v5e, S=2560 D=128:
    154 vs 143 TFLOP/s with the folded q scale — more MXU work per grid
    step against the same VPU softmax setup), but the panel's VMEM bound
    — [block_q, S] f32 scores + the K/V panels — scales with BOTH S and D:
    256 at S=8704 fails to compile (measured r4), and every 256 compile
    check ran at D=128, so a larger head_dim must not inherit the
    unverified config.  256 therefore requires S ≤ 6144 AND
    d ≤ 128 (compile-verified on-chip across 4608/5120/6144 at D=128,
    matching block_q=128 exactly); anything else stays at 128."""
    if streaming:
        return 1024
    return 256 if (kv_tokens <= 6144 and d <= 128) else 128


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    q_offset=None,
    kv_len=None,
    panel_max_kv: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """``[B, S, H, D]`` flash attention; K/V may carry fewer (GQA) heads.

    K up to ``PANEL_MAX_KV`` runs the panel kernel (whole K/V per head in
    VMEM); longer sequences stream K/V blocks with an online-softmax carry
    (``_attn_kernel_stream``) — long-context length is then bounded by HBM
    only.  ``interpret`` defaults to True off-TPU so CPU tests exercise the
    same kernel code path the chip runs.

    ``q_offset``/``kv_len`` (ints or traced scalars) select the chunked-
    prefill mode: q rows sit at global positions ``q_offset + i`` (causal is
    judged against those) and only the first ``kv_len`` K tokens are valid —
    K is typically the FULL cache while q is one chunk of it.  Blocks past
    ``kv_len`` skip their MXU work (``pl.when``), but their K/V DMA into
    VMEM still runs — the pipeline's copies are scheduled by static block
    index, not the predicate — so early chunks of a long cache save compute
    but still pay full-cache K/V bandwidth.  (Trimming the grid per chunk
    would need one compiled program per chunk position; measured overhead
    at 30k/8k-chunks is ~15-40% of prefill, an accepted trade.)  Forces the
    streaming kernel.

    ``block_q``/``block_k`` default per kernel: the panel kernel takes
    block_q 128 (larger overflows VMEM at PANEL_MAX_KV — the [block_q, S]
    fp32 scores dominate), the streaming kernel 1024/1024.  The streaming
    kernel's K/V HBM traffic is ``(Sq/block_q) · Sk`` per head — every
    q-block re-streams the panel — so big q-blocks are decisive: measured
    on v5e at the 8k-chunk-over-17k-cache prefill shape, 1024/1024 runs
    3.1x the default-of-r3 128/512 (123 vs 39 TFLOP/s); block 2048 is
    within noise of 1024 and 2048/2048 fails to compile.

    GQA (``Hkv`` dividing ``H``) is native: the kernel grid walks q heads
    while the K/V BlockSpec index maps ``bh → bh // (H/Hkv)``, so shared
    K/V panels are DMA'd per kv-head without ever materialising the
    repeated tensor (at 32k ctx the repeat would be ~0.5 GB per layer).

    ``window`` (static, needs ``causal``): a q row at position ``i`` sees
    keys ``j`` with ``0 <= i - j < window``; both kernels skip what lies
    wholly behind the band, as they skip what lies above the diagonal.
    """
    if window is not None and not causal:
        raise ValueError("window= needs causal=True")
    # Resolve the trace-time choices OUTSIDE the jit boundary so they join
    # the jit cache key: the module global PANEL_MAX_KV is read here at every
    # call, not baked into a previously compiled signature (tests monkeypatch
    # it to force the streaming kernel at small shapes).
    if panel_max_kv is None:
        panel_max_kv = PANEL_MAX_KV
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # ONE kernel decision, made here and passed down: the block defaults
    # below and the pallas_call branch in _flash_attention must agree (a
    # panel program handed the streaming default block_q=1024 would overflow
    # VMEM), so _flash_attention takes `streaming` as the verdict instead of
    # re-deriving it.
    streaming = (k.shape[1] > panel_max_kv or q_offset is not None
                 or kv_len is not None)
    if block_q is None:
        block_q = _default_block_q(streaming, k.shape[1], q.shape[-1])
    if block_k is None:
        block_k = 1024 if streaming else 512
    return _flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, q_offset=q_offset,
                            kv_len=kv_len, streaming=streaming,
                            panel_max_kv=panel_max_kv, window=window)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "streaming", "panel_max_kv",
                                             "window"))
def _flash_attention(q, k, v, *, causal, scale, block_q, block_k, interpret,
                     q_offset, kv_len, streaming, panel_max_kv, window):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    g = h // hkv
    if scale is None:
        scale = d ** -0.5

    bq = min(block_q, max(8, sq))
    # fold heads into batch; [B*H(q) / B*Hkv(kv), S, D]
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(
        b * t.shape[2], t.shape[1], d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    qf = _pad_to(qf, 1, bq)
    sq_pad = qf.shape[1]
    # grid index bh = bi*h + hi → its K/V panel row is bh // g
    # = bi*hkv + hi//g, matching jnp.repeat(kv, g, axis=2) head expansion

    if not streaming:
        kf = _pad_to(kf, 1, 128)
        vf = _pad_to(vf, 1, 128)
        sk_pad = kf.shape[1]
        grid = (b * h, sq_pad // bq)
        out = pl.pallas_call(
            functools.partial(_attn_kernel, scale=scale, causal=causal,
                              kv_len=sk, block_q=bq, window=window),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh // g, 0, 0)),
                pl.BlockSpec((1, sk_pad, d), lambda bh, i: (bh // g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
            interpret=interpret,
            name="flash_panel",  # the device trace's name for it: a promise
        )(qf, kf, vf)
    else:
        bk = min(block_k, panel_max_kv)
        kf = _pad_to(kf, 1, bk)
        vf = _pad_to(vf, 1, bk)
        sk_pad = kf.shape[1]
        n_k = sk_pad // bk
        off = jnp.asarray(0 if q_offset is None else q_offset,
                          jnp.int32).reshape(1)
        klen = jnp.asarray(sk if kv_len is None else kv_len,
                           jnp.int32).reshape(1)
        grid = (b * h, sq_pad // bq, n_k)  # k innermost: carry is per (bh, qi)
        out = pl.pallas_call(
            functools.partial(_attn_kernel_stream, scale=scale, causal=causal,
                              block_q=bq, block_k=bk, n_k=n_k, window=window),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, sq_pad, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running max m
                pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
                pltpu.VMEM((bq, d), jnp.float32),     # unnormalised acc
            ],
            interpret=interpret,
            name="flash_kstream",
        )(qf, kf, vf, off, klen)

    out = out[:, :sq]                                  # drop q padding
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# --------------------------------------------------------- paged attention
#
# Decode attention that reads the paged KV pool IN PLACE (vLLM-style
# PagedAttention, Kwon et al. SOSP'23): no dense [B, max_seq] gather copy
# ever materialises in HBM.  The pool tensors [n_blocks, block, kvh*hd]
# (heads folded into lanes: how the pool rests, llama.init_kv_pool) stay
# in HBM (memory_space=pl.ANY) as they are; the per-slot block table and the
# lengths are scalar-prefetch operands, and the kernel fetches what it
# reads itself.
#
# The work a call does follows the bytes it has to read.  The grid is one
# step per batch row; inside it a loop bounded by lengths[b] walks the
# row's COMPUTE BLOCKS of `pages` pool blocks each (about
# PAGED_COMPUTE_TOKENS tokens).  For each compute block the kernel issues
# one async copy per VALID pool block of K and of V — all kv heads of it,
# one contiguous [block, kvh*hd] slab — into one half of a double-buffered
# VMEM scratch, and the next compute block's copies (the next row's
# first, at a row's end) are in flight while this one is computed.  A row
# with lengths[b] == 0 fetches nothing and loops zero times; pool blocks
# at or past a row's frontier are never read, wherever their table
# entries point (the reserved block 0 included).  What they leave in the
# scratch is stale, so every use of it is masked by a select, never by a
# multiply.
#
# An int8 pool's scales [n_blocks, kvh*block] take another road: 3% of
# the bytes, in pages of [kvh, block] — a lane row per head, as the
# [rows, tokens] scores want it, but 64 lanes where Mosaic slices no
# operand whose minor dim is under 128.  The kernel gets each row's scales
# through its block table as per-head lane rows [kvh, n_cb, tokens]
# (paged_scale_rows) — a gather of 32 bytes a token that a decode chunk
# makes once, outside its scan (the pool and the tables do not change
# inside a chunk).
#
# Softmax is the online (m, l, acc) carry across the compute blocks,
# exactly like _attn_kernel_stream; the result is returned as the
# UNNORMALISED partial (acc, m, l) in dot_product_attention_partial's
# layout so the continuous decode/verify step can merge it with the
# chunk-buffer partial (merge_attention_partials) — the buffer carries the
# in-segment causal half of a multi-query speculative verify, the pool
# partial the shared [0, cur) prefix every query row attends.

#: tokens of one compute block the paged kernel aims for: enough for the
#: fixed cost of a loop trip (DMA issue and wait, the online-softmax
#: update) to spread over 0.5 MB of int8 K/V at the 7B shape
PAGED_COMPUTE_TOKENS = 512
#: VMEM the double-buffered K/V scratch may take (1 MB at the int8 7B
#: shape; a quarter of what a v5e kernel may use without asking for more)
PAGED_VMEM_BUDGET = 4 * 1024 * 1024
#: what a TPU kernel may use of its core's VMEM without asking for more
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def core_vmem_bytes() -> Optional[int]:
    """VMEM of the TensorCore this trace is for: the process's chip, or
    the abstract device that a compile for a described chip names
    (``jax.sharding.use_abstract_mesh``).  None off the TPU."""
    if jax.default_backend() != "tpu":
        return None
    return pltpu.get_tpu_info().vmem_capacity_bytes


def paged_vmem_claim(pool_bytes: int,
                     vmem_bytes: Optional[int]) -> Optional[int]:
    """Scoped VMEM a paged call CLAIMS (it uses a few MB) to keep the pool
    where it rests: all of the core's but the default 16 MiB, where that
    pays; None where it does not, and the call asks for nothing.

    XLA takes a custom call to read its operands whole, and with VMEM to
    spare its memory-space assignment stages pool tensors in VMEM ahead of
    EVERY call (copies inside the decode scan: 0.4-0.9 GB a step at the 7B
    shape) for a kernel that copies the 2-3% of them a row reads.  No
    operand annotation stops it (a BlockSpec's HBM reaches Mosaic only; a
    colour-0 ``with_memory_space_constraint`` and a ``cost_estimate`` are
    read and overruled); VMEM the call claims for itself is not XLA's to
    fill.  Compiled for a described v5e, XLA staged K/V tensors of 8, 16
    and 32 MiB and none of 48 MiB or more: so the claim is made only
    where the call's two pool tensors would fit in what the claim takes,
    and only where it can do its work, that is where one pool tensor does
    not fit in the 16 MiB it leaves (a smaller pool is staged as before).
    Those 16 MiB still hold a projection weight prefetched for the next
    matmul (13 MB at 7B)."""
    if vmem_bytes is None:
        return None
    claim = vmem_bytes - DEFAULT_SCOPED_VMEM
    if DEFAULT_SCOPED_VMEM < pool_bytes <= claim // 2:
        return claim
    return None


def _sublane_tile(dtype) -> int:
    """Rows of one packed TPU tile for ``dtype``: (8,128) f32, (16,128)
    bf16, (32,128) int8 — what a matmul operand's row count must divide."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def paged_pages_per_step(blk: int, nb: int, hkv: int, d: int,
                         pool_dtype) -> int:
    """Pool blocks of one compute block, from what the call can see: as
    many as reach ``PAGED_COMPUTE_TOKENS``, at most the table's ``nb``,
    halved until both halves of the K/V scratch fit ``PAGED_VMEM_BUDGET``.
    A block shorter than the pool dtype's sublane tile cannot be stacked
    into one ``[tokens, hkv*d]`` operand without a relayout, so it goes
    alone."""
    if blk % _sublane_tile(pool_dtype):
        return 1
    lanes = -(-hkv * d // 128) * 128
    page_bytes = 2 * blk * lanes * jnp.dtype(pool_dtype).itemsize
    pages = max(1, min(nb, PAGED_COMPUTE_TOKENS // blk))
    while pages > 1 and 2 * pages * page_bytes > PAGED_VMEM_BUDGET:
        pages //= 2
    return pages


def _paged_attn_kernel(bt_ref, len_ref, lo_ref, q_ref, k_hbm, v_hbm, ks_ref,
                       vs_ref, acc_out, m_out, l_out, k_buf, v_buf, sem,
                       st_ref, *, scale: float, blk: int, pages: int,
                       n_b: int, hkv: int, d: int, quant: bool,
                       group: Optional[int] = None):
    """One batch row of in-place paged decode attention: a loop over the
    row's compute blocks, walking the kv heads inside each.  ``q_ref``
    holds this row's query rows per kv head ``[Hkv, R, D]`` (R = S·group,
    the multi-query verify rows x GQA group, padded to the q dtype's
    sublane tile); ``k_buf``/``v_buf`` ``[2, pages, blk, Hkv·D]`` the two
    halves of the scratch the table-mapped pool blocks are copied into,
    heads folded into lanes; ``ks_ref``/``vs_ref`` ``[Hkv, n_cb, tokens]``
    this row's int8 scales, a lane row per head per compute block.
    Numerics mirror ``dot_product_attention_partial`` per element: f32
    logits, int8 dequant via cast-to-compute + per-vector scales OUTSIDE
    the d-contraction (``k_scale`` on the scores, ``v_scale`` on the probs
    after the denominator), plain ``exp`` — only the summation ORDER
    differs (per-compute-block online carry vs one-pass), the same split
    the chunk-boundary merge already makes.

    ``st_ref`` (SMEM, lives across the grid): [0] the scratch half the
    row's first compute block is in, [1] whether the previous row already
    started that block's copies.

    A WINDOW layer (``group`` set, static: the GQA group of a multi-query
    segment, 0 for a single query position) reads the key set
    ``[lo_ref[b] + j, lengths[b])`` for its query position ``j``, with
    ``lo_ref[b]`` the oldest position the row's first query sees (below 0
    while the window still reaches past the sequence's start): the walk
    starts at the compute block that holds it, pool blocks wholly behind
    it are not copied, and the head of the first one is masked.  A full
    layer (``group`` None) never reads ``lo_ref``: its walk starts at 0."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    tokens = pages * blk
    kv_len = len_ref[b]
    n_cb = (kv_len + tokens - 1) // tokens
    nxt_row = jnp.minimum(b + 1, n_rows - 1)
    nxt_live = (b + 1 < n_rows) & (len_ref[nxt_row] > 0)
    windowed = group is not None
    # first compute block / first position of a row's walk
    lo_of = lambda row: lo_ref[row] if windowed else 0
    cb0_of = lambda row: (jnp.maximum(lo_ref[row], 0) // tokens
                          if windowed else 0)
    cb0 = cb0_of(b)

    def copies(row, cb, slot, go):
        """Start (``go`` a traced bool) or wait for (``go`` None) the
        copies of compute block ``cb`` of ``row`` into half ``slot``: one
        per pool block that holds a valid position, so a wait meets the
        same set its start issued."""
        for p in range(pages):                  # static
            j = cb * pages + p
            live = j * blk < len_ref[row]
            if windowed:
                live = live & ((j + 1) * blk > lo_of(row))

            @pl.when(live if go is None else live & go)
            def _():
                page = bt_ref[row, jnp.minimum(j, n_b - 1)]
                for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    cp = pltpu.make_async_copy(src.at[page], dst.at[slot, p],
                                               sem.at[slot])
                    if go is None:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _first_row():
        st_ref[0] = 0
        st_ref[1] = 0

    # a row whose EVERY pool column is masked (cur == 0: fresh slot,
    # parked slot) leaves this: m = NEG_INF, l = 0, acc = 0 —
    # merge_attention_partials weights it out against the buffer partial,
    # which always holds the freshly-written position
    m_out[...] = jnp.full_like(m_out, NEG_INF)
    l_out[...] = jnp.zeros_like(l_out)
    acc_out[...] = jnp.zeros_like(acc_out)

    slot0 = st_ref[0]
    copies(b, cb0, slot0, st_ref[1] == 0)

    def compute_block(i, carry):
        slot = (slot0 + i - cb0) % 2
        more = i + 1 < n_cb
        copies(jnp.where(more, b, nxt_row),
               jnp.where(more, i + 1, cb0_of(nxt_row)),
               1 - slot, more | nxt_live)
        copies(b, i, slot, None)

        r_pad = q_ref.shape[2]
        col0 = i * tokens
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (r_pad, tokens), 1)
        valid = col < kv_len
        if windowed:
            lo = lo_ref[b]
            if group:  # query row r is position r // group of the segment
                lo = lo + jax.lax.broadcasted_iota(
                    jnp.int32, (r_pad, tokens), 0) // group
            valid = valid & (col >= lo)
        if quant:
            # a stale block's scales are whatever its table entry points
            # at: NaN·0 would reach acc, so select them away here; on the
            # scores `valid` does it below
            ks_row = lambda h: ks_ref[0, h, pl.ds(i, 1), :]  # [1, tokens]
            vs_row = lambda h: jnp.where(
                valid[:1], vs_ref[0, h, pl.ds(i, 1), :], 0.0)
        else:
            # int8 garbage is finite and meets p == 0; a float pool's
            # stale V may hold NaN, and 0·NaN would reach acc: zero the
            # pool blocks of this compute block that were not fetched
            for p in range(pages):
                dead = col0 + p * blk >= kv_len
                if windowed:
                    dead = dead | (col0 + (p + 1) * blk <= lo_ref[b])

                @pl.when(dead)
                def _():
                    v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

        for h in range(hkv):                    # static: Hkv is 1..8
            q = q_ref[0, h]                                 # [R, D]
            k = k_buf[slot, :, :, h * d:(h + 1) * d].reshape(tokens, d)
            v = v_buf[slot, :, :, h * d:(h + 1) * d].reshape(tokens, d)
            if quant:
                # int8 pool blocks: HALF the bytes cross HBM; the cast to
                # the compute dtype happens here in VMEM (int8 values are
                # exact in bf16 — 8 mantissa bits cover +-127)
                k = k.astype(q.dtype)
                v = v.astype(q.dtype)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [R, tokens]
            if quant:
                logits = logits * ks_row(h)
            logits = logits * scale
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev = m_out[0, h, :, :1]                     # [R, 1]
            l_prev = l_out[0, h, :, :1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(logits - m_cur)
            p = jnp.where(valid, p, 0.0)                    # masked: l += 0
            l_out[0, h] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                l_out.shape[2:])
            if quant:
                p = p * vs_row(h)
            acc_out[0, h] = acc_out[0, h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_out[0, h] = jnp.broadcast_to(m_cur, m_out.shape[2:])
        return carry

    jax.lax.fori_loop(cb0, n_cb, compute_block, 0)

    @pl.when(n_cb > 0)
    def _hand_over():
        st_ref[0] = (slot0 + n_cb - cb0) % 2
        st_ref[1] = nxt_live.astype(jnp.int32)


def paged_scale_rows(scale: jax.Array, block_tables: jax.Array,
                     pool: jax.Array) -> jax.Array:
    """An int8 pool's scale plane ``[N, Hkv·block]`` → the lane rows the
    kernel reads, ``[B, Hkv, n_cb, tokens]``, through ``block_tables [B,
    nb]``: a gather of whole pages (a lane row each, 32 bytes a token),
    then heads before blocks.  ``pool [N, block, Hkv·D]`` is the K or V
    tensor the plane belongs to (its shape and type set the compute
    block).  The pool and the tables do not change inside a decode chunk,
    so the chunk makes these once, outside its scan."""
    _, blk, folded = pool.shape
    b, nb = block_tables.shape
    hkv = scale.shape[1] // blk
    pages = paged_pages_per_step(blk, nb, hkv, folded // hkv, pool.dtype)
    n_cb = -(-nb // pages)
    bt = jnp.pad(block_tables.astype(jnp.int32),
                 ((0, 0), (0, n_cb * pages - nb)))
    # (table entries are pool ids by construction: no fill for strays)
    x = jnp.take(scale, bt, axis=0, mode="clip")  # [B, n_cb·pages, Hkv·blk]
    x = x.reshape(b, n_cb, pages, hkv, blk).transpose(0, 3, 1, 2, 4)
    return x.reshape(b, hkv, n_cb, pages * blk)


def paged_attention_partial(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    scale_rows: Optional[Tuple[jax.Array, jax.Array]] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    q_pos: Optional[jax.Array] = None,
):
    """In-place paged decode attention over key set ``[0, lengths[b])``,
    returned as the online-softmax partial ``(acc [B,S,H,D] f32
    unnormalised, m [B,S,H] f32, l [B,S,H] f32)`` —
    ``dot_product_attention_partial``'s contract, so it merges with the
    chunk-buffer partial via ``merge_attention_partials`` unchanged.

    ``q [B, S, H, D]``: S = 1 for a plain decode step, K+1 for a
    speculative multi-query verify (every row attends the same pool
    prefix; the in-segment causal half lives in the buffer partial).
    ``pool_k/pool_v [N, block, Hkv·D]`` are the POOL tensors as they rest
    (``llama.init_kv_pool``: heads folded into lanes) — read through
    ``block_tables [B, nb]`` in place, never gathered into a dense
    per-row view and never re-laid: the kernel's operand IS the pool.
    ``lengths [B]``: each row's valid prefix (the slot's ``cur``
    frontier); idle table entries may point anywhere (the reserved block
    0 included) — blocks at or past ``lengths`` are neither fetched nor
    computed.  ``scale_rows``: an int8 pool's per-vector dequant scales
    as the kernel reads them, ``paged_scale_rows`` of the K and of the V
    plane ``[N, Hkv·block]`` through the same tables (a decode chunk
    makes them once, outside its scan: the pool is frozen while its
    steps run) — dequant happens IN the kernel, so int8 halves the HBM
    bytes decode actually moves.  GQA (Hkv < H) walks kv heads inside
    the kernel body with the whole q group as rows of one matmul per
    head.

    ``window`` (static) with ``q_pos [B]`` (the position of each row's
    first query; query ``j`` of a multi-query segment sits at ``q_pos +
    j``): a window layer's key set is ``[max(0, q_pos + j - window + 1),
    lengths[b])`` — the kernel starts at the pool block that holds the
    oldest visible key and reads at most ``(window - 2) // block + 2``
    pool blocks a row, whatever the context.

    VMEM: two halves of ``paged_pages_per_step`` pool blocks of K and V
    (1 MB at the int8 7B shape) + the q rows, a row's scale rows and the
    f32 (Hkv x R x D) carry; sequence length is bounded by HBM only.
    """
    b, s, h, d = q.shape
    _, blk, folded = pool_k.shape
    if folded % d:
        raise ValueError(f"pool lanes {folded} are not whole heads of the "
                         f"q head_dim {d}")
    hkv = folded // d
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    nb = block_tables.shape[1]
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # rows of the per-kv-head matmul: the S query positions x the GQA
    # group, padded to the q dtype's sublane tile so the MXU operand is
    # whole tiles (padded rows compute garbage the slice below drops)
    rows = s * g
    r_pad = -(-rows // _sublane_tile(q.dtype)) * _sublane_tile(q.dtype)
    qr = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, rows, d)
    if r_pad != rows:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, r_pad - rows), (0, 0)))

    bt = block_tables.astype(jnp.int32)
    lens = jnp.minimum(lengths.astype(jnp.int32), nb * blk)
    first = ()
    if window is not None:
        # a row whose window no longer reaches the pool has no key here
        lo = q_pos.astype(jnp.int32) - (window - 1)
        lens = jnp.where(lo < lens, lens, 0)
        first = (jnp.where(lo < lens, lo, 0),)
    pages = paged_pages_per_step(blk, nb, hkv, d, pool_k.dtype)

    def slabs(x):
        # one pool block = one [block, lanes] slab the kernel copies whole:
        # the pool rests that way, so this is the identity at served widths
        # (whole 128-lane tiles); a narrower test pool pads its lanes, which
        # IS a copy of the pool
        return jnp.pad(x, ((0, 0), (0, 0), (0, -folded % 128)))

    scales = scale_rows or ()
    vmem_limit = None if interpret else paged_vmem_claim(
        pool_k.size * pool_k.dtype.itemsize, core_vmem_bytes())
    acc, m, l = _paged_call((bt, lens) + first, qr, slabs(pool_k),
                            slabs(pool_v), *scales, scale=scale, d=d,
                            pages=pages, interpret=interpret,
                            group=None if window is None else (
                                g if s > 1 else 0),
                            vmem_limit=vmem_limit)
    m, l = m[..., 0], l[..., 0]

    # [B, Hkv, R(, D)] → [B, S, H(, D)] (drop row padding first)
    acc = acc[:, :, :rows].reshape(b, hkv, s, g, d)
    acc = acc.transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)
    to_bsh = lambda x: (x[:, :, :rows].reshape(b, hkv, s, g)
                        .transpose(0, 2, 1, 3).reshape(b, s, h))
    return acc, to_bsh(m), to_bsh(l)


# The kernel call alone is jitted: a program's 28 layers then trace and
# lower the kernel body once (traced per layer it cost the decode program
# 40 s of set-up on the chip's host), and what surrounds the call still
# fuses with each layer's own operations.
@functools.partial(jax.jit, static_argnames=("scale", "d", "pages",
                                             "interpret", "group",
                                             "vmem_limit"))
def _paged_call(prefetch, qr, k_slabs, v_slabs, *scales, scale, d, pages,
                interpret, group=None, vmem_limit=None):
    """``prefetch``: the scalar-prefetch operands — tables and lengths,
    and for a window layer (``group`` set) each row's first position.
    ``vmem_limit``: ``paged_vmem_claim``'s verdict."""
    b, hkv, r_pad, _ = qr.shape
    blk = k_slabs.shape[1]
    nb = prefetch[0].shape[1]
    quant = bool(scales)
    row_map = lambda bi, *prefetch_refs: (bi, 0, 0, 0)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hkv, r_pad, d), row_map), in_hbm, in_hbm]
    if quant:
        in_specs += [pl.BlockSpec((1,) + scales[0].shape[1:], row_map)] * 2
    else:
        # dummy scalar operands keep ONE kernel arity (the kernel ignores
        # them when quant=False; SMEM spec so no tile constraints apply)
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        scales = (jnp.zeros((1,), jnp.float32),) * 2
    kv_buf = pltpu.VMEM((2, pages) + k_slabs.shape[1:], k_slabs.dtype)

    # m/l leave lane-broadcast ([R, 128]): a [R]-vector output block
    # would need a sublane→lane relayout
    out_specs = [pl.BlockSpec((1, hkv, r_pad, d), row_map),
                 pl.BlockSpec((1, hkv, r_pad, 128), row_map),
                 pl.BlockSpec((1, hkv, r_pad, 128), row_map)]
    kernel = functools.partial(_paged_attn_kernel, scale=scale, blk=blk,
                               pages=pages, n_b=nb, hkv=hkv, d=d,
                               quant=quant, group=group)
    if group is None:
        full, kernel = kernel, lambda bt_ref, len_ref, *refs: full(
            bt_ref, len_ref, None, *refs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[kv_buf, kv_buf, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((2,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, r_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r_pad, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, r_pad, 128), jnp.float32),
        ],
        # rows in order on one core: a row's last compute block starts
        # the next row's first copies, and the scratch half carries over
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        name="paged_attention",
        interpret=interpret,
    )(*prefetch, qr, k_slabs, v_slabs, *scales)


def paged_flash_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Normalised in-place paged attention ``[B, S, H, D]`` (the
    standalone/microbench surface; the serving path merges the partial
    with its chunk-buffer half instead — see ``paged_attention_partial``).
    ``k_scale``/``v_scale``: an int8 pool's scale planes ``[N, Hkv·block]``
    as they rest.  Rows with ``lengths[b] == 0`` return zeros (no valid
    key)."""
    rows = None
    if k_scale is not None:
        rows = (paged_scale_rows(k_scale, block_tables, pool_k),
                paged_scale_rows(v_scale, block_tables, pool_v))
    acc, _, l = paged_attention_partial(
        q, pool_k, pool_v, block_tables, lengths, scale=scale,
        scale_rows=rows, interpret=interpret)
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def paged_bytes_accounting(*, n_valid_blocks: int, blocks_per_seq: int,
                           block: int, kvh: int, hd: int, esize: int,
                           scale_bytes: int, n_steps: int) -> dict:
    """Per-decode-step HBM bytes for ONE slot's pool reads, gather vs
    in-place — the shared arithmetic ``tools/bench_flash.py --paged`` and
    ``bench_llm --paged`` both report (and the microbench asserts on), so
    the two can never disagree.

    Gather (the ``_pool_gather_body`` path) pays, per chunk of
    ``n_steps``: read EVERY table-mapped block + write the dense
    ``[max_seq]`` copy once, then read the full dense copy per step.
    In place pays: read the valid blocks per step and nothing else — the
    kernel copies only the pool blocks a row has.  Bytes are K + V per
    position (``esize`` each) plus the int8 layout's per-vector scales
    (``scale_bytes``: 2 x 4 f32, or 0)."""
    pos_bytes = kvh * (2 * hd * esize + scale_bytes)
    full = blocks_per_seq * block * pos_bytes          # whole table span
    valid = n_valid_blocks * block * pos_bytes
    gather_chunk = 2 * full + n_steps * full           # copy (r+w) + reads
    inplace_chunk = n_steps * valid
    return {
        "gather_step_bytes": gather_chunk / max(1, n_steps),
        "paged_flash_step_bytes": inplace_chunk / max(1, n_steps),
        "gather_chunk_bytes": gather_chunk,
        "paged_flash_chunk_bytes": inplace_chunk,
    }
