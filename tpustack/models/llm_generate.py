"""Autoregressive generation engine (prefill + KV-cache decode) for Llama/Qwen.

TPU-native replacement for the llama.cpp server's generate loop (reference
``cluster-config/apps/llm/deployment.yaml:61-84``: Qwen2.5-7B GGUF,
``--ctx-size 4096 --n-gpu-layers 35``).  Design for XLA:

- **Prefill** pads the prompt to a power-of-two bucket and runs one batched
  pass (MXU-bound); each bucket compiles once.
- **Decode** is a single static-shape token step against a ``max_seq`` KV
  cache (``lax.dynamic_update_slice``), compiled once, with donated caches so
  XLA updates them in place in HBM.
- **Sampling** (greedy / temperature / top-k) happens inside the jitted step
  with a threaded PRNG key — no host round-trip per token.

No quantisation or CPU layer offload: bf16 on a 16 GB-HBM chip holds 7B whole
(the reference's ``--n-gpu-layers 35`` split was a 6 GB-VRAM workaround).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpustack.models.llama import (LlamaConfig, LlamaModel, init_kv_caches,
                                   is_scale_key, pool_lines, pool_rows)
from tpustack.utils import get_logger

log = get_logger("models.llm_generate")


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    temperature: float = 0.8
    top_k: int = 40
    greedy: bool = False


def resolve_paged_flash(env=None, mesh=None) -> bool:
    """The ``TPUSTACK_PAGED_FLASH`` verdict for a paged engine: read the
    KV pool blocks in place via the scalar-prefetch Pallas kernel
    (``ops.pallas.flash_attention.paged_attention_partial``) instead of
    gathering a dense per-slot copy every chunk.

    ``auto`` (the default) turns the kernel on for real TPU backends and
    off on CPU/interpret (where the gather path's XLA ops are faster than
    an interpreted kernel grid) — tests force it on explicitly.  Under a
    tp mesh ``auto`` stays on the gather path too: the kernel's GSPMD
    partition over the head-axis-sharded pool is compile-verified in
    interpret mode (the kernel grid walks kv heads, so the shard split is
    natural) but not yet measured on multi-chip hardware; forcing ``1``
    overrides.  ``0`` is the bisection flag — byte-for-byte the gather
    engine."""
    from tpustack.utils import knobs

    val = knobs.get_str("TPUSTACK_PAGED_FLASH", env=env).strip().lower()
    if val in ("", "auto"):
        return jax.default_backend() == "tpu" and mesh is None
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"TPUSTACK_PAGED_FLASH={val!r} is not auto or a "
                     "boolean (want auto, 1/true/yes/on or 0/false/no/off)")


@jax.named_scope("sample")
def _advance_keys(keys):
    """Advance per-row PRNG chains ``[B, 2]`` one step: returns
    ``(step_keys [B, 2], next_keys [B, 2])``.  Row i's chain is seeded at
    admission from its request seed and advanced once per generated token,
    so the k-th token of a request always draws from the same key no
    matter when the request was admitted or who its batch peers are."""
    split = jax.vmap(jax.random.split)(keys)          # [B, 2, 2]
    return split[:, 0], split[:, 1]


def _page_align(x, shift, blk: int, nj: int, axis: int, fill):
    """A run of tokens along ``axis`` of ``x [R, ...]`` laid where its
    pages hold it: ``[R, .., L, ..] → [R, .., nj * blk, ..]``, token ``t``
    of row ``r`` at ``t + shift[r]`` (``shift < blk``), ``fill`` around.
    ``shift`` None: the runs start on a page's first slot."""
    span, L = nj * blk, x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, span - L) if shift is None else (blk, span - L)
    x = jnp.pad(x, pad, constant_values=fill)
    if shift is None:
        return x
    return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
        row, blk - s, span, axis - 1))(x, shift)


# Jitted by itself, like the paged kernel's call: a program's 4 x layers
# pool tensors then trace and lower this body once a shape, not once each.
@jax.jit
def _write_pages(dst, rows, page, live, shift):
    """One pool tensor's page writes (``_pool_scatter_body``): ``dst`` K/V
    ``[N, blk, kvh·hd]`` with token rows ``rows [R, L, kvh·hd]``, or a
    scale plane ``[N, kvh·blk]`` with ``rows [R, kvh, L]``; ``page [R·nj]``
    the pages' pool ids (out of range: dropped), ``live [R·nj, blk]`` the
    tokens to lay over what a page holds, ``shift [R]`` each run's offset
    in its first page (None: none)."""
    R, blk = rows.shape[0], live.shape[1]
    nj = live.shape[0] // R
    old = jnp.take(dst, page, axis=0, mode="clip")            # [R·nj, page]
    if dst.ndim == 2:                   # a page is [kvh, blk], folded
        new = _page_align(rows, shift, blk, nj, 2, 0)         # [R, kvh, span]
        new = new.reshape(R, -1, nj, blk).swapaxes(1, 2)
        new = jnp.where(live[:, None, :], new.reshape(R * nj, -1, blk),
                        old.reshape(R * nj, -1, blk))
    else:
        new = _page_align(rows, shift, blk, nj, 1, 0)
        new = jnp.where(live[:, :, None], new.reshape(R * nj, blk, -1), old)
    return dst.at[page].set(new.reshape(old.shape), mode="drop",
                            unique_indices=True)


class Generator:
    """Holds params + compiled prefill/decode programs."""

    def __init__(self, config: LlamaConfig, params: Optional[Dict] = None,
                 dtype=jnp.bfloat16, seed: int = 0, mesh=None, rules=None,
                 shard_kv: bool = True):
        """``mesh``: optional ``jax.sharding.Mesh`` — tensor-parallel
        serving.  Params shard per ``rules`` (default ``LLAMA_RULES``: qkv/
        gate column-wise, o/down row-wise over the ``tp`` axis) and every
        compiled prefill/decode program is GSPMD-partitioned across the mesh,
        with XLA inserting the ICI collectives — this is how models larger
        than one chip's HBM serve (e.g. 70B over v5e-8), the inference-side
        counterpart of the training mesh (SURVEY §2.10).

        ``shard_kv`` (with a mesh): host-allocated KV caches and paged pool
        tensors are placed EXPLICITLY head-axis-sharded over ``tp``
        (``kv_mesh`` — passed by the serving call sites into
        ``init_kv_caches``/``init_kv_pool``), so the per-chip KV HBM bill
        divides by tp deterministically instead of riding GSPMD's
        propagation choice.  False (``LLM_SHARD_KV=0``) is the bisection
        path: mesh-partitioned compute, compiler-placed caches — the
        pre-tp-serving behavior."""
        self.cfg = config
        if mesh is not None and config.moe is not None:
            raise NotImplementedError(
                "a routed-expert model under a tp mesh: the expert stacks "
                "have no partition rule and moe_gmm no shard_map yet")
        self.model = LlamaModel(config, dtype=dtype, tp_mesh=mesh)
        self.cache_dtype = dtype
        self.mesh = mesh
        #: mesh the serving KV substrate shards over (None = unsharded
        #: caches even when compute is mesh-partitioned)
        self.kv_mesh = mesh if shard_kv else None
        if self.kv_mesh is not None and "tp" in self.kv_mesh.axis_names:
            tp_ways = int(self.kv_mesh.shape["tp"])
            if tp_ways > 1 and config.n_kv_heads % tp_ways:
                # GQA at high tp: the KV substrate REPLICATES per chip —
                # correct, but the per-chip HBM bill does not divide; size
                # batch/ctx from the replicated figure (/props reports it)
                log.warning(
                    "%d KV heads do not divide tp=%d: serving KV caches "
                    "replicate per chip (weights still shard)",
                    config.n_kv_heads, tp_ways)
        if params is None:
            log.warning("Initialising %s-layer LLM with RANDOM weights", config.n_layers)
            if config.quant:
                params = self._random_quantized_params(config, dtype, seed)
            else:
                params = jax.jit(self.model.init)(
                    jax.random.PRNGKey(seed),
                    jnp.zeros((1, 8), jnp.int32))["params"]
        if mesh is not None:
            from tpustack.parallel.sharding import (LLAMA_RULES,
                                                    match_partition_rules,
                                                    shard_params)

            specs = match_partition_rules(rules or LLAMA_RULES, params)
            params = shard_params(params, specs, mesh)
        self.params = params
        # device-side memo of hot prefix-cache entries (HBM-resident): a
        # repeat hit on the same stored prefix skips the host→device
        # transfer — see _prefix_to_device
        import collections as _collections

        self._prefix_dev: "Any" = _collections.OrderedDict()
        self.prefix_dev_cap = 4
        #: the ``_ride_scan_paged`` shapes an engine has run on this
        #: generator (``ContinuousEngine._ride_dispatch`` runs each once
        #: before any request rides it)
        self.rides_compiled: set = set()

    #: bytes of float random weights materialised per init program before
    #: they are quantised (see _random_quantized_params)
    RANDOM_INIT_CHUNK_BYTES = 4 << 30

    @classmethod
    def _random_quantized_params(cls, cfg: LlamaConfig, dtype,
                                 seed: int) -> Dict:
        """Random int8 weights: random-init the float twin, then quantise
        (int8 kernels themselves init to zeros — a degenerate perf model).

        The twin of a 7B model is 30 GB (flax parameters are float32),
        which no 16 GB chip holds (RESOURCE_EXHAUSTED on a v5e, PR 21), so
        the twin never exists whole: its top-level modules are initialised
        ``RANDOM_INIT_CHUNK_BYTES`` at a time by programs that return ONLY
        that slice of ``init``'s tree (flax derives each parameter's key
        from its path, and XLA prunes the unused RNG, so every value is the
        one the whole-tree init would produce) and each slice is quantised
        before the next is made.  A model under the chunk size — every
        test preset — is one slice: the whole-tree program, as before."""
        from tpustack.ops.quant import quantize_params

        t0 = time.time()
        twin = LlamaModel(dataclasses.replace(cfg, quant=None), dtype=dtype)
        key = jax.random.PRNGKey(seed)
        init = lambda rng: twin.init(
            rng, jnp.zeros((1, 8), jnp.int32))["params"]
        chunks, size = [[]], 0
        for name, sub in jax.eval_shape(init, key).items():
            nbytes = sum(x.size * x.dtype.itemsize
                         for x in jax.tree.leaves(sub))
            if chunks[-1] and size + nbytes > cls.RANDOM_INIT_CHUNK_BYTES:
                chunks.append([])
                size = 0
            chunks[-1].append(name)
            size += nbytes
        params = {}
        for names in chunks:
            part = jax.jit(lambda rng, names=names: {
                n: v for n, v in init(rng).items() if n in names})(key)
            params.update(quantize_params(
                part, quantize_embed=not cfg.tie_embeddings))
        log.info("Random int8 weights: %d init+quantise slice(s) in %.1fs",
                 len(chunks), time.time() - t0)
        return params

    @staticmethod
    def _quantize(cfg: LlamaConfig, params: Dict) -> Dict:
        from tpustack.ops.quant import quantize_params

        t0 = time.time()
        # consumes the bf16 tree (HBM peak); tied-embedding models keep the
        # bf16 table — the model uses embed.attend for logits
        params = quantize_params(params,
                                 quantize_embed=not cfg.tie_embeddings)
        log.info("Quantised weights to int8 in %.1fs", time.time() - t0)
        return params

    @classmethod
    def from_checkpoint(cls, config: LlamaConfig, model_dir: str,
                        dtype=jnp.bfloat16, mesh=None,
                        rules=None, shard_kv: bool = True) -> "Generator":
        """Load HF safetensors without materialising a random template first
        (jax.eval_shape gives the converter shapes at zero device cost).
        With ``config.quant`` the bf16 checkpoint is quantised in one jitted
        pass at load time — the online analog of the reference's offline
        GGUF conversion step.

        With ``mesh``, every tensor goes host → its own shard set as it is
        read (never the whole model on one device), so checkpoints larger
        than a single chip's HBM load as long as the bf16 tree fits the
        MESH's combined HBM; quantisation then runs as a GSPMD program over
        the sharded tree."""
        from tpustack.models.llama_weights import load_llama_safetensors

        bf16_cfg = dataclasses.replace(config, quant=None)
        model = LlamaModel(bf16_cfg, dtype=dtype)
        tmpl = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32)))["params"]
        shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from tpustack.parallel.sharding import (LLAMA_RULES,
                                                    match_partition_rules)

            specs = match_partition_rules(rules or LLAMA_RULES, tmpl)
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                     is_leaf=lambda x: not isinstance(x, dict))
        params = load_llama_safetensors(model_dir, config, tmpl, dtype=dtype,
                                        shardings=shardings)
        if config.quant:
            params = cls._quantize(config, params)
        return cls(config, params=params, dtype=dtype, mesh=mesh, rules=rules,
                   shard_kv=shard_kv)

    def _apply_counted(self, params, *args):
        """``model.apply`` for the paged serving programs: ``(logits,
        caches, moe)`` with ``moe`` the routed-expert layers' counters
        summed over the sparse layer-calls this pass made — int32
        ``[pairs, experts_touched, max_expert_tokens]`` — or None for a
        model without such a layer (its programs then trace as they did)."""
        (logits, caches), extra = self.model.apply(
            {"params": params}, *args, mutable=["moe_stats"])
        counts = jax.tree.leaves(extra)
        return logits, caches, (sum(counts) if counts else None)

    # -------------------------------------------------------------- compiled
    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
    def _prefill(self, params, tokens, length, caches):
        """tokens [B, P] padded; valid prefix ``length [B]``. Returns
        (logits at each row's last real token ``[B, V]``, caches).

        No mask: prefill attention is in-bucket causal (see LlamaAttention) —
        rows past ``length`` are garbage the ``length - 1`` gather never
        reads, and the cache slots they write are masked/overwritten by
        decode before they can be attended.  The hidden-state gather happens
        BEFORE the lm_head (``logits_at``): full [B, P, vocab] f32 logits at
        long context would dwarf the model itself (~10 GB at 16k for Qwen).
        Caches are donated — prefill writes them in place.
        """
        b, p = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(p), (b, p))
        logits, caches = self.model.apply(
            {"params": params}, tokens, positions, caches, 0, None,
            length - 1)
        return logits[:, 0], caches

    #: chunk size of the solo and static-batch routes' long prompts — one 8k
    #: chunk's activations (~1.3 GB of gate/up transients at 7B) bound
    #: prefill memory however long the prompt; a single-shot 32k-bucket
    #: program would need ~23 GB
    PREFILL_CHUNK = 8192

    #: chunk a cold admission's program walks its bucket in
    #: (``_admit_fused_paged``): a bucket above it runs chunk-major and
    #: stops at its longest row's last chunk, so its work follows the
    #: prompt, not the power-of-two bucket.  A constant read off the chip,
    #: not a knob — the smallest chunk at which (a) ONE row's matmuls stay
    #: compute-bound with margin: int8 weights give 2·C flop a weight byte
    #: against the v5e's ridge of 197 TFLOP/s ÷ 819 GB/s = 240, so C = 512
    #: stands at 4.3 ridges, and (b) the k-streaming kernel keeps a whole q
    #: block (``flash_attention``: K/V are re-streamed once a q block).
    #: Read on a v5e at 7B (PERF.md §6, PR 34): a row's 512 tokens cost
    #: 44.5 ms in chunks of 512 and of 1,024 alike, 51.4 ms in chunks of
    #: 256 (2.1 ridges: the margin is gone); padding left is under one
    #: chunk a group, so the smallest chunk that holds the rate wins.
    ADMIT_CHUNK = 512

    #: prompt tokens a decode step carries of a riding admission
    #: (``_ride_scan_paged``; the engine rounds it to whole pool blocks).
    #: A constant, not a knob: a step's int8 weight pass does 2·(B + S)
    #: flop a weight byte against the v5e's ridge of 240 (above), so some
    #: 120 tokens go through a pass the decode rows stream anyway; two
    #: 64-token blocks sit at the ridge beside 8 rows.  Fewer tokens a
    #: step only add steps to a rider's first token: read on a v5e at 7B
    #: (PERF.md §6), 64-token segments took a 350-token prompt six steps,
    #: 115 ms to its first token against the single shot's 75.
    RIDE_SEGMENT = 128

    def _prefill_chunk_body(self, params, tokens, offset, length, caches):
        """Traced body of one prefill chunk: rows at global positions
        offset + i attend the whole cache prefix (flash, traced offset), in
        the cache's own type — an int8 line is attended as quantised, as
        every warm start and every decode step attends it.  Returns
        ``(logits, caches, moe)``: logits at ``length - 1`` clipped into
        this chunk (garbage except on the chunk holding the row's last real
        token), ``moe`` as ``_apply_counted`` gives it.  Single source of
        truth for the host-loop (``_prefill_chunk``) and in-program
        (``_prefill_walk_body``) drivers."""
        b, s = tokens.shape
        positions = offset + jnp.broadcast_to(jnp.arange(s), (b, s))
        local_last = jnp.clip(length - 1 - offset, 0, s - 1)
        logits, caches, moe = self._apply_counted(
            params, tokens, positions, caches, offset, None, local_last)
        return logits[:, 0], caches, moe

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(5,))
    def _prefill_chunk(self, params, tokens, offset, length, caches):
        """One dispatch per chunk (the big-suffix loop of ``_prefill_from``);
        every chunk reuses ONE compiled program — see _prefill_chunk_body."""
        return self._prefill_chunk_body(params, tokens, offset, length,
                                        caches)[:2]

    def _prefill_walk_body(self, params, tokens, length, caches, chunk: int):
        """Traced: a whole chunk-major prefill inside its caller's program.
        ``tokens [B, n · chunk]`` run as ``[B, chunk]`` segments at offset
        ``i · chunk`` through ONE traced ``_prefill_chunk_body`` — a
        ``lax.fori_loop`` whose bound is an operand: ``ceil(max(length) /
        chunk)``, the longest row's last chunk.  Chunks past it hold only
        padding and are never computed (their cache positions stay as
        ``caches`` came: no row reads them before decode writes them), so
        one compiled program per shape does work that follows the prompts.
        One chunk's activations are live at a time.  Returns ``(logits [B,
        V], caches, moe, chunks)``: each row's logits from the chunk that
        holds its last real token, the routed-expert counters summed over
        the chunks that ran (None without such a layer), and the trip
        count as an int32 scalar."""
        b, width = tokens.shape
        assert width % chunk == 0, (width, chunk)
        chunks = jnp.minimum(-(-jnp.max(length) // chunk),
                             width // chunk).astype(jnp.int32)
        moe0 = (None if self.cfg.moe is None
                else jnp.zeros((3,), jnp.int32))

        def body(i, carry):
            out, caches, moe = carry
            offset = i * chunk
            seg = jax.lax.dynamic_slice_in_dim(tokens, offset, chunk, axis=1)
            logits, caches, counts = self._prefill_chunk_body(
                params, seg, offset, length, caches)
            if counts is not None:
                moe = moe + counts
            hit = (length - 1 >= offset) & (length - 1 < offset + chunk)
            return jnp.where(hit[:, None], logits, out), caches, moe

        out0 = jnp.zeros((b, self.cfg.vocab_size), jnp.float32)
        out, caches, moe = jax.lax.fori_loop(0, chunks, body,
                                             (out0, caches, moe0))
        return out, caches, moe, chunks

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
    def _prefill_walk(self, params, tokens, length, caches):
        """Whole chunked prefill of the solo and static-batch routes in ONE
        dispatch: ``_prefill_walk_body`` over PREFILL_CHUNK-sized segments
        of caller-held ``caches`` (a host loop paid a dispatch round-trip
        per chunk).  ``tokens [B, bucket]``; a bucket capped at a ``max_seq``
        that is no multiple of the chunk is padded to whole chunks in here,
        tokens and cache lines alike, as ``_admit_fused_paged`` pads its
        own."""
        C = self.PREFILL_CHUNK
        pad = -tokens.shape[1] % C
        seq = lambda t, n: ((0, 0), (0, n)) + ((0, 0),) * (t.ndim - 2)
        if pad:
            tokens = jnp.pad(tokens, seq(tokens, pad))
            caches = jax.tree.map(lambda t: jnp.pad(t, seq(t, pad)), caches)
        logits, caches = self._prefill_walk_body(params, tokens, length,
                                                 caches, C)[:2]
        if pad:
            caches = jax.tree.map(lambda t: t[:, :t.shape[1] - pad], caches)
        return logits, caches

    #: score-matrix budget (elements) under which a suffix prefill runs as
    #: ONE explicit-mask XLA attention dispatch over the full cache instead
    #: of the k-streaming flash chunk loop: at the prefix-cache's typical
    #: shapes (a few hundred uncached tokens over a 4k cache) the
    #: materialised [s, max_seq] scores are tiny and XLA's fused attention
    #: beats the flash kernel's fixed overhead (and its CPU interpret mode,
    #: which the tiny-preset tests run)
    MASKED_PREFILL_MAX = 1 << 21

    def _prefill_masked_body(self, params, tokens, base, length, caches):
        """Traced body of the small-suffix prefill: rows at global
        positions ``base + i`` attend ``[0, base + i]`` via an explicit
        mask (the full-cache XLA attention path) — semantics identical to
        ``_prefill_chunk``.  Shared by ``_prefill_masked`` and the fused
        restore+prefill program.  Returns ``(logits, caches, moe)``."""
        b, s = tokens.shape
        positions = base + jnp.broadcast_to(jnp.arange(s), (b, s))
        mask = (jnp.arange(self.cfg.max_seq)[None, None, None, :]
                <= positions[:, None, :, None])
        local_last = jnp.clip(length - 1 - base, 0, s - 1)
        logits, caches, moe = self._apply_counted(
            params, tokens, positions, caches, base, mask, local_last)
        return logits[:, 0], caches, moe

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(5,))
    def _prefill_masked(self, params, tokens, base, length, caches):
        """One-dispatch small-suffix prefill — see _prefill_masked_body."""
        return self._prefill_masked_body(params, tokens, base, length,
                                         caches)[:2]

    @functools.partial(jax.jit, static_argnums=(0,))
    def _prefill_prefix_fused(self, params, tokens, base, length, prefix):
        """ONE-dispatch warm start: fresh row caches created in-graph →
        cached prefix written into ``[0, plen)`` → masked suffix prefill.
        This keeps a prefix-cache hit at the SAME dispatch count as a cold
        short-prompt prefill, so TTFT strictly improves even when the
        model is dispatch-bound (tiny/CPU shapes), not just FLOP-bound."""
        b = tokens.shape[0]
        caches = init_kv_caches(self.cfg, b, dtype=self.cache_dtype)
        caches = self._restore_body(caches, prefix)
        return self._prefill_masked_body(params, tokens, base, length,
                                         caches)[:2]

    def _prefill_from(self, tokens: np.ndarray, base: int, length, caches):
        """Prefill ``tokens [B, bucket]`` starting at cache position
        ``base``, attending the already-populated cache ``[0, base)``: the
        prefix-cache suffix path — a restored cross-request KV prefix sits
        in ``[0, base)`` and only the uncached suffix pays prefill FLOPs —
        and the parked chunking's steps (the first at ``base`` 0).  A
        small suffix runs as one masked dispatch; a big one in
        PREFILL_CHUNK segments on the host, each reusing the one compiled
        ``_prefill_chunk`` program (``base`` is a traced offset, so a new
        prefix length never recompiles).  ``length`` stays the TRUE per-row
        prompt length (global), so logits land at ``length - 1``."""
        b, bucket = tokens.shape
        if base > 0 and bucket * self.cfg.max_seq <= self.MASKED_PREFILL_MAX:
            return self._prefill_masked(self.params, jnp.asarray(tokens),
                                        jnp.asarray(base, jnp.int32), length,
                                        caches)
        chunk = self.PREFILL_CHUNK
        out = None
        lo = 0
        while lo < bucket:  # final segment may be shorter (bucket capped at
            n = min(chunk, bucket - lo)  # a non-multiple max_seq): its own
            seg = jnp.asarray(tokens[:, lo:lo + n])  # (one) jit signature
            logits, caches = self._prefill_chunk(
                self.params, seg, jnp.asarray(base + lo, jnp.int32), length,
                caches)
            hit = (length - 1 >= base + lo) & (length - 1 < base + lo + n)
            out = logits if out is None else jnp.where(hit[:, None], logits, out)
            lo += n
        return out, caches

    # ------------------------------------------------- prefix-cache surgery
    #
    # Device side of the cross-request prefix KV cache
    # (tpustack.serving.prefix_cache): extract slices a finished prefill's
    # K/V rows to the host for insertion; restore writes a cached prefix
    # back into fresh row caches so admission prefills ONLY the uncached
    # suffix (_prefill_from with base = prefix length).  Both are generic
    # over the cache layout (bf16 k/v, or int8 + per-vector scales).

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _extract_kv(self, caches, row, start, n: int):
        """Slice cache row ``row`` positions ``[start, start + n)`` of every
        layer/tensor — the device half of a prefix-cache insert.  ``row``
        and ``start`` are traced (no recompile per slot or per boundary);
        ``n`` is static but chunk-snapped by the caller, so signatures stay
        bounded.  NOT donated: the caches keep serving decode; dispatch
        ordering guarantees this read completes before any later donating
        dispatch reuses the buffer."""

        def sl(x):
            idx = (row, start) + (jnp.zeros((), jnp.int32),) * (x.ndim - 2)
            return jax.lax.dynamic_slice(x, idx, (1, n) + x.shape[2:])[0]

        return [{k: sl(v) for k, v in layer.items()} for layer in caches]

    @staticmethod
    @jax.named_scope("kv_write")
    def _restore_body(row_caches, prefix):
        """Traced body of the prefix restore — see _restore_kv_rows."""

        def wr(dst, src):
            src = jnp.broadcast_to(src[None].astype(dst.dtype),
                                   (dst.shape[0],) + src.shape)
            return jax.lax.dynamic_update_slice(
                dst, src, (jnp.zeros((), jnp.int32),) * dst.ndim)

        return [{k: wr(layer[k], pre[k]) for k in layer}
                for layer, pre in zip(row_caches, prefix)]

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _restore_kv_rows(self, row_caches, prefix):
        """Write a cached prefix (per-layer ``[n, ...]`` arrays, host-fetched
        by ``_extract_kv`` earlier) into positions ``[0, n)`` of every row
        of fresh row caches — the device half of a prefix-cache hit.  The
        prefix KV is a pure function of (token ids, weights), so the
        restored rows are exactly what prefill would have written.  The
        small-suffix common case fuses this with the prefill itself
        (``_prefill_prefix_fused``); this standalone dispatch serves the
        big-suffix flash-chunk path."""
        return self._restore_body(row_caches, prefix)

    def _prefix_to_device(self, kv, key=None):
        """Host KV segment → device arrays, memoised by the store's stable
        path ``key`` (small LRU, ``prefix_dev_cap`` entries): the hottest
        prefixes stay HBM-resident, so a warm hit costs zero host→device
        KV traffic.  ``key=None`` (no identity) transfers uncached."""
        dev = self._prefix_dev.get(key) if key is not None else None
        if dev is None:
            dev = [{k: jnp.asarray(v) for k, v in layer.items()}
                   for layer in kv]
            if key is not None:
                self._prefix_dev[key] = dev
                while len(self._prefix_dev) > max(1, self.prefix_dev_cap):
                    self._prefix_dev.popitem(last=False)
        else:
            self._prefix_dev.move_to_end(key)
        return dev

    def extract_prefix_host(self, caches, row: int, start: int, n: int):
        """Host-side convenience: ``_extract_kv`` then fetch to numpy (the
        layout ``tpustack.serving.prefix_cache`` stores)."""
        if n <= 0:
            return []
        dev = self._extract_kv(caches, jnp.asarray(row, jnp.int32),
                               jnp.asarray(start, jnp.int32), n)
        return [{k: np.asarray(v) for k, v in layer.items()} for layer in dev]

    def _topk_scaled(self, logits, temperature, top_k):
        """Shared temperature/top-k filter: ``[B, V]`` f32 logits →
        ``[B, V]`` scaled logits with sub-threshold entries at -inf.

        ``temperature``/``top_k`` may be scalars or per-row ``[B]`` arrays —
        batched serving mixes requests with different sampling settings in
        one device step."""
        b = logits.shape[0]
        col = lambda x: jnp.broadcast_to(
            jnp.atleast_1d(jnp.asarray(x)), (b,))[:, None]  # [B, 1]
        temp, tk = col(temperature), col(top_k)
        scaled = logits / jnp.maximum(temp, 1e-4)
        # top-k with a traced k: take a static top-64 slate (descending),
        # threshold at the clamp(top_k)-th value per row; top_k<=0 disables.
        slate = min(64, self.cfg.vocab_size)
        topv = jax.lax.top_k(scaled, k=slate)[0]  # [B, slate] descending
        idx = jnp.clip(tk - 1, 0, slate - 1).astype(jnp.int32)
        kth = jnp.take_along_axis(topv, idx, axis=1)
        thresh = jnp.where(tk > 0, kth, -jnp.inf)
        return jnp.where(scaled >= thresh, scaled, -jnp.inf)

    @staticmethod
    def _greedy_gated(logits, gr, mixed_fn):
        """All-greedy fast path: when every row is greedy (the common
        serving mix, and every parked slot — parks set greedy) the
        top-k slate + categorical draw are dead weight — a
        ``lax.cond`` on ``all(greedy)`` skips them at RUNTIME, not trace
        time.  Measured on v5e (Qwen-7B int8, 8 slots, 152k vocab):
        736 → 753 tok/s steady aggregate (+2.4%/step)."""
        return jax.lax.cond(
            jnp.all(gr),
            lambda _: jnp.argmax(logits, axis=-1).astype(jnp.int32),
            mixed_fn, None)

    def _sample_from_logits(self, logits, key, temperature, top_k, greedy):
        """``[B, V]`` fp32 logits → ``[B]`` int32 token (traced; shared by the
        single-step and fused-scan decoders so they sample identically).
        ONE key draws the whole batch — the solo/static-batch chains."""
        b = logits.shape[0]
        gr = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(greedy)), (b,))

        def mixed(_):
            scaled = self._topk_scaled(logits, temperature, top_k)
            sampled = jax.random.categorical(key, scaled, axis=-1)
            return jnp.where(gr, jnp.argmax(logits, axis=-1),
                             sampled).astype(jnp.int32)

        with jax.named_scope("sample"):
            return self._greedy_gated(logits, gr, mixed)

    def _sample_from_logits_perrow(self, logits, keys, temperature, top_k,
                                   greedy):
        """``[B, V]`` fp32 logits + PER-ROW keys ``[B, 2]`` → ``[B]`` tokens.

        Each row draws from its own PRNG stream, so a sampled row's output
        is a function of (its seed, its token index) ONLY — independent of
        batch composition and admission timing.  This is what lets the
        server admit seeded-sampled requests into continuous-batching slots
        (greedy rows ignore the key entirely)."""
        b = logits.shape[0]
        gr = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(greedy)), (b,))

        def mixed(_):
            scaled = self._topk_scaled(logits, temperature, top_k)
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            return jnp.where(gr, jnp.argmax(logits, axis=-1),
                             sampled).astype(jnp.int32)

        with jax.named_scope("sample"):
            return self._greedy_gated(logits, gr, mixed)

    def _decode_logits(self, params, token, index, caches):
        """One cached decode step: ``[B,1]`` token → (``[B,V]`` f32, caches)."""
        b = token.shape[0]
        positions = jnp.broadcast_to(index, (b, 1))
        mask = (jnp.arange(self.cfg.max_seq)[None, None, None, :] <= index)
        logits, caches = self.model.apply(
            {"params": params}, token, positions, caches, index, mask)
        return logits[:, -1].astype(jnp.float32), caches

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
    def _decode_step(self, params, token, index, caches, key, temperature,
                     top_k, greedy):
        """One token in → caches updated in place → next token out."""
        logits, caches = self._decode_logits(params, token, index, caches)
        return self._sample_from_logits(logits, key, temperature, top_k,
                                        greedy), caches

    @staticmethod
    def _run_chunk_chain(scan, first_dev, consume, *, chunk: int,
                         budget: int, cache_room: int, cancel_check,
                         initial_stop: bool = False, depth: int = 2) -> None:
        """Pipelined decode-chunk chain — the shared driver of
        ``generate_fused`` and ``generate_batch``.

        Each scan's first token is the PREVIOUS scan's last column as a
        DEVICE array, so no host round-trip sits between chunk dispatches
        (the xprof trace of the un-pipelined loop showed the device idle
        between chunks); the host fetches one chunk behind the frontier
        and a stop costs at most ``depth`` speculative chunks of discarded
        device work.

        ``scan(first_tok_dev, dispatched) -> toks_dev [B, chunk]`` performs
        one fused dispatch (mutating caches/key in its closure);
        ``consume(block) -> bool`` ingests a fetched ``[B, chunk]`` numpy
        block and returns True to stop.  ``budget``: decode steps wanted
        beyond the already-known first token; ``cache_room``: steps the
        cache can still hold — a full chunk must fit or the chain drains
        (callers finish on their single-step tail path).
        """
        chain: List[Any] = []
        next_first = first_dev
        dispatched = 0
        stopped = initial_stop or budget <= 0
        while not stopped or chain:
            # polled before every fill AND every fetch: once dispatching
            # ends, the drain phase must still abandon in-flight chunks on
            # cancellation instead of consuming them
            if cancel_check is not None and cancel_check():
                chain.clear()
                break
            while (not stopped and len(chain) < depth
                   and dispatched < budget
                   and cache_room - dispatched >= chunk):
                if cancel_check is not None and cancel_check():
                    stopped = True
                    chain.clear()  # abandon: drop in-flight chunks unfetched
                    break
                toks = scan(next_first, dispatched)
                next_first = toks[:, -1:]
                chain.append(toks)
                dispatched += chunk
            if not chain:
                break
            # THE chain-boundary fetch: one sync per consumed chunk, with
            # `depth` more already dispatched behind it
            if consume(np.asarray(chain.pop(0))):  # tpulint: disable=TPL101
                stopped = True
                chain.clear()  # speculative chunks beyond the stop

    @functools.partial(jax.jit, static_argnums=(0, 9), donate_argnums=(3,))
    def _decode_scan(self, params, first_tok, caches, start_index, key,
                     temperature, top_k, greedy, n_steps: int):
        """``n_steps`` decode iterations in ONE dispatch (``lax.scan``).

        The per-token host loop costs one dispatch round-trip per token;
        this is the throughput path (``generate_fused``).  The key is
        split per step exactly like the host loop, so greedy fused output
        matches the loop path token-for-token.
        """

        def step(carry, i):
            tok, caches, key = carry
            logits, caches = self._decode_logits(
                params, tok, start_index + i, caches)
            step_key, key = jax.random.split(key)
            nxt = self._sample_from_logits(logits, step_key, temperature,
                                           top_k, greedy)
            return (nxt[:, None], caches, key), nxt

        (_, caches, key_out), toks = jax.lax.scan(
            step, (first_tok, caches, key), jnp.arange(n_steps))
        return toks.T, caches, key_out  # [B, n_steps], advanced key

    # ------------------------------------------------------- batched decode
    #
    # Deliberately a SEPARATE stack from the solo decoders above, not their
    # generalisation: solo decode writes contiguously at n_prompt + i (full
    # ``max_seq - n_prompt`` token budget, the streaming path's layout) while
    # batched decode writes at ``bucket + t`` with a masked gap (uniform
    # write slot across rows, budget ``max_seq - bucket``).  B=1 parity
    # between the stacks is pinned by test_llm_batch.py.
    #
    # B requests with different prompt lengths decode as ONE device program:
    # every row writes its cache at the same slot (``bucket + t`` — uniform,
    # so one dynamic_update_slice serves all rows) while attending with its
    # TRUE rotary position (``lengths[i] + t``, passed through to RoPE) and a
    # per-row mask that sees [0, lengths[i]) ∪ [bucket, bucket + t].  The gap
    # [lengths[i], bucket) holds prefill padding garbage and is never
    # attended.  Decode streams the weights once per step regardless of B, so
    # aggregate tokens/s scales ~linearly until the KV-cache reads catch up —
    # the slot-parallel analog of the reference server's ``--parallel`` and
    # of the SD server's micro-batching.

    def _decode_logits_batch(self, params, token, step, lengths, bucket,
                             caches):
        """``token [B,1]`` → (``[B,V]`` f32, caches); write slot bucket+step."""
        index = bucket + step
        positions = (lengths + step)[:, None]  # true per-row RoPE position
        ar = jnp.arange(self.cfg.max_seq)[None, :]
        valid = (ar < lengths[:, None]) | ((ar >= bucket) & (ar <= index))
        logits, caches = self.model.apply(
            {"params": params}, token, positions, caches, index,
            valid[:, None, None, :])
        return logits[:, -1].astype(jnp.float32), caches

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(6,))
    def _decode_step_batch(self, params, token, step, lengths, bucket, caches,
                           key, temperature, top_k, greedy):
        logits, caches = self._decode_logits_batch(
            params, token, step, lengths, bucket, caches)
        return self._sample_from_logits(logits, key, temperature, top_k,
                                        greedy), caches

    @functools.partial(jax.jit, static_argnums=(0, 11), donate_argnums=(6,))
    def _decode_scan_batch(self, params, first_tok, step0, lengths, bucket,
                           caches, key, temperature, top_k, greedy,
                           n_steps: int):
        """``n_steps`` batched decode iterations in ONE dispatch."""

        def step(carry, i):
            tok, caches, key = carry
            logits, caches = self._decode_logits_batch(
                params, tok, step0 + i, lengths, bucket, caches)
            step_key, key = jax.random.split(key)
            nxt = self._sample_from_logits(logits, step_key, temperature,
                                           top_k, greedy)
            return (nxt[:, None], caches, key), nxt

        (_, caches, key_out), toks = jax.lax.scan(
            step, (first_tok, caches, key), jnp.arange(n_steps))
        return toks.T, caches, key_out  # [B, n_steps]

    # --------------------------------------------------- continuous batching
    #
    # A third decode layout for the CONTINUOUS batcher
    # (tpustack.models.llm_continuous): B persistent slots, each with its own
    # logical cache line (a block table into the KV pool, below) — row i
    # decodes at its own frontier cur[i], attends [0, cur[i]] and takes RoPE
    # position cur[i], exactly the solo decoder's layout per row.  Slots
    # join (admission writes their prefill through their block tables) and
    # retire at chunk boundaries without touching their peers; parked slots
    # idle at position 0 (active=0 freezes cur) until reassigned.  Per-row
    # K/V land in a small chunk-local buffer (the pool stays FROZEN within
    # a chunk; one write through the block tables per chunk), so
    # a row's attention math depends only on its own prompt/seed — greedy
    # rows are token-identical to the solo path in practice (the chunk-
    # boundary softmax split changes fp summation ORDER only, never the
    # attended set), and sampled rows draw from per-slot PRNG streams, so
    # ALL rows are deterministic in (request, seed) regardless of admission
    # timing or batch composition.

    def _decode_cont_body(self, params, first_tok, cur, active, caches, keys,
                          temperature, top_k, greedy, n_steps: int, steps,
                          ride=None):
        """Traced body of one continuous-slot decode dispatch: ``steps``
        steps (a traced scalar, ``1 <= steps <= n_steps``) over a FROZEN
        cache view, K/V landing in chunk-local buffers of ``n_steps``
        positions — a loop whose trip count the engine chooses a dispatch,
        so one compiled program serves every length.
        ``_decode_scan_paged`` presents the view (the pool read in place,
        or gathered through the block tables) and scatters the buffers
        back through the block tables — one body for both reads is what
        makes their greedy outputs byte-identical.  Returns ``(toks [B,
        n_steps], last, cur_end, bufs, keys, moe)``: columns of ``toks``
        and buffer positions from ``steps`` on are zeros no step wrote;
        the PRNG chains advanced once a step that ran; ``moe``:
        ``_apply_counted``'s counters summed over those steps, None for a
        model without routed experts.

        ``ride`` (``_ride_scan_paged``): each step also carries the next
        segment of one row's prompt through the same pass — ``ride`` holds
        the prompt and where the dispatch's segments lie, and ``line``, the
        riding row's dense cache line its segments are written into and
        attend.  Steps past ``seg_n`` recompute the dispatch's last
        segment (the same values to the same positions) and count for
        nothing.  The return then ends with ``(line, logits [1, V] at the
        prompt's last position if a segment held it, the segments'
        routed-expert counters or None)``."""
        from tpustack.models.llama import RIDE_KEYS, init_chunk_bufs

        S = self.cfg.max_seq
        B = first_tok.shape[0]
        cur0 = cur
        bufs0 = init_chunk_bufs(self.cfg, B, n_steps, dtype=self.cache_dtype)

        moe0 = (None if self.cfg.moe is None
                else jnp.zeros((3,), jnp.int32))

        def step(t, carry):
            tok, bufs, keys, moe, toks = carry[:5]
            cur_t = jnp.minimum(cur0 + t * active, S - 1)
            merged = [dict(c, **bf) for c, bf in zip(caches, bufs)]
            if ride is None:
                logits, merged, counts = self._apply_counted(
                    params, tok, cur_t[:, None], merged, (cur0, t), None)
            else:
                line, got, rmoe = carry[5]
                seg = ride["tokens"].shape[1]
                roff = ride["seg_off"] + seg * jnp.minimum(
                    t, jnp.maximum(ride["seg_n"] - 1, 0))
                run = roff + jnp.arange(seg)
                last_here = jnp.clip(ride["length"] - 1 - roff, 0, seg - 1)
                logits, merged, counts = self._apply_counted(
                    params,
                    jnp.concatenate([tok, jax.lax.dynamic_index_in_dim(
                        ride["tokens"], roff // seg, keepdims=False
                    )[:, None]]),
                    jnp.concatenate([cur_t, run])[:, None],
                    [dict(m, **{r: ln[r[1:]] for r in RIDE_KEYS
                                if r[1:] in ln})
                     for m, ln in zip(merged, line)],
                    (cur0, t, roff), None, None,
                    jnp.append(jnp.arange(B), B + last_here))
                live = t < ride["seg_n"]
                if counts is not None:
                    rmoe = rmoe + jnp.where(live, counts[1], 0)
                    counts = counts[0]
                hit = live & (ride["length"] - 1 >= roff) & (
                    ride["length"] - 1 < roff + seg)
                got = jnp.where(hit, logits[B:, 0], got)
                line = [{k: d["r" + k] for k in ln}
                        for d, ln in zip(merged, line)]
                logits = logits[:B]
            if counts is not None:
                moe = moe + counts
            bufs = [{k: d[k] for k in bf} for d, bf in zip(merged, bufs)]
            step_keys, keys = _advance_keys(keys)
            nxt = self._sample_from_logits_perrow(
                logits[:, -1].astype(jnp.float32), step_keys, temperature,
                top_k, greedy)
            out = (nxt[:, None], bufs, keys, moe, toks.at[t].set(nxt))
            return out if ride is None else out + ((line, got, rmoe),)

        steps = jnp.asarray(steps, jnp.int32)
        carry = (first_tok, bufs0, keys, moe0,
                 jnp.zeros((n_steps, B), jnp.int32))
        if ride is not None:
            carry += ((ride["line"],
                       jnp.zeros((1, self.cfg.vocab_size), jnp.float32),
                       moe0),)
        out = jax.lax.fori_loop(0, steps, step, carry)
        last, bufs, keys, moe, toks = out[:5]
        cur_end = jnp.minimum(cur0 + steps * active, S - 1)
        return (toks.T, last, cur_end, bufs, keys, moe) + tuple(out[5:])

    @jax.named_scope("kv_write")
    def _flush_chunk_bufs(self, caches, bufs, cur0, cur_end, n_steps: int):
        """Traced flush of chunk-local K/V buffers into per-row cache lines
        at ``[cur0, cur_end)``: one linear pass per cache tensor — gather
        each row's chunk K/V at (position - cur0) and select it inside the
        window.  No program calls it any more: it stays as the plain
        reference the pool's page writer (``_pool_scatter_body``) is held
        to (``tests/test_pool_layout.py``, and on the chip the hardware
        tier's ``test_pool_writers_spell_the_dense_cache_on_chip``) —
        its only use."""
        S = self.cfg.max_seq
        B = cur0.shape[0]
        ar = jnp.arange(S)[None, :]
        window = (ar >= cur0[:, None]) & (ar < cur_end[:, None])    # [B, S]
        idx = jnp.clip(ar - cur0[:, None], 0, n_steps - 1).astype(jnp.int32)

        def flush(cache, buf):
            out = dict(cache)
            for bk, mk in (("ck", "k"), ("cv", "v"),
                           ("ck_scale", "k_scale"), ("cv_scale", "v_scale")):
                if bk not in buf:
                    continue
                tail = (1,) * (cache[mk].ndim - 2)
                g = jnp.take_along_axis(buf[bk], idx.reshape(B, S, *tail),
                                        axis=1)
                out[mk] = jnp.where(window.reshape(B, S, *tail),
                                    g.astype(cache[mk].dtype), cache[mk])
            return out

        return [flush(c, bf) for c, bf in zip(caches, bufs)]

    # --------------------------------------------------------- paged KV pool
    #
    # Device half of the engine's KV store (tpustack.serving.kv_pool):
    # every layer's K/V lives in pool tensors [n_blocks, block, kvh*hd]
    # (int8 scales [n_blocks, kvh*block]: llama.init_kv_pool — the layout
    # the paged kernel and the page writes below take as it rests) and a
    # slot's logical cache line is a BLOCK TABLE (bt [B, max_seq // block],
    # int32 pool indices; the reserved block 0 backs idle entries).  The
    # compute view is a gather through the table — elementwise equal to
    # what a contiguous cache line would hold, so the attention bodies above
    # run unchanged and greedy outputs are byte-identical to the solo path.
    # Writes land ONLY the freshly produced K/V (an admission's prefill
    # rows, a chunk's buffers) through the table, a whole page at a time
    # (the pages a run touches are read, the run's valid tokens laid over
    # them, and written back: _pool_scatter_body), with positions outside a
    # row's allocation left as they were and pages without a valid token
    # dropped via out-of-range ids — shared prefix blocks (refcount > 1)
    # are never written after their prefill, which is what makes
    # cross-request sharing safe.
    #
    # Reallocation hazard (freed blocks reassigned while chunks are in
    # flight): dispatches execute in order on the device stream, and the
    # host only frees a retiring slot's blocks BEFORE dispatching the new
    # owner's admission — so a stale in-flight chunk's flush into those
    # blocks lands first and is overwritten by the new owner's prefill/
    # decode before any mask can admit it.

    @jax.named_scope("kv_read")
    def _pool_gather_body(self, pool, bt):
        """Traced: pool tensors (``init_kv_pool``'s layout: K/V ``[N, blk,
        kvh·hd]``, scales ``[N, kvh·blk]``) → dense per-row view ``[B,
        max_seq, kvh, hd]`` / ``[B, max_seq, kvh]`` via block tables ``bt
        [B, nb]`` — what a dense cache line would hold, bit for bit."""
        kvh = self.cfg.n_kv_heads

        def ga(key, x):
            blocks = jnp.take(x, bt, axis=0, mode="clip")   # [B, nb, *page]
            return pool_lines(key, blocks, kvh)

        return [{k: ga(k, v) for k, v in layer.items()} for layer in pool]

    @staticmethod
    def _pool_views(pool, bt):
        """Per-layer IN-PLACE pool views for the paged-flash attention
        branch (``TPUSTACK_PAGED_FLASH``): the pool tensors ride into the
        attention dict unchanged under ``pk``/``pv`` next to the block
        table, and ``LlamaAttention`` reads them in place through the
        scalar-prefetch Pallas kernel — the zero-copy replacement for
        ``_pool_gather_body``'s dense ``[B, max_seq]`` materialisation
        (and the whole point of the paged-flash path: the gather's
        read+write copy never happens).  An int8 pool's scales ride as the
        kernel's lane rows (``pk_rows``/``pv_rows``), gathered through the
        tables HERE, once a chunk: the pool is frozen while the chunk's
        steps run, and the compiler hoists only part of that gather out
        of the scan when it is left inside the layer."""
        from tpustack.ops.pallas.flash_attention import paged_scale_rows

        def view(layer):
            v = {"pk": layer["k"], "pv": layer["v"], "bt": bt}
            if "k_scale" in layer:
                with jax.named_scope("kv_read"):
                    v["pk_rows"] = paged_scale_rows(layer["k_scale"], bt,
                                                    layer["k"])
                    v["pv_rows"] = paged_scale_rows(layer["v_scale"], bt,
                                                    layer["v"])
            return v

        return [view(layer) for layer in pool]

    @staticmethod
    @jax.named_scope("kv_write")
    def _pool_scatter_body(pool, bt_rows, src_layers, keymap, start, valid):
        """Traced: write each row's run of ``L`` fresh positions ``[start[r],
        start[r] + L)`` (``valid [R, L]`` selects the real ones; ``start``
        ``[R]`` or one traced scalar, or one STATIC page-aligned int) into the
        pool through ``bt_rows [R, nb]``.  ``src_layers`` arrays are dense
        cache values ``[R, L, kvh, hd]`` / ``[R, L, kvh]``; ``keymap`` maps
        pool key → source key.

        The unit of a write is the unit the pool rests in: a WHOLE PAGE
        (``[block, kvh·hd]`` of K/V, the ``[kvh·block]`` lane row of a scale
        plane).  A run touches at most ``(L - 1) // block + 2`` pages a row:
        they are read, the run's valid tokens selected over what they hold,
        and written back — a dozen page updates for a 512-token admission
        where a token-row scatter makes 512 (0.1-0.2 µs each on a v5e, one
        after another: 88 → 7 µs a K/V tensor, 71 → 6 µs a scale plane), and
        no view of a plane with ``kvh`` minor, which the compiler answers
        with a 17 MB padded copy of it.  Pages without a valid token get
        UNIQUE out-of-range ids and ``mode='drop'``: the scatter stays
        unique-indices, the reserved block 0 and shared prefix blocks
        (never valid here) are never written.  The tables and frontiers
        have to be traced values, as the engine's are: from page ids it
        can fold to constants XLA for the TPU drops the whole scatter
        (seen on a v5e, PR 29; ``tests/test_pool_layout.py``)."""
        n_blocks, blk = pool[0]["k"].shape[:2]
        R, L = valid.shape
        nb = bt_rows.shape[1]
        if isinstance(start, int):
            # a static start on a page's first slot (an admission's 0): no
            # run needs shifting into place, none spills into a further page
            assert start % blk == 0, (start, blk)
            nj, shift = -(-L // blk), None
            j = jnp.broadcast_to(start // blk + jnp.arange(nj), (R, nj))
        else:
            start = jnp.broadcast_to(start, (R,))
            nj = (L - 1) // blk + 2 if L > 1 else 1
            j = start[:, None] // blk + jnp.arange(nj)[None, :]       # [R, nj]
            shift = start % blk
        live = _page_align(valid, shift, blk, nj, 1, False)
        live = live.reshape(R * nj, blk)
        page = jnp.take_along_axis(bt_rows, jnp.clip(j, 0, nb - 1), axis=1)
        oob = n_blocks + jnp.arange(R * nj, dtype=page.dtype)
        page = jnp.where(live.any(axis=1) & (j < nb).reshape(-1),
                         page.reshape(-1), oob)

        def sc(key, dst, src):
            rows = pool_rows(key, src).astype(dst.dtype)
            if is_scale_key(key):
                # tokens minor from the start: a [.., tokens, kvh] temporary
                # pads kvh to 128 lanes
                rows = rows.swapaxes(1, 2)
            return _write_pages(dst, rows, page, live, shift)

        return [{k: sc(k, layer[k], srcl[keymap.get(k, k)]) for k in layer}
                for layer, srcl in zip(pool, src_layers)]

    @jax.named_scope("kv_write")
    def _insert_span_body(self, pool, bt_rows, caches, start, bucket: int,
                          limits):
        """Traced: write cache positions ``[start, start + bucket)`` of R
        rows into the pool through their block tables — the paged splice.
        ``caches`` are full-line row caches (``[R, max_seq, ...]``) whose
        data at those positions is what prefill just produced; ``limits
        [R]`` clips each row's write at its allocation (padded-bucket
        garbage beyond it is dropped)."""

        def sl(x):
            idx = (jnp.zeros((), jnp.int32), start) + (
                jnp.zeros((), jnp.int32),) * (x.ndim - 2)
            return jax.lax.dynamic_slice(
                x, idx, (x.shape[0], bucket) + x.shape[2:])

        src = [{k: sl(v) for k, v in layer.items()} for layer in caches]
        valid = start + jnp.arange(bucket)[None, :] < limits[:, None]
        return self._pool_scatter_body(pool, bt_rows, src, {}, start, valid)

    @functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=(1,))
    def _insert_rows_paged(self, pool, bt_rows, row_caches, start,
                           bucket: int, limits):
        """One-dispatch paged splice (the big-suffix prefix-hit admission
        and the parked chunk steps, whose prefill ran as dispatches of
        their own) — see _insert_span_body."""
        return self._insert_span_body(pool, bt_rows, row_caches, start,
                                      bucket, limits)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _gather_rows_paged(self, pool, bt_rows):
        """Standalone gather of R dense row caches out of the pool (NOT
        donated — the pool keeps serving).  The big-suffix prefix path
        uses it to build row caches for the flash-chunk prefill loop."""
        return self._pool_gather_body(pool, bt_rows)

    @functools.partial(jax.jit, static_argnums=(0, 11),
                       static_argnames=("flash",), donate_argnums=(5,))
    def _decode_scan_paged(self, params, first_tok, cur, active, pool, bt,
                           keys, temperature, top_k, greedy, n_steps: int,
                           steps, flash: bool = False):
        """``steps`` continuous-slot decode iterations in ONE dispatch:
        present the frozen chunk view of the pool, run the loop body
        (``_decode_cont_body``), scatter the chunk buffers back through
        the block tables at ``[cur0, cur_end)``.  Only the new tokens'
        K/V move pool-ward — shared prefix blocks are read, never
        rewritten.

        ``n_steps`` (static) is a dispatch's CAPACITY — the chunk buffers,
        the ``[B, n_steps]`` token block, the scatter's window; ``steps``
        (a traced int32 scalar, ``1 <= steps <= n_steps``) is how many of
        them this dispatch runs, the engine's choice per dispatch
        (``ContinuousEngine._dispatch_len``): ONE compiled program for every
        length.  ``cur_end``, the write window, the PRNG chains, ``moe``
        and ``last`` all follow ``steps``; token columns from ``steps`` on
        are zeros.

        ``cur [B]``: per-slot frontier at chunk START (``cur0``) — advances
        only where ``active``, clamped at max_seq-1.  ``keys [B, 2]``:
        per-slot PRNG streams (see ``_sample_from_logits_perrow``).  The
        pool is read-only for the whole chunk: step t writes its K/V at
        the UNIFORM index t of per-layer chunk buffers
        (``init_chunk_bufs``, loop-internal) and attention merges
        {pool [0, cur0[i])} ∪ {buffer [0, t]} with an exact streaming-
        softmax split (LlamaAttention chunk mode), so per-step write-back
        traffic amortises by the chunk length.  Overshoot steps past
        max_seq-1 are clipped out of the write window entirely, so a
        retiring row's speculative garbage is never written at all.

        ``flash`` (static; the engine passes its knob-resolved
        ``TPUSTACK_PAGED_FLASH`` verdict) picks HOW the frozen view is
        read: False gathers a dense ``[B, max_seq]`` copy per chunk
        (``_pool_gather_body`` — the bisection path), True hands the pool
        tensors + block tables straight to the attention layer, which
        reads the blocks IN PLACE via the scalar-prefetch Pallas kernel
        (``paged_attention_partial``) — no gather copy, no dense
        intermediate, per-row ``cur`` masking and int8 dequant inside the
        kernel.  Same traced scan body either way, so greedy outputs are
        token-identical across the flag.

        Returns ``(toks, last, cur_end, pool, keys, moe)``: ``moe`` rides
        to the host in the fetch that takes ``toks``."""
        view = (self._pool_views(pool, bt) if flash
                else self._pool_gather_body(pool, bt))
        toks, last, cur_end, bufs, keys, moe = self._decode_cont_body(
            params, first_tok, cur, active, view,
            keys, temperature, top_k, greedy, n_steps, steps)
        valid = (cur[:, None] + jnp.arange(n_steps)[None, :]
                 < cur_end[:, None])
        pool = self._pool_scatter_body(
            pool, bt, bufs,
            {"k": "ck", "v": "cv", "k_scale": "ck_scale",
             "v_scale": "cv_scale"}, cur, valid)
        return toks, last, cur_end, pool, keys, moe

    @functools.partial(jax.jit, static_argnums=(0, 11),
                       static_argnames=("flash",), donate_argnums=(5,))
    def _ride_scan_paged(self, params, first_tok, cur, active, pool, bt,
                         keys, temperature, top_k, greedy, n_steps: int,
                         steps, ride, flash: bool = False):
        """``_decode_scan_paged``'s dispatch with a lone admission riding
        it: each decode step also carries the next segment — whole pool
        blocks of tokens (``RIDE_SEGMENT``) — of one row's prompt, through
        the same weight pass (``_decode_cont_body`` with ``ride``;
        ``LlamaAttention._attend_ride``).  ONE program for every prompt
        that fits ``ride["tokens"]`` and every segment: all of ``ride`` is
        operands — ``tokens [L / S, S]`` the prompt padded to ``L``, a
        segment of ``S`` tokens a row, ``slot``, ``length`` (the
        prompt's), ``seg_off`` (where this dispatch's first segment
        starts, a multiple of ``S``),
        ``seg_n`` (the segments it runs, at most ``steps``; 0 runs the
        decode steps alone), ``finish`` (whether the prompt's last segment
        is among them), and the row's ``seed``, ``temp``, ``topk``,
        ``greedy`` (``[1]`` each).

        The riding row is parked while it rides (its lane decodes nothing
        and writes nothing).  Its line is gathered from its pages at the
        start (the segments earlier dispatches wrote), the dispatch's
        segments are written back to them a whole page at a time at the
        end, and with ``finish`` its first token is sampled from the
        logits at the prompt's last position and the row activated, as
        ``_admit_fused_paged`` activates one: it decodes from the next
        dispatch on.  Returns ``(toks, firsts [1], pool, moe, ride_moe,
        cur, active, first, temp, topk, greedy, keys)``: ``moe`` the decode
        rows' routed-expert counters, ``ride_moe`` the segments' (None
        without such a layer)."""
        blk = pool[0]["k"].shape[1]
        L, seg = ride["tokens"].size, ride["tokens"].shape[1]
        assert seg % blk == 0, (seg, blk)
        view = (self._pool_views(pool, bt) if flash
                else self._pool_gather_body(pool, bt))
        bt_r = jax.lax.dynamic_slice_in_dim(bt, ride["slot"], 1)[:, :L // blk]
        line = self._pool_gather_body(pool, bt_r)
        (toks, last, cur_end, bufs, keys, moe, (line, got, ride_moe)
         ) = self._decode_cont_body(
            params, first_tok, cur, active, view, keys, temperature, top_k,
            greedy, n_steps, steps, ride=dict(ride, line=line))
        valid = (cur[:, None] + jnp.arange(n_steps)[None, :]
                 < cur_end[:, None])
        pool = self._pool_scatter_body(
            pool, bt, bufs,
            {"k": "ck", "v": "cv", "k_scale": "ck_scale",
             "v_scale": "cv_scale"}, cur, valid)
        at = jnp.arange(L)[None, :]
        end = jnp.minimum(ride["seg_off"] + ride["seg_n"] * seg,
                          ride["length"])
        pool = self._pool_scatter_body(pool, bt_r, line, {}, 0,
                                       (at >= ride["seg_off"]) & (at < end))
        firsts, next_keys = self._first_sample(
            got, ride["seed"], ride["temp"], ride["topk"], ride["greedy"])
        held = (cur_end, active, last, temperature, top_k, greedy, keys)
        joined = self._activate_rows(
            *held, ride["slot"][None], ride["length"][None], firsts,
            ride["temp"], ride["topk"], ride["greedy"], next_keys)
        state = tuple(jnp.where(ride["finish"], j, h)
                      for j, h in zip(joined, held))
        return (toks, firsts, pool, moe, ride_moe) + state

    # --------------------------------------------------- speculative verify
    #
    # Device half of speculative decoding on the continuous engine
    # (llm_continuous; Leviathan et al. 2023, prompt-lookup per Saxena
    # 2023).  Decode is bandwidth-bound: every plain step streams the full
    # weight + KV working set to emit ONE token per slot.  The verify step
    # feeds each slot's last accepted token plus K host-proposed draft
    # tokens through ONE forward pass (the chunk-mode attention generalised
    # to an in-segment-causal multi-query block — see LlamaAttention),
    # scores all K+1 positions, and accepts the longest draft prefix that
    # agrees with what the model would have produced anyway:
    #
    # - greedy rows accept draft_j while it equals argmax(logits_j) — so
    #   the emitted chain is bit-for-bit the plain greedy chain, just
    #   discovered up to K+1 tokens per weight pass instead of one;
    # - sampled rows rejection-sample (accept draft_j with probability
    #   p_j(draft_j) under the row's temperature/top-k-filtered
    #   distribution; on the first rejection the bonus token draws from
    #   the residual with the draft token removed and renormalised), so
    #   the output DISTRIBUTION is exactly the plain sampling path's —
    #   the standard correctness argument for a deterministic proposal.
    #
    # Every row always emits n_acc + 1 tokens (the bonus comes free from
    # the position after the last accepted draft), so a verify step is
    # never slower than a plain decode step in tokens-per-weight-pass.
    # KV for the accepted tokens only is flushed/scattered ([cur0,
    # cur0 + n_acc + 1)); rejected draft K/V never lands in the cache or
    # the pool, which keeps paged block accounting capacity-true.

    def _spec_verify_parts(self, params, first_tok, draft, draft_len, cur,
                           active, caches, keys, temperature, top_k, greedy,
                           n_draft: int):
        """Traced body of one verify step (``_spec_verify_paged``'s, for
        either read of the pool).  ``first_tok [B,1]``: last accepted token (KV not yet
        written); ``draft [B,K]`` host-proposed continuations with per-row
        valid counts ``draft_len [B]`` (zero-draft rows run exactly one
        plain decode step's worth of work inside the same dispatch).
        Returns ``(toks [B,K+1], n_acc [B], last [B,1], cur_end [B], bufs,
        keys, moe)`` — the host takes ``toks[i, :n_acc[i]+1]``."""
        from tpustack.models.llama import init_chunk_bufs

        S_max = self.cfg.max_seq
        V = self.cfg.vocab_size
        K = n_draft
        S = K + 1
        B = first_tok.shape[0]
        cur0 = cur
        seg = jnp.concatenate([first_tok, draft], axis=1)        # [B, S]
        bufs0 = init_chunk_bufs(self.cfg, B, S, dtype=self.cache_dtype)
        merged = [dict(c, **bf) for c, bf in zip(caches, bufs0)]
        offs = jnp.arange(S)[None, :] * active[:, None]
        positions = jnp.minimum(cur0[:, None] + offs, S_max - 1)
        logits, merged, moe = self._apply_counted(
            params, seg, positions, merged, (cur0, 0), None)
        bufs = [{k: d[k] for k in bf} for d, bf in zip(merged, bufs0)]
        logits = logits.astype(jnp.float32)                      # [B, S, V]

        # PRNG discipline: K acceptance draws + 1 bonus draw per row per
        # verify, advanced UNCONDITIONALLY (outside the all-greedy gate) so
        # the key chain's state never depends on batch composition
        step_keys = []
        for _ in range(S):
            sk, keys = _advance_keys(keys)
            step_keys.append(sk)

        gr = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(greedy)), (B,))
        with jax.named_scope("sample"):
            outs_greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        valid = jnp.arange(K)[None, :] < draft_len[:, None]          # [B, K]

        def greedy_path(_):
            acc = (outs_greedy[:, :K] == draft) & valid
            n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                            axis=1)
            bonus = jnp.take_along_axis(outs_greedy, n_acc[:, None],
                                        axis=1)[:, 0]
            return n_acc, bonus

        def mixed_path(_):
            # rejection sampling under the per-row filtered distribution:
            # the same temperature/top-k filter plain decode samples from
            rep = lambda x: jnp.repeat(jnp.broadcast_to(
                jnp.atleast_1d(jnp.asarray(x)), (B,)), S)
            scaled = self._topk_scaled(logits.reshape(B * S, V),
                                       rep(temperature),
                                       rep(top_k)).reshape(B, S, V)
            probs = jax.nn.softmax(scaled, axis=-1)              # [B, S, V]
            p_draft = jnp.take_along_axis(probs[:, :K], draft[..., None],
                                          axis=-1)[..., 0]       # [B, K]
            u = jnp.stack([jax.vmap(
                lambda k: jax.random.uniform(k))(step_keys[j])
                for j in range(K)], axis=1)                      # [B, K]
            acc_s = (u < p_draft) & valid
            acc = jnp.where(gr[:, None], (outs_greedy[:, :K] == draft)
                            & valid, acc_s)
            n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                            axis=1)
            # bonus at position n_acc: residual (draft token removed,
            # renormalised) after a true rejection; the FULL distribution
            # when the row simply ran out of accepted drafts
            pj = jnp.take_along_axis(probs, n_acc[:, None, None],
                                     axis=1)[:, 0]               # [B, V]
            draft_pad = jnp.pad(draft, ((0, 0), (0, 1)))         # [B, S]
            rejected_tok = jnp.take_along_axis(draft_pad, n_acc[:, None],
                                               axis=1)[:, 0]
            ran_out = n_acc >= draft_len
            residual = jnp.where(
                (jnp.arange(V)[None, :] == rejected_tok[:, None])
                & ~ran_out[:, None], 0.0, pj)
            bonus_s = jax.vmap(jax.random.categorical)(
                step_keys[K], jnp.log(jnp.maximum(residual, 1e-38)))
            bonus_g = jnp.take_along_axis(outs_greedy, n_acc[:, None],
                                          axis=1)[:, 0]
            return n_acc, jnp.where(gr, bonus_g,
                                    bonus_s).astype(jnp.int32)

        # all-greedy runtime gate, like _greedy_gated: the common serving
        # mix (and every parked slot) skips the softmax/draw machinery
        with jax.named_scope("sample"):  # both paths are traced in here
            n_acc, bonus = jax.lax.cond(jnp.all(gr), greedy_path,
                                        mixed_path, None)
        ar = jnp.arange(S)[None, :]
        draft_pad = jnp.pad(draft, ((0, 0), (0, 1)))
        toks = jnp.where(ar < n_acc[:, None], draft_pad,
                         jnp.where(ar == n_acc[:, None], bonus[:, None],
                                   0)).astype(jnp.int32)
        cur_end = jnp.minimum(cur0 + (n_acc + 1) * active, S_max - 1)
        return toks, n_acc, bonus[:, None], cur_end, bufs, keys, moe

    @functools.partial(jax.jit, static_argnums=(0, 13),
                       static_argnames=("flash",), donate_argnums=(7,))
    def _spec_verify_paged(self, params, first_tok, draft, draft_len, cur,
                           active, pool, bt, keys, temperature, top_k,
                           greedy, n_draft: int, flash: bool = False):
        """Speculative verify: one K+1-position forward pass over the
        frozen view of the block pool (``_spec_verify_parts``), then
        scatter ONLY the accepted positions back through the block tables — so shared
        prefix blocks are read but never rewritten, and block accounting
        stays capacity-true (no rejected-draft KV ever lands).

        ``flash=True`` is the FUSED verify: the K+1 query positions go
        through ONE in-place pass over the pool blocks (the multi-query
        rows of the same scalar-prefetch kernel; the in-segment causal
        half rides the chunk-buffer partial) instead of gather + attention
        — a verify step then costs one read of the KV working set, which
        is the whole speculative-bandwidth argument.  See
        ``_decode_scan_paged`` for the flag's contract."""
        view = (self._pool_views(pool, bt) if flash
                else self._pool_gather_body(pool, bt))
        toks, n_acc, last, cur_end, bufs, keys, moe = self._spec_verify_parts(
            params, first_tok, draft, draft_len, cur, active,
            view, keys, temperature, top_k,
            greedy, n_draft)
        valid = (cur[:, None] + jnp.arange(n_draft + 1)[None, :]
                 < cur_end[:, None])
        pool = self._pool_scatter_body(
            pool, bt, bufs,
            {"k": "ck", "v": "cv", "k_scale": "ck_scale",
             "v_scale": "cv_scale"}, cur, valid)
        return toks, n_acc, last, cur_end, pool, keys, moe

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(3, 9, 10, 11, 12, 13, 14, 15))
    def _admit_fused_paged(self, params, tokens, pool, bt_rows, lengths,
                           limits, slot_ids, seeds, cur, active, first, temp,
                           topk, greedy, keys, temp_r, topk_r, greedy_r):
        """ONE-dispatch admission for a same-bucket wave: fresh in-graph
        row caches → batched prefill → write through the rows' block
        tables → per-request first-token sample + key-chain init →
        slot-state activation.  One program per (rows, bucket); each
        dispatch costs a host round-trip, and an unfused admission's ~6 of
        them weigh on short-generation end-to-end.  A bucket of at most
        ADMIT_CHUNK prefills in one shot; a larger one walks its bucket in
        chunks and stops at its longest row's last one
        (``_prefill_walk_body``), on row lines as long as the bucket.
        Returns the pool, the first tokens, the slot state, then
        ``_apply_counted``'s ``moe`` and the chunks the walk ran (None for
        the single shot): they leave with the first tokens."""
        n, bucket = tokens.shape
        C = self.ADMIT_CHUNK
        if bucket <= C:
            chunks = None
            row_caches = init_kv_caches(self.cfg, n, dtype=self.cache_dtype)
            positions = jnp.broadcast_to(jnp.arange(bucket), (n, bucket))
            logits, row_caches, moe = self._apply_counted(
                params, tokens, positions, row_caches, 0, None, lengths - 1)
        else:
            line = -(-bucket // C) * C      # a max_seq-capped bucket: padded
            row_caches = init_kv_caches(self.cfg, n, dtype=self.cache_dtype,
                                        seq=line)
            logits, row_caches, moe, chunks = self._prefill_walk_body(
                params, jnp.pad(tokens, ((0, 0), (0, line - bucket))),
                lengths, row_caches, C)
            logits = logits[:, None]    # the single shot's [n, 1, V]
        pool = self._insert_span_body(pool, bt_rows, row_caches, 0, bucket,
                                      limits)
        firsts, next_keys = self._first_sample(logits[:, 0], seeds, temp_r,
                                               topk_r, greedy_r)
        return (pool, firsts) + self._activate_rows(
            cur, active, first, temp, topk, greedy, keys, slot_ids,
            lengths, firsts, temp_r, topk_r, greedy_r, next_keys) + (
                moe, chunks)

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(3, 10, 11, 12, 13, 14, 15, 16))
    def _admit_prefix_paged(self, params, tokens, pool, bt_rows, base,
                            length, limits, slot_ids, seeds, cur, active,
                            first, temp, topk, greedy, keys, temp_r, topk_r,
                            greedy_r):
        """ONE-dispatch paged warm start: gather the hit row's line (the
        shared prefix blocks hold exactly what prefill wrote — zero-copy
        restore) → masked suffix prefill (the solo route's warm-start
        body) → scatter the suffix span back through the block
        table → sample + activate.  ``moe`` last, as in
        ``_admit_fused_paged``."""
        caches = self._pool_gather_body(pool, bt_rows)
        logits, caches, moe = self._prefill_masked_body(
            params, tokens, base, length, caches)
        pool = self._insert_span_body(pool, bt_rows, caches, base,
                                      tokens.shape[1], limits)
        firsts, next_keys = self._first_sample(logits, seeds, temp_r, topk_r,
                                               greedy_r)
        return (pool, firsts) + self._activate_rows(
            cur, active, first, temp, topk, greedy, keys, slot_ids,
            length, firsts, temp_r, topk_r, greedy_r, next_keys) + (moe,)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _restore_blocks_paged(self, pool, ids, payloads):
        """Host-tier restore: write ``R_pad`` spilled blocks' KV bytes
        back into the pool at block ids ``ids [R_pad]`` — ONE dispatch
        however many blocks a hit restores.  ``payloads`` mirrors the
        pool's per-layer dict layout with arrays ``[R_pad, blk, *tail]``
        (host-stacked from the tier's claimed copies).  The id vector is
        padded to a power of two by REPEATING the last real id with its
        own payload row, so duplicate writes land identical bytes and
        the jit signature count stays bounded in the restore width."""
        def st(dst, src):
            return dst.at[ids].set(src.astype(dst.dtype))

        return [{k: st(layer[k], srcl[k]) for k in layer}
                for layer, srcl in zip(pool, payloads)]

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def _prefill_chunk_paged(self, params, pool, bt_rows, tokens, base,
                             limits):
        """One CHUNKED-prefill step for parked long-prompt rows: gather
        the rows' lines out of the pool (earlier chunks' KV sits in their
        already-allocated blocks) → masked attention over ``[0, base +
        s)`` — the same traced body every warm suffix runs, so resuming
        a chunked prefill is byte-identical to a monolithic one → scatter
        the new span back through the block tables.  No sample, no
        activation: the slot stays PARKED between chunks (PR 14's
        preemption contract) and only the final chunk goes through the
        ordinary ``_admit_prefix_paged`` warm start for its first
        token."""
        caches = self._pool_gather_body(pool, bt_rows)
        # ``limits`` ([B]) is exactly the post-chunk length ``base + step``
        # — reuse it as the masked body's per-row true length (the sampled
        # logits are discarded, but ``logits_at`` still gathers per row)
        _, caches, _ = self._prefill_masked_body(params, tokens, base,
                                                 limits, caches)
        return self._insert_span_body(pool, bt_rows, caches, base,
                                      tokens.shape[1], limits)

    @jax.named_scope("sample")
    def _first_sample(self, logits, seeds, temperature, top_k, greedy):
        """Traced body: per-request key-chain init from seeds + first-token
        sample.  Shared by ``_admit_sample_jit`` and the fused
        admissions."""
        base = jax.vmap(jax.random.PRNGKey)(seeds)          # [n, 2]
        first_keys, next_keys = _advance_keys(base)
        firsts = self._sample_from_logits_perrow(
            logits, first_keys, temperature, top_k, greedy)
        return firsts, next_keys

    @staticmethod
    def _activate_rows(cur, active, first, temp, topk, greedy, keys,
                       slot_ids, n_cur, n_first, n_temp, n_topk, n_greedy,
                       n_keys):
        """Traced body: scatter n admitted rows into the B-slot state
        arrays.  Shared by ``_slot_activate`` and the fused admissions."""
        return (cur.at[slot_ids].set(n_cur),
                active.at[slot_ids].set(1),
                first.at[slot_ids].set(n_first[:, None]),
                temp.at[slot_ids].set(n_temp),
                topk.at[slot_ids].set(n_topk),
                greedy.at[slot_ids].set(n_greedy),
                keys.at[slot_ids].set(n_keys))

    @functools.partial(jax.jit, static_argnums=(0,))
    def _admit_sample_jit(self, logits, seeds, temperature, top_k, greedy):
        """Device-side admission sampling: prefill logits ``[n, V]`` +
        per-request ``seeds [n]`` → (first tokens ``[n]``, per-slot key
        chains ``[n, 2]``).  No host value is needed to build this — the
        engine dispatches it and keeps going; the n int32 tokens are
        fetched at the next natural sync point (fetching the [n, V] logits
        for host sampling would move n x 150k floats per admission wave)."""
        return self._first_sample(logits, seeds, temperature, top_k, greedy)

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(1, 2, 3, 4, 5, 6, 7))
    def _slot_activate(self, cur, active, first, temp, topk, greedy, keys,
                       slot_ids, n_cur, n_first, n_temp, n_topk, n_greedy,
                       n_keys):
        """Scatter n admitted rows into the B-slot state arrays in ONE
        dispatch (chunked long-prompt admissions; the common path fuses
        this into ``_admit_fused_paged``).  Entirely device-valued, so admission
        never syncs the host — the decode chain keeps flowing while
        prefill+activation are still in flight.  See _activate_rows."""
        return self._activate_rows(cur, active, first, temp, topk, greedy,
                                   keys, slot_ids, n_cur, n_first, n_temp,
                                   n_topk, n_greedy, n_keys)

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(1, 2, 3, 4, 5, 6))
    def _slot_update(self, cur, active, first, temp, topk, greedy, mask,
                     new_cur, new_active, new_first, new_temp, new_topk,
                     new_greedy):
        """Apply per-slot state changes for the slots selected by ``mask``
        ([B] bool) in ONE dispatch — retirements coalesce their parks
        instead of paying a dispatch per array.  (Slot PRNG keys
        are left alone: a parked slot's key chain is dead state that
        ``_slot_activate`` overwrites at reassignment.)"""
        pick = lambda a, b: jnp.where(mask, b, a)
        return (pick(cur, new_cur), pick(active, new_active),
                jnp.where(mask[:, None], new_first, first),
                pick(temp, new_temp), pick(topk, new_topk),
                pick(greedy, new_greedy))

    def generate_batch(
        self,
        prompts: List[List[int]],
        max_new_tokens,
        sample: List[SampleConfig],
        seed: Optional[int] = None,
        stop_tokens: Tuple[int, ...] = (),
        chunk: int = 16,
        on_chunk=None,
        on_row_done=None,
        cancel_check=None,
    ) -> Tuple[List[List[int]], Dict[str, float]]:
        """Decode B prompts concurrently; returns (per-row token ids, stats).

        ``max_new_tokens``: int or per-row list.  ``sample``: one
        SampleConfig per row (mixed temperatures/top_k/greedy batch fine).
        ``on_chunk(step_toks)``: called with the ``[B, <=chunk]`` numpy block
        after each fused dispatch — the batched streaming hook (chunk
        granularity).  The first call is the ``[B, 1]`` prefill-sampled
        tokens, so a consumer sees every token of every row; rows may carry
        post-stop garbage the host discarded (track stops consumer-side).  ``on_row_done(i, tokens, row_stats)``: called the
        moment row ``i`` stops (EOS / its own budget) — a short request in a
        batch is answered immediately instead of waiting for the slowest
        peer (every row is notified exactly once; stragglers at return).
        ``cancel_check()`` polled between chunks.

        Row capacity is uniform: every row may generate up to
        ``max_seq - bucket`` tokens, where ``bucket`` is the padded length of
        the LONGEST prompt in the batch (batch peers share the cache layout).
        """
        c = self.cfg
        b = len(prompts)
        if b == 0:
            raise ValueError("empty batch")
        if len(sample) != b:
            raise ValueError(f"need {b} SampleConfigs, got {len(sample)}")
        lens = [len(p) for p in prompts]
        if min(lens) == 0:
            raise ValueError("empty prompt in batch")
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * b
        bucket = self._bucket(max(lens))
        capacity = c.max_seq - bucket
        if capacity <= 0:
            raise ValueError(f"longest prompt ({max(lens)}) exceeds ctx budget "
                             f"{c.max_seq}")
        max_new = [min(m, capacity) for m in max_new_tokens]

        t0 = time.time()
        tokens = np.zeros((b, bucket), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
        caches = init_kv_caches(c, b, dtype=self.cache_dtype,
                                mesh=self.kv_mesh)
        lengths = jnp.asarray(lens, jnp.int32)
        if bucket > self.PREFILL_CHUNK:
            logits, caches = self._prefill_walk(
                self.params, jnp.asarray(tokens), lengths, caches)
        else:
            logits, caches = self._prefill(self.params, jnp.asarray(tokens),
                                           lengths, caches)
        key = jax.random.PRNGKey(np.random.randint(0, 2**31)
                                 if seed is None else seed)
        temperature = jnp.asarray([s.temperature for s in sample], jnp.float32)
        top_k = jnp.asarray([s.top_k for s in sample], jnp.int32)
        greedy = jnp.asarray([s.greedy for s in sample], jnp.bool_)

        first_key, key = jax.random.split(key)
        first = np.asarray(self._sample_from_logits(
            logits, first_key, temperature, top_k, greedy))
        t_prefill = time.time() - t0

        t0 = time.time()
        out: List[List[int]] = [[int(first[i])] if max_new[i] > 0 else []
                                for i in range(b)]
        done = [max_new[i] <= 1 or out[i][0] in stop_tokens for i in range(b)]

        notified = [False] * b

        def notify(i):
            if on_row_done is None or notified[i]:
                return
            notified[i] = True
            dt = time.time() - t0
            on_row_done(i, list(out[i]), {
                "batch": b,
                "prompt_tokens": lens[i],
                "generated_tokens": len(out[i]),
                "prefill_s": t_prefill,
                "decode_s": dt,
                "tokens_per_s": len(out[i]) / dt if dt > 0 else 0.0,
            })

        tok = first[:, None].astype(np.int32)
        if on_chunk is not None:  # before notify: tokens precede sentinels
            on_chunk(tok.copy())
        for i in range(b):
            if done[i]:
                notify(i)
        step = 0  # decode steps already fetched past the first token
        bucket_arr = jnp.asarray(bucket, jnp.int32)
        state = {"caches": caches, "key": key, "tok": tok, "step": step}

        def scan(first_dev, dispatched):
            # always scan a FULL chunk — one compiled signature per
            # (B, chunk); surplus tokens are discarded on the host
            toks, state["caches"], state["key"] = self._decode_scan_batch(
                self.params, first_dev, jnp.asarray(dispatched, jnp.int32),
                lengths, bucket_arr, state["caches"], state["key"],
                temperature, top_k, greedy, chunk)
            return toks

        def consume(block) -> bool:
            if on_chunk is not None:  # before notify: tokens precede sentinels
                on_chunk(block)
            for i in range(b):
                if done[i]:
                    continue
                for t in block[i]:
                    out[i].append(int(t))
                    if int(t) in stop_tokens or len(out[i]) >= max_new[i]:
                        done[i] = True
                        notify(i)
                        break
            state["tok"] = block[:, -1:].astype(np.int32)
            state["step"] += block.shape[1]
            return all(done)

        self._run_chunk_chain(
            scan, jnp.asarray(tok), consume, chunk=chunk,
            budget=max(max_new) - 1, cache_room=capacity - 1,
            cancel_check=cancel_check, initial_stop=all(done))
        # cache tail shorter than a chunk (the only way the chain drains
        # with rows still running): finish on the single-step batched
        # decoder, reusing the same consume() bookkeeping per [B, 1] block
        while (not all(done) and state["step"] < max(max_new) - 1
               and capacity - 1 - state["step"] > 0
               and not (cancel_check is not None and cancel_check())):
            step_key, state["key"] = jax.random.split(state["key"])
            nxt, state["caches"] = self._decode_step_batch(
                self.params, jnp.asarray(state["tok"]),
                jnp.asarray(state["step"], jnp.int32), lengths, bucket_arr,
                state["caches"], step_key, temperature, top_k, greedy)
            # per-step fetch by design: this legacy batch path streams one
            # token per dispatch (the continuous engine is the served path)
            consume(np.asarray(nxt)[:, None].astype(np.int32))  # tpulint: disable=TPL101
        for i in range(b):  # stragglers: budget/cancel exits without done[i]
            notify(i)
        t_decode = time.time() - t0
        n_gen = sum(len(o) for o in out)
        return out, {
            "batch": b,
            "prompt_tokens": sum(lens),
            "generated_tokens": n_gen,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tokens_per_s": n_gen / t_decode if t_decode > 0 else 0.0,
        }

    # ---------------------------------------------------------------- public
    def _bucket(self, n: int) -> int:
        p = 16
        while p < n:
            p *= 2
        return min(p, self.cfg.max_seq)

    def _start_generation(self, prompt_tokens: List[int], max_new_tokens: int,
                          sample: SampleConfig, seed: Optional[int],
                          prefix=None, kv_extract=None, on_prefill_kv=None):
        """Shared prologue of both decoders: validate, prefill, sample the
        first token from prefill logits on the host, seed the split chain.
        Returns (first_tok, caches, key, n_prompt, max_new_tokens, t_prefill,
        n_cached).

        ``prefix``: optional ``(n_cached, kv)`` from a prefix-cache hit —
        the cached KV is restored into ``[0, n_cached)`` and ONLY the
        suffix ``[n_cached, n_prompt)`` pays prefill (``_prefill_from``).
        ``kv_extract``: optional ``(start, end)`` token range to slice out
        of the prefilled cache and hand to ``on_prefill_kv`` as host numpy
        arrays (the prefix-cache insert hook).  With both None the path is
        byte-for-byte the pre-prefix-cache behavior.
        """
        c = self.cfg
        n_prompt = len(prompt_tokens)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if n_prompt + max_new_tokens > c.max_seq:
            max_new_tokens = c.max_seq - n_prompt
            if max_new_tokens <= 0:
                raise ValueError(f"prompt ({n_prompt}) exceeds ctx {c.max_seq}")
        n_cached = 0
        if prefix is not None and prefix[0] > 0:
            n_cached = int(prefix[0])
            if n_cached >= n_prompt:
                raise ValueError(f"cached prefix ({n_cached}) must leave "
                                 f"a suffix of prompt ({n_prompt})")

        t0 = time.time()
        length = jnp.asarray([n_prompt], jnp.int32)
        if n_cached:
            prefix_dev = self._prefix_to_device(
                prefix[1], prefix[2] if len(prefix) > 2 else None)
            bucket = min(self._bucket(n_prompt - n_cached),
                         c.max_seq - n_cached)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n_prompt - n_cached] = prompt_tokens[n_cached:]
            if bucket * c.max_seq <= self.MASKED_PREFILL_MAX:
                # one dispatch: in-graph caches + restore + masked prefill
                # (no host-side cache allocation — the fused program builds
                # its own)
                logits, caches = self._prefill_prefix_fused(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(n_cached, jnp.int32), length, prefix_dev)
            else:
                caches = self._restore_kv_rows(
                    init_kv_caches(c, 1, dtype=self.cache_dtype,
                                   mesh=self.kv_mesh), prefix_dev)
                logits, caches = self._prefill_from(tokens, n_cached, length,
                                                    caches)
        else:
            caches = init_kv_caches(c, 1, dtype=self.cache_dtype,
                                    mesh=self.kv_mesh)
            bucket = self._bucket(n_prompt)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n_prompt] = prompt_tokens
            if bucket > self.PREFILL_CHUNK:
                logits, caches = self._prefill_walk(
                    self.params, jnp.asarray(tokens), length, caches)
            else:
                logits, caches = self._prefill(self.params,
                                               jnp.asarray(tokens),
                                               length, caches)
        if kv_extract is not None and on_prefill_kv is not None:
            s, e = kv_extract
            if e > s:
                # mirror the engine path's guard: a failing cache insert
                # must not 500 a completion the device already produced
                try:
                    on_prefill_kv(self.extract_prefix_host(caches, 0, s,
                                                           e - s))
                except Exception:
                    log.exception("on_prefill_kv failed (prefix-cache "
                                  "insert skipped)")
        key = jax.random.PRNGKey(np.random.randint(0, 2**31) if seed is None else seed)

        # first sampled token comes from prefill logits: reuse decode's sampling
        # by treating it as a temperature/top-k draw on the host side once.
        first = self._sample_host(logits, sample, key)
        key = jax.random.fold_in(key, 0)
        return (first, caches, key, n_prompt, max_new_tokens,
                time.time() - t0, n_cached)

    def generate(
        self,
        prompt_tokens: List[int],
        max_new_tokens: int = 128,
        sample: SampleConfig = SampleConfig(),
        seed: Optional[int] = None,
        stop_tokens: Tuple[int, ...] = (),
        on_token=None,
        prefix=None,
        kv_extract=None,
        on_prefill_kv=None,
    ) -> Tuple[List[int], Dict[str, float]]:
        """Returns (generated token ids, timing stats).

        ``on_token(tok_id)`` — optional per-token callback, invoked as soon as
        each token id is known (including any stop token) — the hook the SSE
        streaming endpoints use.  The decode step for token i+1 is already in
        flight on device when the callback for token i runs, so streaming
        costs no TPU idle time.

        ``prefix`` / ``kv_extract`` / ``on_prefill_kv`` — prefix-KV-cache
        hooks, see ``_start_generation``.
        """
        next_tok, caches, key, n_prompt, max_new_tokens, t_prefill, n_cached = (
            self._start_generation(prompt_tokens, max_new_tokens, sample, seed,
                                   prefix, kv_extract, on_prefill_kv))
        t0 = time.time()

        out: List[int] = []
        for i in range(max_new_tokens):
            tok = int(next_tok)
            out.append(tok)
            if on_token is not None:
                on_token(tok)
            if tok in stop_tokens:
                break
            step_key, key = jax.random.split(key)
            next_tok_arr, caches = self._decode_step(
                self.params, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray(n_prompt + i, jnp.int32), caches, step_key,
                jnp.float32(sample.temperature), jnp.int32(sample.top_k),
                jnp.bool_(sample.greedy))
            # per-token fetch by design: this is the streaming solo path —
            # the on_token SSE cadence IS one token per dispatch
            next_tok = np.asarray(next_tok_arr)[0]  # tpulint: disable=TPL101
        return out, self._finish_stats(out, n_prompt, t_prefill, t0, n_cached)

    def generate_fused(
        self,
        prompt_tokens: List[int],
        max_new_tokens: int = 128,
        sample: SampleConfig = SampleConfig(),
        seed: Optional[int] = None,
        stop_tokens: Tuple[int, ...] = (),
        chunk: int = 32,
        cancel_check=None,
        prefix=None,
        kv_extract=None,
        on_prefill_kv=None,
    ) -> Tuple[List[int], Dict[str, float]]:
        """Like ``generate`` but decodes ``chunk`` tokens per device dispatch
        (``lax.scan``) instead of one — the throughput path when no per-token
        streaming callback is needed.  Chunks are dispatched as a pipelined
        chain (next chunk's first token stays on device), so stop tokens are
        honoured at chunk granularity with up to ``depth`` (2) in-flight
        chunks of speculative device work discarded: at most
        ``chunk - 1 + depth*chunk`` tokens.  With ``greedy`` the output
        matches ``generate`` token-for-token (same split chain).

        ``cancel_check()`` — optional; polled between chunks, return True to
        abandon generation (coarser than ``generate``'s per-token hook by at
        most one chunk of device work).
        """
        if chunk <= 0:
            raise ValueError(f"chunk must be positive, got {chunk}")
        first, caches, key, n_prompt, max_new_tokens, t_prefill, n_cached = (
            self._start_generation(prompt_tokens, max_new_tokens, sample, seed,
                                   prefix, kv_extract, on_prefill_kv))
        t0 = time.time()
        out: List[int] = [] if max_new_tokens <= 0 else [first]
        tok = first
        # Greedy output still matches `generate` token-for-token under the
        # pipelined chain: the scans run in the same order with the same
        # split chain — only the host's fetch position moves.
        state = {"caches": caches, "key": key, "tok": tok}

        def scan(first_dev, dispatched):
            # always scan a FULL chunk — one compiled signature; surplus
            # tokens are discarded on the host
            toks, state["caches"], state["key"] = self._decode_scan(
                self.params, first_dev, state["caches"],
                jnp.asarray(n_prompt + dispatched, jnp.int32), state["key"],
                jnp.float32(sample.temperature), jnp.int32(sample.top_k),
                jnp.bool_(sample.greedy), chunk)
            return toks

        def consume(block) -> bool:
            for t in (int(x) for x in block[0]):
                out.append(t)
                state["tok"] = t
                if (stop_tokens and t in stop_tokens) or \
                        len(out) >= max_new_tokens:
                    return True
            return False

        self._run_chunk_chain(
            scan, jnp.asarray([[tok]], jnp.int32), consume, chunk=chunk,
            budget=max_new_tokens - 1,
            cache_room=self.cfg.max_seq - n_prompt,
            cancel_check=cancel_check,
            initial_stop=bool(stop_tokens and tok in stop_tokens))
        caches, key, tok = state["caches"], state["key"], state["tok"]
        # cache tail shorter than a chunk (the only way the chain drains
        # without stopping): finish on the already-compiled per-token step
        # instead of compiling a new scan signature for this tail length
        while (len(out) and len(out) < max_new_tokens
               and not (stop_tokens and tok in stop_tokens)
               and not (cancel_check is not None and cancel_check())):
            step_key, key = jax.random.split(key)
            nxt, caches = self._decode_step(
                self.params, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray(n_prompt + len(out) - 1, jnp.int32),
                caches, step_key, jnp.float32(sample.temperature),
                jnp.int32(sample.top_k), jnp.bool_(sample.greedy))
            # per-token fetch by design: the stop-token check needs each
            # token on the host before the next dispatch
            tok = int(np.asarray(nxt)[0])  # tpulint: disable=TPL101
            out.append(tok)
        return out, self._finish_stats(out, n_prompt, t_prefill, t0, n_cached)

    def _finish_stats(self, out: List[int], n_prompt: int, t_prefill: float,
                      t0: float, n_cached: int = 0) -> Dict[str, float]:
        t_decode = time.time() - t0
        n_gen = len(out)
        return {
            "prompt_tokens": n_prompt,
            "generated_tokens": n_gen,
            "cached_tokens": n_cached,
            "prefill_tokens": n_prompt - n_cached,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tokens_per_s": n_gen / t_decode if t_decode > 0 and n_gen else 0.0,
        }

    @staticmethod
    def _sample_host(logits, sample: SampleConfig, key) -> int:
        logits = np.asarray(logits, np.float32)[0]
        if sample.greedy:
            return int(np.argmax(logits))
        scaled = logits / max(sample.temperature, 1e-4)
        if sample.top_k > 0 and sample.top_k < scaled.shape[-1]:
            kth = np.partition(scaled, -sample.top_k)[-sample.top_k]
            scaled = np.where(scaled >= kth, scaled, -np.inf)
        probs = np.exp(scaled - scaled.max())
        probs /= probs.sum()
        rng = np.random.RandomState(int(jax.random.randint(key, (), 0, 2**31 - 1)))
        return int(rng.choice(len(probs), p=probs))
