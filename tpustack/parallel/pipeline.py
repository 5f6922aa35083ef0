"""Pipeline parallelism (GPipe fill–drain) over a ``pp`` mesh axis.

The reference's only "pipeline" is llama.cpp's CPU/GPU layer split
(``--n-gpu-layers 35``, reference ``cluster-config/apps/llm/deployment.yaml:
69-83``) — a capacity workaround, not a parallel schedule.  Here pipeline
parallelism is a real training axis, built the TPU way:

- Layers are stacked ``[pp, layers_per_stage, ...]`` and sharded over the
  ``pp`` mesh axis (each device holds its stage's contiguous block).
- ``shard_map`` + ``lax.ppermute`` implement the schedule by hand —
  activations hop stage→stage over nearest-neighbor ICI; no NCCL-style
  send/recv plumbing, and reverse-mode AD differentiates straight through
  the scan + ppermute (backward pipeline for free).
- The batch is cut into microbatches streamed through a ``lax.scan`` over
  ``microbatches + pp - 1`` ticks (GPipe fill–drain; the bubble fraction is
  ``(pp-1) / (M + pp - 1)``).

Composes with ``dp``/``fsdp`` as *batch* axes (the shard_map runs per batch
shard).  Tensor parallelism inside a stage would need manual collectives in
``stage_fn`` (shard_map is manual mode) — by design the ``pp`` mesh puts
tp/sp at 1.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS


def shard_map(fn, *, mesh, in_specs, out_specs):
    # the replication checker can't see through the masked-psum broadcast at
    # the end of the schedule
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    microbatches: int,
    axis: str = "pp",
    batch_axes=("dp", "fsdp"),
) -> jax.Array:
    """Run ``x`` through ``pp`` stages of ``stage_fn``, GPipe-scheduled.

    Args:
      stage_fn: ``(one stage's params, h [mb, ...]) → h [mb, ...]`` — must
        preserve the activation shape (transformer blocks do).
      stage_params: pytree whose leaves lead with the stage dim ``[pp, ...]``
        (shard over ``axis`` via ``tpustack.parallel.sharding`` rules).
      x: ``[B, ...]`` batch; ``B`` must divide by ``microbatches`` (and its
        per-device shard under ``batch_axes`` too).
      mesh: mesh containing ``axis``; its other axes may shard the batch.

    Returns ``[B, ...]`` outputs, identical on every ``pp`` rank.
    """
    pp = mesh.shape[axis]
    m = microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by {m} microbatches")
    if pp < 2:
        raise ValueError(f"pipeline needs pp >= 2 on axis {axis!r}, got {pp}")
    data_ways = 1
    for a in batch_axes:
        if a in mesh.axis_names:
            data_ways *= mesh.shape[a]
    if (b // m) % data_ways:
        raise ValueError(
            f"microbatch size {b // m} (batch {b} / {m} microbatches) must "
            f"divide over the {data_ways} data-parallel shards — use a "
            f"larger batch or fewer microbatches")
    xs = x.reshape(m, b // m, *x.shape[1:])

    batch_spec = PS(None, tuple(a for a in batch_axes if a in mesh.axis_names))

    def spmd(params_local, xs_local):
        rank = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda t: t[0], params_local)  # drop pp dim
        t_total = m + pp - 1
        zero_mb = jnp.zeros_like(xs_local[0])

        def tick(carry, t):
            recv, acc = carry
            mb_idx = jnp.clip(t, 0, m - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs_local, mb_idx, 0,
                                                 keepdims=False)
            h = stage_fn(params, jnp.where(rank == 0, fresh, recv))
            # hop to the next stage (ring; rank pp-1 → 0 hop is ignored)
            recv = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % pp) for i in range(pp)])
            # the last stage emitted microbatch t - (pp-1) this tick
            out_idx = jnp.clip(t - (pp - 1), 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(acc, out_idx, 0, keepdims=False)
            acc = jax.lax.dynamic_update_index_in_dim(
                acc, jnp.where(t - (pp - 1) >= 0, h, cur), out_idx, 0)
            return (recv, acc), None

        (_, acc), _ = jax.lax.scan(
            tick, (zero_mb, jnp.zeros_like(xs_local)), jnp.arange(t_total))
        # every rank ran the scan (SPMD), but only the last stage's ``acc``
        # holds the pipeline's output — broadcast it
        return jax.lax.psum(
            jnp.where(rank == pp - 1, acc, jnp.zeros_like(acc)), axis)

    out = shard_map(
        spmd, mesh=mesh,
        in_specs=(PS(axis), batch_spec),
        out_specs=batch_spec,
    )(stage_params, xs)
    return out.reshape(b, *x.shape[1:])


def stack_stages(stacked_layers: Any, pp: int) -> Any:
    """``[L, ...]`` stacked layer params → ``[pp, L/pp, ...]`` stage blocks."""

    def reshape(t):
        l = t.shape[0]
        if l % pp:
            raise ValueError(f"{l} layers not divisible by pp={pp}")
        return t.reshape(pp, l // pp, *t.shape[1:])

    return jax.tree.map(reshape, stacked_layers)
