"""Grouped matrix product for a routed-expert layer, in Pallas.

``moe_gmm(x, w, scale, plan)``: the rows of ``x [M, K]`` are token-expert
pairs sorted by expert, every expert's group padded to a whole number of
``tm``-row tiles (to its OWN next tile, not to the longest group), and row
tile ``i`` is multiplied by the matrix of expert ``tile_expert[i]`` out of
the stack ``w [E, K, N]`` (int8 with one f32 ``scale [E, N]`` per output
channel, or a float stack with ``scale=None``).

What the call reads follows the routing.  The grid is (column tiles of
``N``, row tiles of ``M``); the stack stays in HBM behind a BlockSpec whose
index map reads ``tile_expert`` from scalar-prefetch memory, so a step
fetches one ``[K, tn]`` panel of one expert — and an expert no token chose
has no tile, hence no fetch.  Row tiles of one expert follow each other, so
its panel is fetched once per column tile however many tiles its group has.
``M`` is sized for the worst routing (every pair lands here); the tiles past
``n_active`` map to the blocks of the last live tile, so they move no bytes,
and skip their product.  What they leave in the output is whatever was
there: callers select rows by the plan, never multiply by a zero gate.

One body serves a decode step (a few rows an expert: bound by the bytes of
the panels) and a 512-token admission (hundreds of rows an expert: bound by
the products); ``tm`` is the caller's, from the rows it expects an expert to
get.  int8 panels are cast to the activations' type in VMEM a ``K`` slab at
a time (int8 values are exact in bf16), the product accumulates in f32, the
scale is applied to the f32 sum and the result rounds once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes one panel of the stack may take in VMEM (it is double-buffered)
PANEL_BYTES = 3 * 1024 * 1024
#: rows of ``K`` cast and multiplied at a time inside a step
K_SLAB = 1024
#: the call states its VMEM need: two panels, two row tiles of ``x``, a
#: slab's cast and the f32 sum pass the 16 MB a v5e kernel gets unasked
VMEM_LIMIT = 64 * 1024 * 1024


def column_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of one panel: all of ``N`` when that fits ``PANEL_BYTES``,
    else the widest multiple of 128 that divides ``N`` and fits."""
    if k * n * itemsize <= PANEL_BYTES or n % 128:
        return n
    tn = max(128, PANEL_BYTES // (k * itemsize) // 128 * 128)
    while n % tn:
        tn -= 128
    return tn


def _gmm_kernel(te_ref, na_ref, x_ref, w_ref, s_ref, o_ref, *, quant: bool):
    del te_ref  # read by the index maps
    k = x_ref.shape[1]

    @pl.when(pl.program_id(1) < na_ref[0])
    def _():
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for k0 in range(0, k, K_SLAB):          # static
            k1 = min(k, k0 + K_SLAB)
            acc += jax.lax.dot_general(
                x_ref[:, k0:k1], w_ref[0, k0:k1, :].astype(x_ref.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if quant:
            acc = acc * s_ref[0]
        o_ref[...] = acc.astype(o_ref.dtype)


def moe_gmm(x: jax.Array, w: jax.Array, scale: Optional[jax.Array],
            tile_expert: jax.Array, n_active: jax.Array, *, tm: int,
            interpret: Optional[bool] = None) -> jax.Array:
    """``x [M, K]`` (``M`` a multiple of ``tm``) x ``w [E, K, N]`` by row
    tile -> ``[M, N]`` in ``x``'s dtype.  ``tile_expert [M // tm]`` int32,
    ``n_active`` int32 scalar: the tiles that hold a pair."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _moe_gmm(x, w, scale, tile_expert.astype(jnp.int32),
                    jnp.asarray(n_active, jnp.int32).reshape(1), tm=tm,
                    interpret=interpret)


# jitted on its own, like the paged kernel's call: a program's layers trace
# and lower the body once
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _moe_gmm(x, w, scale, tile_expert, n_active, *, tm, interpret):
    m, k = x.shape
    e, kw, n = w.shape
    if k != kw or m % tm:
        raise ValueError(f"x {x.shape} against stack {w.shape}, tm {tm}")
    quant = scale is not None
    tn = column_tile(k, n, w.dtype.itemsize)
    live = lambda i, na: jnp.maximum(jnp.minimum(i, na[0] - 1), 0)
    panel = lambda j, i, te, na: (te[live(i, na)], 0, j)
    if quant:
        s_arg = scale.reshape(e, 1, n).astype(jnp.float32)
        s_spec = pl.BlockSpec((1, 1, tn), panel)
    else:
        # one kernel arity: a dummy scalar the kernel ignores
        s_arg = jnp.zeros((1,), jnp.float32)
        s_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, na: (live(i, na), 0)),
            pl.BlockSpec((1, k, tn), panel),
            s_spec,
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, te, na: (live(i, na), j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        # in order on one core: consecutive steps that name the same block
        # are what keeps a panel, and the dead tiles, from being fetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="moe_gmm",  # the device trace's name for it: a promise
        interpret=interpret,
    )(tile_expert, n_active, x, w, s_arg)
