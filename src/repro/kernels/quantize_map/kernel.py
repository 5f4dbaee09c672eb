"""Quantize kernel — Pallas TPU (Map&Process stage of MGARD-X).

Fuses per-level bin selection + uniform quantization + zig-zag in one pass
over the coefficient array.  The coefficients ride a lane-dense
``(rows, 128)`` layout; each grid cell stages ``(T, 128)`` of them in VMEM
next to the (tiny) per-level bin table, one level per sublane row broadcast
across the lanes.  The bin of each element is a compare-and-select over the
few levels (the TPU has no vector gather), which equals the oracle's table
gather for every in-range level id.  Every operand is a lane-dense VMEM
tile, so ``vmap`` (the engine's stacked buckets) batches the kernel with
legal blocks; an SMEM table would gain a batch row the TPU cannot block.
The inverse kernel fuses the matching dequantize.  This is the masked-dense
lowering of the paper's Map&Process abstraction (Fig. 3c).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_T = 512  # rows of 128 elements per grid cell (256 KiB of f32)


def _level_bins(lvl, bins_ref, nl: int):
    """Per-element bin by compare-and-select over the ``nl``-level table.

    Row ``l`` of ``bins_ref`` holds level ``l``'s bin in every lane.  Level
    ids clamp into the table like the oracle's gather does.
    """
    lvl = jnp.clip(lvl, 0, nl - 1)
    b = jnp.broadcast_to(bins_ref[0:1, :], lvl.shape)
    for level in range(1, nl):
        b = jnp.where(lvl == level, bins_ref[level:level + 1, :], b)
    return b


def _quant_kernel(x_ref, lvl_ref, bins_ref, q_ref, *, nl):
    q = jnp.round(x_ref[...] / _level_bins(lvl_ref[...], bins_ref, nl))
    q = q.astype(jnp.int32)
    q_ref[...] = (q << 1) ^ (q >> 31)  # zig-zag (bit pattern of the uint32 key)


def _dequant_kernel(u_ref, lvl_ref, bins_ref, x_ref, *, nl):
    u = u_ref[...]  # zig-zagged keys, carried as int32 bit patterns
    q = jax.lax.shift_right_logical(u, 1) ^ -(u & 1)
    x_ref[...] = q.astype(jnp.float32) * _level_bins(lvl_ref[...], bins_ref, nl)


def _lane_dense(n: int, t: int) -> tuple[int, int]:
    """(rows per tile, padded rows) for ``n`` elements in (rows, 128) tiles."""
    rows = -(-n // LANES)
    tile = min(t, -(-rows // 8) * 8)
    return tile, -(-rows // tile) * tile


def _map_call(kernel, a, levels, bins, out_dtype, t, interpret):
    n = a.shape[0]
    tile, rows = _lane_dense(n, t)
    pad = rows * LANES - n
    a = jnp.pad(a, (0, pad)).reshape(rows, LANES)
    levels = jnp.pad(levels, (0, pad)).reshape(rows, LANES)
    nl = bins.shape[0]
    table_rows = -(-nl // 8) * 8
    table = jnp.broadcast_to(bins.astype(jnp.float32)[:, None], (nl, LANES))
    table = jnp.pad(table, ((0, table_rows - nl), (0, 0)))
    block = pl.BlockSpec((tile, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(kernel, nl=nl),
        grid=(rows // tile,),
        in_specs=[block, block,
                  pl.BlockSpec((table_rows, LANES), lambda i: (0, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a, levels, table)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def quantize(
    x: jax.Array,        # (N,) float32 coefficients
    levels: jax.Array,   # (N,) int32 subset ids
    bins: jax.Array,     # (L+1,) float32
    *,
    interpret: bool,
    t: int = DEFAULT_T,
) -> jax.Array:
    q = _map_call(
        _quant_kernel, x.reshape(-1).astype(jnp.float32),
        levels.reshape(-1).astype(jnp.int32), bins, jnp.int32, t, interpret,
    )
    return jax.lax.bitcast_convert_type(q, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def dequantize(
    u: jax.Array,        # (N,) uint32 zig-zagged keys
    levels: jax.Array,
    bins: jax.Array,
    *,
    interpret: bool,
    t: int = DEFAULT_T,
) -> jax.Array:
    u = jax.lax.bitcast_convert_type(u.reshape(-1).astype(jnp.uint32), jnp.int32)
    return _map_call(
        _dequant_kernel, u, levels.reshape(-1).astype(jnp.int32), bins,
        jnp.float32, t, interpret,
    )
