"""Histogram kernel — Pallas TPU (DEM global stage of Huffman-X).

GPU histograms use shared-memory atomics [paper ref 43]; TPUs have no
atomics, so the TPU-native formulation is a **one-hot matmul** on the MXU.
A bin tile of ``BT = H·128`` bins is viewed as an ``(H, 128)`` grid; a key
``k`` lands in cell ``(hi, lo) = divmod(k - base, 128)``.  For one row of
128 keys (lane-dense), ``onehot(hi)`` is ``(H, 128)`` and ``onehot(lo)`` is
``(128, 128)``, both built by a compare against a sublane iota, and

    counts[hi, lo] += onehot(hi) @ onehot(lo)ᵀ

is one NT matmul: exact in bf16 with f32 accumulation (each product sums at
most 128 ones).  The grid is ``(bin tiles, key tiles)`` with the key axis
last, so each bin tile's ``(H, 128)`` output block stays resident in VMEM
while every key tile accumulates into it — the TPU analogue of the paper's
global-synchronisation stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_KT = 256     # rows of 128 keys per grid cell
MAX_BT = 8192        # bins per grid cell (a multiple of 1024)


def _hist_kernel(keys_ref, out_ref, *, h):
    j = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    base = j * (h * LANES)
    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (h, LANES), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def row(r, acc):
        local = keys_ref[pl.ds(r, 1), :] - base          # (1, 128)
        hi = local >> 7                                    # floor: -1 pads < 0
        lo = local & (LANES - 1)
        oh_hi = (hi_ids == hi).astype(jnp.bfloat16)       # (H, 128 keys)
        oh_lo = (lo_ids == lo).astype(jnp.bfloat16)       # (128 bins, 128 keys)
        return acc + jax.lax.dot_general(
            oh_hi, oh_lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, keys_ref.shape[0], row, jnp.zeros((h, LANES), jnp.float32)
    )
    out_ref[...] += acc.astype(jnp.int32)  # ≤ KT·128 < 2^24: exact


def bin_tile(num_bins: int) -> int:
    """Bins per grid cell: the padded alphabet, up to ``MAX_BT``."""
    return min(MAX_BT, -(-num_bins // 1024) * 1024)


@functools.partial(jax.jit, static_argnames=("num_bins", "kt", "interpret"))
def histogram(
    keys: jax.Array,
    num_bins: int,
    *,
    interpret: bool,
    kt: int = DEFAULT_KT,
) -> jax.Array:
    keys = keys.reshape(-1).astype(jnp.int32)
    n = keys.shape[0]
    rows = max(1, -(-n // LANES))
    kt = min(kt, -(-rows // 8) * 8)
    rows = -(-rows // kt) * kt
    keys = jnp.pad(keys, (0, rows * LANES - n), constant_values=-1)  # no bin
    bt = bin_tile(num_bins)
    nb = -(-num_bins // bt) * bt
    h = bt // LANES
    out = pl.pallas_call(
        functools.partial(_hist_kernel, h=h),
        grid=(nb // bt, rows // kt),
        in_specs=[pl.BlockSpec((kt, LANES), lambda j, i: (i, 0))],
        out_specs=pl.BlockSpec((h, LANES), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb // LANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(keys.reshape(rows, LANES))
    return out.reshape(-1)[:num_bins]
