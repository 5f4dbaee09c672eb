"""Huffman encode kernel — Pallas TPU (Locality stage of Huffman-X).

Per grid cell: a lane-dense ``(T, 128)`` tile of keys is encoded by a
gather of (code, length) from the canonical codebook staged in VMEM — the
analogue of the GPU kernel's shared-memory codebook.  The TPU has no vector
gather, so the lookup is a **two-level one-hot matmul** on the MXU: a key
splits into ``(hi, lo) = divmod(k, LO)``; the codebook is held as byte
planes ``P[p, lo, hi]`` (four code bytes and the length, exact in bf16);
for one row of 128 keys

    M = P @ onehot(hi)          # (planes·LO, H) @ (H, 128) → per-key rows

gathers every key's row of the table, and a compare against ``lo`` selects
the entry.  Each output is a single nonzero product, so the f32 result is
exact and the kernel equals the oracle's gather bit for bit.  The
downstream global compaction (exclusive scan + segment-OR) stays a DEM/XLA
stage because it needs the global prefix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_T = 64   # rows of 128 keys per grid cell
PLANES = 5       # code bytes 0..3, then the code length


def table_split(k: int) -> tuple[int, int]:
    """(H, LO) with H·LO ≥ k: LO a power of two in [16, 128], H ≥ 128."""
    lo = 16
    while lo < LANES and lo * LANES < k:
        lo *= 2
    h = max(LANES, -(-k // (lo * LANES)) * LANES)
    return h, lo


def _encode_kernel(keys_ref, planes_ref, codes_ref, lens_ref, *, h, lo, k):
    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (h, LANES), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (lo, LANES), 0)
    planes = planes_ref[...]                               # (PLANES·LO, H)
    shift = lo.bit_length() - 1

    def row(r, carry):
        key = jnp.clip(keys_ref[pl.ds(r, 1), :], 0, k - 1)  # gather clamps
        oh_hi = (hi_ids == key >> shift).astype(jnp.bfloat16)  # (H, 128 keys)
        m = jnp.dot(planes, oh_hi, preferred_element_type=jnp.float32)
        sel = lo_ids == (key & (lo - 1))                     # (LO, 128)
        vals = [
            jnp.sum(jnp.where(sel, m[p * lo:(p + 1) * lo], 0.0), axis=0,
                    keepdims=True).astype(jnp.int32)
            for p in range(PLANES)
        ]
        codes_ref[pl.ds(r, 1), :] = (
            vals[0] | (vals[1] << 8) | (vals[2] << 16) | (vals[3] << 24)
        )
        lens_ref[pl.ds(r, 1), :] = vals[4]
        return carry

    jax.lax.fori_loop(0, keys_ref.shape[0], row, 0)


def _byte_planes(codes_table, lens_table, h, lo):
    """(PLANES·LO, H) bf16: plane p, entry hi·LO + l at row p·LO + l, col hi."""
    k = codes_table.shape[0]
    codes = codes_table.astype(jnp.uint32)
    planes = jnp.stack(
        [(codes >> (8 * b)) & 0xFF for b in range(4)]
        + [lens_table.astype(jnp.uint32) & 0xFF]
    )
    planes = jnp.pad(planes, ((0, 0), (0, h * lo - k)))
    planes = planes.reshape(PLANES, h, lo).transpose(0, 2, 1)
    return planes.reshape(PLANES * lo, h).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def encode_lookup(
    keys: jax.Array,         # (N,) int32 in [0, K)
    codes_table: jax.Array,  # (K,) uint32
    lens_table: jax.Array,   # (K,) int32, each ≤ 32
    *,
    interpret: bool,
    t: int = DEFAULT_T,
) -> tuple[jax.Array, jax.Array]:
    keys = keys.reshape(-1).astype(jnp.int32)
    n = keys.shape[0]
    k = codes_table.shape[0]
    h, lo = table_split(k)
    rows = max(1, -(-n // LANES))
    t = min(t, -(-rows // 8) * 8)
    rows = -(-rows // t) * t
    keys = jnp.pad(keys, (0, rows * LANES - n)).reshape(rows, LANES)
    block = pl.BlockSpec((t, LANES), lambda i: (i, 0))
    codes, lens = pl.pallas_call(
        functools.partial(_encode_kernel, h=h, lo=lo, k=k),
        grid=(rows // t,),
        in_specs=[
            block,
            pl.BlockSpec((PLANES * lo, h), lambda i: (0, 0)),  # codebook in VMEM
        ],
        out_specs=(block, block),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(keys, _byte_planes(codes_table, lens_table, h, lo))
    codes = jax.lax.bitcast_convert_type(codes.reshape(-1)[:n], jnp.uint32)
    return codes, lens.reshape(-1)[:n]
