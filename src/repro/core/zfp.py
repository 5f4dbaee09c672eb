"""ZFP-X fixed-rate compression — HPDR §IV-C (Algorithm 3), TPU-native.

Per 4^d block (paper Fig. 7):
  1. exponent alignment: block values → common fixed-point scale 2^(30-emax);
  2. forward near-orthogonal lifting transform along each dimension
     (the exact zfp integer lift — lossy in the lowest ~2 bits by design,
     identical to libzfp's non-reversible path);
  3. two's-complement → negabinary so sign information lives in high bits;
  4. coefficient reordering by total sequency (low frequencies first);
  5. bitplane truncation + serialization: keep the top ``rate`` bitplanes,
     pack them plane-major (transposed) into 32-bit words.

Every stage is blockwise (Locality → GEM); fixed rate means every block's
output has identical size, so serialization needs **no** global coordination
(paper: "this can be done without global coordination") — offsets are affine.

TPU adaptation notes (DESIGN.md §2): GPU zfp packs bits with per-thread shifts
inside a warp; here bitplane packing is a dense ``(plane, coeff)`` bit matrix
reduction (``bits_to_words``), which XLA/Pallas lower to vector ops, and the
hot path has a Pallas kernel in ``repro/kernels/zfp_block``.

Header layout per block: 1 × int32 emax word.  Payload: ceil(rate·4^d/32)
uint32 words per block.  ``rate`` is bits/value, 1..32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import bitstream as bs
from .abstractions import pad_to_blocks

NBMASK = 0xAAAAAAAA  # Python int → inlined literal (Pallas-safe)
_I32 = jnp.int32
_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Stage 2: the zfp integer lifting transform (exact libzfp arithmetic)
# ---------------------------------------------------------------------------


def fwd_lift(x, y, z, w):
    """Forward lift of one 4-vector given as its four components (int32)."""
    x = x + w
    x = x >> 1
    w = w - x
    z = z + y
    z = z >> 1
    y = y - z
    x = x + z
    x = x >> 1
    z = z - x
    w = w + y
    w = w >> 1
    y = y - w
    w = w + (y >> 1)
    y = y - (w >> 1)
    return x, y, z, w


def inv_lift(x, y, z, w):
    """Inverse of :func:`fwd_lift` (up to the dropped low bits)."""
    y = y + (w >> 1)
    w = w - (y >> 1)
    y = y + w
    w = w << 1
    w = w - y
    z = z + x
    x = x << 1
    x = x - z
    y = y + z
    z = z << 1
    z = z - y
    w = w + x
    x = x << 1
    x = x - w
    return x, y, z, w


def fwd_lift_vec(v: jax.Array) -> jax.Array:
    """Forward lift of 4-vectors along the last axis (int32)."""
    return jnp.stack(fwd_lift(*(v[..., i] for i in range(4))), axis=-1)


def inv_lift_vec(v: jax.Array) -> jax.Array:
    """Inverse lift of 4-vectors along the last axis (int32)."""
    return jnp.stack(inv_lift(*(v[..., i] for i in range(4))), axis=-1)


def fwd_transform(block: jax.Array) -> jax.Array:
    """Apply the forward lift along every dimension of a 4^d block."""
    for axis in range(block.ndim):
        moved = jnp.moveaxis(block, axis, -1)
        moved = fwd_lift_vec(moved)
        block = jnp.moveaxis(moved, -1, axis)
    return block


def inv_transform(block: jax.Array) -> jax.Array:
    for axis in reversed(range(block.ndim)):
        moved = jnp.moveaxis(block, axis, -1)
        moved = inv_lift_vec(moved)
        block = jnp.moveaxis(moved, -1, axis)
    return block


# ---------------------------------------------------------------------------
# Stage 3: negabinary
# ---------------------------------------------------------------------------


def int_to_negabinary(q: jax.Array) -> jax.Array:
    u = q.astype(_I32).view(_U32)
    return (u + np.uint32(NBMASK)) ^ np.uint32(NBMASK)


def negabinary_to_int(u: jax.Array) -> jax.Array:
    return ((u.astype(_U32) ^ np.uint32(NBMASK)) - np.uint32(NBMASK)).view(_I32)


# ---------------------------------------------------------------------------
# Stage 4: sequency (total-order) permutation
# ---------------------------------------------------------------------------


def sequency_permutation(dims: int) -> np.ndarray:
    """Flat indices of a 4^d block ordered by total sequency (i+j+k...).

    libzfp ships hand-tuned tie-break tables; any *fixed* permutation keyed
    by total order preserves the energy-compaction property — ties are broken
    by flat index (documented format deviation, versioned in the header).
    """
    coords = np.stack(
        np.meshgrid(*([np.arange(4)] * dims), indexing="ij"), axis=-1
    ).reshape(-1, dims)
    total = coords.sum(axis=1)
    flat = np.arange(coords.shape[0])
    order = np.lexsort((flat, total))
    return order.astype(np.int32)


# ---------------------------------------------------------------------------
# Stage 1: exponent alignment
# ---------------------------------------------------------------------------


def float_exponent(a: jax.Array) -> jax.Array:
    """``frexp`` exponent of finite ``a >= 0`` (0 for 0), from the float bits.

    Integer-only, so the XLA and Pallas lowerings agree on every chip,
    subnormals included: ``a = m·2^e`` with ``0.5 <= m < 1``.
    """
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), _I32)
    biased = (bits >> 23) & 0xFF
    mant = bits & 0x7FFFFF
    bitlen = sum(((mant >> k) != 0).astype(_I32) for k in range(23))
    e = jnp.where(biased > 0, biased - 126, bitlen - 149)
    return jnp.where(bits == 0, 0, e).astype(_I32)


def _pow2(k: jax.Array) -> jax.Array:
    """Exact float32 2^k for integer ``k`` in [-126, 127], from its bits."""
    return jax.lax.bitcast_convert_type((k.astype(_I32) + 127) << 23, jnp.float32)


def scale_pow2(x: jax.Array, k: jax.Array) -> jax.Array:
    """``x · 2^k`` for float32 ``x`` and integer ``k`` in [-252, 254].

    Two exact power-of-two factors, each in the normal range, so no factor
    overflows or flushes where one ``exp2(k)`` would.
    """
    h = k.astype(_I32) >> 1
    return x * _pow2(h) * _pow2(k - h)


def block_emax(block: jax.Array) -> jax.Array:
    """Max binary exponent e with |x| < 2^e over the block (0 for all-zero)."""
    return float_exponent(jnp.max(jnp.abs(block)))


def to_fixed_point(block: jax.Array, emax: jax.Array) -> jax.Array:
    """float → int32 at scale 2^(30-emax): |q| < 2^30 (2 headroom bits)."""
    return jnp.round(scale_pow2(block.astype(jnp.float32), 30 - emax)).astype(_I32)


def from_fixed_point(q: jax.Array, emax: jax.Array, dtype=jnp.float32) -> jax.Array:
    return scale_pow2(q.astype(jnp.float32), emax - 30).astype(dtype)


# ---------------------------------------------------------------------------
# Stage 5: bitplane truncation + serialization (fixed rate)
# ---------------------------------------------------------------------------


def plane_bits(block_size: int, rate: int) -> int:
    """Total kept bits per block (excluding the emax header word)."""
    return rate * block_size


def words_per_block(block_size: int, rate: int) -> int:
    return bs.words_needed(plane_bits(block_size, rate))


def pack_bitplanes(u: jax.Array, rate: int) -> jax.Array:
    """``u``: (..., block_size) negabinary coeffs → (..., wpb) uint32 words.

    Plane-major (transposed) layout: all block bits of plane 0 (MSB), then
    plane 1, ... — so truncation is a prefix cut, like zfp's embedded stream.
    """
    block_size = u.shape[-1]
    shifts = 31 - jax.lax.iota(_U32, rate)  # MSB-first planes (traced, Pallas-safe)
    bits = (u[..., None, :] >> shifts[:, None]) & np.uint32(1)  # (..., rate, bs)
    flat = bits.reshape(bits.shape[:-2] + (rate * block_size,))
    pad = (-flat.shape[-1]) % 32
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    grouped = flat.reshape(flat.shape[:-1] + (flat.shape[-1] // 32, 32))
    return bs.bits_to_words(grouped)


def unpack_bitplanes(words: jax.Array, rate: int, block_size: int) -> jax.Array:
    """Inverse of :func:`pack_bitplanes`; dropped planes read as zero."""
    bits = bs.words_to_bits(words)  # (..., wpb, 32)
    flat = bits.reshape(bits.shape[:-2] + (bits.shape[-2] * 32,))
    flat = flat[..., : rate * block_size]
    planes = flat.reshape(flat.shape[:-1] + (rate, block_size))
    shifts = 31 - jax.lax.iota(_U32, rate)
    return jnp.sum(planes.astype(_U32) << shifts[:, None], axis=-2, dtype=_U32)


# ---------------------------------------------------------------------------
# Whole-array fixed-rate compress / decompress (Locality over blocks)
# ---------------------------------------------------------------------------


@dataclass
class ZFPCompressed:
    """Fixed-rate ZFP-X stream: per-block emax headers + bitplane payload."""

    payload: jax.Array           # uint32[n_blocks, words_per_block]
    emax: jax.Array              # int32[n_blocks]
    shape: tuple[int, ...]       # original array shape
    rate: int                    # bits per value
    dtype: str = "float32"
    layout_version: int = 1

    def nbytes(self) -> int:
        return int(self.payload.nbytes + self.emax.nbytes)

    @property
    def dims(self) -> int:
        return len(self.shape)


def _compress_blocks(blocks: jax.Array, rate: int, perm: jax.Array):
    """blocks: (nb, 4, 4, ...) float → (payload, emax).  One GEM stage chain."""
    nb = blocks.shape[0]
    block_size = int(np.prod(blocks.shape[1:]))

    def one(block):
        emax = block_emax(block)
        q = to_fixed_point(block, emax)
        t = fwd_transform(q)
        u = int_to_negabinary(t)
        u = u.reshape(block_size)[perm]
        return pack_bitplanes(u, rate), emax

    payload, emax = jax.vmap(one)(blocks)
    return payload.reshape(nb, -1), emax


def _decompress_blocks(
    payload: jax.Array, emax: jax.Array, rate: int, inv_perm: jax.Array,
    block_shape: tuple[int, ...],
):
    block_size = int(np.prod(block_shape))

    def one(words, e):
        u = unpack_bitplanes(words, rate, block_size)
        u = u[inv_perm].reshape(block_shape)
        t = negabinary_to_int(u)
        q = inv_transform(t)
        return from_fixed_point(q, e)

    return jax.vmap(one)(payload, emax)


def field_to_blocks(padded: jax.Array) -> jax.Array:
    """(4^d, nb) blocks of a block-aligned field, coefficient-major.

    Row ``c`` holds in-block coefficient ``c`` (C order over ``(4,)*d``) of
    every block, blocks in C order.  This is the ``zfp_block`` kernels'
    layout: the block batch rides the minor axis.

    Each axis is split into its (count, offset) pair only while it is off
    the minor (lane) axis, where the split is a reshape; no intermediate
    has a 4-wide minor axis, which the TPU's (8, 128) tiling would pad
    32×.  Axis 0 first takes the minor axis, the other axes split and their
    counts merge into one axis, which then takes the minor axis in turn
    while axis 0 splits.  Merging the counts keeps a leaf of 32-wide axes
    (the engine's ``(n, 32, 32)``) to a 64-wide minor axis, where the exact
    mirror of :func:`blocks_to_field` would leave an (8, 8) minor pair,
    16× padded.  A 1-D field is cut into rows of 128 blocks, whose 512
    values move off the minor axis to split there.

    On a v5e this blocks a 512³ float32 field in about 12 ms.  One strided
    slice per in-block offset, which reads the minor axis at stride 4, took
    41 ms for each of its 64 slices, and 3.0 s for the four of a 1-D field
    of 2^27 values.
    """
    d = padded.ndim
    if d == 1:
        n = padded.shape[0]
        rows = jnp.pad(padded, (0, -n % 512)).reshape(-1, 512)
        a = jnp.transpose(rows.T.reshape(128, 4, -1), (1, 2, 0))  # (o, row, block)
        return a.reshape(4, -1)[:, : n // 4]
    n0 = padded.shape[0]
    split = sum(((p // 4, 4) for p in padded.shape[1:]), ())
    a = jnp.moveaxis(padded, 0, -1).reshape(split + (n0,))
    # (c_1, o_1, .., c_{d-1}, o_{d-1}, n_0) → (o_1.., c_1.., n_0), counts merged
    a = jnp.transpose(a, (*range(1, 2 * d - 2, 2), *range(0, 2 * d - 2, 2), 2 * d - 2))
    a = a.reshape((4,) * (d - 1) + (-1, n0))
    # the merged counts take the minor axis; n_0 splits into (c_0, o_0)
    a = jnp.swapaxes(a, -1, -2).reshape((4,) * (d - 1) + (n0 // 4, 4, -1))
    return jnp.moveaxis(a, -2, 0).reshape(4 ** d, -1)


def blocks_to_field(blocks: jax.Array, padded: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`field_to_blocks` for a field of shape ``padded``.

    Scatter-free: each axis is rebuilt from its (count, offset) pair moved
    to the front, where interleaving is a leading-dims reshape; the minor
    axis stays a block-count or field axis until the final transpose.
    """
    d = len(padded)
    a = blocks.reshape((4,) * d + tuple(p // 4 for p in padded))
    for axis in range(d):
        # dims now: (done axes, newest first) + offsets o_axis.. + counts c_axis..
        done = axis
        o_pos, c_pos = done, done + (d - axis)
        a = jnp.moveaxis(a, (c_pos, o_pos), (0, 1))
        a = a.reshape((a.shape[0] * 4,) + a.shape[2:])
    return jnp.transpose(a, tuple(range(d - 1, -1, -1)))


@partial(jax.jit, static_argnames=("rate", "dims", "shape", "adapter"))
def compress_jit(
    data: jax.Array, rate: int, dims: int, shape: tuple[int, ...],
    adapter: str | None = None,
):
    """Whole-array fixed-rate compress; ``adapter`` binds the block kernel.

    ``adapter=None`` keeps the historical inline jnp path; a concrete adapter
    routes the block stage through the ``zfp_block`` kernel registry
    (xla | pallas | pallas_interpret) — the dispatch happens at trace time,
    i.e. once per plan.
    """
    block_shape = (4,) * dims
    blocks = field_to_blocks(pad_to_blocks(data.reshape(shape), block_shape))
    if adapter is None:
        perm = jnp.asarray(sequency_permutation(dims))
        return _compress_blocks(
            blocks.T.reshape((-1,) + block_shape), rate, perm
        )
    from repro.kernels.zfp_block import ops as zfp_block_ops  # lazy: layer order

    return zfp_block_ops.compress_blocks(blocks, rate, dims, adapter=adapter)


@partial(jax.jit, static_argnames=("rate", "dims", "shape", "adapter"))
def decompress_jit(
    payload: jax.Array, emax: jax.Array, rate: int, dims: int,
    shape: tuple[int, ...], adapter: str | None = None,
):
    block_shape = (4,) * dims
    if adapter is None:
        perm = sequency_permutation(dims)
        inv_perm = jnp.asarray(np.argsort(perm).astype(np.int32))
        blocks = _decompress_blocks(payload, emax, rate, inv_perm, block_shape)
        blocks = blocks.reshape(blocks.shape[0], -1).T
    else:
        from repro.kernels.zfp_block import ops as zfp_block_ops  # lazy

        blocks = zfp_block_ops.decompress_blocks(
            payload, emax, rate, dims, adapter=adapter
        )
    from .abstractions import padded_shape

    full = blocks_to_field(blocks, padded_shape(shape, block_shape))
    return full[tuple(slice(0, d) for d in shape)]


def compress(data: jax.Array, rate: int = 16) -> ZFPCompressed:
    """Fixed-rate compress an N-d float array (N ≤ 4)."""
    if data.ndim > 4:
        raise ValueError("zfp supports 1-4 dimensional data")
    if not 1 <= rate <= 32:
        raise ValueError("rate must be in [1, 32] bits/value")
    payload, emax = compress_jit(data, rate, data.ndim, tuple(data.shape))
    return ZFPCompressed(
        payload=payload, emax=emax, shape=tuple(data.shape), rate=rate,
        dtype=str(data.dtype),
    )


def decompress(z: ZFPCompressed) -> jax.Array:
    out = decompress_jit(z.payload, z.emax, z.rate, z.dims, z.shape)
    return out.astype(jnp.dtype(z.dtype))


def compression_ratio(z: ZFPCompressed) -> float:
    orig = math.prod(z.shape) * jnp.dtype(z.dtype).itemsize
    return orig / z.nbytes()
