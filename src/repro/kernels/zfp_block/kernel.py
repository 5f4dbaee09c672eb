"""ZFP-X block kernel — Pallas TPU implementation (GEM lowering).

One grid cell processes ``TR × 128`` 4^d blocks staged in VMEM (the paper's
block→SM mapping becomes block-batch→grid-cell).  The blocks ride the
lanes: blocks arrive and leave coefficient-major, ``(4^d, blocks)``, and
the wrapper only pads and reshapes them to ``(4^d, blocks/128, 128)``, so every block coefficient is one full
``(TR, 128)`` vector and all five stages (exponent align → lift →
negabinary → permute → bitplane pack) are elementwise vector ops fused in
VMEM — the multi-stage GEM execution of Table I/II.  The lifting partners
and the sequency permutation are static coefficient indices, so the kernel
needs no gather and no lane shuffle; the bitplane packing ORs disjoint
shifted bits into int32 words, which is the oracle's unsigned sum bit for
bit.  Matmul-free: this kernel is VPU (shift/add) bound, which is why ZFP
is the highest-throughput pipeline on every backend (paper Fig. 12).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import zfp as core_zfp

LANES = 128
DEFAULT_TR = 8  # rows of 128 blocks per grid cell
_NB = int(np.int32(np.uint32(core_zfp.NBMASK).view(np.int32)))  # as int32


def _lift_axis(t, dims: int, axis: int, lift):
    """Apply ``lift`` to every 4-vector of ``t`` (4^d, TR, 128) along ``axis``.

    Coefficients are in C order over ``(4,)*dims``, so the four components
    along ``axis`` are index slices of a leading-dims reshape.
    """
    outer, inner = 4 ** axis, 4 ** (dims - 1 - axis)
    tail = t.shape[1:]
    v = t.reshape((outer, 4, inner) + tail)
    parts = lift(*(v[:, k] for k in range(4)))
    return jnp.stack(parts, axis=1).reshape(t.shape)


def _word_segments(bs: int, rate: int):
    """For each packed word, its runs ``(plane, j0, i0, m)``: coefficients
    ``j0..j0+m`` (sequency order) of ``plane`` at word bits ``i0..i0+m``
    (MSB-first)."""
    words = []
    for g in range(-(-rate * bs // 32)):
        runs = []
        f = 32 * g
        while f < min(32 * g + 32, rate * bs):
            p, j = divmod(f, bs)
            m = min(bs - j, 32 * g + 32 - f)
            runs.append((p, j, f - 32 * g, m))
            f += m
        words.append(runs)
    return words


def _msb_shifts(i0: int, m: int, tail):
    """(m, *tail) int32 of ``31 - i`` for word bits ``i = i0..i0+m``."""
    return (31 - i0) - jax.lax.broadcasted_iota(jnp.int32, (m,) + tail, 0)


def _compress_kernel(x_ref, payload_ref, emax_ref, *, rate, dims):
    bs = 4 ** dims
    x = x_ref[...]                                   # (4^d, TR, 128)
    tail = x.shape[1:]
    emax = core_zfp.float_exponent(jnp.max(jnp.abs(x), axis=0))
    t = core_zfp.to_fixed_point(x, emax[None])
    for a in range(dims):
        t = _lift_axis(t, dims, a, core_zfp.fwd_lift)
    perm = core_zfp.sequency_permutation(dims)
    u = (t + _NB) ^ _NB                              # negabinary
    u = jnp.concatenate([u[int(c):int(c) + 1] for c in perm])  # sequency order
    for g, runs in enumerate(_word_segments(bs, rate)):
        word = None
        for p, j0, i0, m in runs:
            bits = jax.lax.shift_right_logical(u[j0:j0 + m], 31 - p) & 1
            part = jnp.sum(bits << _msb_shifts(i0, m, tail), axis=0)  # OR
            word = part if word is None else word | part
        payload_ref[g] = word
    emax_ref[...] = emax


def _decompress_kernel(p_ref, e_ref, out_ref, *, rate, dims):
    bs = 4 ** dims
    tail = e_ref.shape
    planes = {}
    for g, runs in enumerate(_word_segments(bs, rate)):
        w = p_ref[g][None]
        for p, j0, i0, m in runs:
            bits = jax.lax.shift_right_logical(w, _msb_shifts(i0, m, tail)) & 1
            planes.setdefault(p, []).append(bits << (31 - p))
    u = None
    for p in range(rate):
        plane = jnp.concatenate(planes[p]) if len(planes[p]) > 1 else planes[p][0]
        u = plane if u is None else u | plane       # (4^d, TR, 128)
    inv = np.argsort(core_zfp.sequency_permutation(dims))
    u = jnp.concatenate([u[int(j):int(j) + 1] for j in inv])  # natural order
    t = (u ^ _NB) - _NB                              # negabinary → int
    for a in reversed(range(dims)):
        t = _lift_axis(t, dims, a, core_zfp.inv_lift)
    out_ref[...] = core_zfp.from_fixed_point(t, e_ref[...][None])


def _lanes_tiles(n: int, tr: int) -> tuple[int, int]:
    rows = max(1, -(-n // LANES))
    tr = min(tr, -(-rows // 8) * 8)
    return tr, -(-rows // tr) * tr


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


@functools.partial(jax.jit, static_argnames=("rate", "dims", "tr", "interpret"))
def compress_blocks(
    blocks: jax.Array,  # (4^dims, N) float32, coefficient-major
    rate: int,
    dims: int,
    *,
    interpret: bool,
    tr: int = DEFAULT_TR,
) -> tuple[jax.Array, jax.Array]:
    bs, n = blocks.shape
    if bs != 4 ** dims:
        raise ValueError(f"blocks of {bs} values are not 4^{dims}")
    wpb = core_zfp.words_per_block(bs, rate)
    tr, rows = _lanes_tiles(n, tr)
    x = jnp.pad(blocks.astype(jnp.float32), ((0, 0), (0, rows * LANES - n)))
    x = x.reshape(bs, rows, LANES)
    payload, emax = pl.pallas_call(
        functools.partial(_compress_kernel, rate=rate, dims=dims),
        grid=(rows // tr,),
        in_specs=[pl.BlockSpec((bs, tr, LANES), lambda i: (0, i, 0))],
        out_specs=(
            pl.BlockSpec((wpb, tr, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((wpb, rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ),
        compiler_params=_params(),
        interpret=interpret,
    )(x)
    payload = payload.reshape(wpb, rows * LANES).T[:n]
    return jax.lax.bitcast_convert_type(payload, jnp.uint32), emax.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("rate", "dims", "tr", "interpret"))
def decompress_blocks(
    payload: jax.Array,  # (N, wpb) uint32
    emax: jax.Array,     # (N,) int32
    rate: int,
    dims: int,
    *,
    interpret: bool,
    tr: int = DEFAULT_TR,
) -> jax.Array:  # (4^dims, N) float32, coefficient-major
    n, wpb = payload.shape
    bs = 4 ** dims
    tr, rows = _lanes_tiles(n, tr)
    pad = rows * LANES - n
    p = jax.lax.bitcast_convert_type(payload.astype(jnp.uint32), jnp.int32)
    p = jnp.pad(p, ((0, pad), (0, 0))).T.reshape(wpb, rows, LANES)
    e = jnp.pad(emax.astype(jnp.int32), (0, pad)).reshape(rows, LANES)
    out = pl.pallas_call(
        functools.partial(_decompress_kernel, rate=rate, dims=dims),
        grid=(rows // tr,),
        in_specs=[
            pl.BlockSpec((wpb, tr, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((tr, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bs, tr, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, rows, LANES), jnp.float32),
        compiler_params=_params(),
        interpret=interpret,
    )(p, e)
    return out.reshape(bs, rows * LANES)[:, :n]
