"""MGARD-X lossy compression — HPDR §IV-A (Algorithm 1), TPU-native.

Multigrid decomposition on uniform tensor grids (the MGARD-GPU design):
for each level l (fine → coarse):

  1. ``lerp``        multilinear-interpolation coefficients
                     mc = (I − Π_{l−1}) Q_l u            → Locality (GEM)
  2. ``mass_trans``  load vector b = R · M_f · mc        → Locality (GEM)
  3. ``tridiag``     correction c = M_c^{-1} b, solved
                     dimension-by-dimension (mass matrix of multilinear
                     elements is a Kronecker product)     → Iterative (GEM,
                     B vectors per group = lax.scan batched over lanes)
  4. ``add``         Q_{l−1}u = Q_l u|coarse + c          → Locality (GEM)

then per-level linear quantization (Map&Process) and Huffman-X encoding.

Grid handling: each dim is edge-padded to 2^k+1 (per-dim k), the dyadic
hierarchy MGARD's uniform-grid theory assumes; dims stop decomposing when
they reach 2 nodes.  In-place coefficient layout: level-l coefficients live
at their original node positions (stride-2^l nodes with an odd view coord),
like MGARD's output; the level map is a closed-form function of index
trailing-zero counts.

Thomas-solver elimination coefficients depend only on (n, h), so they are
precomputed on host and streamed in as constants — the scan body is one
fused multiply-add per step (the paper's point that solver *context* should
be cached, CMM).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from . import huffman
from .abstractions import iterative, map_and_process_param
from .quantize import (
    dequantize_by_subset,
    quantize_by_subset,
    signed_to_unsigned,
    unsigned_to_signed,
)

# ---------------------------------------------------------------------------
# dyadic grid bookkeeping
# ---------------------------------------------------------------------------


def dim_levels(n: int) -> int:
    """k such that the padded dim is 2^k + 1 (0 for dims too small to split)."""
    if n < 3:
        return 0
    return int(math.ceil(math.log2(n - 1)))


def padded_dim(n: int) -> int:
    k = dim_levels(n)
    return (1 << k) + 1 if k > 0 else n


def pad_to_dyadic(u: jax.Array) -> jax.Array:
    target = tuple(padded_dim(n) for n in u.shape)
    pads = [(0, t - n) for n, t in zip(u.shape, target)]
    if any(p != (0, 0) for p in pads):
        u = jnp.pad(u, pads, mode="edge")
    return u


def total_levels(shape: tuple[int, ...]) -> int:
    return max(dim_levels(n) for n in shape)


@lru_cache(maxsize=None)
def _level_scores_1d(n: int, k: int) -> np.ndarray:
    """Per-index decomposition step score along one dim (∞ → stays nodal)."""
    idx = np.arange(n)
    tz = np.zeros(n, dtype=np.int64)
    nz = idx > 0
    tz[nz] = np.array([int(i & -i).bit_length() - 1 for i in idx[nz]])
    score = np.where((k > 0) & (idx % (1 << max(k, 1)) != 0), tz, np.iinfo(np.int32).max)
    return score.astype(np.int32)


def level_map(shape: tuple[int, ...]) -> np.ndarray:
    """Map node → quantization subset id: step l (0..L-1) or L for nodal values."""
    ks = [dim_levels(n) for n in shape]
    L = max(ks)
    score = None
    for axis, (n, k) in enumerate(zip(shape, ks)):
        s = _level_scores_1d(n, k)
        view = s.reshape([-1 if a == axis else 1 for a in range(len(shape))])
        score = view if score is None else np.minimum(score, view)
    return np.minimum(score, L).astype(np.int32)


# ---------------------------------------------------------------------------
# 1D operators (applied per axis; tensor-product structure)
# ---------------------------------------------------------------------------


def interp_1d(coarse: jax.Array, axis: int) -> jax.Array:
    """Prolongation along ``axis``: size m+1 → 2m+1 (linear midpoints)."""
    c = jnp.moveaxis(coarse, axis, 0)
    mids = 0.5 * (c[:-1] + c[1:])
    n_f = 2 * (c.shape[0] - 1) + 1
    out = jnp.zeros((n_f,) + c.shape[1:], c.dtype)
    out = out.at[0::2].set(c)
    out = out.at[1::2].set(mids)
    return jnp.moveaxis(out, 0, axis)


# Floating-point contraction.  XLA:CPU fuses ``a*b + c`` into one FMA
# whenever one fusion holds both, and which ops share a fusion differs
# between a leaf's own program and the same code vmapped over a stacked
# bucket.  An optimization barrier does not stop it: XLA:CPU removes
# barriers before fusion.  So the transform below adds no inexact product
# it writes itself: its multipliers are powers of two (exact) and its
# inexact scales are divisions.  XLA still turns a division by a constant
# into a multiply by the reciprocal (the forward sweep's ``(d*h)/6`` becomes
# ``d*(h/6)``, fused with the subtraction), so stacked and serial programs
# round alike only as far as those rewrites match in both; the scan bodies
# are computations of their own and compile alike.
# ``tests/test_mgard.py::test_transform_vmapped_matches_serial`` checks it.


def mass_mult_1d(x: jax.Array, axis: int, h: float) -> jax.Array:
    """y = M x along ``axis``; M = h·tridiag(1/6, 2/3, 1/6), boundary h/3.

    Computed as ``h·(4v + left + right)/6`` (``2v`` at the boundary): the
    multipliers 4, 2 and ``h`` are powers of two, so no FMA can change it.
    """
    v = jnp.moveaxis(x, axis, 0)
    n = v.shape[0]
    left = jnp.concatenate([jnp.zeros_like(v[:1]), v[:-1]], axis=0)
    right = jnp.concatenate([v[1:], jnp.zeros_like(v[:1])], axis=0)
    diag = jnp.full((n,) + (1,) * (v.ndim - 1), 4.0, v.dtype)
    diag = diag.at[0].set(2.0).at[-1].set(2.0)
    y = h * ((diag * v + left + right) / 6.0)
    return jnp.moveaxis(y, 0, axis)


def restrict_1d(m: jax.Array, axis: int) -> jax.Array:
    """R = P^T along ``axis``: size 2m+1 → m+1: b_j = m_2j + ½(m_2j−1 + m_2j+1)."""
    v = jnp.moveaxis(m, axis, 0)
    even = v[0::2]
    odd = v[1::2]
    zeros = jnp.zeros_like(odd[:1])
    left = jnp.concatenate([zeros, odd], axis=0)   # odd node left of coarse j
    right = jnp.concatenate([odd, zeros], axis=0)  # odd node right of coarse j
    b = even + 0.5 * (left + right)
    return jnp.moveaxis(b, 0, axis)


@lru_cache(maxsize=None)
def _thomas_denoms(n: int, h: float) -> np.ndarray:
    """Thomas forward-elimination pivots d'_i of the 1D mass matrix."""
    a, b, c = h / 6.0, 2.0 * h / 3.0, h / 6.0
    denom = np.zeros(n)
    denom[0] = h / 3.0
    for i in range(1, n):
        bi = h / 3.0 if i == n - 1 else b
        denom[i] = bi - a * (c / denom[i - 1])
    return denom


@lru_cache(maxsize=None)
def _thomas_coeffs(n: int, h: float) -> tuple[np.ndarray, np.ndarray]:
    """Thomas constants (cp, denom_inv): cp[i] = c_i / d'_i, 1 / d'_i."""
    denom = _thomas_denoms(n, h)
    return (h / 6.0) / denom, 1.0 / denom


def tridiag_solve_1d(rhs: jax.Array, axis: int, h: float) -> jax.Array:
    """Solve M x = rhs along ``axis`` (Thomas; Iterative abstraction).

    Forward sweep and back-substitution are two ``lax.scan``s along the solve
    axis; every other axis is a batch lane (B-vectors-per-group, paper
    Fig. 3b).
    """
    n = rhs.shape[axis]
    denom_np = _thomas_denoms(n, h)
    dinv = jnp.asarray(1.0 / denom_np, rhs.dtype)
    six_denom = jnp.asarray(6.0 * denom_np, rhs.dtype)

    # The sub/super-diagonal h/6 enters as (x·h)/6: h is a power of two, so
    # each subtracted term is a quotient and no step is FMA-contractible.
    def fwd(carry, inp):
        d_prev = carry
        r, di = inp
        d = (r - (d_prev * h) / 6.0) * di
        return d, d

    v = jnp.moveaxis(rhs, axis, 0)
    bshape = (n,) + (1,) * (v.ndim - 1)
    _, dp = jax.lax.scan(
        fwd, jnp.zeros_like(v[0]), (v, dinv.reshape(bshape) * jnp.ones_like(v))
    )
    # NB: broadcast trick — scan inputs must share the leading dim.

    def back(carry, inp):
        x_next = carry
        d, sd = inp
        x = d - (x_next * h) / sd  # cp_i·x = (h/6)/d'_i·x
        return x, x

    _, xs = jax.lax.scan(
        back,
        jnp.zeros_like(v[0]),
        (dp, six_denom.reshape(bshape) * jnp.ones_like(v)),
        reverse=True,
    )
    return jnp.moveaxis(xs, 0, axis)


# ---------------------------------------------------------------------------
# per-level decompose / recompose
# ---------------------------------------------------------------------------


def _participating(shape: tuple[int, ...]) -> list[int]:
    """Axes with an odd-size view ≥ 3 (still decomposable)."""
    return [a for a, n in enumerate(shape) if n >= 3 and (n - 1) % 2 == 0]


def _decompose_level(view: jax.Array, h: float) -> jax.Array:
    """One level of MGARD decomposition on the current strided view."""
    axes = _participating(view.shape)
    coarse = view[tuple(slice(None, None, 2) if a in axes else slice(None) for a in range(view.ndim))]
    # (1) lerp: multilinear interpolation of coarse onto fine grid
    interp = coarse
    for a in axes:
        interp = interp_1d(interp, a)
    mc = view - interp
    # (2) mass transfer: b = R · M_f · mc per participating axis
    b = mc
    for a in axes:
        b = restrict_1d(mass_mult_1d(b, a, h), a)
    # (3) correction: c = M_c^{-1} b (Kronecker → dimension-split solves)
    c = b
    for a in axes:
        c = tridiag_solve_1d(c, a, 2.0 * h)
    # (4) add correction to coarse values
    corrected = coarse + c
    out = mc
    out = out.at[tuple(slice(None, None, 2) if a in axes else slice(None) for a in range(view.ndim))].set(corrected)
    return out


def _recompose_level(view: jax.Array, h: float) -> jax.Array:
    """Exact inverse of :func:`_decompose_level`."""
    axes = _participating(view.shape)
    sl = tuple(slice(None, None, 2) if a in axes else slice(None) for a in range(view.ndim))
    corrected = view[sl]
    mc = view.at[sl].set(0.0)
    b = mc
    for a in axes:
        b = restrict_1d(mass_mult_1d(b, a, h), a)
    c = b
    for a in axes:
        c = tridiag_solve_1d(c, a, 2.0 * h)
    coarse = corrected - c
    interp = coarse
    for a in axes:
        interp = interp_1d(interp, a)
    fine = mc + interp
    # coarse nodes: mc slot was zeroed, interp(coarse)=coarse there → exact.
    return fine


def _strided_slices(ndim: int, shape: tuple[int, ...], stride_per_axis: tuple[int, ...]):
    return tuple(slice(None, None, s) for s in stride_per_axis)


@partial(jax.jit, static_argnames=("shape",))
def decompose(u: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Full multilevel decomposition (paper Alg. 1 lines 5–13), in-place layout."""
    u = u.reshape(shape).astype(jnp.float32)
    u = pad_to_dyadic(u)
    pshape = u.shape
    ks = [dim_levels(n) for n in shape]
    L = max(ks)
    for l in range(L):
        strides = tuple(1 << min(l, k) for k in ks)
        sl = _strided_slices(u.ndim, pshape, strides)
        view = u[sl]
        h = float(1 << l)
        u = u.at[sl].set(_decompose_level(view, h))
    return u


@partial(jax.jit, static_argnames=("shape",))
def recompose(coeffs: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`decompose`; returns array of original ``shape``."""
    u = coeffs
    ks = [dim_levels(n) for n in shape]
    L = max(ks)
    for l in range(L - 1, -1, -1):
        strides = tuple(1 << min(l, k) for k in ks)
        sl = _strided_slices(u.ndim, u.shape, strides)
        view = u[sl]
        h = float(1 << l)
        u = u.at[sl].set(_recompose_level(view, h))
    return u[tuple(slice(0, n) for n in shape)]


# ---------------------------------------------------------------------------
# quantization (Map&Process) + entropy stage → full pipeline
# ---------------------------------------------------------------------------

# Empirically calibrated L∞ safety factor for the per-level bin schedule
# (see tests/test_mgard.py::test_error_bound): covers interpolation gain
# (L∞-norm 1 per level, additive across levels — hence the 1/(L+1) split)
# plus the correction-feedback gain of c = M_c^{-1}·R·M_f applied to the
# quantization noise during recomposition.
_SAFETY = 2.0


def level_bins(eb: float, L: int) -> np.ndarray:
    """Per-level quantization bin sizes τ_l (paper: 'different bin sizes').

    MGARD's uniform-norm (s=∞) budget: each of the L+1 levels contributes
    ≤ τ_l/2 · gain to the reconstruction error with gain ≈ 1, so the budget
    is split evenly; the nodal (coarsest) subset gets a tighter bin because
    its values seed every interpolation level below it.
    """
    w = np.ones(L + 1)
    w[L] = 0.5  # nodal values: tighter bin (seed of the recomposition)
    return (2.0 * eb / ((L + 1) * _SAFETY) * w).astype(np.float64)


@dataclass
class MGARDCompressed:
    entropy: huffman.Encoded
    outlier_idx: np.ndarray      # int64[n_out] flat indices (padded grid)
    outlier_val: np.ndarray      # int32[n_out] quantized values
    bins: np.ndarray             # float64[L+1]
    shape: tuple[int, ...]
    padded: tuple[int, ...]
    error_bound: float
    dict_size: int
    dtype: str = "float32"

    def nbytes(self) -> int:
        return int(
            self.entropy.nbytes()
            + self.outlier_idx.nbytes
            + self.outlier_val.nbytes
            + self.bins.nbytes
        )


def _quantize_stage_impl(coeffs, lmap, bins, shape, dict_size, adapter):
    if adapter is None:
        q = quantize_by_subset(coeffs, lmap, bins)
        u = signed_to_unsigned(q)
    else:
        from repro.kernels.quantize_map import ops as quantize_ops  # lazy

        u = quantize_ops.quantize(coeffs, lmap, bins, adapter=adapter).reshape(shape)
        q = unsigned_to_signed(u)
    escape = dict_size - 1
    inlier = u < escape
    keys = jnp.where(inlier, u, jnp.uint32(escape)).astype(jnp.int32)
    return q, keys, inlier


@partial(jax.jit, static_argnames=("shape", "dict_size"))
def _quantize_stage(coeffs, lmap, bins, shape, dict_size):
    return _quantize_stage_impl(coeffs, lmap, bins, shape, dict_size, None)


def planned_quantize_stage(shape, dict_size, adapter):
    """Plan-bound quantize executable with the level map *donated*.

    Returns the (aliased) level map as an extra output; the codec re-stores
    it in the plan workspace (``ReductionPlan.recycle``) so reuse is true
    in-place recycling where XLA implements donation (TPU/GPU) and a plain
    pass-through elsewhere.
    """
    from . import adapters

    def stage(coeffs, lmap, bins):
        q, keys, inlier = _quantize_stage_impl(
            coeffs, lmap, bins, shape, dict_size, adapter
        )
        return q, keys, inlier, lmap

    return adapters.donating_jit(stage, donate_argnums=(1,))


def planned_dequantize_stage(adapter):
    """Plan-bound dequantize executable (level map donated, see above)."""
    from . import adapters

    def stage(q, lmap, bins):
        if adapter is None:
            coeffs = dequantize_by_subset(q, lmap, bins)
        else:
            from repro.kernels.quantize_map import ops as quantize_ops  # lazy

            coeffs = quantize_ops.dequantize(
                signed_to_unsigned(q), lmap, bins, adapter=adapter
            ).reshape(q.shape)
        return coeffs, lmap

    return adapters.donating_jit(stage, donate_argnums=(1,))


def compress(
    data: jax.Array,
    error_bound: float,
    dict_size: int = 4096,
    chunk_size: int = huffman.DEFAULT_CHUNK,
) -> MGARDCompressed:
    """MGARD-X end-to-end compression (paper Algorithm 1)."""
    shape = tuple(data.shape)
    coeffs = decompose(data, shape)
    padded = tuple(coeffs.shape)
    lmap = jnp.asarray(level_map(padded))
    L = total_levels(padded)
    bins = level_bins(error_bound, L)
    q, keys, inlier = _quantize_stage(
        coeffs, lmap, jnp.asarray(bins, jnp.float32), padded, dict_size
    )
    # Outliers: stored losslessly (sparse), exactly like MGARD's escape path.
    inlier_np = np.asarray(inlier).reshape(-1)
    out_idx = np.nonzero(~inlier_np)[0]
    out_val = np.asarray(q).reshape(-1)[out_idx]
    enc = huffman.compress(keys, dict_size, chunk_size=chunk_size)
    return MGARDCompressed(
        entropy=enc,
        outlier_idx=out_idx.astype(np.int64),
        outlier_val=out_val.astype(np.int32),
        bins=bins,
        shape=shape,
        padded=padded,
        error_bound=float(error_bound),
        dict_size=dict_size,
        dtype=str(data.dtype),
    )


def decompress(obj: MGARDCompressed) -> jax.Array:
    keys = huffman.decompress(obj.entropy)
    u = keys.astype(jnp.uint32)
    q = unsigned_to_signed(u)
    qf = np.asarray(q).reshape(-1)
    if obj.outlier_idx.size:
        qf = qf.copy()
        qf[obj.outlier_idx] = obj.outlier_val
    q = jnp.asarray(qf.reshape(obj.padded))
    lmap = jnp.asarray(level_map(obj.padded))
    coeffs = dequantize_by_subset(q, lmap, jnp.asarray(obj.bins, jnp.float32))
    out = recompose(coeffs, obj.shape)
    return out.astype(jnp.dtype(obj.dtype))


def compression_ratio(obj: MGARDCompressed) -> float:
    orig = math.prod(obj.shape) * jnp.dtype(obj.dtype).itemsize
    return orig / obj.nbytes()
