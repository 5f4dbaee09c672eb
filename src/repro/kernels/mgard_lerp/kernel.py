"""MGARD lerp kernel — Pallas TPU (Locality stage, paper Alg. 1 line 6).

Computes 1-D interpolation coefficients mc_i = u_{2i+1} − ½(u_{2i} + u_{2i+2})
for a batch of vectors: each grid cell stages ``R`` full rows in VMEM and
evaluates the stencil with strided slices — no halo exchange needed because
the full solve axis is resident (MGARD grids after padding are ≤ 2^k+1 ≈ 4 K
elements: a (R=8, 4097) f32 tile is 128 KiB).

The multi-dimensional / multi-level coefficient computation in ``core.mgard``
composes this axis kernel, exactly as MGARD-GPU composes its 1-D passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_R = 8  # rows per grid cell


def _lerp_kernel(u_ref, mc_ref):
    u = u_ref[...]  # (R, n) with n = 2m+1
    mc_ref[...] = u[:, 1::2] - 0.5 * (u[:, 0:-2:2] + u[:, 2::2])


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def lerp_coefficients(
    rows: jax.Array,  # (B, n) float32, n odd
    *,
    interpret: bool,
    r: int = DEFAULT_R,
) -> jax.Array:
    b, n = rows.shape
    assert n % 2 == 1 and n >= 3, "solve axis must be odd-sized (2m+1)"
    m = (n - 1) // 2
    b_pad = (-b) % r
    if b_pad:
        rows = jnp.pad(rows, ((0, b_pad), (0, 0)))
    out = pl.pallas_call(
        _lerp_kernel,
        grid=(rows.shape[0] // r,),
        in_specs=[pl.BlockSpec((r, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((r, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], m), jnp.float32),
        interpret=interpret,
    )(rows.astype(jnp.float32))
    return out[:b]
