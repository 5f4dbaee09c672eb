"""Tridiagonal mass-solve kernel — Pallas TPU (Iterative abstraction).

Thomas algorithm for M x = b with the 1-D FEM mass matrix, batched over B
vectors per grid cell (the paper's B:1 vector→group mapping, Fig. 3b): a
``(B, n)`` tile plus the precomputed elimination constants (cp, d_inv — the
CMM-cached solver context) live in VMEM; the forward/backward sweeps are
``lax.scan`` over the solve axis with all B lanes advancing together, so the
VPU lane dimension stays full while the recurrence is sequential — the exact
TPU analogue of the paper's iterative execution model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mgard import _thomas_coeffs

DEFAULT_B = 64  # vectors per grid cell


def _tridiag_kernel(rhs_ref, cp_ref, dinv_ref, x_ref, *, sub):
    rhs = rhs_ref[...]          # (B, n)
    cp = cp_ref[...]            # (n,)
    dinv = dinv_ref[...]        # (n,)
    v = rhs.T                   # (n, B): scan over axis 0

    def fwd(carry, inp):
        r, di = inp
        d = (r - sub * carry) * di
        return d, d

    _, dp = jax.lax.scan(fwd, jnp.zeros_like(v[0]), (v, dinv))

    def back(carry, inp):
        d, cpi = inp
        x = d - cpi * carry
        return x, x

    _, xs = jax.lax.scan(back, jnp.zeros_like(v[0]), (dp, cp), reverse=True)
    x_ref[...] = xs.T


@functools.partial(jax.jit, static_argnames=("h", "b", "interpret"))
def solve_mass(
    rhs: jax.Array,  # (N, n) float32 — N independent systems
    h: float,
    *,
    interpret: bool,
    b: int = DEFAULT_B,
) -> jax.Array:
    nsys, n = rhs.shape
    cp_np, dinv_np = _thomas_coeffs(n, h)
    n_pad = (-nsys) % b
    if n_pad:
        rhs = jnp.pad(rhs, ((0, n_pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_tridiag_kernel, sub=h / 6.0),
        grid=(rhs.shape[0] // b,),
        in_specs=[
            pl.BlockSpec((b, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((b, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(rhs.shape, jnp.float32),
        interpret=interpret,
    )(rhs.astype(jnp.float32), jnp.asarray(cp_np, jnp.float32), jnp.asarray(dinv_np, jnp.float32))
    return out[:nsys]
