"""Pure-jnp oracle for the zfp_block kernel (XLA adapter implementation)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import zfp as core_zfp


def compress_blocks(blocks: jax.Array, rate: int, dims: int):
    """(4^dims, N) float32 → ((N, wpb) uint32, (N,) int32) — vmapped core path."""
    perm = jnp.asarray(core_zfp.sequency_permutation(dims))
    shaped = blocks.T.reshape((blocks.shape[1],) + (4,) * dims)
    return core_zfp._compress_blocks(shaped, rate, perm)


def decompress_blocks(payload: jax.Array, emax: jax.Array, rate: int, dims: int):
    """((N, wpb) uint32, (N,) int32) → (4^dims, N) float32."""
    inv_perm = jnp.asarray(
        np.argsort(core_zfp.sequency_permutation(dims)).astype(np.int32)
    )
    out = core_zfp._decompress_blocks(payload, emax, rate, inv_perm, (4,) * dims)
    return out.reshape(out.shape[0], -1).T
