"""Adapter-dispatched entry points for the zfp_block kernel."""

from __future__ import annotations

import jax

from repro.core import adapters

from . import kernel, ref


@adapters.register("zfp_block_compress", adapters.XLA)
def _compress_xla(blocks, rate, dims):
    return ref.compress_blocks(blocks, rate, dims)


@adapters.register("zfp_block_compress", adapters.PALLAS)
def _compress_pallas(blocks, rate, dims):
    return kernel.compress_blocks(blocks, rate, dims, interpret=False)


@adapters.register("zfp_block_compress", adapters.PALLAS_INTERPRET)
def _compress_interp(blocks, rate, dims):
    return kernel.compress_blocks(blocks, rate, dims, interpret=True)


@adapters.register("zfp_block_decompress", adapters.XLA)
def _decompress_xla(payload, emax, rate, dims):
    return ref.decompress_blocks(payload, emax, rate, dims)


@adapters.register("zfp_block_decompress", adapters.PALLAS)
def _decompress_pallas(payload, emax, rate, dims):
    return kernel.decompress_blocks(payload, emax, rate, dims, interpret=False)


@adapters.register("zfp_block_decompress", adapters.PALLAS_INTERPRET)
def _decompress_interp(payload, emax, rate, dims):
    return kernel.decompress_blocks(payload, emax, rate, dims, interpret=True)


def compress_blocks(blocks: jax.Array, rate: int, dims: int, adapter: str | None = None):
    """(4^dims, N) coefficient-major blocks → ((N, wpb) uint32, (N,) int32)."""
    return adapters.dispatch("zfp_block_compress", adapter)(blocks, rate, dims)


def decompress_blocks(
    payload: jax.Array, emax: jax.Array, rate: int, dims: int, adapter: str | None = None
):
    """((N, wpb) uint32, (N,) int32) → (4^dims, N) coefficient-major blocks."""
    return adapters.dispatch("zfp_block_decompress", adapter)(payload, emax, rate, dims)
