"""Huffman decode kernel — Pallas TPU (chunk-parallel canonical scan).

Per grid cell: one self-synchronising chunk decodes its ``chunk_size``
symbols with a sequential scalar loop on the TPU's scalar unit.  The chunk
bit offsets are scalar-prefetched into SMEM, and they steer the pipeline:
the word stream stays in HBM and each cell DMAs only the two aligned
``B``-word blocks that hold its chunk (``B`` covers the longest chunk a
``max_len``-bit code allows), so the stream is tiled, never resident.  The
canonical decode tables (first_code/count/sym_offset/sym_sorted) are
replicated in SMEM — every table probe is a scalar load, the placement GPU
Huffman decoders get from shared memory.

The scan is the oracle's (``ref.decode_chunks``) in scalar form: read a
32-bit window at the cursor, take the shortest length whose prefix is a
valid code, emit the symbol, advance.  Unsigned compares are done on int32
with the sign bit flipped, and out-of-range table indices follow the
oracle's gather (negative wraps once, then clamps), so the two agree bit
for bit, padding symbols included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIN = -(1 << 31)


def _ult(a, b):
    """Unsigned a < b on int32 bit patterns."""
    return (a ^ _MIN) < (b ^ _MIN)


def _srl(x, n):
    return jax.lax.shift_right_logical(x, n)


def stream_block(chunk_size: int, max_len: int) -> int:
    """Words per stream block: a power of two ≥ 1024 that holds one chunk's
    longest span."""
    span = -(-chunk_size * max_len // 32) + 2
    return max(1024, 1 << (span - 1).bit_length())


def _decode_kernel(
    offs_ref, w0_ref, w1_ref, fc_ref, ct_ref, so_ref, sym_ref, out_ref,
    *, chunk_size: int, max_len: int, block: int,
):
    off = offs_ref[pl.program_id(0)]
    shift = block.bit_length() - 1
    base = (off >> (5 + shift)) << shift  # word index of w0_ref[0]
    n_sym = sym_ref.shape[0]

    def word(idx):
        rel = idx - base
        lo = w0_ref[jnp.minimum(rel, block - 1)]
        hi = w1_ref[jnp.maximum(rel - block, 0)]
        return jnp.where(rel < block, lo, hi)

    def body(k, cursor):
        wi = cursor >> 5
        b = cursor & 31
        tail = _srl(word(wi + 1), (32 - b) & 31)
        window = (word(wi) << b) | jnp.where(b == 0, 0, tail)

        def cand(length):
            return _srl(window, 32 - length)

        def valid(length):
            c = cand(length)
            f = fc_ref[length]
            return jnp.logical_not(_ult(c, f)) & _ult(c - f, ct_ref[length])

        length = jax.lax.while_loop(
            lambda n: (n <= max_len) & jnp.logical_not(valid(n)),
            lambda n: n + 1,
            jnp.int32(1),
        )
        length = jnp.where(length > max_len, 1, length)  # oracle's argmax → 0
        idx = so_ref[length] + (cand(length) - fc_ref[length])
        idx = jnp.where(idx < 0, idx + n_sym, idx)
        out_ref[k] = sym_ref[jnp.clip(idx, 0, n_sym - 1)]
        return cursor + length

    jax.lax.fori_loop(0, chunk_size, body, off)


@functools.partial(
    jax.jit, static_argnames=("chunk_size", "max_len", "interpret")
)
def decode_chunks(
    words: jax.Array,
    chunk_offsets: jax.Array,
    first_code: jax.Array,
    count: jax.Array,
    sym_offset: jax.Array,
    sym_sorted: jax.Array,
    chunk_size: int,
    max_len: int,
    *,
    interpret: bool,
) -> jax.Array:
    n_chunks = chunk_offsets.shape[0]
    if n_chunks == 0:
        return jnp.zeros((0, chunk_size), jnp.int32)
    sym_sorted = sym_sorted.reshape(-1).astype(jnp.int32)
    if sym_sorted.shape[0] == 0:  # empty alphabet: keep the lookup well-formed
        sym_sorted = jnp.zeros(1, jnp.int32)
    block = stream_block(chunk_size, max_len)
    shift = block.bit_length() - 1
    words = jax.lax.bitcast_convert_type(words.astype(jnp.uint32), jnp.int32)
    n_words = words.shape[0]
    # zero words past the end read as zero bits, like the oracle's window
    words = jnp.pad(words, (0, ((n_words >> shift) + 2) * block - n_words))

    def stream(k):
        return pl.BlockSpec(
            (block,), lambda i, offs: ((offs[i] >> (5 + shift)) + k,),
            memory_space=pltpu.SMEM,
        )

    table = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, chunk_size=chunk_size, max_len=max_len, block=block
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=[stream(0), stream(1), table, table, table, table],
            out_specs=pl.BlockSpec(
                (chunk_size,), lambda i, offs: (i,), memory_space=pltpu.SMEM
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk_size,), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(
        chunk_offsets.astype(jnp.int32),
        words,
        words,
        jax.lax.bitcast_convert_type(first_code.astype(jnp.uint32), jnp.int32),
        count.astype(jnp.int32),
        sym_offset.astype(jnp.int32),
        sym_sorted,
    )
    return out.reshape(n_chunks, chunk_size)
