"""Plain fixed-rate ZFP for 3-D float32 fields, the reference of ``zfp``.

Per 4×4×4 block, in C order over the block grid: the block's largest
binary exponent ``e`` (``max|x| = m·2^e``, ``0.5 <= m < 1``); the values
as integers ``round(x·2^(30-e))``; libzfp's forward lift along axis 0,
then 1, then 2; negabinary; the 64 coefficients ordered by total sequency
``i+j+k`` (ties by their C-order index); and the top ``rate`` bit planes,
most significant first, each plane's 64 bits packed MSB-first into two
32-bit words. A block is ``2·rate`` words of payload and one int32
exponent. Decoding inverts each step; dropped planes read as zero.

Blocks are held coefficient-major, ``(64, n_blocks)``, so that the block
count is the minor axis on the device. The arithmetic is exact in int32
and in power-of-two float scaling, so a correct stream matches this one
bit for bit. ``dtype`` is the float type the values are taken and given
back in: the benchmark's control runs it in bfloat16.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NB_MASK = np.uint32(0xAAAAAAAA)
_OFFSETS = list(itertools.product(range(4), repeat=3))
ORDER = np.array(sorted(range(64), key=lambda c: (sum(_OFFSETS[c]), c)), np.int32)
INVERSE = np.argsort(ORDER).astype(np.int32)
SLAB_VALUES = 1 << 24  # values per device call at most: bounds the reference's memory


def to_blocks(x: jax.Array) -> jax.Array:
    """``(Z, Y, X)`` → ``(64, Z/4·Y/4·X/4)``: row ``c`` is in-block offset ``c``."""
    return jnp.stack([x[o[0]::4, o[1]::4, o[2]::4] for o in _OFFSETS]).reshape(64, -1)


def _lift(t, axis, step):
    parts = step(*(jnp.take(t, i, axis=axis) for i in range(4)))
    return jnp.stack(parts, axis=axis)


def _fwd(x, y, z, w):
    x = (x + w) >> 1
    w = w - x
    z = (z + y) >> 1
    y = y - z
    x = (x + z) >> 1
    z = z - x
    w = (w + y) >> 1
    y = y - w
    w = w + (y >> 1)
    y = y - (w >> 1)
    return x, y, z, w


def _inv(x, y, z, w):
    y = y + (w >> 1)
    w = w - (y >> 1)
    y = y + w
    w = (w << 1) - y
    z = z + x
    x = (x << 1) - z
    y = y + z
    z = (z << 1) - y
    w = w + x
    x = (x << 1) - w
    return x, y, z, w


@partial(jax.jit, static_argnames=("rate", "dtype"))
def encode_slab(x, *, rate: int, dtype):
    """Field rows → ``(2·rate, n_blocks)`` uint32 words, ``(n_blocks,)`` exponents."""
    b = to_blocks(x).astype(dtype)
    amax = jnp.max(jnp.abs(b), axis=0)
    _, e = jnp.frexp(amax)
    e = jnp.where(amax == 0, 0, e).astype(jnp.int32)
    q = jnp.round(jnp.ldexp(b, (30 - e)[None, :])).astype(jnp.int32)
    t = q.reshape(4, 4, 4, -1)
    for axis in range(3):
        t = _lift(t, axis, _fwd)
    u = jax.lax.bitcast_convert_type(t.reshape(64, -1), jnp.uint32)
    u = ((u + NB_MASK) ^ NB_MASK)[ORDER]
    shifts = jnp.uint32(31) - jnp.arange(32, dtype=jnp.uint32)[:, None]
    words = []
    for p in range(rate):
        bits = (u >> jnp.uint32(31 - p)) & jnp.uint32(1)
        for half in (bits[:32], bits[32:]):
            words.append(jnp.sum(half << shifts, axis=0, dtype=jnp.uint32))
    return jnp.stack(words), e


@partial(jax.jit, static_argnames=("rate", "dtype"))
def decode_slab(words, e, *, rate: int, dtype):
    """Inverse of :func:`encode_slab`: ``(64, n_blocks)`` values in ``dtype``."""
    shifts = jnp.uint32(31) - jnp.arange(32, dtype=jnp.uint32)[:, None]
    halves = []
    for h in range(2):
        acc = jnp.zeros((32, words.shape[1]), jnp.uint32)
        for p in range(rate):
            bit = (words[2 * p + h][None, :] >> shifts) & jnp.uint32(1)
            acc = acc | (bit << jnp.uint32(31 - p))
        halves.append(acc)
    u = jnp.concatenate(halves)[INVERSE]
    t = jax.lax.bitcast_convert_type((u ^ NB_MASK) - NB_MASK, jnp.int32).reshape(4, 4, 4, -1)
    for axis in (2, 1, 0):
        t = _lift(t, axis, _inv)
    return jnp.ldexp(t.reshape(64, -1).astype(dtype), (e - 30)[None, :])


def _rows(shape) -> int:
    """Rows of the field per slab: halved while a slab is too large."""
    if len(shape) != 3 or any(n % 4 for n in shape):
        raise ValueError(f"the ZFP reference takes 3-D fields with edges divisible by 4, "
                         f"got {tuple(shape)}")
    rows = shape[0]
    while rows * shape[1] * shape[2] > SLAB_VALUES and rows % 8 == 0:
        rows //= 2
    return rows


def _slabs(x):
    rows = _rows(x.shape)
    return [x[i:i + rows] for i in range(0, x.shape[0], rows)]


def encode(x, rate: int, dtype=jnp.float32):
    """The stream of field ``x`` as host arrays ``(payload (n, 2·rate), emax (n,))``."""
    out = [encode_slab(s, rate=rate, dtype=dtype) for s in _slabs(x)]
    payload = np.concatenate([np.asarray(w).T for w, _ in out])
    emax = np.concatenate([np.asarray(e) for _, e in out])
    return payload, emax


def differing_values(y, payload, emax, rate: int, dtype=jnp.float32) -> int:
    """How many values of decoded field ``y`` differ, bit for bit, from the
    reference's decoding of ``payload``/``emax``."""
    y = jnp.asarray(y)
    device = next(iter(y.devices()))
    per_slab = _rows(y.shape) * y.shape[1] * y.shape[2] // 64
    bad = 0
    for k, s in enumerate(_slabs(y)):
        lo = k * per_slab
        words = jax.device_put(np.ascontiguousarray(payload[lo:lo + per_slab].T), device)
        e = jax.device_put(emax[lo:lo + per_slab], device)
        ref = decode_slab(words, e, rate=rate, dtype=dtype).astype(jnp.float32)
        got = to_blocks(s).astype(jnp.float32)
        bad += int(jnp.sum(jax.lax.bitcast_convert_type(ref, jnp.int32)
                           != jax.lax.bitcast_convert_type(got, jnp.int32)))
    return bad


def compare(fields: dict, streams: list[dict], decoded: list[dict], params: dict) -> dict:
    """The numbers that decide ``correct`` for ZFP cells.

    ``streams``: the sampled compress outputs, ``{key: (meta, arrays)}`` each;
    ``decoded``: the sampled decompress outputs, ``{key: field}`` each.
    Every stream has to equal the reference's, word for word, and every
    decoded field the reference's decoding, bit for bit.
    """
    rate = int(params["rate"])
    ref = {k: encode(x, rate) for k, x in fields.items()}
    words = 0
    for stream in streams:
        for k, (_meta, arrays) in stream.items():
            p, e = ref[k]
            got_p, got_e = arrays.get("payload"), arrays.get("emax")
            if got_p is None or got_e is None or got_p.shape != p.shape or got_e.shape != e.shape:
                words += p.size + e.size
            else:
                words += int(np.sum(got_p != p)) + int(np.sum(got_e != e))
    values = 0
    for out in decoded:
        for k, y in out.items():
            p, e = ref[k]
            if tuple(y.shape) != tuple(fields[k].shape):
                values += fields[k].size
            else:
                values += differing_values(y, p, e, rate)
    return {"stream_words_differing": float(words), "decoded_values_differing": float(values)}


def decode(payload, emax, rate: int, shape, dtype=jnp.float32) -> np.ndarray:
    """The field a stream holds, as a host array of ``shape`` in float32."""
    blocks = np.asarray(decode_slab(jnp.asarray(np.ascontiguousarray(payload.T)),
                                    jnp.asarray(emax), rate=rate, dtype=dtype),
                        np.float32)
    z, y, x = (n // 4 for n in shape)
    return blocks.reshape(4, 4, 4, z, y, x).transpose(3, 0, 4, 1, 5, 2).reshape(shape)
