"""ZFP-X: transform inversion, rate behaviour, roundtrip error decay."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import zfp
from repro.core.abstractions import pad_to_blocks
from conftest import smooth_field_3d


def test_lift_near_inverse(rng):
    v = rng.integers(-(2**29), 2**29, (1000, 4)).astype(np.int32)
    f = zfp.fwd_lift_vec(jnp.asarray(v))
    r = np.asarray(zfp.inv_lift_vec(f))
    # zfp's lift drops low bits by design; error is a few ULPs at 2^29 scale
    assert np.abs(r - v).max() <= 4


def test_negabinary_roundtrip(rng):
    q = rng.integers(-(2**31), 2**31 - 1, 10000).astype(np.int32)
    u = zfp.int_to_negabinary(jnp.asarray(q))
    out = np.asarray(zfp.negabinary_to_int(u))
    assert (out == q).all()


def test_bitplane_pack_roundtrip(rng):
    u = rng.integers(0, 2**32, (50, 64), dtype=np.uint32)
    for rate in (1, 7, 16, 32):
        words = zfp.pack_bitplanes(jnp.asarray(u), rate)
        out = np.asarray(zfp.unpack_bitplanes(words, rate, 64))
        mask = np.uint64(0xFFFFFFFF) << np.uint64(32 - rate)
        expect = (u.astype(np.uint64) & mask).astype(np.uint32)
        assert (out == expect).all(), rate


def test_error_decays_with_rate():
    data = smooth_field_3d(32)
    errs = []
    for rate in (4, 8, 16, 32):
        z = zfp.compress(jnp.asarray(data), rate=rate)
        out = np.asarray(zfp.decompress(z))
        errs.append(np.abs(out - data).max())
    assert errs[-1] < 1e-6  # near-lossless at rate 32
    for a, b in zip(errs, errs[1:]):
        assert b <= a * 1.01  # monotone (within float noise)


def test_fixed_rate_size():
    data = smooth_field_3d(32)
    z = zfp.compress(jnp.asarray(data), rate=8)
    n_blocks = (32 // 4) ** 3
    assert z.payload.shape == (n_blocks, zfp.words_per_block(64, 8))
    assert z.emax.shape == (n_blocks,)


@settings(max_examples=20, deadline=None)
@given(
    st.integers(1, 3),
    st.sampled_from([4, 8, 16, 32]),
    st.integers(0, 2**31),
)
def test_roundtrip_property(dims, rate, seed):
    rng = np.random.default_rng(seed)
    shape = tuple(rng.integers(3, 17, dims))
    scale = 10.0 ** rng.integers(-8, 8)
    data = (rng.normal(size=shape) * scale).astype(np.float32)
    z = zfp.compress(jnp.asarray(data), rate=rate)
    out = np.asarray(zfp.decompress(z))
    assert out.shape == data.shape
    vrange = np.abs(data).max() + 1e-30
    rel = np.abs(out - data).max() / vrange
    # Negabinary truncation + inverse-transform gain: worst-case relative
    # error ≈ gain·2^(2-rate).  At rate 4 on adversarial (white-noise) data
    # hypothesis found rel ≈ 2.5 — the documented cost of fixed truncation
    # without zfp's group testing; real use keeps rate ≥ 8 (rel ≤ 0.5).
    bound = {4: 6.0, 8: 0.5, 16: 2e-3, 32: 5e-6}[rate]
    assert rel <= bound, (shape, rate, rel)


def test_zero_and_constant_blocks():
    for val in (0.0, 3.25, -1e-20):
        data = np.full((16, 16), val, np.float32)
        z = zfp.compress(jnp.asarray(data), rate=16)
        out = np.asarray(zfp.decompress(z))
        assert np.abs(out - data).max() <= max(abs(val) * 1e-4, 1e-30)


def _blocks_by_strided_slices(padded):
    """The layout's definition: one stride-4 slice per in-block offset."""
    planes = [padded[tuple(slice(o, None, 4) for o in off)]
              for off in itertools.product(range(4), repeat=padded.ndim)]
    return jnp.stack(planes).reshape(len(planes), -1)


@pytest.mark.parametrize("shape", [
    (16,), (13,), (1030,), (8, 12), (7, 10), (8, 12, 16), (5, 9, 6),
    (4, 8, 12, 16), (3, 5, 6, 7),
])
def test_field_to_blocks_matches_strided_slices(shape, rng):
    """Blocking equals its strided-slice definition, and
    :func:`zfp.blocks_to_field` inverts it exactly, edge padding included."""
    data = rng.normal(size=shape).astype(np.float32)
    padded = pad_to_blocks(jnp.asarray(data), (4,) * len(shape))
    blocks = np.asarray(zfp.field_to_blocks(padded))
    expect = np.asarray(_blocks_by_strided_slices(padded))
    assert blocks.shape == expect.shape
    assert (blocks == expect).all()
    back = zfp.blocks_to_field(jnp.asarray(blocks), tuple(padded.shape))
    assert (np.asarray(back) == np.asarray(padded)).all()


@pytest.mark.parametrize("shape", [(4096,), (64, 64), (64, 64, 64), (16, 16, 16, 16)])
def test_field_to_blocks_has_no_strided_access(shape):
    """A stride-4 slice or gather on the minor axis is the TPU's worst case
    (41 ms for 8 MiB of a 512³ field on a v5e): blocking moves data with
    reshapes and transposes only."""
    jaxpr = jax.make_jaxpr(zfp.field_to_blocks)(
        jax.ShapeDtypeStruct(shape, jnp.float32))
    prims = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "gather" not in prims
    strided = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "slice"
               and any(s != 1 for s in (e.params["strides"] or ()))]
    assert not strided
