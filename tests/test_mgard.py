"""MGARD-X: decomposition losslessness, error-bound guarantee, level map."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mgard
from conftest import smooth_field_3d


@pytest.mark.parametrize(
    "shape", [(17,), (33,), (100,), (65, 65), (20, 33), (17, 9, 13), (5, 5, 5, 5)]
)
def test_decompose_recompose_lossless(shape, rng):
    u = rng.normal(size=shape).astype(np.float32)
    c = mgard.decompose(jnp.asarray(u), shape)
    r = np.asarray(mgard.recompose(c, shape))
    assert np.abs(r - u).max() < 5e-6


@pytest.mark.parametrize(
    "shape", [(65,), (17, 17), (33, 17), (9, 9, 9), (16, 16, 16), (20, 30, 40)]
)
def test_transform_vmapped_matches_serial(shape, rng):
    """A stacked bucket vmaps the transform; it must round like one leaf's
    own program (README invariant 1), whatever XLA fuses differently."""
    import jax

    u = jnp.asarray(rng.normal(size=(3,) + shape).astype(np.float32) * 10)
    dec = lambda x: mgard.decompose(x, shape)  # noqa: E731
    rec = lambda c: mgard.recompose(c, shape)  # noqa: E731
    c_serial = jnp.stack([jax.jit(dec)(x) for x in u])
    np.testing.assert_array_equal(np.asarray(jax.jit(jax.vmap(dec))(u)),
                                  np.asarray(c_serial))
    c = c_serial + jnp.asarray(rng.normal(size=c_serial.shape).astype(np.float32))
    r_serial = jnp.stack([jax.jit(rec)(x) for x in c])
    np.testing.assert_array_equal(np.asarray(jax.jit(jax.vmap(rec))(c)),
                                  np.asarray(r_serial))


def test_error_bound_smooth():
    f = smooth_field_3d(48)
    vr = float(f.max() - f.min())
    for rel_eb in (1e-2, 1e-3):
        eb = rel_eb * vr
        z = mgard.compress(jnp.asarray(f), eb)
        out = np.asarray(mgard.decompress(z))
        assert np.abs(out - f).max() <= eb


def test_error_bound_noisy():
    f = smooth_field_3d(32, noise=0.1)
    eb = 1e-2 * float(f.max() - f.min())
    z = mgard.compress(jnp.asarray(f), eb, dict_size=65536)
    out = np.asarray(mgard.decompress(z))
    assert np.abs(out - f).max() <= eb


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(0, 2**31))
def test_error_bound_property(dims, seed):
    rng = np.random.default_rng(seed)
    shape = tuple(int(x) for x in rng.integers(5, 25, dims))
    u = rng.normal(size=shape).astype(np.float32)
    eb = 1e-2 * float(u.max() - u.min())
    z = mgard.compress(jnp.asarray(u), eb, dict_size=65536)
    out = np.asarray(mgard.decompress(z))
    assert np.abs(out - u).max() <= eb, shape


def test_compression_beats_raw_on_smooth():
    f = smooth_field_3d(48)
    eb = 1e-2 * float(f.max() - f.min())
    z = mgard.compress(jnp.asarray(f), eb)
    assert mgard.compression_ratio(z) > 3.0


def test_level_map_structure():
    lm = mgard.level_map((9, 9))
    # corners of the coarsest grid are nodal (id = L)
    L = mgard.total_levels((9, 9))
    assert lm[0, 0] == L and lm[8, 8] == L and lm[0, 8] == L
    # odd nodes are finest level 0
    assert lm[1, 3] == 0 and lm[5, 5] == 0
    # stride-2-only nodes are level 1
    assert lm[2, 2] == 1
    assert lm.shape == (9, 9)


def test_outliers_roundtrip(rng):
    # data with one huge spike → outlier path must restore it within eb
    f = smooth_field_3d(16)
    f[3, 3, 3] = 100.0
    eb = 1e-3 * float(f.max() - f.min())
    z = mgard.compress(jnp.asarray(f), eb, dict_size=256)
    assert z.outlier_idx.size > 0
    out = np.asarray(mgard.decompress(z))
    assert np.abs(out - f).max() <= eb
