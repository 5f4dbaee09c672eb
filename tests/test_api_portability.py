"""Unified API: serialization roundtrip + cross-adapter portability.

The paper's portability contract: a bitstream produced under one device
adapter decodes under any other.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import api
from repro.core.context import GLOBAL_CMM
from repro.kernels.zfp_block import ops as zfp_ops
from conftest import smooth_field_3d


@pytest.mark.parametrize(
    "method,kw",
    [
        ("mgard", {"error_bound": 1e-2}),
        ("zfp", {"rate": 12}),
        ("huffman-bytes", {}),
    ],
)
def test_bytes_roundtrip(method, kw):
    f = smooth_field_3d(32)
    c = api.compress(jnp.asarray(f), method, **kw)
    c2 = api.Compressed.from_bytes(c.to_bytes())
    assert c2.method == c.method
    out = np.asarray(api.decompress(c2))
    if method == "huffman-bytes":
        np.testing.assert_array_equal(out, f)
    else:
        vr = f.max() - f.min()
        assert np.abs(out - f).max() <= 2e-2 * vr


def test_huffman_int_roundtrip(rng):
    keys = np.minimum(np.abs(rng.normal(0, 10, 20000)).astype(np.int32), 255)
    c = api.compress(jnp.asarray(keys), "huffman")
    out = np.asarray(api.decompress(api.Compressed.from_bytes(c.to_bytes())))
    np.testing.assert_array_equal(out, keys)


def test_cross_adapter_bitstream_portability(rng):
    """Compress with the Pallas kernel, decompress with the XLA oracle (and
    vice versa) — the paper's cross-architecture data portability claim."""
    blocks = rng.normal(size=(64, 64)).astype(np.float32).T  # (4^3, N)
    for enc_a, dec_a in [("pallas_interpret", "xla"), ("xla", "pallas_interpret")]:
        p, e = zfp_ops.compress_blocks(jnp.asarray(blocks), 16, 3, adapter=enc_a)
        out = np.asarray(zfp_ops.decompress_blocks(p, e, 16, 3, adapter=dec_a))
        ref = np.asarray(
            zfp_ops.decompress_blocks(
                *zfp_ops.compress_blocks(jnp.asarray(blocks), 16, 3, adapter=dec_a),
                16, 3, adapter=dec_a,
            )
        )
        np.testing.assert_array_equal(out, ref)


def test_cmm_caches_contexts():
    before = GLOBAL_CMM.hit_count + GLOBAL_CMM.miss_count
    f = smooth_field_3d(16)
    api.compress(jnp.asarray(f), "zfp", rate=8)
    api.compress(jnp.asarray(f), "zfp", rate=8)  # same characteristics → hit
    assert GLOBAL_CMM.hit_count + GLOBAL_CMM.miss_count >= before + 2
    assert GLOBAL_CMM.hit_count >= 1


def test_unknown_method_raises():
    with pytest.raises(ValueError):
        api.compress(jnp.zeros(4), "lz77")
