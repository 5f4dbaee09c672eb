"""Per-kernel validation: pallas_interpret vs pure-jnp oracle, shape/dtype sweeps."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.histogram import ops as hist_ops
from repro.kernels.huffman_encode import ops as enc_ops
from repro.kernels.mgard_lerp import ops as lerp_ops
from repro.kernels.quantize_map import ops as quant_ops
from repro.kernels.tridiag import ops as tri_ops
from repro.kernels.zfp_block import ops as zfp_ops

ADAPTERS = ("pallas_interpret", "xla")


@pytest.mark.parametrize("dims", [1, 2, 3])
@pytest.mark.parametrize("rate", [8, 16, 32])
def test_zfp_block_kernel(dims, rate, rng):
    bs = 4**dims
    blocks = (rng.normal(size=(130, bs)) * 10.0 ** rng.integers(-3, 4, (130, 1))).astype(
        np.float32
    )
    blocks = jnp.asarray(blocks.T)  # coefficient-major (4^d, N)
    p_k, e_k = zfp_ops.compress_blocks(blocks, rate, dims, adapter="pallas_interpret")
    p_r, e_r = zfp_ops.compress_blocks(blocks, rate, dims, adapter="xla")
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_array_equal(np.asarray(e_k), np.asarray(e_r))
    out_k = zfp_ops.decompress_blocks(p_k, e_k, rate, dims, adapter="pallas_interpret")
    out_r = zfp_ops.decompress_blocks(p_r, e_r, rate, dims, adapter="xla")
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("num_bins", [16, 1000, 4096])
def test_histogram_kernel(num_bins, rng):
    keys = rng.integers(0, num_bins, 30000).astype(np.int32)
    h_k = np.asarray(hist_ops.histogram(jnp.asarray(keys), num_bins, adapter="pallas_interpret"))
    h_r = np.asarray(hist_ops.histogram(jnp.asarray(keys), num_bins, adapter="xla"))
    np.testing.assert_array_equal(h_k, h_r)
    assert h_k.sum() == keys.size


def test_huffman_encode_kernel(rng):
    k = 2048
    codes_t = rng.integers(0, 2**20, k).astype(np.uint32)
    lens_t = rng.integers(1, 21, k).astype(np.int32)
    keys = rng.integers(0, k, 50000).astype(np.int32)
    c_k, l_k = enc_ops.encode_lookup(
        jnp.asarray(keys), jnp.asarray(codes_t), jnp.asarray(lens_t),
        adapter="pallas_interpret",
    )
    c_r, l_r = enc_ops.encode_lookup(
        jnp.asarray(keys), jnp.asarray(codes_t), jnp.asarray(lens_t), adapter="xla"
    )
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("n", [1000, 65536, 100001])
def test_quantize_kernel(n, rng):
    x = rng.normal(size=n).astype(np.float32)
    lv = rng.integers(0, 6, n).astype(np.int32)
    bins = (10.0 ** -rng.uniform(2, 4, 6)).astype(np.float32)
    q_k = quant_ops.quantize(jnp.asarray(x), jnp.asarray(lv), jnp.asarray(bins), adapter="pallas_interpret")
    q_r = quant_ops.quantize(jnp.asarray(x), jnp.asarray(lv), jnp.asarray(bins), adapter="xla")
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    x_k = np.asarray(quant_ops.dequantize(q_k, jnp.asarray(lv), jnp.asarray(bins), adapter="pallas_interpret"))
    err = np.abs(x_k - x)
    assert (err <= bins[lv] / 2 + 1e-7).all()


@pytest.mark.parametrize("n", [17, 65, 4097])
def test_mgard_lerp_kernel(n, rng):
    rows = rng.normal(size=(19, n)).astype(np.float32)
    m_k = np.asarray(lerp_ops.lerp_coefficients(jnp.asarray(rows), adapter="pallas_interpret"))
    m_r = np.asarray(lerp_ops.lerp_coefficients(jnp.asarray(rows), adapter="xla"))
    np.testing.assert_array_equal(m_k, m_r)


@pytest.mark.parametrize("n,h", [(17, 1.0), (33, 2.0), (129, 8.0)])
def test_tridiag_kernel(n, h, rng):
    rhs = rng.normal(size=(23, n)).astype(np.float32)
    x_k = np.asarray(tri_ops.solve_mass(jnp.asarray(rhs), h, adapter="pallas_interpret"))
    x_r = np.asarray(tri_ops.solve_mass(jnp.asarray(rhs), h, adapter="xla"))
    np.testing.assert_allclose(x_k, x_r, rtol=3e-5, atol=3e-6)
    # verify against dense solve for the first system
    m = np.zeros((n, n))
    for i in range(n):
        m[i, i] = 2 * h / 3 if 0 < i < n - 1 else h / 3
        if i > 0:
            m[i, i - 1] = h / 6
        if i < n - 1:
            m[i, i + 1] = h / 6
    xd = np.linalg.solve(m, rhs[0])
    np.testing.assert_allclose(x_k[0], xd, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("nk,chunk", [(50, 1024), (4096, 4096)])
def test_huffman_decode_kernel(nk, chunk, rng):
    from repro.core import huffman
    from repro.kernels.huffman_decode import ops as dec_ops

    keys = (rng.zipf(1.5, 20000) % nk).astype(np.int32)
    freq = np.bincount(keys, minlength=nk)
    book = huffman.build_codebook(freq)
    codes, lens = enc_ops.encode_lookup(
        jnp.asarray(keys), jnp.asarray(book.codes), jnp.asarray(book.lengths),
        adapter="xla",
    )
    total = int(np.sum(freq * book.lengths))
    words, offs, _ = enc_ops.pack_stream(codes, lens, total // 32 + 1, chunk, adapter="xla")
    t = huffman.decode_tables(np.asarray(book.lengths, np.int32))
    args = (words, offs, jnp.asarray(t.first_code), jnp.asarray(t.count),
            jnp.asarray(t.sym_offset), jnp.asarray(t.sym_sorted), chunk, t.max_len)
    d_k = np.asarray(dec_ops.decode_chunks(*args, adapter="pallas_interpret"))
    d_r = np.asarray(dec_ops.decode_chunks(*args, adapter="xla"))
    np.testing.assert_array_equal(d_k, d_r)  # padding symbols included
    np.testing.assert_array_equal(d_k.reshape(-1)[: keys.size], keys)


@pytest.mark.parametrize("name", [
    "histogram", "encode_lookup", "decode_chunks", "quantize", "dequantize",
    "compress_blocks", "decompress_blocks", "lerp_coefficients", "solve_mass",
])
def test_kernel_wrappers_require_interpret(name):
    """A direct caller must choose: no kernel defaults to the interpreter."""
    import inspect

    from repro.kernels.histogram import kernel as hk
    from repro.kernels.huffman_decode import kernel as dk
    from repro.kernels.huffman_encode import kernel as ek
    from repro.kernels.mgard_lerp import kernel as lk
    from repro.kernels.quantize_map import kernel as qk
    from repro.kernels.tridiag import kernel as tk
    from repro.kernels.zfp_block import kernel as zk

    mods = {"histogram": hk, "encode_lookup": ek, "decode_chunks": dk,
            "quantize": qk, "dequantize": qk, "compress_blocks": zk,
            "decompress_blocks": zk, "lerp_coefficients": lk, "solve_mass": tk}
    param = inspect.signature(getattr(mods[name], name)).parameters["interpret"]
    assert param.kind is inspect.Parameter.KEYWORD_ONLY
    assert param.default is inspect.Parameter.empty
