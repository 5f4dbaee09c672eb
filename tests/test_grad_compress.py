"""Gradient compression: quantization bounds, error feedback, convergence."""

import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.optim import grad_compress as gc


def test_quantize_roundtrip_bound(rng):
    g = rng.normal(size=10000).astype(np.float32)
    q, s = gc.quantize_blocks(jnp.asarray(g), bits=8)
    out = np.asarray(gc.dequantize_blocks(q, s, g.shape))
    # per-block error <= scale/2 = absmax/127/2
    blocks = np.pad(g, (0, (-len(g)) % gc.BLOCK)).reshape(-1, gc.BLOCK)
    bound = np.abs(blocks).max(1) / 127.0 / 2.0 + 1e-8
    err = np.abs(out - g)
    err_blocks = np.pad(err, (0, (-len(err)) % gc.BLOCK)).reshape(-1, gc.BLOCK)
    assert (err_blocks.max(1) <= bound + 1e-7).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(10, 5000), st.integers(0, 2**31))
def test_ef_residual_property(n, seed):
    rng = np.random.default_rng(seed)
    g = rng.normal(size=n).astype(np.float32)
    res = jnp.zeros(n)
    (q, s), new_res = gc.ef_step(jnp.asarray(g), res, bits=8)
    approx = np.asarray(gc.dequantize_blocks(q, s, g.shape))
    # residual == exactly what compression lost
    np.testing.assert_allclose(np.asarray(new_res), g - approx, rtol=1e-5, atol=1e-6)


def test_error_feedback_accumulates_to_truth(rng):
    """Σ transmitted ≈ Σ true gradients (EF keeps long-run sums unbiased)."""
    n, steps = 512, 50
    res = jnp.zeros(n)
    total_true = np.zeros(n)
    total_sent = np.zeros(n)
    for i in range(steps):
        g = rng.normal(size=n).astype(np.float32) * 0.1
        total_true += g
        (q, s), res = gc.ef_step(jnp.asarray(g), res, bits=4)  # aggressive 4-bit
        total_sent += np.asarray(gc.dequantize_blocks(q, s, g.shape))
    # all that's missing is the final residual
    np.testing.assert_allclose(total_sent + np.asarray(res), total_true,
                               rtol=1e-4, atol=1e-4)


def test_ef_sgd_converges(rng):
    """EF-compressed SGD reaches the same optimum on a quadratic."""
    dim = 64
    target = rng.normal(size=dim).astype(np.float32)
    for bits, tol in ((8, 1e-3), (4, 5e-3)):
        x = np.zeros(dim, np.float32)
        res = jnp.zeros(dim)
        for _ in range(300):
            g = x - target
            (q, s), res = gc.ef_step(jnp.asarray(g), res, bits=bits)
            x = x - 0.2 * np.asarray(gc.dequantize_blocks(q, s, g.shape))
        assert np.abs(x - target).max() < tol * np.abs(target).max() + tol


def test_pod_compressed_mean_shardmap():
    """pod_compressed_mean inside shard_map equals the true mean (±quant err)."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        import pytest

        pytest.skip("needs >=2 devices")
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2,), ("pod",))
    from jax.sharding import PartitionSpec as P

    g = jnp.arange(2 * 512, dtype=jnp.float32).reshape(2, 512) / 100.0

    def f(local):
        return gc.pod_compressed_mean(local[0], axis_name="pod")

    out = jax.shard_map(
        f, mesh=mesh, in_specs=P("pod", None), out_specs=P(None)
    )(g)
    true = np.asarray(g).mean(0)
    np.testing.assert_allclose(np.asarray(out), true, atol=np.abs(true).max() / 100)
