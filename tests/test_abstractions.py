"""HPDR parallel abstractions + machine models + adapter registry."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import abstractions as ab
from repro.core import adapters
from repro.core.machine import block_view, unblock_view
import repro.kernels  # registers adapter implementations  # noqa: F401


def test_locality_blockwise(rng):
    data = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    out = ab.locality(data, lambda b: b * 2.0, (4, 4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(data) * 2.0)


def test_locality_pads_odd_shapes(rng):
    data = jnp.asarray(rng.normal(size=(10, 7)), jnp.float32)
    out = ab.locality(data, lambda b: b + 1.0, (4, 4))
    assert out.shape == data.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(data) + 1.0)


def test_block_view_roundtrip(rng):
    data = jnp.asarray(rng.normal(size=(8, 12, 4)), jnp.float32)
    blocks, counts = block_view(data, (4, 4, 4))
    assert blocks.shape == (2 * 3 * 1, 4, 4, 4)
    back = unblock_view(blocks, counts, (4, 4, 4))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(data))


def test_iterative_prefix_sum(rng):
    data = jnp.asarray(rng.normal(size=(16, 5)), jnp.float32)

    def step(carry, x):
        carry = carry + x
        return carry, carry

    _, out = ab.iterative(data, step, jnp.zeros(5), axis=0)
    np.testing.assert_allclose(
        np.asarray(out), np.cumsum(np.asarray(data), axis=0), rtol=1e-6
    )


def test_map_and_process(rng):
    data = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 3, 64), jnp.int32)
    out = ab.map_and_process(data, ids, [lambda x: x, lambda x: 2 * x, lambda x: -x])
    expect = np.asarray(data).copy()
    ids_np = np.asarray(ids)
    expect[ids_np == 1] *= 2
    expect[ids_np == 2] *= -1
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_global_pipeline_stages(rng):
    data = jnp.asarray(rng.normal(size=(100,)), jnp.float32)
    pipe = ab.global_pipeline(lambda x: x - jnp.mean(x), lambda x: x / (jnp.std(x) + 1e-9))
    out = np.asarray(pipe(data))
    assert abs(out.mean()) < 1e-5 and abs(out.std() - 1) < 1e-4


def test_adapter_registry_dispatch():
    assert adapters.resolve(None) in adapters.ADAPTERS
    assert adapters.resolve("auto") in adapters.ADAPTERS
    with pytest.raises(ValueError):
        adapters.resolve("cuda")
    fn = adapters.dispatch("histogram", "xla")
    assert callable(fn)
    assert adapters.lowering("histogram", "pallas_interpret") == "pallas_interpret"
    # only an op declared XLA-only falls back to its xla implementation
    assert "huffman_pack_stream" in adapters.XLA_ONLY_OPS
    assert adapters.lowering("huffman_pack_stream", "pallas_interpret") == "xla"
    assert (adapters.dispatch("huffman_pack_stream", "pallas_interpret")
            is adapters.dispatch("huffman_pack_stream", "xla"))
    with pytest.raises(KeyError):
        adapters.dispatch("nonexistent_op", "xla")


def test_dispatch_refuses_undeclared_xla_fallback(monkeypatch):
    """An op with only an xla implementation raises under a kernel adapter
    unless it is declared XLA-only."""
    monkeypatch.setitem(adapters._REGISTRY, ("_probe_op", adapters.XLA), len)
    assert adapters.dispatch("_probe_op", "xla") is len
    with pytest.raises(KeyError, match="_probe_op"):
        adapters.dispatch("_probe_op", "pallas_interpret")
    monkeypatch.setattr(
        adapters, "XLA_ONLY_OPS", adapters.XLA_ONLY_OPS | {"_probe_op"}
    )
    assert adapters.dispatch("_probe_op", "pallas_interpret") is len
