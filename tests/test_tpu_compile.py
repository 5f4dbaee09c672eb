"""Compile the main path for a described TPU v5e chip, with no chip attached.

Every Pallas kernel the ``pallas`` adapter dispatches on the main path is
compiled at the width of a NYX 512³ field (513³ after MGARD's dyadic
padding), and so are MGARD's decompose and recompose.  Each kernel is
compiled a second time under ``vmap`` over four 64 MiB leaves, as the
engine's stacked buckets run it.  The TPU compiler
refuses here what the chip would refuse: unaligned blocks, unsupported
gathers or reductions, more VMEM/SMEM than a kernel may use, a program
larger than the chip's memory.  Nothing runs, so this says nothing about
results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mgard
from repro.kernels.histogram import kernel as hist_k
from repro.kernels.huffman_decode import kernel as dec_k
from repro.kernels.huffman_encode import kernel as enc_k
from repro.kernels.quantize_map import kernel as quant_k
from repro.kernels.zfp_block import kernel as zfp_k

NYX = (512, 512, 512)
PADDED = 513 ** 3           # MGARD's dyadic padding of a 512³ field
LEAF = 257 ** 3             # a padded 256³ (64 MiB) engine leaf
STACK = 4                   # leaves in a stacked engine bucket
LEVELS = 10                 # per-level bins of a 513³ grid: L + 1 with L = 9
CHUNK = 4096                # huffman.DEFAULT_CHUNK
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_cases(n: int = PADDED):
    """name → (fn, [(shape, dtype), ...]) for ``n`` elements; interpret=False
    is the chip path."""
    zfp_blocks = -(-n // 64)
    return {
        "quantize_map": (
            lambda x, l, b: quant_k.quantize(x, l, b, interpret=False),
            [((n,), jnp.float32), ((n,), jnp.int32), ((LEVELS,), jnp.float32)],
        ),
        "dequantize_map": (
            lambda u, l, b: quant_k.dequantize(u, l, b, interpret=False),
            [((n,), jnp.uint32), ((n,), jnp.int32), ((LEVELS,), jnp.float32)],
        ),
        "histogram_4096": (
            lambda k: hist_k.histogram(k, 4096, interpret=False),
            [((n,), jnp.int32)],
        ),
        "histogram_65536": (
            lambda k: hist_k.histogram(k, 1 << 16, interpret=False),
            [((n,), jnp.int32)],
        ),
        "huffman_encode_lookup_4096": (
            lambda k, c, l: enc_k.encode_lookup(k, c, l, interpret=False),
            [((n,), jnp.int32), ((4096,), jnp.uint32), ((4096,), jnp.int32)],
        ),
        "huffman_encode_lookup_65536": (
            lambda k, c, l: enc_k.encode_lookup(k, c, l, interpret=False),
            [((n,), jnp.int32), ((1 << 16,), jnp.uint32), ((1 << 16,), jnp.int32)],
        ),
        "huffman_decode_chunks": (
            lambda w, o, f, c, s, y: dec_k.decode_chunks(
                w, o, f, c, s, y, CHUNK, 32, interpret=False),
            # a stream of ~10 bits/symbol; 33-entry canonical tables
            [((n * 10 // 32,), jnp.uint32), ((-(-n // CHUNK),), jnp.int32),
             ((33,), jnp.uint32), ((33,), jnp.int32), ((33,), jnp.int32),
             ((1 << 16,), jnp.int32)],
        ),
        "zfp_block_compress": (
            lambda b: zfp_k.compress_blocks(b, 16, 3, interpret=False),
            [((64, zfp_blocks), jnp.float32)],
        ),
        "zfp_block_decompress": (
            lambda p, e: zfp_k.decompress_blocks(p, e, 16, 3, interpret=False),
            [((zfp_blocks, 32), jnp.uint32), ((zfp_blocks,), jnp.int32)],
        ),
    }


def _compile(fn, args, sharding):
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    return jax.jit(fn).lower(*shapes).compile()


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
    return total < HBM_BYTES


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_cases()[name]
    compiled = _compile(fn, args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is there
    assert _fits(compiled)


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_vmapped_compiles_for_v5e(name, one_chip):
    """The engine vmaps each kernel over a stacked bucket of leaves."""
    fn, args = _kernel_cases(LEAF)[name]
    compiled = _compile(jax.vmap(fn), [((STACK,) + s, d) for s, d in args],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_mgard_decompose_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda u: mgard.decompose(u, shape=NYX), [(NYX, jnp.float32)], one_chip
    )
    assert _fits(compiled)


def test_mgard_recompose_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda c: mgard.recompose(c, shape=NYX), [((513,) * 3, jnp.float32)],
        one_chip,
    )
    assert _fits(compiled)
