"""Fig. 12 — kernel throughput per pipeline × adapter ("portability × perf").

The paper's five processors become our adapter matrix: xla-cpu (measured),
pallas_interpret (measured; Python interpretation, correctness surface), and
the TPU-v5e projection (roofline: these kernels are memory-bound, so
throughput ≈ HBM_bw / bytes-touched-per-input-byte).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .common import Row, nyx_like, timeit
from repro.core import huffman
from repro.kernels.zfp_block import ops as zfp_ops
from repro.runtime import roofline

# bytes touched per input byte (read in + write out + tables), per pipeline
_TPU_TRAFFIC_FACTOR = {"zfp": 1.6, "huffman": 2.2, "mgard": 3.5}


def main() -> None:
    data = nyx_like(32)
    blocks = data.reshape(-1, 64)[:2048].T  # coefficient-major (4^3, N)

    for adapter in ("xla", "pallas_interpret"):
        x = jnp.asarray(blocks)
        t = timeit(
            lambda: zfp_ops.compress_blocks(x, 16, 3, adapter=adapter), repeat=2
        )
        Row(
            f"fig12.zfp.{adapter}",
            t * 1e6,
            f"bps={blocks.nbytes/t/1e6:.1f}MB/s",
        ).emit()

    keys = jnp.asarray(
        np.minimum(np.abs(np.random.default_rng(0).normal(0, 30, 1 << 18)), 4095
                   ).astype(np.int32)
    )
    t = timeit(lambda: huffman.histogram(keys, 4096), repeat=2)
    Row("fig12.huffman_hist.xla", t * 1e6,
        f"bps={keys.nbytes/t/1e6:.1f}MB/s").emit()

    for method, factor in _TPU_TRAFFIC_FACTOR.items():
        proj = roofline.peaks(roofline.V5E_KIND).hbm_bw / factor
        Row(f"fig12.{method}.tpu_v5e_roofline", 0.0,
            f"projected_bps={proj/1e9:.0f}GB/s (memory-bound, factor={factor})").emit()


if __name__ == "__main__":
    main()
