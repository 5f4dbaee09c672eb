"""The paper's scenario as a framework feature: reduction-accelerated I/O.

Writes a model checkpoint through all three HPDR pipelines, measures ratio
and throughput, and projects the multi-node I/O acceleration with the
Frontier/Summit filesystem model (paper Figs. 15/17/18).

    PYTHONPATH=src python examples/compressed_checkpoint_io.py
"""

import tempfile
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager, CheckpointPolicy
from repro.configs import get_config
from repro.models import build_model


def main() -> None:
    cfg = get_config("qwen1.5-4b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model: {nbytes/1e6:.1f} MB of parameters\n")

    for name, policy in (
        ("lossless (huffman-bytes)", CheckpointPolicy(exact=True)),
        ("zfp rate-28 (~1e-6 rel)", CheckpointPolicy(float_method="zfp", zfp_rate=28, lossless_small=1)),
        ("zfp rate-16 (transport)", CheckpointPolicy(float_method="zfp", zfp_rate=16, lossless_small=1)),
        ("mgard eb 1e-4", CheckpointPolicy(float_method="mgard", mgard_eb=1e-4, lossless_small=1)),
    ):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, policy)
            t0 = time.perf_counter()
            rep = mgr.save(0, {"params": params})
            dt = time.perf_counter() - t0
            restored, _ = mgr.restore(0, target={"params": params})
            err = max(
                float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params))
            )
            print(f"{name:28s} ratio={rep['ratio']:5.2f}x  "
                  f"{nbytes/dt/1e6:6.1f} MB/s (CPU)  max_abs_err={err:.2e}")

    # multi-node projection (paper's weak-scaling I/O model)
    print("\nI/O projection @ Frontier (1024 nodes × 4 GPUs, Lustre 9.4 TB/s):")
    for ratio, red_bps in (("4.0x (mgard 1e-2)", 4.0), ("2.6x (zfp r12)", 2.6)):
        r = float(ratio.split("x")[0])
        raw = 7.5e9 * 4096
        t_raw = raw / 9.4e12
        t_comp = raw / r / 9.4e12 + raw / (4096 * 11.8e9 * 0.96)
        print(f"  ratio {ratio:18s} write accel = {t_raw/t_comp:.1f}x")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
