"""Quickstart: compress/decompress a scientific field with all three pipelines.

Demonstrates the plan-based API: a ``ReductionSpec`` is built per setting,
its ``ReductionPlan`` (jitted executables + workspace) is CMM-cached, and
re-encoding with the same spec is a pure cache hit.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import api
from repro.core.context import GLOBAL_CMM


def main() -> None:
    # synthetic smooth 3-D field (NYX-density stand-in)
    n = 64
    g = np.linspace(0, 8 * np.pi, n)
    x, y, z = np.meshgrid(g, g, g, indexing="ij")
    rng = np.random.default_rng(0)
    data = np.exp(
        np.sin(x) * np.cos(y) * np.sin(z) + 0.05 * rng.normal(size=x.shape)
    ).astype(np.float32)
    print(f"input: {data.shape} float32, {data.nbytes/1e6:.1f} MB\n")

    for method, kw, note in (
        ("mgard", {"error_bound": 1e-2}, "error-bounded lossy (rel 1e-2)"),
        ("mgard", {"error_bound": 1e-4, "dict_size": 65536}, "error-bounded lossy (rel 1e-4)"),
        ("zfp", {"rate": 8}, "fixed-rate 8 bits/value"),
        ("zfp", {"rate": 16}, "fixed-rate 16 bits/value"),
        ("huffman-bytes", {}, "lossless byte-entropy (LZ-class)"),
    ):
        spec = api.make_spec(data, method, **kw)     # hashable CMM key
        comp = api.encode(spec, jnp.asarray(data))   # plan built once, cached
        blob = comp.to_bytes()  # portable v2 stream (what the checkpointer writes)
        out = np.asarray(api.decompress(api.Compressed.from_bytes(blob)))
        err = np.abs(out - data).max()
        rel = err / (data.max() - data.min())
        print(f"{method:14s} {note:32s} ratio={comp.ratio():6.2f}x  "
              f"stream={len(blob)/1e6:6.2f}MB  max_rel_err={rel:.2e}")

    # second encode with an identical spec: a pure plan-cache hit
    hits_before = GLOBAL_CMM.hit_count
    spec = api.make_spec(data, "zfp", rate=16)
    api.encode(spec, jnp.asarray(data))
    print(f"\nre-encode with cached plan: +{GLOBAL_CMM.hit_count - hits_before} CMM hit(s)")
    print("CMM context cache:", GLOBAL_CMM.stats())


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
