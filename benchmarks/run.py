"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (brief requirement).
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from . import (
        fig01_breakdown,
        fig10_13_pipeline,
        fig11_chunk_model,
        fig12_kernel_throughput,
        fig14_ratio,
        fig15_17_18_multinode_io,
        fig16_scalability,
        fig_progressive_gradcomp,
        roofline_report,
    )

    modules = [
        ("fig01_breakdown", fig01_breakdown),
        ("fig10_13_pipeline", fig10_13_pipeline),
        ("fig11_chunk_model", fig11_chunk_model),
        ("fig12_kernel_throughput", fig12_kernel_throughput),
        ("fig14_ratio", fig14_ratio),
        ("fig16_scalability", fig16_scalability),
        ("fig15_17_18_multinode_io", fig15_17_18_multinode_io),
        ("fig_progressive_gradcomp", fig_progressive_gradcomp),
        ("roofline_report", roofline_report),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            mod.main()
            print(f"bench.{name}.wall,{(time.time()-t0)*1e6:.0f},ok")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"bench.{name}.wall,{(time.time()-t0)*1e6:.0f},FAILED")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
