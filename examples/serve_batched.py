"""Batched serving example: continuous-batching engine + KV-cache parking.

    PYTHONPATH=src python examples/serve_batched.py
"""

import numpy as np
import jax

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import (
    Request,
    ServingEngine,
    compress_kv_cache,
    decompress_kv_cache,
)


def main() -> None:
    cfg = get_config("qwen2.5-3b").smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, batch_size=2, max_len=64)

    rng = np.random.default_rng(0)
    requests = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                max_new_tokens=8)
        for i in range(5)
    ]
    stats = engine.serve(requests)
    print("serve stats:", stats)
    for r in requests[:3]:
        print(f"  req {r.uid}: prompt={list(r.prompt)} -> {r.out_tokens}")

    # park the session: ZFP-X fixed-rate compression of the KV cache
    comp, cstats = compress_kv_cache(engine.cache, rate=12)
    print(f"\nKV cache parked: {cstats['raw']/1e6:.2f}MB → "
          f"{cstats['compressed']/1e6:.2f}MB ({cstats['ratio']:.1f}x)")
    restored = decompress_kv_cache(comp, engine.cache)
    engine.cache = restored
    print("session resumed from compressed cache.")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
