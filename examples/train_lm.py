"""End-to-end training driver: LM training with HPDR-compressed checkpoints.

Default preset trains a ~10M-param qwen-family model for 200 steps on CPU;
``--preset 100m`` selects a ~100M-param config (a few hundred steps on a
real accelerator; pass --steps to trim on CPU).

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.train import train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["small", "100m"], default="small")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/hpdr_train_ckpt")
    ap.add_argument("--arch", default="qwen2.5-3b")
    args = ap.parse_args()

    if args.preset == "small":
        out = train_loop(
            args.arch, steps=args.steps, batch=8, seq=128, smoke=True,
            ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 4, 1),
            sched="wsd",
        )
    else:
        # ~100M params: d_model 512, 12 layers, vocab 32k (smoke-based resize)
        cfg = get_config(args.arch).smoke()
        cfg = dataclasses.replace(
            cfg, d_model=512, n_layers=12, n_heads=8, n_kv_heads=8,
            head_dim=64, d_ff=2048, vocab=32000,
        )
        from repro.launch import train as T

        orig = T.get_config
        T.get_config = lambda name: cfg  # inject the resized config
        try:
            out = train_loop(
                args.arch, steps=args.steps, batch=8, seq=256, smoke=False,
                ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 4, 1),
            )
        finally:
            T.get_config = orig
    print("\nresult:", {k: v for k, v in out.items() if k != "ckpt_report"})
    if out.get("ckpt_report"):
        r = out["ckpt_report"]
        print(f"checkpoint: {r['raw_bytes']/1e6:.1f}MB → "
              f"{r['compressed_bytes']/1e6:.1f}MB (ratio {r['ratio']:.2f}x) "
              f"in {r['save_s']:.1f}s")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
