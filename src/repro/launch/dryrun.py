import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (brief: MULTI-POD DRY-RUN).

Lowers + compiles every (architecture × input-shape × mesh) cell against the
production meshes — (16,16) "data","model" single-pod and (2,16,16)
"pod","data","model" multi-pod — on 512 placeholder CPU devices, records
``memory_analysis()`` / ``cost_analysis()`` / HLO collective bytes per cell
into ``results/dryrun/*.json``, which §Roofline and §Perf read.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # only 512-chip mesh
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, SHAPES, applicable_shapes, get_config
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import hlo_analysis, roofline
from repro.runtime import sharding as shr

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# §Perf hillclimb levers per (architecture × step kind) — variant "opt".
# Every lever is a config knob so the baseline (paper-faithful naive
# sharding) stays reproducible.  Keys: train / prefill / decode / "*".
_ZERO1 = {"sharding_policy": "dp_zero1", "param_dtype": "bfloat16"}
# inference wants the serving layout: TP-only bf16 params (no FSDP regather),
# scatter-free masked cache writes on the seq-sharded cache
_SERVE = {"fsdp": False, "param_dtype": "bfloat16", "decode_masked_update": True}
OPT_OVERRIDES: dict[str, dict[str, dict]] = {
    # ZeRO-1 for small dense archs: TP activation ARs dominated their baseline
    "qwen2.5-3b": {"train": _ZERO1},
    "minicpm-2b": {"train": _ZERO1},
    "qwen1.5-4b": {"train": _ZERO1},
    "mamba2-370m": {"train": _ZERO1},
    # group-blocked MoE dispatch (GShard groups) kills the (T,E,C) pathology;
    # bf16 params halve the FSDP regather + fit the optimizer in HBM.
    # NOT applied at decode: grouped dispatch on 128-token steps regressed
    # 2.1–2.4× in the sweep (capacity quantisation) — see the §Perf appendix.
    "deepseek-v3-671b": {
        "train": {"moe_group_size": 4096, "param_dtype": "bfloat16", "moe_impl": "a2a"},
        "prefill": {"moe_group_size": 4096, "param_dtype": "bfloat16", "moe_impl": "a2a"},
        # decode: the dense _SERVE layout regressed 2.7× (unsharded expert
        # weights exceed HBM and dominate reads) — MoE serving needs
        # full-mesh EP + token-level a2a, left as documented future work.
    },
    "llama4-scout-17b-a16e": {
        "train": {"moe_group_size": 4096, "param_dtype": "bfloat16"},
        "prefill": {"moe_group_size": 4096, "param_dtype": "bfloat16"},
    },
    # prefill is inference too: the FSDP-regather pathology applies equally
    "deepseek-67b": {"decode": _SERVE, "prefill": _SERVE},
    "qwen2-vl-72b": {"decode": _SERVE, "prefill": _SERVE},
    "recurrentgemma-9b": {},
    "seamless-m4t-medium": {},
}


def opt_overrides_for(arch: str, kind: str) -> dict:
    table = OPT_OVERRIDES.get(arch, {})
    out = dict(table.get("*", {}))
    out.update(table.get(kind, {}))
    return out


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = (
        "generated_code_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "alias_size_in_bytes", "temp_size_in_bytes",
    )
    return {k: int(getattr(mem, k, 0)) for k in keys}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             force: bool = False, variant: str = "baseline") -> dict:
    mesh_tag = "pod512" if multi_pod else "pod256"
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_tag}__{variant}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if variant == "opt":
        from dataclasses import replace as _replace

        cfg = _replace(cfg, **opt_overrides_for(arch, shape.kind))
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": list(mesh.shape.values()),
        "multi_pod": multi_pod, "variant": variant, "kind": shape.kind,
    }
    t0 = time.time()
    try:
        param_sds = S.param_specs(model, mesh)
        params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        record["param_report"] = shr.sharding_report(params_shape, cfg, mesh)
        counts = roofline.count_params(params_shape)
        record["param_counts"] = counts

        with jax.set_mesh(mesh):  # ambient mesh: activation constraints resolve
            if shape.kind == "train":
                # opt variant for FSDP giants: bf16 moments (memory-roofline lever)
                moment_dtype = "bfloat16" if (variant == "opt" and cfg.fsdp) else "float32"
                opt_cfg = adamw.AdamWConfig(moment_dtype=moment_dtype)
                opt_sds = S.opt_state_specs(param_sds, mesh, opt_cfg, cfg)
                batch_sds = S.batch_specs(cfg, shape, mesh)
                step = S.make_train_step(model, opt_cfg)
                lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                    param_sds, opt_sds, batch_sds
                )
            elif shape.kind == "prefill":
                batch_sds = S.batch_specs(cfg, shape, mesh)
                step = S.make_prefill_step(model)
                lowered = jax.jit(step).lower(param_sds, batch_sds)
            else:  # decode
                cache_sds = S.cache_specs(model, shape, mesh)
                tok_sds = S.token_specs(cfg, shape, mesh)
                step = S.make_decode_step(model)
                lowered = jax.jit(step, donate_argnums=(2,)).lower(
                    param_sds, tok_sds, cache_sds,
                    jax.ShapeDtypeStruct((), jax.numpy.int32),
                )
            record["lower_s"] = time.time() - t0

            t1 = time.time()
            compiled = lowered.compile()
            record["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        print(mem)   # proves it fits (per-device bytes)
        cost = hlo_analysis.cost_analysis_dict(compiled)
        print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
        record["memory"] = _mem_dict(mem)
        record["cost"] = {
            k: float(v)
            for k, v in (cost or {}).items()
            if isinstance(v, (int, float)) and "{" not in k
        }
        hlo = compiled.as_text()
        record["collectives_raw"] = hlo_analysis.parse_collectives(hlo).to_dict()
        coll = hlo_analysis.parse_collectives_scaled(hlo)  # while-body × trips
        record["collectives"] = coll.to_dict()
        record["hlo_bytes"] = len(hlo)

        chips = mesh.size
        mf = roofline.model_flops(cfg, shape, counts)
        record["model_flops"] = mf
        analytic_mem = roofline.analytic_memory_bytes(
            cfg, shape, counts,
            record["param_report"]["bytes_per_device"], chips,
        )
        record["analytic_memory_bytes_per_device"] = analytic_mem
        # Three-term roofline: compute from analytic MODEL_FLOPS (HLO cost
        # counts while bodies once — raw kept alongside for transparency),
        # memory = max(HLO bytes, analytic traffic), collective = scaled HLO.
        hlo_bytes_dev = record["cost"].get("bytes accessed", 0.0)
        pk = roofline.peaks(roofline.V5E_KIND)  # the production mesh's chip
        terms = roofline.RooflineTerms(
            t_compute=(mf["model_flops"] / chips) / pk.bf16_flops,
            t_memory=max(hlo_bytes_dev, analytic_mem) / pk.hbm_bw,
            t_collective=coll.total_link_bytes / pk.ici_bw,
            flops=mf["model_flops"] / chips,
            bytes_accessed=max(hlo_bytes_dev, analytic_mem),
            link_bytes=coll.total_link_bytes,
        )
        record["roofline"] = terms.to_dict()
        record["roofline_raw_hlo"] = roofline.terms_from_analysis(
            record["cost"], record["collectives_raw"]["total_link_bytes"],
            device_kind=roofline.V5E_KIND,
        ).to_dict()
        hlo_flops_global = record["cost"].get("flops", 0.0) * chips
        record["useful_flops_ratio_vs_raw_hlo"] = (
            mf["model_flops"] / hlo_flops_global if hlo_flops_global else None
        )
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["total_s"] = time.time() - t0

    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(record, indent=2))
    status = record["status"]
    print(f"[{status}] {arch} × {shape_name} × {mesh_tag} ({record['total_s']:.1f}s)",
          flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true", help="only the 512-chip mesh")
    ap.add_argument("--single-pod", action="store_true", help="only the 256-chip mesh")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "opt"])
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = [args.arch] if args.arch else list(ARCHS)
    meshes = [False, True]
    if args.multi_pod:
        meshes = [True]
    if args.single_pod:
        meshes = [False]

    results = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else applicable_shapes(cfg)
        for shape_name in shapes:
            for mp in meshes:
                results.append(
                    run_cell(arch, shape_name, mp, out_dir, args.force,
                             variant=args.variant)
                )
    ok = sum(r["status"] == "ok" for r in results)
    print(f"\n{ok}/{len(results)} cells OK")
    if ok < len(results):
        for r in results:
            if r["status"] != "ok":
                print(f"  FAILED {r['arch']} × {r['shape']} × "
                      f"{'pod512' if r['multi_pod'] else 'pod256'}: {r.get('error')}")


if __name__ == "__main__":
    main()
