#!/usr/bin/env python3
"""The readings that set a cell's correctness limits, and its control.

    python3 bench/control.py --workload <cell> --seeds <first> <count>

For each seed, in one process: the cell's field is made as a run makes
it, written once and read back once through the cell's own entry points
(``loop.Caller``), and compared with the plain reference, as a run
compares its window's outputs: the program's readings. Then the control
takes the program's place: the codec's reference computed in bfloat16,
the precision below the float32 the configuration states, writes and
reads the field. Its readings have to fail a limit. One line of JSON
per seed; ``control_fails`` says whether the control failed a limit.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_outputs(spec, caller):
    """``(streams, decoded)`` of the bfloat16 reference in the program's place."""
    import jax.numpy as jnp

    from bench.reference import zfp

    from bench.run import as_compressed

    codec = spec.config["codec"]
    if codec == "zfp":
        rate = int(spec.config["params"]["rate"])
        out_streams, decoded = {}, {}
        for k, x in as_compressed(spec, caller.fields).items():
            p, e = zfp.encode(x, rate, jnp.bfloat16)
            out_streams[k] = ({"rate": rate}, {"payload": p, "emax": e})
            decoded[k] = zfp.decode(p, e, rate, x.shape, jnp.bfloat16).reshape(
                caller.fields[k].shape)
        return [out_streams], [decoded]
    raise ValueError(f"no control for codec {codec!r}")


def readings(spec, devices, seed: int) -> dict:
    """The program's and the control's numbers for one seed."""
    import importlib

    from bench import loop
    from bench.reference import container

    from bench.run import as_compressed

    ref = importlib.import_module(f"bench.reference.{spec.config['codec']}")
    caller = loop.Caller(spec.config, spec.traffic, devices, seed)
    try:
        blobs = caller.write()
        outs = caller.read(blobs)
    finally:
        caller.close()
    streams = [{k: container.parse(b)[1:] for k, b in blobs.items()}]

    fields = as_compressed(spec, caller.fields)
    program = ref.compare(fields, streams, [as_compressed(spec, outs)], spec.config["params"])
    c_streams, c_decoded = control_outputs(spec, caller)
    control = ref.compare(fields, c_streams, [as_compressed(spec, d) for d in c_decoded],
                          spec.config["params"])
    limits = spec.config["limits"]
    return {"seed": seed, "program": program, "control": control, "limits": limits,
            "program_passes": all(program[k] <= limits[k] for k in limits),
            "control_fails": any(control[k] > limits[k] for k in limits)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Readings of the program and its control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, metavar=("FIRST", "COUNT"), required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run

    spec = run.cell_spec(args.workload)
    devices = run.require_chips(int(spec.cell["chips"]))
    run.enable_compile_cache()
    first, count = args.seeds
    for seed in range(first, first + count):
        print(json.dumps(readings(spec, devices, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.exit(main())
