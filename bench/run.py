#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``: field shape, codec and its parameters,
the limits of the correctness check) and a traffic mix
(``bench/traffic/<name>.json``, driven by ``bench/loop.py``).

Set-up (``setup_s``): find the chips (a TPU with as many chips as the cell
asks for, or exit 1 with no result), turn on JAX's persistent compilation
cache (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
make the field on the device from ``--seed``, and warm up one write and
one read at the cell's shapes.

The window: ``--seconds`` split into two halves. In the first, writes are
repeated until the half has passed and the last one has finished; in the
second, reads of the bytes the last write produced, the same way. A rate
is the raw field bytes of all calls of a phase over the phase's whole
time. With ``--trace 1`` the window runs under ``jax.profiler`` and the
run reports the per-layer metrics (``bench/metrics/<name>.py``) instead
of the end-to-end ones.

After the window (and after the device's peak memory is read) a sample of
the window's outputs, drawn from the seed, is checked against the plain
reference of the codec (``bench/reference/<codec>.py``). Each number
compared is printed beside its limit, as the last lines of standard error
and under ``checks`` in the result, the last line of standard output.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str) -> SimpleNamespace:
    """The cell's entry, configuration, traffic and metric lists, by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}

    def applies(m):
        return name in m.get("workloads", [name])

    return SimpleNamespace(
        cell=cell,
        config=load_json(ROOT / configs[cell["config"]]["file"]),
        traffic=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices, or exit 1 with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        raise SystemExit(1)
    if len(devices) < chips:
        print(f"run.py: {chips} chips asked for, {len(devices)} present", file=sys.stderr)
        raise SystemExit(1)
    return devices[:chips]


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Programs compiled (or loaded from the cache) per phase of the run, the
    seconds spent tracing, lowering and compiling them, and the programs
    missing from the persistent cache (compiled anew)."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts = {"setup": 0, "compress": 0, "decompress": 0, "check": 0}
        self.seconds = {}
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        if event == CACHE_MISS_EVENT:
            self.misses += 1

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.counts[self.phase] += 1
        if event.startswith("/jax/core/compile/"):
            key = f"{self.phase}:{event.rsplit('/', 1)[-1].removesuffix('_duration')}"
            self.seconds[key] = self.seconds.get(key, 0.0) + duration


def warm_up(call, counter: CompileCounter, most: int = 3):
    """Call twice, and a third time only if the second compiled a program
    anew (at most ``most`` calls).

    The first call compiles, or loads from the persistent cache, every
    program; a second can still meet a new signature (a donated workspace
    buffer comes back committed to its device), and the window has to meet
    none. A second call that compiles nothing, or only loads programs the
    cache already holds (the engine builds its programs anew on every
    call), ends the warm-up.
    """
    out = call()
    for _ in range(most - 1):
        compiled, missed = counter.counts["setup"], counter.misses
        out = call()
        if counter.counts["setup"] == compiled or counter.misses == missed:
            break
    return out


def phase(name: str, call, seconds: float, keep: set, counter: CompileCounter):
    """Repeat ``call`` until ``seconds`` have passed and the last call ended."""
    from jax.profiler import TraceAnnotation

    counter.phase = name
    kept, last, calls, failed, times = {}, None, 0, 0, []
    start = time.perf_counter()
    with TraceAnnotation(f"window.{name}"):
        while True:
            t = time.perf_counter()
            try:
                out = call()
            except Exception:  # a failed call is counted and reported, not fatal
                failed += 1
                traceback.print_exc()
                out = None
            if out is not None:
                last = out
                if calls in keep:
                    kept[calls] = out
            calls += 1
            times.append(time.perf_counter() - t)
            if time.perf_counter() - start >= seconds:
                break
    elapsed = time.perf_counter() - start
    counter.phase = "check"
    return SimpleNamespace(calls=calls, failed=failed, seconds=elapsed, kept=kept, last=last,
                           times=times)


def peak(kind: str, key: str) -> float:
    """A published peak of one chip of ``kind`` (``bench/peaks.json``)."""
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in bench/peaks.json")
    return float(peaks[kind][key])


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def as_compressed(spec, leaves: dict) -> dict:
    """Each field as the codec sees it: the traffic's ``leaf_view`` of it, if any."""
    view = spec.traffic.get("leaf_view", {}).get(spec.config["codec"])
    return {k: (v.reshape(view) if view else v) for k, v in leaves.items()}


def check(spec, caller, writes, reads) -> dict:
    """Compare the sampled outputs of the window with the plain reference."""
    from bench.reference import container

    keys = set(caller.fields)
    unique = []
    for w in [w for _, w in sorted(writes.kept.items())] + [writes.last]:
        if w is not None and w not in unique:
            unique.append(w)
    if writes.last is not None:  # the stream the reads decoded goes last
        unique.remove(writes.last)
        unique.append(writes.last)
    outs = [r for _, r in sorted(reads.kept.items())]
    if reads.last is not None and all(r is not reads.last for r in outs):
        outs.append(reads.last)
    missing = sum(len(keys - set(s)) for s in unique + outs)
    missing += len(keys) * ((not unique) + (not outs))
    numbers = {"leaves_missing": float(missing),
               "calls_failed": float(writes.failed + reads.failed)}
    limits = {"leaves_missing": 0.0, "calls_failed": 0.0}
    limits.update(spec.config["limits"])
    try:
        streams = [{k: container.parse(raw)[1:] for k, raw in w.items() if k in keys}
                   for w in unique]
    except container.StreamError as e:
        print(f"run.py: unreadable stream: {e}", file=sys.stderr)
        streams = None
    if streams is None or not streams or not outs:
        numbers.update({name: math.inf for name in spec.config["limits"]})
    else:
        ref = importlib.import_module(f"bench.reference.{spec.config['codec']}")
        numbers.update(ref.compare(as_compressed(spec, caller.fields), streams,
                                   [as_compressed(spec, {k: v for k, v in o.items() if k in keys})
                                    for o in outs],
                                   spec.config["params"]))
    return {name: {"value": numbers[name], "limit": float(limits[name])} for name in numbers}


def read_per_layer(spec, ctx) -> dict:
    out = {}
    for m in spec.per_layer:
        path = BENCH / "metrics" / f"{m['name']}.py"
        module_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"), path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        value = module.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _finite(x: float) -> float:
    return x if math.isfinite(x) else sys.float_info.max


def run_cell(spec, devices, seed: int, seconds: float, traced: bool, t0: float) -> dict:
    """Set-up, window, check and metrics of one run on ``devices``."""
    import jax
    import numpy as np

    from bench import loop
    from bench import trace as tr

    counter = CompileCounter()
    t_field = time.perf_counter()
    caller = loop.Caller(spec.config, spec.traffic, devices, seed)
    try:
        t_write = time.perf_counter()
        blobs = warm_up(caller.write, counter)
        t_read = time.perf_counter()
        warm_up(lambda: caller.read(blobs), counter)
        setup_s = time.perf_counter() - t0
        setup_parts = (f"start {t_field - t0:.3f}, field {t_write - t_field:.3f}, "
                       f"writes {t_read - t_write:.3f}, reads {t0 + setup_s - t_read:.3f}")

        rng = np.random.default_rng(seed)
        keep = {0, 1 + int(rng.integers(7))}
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        writes = phase("compress", caller.write, seconds / 2, keep, counter)
        source = writes.last or {}
        reads = phase("decompress", lambda: caller.read(source), seconds / 2, keep, counter)
        if traced:
            jax.profiler.stop_trace()
        memory = memory_peak(devices)
        t_check = time.perf_counter()
        checks = check(spec, caller, writes, reads)
        print(f"timing: setup_s={setup_s} ({setup_parts}) compress_s={writes.seconds} "
              f"decompress_s={reads.seconds} check_s={time.perf_counter() - t_check} "
              f"compiles={counter.counts} cache_misses={counter.misses} "
              f"compile_s={counter.seconds} "
              f"write_s={[round(t, 3) for t in writes.times[:60]]} "
              f"read_s={[round(t, 3) for t in reads.times[:60]]}", file=sys.stderr)
    finally:
        caller.close()

    raw = caller.raw_bytes
    stream_bytes = sum(len(b) for b in source.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    breakdown = None
    if not traced:
        values = {
            "compress_GBps": writes.calls * raw / writes.seconds / 1e9,
            "decompress_GBps": reads.calls * raw / reads.seconds / 1e9,
            "ratio": raw / stream_bytes if stream_bytes else 0.0,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}
    else:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        trace = tr.load(files[-1]) if files else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        from bench.reference import container

        ctx = SimpleNamespace(
            trace=trace, config=spec.config, traffic=spec.traffic,
            calls={"compress": writes.calls, "decompress": reads.calls},
            compiles=counter.counts,
            streams={k: container.parse(b)[1:] for k, b in source.items()},
            peak=lambda key: peak(d0.device_kind, key),
        )
        metrics = read_per_layer(spec, ctx)
        if trace is not None and trace.devices and trace.window("compress"):
            lo = trace.window("compress")[0]
            hi = trace.window("decompress")[1]
            busy = [tr.busy_seconds(ops, lo, hi) for ops in trace.devices.values()]
            device.update(busy_s=sum(busy) / len(busy), window_s=hi - lo)
            for name in tr.PHASES:
                for dev, share in sorted((tr.idle_shares(trace, name) or {}).items()):
                    print(f"idle.{name} {dev} = {share} %", file=sys.stderr)
            breakdown = {"device_ops": tr.top_ops(trace, lo, hi),
                         "idle_gaps": tr.idle_gaps(trace, lo, hi)}
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": writes.calls + reads.calls,
              "failed": writes.failed + reads.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the repro package is not in src/ of this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    spec = cell_spec(args.workload)
    devices = require_chips(int(spec.cell["chips"]))
    enable_compile_cache()
    result = run_cell(spec, devices, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # this file's directory holds a module named ``trace``: import the
    # benchmark's modules as ``bench.<name>`` from the checkout instead
    sys.path[0] = str(ROOT)
    sys.exit(main())
