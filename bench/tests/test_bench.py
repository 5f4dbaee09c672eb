"""The benchmark harness on the CPU: its files, its trace reduction, its byte
counts, each traffic loop at a tiny size, and the correctness check, which
has to pass the program and fail the control and every planted fault.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import control, loop, run
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# the smallest shapes at which each configuration's cells still split and block
TINY = {"nyx512-zfp": [32, 32, 32]}
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|projection|head|"
                    r"expansion|experts_per_tok")


def metric_module(name: str):
    spec = importlib.util.spec_from_file_location(
        "m_" + re.sub(r"\W", "_", name), ROOT / "bench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny_spec(cell: str):
    spec = run.cell_spec(cell)
    spec.config["shape"] = TINY[spec.cell["config"]]
    spec.config["params"]["backend"] = "pallas_interpret"
    return spec


def devices_for(spec):
    import jax

    chips = int(spec.cell["chips"])
    if len(jax.devices()) < chips:
        pytest.skip(f"{chips} virtual devices needed; set XLA_FLAGS before jax loads")
    return jax.devices()[:chips]


# --------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# --------------------------------------------------------------------------


def test_every_name_resolves_to_its_files():
    b = BENCHMARK
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == ["bench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert all(k in data for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert data.get("fields_per_snapshot", 1) == 1  # a call writes one field
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        assert (ROOT / "bench" / "reference" / f"{data['codec']}.py").is_file()
        assert set(data["limits"]) and all(v >= 0 for v in data["limits"].values())
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert callable(metric_module(m["name"]).read)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    names = ([c["name"] for c in b["configs"]] + CELLS + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(CELLS)) == len(CELLS) and len(set(configs)) == len(b["configs"])
    texts = ([w["why"] for w in b["workloads"]] + [c["why"] for c in b["configs"]]
             + [c["source"] for c in b["configs"]] + [m["layer"] for m in b["per_layer"]])
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t for t in texts)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    spec = run.cell_spec(cell)
    names = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and spec.per_layer


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------


def synthetic_trace():
    E = tr.Event
    t = tr.Trace()
    t.host = [E("window.compress", 0.0, 10.0), E("compress", 0.0, 6.0),
              E("to_bytes", 6.0, 10.0), E("window.decompress", 10.0, 20.0),
              E("from_bytes", 10.0, 12.0), E("decompress", 12.0, 20.0)]
    t.devices = {
        "/device:TPU:0": [
            E("%fusion.1 = f32[64]{0} fusion(f32[4,4,4]{2,1,0} %p), kind=kLoop", 1.0, 3.0),
            E('%compress_blocks.1 = (u32[8,32]{1,0}) custom-call(f32[64,8]{1,0} %f), '
              'custom_call_target="tpu_custom_call"', 2.0, 4.0),
            E('%decode_chunks.2 = s32[8192]{0} custom-call(s32[2]{0} %o), '
              'custom_call_target="tpu_custom_call"', 13.0, 19.0)],
        "/device:TPU:1": [
            E("%fusion.1 = f32[64]{0} fusion(f32[4,4,4]{2,1,0} %p), kind=kLoop", 1.0, 2.0),
            E('%decompress_blocks.1 = f32[64,8]{1,0} custom-call(u32[8,32]{1,0} %w), '
              'custom_call_target="tpu_custom_call"', 14.0, 16.0)],
    }
    return t


def test_busy_time_is_the_union_of_operations():
    t = synthetic_trace()
    assert tr.union(t.devices["/device:TPU:0"], 0.0, 10.0) == [(1.0, 4.0)]
    assert tr.busy_seconds(t.devices["/device:TPU:0"], 0.0, 20.0) == 9.0
    assert tr.busy_seconds(t.devices["/device:TPU:0"], 2.5, 14.0) == 2.5


def test_idle_share_per_chip_and_phase():
    t = synthetic_trace()
    assert tr.idle_shares(t, "compress") == {"/device:TPU:0": 70.0, "/device:TPU:1": 90.0}
    assert tr.idle_shares(t, "decompress") == {"/device:TPU:0": 40.0, "/device:TPU:1": 80.0}
    assert tr.idle_shares(tr.Trace(), "compress") is None


def test_kernel_time_by_name_and_detail():
    t = synthetic_trace()
    zfp = metric_module("zfp_block_compress_roofline").PATTERN
    assert tr.kernel_seconds(t, zfp, "compress") == 2.0
    assert tr.kernel_seconds(t, zfp, "decompress") == 0.0
    assert tr.kernel_seconds(t, r"^%decode_chunks", "decompress") == 6.0
    assert tr.kernel_seconds(t, r"^%decompress_blocks", "decompress") == 2.0


def test_idle_gaps_are_named_by_the_open_call():
    t = synthetic_trace()
    gaps = tr.idle_gaps(t, 0.0, 20.0)
    # busy on some chip: [1, 4] and [13, 19]; the 9 s gap's midpoint is in to_bytes
    assert gaps[0] == ["to_bytes", 9.0]
    assert sorted(g[0] for g in gaps[1:]) == ["compress", "decompress"]
    assert math.isclose(sum(g[1] for g in gaps), 20.0 - 3.0 - 6.0)
    assert tr.top_ops(t, 0.0, 20.0)[0] == ["%decode_chunks.2 = s32[8192]{0} custom-call", 6.0]
    assert tr.top_ops(t, 0.0, 20.0)[1] == ["%fusion.1 = f32[64]{0} fusion", 3.0]


def test_recorded_trace(tmp_path):
    """A trace recorded on one TPU v5e; ``tpu_small.json`` says what ran and
    holds readings worked out from the raw events without this module."""
    import gzip

    data = ROOT / "bench" / "testdata"
    meta = json.loads((data / "tpu_small.json").read_text())
    path = tmp_path / "tpu_small.xplane.pb"
    path.write_bytes(gzip.decompress((data / "tpu_small.xplane.pb.gz").read_bytes()))
    t = tr.load(path)
    assert sorted(t.devices) == meta["devices"]
    assert {e.name for e in t.host} == set(meta["annotations"])
    for phase, share in meta["idle_percent"].items():
        got = tr.idle_shares(t, phase)
        assert math.isclose(got[meta["devices"][0]], share, rel_tol=1e-9)
    for phase, kernels in meta["kernel_seconds"].items():
        for pattern, seconds in kernels.items():
            assert math.isclose(tr.kernel_seconds(t, pattern, phase), seconds, rel_tol=1e-9)
    zfp = metric_module("zfp_block_compress_roofline").PATTERN
    assert meta["kernel_seconds"]["compress"][zfp] > 0


# --------------------------------------------------------------------------
# roofline byte counts
# --------------------------------------------------------------------------


def test_zfp_compress_bytes_by_hand():
    m = metric_module("zfp_block_compress_roofline")
    arrays = {"payload": np.zeros((5, 32), np.uint32), "emax": np.zeros(5, np.int32)}
    # 5 blocks: 64 float32 in, 32 payload words and one exponent out
    assert m.bytes_moved({}, arrays) == 5 * 64 * 4 + 5 * 32 * 4 + 5 * 4


# --------------------------------------------------------------------------
# traffic loops and whole runs, tiny and in interpret mode
# --------------------------------------------------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_loop_round_trips(cell):
    spec = tiny_spec(cell)
    caller = loop.Caller(spec.config, spec.traffic, devices_for(spec), seed=2**33 + 5)
    try:
        blobs = caller.write()
        outs = caller.read(blobs)
    finally:
        caller.close()
    split = spec.traffic["split"]
    assert set(blobs) == set(outs) == set(caller.fields) and len(blobs) == math.prod(split)
    assert caller.raw_bytes == 4 * math.prod(spec.config["shape"])
    for k, x in caller.fields.items():
        assert outs[k].shape == x.shape and all(b[:4] == b"HPDR" for b in blobs.values())


def test_seeds_above_32_bits_give_other_fields():
    import jax

    spec = tiny_spec("nyx512-zfp.snapshot")
    f = [loop.fields.subdomains(s, spec.config["shape"], [1, 1, 1], spec.config["field"],
                                jax.devices()[:1])["d0"] for s in (5, 5 + 2**32, 5)]
    assert not np.array_equal(f[0], f[1]) and np.array_equal(f[0], f[2])


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_whole_run_is_correct(cell, traced):
    spec = tiny_spec(cell)
    result = run.run_cell(spec, devices_for(spec), 2**33 + 11, 1.0, bool(traced),
                          time.perf_counter())
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks" and result["failed"] == 0
    wanted = spec.per_layer if traced else spec.end_to_end
    assert set(result["metrics"]) <= {m["name"] for m in wanted}
    if not traced:
        assert set(result["metrics"]) == {m["name"] for m in wanted}
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result, allow_nan=False)


def test_traced_run_reads_every_layer_metric(monkeypatch):
    """The traced path end to end, on a trace in which the chip's kernels ran."""
    spec = tiny_spec("nyx512-zfp.snapshot")
    kind = devices_for(spec)[0].device_kind
    t = synthetic_trace()
    monkeypatch.setattr(tr, "load", lambda path: t)
    monkeypatch.setattr(run, "peak", lambda device_kind, key: {
        "hbm_bytes_per_s": 819e9}[key] if device_kind == kind else None)
    result = run.run_cell(spec, devices_for(spec), 7, 1.0, True, time.perf_counter())
    assert set(result["metrics"]) == {m["name"] for m in spec.per_layer}
    assert result["metrics"]["idle.compress"]["value"] == (70.0 + 90.0) / 2
    assert 0 < result["metrics"]["zfp_block_compress_roofline"]["value"] < 100
    assert result["device"]["busy_s"] == (9.0 + 3.0) / 2 and result["device"]["window_s"] == 20.0
    assert result["breakdown"]["idle_gaps"][0] == ["to_bytes", 9.0]


@pytest.mark.parametrize("compiled,missed,calls", [
    ([3, 0, 0], [3, 0, 0], 2),  # the second call compiles nothing
    ([4, 4, 4], [4, 0, 0], 2),  # the engine: every call loads its programs from the cache
    ([3, 1, 0], [3, 1, 0], 3),  # the second call compiles a new signature
    ([3, 1, 1], [3, 1, 1], 3),  # never more than ``most`` calls
])
def test_warm_up_stops_once_a_call_compiles_nothing_anew(compiled, missed, calls):
    counter = run.SimpleNamespace(counts={"setup": 0}, misses=0)
    made = []

    def call():
        i = len(made)
        counter.counts["setup"] += compiled[i]
        counter.misses += missed[i]
        made.append(i)
        return i

    assert run.warm_up(call, counter) == calls - 1 and len(made) == calls


def test_peaks_are_keyed_by_device_kind():
    assert run.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(SystemExit):
        run.peak("TPU v9 imagined", "hbm_bytes_per_s")


def _bump(x):
    import jax.numpy as jnp

    x = jnp.asarray(x)
    span = float(jnp.max(x) - jnp.min(x))
    return x.reshape(-1).at[x.size // 2].add(1e-3 * span).reshape(x.shape)


def _half(x):
    import jax.numpy as jnp

    x = jnp.asarray(x)
    flat = x.reshape(-1)
    return flat.at[flat.size // 2:].set(0).reshape(x.shape)


def plant(monkeypatch, fault: str):
    """Break the program underneath the harness."""
    from repro.core import api
    from repro.core.engine import ExecutionEngine

    compress, decompress = api.compress, api.decompress
    engine_compress, engine_decompress = (ExecutionEngine.compress_pytree,
                                          ExecutionEngine.decompress_pytree)
    seen = {}

    def api_compress(x, *a, **k):
        c = compress(x, *a, **k)
        seen["x"] = x
        if fault == "stream_altered":  # the first code word: a coarsest node's key
            name = "payload" if "payload" in c.arrays else "words"
            arr = np.array(c.arrays[name])
            arr.reshape(-1)[0] ^= np.uint32(1 << 31)
            c.arrays[name] = arr
        return c

    def api_decompress(c):
        out = decompress(c)
        return {"answer_altered": _bump, "half_left_out": _half,
                "input_returned": lambda y: seen["x"]}.get(fault, lambda y: y)(out)

    def pytree_compress(self, tree, *a, **k):
        comp, stats = engine_compress(self, tree, *a, **k)
        if fault == "exchange_left_out":  # every chip's result is chip 0's
            first = comp[next(iter(comp))]
            comp = {key: first for key in comp}
        return comp, stats

    def pytree_decompress(self, comp, like, *a, **k):
        out = engine_decompress(self, comp, like, *a, **k)
        keys = list(out)
        if fault == "half_left_out":
            return {key: out[key] for key in keys[: len(keys) // 2]}
        if fault == "answer_altered":
            out[keys[0]] = _bump(out[keys[0]])
        return out

    monkeypatch.setattr(api, "compress", api_compress)
    monkeypatch.setattr(api, "decompress", api_decompress)
    monkeypatch.setattr(ExecutionEngine, "compress_pytree", pytree_compress)
    monkeypatch.setattr(ExecutionEngine, "decompress_pytree", pytree_decompress)


FAULTS = [(c, f) for c in CELLS for f in ("answer_altered", "half_left_out", "input_returned",
                                          "stream_altered", "exchange_left_out")
          if (f == "exchange_left_out") == ("subdomains" in c)
          and not ("subdomains" in c and f in ("input_returned", "stream_altered"))]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(monkeypatch, cell, fault):
    spec = tiny_spec(cell)
    devices = devices_for(spec)
    plant(monkeypatch, fault)
    result = run.run_cell(spec, devices, 2**33 + 12, 1.0, False, time.perf_counter())
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell):
    spec = tiny_spec(cell)
    for seed in (3, 2**33 + 3):
        r = control.readings(spec, devices_for(spec), seed)
        assert r["program_passes"] and r["control_fails"], r


# --------------------------------------------------------------------------
# the command itself
# --------------------------------------------------------------------------


def _command(cwd: Path, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_exits_non_zero_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
