"""Execution engine: plan-bound backends, sharded fan-out, async submission.

Covers the three engine contracts:
  * backend parity — every runnable adapter produces bit-identical
    encode/decode for every registered codec (and the six kernel ops agree
    pairwise across adapters);
  * sharded fan-out — ``compress_pytree`` buckets leaves by post-policy
    spec, builds one plan per bucket (CMM miss counters), and schedules
    buckets over the mesh "data" axis (a ≥2-device CPU mesh is exercised in
    a subprocess with ``--xla_force_host_platform_device_count``, since the
    in-process device count is fixed at backend init);
  * async submission — submit()/result() futures, the checkpoint manager's
    io-lane save, and serving-side background KV parking.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import api
from repro.core.adapters import available_backends, resolve_backend, supports_donation
from repro.core.context import GLOBAL_CMM
from repro.core.engine import ExecutionEngine, data_devices, make_data_mesh
from conftest import smooth_field_3d

ALL_METHODS = [
    ("mgard", {"error_bound": 1e-2}),
    ("zfp", {"rate": 12}),
    ("huffman", {}),
    ("huffman-bytes", {}),
]


def _data_for(method, rng):
    if method == "huffman":
        return np.minimum(np.abs(rng.normal(0, 10, 8192)).astype(np.int32), 255)
    return smooth_field_3d(24)


# ---------------------------------------------------------------------------
# backend resolution + plan binding
# ---------------------------------------------------------------------------


def test_backend_resolution():
    assert resolve_backend(None) == resolve_backend("auto")
    assert resolve_backend("auto") in available_backends()
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda-graphs")
    if "pallas" not in available_backends():  # CPU container
        with pytest.raises(ValueError, match="not runnable"):
            resolve_backend("pallas")


def test_spec_backend_is_plan_bound():
    f = smooth_field_3d(16)
    spec = api.make_spec(f, "zfp", rate=9, backend="pallas_interpret")
    assert spec.backend == "pallas_interpret"  # resolved at spec build
    plan = api.get_plan(spec)
    assert plan.spec.backend == "pallas_interpret"
    # auto and the explicit platform default share one CMM entry
    auto = api.make_spec(f, "zfp", rate=9)
    explicit = api.make_spec(f, "zfp", rate=9, backend=resolve_backend("auto"))
    assert auto.key() == explicit.key()
    # ...but a different backend is a different plan
    assert spec.key() != auto.key() or resolve_backend("auto") == "pallas_interpret"


@pytest.mark.parametrize("method,kw", ALL_METHODS)
def test_backend_parity_all_codecs(method, kw, rng):
    """xla and pallas_interpret produce bit-identical streams and decodes."""
    data = _data_for(method, rng)
    streams, decoded = {}, {}
    for backend in ("xla", "pallas_interpret"):
        c = api.compress(jnp.asarray(data), method, backend=backend, **kw)
        streams[backend] = c.to_bytes()
        decoded[backend] = np.asarray(api.decode(c, backend=backend))
    assert streams["xla"] == streams["pallas_interpret"]
    np.testing.assert_array_equal(decoded["xla"], decoded["pallas_interpret"])


def test_cross_backend_decode_portability(rng):
    """A stream written under one backend decodes under any other."""
    f = smooth_field_3d(16)
    c = api.compress(jnp.asarray(f), "mgard", backend="pallas_interpret")
    c2 = api.Compressed.from_bytes(c.to_bytes())
    np.testing.assert_array_equal(
        np.asarray(api.decode(c2, backend="xla")),
        np.asarray(api.decode(c, backend="pallas_interpret")),
    )


def test_kernel_ops_adapter_parity(rng):
    """All six kernel ops agree across registered adapters (bitstream ops
    bit-identically; the float tridiag solver to accumulation tolerance)."""
    from repro.kernels.histogram import ops as hist_ops
    from repro.kernels.huffman_encode import ops as enc_ops
    from repro.kernels.mgard_lerp import ops as lerp_ops
    from repro.kernels.quantize_map import ops as quant_ops
    from repro.kernels.tridiag import ops as tri_ops
    from repro.kernels.zfp_block import ops as zfp_ops

    a, b = "xla", "pallas_interpret"
    blocks = rng.normal(size=(40, 64)).astype(np.float32).T  # (4^3, N)
    for enc, dec in ((a, b), (b, a)):
        p, e = zfp_ops.compress_blocks(jnp.asarray(blocks), 12, 3, adapter=enc)
        p2, e2 = zfp_ops.compress_blocks(jnp.asarray(blocks), 12, 3, adapter=dec)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p2))
        np.testing.assert_array_equal(
            np.asarray(zfp_ops.decompress_blocks(p, e, 12, 3, adapter=dec)),
            np.asarray(zfp_ops.decompress_blocks(p2, e2, 12, 3, adapter=enc)),
        )
    keys = rng.integers(0, 500, 20000).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(hist_ops.histogram(jnp.asarray(keys), 512, adapter=a)),
        np.asarray(hist_ops.histogram(jnp.asarray(keys), 512, adapter=b)),
    )
    codes = rng.integers(0, 2**16, 512).astype(np.uint32)
    lens = rng.integers(1, 17, 512).astype(np.int32)
    ca, la = enc_ops.encode_lookup(jnp.asarray(keys), jnp.asarray(codes),
                                   jnp.asarray(lens), adapter=a)
    cb, lb = enc_ops.encode_lookup(jnp.asarray(keys), jnp.asarray(codes),
                                   jnp.asarray(lens), adapter=b)
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    x = rng.normal(size=9000).astype(np.float32)
    lv = rng.integers(0, 5, 9000).astype(np.int32)
    bins = (10.0 ** -rng.uniform(2, 4, 5)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(quant_ops.quantize(jnp.asarray(x), jnp.asarray(lv),
                                      jnp.asarray(bins), adapter=a)),
        np.asarray(quant_ops.quantize(jnp.asarray(x), jnp.asarray(lv),
                                      jnp.asarray(bins), adapter=b)),
    )
    rows = rng.normal(size=(7, 33)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(lerp_ops.lerp_coefficients(jnp.asarray(rows), adapter=a)),
        np.asarray(lerp_ops.lerp_coefficients(jnp.asarray(rows), adapter=b)),
    )
    np.testing.assert_allclose(
        np.asarray(tri_ops.solve_mass(jnp.asarray(rows), 2.0, adapter=a)),
        np.asarray(tri_ops.solve_mass(jnp.asarray(rows), 2.0, adapter=b)),
        rtol=3e-5, atol=3e-6,
    )


# ---------------------------------------------------------------------------
# workspace donation
# ---------------------------------------------------------------------------


def test_mgard_workspace_recycled_through_donation_path():
    """The planned quantize/dequantize executables return the (donated)
    level map and the codec re-stores it — true in-place recycling where
    the platform implements donation, a pass-through elsewhere."""
    f = smooth_field_3d(16)
    spec = api.make_spec(f, "mgard", error_bound=1e-2, dict_size=1024)
    plan = api.get_plan(spec)
    lmap_before = np.asarray(plan.workspace["lmap"]).copy()
    c1 = api.encode(spec, jnp.asarray(f))
    c2 = api.encode(spec, jnp.asarray(f))
    assert c1.to_bytes() == c2.to_bytes()  # recycling never corrupts results
    assert "lmap" in plan.workspace
    np.testing.assert_array_equal(np.asarray(plan.workspace["lmap"]), lmap_before)
    out = np.asarray(api.decode(c2))
    vr = f.max() - f.min()
    assert np.abs(out - f).max() <= 2e-2 * vr
    # donation is a platform capability, not a hard requirement
    assert isinstance(supports_donation(), bool)


# ---------------------------------------------------------------------------
# engine fan-out (current device count; ≥2-device mesh below via subprocess)
# ---------------------------------------------------------------------------


def test_engine_bucketed_pytree_single_plan_per_bucket(rng):
    tree = {
        "a": rng.normal(size=(64, 128)).astype(np.float32),
        "b": rng.normal(size=(128, 64)).astype(np.float32),   # same blocked shape
        "c": rng.normal(size=(64, 128)).astype(np.float32),
        "ids": np.arange(32, dtype=np.int32),                 # raw passthrough
    }
    eng = ExecutionEngine()
    h0, m0 = GLOBAL_CMM.hit_count, GLOBAL_CMM.miss_count
    GLOBAL_CMM.clear()
    comp, stats = eng.compress_pytree(tree, select=lambda k, a: (
        ("zfp", {"rate": 8}) if a.dtype.kind == "f" else None))
    hits = GLOBAL_CMM.hit_count - h0
    misses = GLOBAL_CMM.miss_count - m0
    assert stats["leaves"] == 4 and stats["compressed_leaves"] == 3
    assert stats["buckets"] == 1          # all three flatten to (8, 32, 32)
    assert stats["sharded_leaves"] == 3   # zfp leaves ran the shard_map path
    assert misses == 1                    # one plan build per bucket
    assert hits >= 2                      # every other leaf a real CMM hit
    out = eng.decompress_pytree(comp, tree)
    for k in tree:
        a, b = np.asarray(out[k]), np.asarray(tree[k])
        assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_array_equal(np.asarray(out["ids"]), tree["ids"])
    eng.close()


def test_engine_matches_serial_leaf_compression(rng):
    """Engine fan-out is bit-identical to the serial compress_leaf path."""
    tree = {f"w{i}": rng.normal(size=(48, 64)).astype(np.float32) for i in range(4)}
    eng = ExecutionEngine(backend="xla")
    comp, _ = eng.compress_pytree(tree, select=lambda k, a: ("zfp", {"rate": 10}))
    for key, arr in tree.items():
        serial = api.compress_leaf(arr, "zfp", rate=10, backend="xla")
        assert comp[key].to_bytes() == serial.to_bytes()
    eng.close()


def test_engine_select_params_may_carry_backend(rng):
    """A per-leaf ``backend`` in the select policy overrides the engine's."""
    tree = {"w": rng.normal(size=(64, 128)).astype(np.float32)}
    comp, _ = api.compress_pytree(
        tree, select=lambda k, a: ("zfp", {"rate": 8, "backend": "pallas_interpret"})
    )
    serial = api.compress_leaf(tree["w"], "zfp", rate=8, backend="pallas_interpret")
    assert comp["w"].to_bytes() == serial.to_bytes()


def test_engine_mixed_methods_futures_path(rng):
    tree = {
        "f": smooth_field_3d(24),
        "g": smooth_field_3d(24, noise=0.1, seed=1),
        "k": np.arange(8192, dtype=np.int32),
    }
    eng = ExecutionEngine()

    def select(key, arr):
        if arr.dtype.kind == "f":
            return "mgard", {"error_bound": 1e-2}
        return "huffman-bytes", {}

    comp, stats = eng.compress_pytree(tree, select=select)
    assert stats["compressed_leaves"] == 3
    assert stats["buckets"] == 2          # mgard bucket + huffman-bytes bucket
    out = eng.decompress_pytree(comp, tree)
    np.testing.assert_array_equal(np.asarray(out["k"]), tree["k"])
    for k in ("f", "g"):
        vr = tree[k].max() - tree[k].min()
        assert np.abs(np.asarray(out[k]) - tree[k]).max() <= 2e-2 * vr
    eng.close()


def test_engine_submit_result_futures(rng):
    eng = ExecutionEngine()
    f = smooth_field_3d(16)
    spec = eng.make_spec(f, "zfp", rate=8)
    subs = [eng.submit_encode(spec, f) for _ in range(4)]
    blobs = {eng.result(s).to_bytes() for s in subs}
    assert len(blobs) == 1  # all futures agree
    c = subs[0].result()
    dec = eng.submit_decode(c)
    assert np.asarray(dec.result()).shape == f.shape
    assert eng.stats()["submitted"] >= 5
    eng.close()


@pytest.mark.subprocess
def test_engine_fanout_multidevice_subprocess(tmp_path):
    """Acceptance: on a ≥2-device mesh, compress_pytree shards leaves over
    the data axis with one plan build per bucket (CMM counters).

    The in-process device count is locked at backend init, so the multi-
    device CPU mesh runs in a subprocess with
    ``--xla_force_host_platform_device_count=4``.
    """
    if jax.device_count() >= 2:
        pytest.skip("in-process mesh already multi-device; covered inline")
    script = textwrap.dedent("""
        import json
        import numpy as np
        import jax
        from repro.core import api
        from repro.core.context import GLOBAL_CMM
        from repro.core.engine import ExecutionEngine

        rng = np.random.default_rng(0)
        tree = {f"w{i}": rng.normal(size=(64, 128)).astype(np.float32)
                for i in range(8)}
        eng = ExecutionEngine()
        GLOBAL_CMM.clear()
        h0, m0 = GLOBAL_CMM.hit_count, GLOBAL_CMM.miss_count
        comp, stats = eng.compress_pytree(
            tree, select=lambda k, a: ("zfp", {"rate": 8}))
        out = eng.decompress_pytree(comp, tree)
        exact = all(np.asarray(out[k]).shape == tree[k].shape for k in tree)
        print(json.dumps({
            "devices": jax.device_count(),
            "engine_devices": len(eng.devices),
            "buckets": stats["buckets"],
            "sharded_leaves": stats["sharded_leaves"],
            "hits": GLOBAL_CMM.hit_count - h0,
            "misses": GLOBAL_CMM.miss_count - m0,
            "shapes_ok": exact,
        }))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = (
        str(Path(__file__).resolve().parent.parent / "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=480,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["devices"] >= 2
    assert report["engine_devices"] >= 2
    assert report["buckets"] == 1
    assert report["sharded_leaves"] == 8      # all leaves over the data axis
    assert report["misses"] == 1              # one plan build per bucket
    assert report["hits"] >= 7                # shards are real CMM hits
    assert report["shapes_ok"]


def test_data_devices_and_mesh_helpers():
    mesh = make_data_mesh()
    assert mesh.axis_names == ("data",)
    assert len(data_devices(mesh)) == len(jax.devices())
    from repro.launch.mesh import data_axis_size, make_data_mesh as launch_mesh

    m2 = launch_mesh()
    assert data_axis_size(m2) == len(jax.devices())


# ---------------------------------------------------------------------------
# async orchestration (checkpoint io lane, serving KV parking)
# ---------------------------------------------------------------------------


def test_checkpoint_save_async_runs_on_engine(tmp_path, rng):
    from repro.checkpoint import CheckpointManager, CheckpointPolicy

    mgr = CheckpointManager(tmp_path, CheckpointPolicy(exact=True))
    tree = {"w": rng.normal(size=(64, 64)).astype(np.float32)}
    sub = mgr.save_async(11, tree)
    assert sub.lane == "io"
    manifest = mgr.wait()
    assert manifest["step"] == 11
    assert mgr.latest_step() == 11
    out, _ = mgr.restore(11, target=tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


def test_checkpoint_colliding_leaf_keys_get_distinct_files(tmp_path, rng):
    """Keys that sanitize to the same segment name must not share one."""
    from repro.checkpoint import CheckpointManager, CheckpointPolicy

    tree = {
        "a/b": rng.normal(size=(16, 16)).astype(np.float32),
        "a_b": rng.normal(size=(16, 16)).astype(np.float32),
    }
    mgr = CheckpointManager(tmp_path, CheckpointPolicy(exact=True))
    manifest = mgr.save(1, tree)
    files = [info["segment"] for info in manifest["leaves"].values()]
    assert len(files) == len(set(files))
    out, _ = mgr.restore(1, target=tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]), tree[k])


def test_park_kv_cache_async(rng):
    from repro.serving.engine import decompress_kv_cache, park_kv_cache_async

    cache = {
        "k": rng.normal(size=(2, 4, 64, 8, 16)).astype(np.float32),
        "v": rng.normal(size=(2, 4, 64, 8, 16)).astype(np.float32),
        "pos": np.arange(4, dtype=np.int32),
    }
    sub = park_kv_cache_async(cache, rate=16)
    comp, stats = sub.result()
    assert stats["compressed_leaves"] == 2
    restored = decompress_kv_cache(comp, cache)
    np.testing.assert_array_equal(np.asarray(restored["pos"]), cache["pos"])
    for k in ("k", "v"):
        err = np.abs(np.asarray(restored[k]) - cache[k]).max()
        assert err < 1e-2 * np.abs(cache[k]).max()


# ---------------------------------------------------------------------------
# lazy chunked-stream fetch
# ---------------------------------------------------------------------------


def test_stream_from_bytes_is_lazy():
    data = smooth_field_3d(32)
    stream = api.CompressorStream("zfp", mode="fixed",
                                  c_fixed_elems=8 * 32 * 32, rate=16)
    res = stream.compress(data)
    assert len(res.chunks) > 2
    blob = api.CompressorStream.to_bytes(res)

    res2 = api.CompressorStream.from_bytes(blob)
    assert isinstance(res2.chunks, api.LazyChunks)
    assert res2.chunks.materialized == 0     # nothing parsed yet
    first = res2.chunks[0]                   # progressive prefix fetch
    assert res2.chunks.materialized == 1
    np.testing.assert_array_equal(
        np.asarray(api.decompress(first)), np.asarray(api.decompress(res.chunks[0]))
    )
    # full decompress touches (and caches) every chunk exactly once
    out = stream.decompress(res2)
    assert res2.chunks.materialized == len(res2.chunks)
    np.testing.assert_array_equal(out, stream.decompress(res))
    # eager mode still available
    res3 = api.CompressorStream.from_bytes(blob, lazy=False)
    assert isinstance(res3.chunks, list)


def test_stream_lazy_bounds_validated_eagerly():
    data = smooth_field_3d(32)
    stream = api.CompressorStream("zfp", mode="fixed",
                                  c_fixed_elems=8 * 32 * 32, rate=16)
    blob = api.CompressorStream.to_bytes(stream.compress(data))
    with pytest.raises(ValueError, match="truncated"):
        api.CompressorStream.from_bytes(blob[: len(blob) - 7])


# ---------------------------------------------------------------------------
# executor lifecycle: idempotent shutdown, drain, lane metrics, chaining
# ---------------------------------------------------------------------------


def test_executor_shutdown_idempotent_and_submit_after_close():
    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    assert ex.submit(lambda: 41 + 1).result() == 42
    ex.shutdown()
    assert ex.closed
    ex.shutdown()  # second shutdown: no-op, no hang, no error
    ex.shutdown(wait=False)
    with pytest.raises(RuntimeError, match="shut down"):
        ex.submit(lambda: 0)
    with pytest.raises(RuntimeError, match="shut down"):
        ex.submit(lambda: 0, lane="io")


def test_executor_shutdown_safe_under_concurrent_submit():
    import threading

    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    stop = threading.Event()
    outcomes = {"ok": 0, "refused": 0, "other": []}

    def spammer():
        while not stop.is_set():
            try:
                ex.submit(lambda: 1).result()
                outcomes["ok"] += 1
            except RuntimeError as e:
                if "shut down" in str(e):
                    outcomes["refused"] += 1
                    return
                outcomes["other"].append(e)  # pragma: no cover
                return

    threads = [threading.Thread(target=spammer) for _ in range(4)]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.05)
    ex.shutdown()  # races against in-flight submits
    stop.set()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    # every spammer either succeeded or got the clear refusal — nothing hung
    assert not outcomes["other"]
    st = ex.lane_stats()
    total = sum(v["submitted"] for v in st.values())
    assert total == sum(v["completed"] for v in st.values())


def test_executor_drain_and_lane_stats():
    import threading
    import time as _time

    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    gate = threading.Event()
    subs = [ex.submit(gate.wait, 30) for _ in range(3)]
    subs.append(ex.submit(gate.wait, 30, lane="io"))
    assert not ex.drain(timeout=0.1)  # gated work: drain times out False
    st = ex.lane_stats()
    assert st["compute"]["submitted"] == 3 and st["io"]["submitted"] == 1
    assert st["compute"]["depth"] + st["compute"]["inflight"] > 0
    gate.set()
    assert ex.drain(timeout=30)  # all lanes idle
    for s in subs:
        s.result()
    st = ex.lane_stats()
    for lane in ("compute", "io"):
        assert st[lane]["completed"] == st[lane]["submitted"]
        assert st[lane]["depth"] == 0 and st[lane]["inflight"] == 0
        assert st[lane]["wait_s"] >= 0.0
    t0 = _time.monotonic()
    assert ex.drain(timeout=5)  # idle drain returns immediately
    assert _time.monotonic() - t0 < 1.0
    ex.shutdown()


def test_executor_submit_after_propagates_upstream_failure():
    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())

    def boom():
        raise ValueError("upstream boom")

    first = ex.submit(boom)
    chained = ex.submit_after(first, lambda r: r + 1)
    with pytest.raises(ValueError, match="upstream boom"):
        chained.result(timeout=30)
    # a healthy chain on the same executor still works afterwards
    ok = ex.submit_after(ex.submit(lambda: 2), lambda r: r + 3)
    assert ok.result(timeout=30) == 5
    ex.shutdown()


def test_executor_done_callback_fires_with_submission():
    import threading

    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    seen = []
    done = threading.Event()
    sub = ex.submit(lambda: "payload")

    def cb(s):
        seen.append(s.result())
        done.set()

    sub.add_done_callback(cb)
    assert done.wait(30)
    assert seen == ["payload"]
    ex.shutdown()


def test_executor_drain_waits_for_completion_callbacks():
    """Regression: ``drain()`` returned once a *task* finished, before its
    done-callbacks ran — a callback chaining io-lane work (the serving /
    checkpoint pattern) could still be submitting after a "successful"
    drain, and shutdown would strand it.  Drain must not return between a
    submission completing and its completion callbacks finishing."""
    import threading
    import time as _time

    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    rounds = 25
    for _ in range(rounds):
        gate = threading.Event()
        hits = []
        first = ex.submit(gate.wait, 30)
        # continuation rides the io lane, submitted from first's callback
        chained = ex.submit_after(
            first, lambda _r: (_time.sleep(0.002), hits.append("io"))[-1],
            lane="io",
        )
        gate.set()
        assert ex.drain(timeout=30)
        # a successful drain means the chained io work already RAN
        assert hits == ["io"]
        assert chained.done()
    st = ex.lane_stats()
    assert st["io"]["submitted"] == rounds
    assert st["io"]["completed"] == rounds
    assert st["compute"]["completed"] == st["compute"]["submitted"]
    # plain done-callbacks too: drain covers them, not just chains
    flags = []
    sub = ex.submit(lambda: 41 + 1)
    sub.add_done_callback(lambda s: (_time.sleep(0.01), flags.append(s.result())))
    assert ex.drain(timeout=30)
    assert flags == [42]
    ex.shutdown()


def test_executor_priority_stats_tagged_lanes():
    """`submit(..., priority=)` feeds per-class counters independent of the
    physical lane — the serving layer's interactive/bulk split."""
    import threading

    from repro.runtime.executor import DeviceExecutor

    ex = DeviceExecutor(jax.devices())
    gate = threading.Event()
    subs = [ex.submit(gate.wait, 30, priority="bulk") for _ in range(3)]
    subs.append(ex.submit(gate.wait, 30, lane="io", priority="interactive"))
    ex.submit(lambda: 0).result()  # untagged: must not appear below
    st = ex.priority_stats()
    assert st["bulk"]["submitted"] == 3
    assert st["interactive"]["submitted"] == 1
    assert set(st) == {"bulk", "interactive"}
    gate.set()
    assert ex.drain(timeout=30)
    st = ex.priority_stats()
    for cls in ("bulk", "interactive"):
        assert st[cls]["completed"] == st[cls]["submitted"]
        assert st[cls]["depth"] == 0 and st[cls]["inflight"] == 0
        assert st[cls]["wait_s"] >= 0.0
    ex.shutdown()
