"""The program's spans on the profiler's trace, and the byte counts beside them.

Spans (``repro.runtime.spans``) are ``jax.profiler`` annotations named
``hpdr.*``; each carries the ``call`` id of the entry point that caused
it. These tests take a CPU trace and read the spans back.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.codecs import get_codec
from repro.core.container import Compressed
from repro.core.engine import ExecutionEngine, make_data_mesh
from repro.core.stages import CallEnv
from repro.runtime import spans
from repro.runtime.executor import DeviceExecutor
from conftest import smooth_field_3d

ZFP_PATH = {
    "hpdr.compress": ("hpdr.segment", "hpdr.d2h"),
    "hpdr.to_bytes": ("hpdr.to_bytes.copy", "hpdr.to_bytes.crc32"),
    "hpdr.from_bytes": ("hpdr.from_bytes.copy", "hpdr.from_bytes.crc32",
                        "hpdr.from_bytes.parse"),
    "hpdr.decompress": ("hpdr.h2d", "hpdr.segment"),
}


def _inside(child, parent) -> bool:
    return (child.thread == parent.thread
            and parent.start <= child.start and child.end <= parent.end)


def _round_trip(x):
    c = api.compress(x, "zfp", rate=16)
    raw = c.to_bytes()
    c2 = Compressed.from_bytes(raw)
    return c, raw, api.decompress(c2)


def test_zfp_round_trip_spans_nest_under_their_roots(trace_spans):
    x = jnp.asarray(smooth_field_3d(16))
    _round_trip(x)  # warm: the traced calls build no plan
    (c, raw, out), found = trace_spans(lambda: _round_trip(x))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(api.decompress(c)))

    roots = {name: [s for s in found if s.name == name] for name in ZFP_PATH}
    assert all(len(r) == 1 for r in roots.values()), roots
    calls = set()
    for name, children in ZFP_PATH.items():
        (parent,) = roots[name]
        inner = [s for s in found if s is not parent and _inside(s, parent)]
        assert {s.name for s in inner} >= set(children), name
        if name in ("hpdr.compress", "hpdr.decompress"):
            # one call id for the root and everything it caused
            assert {s.stats["call"] for s in inner} == {parent.stats["call"]}
            calls.add(parent.stats["call"])
            assert parent.stats["raw_bytes"] == x.nbytes
            assert parent.stats["method"] == "zfp"
    assert len(calls) == 2  # each entry-point call takes its own id

    payload = c.nbytes()
    crc = [s for s in found if s.name == "hpdr.to_bytes.crc32"]
    assert crc and all(s.stats["bytes"] == payload for s in crc)
    d2h = [s for s in found if s.name == "hpdr.d2h"]
    assert sum(s.stats["bytes"] for s in d2h) == payload
    # the field was on the device already: the only upload is the stream's
    h2d = [s for s in found if s.name == "hpdr.h2d"]
    assert sum(s.stats["bytes"] for s in h2d) == payload
    assert {s.stats["segment"] for s in found if s.name == "hpdr.segment"} == {
        "zfp_block_transform", "invert[zfp_block_transform]"}


def test_plan_build_span_labels_a_cache_miss(trace_spans):
    x = jnp.asarray(smooth_field_3d(8)) * 3.0  # a shape no other test plans
    x = x.reshape(8, 8, 8)[:, :, :7]
    _c, found = trace_spans(lambda: api.compress(x, "zfp", rate=12))
    builds = [s for s in found if s.name == "hpdr.plan.build"]
    (root,) = [s for s in found if s.name == "hpdr.compress"]
    assert builds and all(_inside(b, root) for b in builds)
    assert builds[0].stats == {"method": "zfp", "call": root.stats["call"]}


def test_engine_task_spans_carry_the_callers_call(trace_spans):
    rng = np.random.default_rng(3)
    tree = {f"w{i}": rng.normal(size=(64, 32)).astype(np.float32) for i in range(2)}
    eng = ExecutionEngine(mesh=make_data_mesh(jax.devices()[:1]), backend="xla")
    try:
        sel = lambda k, a: ("zfp", {"rate": 16})
        eng.compress_pytree(tree, select=sel)  # warm
        (flat, _stats), found = trace_spans(lambda: eng.compress_pytree(tree, select=sel))
    finally:
        eng.close()
    (root,) = [s for s in found if s.name == "hpdr.engine.compress_pytree"]
    tasks = [s for s in found if s.name == "hpdr.executor.task"]
    assert tasks and all(t.stats["call"] == root.stats["call"] for t in tasks)
    assert all(t.stats["lane"] == "compute" and t.stats["wait_us"] >= 0 for t in tasks)
    # the task ran on a pool thread, and its spans say which call it served
    names = {s.name for s in found if s.stats.get("call") == root.stats["call"]}
    assert names >= {"hpdr.engine.leaf_jobs", "hpdr.engine.stack",
                     "hpdr.engine.finish", "hpdr.segment", "hpdr.h2d", "hpdr.d2h"}
    assert set(flat) == set(tree)


@pytest.mark.parametrize("on_device", [True, False])
def test_engine_surface_d2h_counts_device_leaves(on_device):
    rng = np.random.default_rng(4)
    host = {f"w{i}": rng.normal(size=(32, 32)).astype(np.float32) for i in range(2)}
    tree = {k: jnp.asarray(v) for k, v in host.items()} if on_device else host
    eng = ExecutionEngine(mesh=make_data_mesh(jax.devices()[:1]), backend="xla")
    try:
        before = eng.stats()
        comp, _ = eng.compress_pytree(tree, select=lambda k, a: ("zfp", {"rate": 16}))
        after = eng.stats()
        expect = sum(v.nbytes for v in host.values()) if on_device else 0
        assert after["surface_d2h"] - before["surface_d2h"] == expect
        # the stacked leaves are the pipeline's own upload, not the surface's
        assert after["surface_h2d"] == before["surface_h2d"]
        out = eng.decompress_pytree(comp, tree)
        # restore fetches each decoded leaf and uploads it again
        final = eng.stats()
        assert final["surface_h2d"] - after["surface_h2d"] == sum(
            v.nbytes for v in host.values())
        assert final["surface_d2h"] - after["surface_d2h"] == sum(
            v.nbytes for v in host.values())
        assert set(out) == set(tree)
    finally:
        eng.close()


@pytest.mark.parametrize("on_device", [True, False])
def test_pipeline_counts_only_host_input_as_h2d(on_device):
    x = smooth_field_3d(16)
    data = jnp.asarray(x) if on_device else x
    spec = api.make_spec(x, "zfp", rate=16)
    plan = api.get_plan(spec)
    env = CallEnv(plan)
    c = get_codec("zfp").encode(plan, data, env=env)
    assert env.transfers.h2d == (0 if on_device else x.nbytes)
    assert env.transfers.d2h == c.nbytes()


def test_segments_lower_to_modules_named_after_them():
    x = smooth_field_3d(8)
    plan = api.get_plan(api.make_spec(x, "zfp", rate=16))
    (fwd,) = plan.pipeline.device_segments
    (inv,) = plan.pipeline.inv_segments
    assert inv.name == "invert[zfp_block_transform]"
    assert inv.jit_name == "invert_zfp_block_transform"
    exe = plan.pipeline.segment_exe(fwd, {}, batched=False)
    text = exe.lower((jnp.asarray(x),), (), ()).as_text()
    assert "jit_zfp_block_transform" in text


def test_executor_tasks_run_in_the_submitters_call():
    ex = DeviceExecutor(jax.devices()[:1])
    try:
        assert ex.submit(spans._CALL.get).result() is None
        with spans.root("hpdr.compress") as call:
            sub = ex.submit(spans._CALL.get)
            chained = ex.submit_after(sub, lambda _prev: spans._CALL.get())
            with spans.root("hpdr.decompress") as inner:
                assert inner == call  # a root inside a call joins it
        assert sub.result() == call and chained.result() == call
        assert spans._CALL.get() is None
        with spans.root("hpdr.compress") as other:
            assert other != call
    finally:
        ex.shutdown()
