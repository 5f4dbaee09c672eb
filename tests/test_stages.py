"""Stage-graph codec pipeline: structure, parity, bit-identity, fan-out.

Covers the PR-3 contracts:
  * graph structure — codecs compile into fused device segments with host
    barriers only at genuine sync points, and intermediates that nothing
    downstream consumes are pruned from segment outputs;
  * device-resident entropy — xla and pallas_interpret produce bit-identical
    streams through the stage pipeline, and the streams equal the historical
    host encoder's on fixed seeds (section-for-section);
  * stacked engine path — MGARD/Huffman buckets now ride the shard_map path
    (one bucket = one executor submission, not one per leaf), bit-identical
    to serial encodes, with CMM counters as in tests/test_engine.py;
  * decode-table caching — repeated decompress calls derive the canonical
    decode tables once per codebook, cached on the CMM plan;
  * transfer accounting — encode fetches are bounded by metadata + the
    compressed stream, never the raw array.
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

from repro.core import api, huffman, mgard
from repro.core.codecs import get_codec
from repro.core.context import GLOBAL_CMM
from repro.core.engine import ExecutionEngine
from repro.core.stages import CallEnv, StageGraph, Stage
from conftest import smooth_field_3d, span_seconds


# ---------------------------------------------------------------------------
# graph structure / compilation
# ---------------------------------------------------------------------------


def _pipeline_for(data, method, **params):
    spec = api.make_spec(data, method, **params)
    return api.get_plan(spec).pipeline


def test_codecs_compile_to_expected_segments():
    f = smooth_field_3d(16)
    # zfp: one fused device segment, no host barrier
    assert len(_pipeline_for(f, "zfp", rate=8).device_segments) == 1
    # mgard: decorrelate | quantize+histogram | entropy+pack
    mg = _pipeline_for(f, "mgard", error_bound=1e-2)
    assert [s.name for s in mg.device_segments] == [
        "mgard_decorrelate",
        "uniform_quantize+huffman_histogram",
        "huffman_entropy+bit_pack",
    ]
    # huffman-bytes: histogram front, entropy tail; one host barrier
    hb = _pipeline_for(f, "huffman-bytes")
    assert [s.name for s in hb.device_segments] == [
        "byte_keys+huffman_histogram",
        "huffman_entropy+bit_pack",
    ]


def test_segment_outputs_are_liveness_pruned():
    """(code, length) pairs are consumed by bit_pack inside the same fused
    segment — they must never be segment outputs (device-residency)."""
    f = smooth_field_3d(16)
    for method, kw in (("mgard", {"error_bound": 1e-2}), ("huffman-bytes", {})):
        pipe = _pipeline_for(f, method, **kw)
        tail = pipe.device_segments[-1]
        assert "codes" not in tail.out_keys and "lens" not in tail.out_keys
        assert set(tail.out_keys) >= {"words", "chunk_offsets"}


def test_stage_graph_rejects_undeclared_reads():
    class Bad(Stage):
        name = "bad"
        reads = ("nope",)
        writes = ("x",)

    f = smooth_field_3d(16)
    plan = api.get_plan(api.make_spec(f, "zfp", rate=8))
    with pytest.raises(ValueError, match="no earlier stage produces"):
        StageGraph(stages=(Bad(),), finish_keys=("x",)).compile(plan)


def test_container_records_per_stage_metadata():
    f = smooth_field_3d(16)
    c = api.compress(jnp.asarray(f), "mgard", error_bound=1e-2)
    names = [s["stage"] for s in c.meta["stages"]]
    assert names == ["mgard_decorrelate", "bin_schedule", "uniform_quantize",
                     "huffman_histogram", "codebook_build", "huffman_entropy",
                     "bit_pack"]
    kinds = {s["stage"]: s["kind"] for s in c.meta["stages"]}
    assert kinds["codebook_build"] == "host"
    assert kinds["huffman_entropy"] == "device"
    # the stream (with stage metadata) stays readable by the v2 reader and
    # still writes v1 for compatibility
    for version in (1, 2):
        c2 = api.Compressed.from_bytes(c.to_bytes(version=version))
        np.testing.assert_array_equal(
            np.asarray(api.decompress(c2)), np.asarray(api.decompress(c))
        )


# ---------------------------------------------------------------------------
# device-resident entropy: backend parity + host-encoder bit-identity
# ---------------------------------------------------------------------------


def test_entropy_stage_backend_parity(rng):
    """xla and pallas_interpret runs of the stage pipeline produce
    bit-identical entropy streams (lookup kernel vs jnp gather)."""
    keys = np.minimum(np.abs(rng.normal(0, 30, 20000)).astype(np.int32), 511)
    streams = {}
    for backend in ("xla", "pallas_interpret"):
        c = api.compress(jnp.asarray(keys), "huffman", backend=backend)
        streams[backend] = c.to_bytes()
    assert streams["xla"] == streams["pallas_interpret"]


def test_huffman_stream_bit_identical_to_host_encoder(rng):
    """The device-resident entropy stage reproduces the host encoder's
    stream section-for-section on fixed seeds."""
    keys = np.minimum(np.abs(rng.normal(0, 10, 8192)).astype(np.int32), 255)
    c = api.compress(jnp.asarray(keys), "huffman", backend="xla")
    enc = huffman.compress(jnp.asarray(keys), int(keys.max()) + 1, adapter="xla")
    np.testing.assert_array_equal(c.arrays["words"], np.asarray(enc.words))
    np.testing.assert_array_equal(
        c.arrays["chunk_offsets"], np.asarray(enc.chunk_offsets)
    )
    np.testing.assert_array_equal(c.arrays["length_table"], enc.length_table)
    assert c.meta["total_bits"] == enc.total_bits
    assert c.meta["num_keys"] == enc.num_keys
    assert c.meta["n_symbols"] == enc.n_symbols


def test_mgard_stream_bit_identical_to_host_path():
    f = smooth_field_3d(24)
    c = api.compress(jnp.asarray(f), "mgard", error_bound=1e-2, relative=False,
                     backend="xla")
    obj = mgard.compress(jnp.asarray(f), 1e-2)
    np.testing.assert_array_equal(c.arrays["words"], np.asarray(obj.entropy.words))
    np.testing.assert_array_equal(c.arrays["outlier_idx"], obj.outlier_idx)
    np.testing.assert_array_equal(c.arrays["outlier_val"], obj.outlier_val)
    np.testing.assert_array_equal(c.arrays["bins"], obj.bins)
    assert c.meta["total_bits"] == obj.entropy.total_bits


def test_mgard_outlier_cap_overflow_falls_back(rng):
    """A leaf whose escape count overflows the device compaction cap takes
    the full-fetch fallback and still matches the host oracle."""
    noisy = rng.normal(size=(17, 17)).astype(np.float32) * 100
    spec = api.make_spec(noisy, "mgard", error_bound=1e-6, relative=False,
                         dict_size=16, backend="xla")
    plan = api.get_plan(spec)
    c = api.encode(spec, jnp.asarray(noisy))
    assert len(c.arrays["outlier_idx"]) > plan.meta["out_cap"]
    obj = mgard.compress(jnp.asarray(noisy), 1e-6, dict_size=16)
    np.testing.assert_array_equal(c.arrays["outlier_idx"], obj.outlier_idx)
    np.testing.assert_array_equal(c.arrays["outlier_val"], obj.outlier_val)
    out = np.asarray(api.decode(c))
    assert np.abs(out - noisy).max() <= 1e-4


def test_single_symbol_and_tiny_inputs_roundtrip():
    zeros = np.zeros(777, np.int32)
    c = api.compress(jnp.asarray(zeros), "huffman")
    np.testing.assert_array_equal(np.asarray(api.decompress(c)), zeros)
    one = np.asarray([3.5], np.float32)
    c2 = api.compress(jnp.asarray(one), "huffman-bytes")
    np.testing.assert_array_equal(np.asarray(api.decompress(c2)), one)


# ---------------------------------------------------------------------------
# decode-table caching on the plan (CMM hits for repeated decompress)
# ---------------------------------------------------------------------------


def test_decode_tables_cached_on_plan(rng, monkeypatch):
    keys = np.minimum(np.abs(rng.normal(0, 10, 8192)).astype(np.int32), 127)
    c = api.compress(jnp.asarray(keys), "huffman")
    codec = get_codec("huffman")
    plan = api.get_plan(codec.decode_spec(c))
    for k in [k for k in plan.workspace
              if isinstance(k, str) and k.startswith("decode_tables:")]:
        del plan.workspace[k]

    builds = {"n": 0}
    real = huffman.decode_tables

    def counting(length_table):
        builds["n"] += 1
        return real(length_table)

    monkeypatch.setattr(huffman, "decode_tables", counting)
    h0 = GLOBAL_CMM.hit_count
    out1 = np.asarray(api.decode(c))
    out2 = np.asarray(api.decode(c))
    np.testing.assert_array_equal(out1, keys)
    np.testing.assert_array_equal(out2, keys)
    assert builds["n"] == 1                    # derived once, reused after
    assert GLOBAL_CMM.hit_count >= h0 + 1      # decode plan itself a CMM hit
    cached = [k for k in plan.workspace
              if isinstance(k, str) and k.startswith("decode_tables:")]
    assert len(cached) == 1
    assert plan.nbytes() > 0                   # tables visible to accounting


# ---------------------------------------------------------------------------
# stacked engine path for the formerly host-staged codecs
# ---------------------------------------------------------------------------


def test_engine_mgard_bucket_takes_stacked_path(rng):
    tree = {f"w{i}": rng.normal(size=(48, 64)).astype(np.float32)
            for i in range(4)}
    eng = ExecutionEngine(backend="xla")
    comp, stats = eng.compress_pytree(
        tree, select=lambda k, a: ("mgard", {"error_bound": 1e-2}))
    assert stats["sharded_leaves"] == 4        # no per-leaf future fan-out
    assert eng.stats()["shard_map_calls"] >= 3  # one per fused segment
    for key, arr in tree.items():
        serial = api.compress_leaf(arr, "mgard", error_bound=1e-2, backend="xla")
        assert comp[key].to_bytes() == serial.to_bytes()
    out = eng.decompress_pytree(comp, tree)
    for k in tree:
        vr = tree[k].max() - tree[k].min()
        assert np.abs(np.asarray(out[k]) - tree[k]).max() <= 2e-2 * vr
    eng.close()


def test_engine_huffman_bucket_mixed_alphabets(rng):
    """Int-key leaves with different alphabets share one stacked bucket and
    still produce streams identical to serial encodes (per-leaf codebooks)."""
    tree = {
        f"k{i}": np.minimum(
            np.abs(rng.normal(0, 5 * (i + 1), 4096)).astype(np.int32),
            40 * (i + 1),
        )
        for i in range(3)
    }
    eng = ExecutionEngine(backend="xla")
    comp, stats = eng.compress_pytree(tree, select=lambda k, a: ("huffman", {}))
    assert stats["buckets"] == 1 and stats["sharded_leaves"] == 3
    for key, arr in tree.items():
        serial = api.compress_leaf(arr, "huffman", backend="xla")
        assert comp[key].to_bytes() == serial.to_bytes()
    out = eng.decompress_pytree(comp, tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]), tree[k])
    eng.close()


@pytest.mark.subprocess
def test_engine_stacked_multidevice_subprocess():
    """Acceptance: on a ≥2-device mesh, MGARD and Huffman buckets execute
    via the stacked shard_map path — one executor submission per bucket
    (not per leaf), one plan build per bucket (CMM counters), streams
    bit-identical to serial.
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
        tree = {f"w{i}": rng.normal(size=(48, 64)).astype(np.float32)
                for i in range(8)}
        itree = {f"k{i}": rng.integers(0, 200, 4096).astype(np.int32)
                 for i in range(4)}
        eng = ExecutionEngine(backend="xla")
        GLOBAL_CMM.clear()
        h0, m0 = GLOBAL_CMM.hit_count, GLOBAL_CMM.miss_count
        comp, stats = eng.compress_pytree(
            tree, select=lambda k, a: ("mgard", {"error_bound": 1e-2}))
        submitted_after_mgard = eng.stats()["submitted"]
        comp2, stats2 = eng.compress_pytree(
            itree, select=lambda k, a: ("huffman", {}))
        serial_ok = all(
            comp[k].to_bytes() == api.compress_leaf(
                tree[k], "mgard", error_bound=1e-2, backend="xla").to_bytes()
            for k in tree
        ) and all(
            comp2[k].to_bytes() == api.compress_leaf(
                itree[k], "huffman", backend="xla").to_bytes()
            for k in itree
        )
        out = eng.decompress_pytree(comp2, itree)
        exact = all((np.asarray(out[k]) == itree[k]).all() for k in itree)
        print(json.dumps({
            "devices": jax.device_count(),
            "engine_devices": len(eng.devices),
            "mgard_sharded": stats["sharded_leaves"],
            "huffman_sharded": stats2["sharded_leaves"],
            "submitted_after_mgard": submitted_after_mgard,
            "shard_map_calls": eng.stats()["shard_map_calls"],
            "transfer_d2h": eng.stats()["transfer_d2h"],
            "hits": GLOBAL_CMM.hit_count - h0,
            "misses": GLOBAL_CMM.miss_count - m0,
            "serial_ok": serial_ok,
            "exact": exact,
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
    assert report["devices"] >= 2 and report["engine_devices"] >= 2
    assert report["mgard_sharded"] == 8        # whole bucket on shard_map
    assert report["huffman_sharded"] == 4
    # the encode hot loop is one whole-mesh submission for the bucket, not
    # one future per leaf
    assert report["submitted_after_mgard"] == 1
    # mgard 3 + huffman 3 encode segments, + 1 fused inverse segment for the
    # stacked huffman decode (decompress_pytree rides shard_map since PR 4)
    assert report["shard_map_calls"] == 3 + 3 + 1
    assert report["transfer_d2h"] > 0
    assert report["serial_ok"] and report["exact"]
    # CMM: one plan build per bucket; every further leaf a real hit
    assert report["misses"] == 2
    assert report["hits"] >= (8 - 1) + (4 - 1)


# ---------------------------------------------------------------------------
# transfer accounting
# ---------------------------------------------------------------------------


def test_encode_transfers_are_metadata_plus_stream(rng, trace_spans):
    """The encode path never stages the raw array back to host: D2H is the
    compressed stream plus metadata-scale barrier fetches."""
    keys = np.minimum(np.abs(rng.normal(0, 6, 1 << 16)).astype(np.int32), 63)
    spec = api.make_spec(keys, "huffman")
    codec, plan = get_codec("huffman"), api.get_plan(spec)
    codec.encode(plan, jnp.asarray(keys))  # warm
    env = CallEnv(plan)
    c, spans = trace_spans(lambda: codec.encode(plan, jnp.asarray(keys), env=env))
    transfers = env.transfers
    assert transfers.d2h < keys.nbytes / 2      # << raw input
    assert transfers.d2h >= c.nbytes() - c.arrays["length_table"].nbytes
    stage_s = span_seconds(spans)
    assert set(stage_s) >= {"codebook_build", "huffman_entropy+bit_pack"}
    assert stage_s["codebook_build"] > 0

