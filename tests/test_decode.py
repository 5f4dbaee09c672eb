"""Stacked device-resident decode path: the mirror of PR 3's encode work.

Covers the PR-4 contracts:
  * compiled inverse pipelines — every stage-graph codec compiles a decode
    direction with NO host barrier: host stages become metadata-only
    prepares, so the whole decode chain fuses into one jitted segment;
  * bit-identity — decoded arrays agree exactly across (a) xla vs
    pallas_interpret backends, (b) serial vs engine-stacked decode,
    (c) the chunk-parallel inverse pipeline vs the legacy host-orchestrated
    Huffman decoder;
  * compatibility — streams without the decode chunk index (anything
    written before this PR, simulated by stripping the per-stage index)
    still decode through the host fallback, including v1-container bytes;
  * stacked engine path — decompress_pytree groups leaves by decode spec
    into one whole-mesh shard_map submission per bucket, with CMM hit
    counters mirroring the encode direction (multi-device subprocess);
  * transfer symmetry — decode H2D is the compressed sections plus
    metadata-scale operands, never a raw-array-sized staging transfer;
  * batched-path donation — per-shard workspace stacks are donated and the
    recycled buffers re-stored (pointer-stable where XLA implements
    donation).
"""

import copy
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

from repro.core import adapters, api, huffman
from repro.core.codecs import get_codec
from repro.core.codecs.huffman_codec import stream_decode_index
from repro.core.engine import ExecutionEngine
from repro.core.stages import CallEnv
from conftest import smooth_field_3d, span_seconds


def _strip_decode_index(c):
    """A pre-PR-4 stream: same sections, no decode chunk index."""
    old = copy.deepcopy(c)
    for s in old.meta.get("stages", ()):
        if isinstance(s, dict):
            s.pop("decode_index", None)
    return old


CASES = (
    ("mgard", {"error_bound": 1e-2}),
    ("zfp", {"rate": 16}),
    ("huffman", {}),
    ("huffman-bytes", {}),
)


def _data_for(method, rng):
    if method == "huffman":
        return np.minimum(np.abs(rng.normal(0, 25, 17000)).astype(np.int32), 400)
    return smooth_field_3d(20)


# ---------------------------------------------------------------------------
# compiled inverse structure
# ---------------------------------------------------------------------------


def test_inverse_pipelines_fuse_to_single_segment(rng):
    """Decode has no host barrier: one fused inverse segment per codec,
    preceded only by metadata-scale host prepares."""
    expected = {
        "mgard": "invert[huffman_entropy·uniform_quantize·mgard_decorrelate]",
        "zfp": "invert[zfp_block_transform]",
        "huffman": "invert[huffman_entropy·int_keys]",
        "huffman-bytes": "invert[huffman_entropy·byte_keys]",
    }
    for method, kw in CASES:
        data = _data_for(method, rng)
        pipe = api.get_plan(api.make_spec(data, method, **kw)).pipeline
        assert pipe.invertible
        assert [s.name for s in pipe.inv_segments] == [expected[method]]
        assert all(not st.device for st in pipe.inv_preps)


def test_streams_carry_decode_chunk_index(rng):
    keys = _data_for("huffman", rng)
    c = api.compress(jnp.asarray(keys), "huffman")
    idx = stream_decode_index(c)
    assert idx is not None
    assert idx["n_chunks"] == int(c.arrays["chunk_offsets"].shape[0])
    assert idx["n_symbols"] == keys.size
    # survives a byte roundtrip in both container versions
    for version in (1, 2):
        c2 = api.Compressed.from_bytes(c.to_bytes(version=version))
        assert stream_decode_index(c2) == idx


# ---------------------------------------------------------------------------
# bit-identity: backends / legacy host decoder / old streams
# ---------------------------------------------------------------------------


def test_decode_bit_identity_across_backends(rng):
    """Acceptance (a): xla and pallas_interpret decode bit-identically."""
    for method, kw in CASES:
        data = _data_for(method, rng)
        c = api.compress(jnp.asarray(data), method, backend="xla", **kw)
        out_xla = np.asarray(api.decode(c, backend="xla"))
        out_int = np.asarray(api.decode(c, backend="pallas_interpret"))
        np.testing.assert_array_equal(out_xla, out_int, err_msg=method)


def test_decode_pipeline_matches_legacy_host_decoder(rng):
    """Acceptance (c): the chunk-parallel inverse pipeline reproduces the
    host-orchestrated decoder exactly, and old streams still decode."""
    calls = {"n": 0}
    real = huffman.decode

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    for method, kw in CASES:
        data = _data_for(method, rng)
        c = api.compress(jnp.asarray(data), method, backend="xla", **kw)
        new = np.asarray(api.decode(c))
        old_stream = _strip_decode_index(c)
        before = calls["n"]
        huffman_mod_decode = huffman.decode
        try:
            huffman.decode = counting
            legacy = np.asarray(api.decode(old_stream))
        finally:
            huffman.decode = huffman_mod_decode
        np.testing.assert_array_equal(new, legacy, err_msg=method)
        if method != "zfp":  # zfp has no entropy tail (always pipeline)
            assert calls["n"] == before + 1  # fallback actually ran


def test_old_v1_stream_roundtrip(rng):
    """Pre-index v1-container bytes decode via the host fallback."""
    keys = _data_for("huffman", rng)
    c = _strip_decode_index(api.compress(jnp.asarray(keys), "huffman"))
    c2 = api.Compressed.from_bytes(c.to_bytes(version=1))
    assert stream_decode_index(c2) is None
    np.testing.assert_array_equal(np.asarray(api.decode(c2)), keys)


def test_huffman_bytes_unusual_dtypes_fall_back(rng):
    """Element types the device bitcast cannot express stay correct via the
    host fallback (decode_state returns None)."""
    f64 = rng.normal(size=257)  # float64: 8-byte elements under 32-bit jax
    c = api.compress_leaf(f64, "huffman-bytes")
    np.testing.assert_array_equal(api.decompress_leaf(c), f64)


# ---------------------------------------------------------------------------
# serial vs stacked (acceptance b)
# ---------------------------------------------------------------------------


def test_stacked_decode_bit_identical_to_serial(rng):
    tree = {f"w{i}": rng.normal(size=(48, 64)).astype(np.float32)
            for i in range(4)}
    itree = {f"k{i}": np.minimum(
        np.abs(rng.normal(0, 5 * (i + 1), 4096)).astype(np.int32), 40 * (i + 1))
        for i in range(3)}
    eng = ExecutionEngine(backend="xla")
    for src, sel in (
        (tree, lambda k, a: ("mgard", {"error_bound": 1e-2})),
        (itree, lambda k, a: ("huffman", {})),
        (tree, lambda k, a: ("zfp", {"rate": 16})),
    ):
        comp, _ = eng.compress_pytree(src, select=sel)
        before = eng.stats()["sharded_decoded_leaves"]
        out = eng.decompress_pytree(comp, src)
        assert eng.stats()["sharded_decoded_leaves"] == before + len(src)
        for k in src:
            serial = api.decompress_leaf(comp[k])
            np.testing.assert_array_equal(np.asarray(out[k]), serial)
    eng.close()


def test_stacked_decode_falls_back_for_old_streams(rng):
    """A bucket containing one pre-index stream decodes per-leaf (host
    path) and still restores exactly."""
    itree = {f"k{i}": rng.integers(0, 100, 2048).astype(np.int32)
             for i in range(3)}
    eng = ExecutionEngine(backend="xla")
    comp, _ = eng.compress_pytree(itree, select=lambda k, a: ("huffman", {}))
    comp["k1"] = _strip_decode_index(comp["k1"])
    before = eng.stats()["sharded_decoded_leaves"]
    out = eng.decompress_pytree(comp, itree)
    assert eng.stats()["sharded_decoded_leaves"] == before  # no stacked run
    for k in itree:
        np.testing.assert_array_equal(np.asarray(out[k]), itree[k])
    eng.close()


# ---------------------------------------------------------------------------
# transfer symmetry: decode H2D = compressed bytes + metadata
# ---------------------------------------------------------------------------


def test_decode_transfers_are_stream_plus_metadata(rng, trace_spans):
    keys = np.minimum(np.abs(rng.normal(0, 6, 1 << 16)).astype(np.int32), 63)
    spec = api.make_spec(keys, "huffman")
    c = api.encode(spec, jnp.asarray(keys))
    codec = get_codec("huffman")
    plan = api.get_plan(codec.decode_spec(c))
    codec.decode(plan, c)  # warm
    env = CallEnv(plan)
    out, spans = trace_spans(lambda: codec.decode(plan, c, env=env))
    transfers, stage_s = env.transfers, span_seconds(spans)
    np.testing.assert_array_equal(np.asarray(out), keys)
    # H2D: the compressed sections plus metadata-scale decode operands —
    # far below the raw array the decode produces
    assert transfers.h2d < keys.nbytes / 2
    assert transfers.h2d >= c.arrays["words"].nbytes
    assert transfers.h2d <= c.nbytes() + 65536
    assert transfers.d2h == 0  # nothing comes back until the caller looks
    assert any(k.startswith("invert[") for k in stage_s)
    assert "codebook_build" in stage_s


# ---------------------------------------------------------------------------
# batched-path donation (ROADMAP item)
# ---------------------------------------------------------------------------


def test_batched_workspace_donation_recycles_stacks(rng, monkeypatch):
    """The stacked path builds one per-shard workspace stack per segment,
    donates it into every dispatch, and re-stores the recycled buffers —
    the stack is built once across repeated bucket encodes."""
    monkeypatch.setattr(adapters, "supports_donation", lambda: True)
    tree = {f"w{i}": rng.normal(size=(48, 64)).astype(np.float32)
            for i in range(4)}
    eng = ExecutionEngine(backend="xla")
    try:
        sel = lambda k, a: ("mgard", {"error_bound": 1e-2})
        comp, stats = eng.compress_pytree(tree, select=sel)
        assert stats["sharded_leaves"] == 4
        s = eng.stats()
        assert s["ws_donated_calls"] >= 1       # quantize segment donated
        assert s["ws_stack_builds"] == 1        # one stack, then recycled
        assert eng._ws_stacks                   # recycled stack re-stored
        comp2, _ = eng.compress_pytree(tree, select=sel)
        s2 = eng.stats()
        assert s2["ws_stack_builds"] == 1       # reused, not rebuilt
        assert s2["ws_donated_calls"] > s["ws_donated_calls"]
        # streams stay bit-identical to the serial (broadcast-free) encode
        for k in tree:
            serial = api.compress_leaf(
                tree[k], "mgard", error_bound=1e-2, backend="xla")
            assert comp2[k].to_bytes() == serial.to_bytes()
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# stacked multi-device subprocess (acceptance: CMM counters + one
# whole-mesh submission per decode bucket)
# ---------------------------------------------------------------------------


@pytest.mark.subprocess
def test_stacked_decode_multidevice_subprocess():
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
        comp, _ = eng.compress_pytree(
            tree, select=lambda k, a: ("mgard", {"error_bound": 1e-2}))
        comp2, _ = eng.compress_pytree(
            itree, select=lambda k, a: ("huffman", {}))
        GLOBAL_CMM.clear()
        h0, m0 = GLOBAL_CMM.hit_count, GLOBAL_CMM.miss_count
        mesh0 = eng.stats()["mesh_submitted"]
        smap0 = eng.stats()["shard_map_calls"]
        h2d0 = eng.stats()["transfer_h2d"]
        out = eng.decompress_pytree(comp, tree)
        out2 = eng.decompress_pytree(comp2, itree)
        stream_bytes = sum(c.nbytes() for c in comp.values())
        stream_bytes += sum(c.nbytes() for c in comp2.values())
        raw_bytes = (sum(a.nbytes for a in tree.values())
                     + sum(a.nbytes for a in itree.values()))
        exact = all((np.asarray(out2[k]) == itree[k]).all() for k in itree)
        serial_ok = all(
            (np.asarray(out[k]) == api.decompress_leaf(comp[k])).all()
            for k in tree
        )
        print(json.dumps({
            "devices": jax.device_count(),
            "engine_devices": len(eng.devices),
            "sharded_decoded": eng.stats()["sharded_decoded_leaves"],
            "mesh_submissions": eng.stats()["mesh_submitted"] - mesh0,
            "shard_map_calls": eng.stats()["shard_map_calls"] - smap0,
            "decode_h2d": eng.stats()["transfer_h2d"] - h2d0,
            "stream_bytes": stream_bytes,
            "raw_bytes": raw_bytes,
            "hits": GLOBAL_CMM.hit_count - h0,
            "misses": GLOBAL_CMM.miss_count - m0,
            "exact": exact,
            "serial_ok": serial_ok,
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
    assert report["sharded_decoded"] == 8 + 4   # both buckets stacked
    # one whole-mesh submission per decode bucket, one fused inverse
    # segment each — not one future per leaf
    assert report["mesh_submissions"] == 2
    assert report["shard_map_calls"] == 2
    # CMM: decode plans resolved per leaf — the first leaf of each bucket
    # is the only miss, every further leaf a real hit
    assert report["misses"] == 2
    assert report["hits"] >= (8 - 1) + (4 - 1)
    # H2D symmetry: compressed sections (stack-padded per bucket) plus
    # metadata-scale operands.  If decode staged the raw arrays the count
    # would exceed raw_bytes by construction; the exact per-leaf accounting
    # is asserted in test_decode_transfers_are_stream_plus_metadata.
    assert report["decode_h2d"] < report["raw_bytes"]
    assert report["decode_h2d"] >= report["stream_bytes"] // 2
    assert report["exact"] and report["serial_ok"]


# ---------------------------------------------------------------------------
# mixed chunk geometry (ROADMAP item): group, don't merge-by-max
# ---------------------------------------------------------------------------


def test_stacked_decode_groups_mixed_chunk_sizes(rng):
    """Same-spec streams packed with different chunk_size must decode in
    separate stacked dispatches — merging their statics by max used to
    decode the smaller-chunk streams as garbage."""
    itree = {f"k{i}": np.minimum(
        np.abs(rng.normal(0, 20, 4096)).astype(np.int32), 300)
        for i in range(4)}
    sel = lambda k, a: ("huffman",
                        {"chunk_size": 512 if k in ("k1", "k3") else 4096})
    eng = ExecutionEngine(backend="xla")
    try:
        comp, _ = eng.compress_pytree(itree, select=sel)
        assert {comp[k].meta["chunk_size"] for k in itree} == {512, 4096}
        # decode specs are identical (chunk_size is encode-side only) …
        specs = {get_codec(c.method).decode_spec(c).key() for c in comp.values()}
        assert len(specs) == 1
        before = eng.stats()["sharded_decoded_leaves"]
        smap0 = eng.stats()["shard_map_calls"]
        out = eng.decompress_pytree(comp, itree)
        # … yet both geometry groups ran stacked, one dispatch each
        assert eng.stats()["sharded_decoded_leaves"] == before + 4
        assert eng.stats()["shard_map_calls"] == smap0 + 2
        for k in itree:
            np.testing.assert_array_equal(np.asarray(out[k]), itree[k])
            serial = api.decompress_leaf(comp[k])
            np.testing.assert_array_equal(np.asarray(out[k]), serial)
    finally:
        eng.close()


def test_mixed_chunk_size_merge_is_rejected_at_stage_level(rng):
    """Defence in depth: if mixed geometries ever reach one stacked batch,
    the strict chunk_size merge refuses instead of decoding garbage."""
    from repro.core.stages.library import CodebookBuild

    st = CodebookBuild()
    assert st.merge_static("n_symbols", [4096, 1024]) == 4096  # pad: safe
    with pytest.raises(ValueError, match="chunk_size"):
        st.merge_static("chunk_size", [4096, 512])
