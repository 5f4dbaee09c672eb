"""Serving layer: batched generation, KV parking, and the multi-tenant
reduction service (admission, coalescing, quotas, backpressure)."""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import (
    Request,
    ServingEngine,
    compress_kv_cache,
    decompress_kv_cache,
)

KEY = jax.random.PRNGKey(0)


def _engine(batch=2, max_len=64):
    cfg = get_config("qwen2.5-3b").smoke()
    model = build_model(cfg)
    params = model.init(KEY)
    return cfg, model, params, ServingEngine(model, params, batch, max_len)


def test_serve_batched_requests(rng):
    cfg, model, params, eng = _engine()
    reqs = [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
                max_new_tokens=6)
        for i in range(4)  # more requests than slots → refill path
    ]
    stats = eng.serve(reqs)
    assert stats["requests"] == 4
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 6 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.out_tokens)


def test_greedy_decode_is_deterministic(rng):
    cfg, model, params, eng1 = _engine(batch=1)
    _, _, _, eng2 = _engine(batch=1)
    prompt = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    r1 = Request(uid=0, prompt=prompt, max_new_tokens=5)
    r2 = Request(uid=0, prompt=prompt, max_new_tokens=5)
    eng1.serve([r1])
    eng2.serve([r2])
    assert r1.out_tokens == r2.out_tokens


def test_kv_cache_compression_roundtrip():
    cfg, model, params, eng = _engine()
    cache = model.init_cache(2, 32, jnp.float32)
    # fill with realistic values
    cache = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(KEY, x.shape, x.dtype)
        if x.dtype.kind == "f" else x,
        cache,
    )
    comp, stats = compress_kv_cache(cache, rate=16)
    assert stats["ratio"] > 1.5
    restored = decompress_kv_cache(comp, cache)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(cache)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype.kind == "f" and a.size >= 4096:
            scale = np.abs(b).max() + 1e-9
            assert np.abs(a - b).max() / scale < 2e-3
        else:
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# parked-session store: byte-budget LRU eviction + transparent rematerialize
# ---------------------------------------------------------------------------


def _session_cache(seed):
    r = np.random.default_rng(seed)
    return {
        "k": r.normal(size=(2, 4, 64, 8, 16)).astype(np.float32),
        "v": r.normal(size=(2, 4, 64, 8, 16)).astype(np.float32),
        "pos": np.arange(4, dtype=np.int32),
    }


def test_kv_page_store_evicts_and_rematerializes(tmp_path):
    from repro.serving.engine import KVPageStore

    store = KVPageStore(capacity_bytes=600_000, spill_dir=tmp_path, rate=16)
    sessions = {f"s{i}": _session_cache(i) for i in range(4)}
    for sid, cache in sessions.items():
        stats = store.park(sid, cache)
        assert stats["compressed_leaves"] == 2
    st = store.stats()
    # memory pressure: parked bytes stay within budget, LRU sessions spilled
    assert st["parked_bytes"] <= st["capacity_bytes"]
    assert st["spills"] >= 1 and st["evictions"] >= 1
    assert store._path("s0").exists()

    # evicted session rematerializes transparently on access
    restored = store.restore("s0", sessions["s0"])
    np.testing.assert_array_equal(np.asarray(restored["pos"]),
                                  sessions["s0"]["pos"])
    for leaf in ("k", "v"):
        err = np.abs(np.asarray(restored[leaf]) - sessions["s0"][leaf]).max()
        assert err < 1e-2 * np.abs(sessions["s0"][leaf]).max()
    assert store.stats()["loads"] >= 1

    # a still-resident (most recent) session restores without a disk load
    loads_before = store.stats()["loads"]
    store.restore("s3", sessions["s3"])
    assert store.stats()["loads"] == loads_before

    store.release("s0")
    assert not store._path("s0").exists()


def test_kv_page_store_async_and_unknown_session(tmp_path):
    import pytest

    from repro.serving.engine import KVPageStore

    store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path, rate=16)
    sub = store.park_async("bg", _session_cache(7))
    stats = sub.result()
    assert stats["compressed_leaves"] == 2
    assert "bg" in str(sorted(k[2] for k in store.cache._entries))
    with pytest.raises(KeyError, match="unknown parked session"):
        store.fetch("never-parked")


def test_kv_page_store_colliding_session_ids_get_distinct_spills(tmp_path):
    from repro.serving.engine import KVPageStore

    store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path, rate=16)
    assert store._path("user:1") != store._path("user_1")
    a, b = _session_cache(1), _session_cache(2)
    store.park("user:1", a)
    store.park("user_1", b)
    store.cache.evict(("kv_page", "default", "user:1"))  # force both to spill
    store.cache.evict(("kv_page", "default", "user_1"))
    ra = store.restore("user:1", a)
    rb = store.restore("user_1", b)
    assert not np.allclose(np.asarray(ra["k"]), np.asarray(rb["k"]))
    err = np.abs(np.asarray(ra["k"]) - a["k"]).max()
    assert err < 1e-2 * np.abs(a["k"]).max()


# ---------------------------------------------------------------------------
# per-tenant quotas: one tenant's pressure never displaces another tenant
# ---------------------------------------------------------------------------


def test_two_tenant_quota_eviction_ordering(tmp_path):
    from repro.serving.engine import KVPageStore

    store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path, rate=16,
                        tenant_quota_bytes={"heavy": 450_000})
    # park in a known order: heavy a0 (oldest) .. a3, light b0
    for i in range(4):
        store.park(f"a{i}", _session_cache(i), tenant="heavy")
    store.park("b0", _session_cache(9), tenant="light")

    st = store.stats()
    # the heavy tenant was trimmed to its quota, LRU-first
    assert st["tenant_bytes"]["heavy"] <= 450_000
    assert st["tenant_evictions"]["heavy"] >= 1
    resident = {k[2] for k in store.cache._entries if k[1] == "heavy"}
    evicted = {f"a{i}" for i in range(4)} - resident
    # eviction ordering: every evicted session is older than every resident
    assert max(int(s[1]) for s in evicted) < min(int(s[1]) for s in resident)
    for sid in evicted:
        assert store._path(sid, "heavy").exists()  # spilled, not lost
    # the light tenant was untouched by the heavy tenant's pressure
    assert "light" not in st["tenant_evictions"]
    loads = store.stats()["loads"]
    store.restore("b0", _session_cache(9), tenant="light")
    assert store.stats()["loads"] == loads  # still resident: no disk load
    # evicted heavy sessions re-materialise transparently
    sid = sorted(evicted)[0]
    restored = store.restore(sid, _session_cache(int(sid[1])), tenant="heavy")
    err = np.abs(np.asarray(restored["k"]) - _session_cache(int(sid[1]))["k"]).max()
    assert err < 1e-2 * np.abs(_session_cache(int(sid[1]))["k"]).max()
    assert store.stats()["loads"] == loads + 1


def test_same_session_id_isolated_across_tenants(tmp_path):
    from repro.serving.engine import KVPageStore

    store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path, rate=16)
    a, b = _session_cache(1), _session_cache(2)
    store.park("shared", a, tenant="t1")
    store.park("shared", b, tenant="t2")
    assert store._path("shared", "t1") != store._path("shared", "t2")
    ra = store.restore("shared", a, tenant="t1")
    rb = store.restore("shared", b, tenant="t2")
    assert not np.allclose(np.asarray(ra["k"]), np.asarray(rb["k"]))


# ---------------------------------------------------------------------------
# park_async / fetch race: readers wait on the in-flight park
# ---------------------------------------------------------------------------


class _GatedIOExecutor:
    """Instrumented executor: io-lane bodies stall until ``gate`` is set."""

    def __init__(self, inner, gate):
        self._inner = inner
        self.gate = gate

    def submit(self, fn, /, *args, lane="compute", **kwargs):
        if lane == "io":
            gate = self.gate

            def gated(*a, **k):
                gate.wait(30)
                return fn(*a, **k)

            return self._inner.submit(gated, *args, lane=lane, **kwargs)
        return self._inner.submit(fn, *args, lane=lane, **kwargs)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_park_async_fetch_waits_for_inflight_park(tmp_path):
    from repro.core.engine import ExecutionEngine
    from repro.serving.engine import KVPageStore

    gate = threading.Event()
    with ExecutionEngine(backend="xla") as eng:
        eng.executor = _GatedIOExecutor(eng.executor, gate)
        store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path,
                            rate=16, engine=eng)
        cache = _session_cache(3)
        sub = store.park_async("s", cache)
        got = {}

        def fetcher():
            got["flat"] = store.fetch("s")

        t = threading.Thread(target=fetcher)
        t.start()
        time.sleep(0.15)
        # the park is gated in flight: fetch must wait, not raise KeyError
        assert t.is_alive() and "flat" not in got
        gate.set()
        t.join(30)
        assert not t.is_alive()
        assert sub.result()["compressed_leaves"] == 2
        restored = store.restore("s", cache)
        err = np.abs(np.asarray(restored["k"]) - cache["k"]).max()
        assert err < 1e-2 * np.abs(cache["k"]).max()


def test_park_async_release_waits_for_inflight_park(tmp_path):
    from repro.core.engine import ExecutionEngine
    from repro.serving.engine import KVPageStore

    gate = threading.Event()
    with ExecutionEngine(backend="xla") as eng:
        eng.executor = _GatedIOExecutor(eng.executor, gate)
        store = KVPageStore(capacity_bytes=64 << 20, spill_dir=tmp_path,
                            rate=16, engine=eng)
        sub = store.park_async("s", _session_cache(4))
        released = threading.Event()

        def releaser():
            store.release("s")
            released.set()

        t = threading.Thread(target=releaser)
        t.start()
        time.sleep(0.15)
        assert not released.is_set()  # release waits for the park to land
        gate.set()
        t.join(30)
        sub.result()
        # the release observed the *parked* state and removed it entirely
        with pytest.raises(KeyError):
            store.fetch("s")


# ---------------------------------------------------------------------------
# ReductionService: admission, coalescing, backpressure, metrics
# ---------------------------------------------------------------------------


def _zfp_select(key, arr):
    del key, arr
    return "zfp", {"rate": 16}


def test_service_coalesces_across_requests_with_cmm_hits():
    from repro.core.context import GLOBAL_CMM
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService

    from repro.core import api

    rng = np.random.default_rng(0)
    trees = [{"w": rng.normal(size=(37, 53)).astype(np.float32)}
             for _ in range(5)]
    # leaf_policy re-blocks ZFP leaves to (n, 32, 32), so other tests share
    # this bucket's plan: drop it, and the build below is the only CMM miss
    x, method, params = api.leaf_policy(trees[0]["w"], *_zfp_select("w", None))
    GLOBAL_CMM.discard(api.make_spec(x, method, backend="xla", **params).key())
    with ExecutionEngine(backend="xla") as eng:
        with ReductionService(eng, batch_window=0.05, max_queue=16) as svc:
            misses0 = GLOBAL_CMM.miss_count
            hits0 = GLOBAL_CMM.hit_count
            outs = [None] * len(trees)

            def worker(i):
                outs[i] = svc.compress(trees[i], _zfp_select)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(trees))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = svc.stats()
        # coalescing engaged: >1 request per stacked bucket, every leaf
        # after the first a real CMM hit (one plan build for the bucket)
        assert snap.stacked_buckets >= 1
        assert snap.batch_fill_ratio > 1.0
        assert snap.coalesced_requests >= 2
        assert GLOBAL_CMM.miss_count - misses0 == 1
        assert GLOBAL_CMM.hit_count - hits0 >= len(trees) - 1
        assert all(o is not None for o in outs)
        assert snap.completed == len(trees)
        assert snap.wait_s_mean >= 0.0


def test_service_overload_reject_and_block_timeout():
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService, ServiceOverloaded

    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(32, 32)).astype(np.float32)}
    gate = threading.Event()

    def stalling_select(key, arr):
        gate.wait(30)  # runs in the dispatcher: deterministically stalls it
        return _zfp_select(key, arr)

    with ExecutionEngine(backend="xla") as eng:
        svc = ReductionService(eng, max_queue=1, overload="reject",
                               batch_window=0.0)
        stalled = svc.submit_compress(tree, stalling_select)
        time.sleep(0.1)  # dispatcher pops `stalled`, stalls inside select
        queued = svc.submit_compress(tree, _zfp_select)  # fills the queue
        with pytest.raises(ServiceOverloaded):
            svc.submit_compress(tree, _zfp_select)
        assert svc.stats().rejected == 1
        gate.set()
        stalled.result()
        queued.result()
        svc.close()

        # block policy with a timeout: admission raises instead of hanging
        gate.clear()
        svc = ReductionService(eng, max_queue=1, overload="block",
                               batch_window=0.0)
        stalled = svc.submit_compress(tree, stalling_select)
        time.sleep(0.1)
        queued = svc.submit_compress(tree, _zfp_select)
        t0 = time.monotonic()
        with pytest.raises(ServiceOverloaded):
            svc.submit_compress(tree, _zfp_select, timeout=0.2)
        assert time.monotonic() - t0 >= 0.2
        gate.set()
        stalled.result()
        queued.result()
        svc.close()


def test_service_overload_shed_drops_oldest():
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService, ServiceOverloaded

    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(32, 32)).astype(np.float32)}
    gate = threading.Event()

    def stalling_select(key, arr):
        gate.wait(30)
        return _zfp_select(key, arr)

    with ExecutionEngine(backend="xla") as eng:
        svc = ReductionService(eng, max_queue=2, overload="shed",
                               batch_window=0.0)
        stalled = svc.submit_compress(tree, stalling_select)
        time.sleep(0.1)
        old = svc.submit_compress(tree, _zfp_select)
        mid = svc.submit_compress(tree, _zfp_select)
        new = svc.submit_compress(tree, _zfp_select)  # sheds `old`
        with pytest.raises(ServiceOverloaded, match="shed"):
            old.result(timeout=5)
        gate.set()
        stalled.result()
        mid.result()
        new.result()  # the newest request survived at the oldest's expense
        assert svc.stats().shed == 1
        svc.close()


def test_service_submit_after_close_raises():
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService

    with ExecutionEngine(backend="xla") as eng:
        svc = ReductionService(eng)
        svc.close()
        svc.close()  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit_compress({"w": np.zeros((8, 8), np.float32)},
                                _zfp_select)


def test_service_bad_request_fails_future_only():
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService

    rng = np.random.default_rng(0)
    with ExecutionEngine(backend="xla") as eng:
        with ReductionService(eng, batch_window=0.0) as svc:
            def broken_select(key, arr):
                raise ValueError("select blew up")

            bad = svc.submit_compress(
                {"w": rng.normal(size=(16, 16)).astype(np.float32)},
                broken_select,
            )
            good = svc.submit_compress(
                {"w": rng.normal(size=(16, 16)).astype(np.float32)},
                _zfp_select,
            )
            with pytest.raises(ValueError, match="select blew up"):
                bad.result(timeout=30)
            flat, stats = good.result(timeout=30)
            assert stats["compressed_leaves"] == 1
            assert svc.stats().failed == 1


@pytest.mark.slow
def test_service_soak_bit_identity_with_direct_api():
    """N client threads, mixed codecs + a per-thread unique-shape leaf:
    every response byte-identical to the direct API, coalesced and
    fallback paths both exercised, decompress round-trips exactly."""
    from repro.core import api
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService

    rng = np.random.default_rng(7)
    n_threads, n_rounds = 6, 3

    def make_tree(i, r):
        return {
            # shared shapes across threads: coalesce into stacked buckets
            "shared_zfp": rng.normal(size=(40, 48)).astype(np.float32),
            "shared_mgard": rng.normal(size=(24, 24)).astype(np.float32),
            # unique shape per (thread, round): mgard keeps the geometry, so
            # each is a singleton spec → exercises the per-leaf fallback
            "unique": rng.normal(size=(8 + i, 9 + r)).astype(np.float32),
            "raw": np.arange(4, dtype=np.int32),  # passthrough
        }

    def select(key, arr):
        if key in ("shared_mgard", "unique"):
            return "mgard", {"error_bound": 1e-2}
        if arr.dtype.kind == "f":
            return "zfp", {"rate": 16}
        return None

    trees = {(i, r): make_tree(i, r)
             for i in range(n_threads) for r in range(n_rounds)}
    with ExecutionEngine(backend="xla") as eng:
        with ReductionService(eng, batch_window=0.02, max_queue=64) as svc:
            outs = {}
            errs = []

            def worker(i):
                try:
                    for r in range(n_rounds):
                        outs[(i, r)] = svc.compress(trees[(i, r)], select)
                except Exception as e:  # pragma: no cover - surfaced below
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs
            snap = svc.stats()

            # bit-identity: every container equals the direct API's bytes,
            # coalesced buckets and per-leaf fallbacks alike
            for (i, r), (flat, _stats) in outs.items():
                direct, _ = api.compress_pytree(trees[(i, r)], select,
                                                engine=eng)
                for key, val in direct.items():
                    if isinstance(val, api.Compressed):
                        assert flat[key].to_bytes() == val.to_bytes(), (
                            i, r, key)
                    else:
                        np.testing.assert_array_equal(flat[key], val)

            # decompress through the service matches the direct inverse
            i_r = (0, 0)
            flat, _ = outs[i_r]
            via_svc = svc.decompress(flat, trees[i_r])
            via_api = api.decompress_pytree(flat, trees[i_r], engine=eng)
            for a, b in zip(jax.tree.leaves(via_svc), jax.tree.leaves(via_api)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # both execution shapes ran
        assert snap.stacked_leaves > 0
        assert snap.fallback_leaves > 0
        assert snap.completed == n_threads * n_rounds  # snapshot pre-decompress
        assert snap.batch_fill_ratio > 1.0  # coalescing demonstrably engaged


def test_service_priority_starvation_bound():
    """Regression (PR 10 priority lanes): interactive work admitted *behind*
    a saturating bulk backlog must jump the queue — its p99 wait stays below
    the backlog's — while the starvation bound keeps forcing bulk through
    between interactive dequeues (no bulk lockout)."""
    from repro.core.engine import ExecutionEngine
    from repro.serving import ReductionService

    rng = np.random.default_rng(0)
    tree = {"w": rng.normal(size=(16, 16)).astype(np.float32)}
    dwell = 0.15

    def slow_select(key, arr):
        time.sleep(dwell)  # runs in the dispatcher: one slow bulk dispatch
        return _zfp_select(key, arr)

    with ExecutionEngine(backend="xla") as eng:
        with ReductionService(eng, max_queue=64, batch_window=0.0,
                              max_batch_requests=1,
                              starvation_limit=2) as svc:
            svc.park_kv("starve", {"k": tree["w"]})  # interactive target
            # saturate the bulk lane: 6 dispatch cycles of `dwell` each
            bulk = [svc.submit_compress(tree, slow_select) for _ in range(6)]
            time.sleep(dwell / 2)  # dispatcher is inside bulk[0]'s select
            # interactive arrives LATE, behind the whole bulk backlog
            inter = [svc.submit_fetch_kv("starve") for _ in range(4)]
            for s in inter:
                assert "k" in s.result(timeout=60)
            for s in bulk:
                s.result(timeout=60)
            st = svc.stats()

    pi, pb = st.priorities["interactive"], st.priorities["bulk"]
    assert pi["admitted"] == pi["dispatched"] == 4
    assert pb["dispatched"] == 7  # 6 compresses + the park
    # the histograms exist and carry real samples
    for h in (pi, pb):
        assert h["samples"] >= 1
        assert 0.0 <= h["wait_p50"] <= h["wait_p99"]
        assert h["wait_p99"] <= h["wait_max"] + 1e-9
    # interactive jumped a 5-deep bulk backlog it arrived behind: even its
    # p99 wait undercuts bulk's (which eats the serial `dwell` dispatches)
    assert pi["wait_p99"] < pb["wait_p99"]
    # interactive p99 is bounded by the starvation design: at most one
    # in-progress dispatch + starvation_limit forced-bulk dwells + slack
    assert pi["wait_p99"] < 4 * dwell
    # and the bound engaged: bulk was forced through between interactives
    assert pb["forced"] >= 1
    # executor saw the same tags end-to-end (engine submissions are bulk)
    assert st.executor_priorities.get("bulk", {}).get("submitted", 0) >= 1
