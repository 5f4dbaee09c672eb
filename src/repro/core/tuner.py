"""Chunk/window auto-tuner — solve the HPDR §V-C schedule instead of guessing.

Combines the persisted machine calibration (``runtime/calibrate.py``) with
the lane-accurate stream simulator (``runtime/roofline.simulate_stream``,
built on ``core/pipeline.TimelineSimulator``) to pick the ``(chunk_size,
window)`` minimizing *predicted* makespan for a stream of ``total_elems``
elements:

  * candidate chunk sizes split the payload into k ∈ {1, 2, 3, 4, 6, 8,
    12, 16, 24, 32} chunks (every candidate is a real ``fixed`` schedule,
    so the winner is exactly reproducible with an explicit
    ``chunk_size=N``);
  * candidate windows come from ``windows`` (default 1–3); single-chunk
    payloads are pinned to ``window=1``, and the measured per-stream
    (``stream_t0``) and per-chunk (``chunk_t0``) fixed costs make
    over-splitting and premature pipelining visibly expensive — the
    `BENCH_pipeline.json` small-payload regression fix: a tiny payload's
    predicted overlap gain goes negative and the final guard degrades it
    to serial;
  * each candidate's makespan is simulated with the calibrated Φ /
    ``AffineCost`` stage costs plus the measured fixed costs and window
    overhead; the final guard re-simulates the winner at ``window=1`` and
    degrades to serial whenever predicted overlap gain is non-positive.

The model ranks; measurements decide.  For a store-backed full-auto
spec the tuner *races* the top-``_EXPLORE_K`` predicted candidates: the
first K real runs of that spec each execute a different candidate (fed
back by ``observe``), after which the plan is pinned to the measured
winner.  A spec run once gets the model's argmin, exactly as before;
a spec run repeatedly converges onto the true best schedule even where
the monotone Φ model mis-ranks (e.g. codecs whose throughput is
non-monotone in chunk size).

Without a calibration (and with measurement disabled or failing) the
tuner falls back to a deterministic heuristic: ~8 chunks, ``window=1``
when ≤ 2 chunks result, else the default window.  Auto-resolved settings
never enter the CMM plan key — a chunk schedule is just row slices, so
``chunk_size="auto"`` resolving to N builds byte-identical specs (and
hits the same cached plans) as an explicit ``chunk_size=N``.
"""

from __future__ import annotations

import threading
from dataclasses import asdict, dataclass

import numpy as np

from . import chunk_model

#: payload-split candidates: number of chunks each chunk-size candidate yields
DEFAULT_SPLITS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32)
DEFAULT_WINDOWS = (1, 2, 3)

#: at or below this many chunks, pipelining cannot pay its staging
#: overhead (a single chunk has nothing to overlap with); 2-chunk
#: schedules may still race ``window=2`` — the predicted-gain guard and
#: the measured fixed costs decide
SERIAL_CHUNK_FLOOR = 1

_HEURISTIC_SPLITS = 8
_MIN_CHUNK_ELEMS = 1 << 10

#: how many candidates a repeatedly-run spec explores with real
#: measurements before pinning the measured winner, and how many runs
#: each candidate gets (the first run of a fresh chunk spec carries
#: plan compilation; the second is warm — racing on cold walls mis-ranks)
_EXPLORE_K = 5
_EXPLORE_RUNS = 2
#: race exploration is stratified across chunk counts — the best
#: predicted candidate in each stratum races, because Φ extrapolation
#: across chunk size is the model's least-trusted axis (real codec
#: throughput can be non-monotone in chunk size: cache effects,
#: per-chunk table builds).  1 and 2 chunks are separate strata: they
#: are the configs the model most often confuses (whole-payload Φ vs
#: one overlap opportunity)
_RACE_STRATA = ((1, 1), (2, 2), (3, 8), (9, None))

_LOCK = threading.Lock()
#: solved plans keyed by the full stream spec — repeated auto streams of
#: the same payload resolve with a dict lookup, not a candidate sweep
_PLAN_CACHE: dict[tuple, "TunedPlan"] = {}
#: online measured/predicted residual per (method, dtype, total, itemsize)
#: — fed back by ChunkedPipeline after each auto run (see ``observe``)
_RESIDUALS: dict[tuple, float] = {}
#: candidate races per (method, dtype, total, itemsize):
#: {"order": [(chunk_elems, window), ...],
#:  "measured": {(ce, w): best wall}, "count": {(ce, w): runs}}
_RACES: dict[tuple, dict] = {}
#: process-wide count of candidate races started *with exploration runs*
#: (a race seeded from a persisted winner does not count).  The
#: race-persistence acceptance test asserts a warm process stays at 0.
RACES_STARTED = 0
#: residual changes smaller than this keep the cached plan (hysteresis)
_RESIDUAL_DEADBAND = 0.05


def clear_caches() -> None:
    """Drop solved plans, races, and residuals (calibration dir changed)."""
    with _LOCK:
        _PLAN_CACHE.clear()
        _RESIDUALS.clear()
        _RACES.clear()


def _residual_key(method, dtype, total_elems, itemsize) -> tuple:
    return (str(method), str(np.dtype(dtype).name),
            int(total_elems), int(itemsize))


def _persisted_race(
    method, dtype, total_elems, itemsize, backend, cands
) -> dict | None:
    """A pre-converged race seeded from the calibration store, or ``None``.

    A prior process that finished racing this exact spec geometry persisted
    its measured winner next to the calibration; a fresh process starts
    pinned to it — zero exploration runs — while ``observe`` feedback can
    still dethrone it.  A winner outside the current candidate grid (e.g.
    a changed ``c_limit_elems``) is ignored and the spec re-races.
    """
    from ..runtime import calibrate

    rec = calibrate.get_race_winner(method, dtype, total_elems, itemsize, backend)
    if rec is None:
        return None
    cand = (int(rec["chunk_elems"]), int(rec["window"]))
    wall = float(rec.get("measured_s", 0.0))
    if cand not in cands or wall <= 0:
        return None
    return {
        "order": [cand],
        "measured": {cand: wall},
        "count": {cand: _EXPLORE_RUNS},
        "persisted": True,
    }


def _persist_winner(
    method, dtype, total_elems, itemsize, backend, ce, w, wall
) -> None:
    """Record a converged race winner in the calibration store.

    Persisting only saves a later process its exploration runs, so a store
    that cannot be written (read-only home) does not fail the stream.
    """
    from ..runtime import calibrate

    try:
        calibrate.record_race_winner(
            method, dtype, total_elems, itemsize, backend,
            chunk_elems=ce, window=w, measured_s=wall,
        )
    except OSError:
        pass


def observe(
    plan: "TunedPlan", total_elems: int, itemsize: int, measured_s: float
) -> None:
    """Feed one measured auto-run wall back into future predictions.

    The calibrated model is fit on synthetic sweep geometry; real payload
    shapes (e.g. MGARD's dimension-dependent multigrid) can deviate.  The
    residual is the *minimum* observed measured/predicted ratio — the
    best-achieved wall, matching best-of-N measurement semantics (a first
    run inflated by plan compilation is superseded by the first warm
    run).  Predictions for the same spec then track reality to within
    run-to-run noise.  Updates inside a ±5% deadband are dropped so
    cached plans survive.
    """
    if plan is None or plan.source != "calibrated" or plan.method is None:
        return
    raw = plan.predicted_raw_s
    if not (raw and measured_s) or raw <= 0 or measured_s <= 0:
        return
    key = _residual_key(plan.method, plan.dtype or "float32",
                        total_elems, itemsize)
    new = float(np.clip(measured_s / raw, 0.1, 10.0))
    with _LOCK:
        invalidate = False
        # race lane: per-candidate best-achieved wall
        race = _RACES.get(key)
        if race is not None:
            cand = (int(plan.chunk_elems), int(plan.window))
            if cand in race["order"]:
                race["count"][cand] = race["count"].get(cand, 0) + 1
                prev = race["measured"].get(cand)
                if prev is None or measured_s < prev:
                    race["measured"][cand] = float(measured_s)
                    invalidate = True
        # residual lane: global measured/predicted scale
        old = _RESIDUALS.get(key)
        if old is not None:
            new = min(new, old)
        if old is None or abs(new / old - 1.0) > _RESIDUAL_DEADBAND:
            _RESIDUALS[key] = new
            invalidate = True
        if invalidate:
            for k in [k for k in _PLAN_CACHE if k[:4] == key]:
                del _PLAN_CACHE[k]


@dataclass(frozen=True)
class TunedPlan:
    """The tuner's decision plus the predictions that justified it."""

    chunk_elems: int
    window: int
    n_chunks: int
    predicted_s: float          # predicted makespan of the chosen schedule
    predicted_serial_s: float   # same chunking at window=1 (the guard rail)
    source: str                 # "calibrated" | "heuristic"
    method: str | None = None
    dtype: str | None = None
    predicted_raw_s: float = 0.0  # before the observed residual (``observe``)

    def to_dict(self) -> dict:
        return asdict(self)


def predict_makespan(
    cal,
    total_bytes: int,
    chunk_bytes: int,
    window: int,
    window_overhead_s: float = 0.0,
) -> tuple[float, int]:
    """Predicted stream makespan for one (chunk, window) candidate.

    ``cal`` is a :class:`~repro.runtime.calibrate.MethodCalibration`.
    Returns ``(seconds, n_chunks)``.
    """
    from ..runtime import roofline

    sizes = chunk_model.fixed_chunk_schedule(int(total_bytes), int(chunk_bytes))
    makespan, _ = roofline.simulate_stream(
        sizes,
        h2d_time=cal.h2d.time_for,
        compute_time=cal.phi.time_for,
        serialize_time=cal.serialize.time_for,
        window=window,
        window_overhead_s=window_overhead_s,
    )
    # fixed per-stream and per-chunk costs, then the calibrated
    # measured/simulated residual: lanes that contend (CPU backends) make
    # the raw pipelined simulation optimistic
    makespan += getattr(cal, "stream_t0", 0.0)
    makespan += getattr(cal, "chunk_t0", 0.0) * len(sizes)
    if window > 1:
        makespan *= getattr(cal, "overlap_scale", 1.0)
    else:
        makespan *= getattr(cal, "serial_scale", 1.0)
    return makespan, len(sizes)


def heuristic_plan(
    total_elems: int,
    itemsize: int,
    *,
    chunk_elems: int | None = None,
    c_limit_elems: int = 1 << 28,
    default_window: int = 2,
    method: str | None = None,
    dtype: str | None = None,
) -> TunedPlan:
    """Calibration-free fallback: ~8 chunks, serial when ≤ 2 result."""
    total_elems = max(1, int(total_elems))
    if chunk_elems is None:
        chunk_elems = -(-total_elems // _HEURISTIC_SPLITS)
        chunk_elems = int(np.clip(chunk_elems, _MIN_CHUNK_ELEMS, c_limit_elems))
    n = len(chunk_model.fixed_chunk_schedule(total_elems, chunk_elems))
    window = 1 if n <= SERIAL_CHUNK_FLOOR else max(1, int(default_window))
    return TunedPlan(
        chunk_elems=int(chunk_elems), window=window, n_chunks=n,
        predicted_s=0.0, predicted_serial_s=0.0, source="heuristic",
        method=method, dtype=dtype,
    )


def plan_stream(
    total_elems: int,
    itemsize: int,
    method: str | None = None,
    dtype: str = "float32",
    backend: str | None = None,
    *,
    chunk_elems: int | None = None,
    windows: tuple = DEFAULT_WINDOWS,
    c_limit_elems: int = 1 << 28,
    default_window: int = 2,
    measure: bool = True,
    params: dict | None = None,
    calibration=None,
    window_overhead_s: float | None = None,
) -> TunedPlan:
    """Solve for the (chunk_elems, window) minimizing predicted makespan.

    ``chunk_elems`` pins the chunk size (auto-window-only mode, e.g. the
    caller chose an explicit chunk); ``calibration`` injects a
    :class:`MethodCalibration` directly (tests / dry-run planning).  When
    no calibration can be obtained the deterministic heuristic decides.
    """
    total_elems = max(1, int(total_elems))
    itemsize = max(1, int(itemsize))
    # solved-plan cache: only for the store-backed path (injected
    # calibrations/overheads are test/dry-run inputs that may vary)
    use_cache = (calibration is None and window_overhead_s is None
                 and method is not None)
    cache_key = None
    if use_cache:
        cache_key = _residual_key(method, dtype, total_elems, itemsize) + (
            backend, chunk_elems, tuple(windows), default_window,
            c_limit_elems,
        )
        with _LOCK:
            cached = _PLAN_CACHE.get(cache_key)
        if cached is not None:
            return cached
    cal = calibration
    ov = window_overhead_s
    if cal is None and method is not None:
        from ..runtime import calibrate

        cal = calibrate.get_method_calibration(
            method, dtype, backend, measure=measure, params=params
        )
        if ov is None:
            ov = calibrate.window_overhead_s(backend)
    if cal is None:
        return heuristic_plan(
            total_elems, itemsize, chunk_elems=chunk_elems,
            c_limit_elems=c_limit_elems, default_window=default_window,
            method=method, dtype=dtype,
        )
    ov = float(ov or 0.0)

    total_bytes = total_elems * itemsize
    if chunk_elems is not None:
        cand_elems = [int(np.clip(chunk_elems, 1, c_limit_elems))]
    else:
        cand_elems = sorted(
            {
                int(np.clip(-(-total_elems // k), _MIN_CHUNK_ELEMS,
                            c_limit_elems))
                for k in DEFAULT_SPLITS
            },
            reverse=True,  # fewest chunks first: deterministic tie-breaks
        )

    # rank every (chunk, window) candidate by predicted makespan; ties
    # break toward smaller windows (serial is the safer schedule)
    cands: dict[tuple[int, int], tuple[float, int]] = {}  # (ce,w)->(mk,n)
    for ce in cand_elems:
        cb = ce * itemsize
        n = len(chunk_model.fixed_chunk_schedule(total_bytes, cb))
        ws = (1,) if n <= SERIAL_CHUNK_FLOOR else tuple(
            sorted({max(1, int(w)) for w in windows})
        )
        for w in ws:
            mk, n = predict_makespan(cal, total_bytes, cb, w, ov)
            cands.setdefault((ce, w), (mk, n))
    ranked = sorted(cands, key=lambda c: (cands[c][0], c[1]))
    ce, w = ranked[0]
    mk, n = cands[(ce, w)]
    serial_mk, _ = predict_makespan(cal, total_bytes, ce * itemsize, 1, 0.0)
    if w > 1 and mk >= serial_mk:
        # predicted overlap gain non-positive: degrade to the serial schedule
        w, mk = 1, serial_mk
        n = cands.get((ce, 1), (serial_mk, n))[1]

    def build(ce, w, n, mk, pred, pred_serial):
        return TunedPlan(
            chunk_elems=int(ce), window=int(w), n_chunks=int(n),
            predicted_s=pred, predicted_serial_s=pred_serial,
            source="calibrated", method=method,
            dtype=str(np.dtype(dtype).name), predicted_raw_s=mk,
        )

    if not use_cache:
        return build(ce, w, n, mk, mk, serial_mk)

    rkey = _residual_key(method, dtype, total_elems, itemsize)
    with _LOCK:
        residual = _RESIDUALS.get(rkey, 1.0)

    race = None
    if chunk_elems is None:
        # candidate race: the model winner, the best predicted candidate
        # in each chunk-count stratum, and the winner's serial twin (so
        # "never worse than serial" is measured, not assumed)
        global RACES_STARTED
        with _LOCK:
            race = _RACES.get(rkey)
        persisted = None
        if race is None:
            # store lookup outside the tuner lock (it takes the
            # calibration store's own lock)
            persisted = _persisted_race(
                method, dtype, total_elems, itemsize, backend, cands
            )
        with _LOCK:
            race = _RACES.get(rkey)
            if race is None:
                if persisted is not None:
                    race = persisted
                else:
                    order = [(ce, w)]
                    for lo, hi in _RACE_STRATA:
                        pick = next(
                            (c for c in ranked
                             if lo <= cands[c][1] and (hi is None
                                                       or cands[c][1] <= hi)),
                            None,
                        )
                        if pick is not None and pick not in order:
                            order.append(pick)
                    twin = (ce, 1)
                    if twin in cands and twin not in order:
                        order.append(twin)
                    order = order[:_EXPLORE_K]
                    race = {"order": order, "measured": {}, "count": {}}
                    RACES_STARTED += 1
                _RACES[rkey] = race
            measured = dict(race["measured"])
            counts = dict(race["count"])
        unexplored = [c for c in race["order"]
                      if c in cands and counts.get(c, 0) < _EXPLORE_RUNS]
        if unexplored:
            # explore: run the next untried candidate for real; its wall
            # comes back through ``observe``
            ce, w = unexplored[0]
            mk, n = cands[(ce, w)]
            serial_mk = cands.get((ce, 1), (mk, n))[0]
            return build(ce, w, n, mk, mk * residual, serial_mk * residual)
        if measured:
            # exploit: pin the measured winner; the prediction IS its
            # best-achieved wall (the converged empirical cost model)
            ce, w = min(measured, key=measured.get)
            mk, n = cands.get((ce, w), (mk, n))
            pred = measured[(ce, w)]
            pred_serial = measured.get(
                (ce, 1), cands.get((ce, 1), (mk, n))[0] * residual)
            plan = build(ce, w, n, mk, pred, pred_serial)
            with _LOCK:
                _PLAN_CACHE[cache_key] = plan
            # persist the converged winner so fresh processes start here
            # (idempotent: re-pinning the same winner is a no-op save)
            _persist_winner(
                method, dtype, total_elems, itemsize, backend, ce, w, pred
            )
            return plan

    plan = build(ce, w, n, mk, mk * residual, serial_mk * residual)
    with _LOCK:
        _PLAN_CACHE[cache_key] = plan
    return plan
