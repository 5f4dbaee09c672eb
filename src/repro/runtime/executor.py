"""Device-aware asynchronous executor — the host side of HDEM fan-out.

The paper's multi-accelerator result (Fig. 16: 96% of theoretical speedup)
comes from running independent reductions concurrently on separate devices
while the shared runtime does no per-call allocation (CMM).  This module is
the submission machinery the execution engine (:mod:`repro.core.engine`)
schedules through:

  * :class:`DeviceExecutor` — a thread pool that round-robins work over an
    explicit device list; each task runs under ``jax.default_device`` for
    its assigned device, so JAX async dispatch overlaps device compute
    across the pool while host-side stages (codebook builds, container
    packing) overlap on threads.
  * :class:`Submission` — the ``submit()/result()`` future handle.  It also
    carries the device the work was placed on, which tests and benchmarks
    use to assert real fan-out.

Two lanes, mirroring the HDEM machine model: ``compute`` (per-device
reduction work, pool sized to the device count) and ``io`` (long-running
orchestration such as an async checkpoint save, single-threaded so saves
serialize against each other and can safely *wait on* compute-lane work
without deadlocking the pool).
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Sequence

import jax

from .spans import span

COMPUTE, IO = "compute", "io"

# Placement sentinel: run on the compute pool WITHOUT pinning a default
# device.  Used for whole-mesh work — e.g. the engine's stacked shard_map
# buckets, which span every data-axis device and must not be confined to
# one ring slot (a pinned default_device would fight the mesh sharding).
MESH = object()


class Submission:
    """Handle for one submitted task (the engine's future type)."""

    def __init__(self, future: Future, device: Any = None, lane: str = COMPUTE):
        self._future = future
        self.device = device
        self.lane = lane

    def done(self) -> bool:
        return self._future.done()

    def result(self, timeout: float | None = None) -> Any:
        return self._future.result(timeout)

    def exception(self, timeout: float | None = None):
        return self._future.exception(timeout)

    def add_done_callback(self, fn: Callable[["Submission"], None]) -> None:
        """Invoke ``fn(self)`` when the submission resolves (any outcome).

        The serving layer's request demultiplexer rides this: a coalesced
        bucket submission fans its per-leaf results back out to every
        participating request without a thread parked on ``result()``.
        """
        self._future.add_done_callback(lambda _f: fn(self))


class DeviceExecutor:
    """Round-robin device-aware async executor.

    ``devices`` is the placement ring — normally the mesh's ``data``-axis
    devices.  Tasks submitted without an explicit ``device`` are assigned the
    next ring slot; the task body runs with that device as JAX's default, so
    arrays it creates (and the compute they feed) land there.
    """

    def __init__(
        self,
        devices: Sequence[Any] | None = None,
        max_workers: int | None = None,
        io_workers: int = 1,
    ):
        self.devices = list(devices) if devices else list(jax.devices()[:1])
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers or max(2, len(self.devices)),
            thread_name_prefix="hpdr-compute",
        )
        self._io_pool = ThreadPoolExecutor(
            max_workers=io_workers, thread_name_prefix="hpdr-io"
        )
        self._rr = itertools.count()
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._closed = False
        self.submitted = 0
        self.completed = 0
        self.mesh_submitted = 0  # whole-mesh (device=MESH) tasks
        # per-lane service metrics: queue depth (submitted - started) and
        # cumulative time tasks spent waiting for a pool thread — the
        # executor-level half of the serving layer's ServiceStats surface
        self._lane_submitted = {COMPUTE: 0, IO: 0}
        self._lane_started = {COMPUTE: 0, IO: 0}
        self._lane_completed = {COMPUTE: 0, IO: 0}
        self._lane_wait_s = {COMPUTE: 0.0, IO: 0.0}
        # per-priority counters (priority is an opaque caller label — the
        # serving layer tags submissions "interactive"/"bulk" so operators
        # can see which class is eating each lane)
        self._prio: dict[str, dict[str, float]] = {}

    # ------------------------------------------------------------ submission

    def next_device(self) -> Any:
        return self.devices[next(self._rr) % len(self.devices)]

    def submit(
        self,
        fn: Callable,
        /,
        *args: Any,
        device: Any = None,
        lane: str = COMPUTE,
        priority: str | None = None,
        **kwargs: Any,
    ) -> Submission:
        """Schedule ``fn(*args, **kwargs)``; returns a :class:`Submission`.

        ``lane="io"`` routes to the single-threaded orchestration pool (used
        by async checkpoint saves); ``lane="compute"`` (default) round-robins
        over the device ring.  ``device=MESH`` runs on the compute pool with
        no default-device pin — for tasks that span the whole mesh (stacked
        shard_map buckets).  ``priority`` is an optional caller label
        accumulated into :meth:`priority_stats` (the serving layer tags
        interactive vs bulk work).  The task runs in a copy of the caller's
        context, so its spans carry the ``call`` of the entry point that
        submitted it.
        """
        if lane == IO:
            pool, dev = self._io_pool, None
        elif device is MESH:
            pool, dev = self._pool, None
        else:
            pool, dev = self._pool, (device if device is not None else self.next_device())
        lane_key = IO if lane == IO else COMPUTE
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    "DeviceExecutor is shut down: submit after close"
                )
            self.submitted += 1
            self._lane_submitted[lane_key] += 1
            if priority is not None:
                self._prio_entry(priority)["submitted"] += 1
            if device is MESH:
                self.mesh_submitted += 1
        t_sub = time.perf_counter()
        out: Future = Future()
        try:
            pool.submit(
                self._run, out, dev, lane_key, priority, t_sub,
                contextvars.copy_context(), fn, args, kwargs,
            )
        except RuntimeError as e:
            # lost the race with a concurrent shutdown(): undo the counters
            # so drain() still converges, and surface a clear error instead
            # of the pool's (or, worse, a hang on a never-run future)
            with self._lock:
                self.submitted -= 1
                self._lane_submitted[lane_key] -= 1
                if priority is not None:
                    self._prio_entry(priority)["submitted"] -= 1
                if device is MESH:
                    self.mesh_submitted -= 1
            raise RuntimeError(
                "DeviceExecutor is shut down: submit after close"
            ) from e
        return Submission(out, dev, lane)

    def _prio_entry(self, priority: str) -> dict[str, float]:
        # caller holds self._lock
        return self._prio.setdefault(
            priority,
            {"submitted": 0, "started": 0, "completed": 0, "wait_s": 0.0},
        )

    def submit_after(
        self,
        sub: Submission,
        fn: Callable,
        /,
        *args: Any,
        device: Any = None,
        lane: str = COMPUTE,
        priority: str | None = None,
        **kwargs: Any,
    ) -> Submission:
        """Schedule ``fn(sub.result(), *args, **kwargs)`` once ``sub`` resolves.

        The continuation is *submitted* only when the upstream future
        completes, so it never occupies a pool thread while waiting — the
        chunk-pipelined scheduler chains each chunk's io-lane serialization
        off its compute-lane future this way without ever blocking the
        single io thread on device work.  Upstream failures propagate to
        the returned :class:`Submission` without running ``fn``.
        """
        out: Future = Future()
        ctx = contextvars.copy_context()  # the caller's call, for the spans

        def _copy(src: Future) -> None:
            exc = src.exception()
            if exc is not None:
                out.set_exception(exc)
            else:
                out.set_result(src.result())

        def _chain(upstream: Future) -> None:
            exc = upstream.exception()
            if exc is not None:
                out.set_exception(exc)
                return
            try:
                inner = ctx.run(
                    self.submit, fn, upstream.result(), *args,
                    device=device, lane=lane, priority=priority, **kwargs
                )
            except BaseException as e:  # e.g. pool already shut down —
                # done-callbacks swallow exceptions, so surface it on the
                # returned Submission instead of hanging its waiters
                out.set_exception(e)
                return
            inner._future.add_done_callback(_copy)

        sub._future.add_done_callback(_chain)
        return Submission(out, device, lane)

    def _run(
        self, out: Future, device: Any, lane: str, priority: str | None,
        t_sub: float, ctx: contextvars.Context, fn: Callable, args: tuple,
        kwargs: dict,
    ) -> None:
        t_start = time.perf_counter()
        with self._lock:
            self._lane_started[lane] += 1
            self._lane_wait_s[lane] += t_start - t_sub
            if priority is not None:
                e = self._prio_entry(priority)
                e["started"] += 1
                e["wait_s"] += t_start - t_sub
        try:
            try:
                res = ctx.run(
                    self._task, device, lane, t_start - t_sub, fn, args, kwargs
                )
            except BaseException as exc:
                out.set_exception(exc)
            else:
                # resolve BEFORE counting the task complete: done-callbacks
                # (the serving demux, submit_after continuations) run inline
                # here, so drain() cannot return while a completion callback
                # is still fanning results out or chaining io-lane work
                out.set_result(res)
        finally:
            with self._lock:
                self.completed += 1
                self._lane_completed[lane] += 1
                if priority is not None:
                    self._prio_entry(priority)["completed"] += 1
                self._idle.notify_all()

    @staticmethod
    def _task(device: Any, lane: str, wait_s: float, fn: Callable,
              args: tuple, kwargs: dict) -> Any:
        with span("hpdr.executor.task", lane=lane, wait_us=int(wait_s * 1e6)):
            if device is None:
                return fn(*args, **kwargs)
            with jax.default_device(device):
                return fn(*args, **kwargs)

    def map(self, fn: Callable, items: Sequence[Any]) -> list[Any]:
        """Fan ``fn`` over ``items`` across the device ring; ordered results."""
        return [s.result() for s in [self.submit(fn, it) for it in items]]

    # ------------------------------------------------------------- lifecycle

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "devices": len(self.devices),
                "submitted": self.submitted,
                "completed": self.completed,
                "mesh_submitted": self.mesh_submitted,
            }

    def lane_stats(self) -> dict[str, dict[str, float]]:
        """Per-lane service counters: depth, in-flight and cumulative wait.

        ``depth`` is tasks submitted but not yet started (queued for a pool
        thread); ``wait_s`` is the total time started tasks spent in that
        queue.  The serving layer snapshots this into ``ServiceStats`` so
        operators can see which lane is the bottleneck under load.
        """
        with self._lock:
            return {
                lane: {
                    "submitted": self._lane_submitted[lane],
                    "started": self._lane_started[lane],
                    "completed": self._lane_completed[lane],
                    "depth": self._lane_submitted[lane] - self._lane_started[lane],
                    "inflight": self._lane_started[lane] - self._lane_completed[lane],
                    "wait_s": self._lane_wait_s[lane],
                }
                for lane in (COMPUTE, IO)
            }

    def priority_stats(self) -> dict[str, dict[str, float]]:
        """Per-priority counters for submissions tagged with ``priority=``.

        Keys are whatever labels callers used (the serving layer submits
        ``"interactive"`` and ``"bulk"``); values mirror the lane counters:
        submitted/started/completed, ``depth`` (queued for a thread) and
        cumulative ``wait_s``.
        """
        with self._lock:
            return {
                p: {
                    **e,
                    "depth": e["submitted"] - e["started"],
                    "inflight": e["started"] - e["completed"],
                }
                for p, e in self._prio.items()
            }

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every submitted task has completed; True on quiesce.

        Safe to call concurrently with ``submit`` (tasks submitted while
        draining extend the wait) and idempotent.  A task counts as
        complete only after its :class:`Submission` resolved and every
        ``add_done_callback`` ran — so continuations chained with
        ``submit_after`` are *submitted* (and therefore awaited) before the
        upstream task can satisfy drain.  A full dataflow chain quiesces
        under one ``drain()`` call; it cannot return between a submission
        completing and its io-lane completion callbacks finishing (the
        pre-PR-10 shutdown race).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._idle:
            while self.completed < self.submitted:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return False
                self._idle.wait(remaining)
        return True

    def shutdown(self, wait: bool = True) -> None:
        """Stop accepting work and (optionally) wait for in-flight tasks.

        Idempotent: repeated calls are no-ops.  Submissions racing a
        shutdown either run to completion or raise the clear
        ``RuntimeError`` from :meth:`submit` — they never hang.
        """
        with self._lock:
            already = self._closed
            self._closed = True
        if already:
            if wait:
                # second caller still honours wait=True semantics
                self._pool.shutdown(wait=True)
                self._io_pool.shutdown(wait=True)
            return
        self._pool.shutdown(wait=wait)
        self._io_pool.shutdown(wait=wait)
