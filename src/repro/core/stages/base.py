"""Stage-graph codec pipeline — reductions as composable device stages.

HPDR's architectural claim (paper §III, Fig. 1) is that a reduction is a
*pipeline of composable stages* — decorrelate → quantize → entropy → pack —
that runs end-to-end on the device, with host↔device traffic reduced to the
few metadata-scale synchronisation points the algorithm genuinely needs
(2.3% of runtime in the paper's measurement).  This package makes that
structure explicit:

  * :class:`Stage` — the protocol one pipeline stage implements.  *Device*
    stages expose pure, jittable ``apply``/``invert`` transformations of the
    flowing state; *host* stages are the explicit synchronisation points
    (e.g. canonical-codebook construction from the device histogram) and
    declare exactly which state keys they pull to host (``fetches``) — the
    quantity the transfer-bytes benchmark tracks.
  * :class:`StageGraph` — a codec's declarative stage composition plus the
    state keys its container serialiser consumes (``finish_keys``).
  * :class:`CompiledPipeline` — what ``StageGraph.compile(plan)`` produces
    and ``ReductionPlan.pipeline`` stores: maximal runs of device stages
    fused into **one jitted executable per segment** (host barriers are the
    only cut points), with liveness-pruned inputs/outputs so intermediate
    arrays never leave the device.

The same compiled segments serve both execution shapes: the per-leaf path
(:meth:`CompiledPipeline.run`) and the execution engine's stacked
``shard_map`` path (:meth:`CompiledPipeline.run_batched`), where every
device segment is vmapped over the leaf axis and the host stages loop over
metadata-scale per-leaf fetches.  That is what lets the host-staged codecs
(MGARD, Huffman) join ZFP on the engine's stacked fan-out: the only host
work left per bucket is codebook construction.

State is a flat ``dict[str, Array]``; stages declare ``reads``/``writes``
so the compiler can partition and prune without tracing.  Statics (e.g. the
packed word-buffer size) flow through :class:`CallEnv` — host stages set
them, and each later segment is re-jitted per distinct static tuple (with
:meth:`Stage.jit_statics` rounding, so e.g. word buffers bucket to 4 KiB
multiples instead of retracing per byte-length).
"""

from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import adapters
from ...runtime.spans import span


# ---------------------------------------------------------------------------
# transfer accounting
# ---------------------------------------------------------------------------


@dataclass
class TransferStats:
    """Host↔device byte accounting for pipeline executions.

    Every crossing goes through :meth:`upload` or :meth:`fetch`, which
    count it and mark it on the trace as an ``hpdr.h2d``/``hpdr.d2h`` span
    with its ``bytes``.  ``d2h`` counts exactly the bytes host stages fetch
    plus the bytes the container serialiser pulls (:meth:`LeafView.fetch`);
    ``h2d`` counts inputs that come from the host plus the operands host
    stages ship.  An array already on the device crosses nothing and is
    not counted.
    """

    h2d: int = 0
    d2h: int = 0

    def upload(self, value: Any) -> jax.Array:
        """``value`` on the device; a host array's bytes count as H2D."""
        if isinstance(value, jax.Array):
            return value
        value = np.asarray(value)
        nbytes = value.size * jax.dtypes.canonicalize_dtype(value.dtype).itemsize
        with span("hpdr.h2d", bytes=nbytes):
            arr = jnp.asarray(value)
        self.h2d += nbytes
        return arr

    def fetch(self, value: Any) -> np.ndarray:
        """``value`` on the host; a device array's bytes count as D2H."""
        if not isinstance(value, jax.Array):
            return np.asarray(value)
        with span("hpdr.d2h", bytes=value.nbytes):
            out = np.asarray(value)
        self.d2h += out.nbytes
        return out


# ---------------------------------------------------------------------------
# per-call environment
# ---------------------------------------------------------------------------


class CallEnv:
    """Mutable per-call environment threaded through one pipeline run.

    Host stages write three kinds of products here:
      * ``meta``     — per-call metadata destined for the container header
                       (per-stage sections, see :meth:`StageGraph.describe`);
      * ``operands`` — host-built arrays later device segments consume
                       (canonical codebook tables, bin schedules), shipped
                       H2D once per call;
      * ``statics``  — python ints later segments are specialised on
                       (packed word count, alphabet size).
    """

    __slots__ = ("plan", "spec", "meta", "operands", "statics", "transfers")

    def __init__(self, plan: Any, transfers: TransferStats | None = None):
        self.plan = plan
        self.spec = plan.spec
        self.meta: dict[str, Any] = {}
        self.operands: dict[str, Any] = {}
        self.statics: dict[str, int] = dict(plan.meta.get("statics", ()) or {})
        self.transfers = transfers if transfers is not None else TransferStats()


class TraceEnv:
    """What a device stage sees inside a fused jitted segment: traced
    operand/workspace arrays plus the segment's static values."""

    __slots__ = ("statics", "backend", "_operands", "_workspace")

    def __init__(self, statics: dict, backend: str, operands: dict, workspace: dict):
        self.statics = statics
        self.backend = backend
        self._operands = operands
        self._workspace = workspace

    def static(self, name: str) -> Any:
        return self.statics[name]

    def operand(self, name: str) -> jax.Array:
        return self._operands[name]

    def workspace(self, name: str) -> jax.Array:
        return self._workspace[name]


# ---------------------------------------------------------------------------
# the Stage protocol
# ---------------------------------------------------------------------------


class Stage:
    """One named, composable pipeline stage.

    Device stages (``device = True``) implement :meth:`apply` (and
    :meth:`invert` for the decode direction) as *pure jittable* functions:
    they may only read the declared ``reads`` state keys, ``operands``,
    ``workspace`` buffers and ``statics``, and must return the declared
    ``writes``.  The compiler fuses consecutive device stages into one
    jitted executable — a stage never implies a dispatch boundary.

    Host stages (``device = False``) implement :meth:`host_apply`.  They are
    the explicit synchronisation points of the graph: ``fetches`` names the
    state keys pulled D2H (metadata scale by design), and anything they put
    in ``env.operands`` is shipped H2D for the segments that follow.

    ``stage_meta`` is the stage's metadata contract: the static,
    plan-derived parameters recorded per stage in the container header so a
    reader can reconstruct the pipeline that wrote a stream.
    """

    name: str = "stage"
    device: bool = True
    reads: tuple[str, ...] = ()
    writes: tuple[str, ...] = ()
    operands: tuple[str, ...] = ()
    workspace: tuple[str, ...] = ()
    donates: tuple[str, ...] = ()
    statics: tuple[str, ...] = ()
    fetches: tuple[str, ...] = ()         # host stages only
    static_outputs: tuple[str, ...] = ()  # host stages only

    # -- decode direction ----------------------------------------------------
    # Device stages with a non-empty ``inv_writes`` participate in the
    # compiled inverse pipeline: ``invert`` is fused exactly like ``apply``,
    # with its own reads/writes/operands/statics declarations.  Host stages
    # implement ``host_prepare`` instead of a device fetch: the decode
    # direction has *no* device→host synchronisation points — everything a
    # host stage contributed at encode time (codebooks, bin schedules) is in
    # the container, so preparation only reads ``env.meta`` and ships
    # operands.  That is why a codec's whole decode chain fuses into a
    # single jitted executable (see CompiledPipeline.invert).
    inv_reads: tuple[str, ...] = ()
    inv_writes: tuple[str, ...] = ()
    inv_operands: tuple[str, ...] = ()
    inv_workspace: tuple[str, ...] = ()
    inv_donates: tuple[str, ...] = ()
    inv_statics: tuple[str, ...] = ()
    inv_static_outputs: tuple[str, ...] = ()  # host stages only

    def planned(self, plan: Any) -> None:
        """Plan-time hook: record plan-constant statics/workspace/meta."""

    # -- device stages -------------------------------------------------------

    def apply(self, env: TraceEnv, state: dict) -> dict:
        raise NotImplementedError(f"{self.name} is not a device stage")

    def invert(self, env: TraceEnv, state: dict) -> dict:
        raise NotImplementedError(f"{self.name} has no inverse")

    # -- host stages ---------------------------------------------------------

    def host_apply(self, env: CallEnv, fetched: dict[str, np.ndarray]) -> None:
        raise NotImplementedError(f"{self.name} is not a host stage")

    def host_prepare(self, env: CallEnv) -> None:
        """Decode-direction preparation: derive operands/statics from the
        container metadata in ``env.meta`` (never a device fetch)."""

    def merge_static(self, name: str, values: Sequence[int]) -> int:
        """Combine per-leaf statics for a stacked batch (default: must agree)."""
        v0 = values[0]
        if any(v != v0 for v in values):
            raise ValueError(
                f"stage {self.name}: static {name!r} differs across leaves "
                f"({sorted(set(values))}); override merge_static to combine"
            )
        return v0

    def jit_statics(self, statics: dict[str, int]) -> dict[str, int]:
        """Statics as baked into the jitted segment (hook for bucketing
        data-dependent sizes so traces are reused across calls)."""
        return statics

    def stage_meta(self, plan: Any) -> dict[str, Any]:
        return {}


# ---------------------------------------------------------------------------
# graph → compiled pipeline
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StageGraph:
    """A codec's declarative stage composition.

    ``finish_keys`` are the state keys the codec's container serialiser may
    fetch after the run — the liveness roots that keep segment outputs
    alive.  ``inputs`` names the initial state (default: the raw ``data``
    array).
    """

    stages: tuple[Stage, ...]
    finish_keys: tuple[str, ...]
    inputs: tuple[str, ...] = ("data",)
    # decode direction: ``inv_inputs`` names the state the codec rebuilds
    # from container sections (empty: the graph has no compiled inverse);
    # ``inv_finish`` the keys the inverse run must produce; ``inv_pads``
    # rounds named state arrays up to a size bucket before the fused
    # executable sees them (bounds retraces across stream sizes, the decode
    # analogue of BitPack.jit_statics); ``inv_fills`` sets the pad fill
    # value per key (e.g. an out-of-range sentinel for scatter indices).
    inv_inputs: tuple[str, ...] = ()
    inv_finish: tuple[str, ...] = ("data",)
    inv_pads: tuple[tuple[str, int], ...] = ()
    inv_fills: tuple[tuple[str, int], ...] = ()

    def compile(self, plan: Any) -> "CompiledPipeline":
        return CompiledPipeline(self, plan)

    def describe(self, plan: Any) -> list[dict]:
        """Per-stage metadata layout recorded in the container header."""
        out = []
        for st in self.stages:
            entry = {"stage": st.name, "kind": "device" if st.device else "host"}
            entry.update(st.stage_meta(plan))
            out.append(entry)
        return out


@dataclass
class _Segment:
    """A maximal run of device stages fused into one jitted executable.

    ``direction`` selects which side of the Stage protocol the fused
    executable calls: ``"fwd"`` runs ``apply`` in graph order, ``"inv"``
    runs ``invert`` with ``stages`` already stored in inverse execution
    order (the compiler reverses the graph when partitioning).
    """

    index: int
    stages: list[Stage]
    direction: str = "fwd"
    in_keys: tuple[str, ...] = ()
    out_keys: tuple[str, ...] = ()
    operand_keys: tuple[str, ...] = ()
    workspace_keys: tuple[str, ...] = ()
    donate_keys: tuple[str, ...] = ()
    static_keys: tuple[str, ...] = ()

    @property
    def name(self) -> str:
        sep = "+" if self.direction == "fwd" else "·"
        base = sep.join(st.name for st in self.stages)
        return base if self.direction == "fwd" else f"invert[{base}]"

    @property
    def jit_name(self) -> str:
        """:attr:`name` as an identifier: the segment's jitted module reads
        ``jit_<jit_name>`` on the trace and in compile logs."""
        return re.sub(r"\W+", "_", self.name).strip("_")


def _dedup(items) -> tuple:
    seen, out = set(), []
    for it in items:
        if it not in seen:
            seen.add(it)
            out.append(it)
    return tuple(out)


class CompiledPipeline:
    """Compiled stage graph bound to one :class:`ReductionPlan`.

    Segment executables are built lazily per distinct static tuple and
    cached here (the plan lives in the CMM, so the cache has plan lifetime —
    the stage-graph analogue of the paper's cached plans).  ``run`` executes
    the per-leaf path; ``run_batched`` drives a stacked leaf batch, with the
    engine supplying the mesh mapping for each device segment.
    """

    def __init__(self, graph: StageGraph, plan: Any):
        self.graph = graph
        self.plan = plan
        self._lock = threading.Lock()
        self._exe: dict[tuple, Callable] = {}
        for st in graph.stages:
            st.planned(plan)
        self.steps = self._partition()
        self.inv_preps, self.inv_segments = self._partition_inverse()
        plan.meta.setdefault("stage_graph", graph.describe(plan))

    @property
    def invertible(self) -> bool:
        """True when the graph compiled a device-resident decode direction."""
        return bool(self.inv_segments)

    # -- compilation ---------------------------------------------------------

    def _partition(self) -> list[Any]:
        """Group consecutive device stages; compute liveness per boundary."""
        groups: list[Any] = []
        for st in self.graph.stages:
            if st.device and groups and isinstance(groups[-1], _Segment):
                groups[-1].stages.append(st)
            elif st.device:
                groups.append(_Segment(index=len(groups), stages=[st]))
            else:
                groups.append(st)

        # keys needed after each step: later reads/fetches + finish keys
        needed_after: list[set[str]] = []
        needed = set(self.graph.finish_keys)
        for step in reversed(groups):
            needed_after.append(set(needed))
            if isinstance(step, _Segment):
                for st in step.stages:
                    needed |= set(st.reads)
            else:
                needed |= set(step.fetches)
        needed_after.reverse()

        available = set(self.graph.inputs)
        for step, after in zip(groups, needed_after):
            if not isinstance(step, _Segment):
                missing = set(step.fetches) - available
                if missing:
                    raise ValueError(
                        f"host stage {step.name} fetches {sorted(missing)} "
                        "which no earlier stage produces"
                    )
                continue
            written: set[str] = set()
            ins: list[str] = []
            for st in step.stages:
                for k in st.reads:
                    if k not in written:
                        if k not in available:
                            raise ValueError(
                                f"stage {st.name} reads {k!r} which no earlier "
                                "stage produces"
                            )
                        ins.append(k)
                written |= set(st.writes)
            step.in_keys = _dedup(ins)
            step.out_keys = _dedup(k for k in written if k in after)
            step.operand_keys = _dedup(k for st in step.stages for k in st.operands)
            step.workspace_keys = _dedup(k for st in step.stages for k in st.workspace)
            step.donate_keys = _dedup(k for st in step.stages for k in st.donates)
            step.static_keys = _dedup(k for st in step.stages for k in st.statics)
            available |= written
        return groups

    def _partition_inverse(self) -> tuple[list[Stage], list[_Segment]]:
        """Compile the decode direction: host prepares + fused inverse runs.

        Host stages become *prepare* steps (container metadata → operands/
        statics, no device fetch), hoisted ahead of all device work; every
        device stage with a declared inverse joins a maximal inverse run,
        walking the graph backwards.  Stages without an inverse contract
        (histograms, scans — encode-only analysis) are identities in the
        decode direction and never cut a run, so with no host barriers left
        the whole decode chain typically fuses into ONE jitted executable —
        the mirror image of the forward direction's segment structure.
        """
        if not self.graph.inv_inputs:
            return [], []
        preps = [st for st in self.graph.stages if not st.device]
        segs: list[_Segment] = []
        for st in reversed(self.graph.stages):
            if not (st.device and st.inv_writes):
                continue
            if segs:
                segs[-1].stages.append(st)
            else:
                segs.append(_Segment(index=0, stages=[st], direction="inv"))
        available = set(self.graph.inv_inputs)
        for seg in segs:
            written: set[str] = set()
            ins: list[str] = []
            for st in seg.stages:
                for k in st.inv_reads:
                    if k not in written:
                        if k not in available:
                            raise ValueError(
                                f"inverse of {st.name} reads {k!r} which "
                                "neither inv_inputs nor an earlier inverse "
                                "stage produces"
                            )
                        ins.append(k)
                written |= set(st.inv_writes)
            seg.in_keys = _dedup(ins)
            seg.out_keys = _dedup(
                k for k in self.graph.inv_finish if k in written
            )
            seg.operand_keys = _dedup(
                k for st in seg.stages for k in st.inv_operands
            )
            seg.workspace_keys = _dedup(
                k for st in seg.stages for k in st.inv_workspace
            )
            seg.donate_keys = _dedup(
                k for st in seg.stages for k in st.inv_donates
            )
            seg.static_keys = _dedup(
                k for st in seg.stages for k in st.inv_statics
            )
            available |= written
        missing = set(self.graph.inv_finish) - available
        if missing:
            raise ValueError(
                f"inverse pipeline never produces {sorted(missing)}"
            )
        return preps, segs

    def _seg_statics(self, seg: _Segment, statics: dict) -> tuple[tuple, dict]:
        sub = {k: statics[k] for k in seg.static_keys}
        for st in seg.stages:
            sub = st.jit_statics(sub)
        return tuple(sorted(sub.items())), sub

    def _raw_fn(self, seg: _Segment, jit_statics: dict, with_ws_out: bool) -> Callable:
        backend = self.plan.spec.backend
        inverse = seg.direction == "inv"

        def fn(state_vals, operand_vals, ws_vals):
            state = dict(zip(seg.in_keys, state_vals))
            env = TraceEnv(
                jit_statics, backend,
                dict(zip(seg.operand_keys, operand_vals)),
                dict(zip(seg.workspace_keys, ws_vals)),
            )
            for st in seg.stages:
                state.update(st.invert(env, state) if inverse
                             else st.apply(env, state))
            outs = tuple(state[k] for k in seg.out_keys)
            if not with_ws_out:
                return outs
            return outs, tuple(env._workspace[k] for k in seg.workspace_keys)

        fn.__name__ = fn.__qualname__ = seg.jit_name
        return fn

    def segment_exe(self, seg: _Segment, statics: dict, batched: bool) -> Callable:
        """Jitted (serial) or vmapped-raw (batched) segment executable.

        Serial executables donate the plan workspace where the platform
        supports it (the PR-2 recycle contract); batched executables return
        ``(outs, workspace)`` with the workspace un-vmapped, leaving the
        broadcast-vs-donate decision to the engine's mesh mapper.
        """
        key_statics, jit_statics = self._seg_statics(seg, statics)
        key = (seg.index, seg.direction, key_statics, batched)
        with self._lock:
            exe = self._exe.get(key)
        if exe is not None:
            return exe
        if batched:
            # Workspace rides along un-vmapped (one copy per shard) and is
            # passed back out, so the engine's mesh mapper can either drop
            # it (broadcast semantics) or donate per-shard stacks and
            # recycle the returned buffers (see ExecutionEngine).
            raw = self._raw_fn(seg, jit_statics, with_ws_out=True)
            exe = jax.vmap(raw, in_axes=(0, 0, None), out_axes=(0, None))
        else:
            raw = self._raw_fn(seg, jit_statics, with_ws_out=True)
            donate = ()
            if seg.donate_keys and seg.donate_keys == seg.workspace_keys:
                donate = (2,)
            exe = adapters.donating_jit(raw, donate_argnums=donate)
        with self._lock:
            exe = self._exe.setdefault(key, exe)
        return exe

    # -- execution: per-leaf -------------------------------------------------

    def run(
        self,
        state0: dict[str, Any],
        env: CallEnv | None = None,
        workspace: dict[str, Any] | None = None,
    ) -> tuple[dict[str, Any], CallEnv]:
        """Execute the encode direction for one leaf.

        Device segments run as single fused dispatches, each in an
        ``hpdr.segment`` span; host stages fetch exactly their declared keys
        (counted in ``env.transfers``) inside an ``hpdr.host_stage`` span.

        ``workspace`` overrides the plan's shared workspace buffers with a
        caller-owned dict — the chunk-pipelined scheduler passes one such
        dict per in-flight slot, so concurrent chunk encodes on one plan
        neither contend on ``plan.lock`` nor donate each other's buffers;
        donated-and-returned buffers are recycled back into the caller's
        dict (the per-slot analogue of ``ReductionPlan.recycle``).
        """
        plan = self.plan
        env = env or CallEnv(plan)
        state = {k: env.transfers.upload(v) for k, v in state0.items()}
        for step in self.steps:
            if not isinstance(step, _Segment):
                with span("hpdr.host_stage", stage=step.name):
                    fetched = {k: env.transfers.fetch(state[k]) for k in step.fetches}
                    step.host_apply(env, fetched)
                continue
            with span("hpdr.segment", segment=step.name):
                operand_vals = tuple(
                    self._ship(env, k) for k in step.operand_keys
                )
                exe = self.segment_exe(step, env.statics, batched=False)
                state_vals = tuple(state[k] for k in step.in_keys)
                if step.workspace_keys and workspace is not None:
                    # caller-owned slot workspace: no plan.lock needed —
                    # the slot is exclusively ours for this run
                    ws_vals = tuple(
                        workspace[k] for k in step.workspace_keys
                    )
                    outs, ws_out = exe(state_vals, operand_vals, ws_vals)
                    for k, buf in zip(step.workspace_keys, ws_out):
                        workspace[k] = buf
                elif step.workspace_keys:
                    # Read the workspace inside the lock: a concurrent
                    # donating dispatch invalidates and replaces these
                    # buffers under the same lock, so a reference captured
                    # outside it could be a use-after-donate.
                    with plan.lock:
                        ws_vals = tuple(
                            plan.workspace[k] for k in step.workspace_keys
                        )
                        outs, ws_out = exe(state_vals, operand_vals, ws_vals)
                        for k, buf in zip(step.workspace_keys, ws_out):
                            plan.recycle(k, buf)
                else:
                    outs, _ = exe(state_vals, operand_vals, ())
                state.update(zip(step.out_keys, outs))
        return state, env

    @staticmethod
    def _ship(env: CallEnv, name: str) -> jax.Array:
        """A host stage's operand on the device; the first use of a call
        uploads it and later uses find it there."""
        arr = env.operands[name] = env.transfers.upload(env.operands[name])
        return arr

    @staticmethod
    def _upload_stacked(transfers: TransferStats, envs: list[CallEnv],
                        keys: tuple[str, ...], stacked: dict) -> tuple:
        """Per-leaf operands ``keys``, stacked and uploaded once a batch."""
        for k in keys:
            if k not in stacked:
                stacked[k] = transfers.upload(_stack_pad(
                    [np.asarray(e.operands[k]) for e in envs]
                ))
        return tuple(stacked[k] for k in keys)

    # -- execution: stacked batch (engine shard_map path) --------------------

    def run_batched(
        self,
        state0: dict[str, Any],
        envs: list[CallEnv],
        device_mapper: Callable,
        transfers: TransferStats,
    ) -> dict[str, Any]:
        """Drive a stacked leaf batch through the pipeline.

        ``state0`` holds arrays with a leading leaf axis of ``len(envs)``;
        ``device_mapper(seg, vfn, state_vals, operand_vals, ws_vals)`` is
        supplied by the execution engine and wraps the vmapped segment in
        its mesh ``shard_map``.  Host stages loop over per-leaf fetches —
        metadata scale — and their statics are merged across leaves
        (:meth:`Stage.merge_static`) before the next segment is specialised.
        """
        plan = self.plan
        state = {k: transfers.upload(v) for k, v in state0.items()}
        merged: dict[str, int] = dict(envs[0].statics)
        stacked_ops: dict[str, jax.Array] = {}
        for step in self.steps:
            if not isinstance(step, _Segment):
                with span("hpdr.host_stage", stage=step.name):
                    fetched = {k: transfers.fetch(state[k]) for k in step.fetches}
                    for i, env in enumerate(envs):
                        step.host_apply(env, {k: fetched[k][i] for k in step.fetches})
                for name in step.static_outputs:
                    merged[name] = step.merge_static(
                        name, [env.statics[name] for env in envs]
                    )
                continue
            with span("hpdr.segment", segment=step.name):
                operand_vals = self._upload_stacked(
                    transfers, envs, step.operand_keys, stacked_ops
                )
                vfn = self.segment_exe(step, merged, batched=True)
                state_vals = tuple(state[k] for k in step.in_keys)
                if step.workspace_keys:
                    # Dispatch under plan.lock: the serial path *donates*
                    # these buffers under the same lock, so a concurrent
                    # per-leaf encode can neither invalidate the buffer we
                    # captured before our dispatch nor donate it mid-window
                    # (after dispatch XLA holds its own reference).
                    with plan.lock:
                        ws_vals = tuple(
                            plan.workspace[k] for k in step.workspace_keys
                        )
                        outs = device_mapper(
                            step, vfn, state_vals, operand_vals, ws_vals
                        )
                else:
                    outs = device_mapper(step, vfn, state_vals, operand_vals, ())
                state.update(zip(step.out_keys, outs))
        return state

    @property
    def device_segments(self) -> list[_Segment]:
        return [s for s in self.steps if isinstance(s, _Segment)]

    # -- execution: decode direction ----------------------------------------

    def _pad_state(self, state: dict) -> dict:
        """Round ``inv_pads`` keys up to their bucket on device (a cheap
        concat, no H2D) so nearby stream sizes share one fused trace."""
        for key, mult in self.graph.inv_pads:
            arr = state.get(key)
            if arr is None:
                continue
            pad = (-arr.shape[0]) % mult
            if pad:
                state[key] = jnp.concatenate(
                    [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)]
                )
        return state

    def invert(
        self,
        state0: dict[str, Any],
        env: CallEnv | None = None,
    ) -> tuple[dict[str, Any], CallEnv]:
        """Execute the decode direction for one leaf.

        ``state0`` is the container-section state (``graph.inv_inputs``);
        ``env.meta`` must already hold the stream's metadata.  Host stages
        run as *prepare* steps — metadata-only, no device fetch — then the
        fused inverse segments run back-to-back, so H2D is exactly the
        compressed sections plus the prepared operands, and nothing comes
        back D2H until the caller looks at the output.
        """
        if not self.invertible:
            raise NotImplementedError(
                f"codec {self.plan.spec.method!r} has no compiled inverse"
            )
        plan = self.plan
        env = env or CallEnv(plan)
        for st in self.inv_preps:
            with span("hpdr.host_stage", stage=st.name):
                st.host_prepare(env)
        state = self._pad_state(
            {k: env.transfers.upload(v) for k, v in state0.items()}
        )
        for seg in self.inv_segments:
            with span("hpdr.segment", segment=seg.name):
                operand_vals = tuple(
                    self._ship(env, k) for k in seg.operand_keys
                )
                exe = self.segment_exe(seg, env.statics, batched=False)
                state_vals = tuple(state[k] for k in seg.in_keys)
                if seg.workspace_keys:
                    # workspace read under the lock — see run() for the
                    # use-after-donate rationale
                    with plan.lock:
                        ws_vals = tuple(
                            plan.workspace[k] for k in seg.workspace_keys
                        )
                        outs, ws_out = exe(state_vals, operand_vals, ws_vals)
                        for k, buf in zip(seg.workspace_keys, ws_out):
                            plan.recycle(k, buf)
                else:
                    outs, _ = exe(state_vals, operand_vals, ())
                state.update(zip(seg.out_keys, outs))
        return state, env

    def invert_batched(
        self,
        states: list[dict[str, Any]],
        envs: list[CallEnv],
        device_mapper: Callable,
        transfers: TransferStats,
    ) -> dict[str, Any]:
        """Drive a stacked leaf batch through the decode direction.

        ``states`` holds one container-section state dict per leaf; they are
        stacked here with ``inv_fills`` padding (e.g. out-of-range scatter
        sentinels) and ``inv_pads`` bucketing, so streams of differing sizes
        share one vmapped trace.  Host prepares run per leaf — metadata
        scale — and their statics merge (:meth:`Stage.merge_static`) before
        the fused inverse segments dispatch under the engine's mesh
        ``shard_map``, exactly like the forward ``run_batched`` path.
        """
        plan = self.plan
        for st in self.inv_preps:
            with span("hpdr.host_stage", stage=st.name):
                for env in envs:
                    st.host_prepare(env)
        merged: dict[str, int] = dict(envs[0].statics)
        for st in self.inv_preps:
            for name in st.inv_static_outputs:
                merged[name] = st.merge_static(
                    name, [env.statics[name] for env in envs]
                )
        fills = dict(self.graph.inv_fills)
        pads = dict(self.graph.inv_pads)
        state: dict[str, Any] = {}
        for key in states[0]:
            arr = _stack_pad(
                [np.asarray(s[key]) for s in states], fill=fills.get(key, 0)
            )
            mult = pads.get(key)
            if mult and (-arr.shape[1]) % mult:
                pad = (-arr.shape[1]) % mult
                arr = np.concatenate(
                    [arr, np.full((arr.shape[0], pad) + arr.shape[2:],
                                  fills.get(key, 0), arr.dtype)], axis=1,
                )
            state[key] = transfers.upload(arr)
        stacked_ops: dict[str, jax.Array] = {}
        for seg in self.inv_segments:
            with span("hpdr.segment", segment=seg.name):
                operand_vals = self._upload_stacked(
                    transfers, envs, seg.operand_keys, stacked_ops
                )
                vfn = self.segment_exe(seg, merged, batched=True)
                state_vals = tuple(state[k] for k in seg.in_keys)
                if seg.workspace_keys:
                    with plan.lock:
                        ws_vals = tuple(
                            plan.workspace[k] for k in seg.workspace_keys
                        )
                        outs = device_mapper(
                            seg, vfn, state_vals, operand_vals, ws_vals
                        )
                else:
                    outs = device_mapper(seg, vfn, state_vals, operand_vals, ())
                state.update(zip(seg.out_keys, outs))
        return state


def _stack_pad(arrs: list[np.ndarray], fill: int = 0) -> np.ndarray:
    """Stack per-leaf operands, padding axis 0 to the widest leaf.

    Needed when a host stage builds data-dependent tables per leaf (e.g.
    per-leaf codebooks over differing alphabets): zero-length codes are
    never gathered for keys inside a leaf's own alphabet, so zero padding
    is inert by construction.  ``fill`` overrides the pad value for state
    whose neutral element is not zero (e.g. scatter indices, which pad with
    an out-of-range sentinel so the padded rows drop).
    """
    if all(a.shape == arrs[0].shape for a in arrs):
        return np.stack(arrs)
    width = max(a.shape[0] for a in arrs)
    out = np.full((len(arrs), width) + arrs[0].shape[1:], fill, arrs[0].dtype)
    for i, a in enumerate(arrs):
        out[i, : a.shape[0]] = a
    return out


# ---------------------------------------------------------------------------
# container-side fetch view
# ---------------------------------------------------------------------------


class LeafView:
    """One leaf's window onto (possibly stacked) pipeline state.

    The container serialiser pulls arrays through :meth:`fetch`, which
    slices the leaf row (batched runs) and an optional leading-axis prefix
    *on device* before the D2H copy — so a Huffman stream whose exact word
    count is known host-side transfers exactly its compressed bytes, never
    the worst-case buffer.
    """

    def __init__(
        self,
        state: dict[str, Any],
        index: int | None,
        env: CallEnv,
        transfers: TransferStats | None = None,
    ):
        self.state = state
        self.index = index
        self.env = env
        self.transfers = transfers if transfers is not None else env.transfers

    def fetch(self, key: str, length: int | None = None) -> np.ndarray:
        arr = self.state[key]
        if self.index is not None:
            arr = arr[self.index]
        if length is not None:
            arr = arr[:length]
        return self.transfers.fetch(arr)
