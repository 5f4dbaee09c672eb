"""Codec protocol + plan objects for the HPDR codec registry.

The paper's CMM (§III-B) caches *contexts*: the plan (jitted executable) and
workspace allocations a reduction needs beyond its input/output.  This module
defines what a cached context holds in this framework:

  * :class:`ReductionSpec` — the hashable description of a reduction
    (method, shape, dtype, method parameters).  Its :meth:`ReductionSpec.key`
    is the CMM hash key ("similar data characteristics").
  * :class:`ReductionPlan` — what planning produces: jitted executables bound
    to the spec's static arguments plus persistent workspace buffers
    (level maps, permutations, codebooks) that repeated calls reuse.
  * :class:`Codec` — the three-method protocol every registered compressor
    implements: ``plan(spec)``, ``encode(plan, data)``, ``decode(plan, c)``.

Codecs are stateless; all per-(shape, dtype, params) state lives in the plan,
which the API layer stores in the global CMM so the second call with an
identical spec is a cache hit.
"""

from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from .. import adapters
from ..container import Compressed
from ..context import context_key


@dataclass(frozen=True)
class ReductionSpec:
    """Hashable description of one reduction: method + data characteristics.

    ``backend`` names the device adapter the plan's executables are bound to
    (``auto`` | ``xla`` | ``pallas`` | ``pallas_interpret``).  ``auto``
    resolves to the platform default through :func:`adapters.resolve_backend`
    capability probing, so a defaulted spec and an explicit platform-default
    spec share one CMM entry.
    """

    method: str
    shape: tuple[int, ...]
    dtype: str
    params: tuple[tuple[str, Any], ...] = ()
    backend: str = adapters.AUTO

    @classmethod
    def create(
        cls,
        method: str,
        shape: tuple[int, ...],
        dtype: Any,
        backend: str = adapters.AUTO,
        **params: Any,
    ) -> "ReductionSpec":
        return cls(
            method=method,
            shape=tuple(int(n) for n in shape),
            dtype=str(dtype),
            params=tuple(sorted(params.items())),
            backend=str(backend),
        )

    def param(self, name: str, default: Any = None) -> Any:
        for k, v in self.params:
            if k == name:
                return v
        return default

    def resolved(self) -> "ReductionSpec":
        """This spec with ``backend`` bound to a concrete, runnable adapter."""
        concrete = adapters.resolve_backend(self.backend)
        if concrete == self.backend:
            return self
        return dataclasses.replace(self, backend=concrete)

    def key(self) -> tuple:
        """Canonical CMM hash key for this spec (backend-resolved)."""
        return context_key(
            self.method, self.shape, self.dtype,
            backend=adapters.resolve_backend(self.backend),
            **dict(self.params),
        )


@dataclass
class ReductionPlan:
    """A built plan: jitted executables + persistent workspace buffers.

    ``executables`` maps stage name → jitted callable with the spec's static
    arguments already bound (tracing/compilation happens once per plan) and
    the spec's ``backend`` adapter baked in — kernel dispatch happens at plan
    time, never per call.  ``workspace`` holds device/host arrays that are
    data-independent for the spec (level maps, bin layouts, permutations,
    cached decode tables) — the paper's persistent context allocations.
    Executables that *donate* a workspace buffer return the recycled buffer;
    callers re-store it under :meth:`recycle` while holding :attr:`lock`
    (plans are shared across engine worker threads).

    ``pipeline`` is the compiled stage graph
    (:class:`repro.core.stages.base.CompiledPipeline`) for codecs declared
    as stage compositions: maximal device-stage runs fused into one jitted
    executable each, host barriers between them.  Both execution shapes —
    the per-leaf path and the engine's stacked ``shard_map`` path — run the
    same compiled segments.
    """

    spec: ReductionSpec
    executables: dict[str, Callable] = field(default_factory=dict)
    workspace: dict[str, Any] = field(default_factory=dict)
    meta: dict[str, Any] = field(default_factory=dict)
    pipeline: Any = field(default=None, repr=False, compare=False)
    lock: Any = field(default_factory=threading.Lock, repr=False, compare=False)

    def nbytes(self) -> int:
        return sum(int(getattr(b, "nbytes", 0)) for b in self.workspace.values())

    def recycle(self, name: str, buf: Any) -> None:
        """Re-store a donated-and-returned workspace buffer."""
        self.workspace[name] = buf


class Codec:
    """Base class for registered codecs (see :mod:`repro.core.codecs`).

    Subclasses set :attr:`spec_defaults` — the parameter names that belong
    in this codec's :class:`ReductionSpec` (and therefore in its CMM key),
    with their default values — and implement :meth:`plan` / :meth:`encode`
    / :meth:`decode` / :meth:`decode_spec`.
    """

    spec_defaults: dict[str, Any] = {}

    def __init__(self, name: str):
        self.name = name

    @property
    def spec_params(self) -> tuple[str, ...]:
        return tuple(self.spec_defaults)

    def make_spec(self, shape: tuple[int, ...], dtype: Any, **kwargs: Any) -> ReductionSpec:
        """Build a canonical spec from loose kwargs.

        Irrelevant kwargs are dropped and missing ones filled with the
        codec's defaults, so a defaulted call and an explicit-default call
        map to the same CMM key.  ``backend`` is resolved through adapter
        capability probing here — the spec a caller holds is already bound
        to a concrete adapter.
        """
        backend = adapters.resolve_backend(kwargs.pop("backend", None))
        params = {k: kwargs.get(k, d) for k, d in self.spec_defaults.items()}
        return ReductionSpec.create(self.name, shape, dtype, backend=backend, **params)

    # -- protocol ------------------------------------------------------------

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        """Build the persistent plan for ``spec`` (called once per CMM miss)."""
        raise NotImplementedError

    def encode_input(self, plan: ReductionPlan, data: Any) -> dict[str, Any]:
        """Initial pipeline state for ``data`` (the input-policy hook).

        Codecs whose pipeline consumes a host-side reinterpretation of the
        input (e.g. the huffman-bytes byte view) override this; everything
        downstream — serial encode, the engine's stacked path via
        ``leaf_policy``, and the chunk-pipelined stream — then feeds the
        pipeline identical bytes.
        """
        return {"data": data}

    def encode_begin(
        self,
        plan: ReductionPlan,
        data: Any,
        *,
        env: Any = None,
        workspace: dict | None = None,
    ) -> tuple[dict, Any]:
        """Phase 1 of a two-phase encode: run the forward pipeline only.

        Returns ``(state, env)`` with every array-scale product still
        device-resident — nothing has been fetched for serialisation yet.
        The chunk-pipelined scheduler runs this on the compute lane (with a
        per-slot ``workspace``) while the *previous* chunk's
        :meth:`encode_finish` runs on the io lane.
        """
        if plan.pipeline is None:
            raise NotImplementedError(
                f"codec {self.name!r} declares no stage graph; override "
                "encode() or implement build_stages()"
            )
        return plan.pipeline.run(
            self.encode_input(plan, data), env=env, workspace=workspace
        )

    def encode_finish(self, plan: ReductionPlan, state: dict, env: Any) -> Compressed:
        """Phase 2: fetch the exact-sized sections and build the container."""
        from ..stages.base import LeafView  # local: codecs ↔ stages layering

        return self.finish_container(plan, env, LeafView(state, None, env))

    def encode(
        self,
        plan: ReductionPlan,
        data: jax.Array,
        *,
        env: Any = None,
    ) -> Compressed:
        """Default encode: run the compiled stage pipeline, then serialise.

        ``env`` is the caller's :class:`~repro.core.stages.base.CallEnv`,
        whose ``transfers`` count the call's host↔device bytes.  Exactly
        :meth:`encode_begin` followed by :meth:`encode_finish`, so the
        pipelined two-phase path is bit-identical by construction.
        """
        state, env = self.encode_begin(plan, data, env=env)
        return self.encode_finish(plan, state, env)

    def decode(
        self,
        plan: ReductionPlan,
        c: Compressed,
        *,
        env: Any = None,
    ) -> jax.Array:
        raise NotImplementedError

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        """Spec keying the decode-side plan, recovered from container meta."""
        raise NotImplementedError

    # -- decode direction ----------------------------------------------------
    #
    # Codecs with an invertible stage graph expose the compiled decode path
    # through two hooks: decode_state() maps a container onto the inverse
    # pipeline's initial state (or None when the stream predates the decode
    # chunk index / needs the host fallback), and finish_decode() extracts
    # the result.  The default decode flow then mirrors encode: a single
    # fused device dispatch per inverse segment, H2D = compressed sections
    # plus metadata-scale operands.  The engine stacks whole buckets of
    # same-spec containers through the same hooks (invert_batched).

    def decode_state(
        self, plan: ReductionPlan, c: Compressed
    ) -> tuple[dict[str, Any], dict[str, Any]] | None:
        """``(inverse state0, env meta)`` for a container, or None."""
        return None

    def decode_bucket_key(self, c: Compressed) -> Any:
        """Per-stream decode *geometry* beyond the decode spec (hashable).

        Streams whose compiled-inverse statics differ — e.g. entropy
        streams packed with different ``chunk_size`` — must not share one
        stacked dispatch: merging their statics would decode garbage.  The
        engine groups decode buckets by ``(decode spec, this key)``; the
        default ``None`` groups purely by spec.
        """
        return None

    def finish_decode(
        self, plan: ReductionPlan, env: Any, state: dict, c: Compressed
    ) -> jax.Array:
        """Extract one leaf's decoded array from inverse pipeline state."""
        return state["data"]

    def _pipeline_decode(
        self,
        plan: ReductionPlan,
        c: Compressed,
        env: Any = None,
    ) -> jax.Array | None:
        """Run the compiled inverse pipeline; None → caller's host fallback."""
        if plan.pipeline is None or not plan.pipeline.invertible:
            return None
        prepared = self.decode_state(plan, c)
        if prepared is None:
            return None
        state0, meta = prepared
        from ..stages.base import CallEnv  # local: codecs ↔ stages layering

        env = env if env is not None else CallEnv(plan)
        env.meta.update(meta)
        state, env = plan.pipeline.invert(state0, env=env)
        return self.finish_decode(plan, env, state, c)

    @property
    def supports_batched_decode(self) -> bool:
        return (
            type(self).decode_state is not Codec.decode_state
        )

    # -- stage graph ---------------------------------------------------------
    #
    # Codecs declare their encode chain as a StageGraph; plan() attaches the
    # compiled pipeline via _attach_pipeline.  The execution engine reuses
    # the same compiled segments to stack same-spec leaves under one
    # shard_map over the mesh "data" axis (vmapped segments, host stages
    # looping over per-leaf metadata), so *every* stage-graph codec has a
    # batched encode path — the host-staged ones included, since their only
    # remaining host work is codebook construction.

    def build_stages(self, spec: ReductionSpec):
        """Return this codec's :class:`StageGraph` (or ``None``)."""
        return None

    def _attach_pipeline(self, plan: ReductionPlan) -> ReductionPlan:
        graph = self.build_stages(plan.spec)
        if graph is not None:
            plan.pipeline = graph.compile(plan)
        return plan

    def finish_container(self, plan: ReductionPlan, env: Any, view: Any) -> Compressed:
        """Serialise one leaf's pipeline state into a container."""
        raise NotImplementedError

    @property
    def supports_batched_encode(self) -> bool:
        return type(self).build_stages is not Codec.build_stages
