"""Execution engine — owns *where* and *how* a ReductionPlan runs.

This layer sits between the plan architecture (``ReductionSpec`` /
``ReductionPlan`` cached in the CMM) and the codec kernels, and implements
the two at-scale behaviours of the paper that the specify→plan→execute
split alone does not give:

  1. **Plan-bound backends** (§III-C): every spec carries a ``backend``
     (``auto`` | ``xla`` | ``pallas`` | ``pallas_interpret``); plan build
     resolves it through :func:`repro.core.adapters.resolve_backend`
     capability probing and bakes the chosen adapter into the jitted
     executables.  Kernel dispatch happens once, at plan time — never per
     call.
  2. **Sharded fan-out + async submission** (§V / Fig. 16): independent
     reductions — pytree leaves, stream chunks — are scheduled across the
     mesh's ``data``-axis devices.  Same-spec leaves are bucketed so each
     bucket builds *one* plan (a CMM miss) and every other leaf is a real
     CMM hit; every stage-graph codec's bucket is stacked and driven
     through the plan's compiled pipeline under ``shard_map`` over the
     ``data`` axis — each fused device segment is vmapped over the leaf
     axis, and the host barriers (codebook construction) loop over
     metadata-scale per-leaf fetches.  Since PR 3 that includes the
     formerly host-staged codecs (MGARD, Huffman): their entropy stage is
     device-resident, so the per-leaf host-future fan-out only remains for
     singleton buckets.  ``submit()/result()`` expose the future surface
     the checkpoint writer and the serving engine's KV parking run on.

Most callers use the process-wide :func:`default_engine` (all local devices
on one ``data`` axis) implicitly through ``api.compress_pytree``; custom
meshes/backends construct :class:`ExecutionEngine` directly::

    eng = ExecutionEngine(mesh=make_mesh((4,), ("data",)),
                          backend="pallas_interpret")
    flat, stats = eng.compress_pytree(params)
    sub = eng.submit_encode(spec, x)      # async single reduction
    c = sub.result()
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import adapters
from .codecs import get_codec
from .codecs.base import ReductionSpec
from .container import Compressed
from .stages.base import CallEnv, LeafView, TransferStats
from ..runtime.executor import COMPUTE, MESH, DeviceExecutor, Submission


def data_devices(mesh: Mesh | None) -> list:
    """Devices holding distinct ``data``-axis shards (fan-out placement ring).

    For a multi-axis mesh this walks the ``data`` axis with every other axis
    pinned at index 0 — one device per data shard.  Meshes without a
    ``data`` axis fall back to every device.
    """
    if mesh is None:
        return list(jax.devices())
    names = list(mesh.axis_names)
    if "data" not in names:
        return list(np.asarray(mesh.devices).flat)
    dev = np.moveaxis(np.asarray(mesh.devices), names.index("data"), 0)
    return list(dev.reshape(dev.shape[0], -1)[:, 0])


def make_data_mesh(devices=None) -> Mesh:
    """One-axis ``("data",)`` mesh over ``devices`` (default: all local).

    The default path delegates to :func:`repro.launch.mesh.make_data_mesh`
    (the version-portable constructor) so the two stay one implementation;
    an explicit device list builds the mesh over exactly those devices.
    """
    if devices is None:
        from ..launch import mesh as launch_mesh  # runtime import: layering

        return launch_mesh.make_data_mesh()
    return Mesh(np.array(list(devices)), ("data",))


class ExecutionEngine:
    """Plan-bound, mesh-sharded, async reduction executor."""

    def __init__(
        self,
        mesh: Mesh | None = None,
        backend: str = adapters.AUTO,
        max_workers: int | None = None,
        io_workers: int = 1,
        topology=None,
    ):
        self.backend = adapters.resolve_backend(backend)
        self.mesh = mesh if mesh is not None else make_data_mesh()
        self.devices = data_devices(self.mesh)
        if topology is None:
            from ..launch import mesh as launch_mesh  # runtime import: layering

            topology = launch_mesh.detect_topology()
        #: which controller process this engine runs in (multi-host I/O
        #: routing): the checkpoint writer coalesces this host's leaf
        #: compressions into its local shard, and ``encode_leaf_jobs``
        #: can drop leaves owned by other hosts before any plan work
        self.topology = topology
        self.executor = DeviceExecutor(
            self.devices, max_workers=max_workers, io_workers=io_workers
        )
        self._lock = threading.Lock()
        # LRU-bounded: entries pin their vmapped segment (and its compiled
        # traces) alive, so an unbounded map would defeat CMM plan eviction
        # in long-running processes with high spec diversity.
        self._smap_cache: "OrderedDict[tuple, Callable]" = OrderedDict()
        self._smap_capacity = 128
        # per-shard workspace stacks for the donating batched path: keyed by
        # the vmapped segment, popped before dispatch and re-stored from the
        # executable's pass-through output (true recycling where XLA
        # implements donation)
        self._ws_stacks: dict[tuple, tuple] = {}
        self.shard_map_calls = 0
        #: ids of the devices that held a stacked bucket's state — where the
        #: data-axis fan-out actually ran
        self.stack_devices: set[int] = set()
        self.sharded_leaves = 0
        self.sharded_decoded_leaves = 0
        self.transfer_h2d = 0
        self.transfer_d2h = 0
        self.ws_stack_builds = 0
        self.ws_donated_calls = 0

    # ----------------------------------------------------------- single spec

    def make_spec(self, data: Any, method: str, **params: Any) -> ReductionSpec:
        """Spec for ``data`` with this engine's backend bound (unless given)."""
        from . import api  # runtime import: api ↔ engine are peer layers

        params.setdefault("backend", self.backend)
        return api.make_spec(data, method, **params)

    def submit_encode(
        self, spec: ReductionSpec, data: Any, device: Any = None
    ) -> Submission:
        """Asynchronously compress ``data`` under ``spec``; returns a future."""
        from . import api

        return self.executor.submit(
            lambda: api.encode(spec, jnp.asarray(data)), device=device
        )

    def submit_decode(self, c: Compressed, device: Any = None) -> Submission:
        from . import api

        return self.executor.submit(lambda: api.decode(c), device=device)

    def stream(self, method: str = "zfp", **kwargs: Any):
        """A :class:`~repro.core.api.CompressorStream` bound to this engine.

        The stream's chunks fan out round-robin over the engine's
        ``data``-axis devices on the engine's executor lanes.  Defaults to
        the auto-tuned schedule (``chunk_size="auto", window="auto"`` —
        the calibrated machine cost model picks both); pass explicit
        values to override.  NB: build streams from caller threads, not
        from inside engine lane tasks — the stream's staging loop must not
        occupy the lane its own chunks need.
        """
        from . import api  # runtime import: api ↔ engine are peer layers

        kwargs.setdefault("chunk_size", "auto")
        kwargs.setdefault("window", "auto")
        return api.CompressorStream(method, engine=self, **kwargs)

    def submit(self, fn: Callable, /, *args: Any, **kwargs: Any) -> Submission:
        """Raw task submission (``lane="io"`` for orchestration work)."""
        return self.executor.submit(fn, *args, **kwargs)

    @staticmethod
    def result(sub: Submission, timeout: float | None = None) -> Any:
        return sub.result(timeout)

    def encode(self, spec: ReductionSpec, data: Any) -> Compressed:
        return self.submit_encode(spec, data).result()

    def decode(self, c: Compressed) -> jax.Array:
        return self.submit_decode(c).result()

    # ------------------------------------------------- bucket job surface
    #
    # The pytree entry points below and the serving layer's request
    # coalescer share these helpers: leaf-job construction (policy + spec +
    # per-leaf CMM resolution), bucketing by post-policy spec, and one
    # whole-mesh submission per stackable bucket.  The serving layer merges
    # jobs from *different requests* into one bucket — bit-identity holds
    # because stacked and per-leaf execution agree byte-for-byte.

    def encode_leaf_jobs(
        self,
        tree: Any,
        select: Callable[[str, np.ndarray], tuple[str, dict] | None] | None = None,
        *,
        sep: str = "/",
        owned_only: bool = False,
    ) -> tuple[list[str], dict[str, np.ndarray], list[tuple], dict]:
        """Flatten ``tree`` into encode jobs: ``(order, raw, jobs, stats)``.

        Each job is ``(key, arr, x, spec)`` — original leaf, post-policy
        array, and the engine-bound spec.  Plan resolution happens here,
        per leaf: the first leaf of a bucket builds the plan (CMM miss),
        every further leaf is a real CMM hit — the observable the
        scalability benchmark counts.

        ``owned_only=True`` is the multi-controller io-lane route: leaves
        owned by other hosts under ``self.topology`` are dropped *before*
        any plan or compression work (``stats["remote_leaves"]`` counts
        them), so each host's compute and io lanes carry exactly the
        leaves that coalesce into its local shard.
        """
        from . import api

        select = select or api.default_select
        stats = {
            "raw": 0, "compressed": 0, "leaves": 0, "compressed_leaves": 0,
            "buckets": 0, "sharded_leaves": 0, "devices": len(self.devices),
            "remote_leaves": 0,
        }
        order: list[str] = []
        raw_leaves: dict[str, np.ndarray] = {}
        jobs: list[tuple[str, np.ndarray, np.ndarray, ReductionSpec]] = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            key = api._path_key(path, sep)
            if owned_only and not self.topology.owns(key):
                stats["remote_leaves"] += 1
                continue
            arr = np.asarray(leaf)
            order.append(key)
            stats["raw"] += arr.nbytes
            stats["leaves"] += 1
            choice = select(key, arr)
            if choice is None:
                raw_leaves[key] = arr
                stats["compressed"] += arr.nbytes
                continue
            method, params = choice
            x, pol_method, pol_params = api.leaf_policy(arr, method, params)
            # a per-leaf backend in the policy overrides the engine default
            backend = pol_params.pop("backend", None) or self.backend
            spec = api.make_spec(x, pol_method, backend=backend, **pol_params)
            api.get_plan(spec)
            jobs.append((key, arr, x, spec))
        return order, raw_leaves, jobs, stats

    @staticmethod
    def bucket_encode_jobs(jobs: list[tuple]) -> dict[ReductionSpec, list]:
        """Group encode jobs by their post-policy spec (insertion-ordered)."""
        buckets: dict[ReductionSpec, list] = {}
        for job in jobs:
            buckets.setdefault(job[3], []).append(job)
        return buckets

    def encode_bucket_stackable(self, spec: ReductionSpec, items: list) -> bool:
        """Whether a bucket rides the stacked whole-mesh ``shard_map`` path."""
        from . import api

        codec = get_codec(spec.method)
        return (
            codec.supports_batched_encode
            and len(items) > 1
            and api.get_plan(spec).pipeline is not None
        )

    def submit_encode_bucket(
        self, spec: ReductionSpec, items: list, *, priority: str | None = None
    ) -> Submission:
        """One whole-mesh submission for a stackable bucket.

        Resolves to the per-item containers (leaf meta finished), aligned
        with ``items``.  Stacked buckets overlap each other's host barriers
        (codebook builds) on the compute pool.
        """
        from . import api

        codec = get_codec(spec.method)

        def run() -> list:
            out = self._encode_bucket_sharded(codec, spec, items)
            for (_key, arr, _x, _s), c in zip(items, out):
                api.finish_leaf_meta(c, arr)
            with self._lock:
                self.sharded_leaves += len(items)
            return out

        return self.executor.submit(run, device=MESH, priority=priority)

    def submit_encode_job(
        self, job: tuple, *, priority: str | None = None
    ) -> Submission:
        """Per-leaf fallback submission; resolves to one finished container."""
        key, arr, x, spec = job
        del key
        return self.executor.submit(
            self._encode_leaf, spec, x, arr, priority=priority
        )

    def decode_leaf_groups(
        self, comp: dict[str, Any]
    ) -> dict[tuple, list[tuple[str, Compressed]]]:
        """Group a flat compressed mapping into decode buckets.

        Keys group by ``(decode spec, decode geometry)`` — the codec's
        :meth:`~repro.core.codecs.base.Codec.decode_bucket_key` — with
        per-leaf plan resolution (CMM hit accounting) exactly mirroring the
        encode direction.  Raw (non-``Compressed``) entries are skipped.
        """
        import dataclasses as _dc

        from . import api

        buckets: dict[tuple, list] = {}
        for key, val in comp.items():
            if not isinstance(val, Compressed):
                continue
            codec = get_codec(val.method)
            spec = _dc.replace(codec.decode_spec(val), backend=self.backend)
            api.get_plan(spec)
            group = (spec, codec.decode_bucket_key(val))
            buckets.setdefault(group, []).append((key, val))
        return buckets

    def decode_bucket_prepared(
        self, spec: ReductionSpec, items: list
    ) -> list | None:
        """Per-item inverse-pipeline states, or ``None`` → per-leaf path."""
        from . import api

        codec = get_codec(spec.method)
        plan = api.get_plan(spec)
        if not (
            codec.supports_batched_decode
            and len(items) > 1
            and plan.pipeline is not None
            and plan.pipeline.invertible
        ):
            return None
        prepared = [codec.decode_state(plan, c) for _k, c in items]
        if any(p is None for p in prepared):
            return None  # old streams in the bucket: host path
        return prepared

    def submit_decode_bucket(
        self, spec: ReductionSpec, items: list, prepared: list,
        *, priority: str | None = None,
    ) -> Submission:
        """One whole-mesh submission for a stacked decode bucket.

        Resolves to the restored per-item leaves (original dtype/shape),
        aligned with ``items``.
        """
        codec = get_codec(spec.method)

        def run() -> list:
            out = self._decode_bucket_sharded(codec, spec, items, prepared)
            with self._lock:
                self.sharded_decoded_leaves += len(items)
            return out

        return self.executor.submit(run, device=MESH, priority=priority)

    def submit_decode_job(
        self, spec: ReductionSpec, c: Compressed, *, priority: str | None = None
    ) -> Submission:
        """Per-leaf decode fallback; resolves to the restored leaf."""
        return self.executor.submit(self._decode_leaf, spec, c, priority=priority)

    # -------------------------------------------------------- pytree fan-out

    def compress_pytree(
        self,
        tree: Any,
        select: Callable[[str, np.ndarray], tuple[str, dict] | None] | None = None,
        *,
        sep: str = "/",
        owned_only: bool = False,
    ) -> tuple[dict[str, Any], dict]:
        """Sharded-parallel :func:`repro.core.api.compress_pytree`.

        Leaves are bucketed by post-policy spec (shape, dtype, method,
        params, backend); each bucket builds one plan — further leaves are
        CMM hits — and buckets execute across the ``data``-axis devices:
        stacked under one ``shard_map`` where the codec's encode chain is
        fully jittable, as per-leaf executor futures otherwise.
        ``owned_only=True`` restricts the fan-out to this host's leaves
        under ``self.topology`` (multi-controller mode — each host emits
        exactly the flat mapping its local shard will hold).
        """
        order, raw_leaves, jobs, stats = self.encode_leaf_jobs(
            tree, select, sep=sep, owned_only=owned_only
        )

        buckets = self.bucket_encode_jobs(jobs)
        stats["buckets"] = len(buckets)

        results: dict[str, Compressed] = {}
        pending: list[tuple[str, Submission]] = []
        stacked: list[tuple[list, Submission]] = []
        for spec, items in buckets.items():
            if self.encode_bucket_stackable(spec, items):
                stacked.append((items, self.submit_encode_bucket(spec, items)))
            else:
                for key, arr, x, spec_i in items:
                    pending.append(
                        (key, self.executor.submit(self._encode_leaf, spec_i, x, arr))
                    )
        for items, sub in stacked:
            for (key, _arr, _x, _s), c in zip(items, sub.result()):
                results[key] = c
            stats["sharded_leaves"] += len(items)
        for key, sub in pending:
            results[key] = sub.result()

        flat: dict[str, Any] = {}
        for key in order:
            if key in raw_leaves:
                flat[key] = raw_leaves[key]
                continue
            c = results[key]
            flat[key] = c
            stats["compressed"] += c.nbytes()
            stats["compressed_leaves"] += 1
        stats["ratio"] = stats["raw"] / max(stats["compressed"], 1)
        return flat, stats

    def decompress_pytree(self, comp: dict[str, Any], like: Any, *, sep: str = "/") -> Any:
        """Sharded-parallel inverse of :meth:`compress_pytree`.

        The mirror image of the encode fan-out: leaves are bucketed by
        decode spec — one plan resolution per leaf, so repeat leaves are
        CMM hits — and every bucket whose codec compiled an inverse
        pipeline is stacked and driven through ``invert_batched`` under one
        whole-mesh ``shard_map`` submission (H2D = compressed sections plus
        metadata-scale decode operands, never a raw-array-sized transfer).
        Streams without a decode chunk index, singleton buckets, and
        codecs without a compiled inverse fall back to per-leaf futures.
        Buckets group by ``(decode spec, decode geometry)`` — the codec's
        :meth:`~repro.core.codecs.base.Codec.decode_bucket_key` — so
        same-shaped streams whose compiled-inverse statics differ (e.g.
        entropy streams packed with different ``chunk_size``) never share
        one stacked dispatch.
        """
        from . import api

        buckets = self.decode_leaf_groups(comp)

        results: dict[str, Any] = {}
        pending: list[tuple[str, Submission]] = []
        stacked: list[tuple[list, Submission]] = []
        for (spec, _geo), items in buckets.items():
            prepared = self.decode_bucket_prepared(spec, items)
            if prepared is not None:
                stacked.append(
                    (items, self.submit_decode_bucket(spec, items, prepared))
                )
            else:
                for key, c in items:
                    pending.append((key, self.submit_decode_job(spec, c)))
        for items, sub in stacked:
            for (key, _c), out in zip(items, sub.result()):
                results[key] = out
        for key, sub in pending:
            results[key] = sub.result()

        flat = {
            key: results[key] if isinstance(val, Compressed) else val
            for key, val in comp.items()
        }
        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(like)
        out = [jnp.asarray(flat[api._path_key(p, sep)]) for p, _leaf in leaves_with_path]
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------- internals

    def _encode_leaf(self, spec: ReductionSpec, x: np.ndarray, arr: np.ndarray):
        from . import api

        plan = api.get_plan(spec)
        env = CallEnv(plan)
        c = get_codec(spec.method).encode(plan, jnp.asarray(x), env=env)
        api.finish_leaf_meta(c, arr)
        with self._lock:
            self.transfer_h2d += env.transfers.h2d
            self.transfer_d2h += env.transfers.d2h
        return c

    def _decode_leaf(self, spec: ReductionSpec, c: Compressed):
        """Per-leaf decode under the engine-bound spec (the plan the bucket
        loop already resolved), mirroring `_encode_leaf` — the fallback must
        not rebuild a second platform-default plan via `api.decode`."""
        from . import api

        plan = api.get_plan(spec)
        env = CallEnv(plan)
        out = get_codec(spec.method).decode(plan, c, env=env)
        with self._lock:
            self.transfer_h2d += env.transfers.h2d
            self.transfer_d2h += env.transfers.d2h
        return api.restore_leaf(np.asarray(out), c)

    def _encode_bucket_sharded(self, codec, spec: ReductionSpec, items) -> list:
        """Stack same-spec leaves and drive them through the plan's compiled
        stage pipeline, one ``shard_map`` per fused device segment.

        The bucket's plan was resolved per leaf during bucketing (CMM hit
        accounting); the stack is padded to a multiple of the ``data``-axis
        size and the pad rows dropped at serialisation.  Host stages (bin
        schedules, codebook construction) loop over per-leaf metadata-scale
        fetches — the only host work in the bucket — while every array-scale
        intermediate (coefficients, keys, codes, words) stays device
        resident until the exact-sized container fetch.
        """
        from . import api

        plan = api.get_plan(spec)
        stacked = np.stack([x for (_k, _a, x, _s) in items])
        k, n = len(items), len(self.devices)
        pad = (-k) % n
        if pad:
            stacked = np.concatenate([stacked, np.repeat(stacked[-1:], pad, 0)])
        transfers = TransferStats()
        envs = [CallEnv(plan, transfers) for _ in range(k + pad)]
        state = plan.pipeline.run_batched(
            {"data": stacked}, envs, self._mesh_segment_mapper(), transfers
        )
        self._note_stack_devices(state)
        out = [
            codec.finish_container(
                plan, envs[i], LeafView(state, i, envs[i], transfers)
            )
            for i in range(k)
        ]
        with self._lock:
            self.shard_map_calls += len(plan.pipeline.device_segments)
            self.transfer_h2d += transfers.h2d
            self.transfer_d2h += transfers.d2h
        return out

    def _decode_bucket_sharded(
        self, codec, spec: ReductionSpec, items, prepared
    ) -> list:
        """Stack same-spec containers and drive them through the plan's
        compiled inverse pipeline, one ``shard_map`` per fused inverse
        segment (in practice: one per bucket — the decode direction has no
        host barriers).

        The stack is padded to a multiple of the ``data``-axis size and the
        pad rows dropped at restore.  H2D is the compressed sections plus
        the decode-table/bin-schedule operands; the decoded arrays stay
        device-resident until the per-leaf restore slices them out.
        """
        from . import api

        plan = api.get_plan(spec)
        k, n = len(items), len(self.devices)
        pad = (-k) % n
        prepared = list(prepared) + [prepared[-1]] * pad
        transfers = TransferStats()
        envs = []
        for state0, meta in prepared:
            env = CallEnv(plan, transfers)
            env.meta.update(meta)
            envs.append(env)
        state = plan.pipeline.invert_batched(
            [p[0] for p in prepared], envs, self._mesh_segment_mapper(),
            transfers,
        )
        self._note_stack_devices(state)
        out = []
        for i, (_key, c) in enumerate(items):
            row = {key: arr[i] for key, arr in state.items()}
            leaf = codec.finish_decode(plan, envs[i], row, c)
            out.append(api.restore_leaf(np.asarray(leaf), c))
        with self._lock:
            self.shard_map_calls += len(plan.pipeline.inv_segments)
            self.transfer_h2d += transfers.h2d
            self.transfer_d2h += transfers.d2h
        return out

    def _note_stack_devices(self, state: dict) -> None:
        ids = {
            d.id for v in state.values() if isinstance(v, jax.Array)
            for d in v.devices()
        }
        with self._lock:
            self.stack_devices |= ids

    def _mesh_segment_mapper(self) -> Callable:
        """Wrap a vmapped pipeline segment in this engine's mesh shard_map.

        State and per-leaf operands split over the ``data`` axis.  Plan
        workspace buffers take one of two routes:

          * **broadcast** (platforms without XLA buffer donation): the
            single plan copy is replicated to every shard and the vmapped
            segment's workspace pass-through is dropped;
          * **per-shard donation** (TPU/GPU, the ROADMAP "batched-path
            donation" item): the engine keeps a per-segment stack of one
            workspace copy per data shard, donates it into the dispatch,
            and re-stores the recycled stack the executable passes back —
            so stacked buckets reuse buffers in place exactly like the
            serial path's ``ReductionPlan.recycle`` contract.

        The wrapped executable is cached per vmapped segment (the pipeline
        keeps segment identity stable per statics tuple, so jit re-traces
        only on genuinely new shapes).
        """

        def shard(a) -> P:
            return P(*(["data"] + [None] * (np.ndim(a) - 1)))

        def mapper(seg, vfn, state_vals, operand_vals, ws_vals):
            donate = (
                bool(ws_vals)
                and seg.donate_keys == seg.workspace_keys
                and adapters.supports_donation()
            )
            key = (id(vfn), donate)
            with self._lock:
                exe = self._smap_cache.get(key)
                if exe is not None:
                    self._smap_cache.move_to_end(key)
            if exe is None:
                state_specs = tuple(shard(a) for a in state_vals)
                op_specs = tuple(shard(a) for a in operand_vals)
                outs_shapes, _ws_shapes = jax.eval_shape(
                    vfn, state_vals, operand_vals, ws_vals
                )
                outs_specs = tuple(
                    P(*(["data"] + [None] * (len(s.shape) - 1)))
                    for s in outs_shapes
                )
                if donate:
                    ws_specs = tuple(
                        P(*(["data"] + [None] * np.ndim(a))) for a in ws_vals
                    )

                    def wrapped(s, o, wstack):
                        outs, _ = vfn(s, o, tuple(w[0] for w in wstack))
                        return outs, wstack

                    exe = adapters.donating_jit(
                        jax.shard_map(
                            wrapped, mesh=self.mesh,
                            in_specs=(state_specs, op_specs, ws_specs),
                            out_specs=(outs_specs, ws_specs),
                            check_vma=False,
                        ),
                        donate_argnums=(2,),
                    )
                else:
                    ws_specs = tuple(
                        P(*([None] * np.ndim(a))) for a in ws_vals
                    )
                    exe = jax.shard_map(
                        lambda s, o, w: vfn(s, o, w)[0],
                        mesh=self.mesh,
                        in_specs=(state_specs, op_specs, ws_specs),
                        out_specs=outs_specs,
                        check_vma=False,
                    )
                with self._lock:
                    exe = self._smap_cache.setdefault(key, exe)
                    self._smap_cache.move_to_end(key)
                    while len(self._smap_cache) > self._smap_capacity:
                        old_key, _ = self._smap_cache.popitem(last=False)
                        # keep workspace stacks bounded with the exe cache;
                        # a re-run of the segment simply rebuilds its stack
                        self._ws_stacks.pop(old_key, None)
            if not donate:
                return exe(state_vals, operand_vals, ws_vals)
            stacks = self._take_ws_stacks(key, ws_vals, vfn)
            outs, stacks = exe(state_vals, operand_vals, stacks)
            with self._lock:
                self._ws_stacks[key] = stacks
                self.ws_donated_calls += 1
            return outs

        return mapper

    def _take_ws_stacks(self, key: tuple, ws_vals: tuple, vfn: Callable) -> tuple:
        """Pop (or build) the per-shard workspace stack for a segment.

        Popping under the lock gives each concurrent bucket exclusive
        ownership of a stack for the duration of its dispatch — donation
        invalidates the input buffer, so a shared reference would be a
        use-after-donate.  The entry's lifetime is tied to the vmapped
        segment itself: a ``weakref.finalize`` on ``vfn`` drops the stack
        when the segment (and its owning plan) is collected, so evicted
        plans release their device buffers AND a recycled ``id()`` can
        never resurrect another plan's workspace contents (the finalizer
        runs before the id can be reused).
        """
        with self._lock:
            stacks = self._ws_stacks.pop(key, None)
        if stacks is None:
            n = len(self.devices)
            stacks = tuple(
                jnp.stack([jnp.asarray(w)] * n) for w in ws_vals
            )
            # no engine lock in the callback: it may fire from GC at any
            # point, and dict.pop is GIL-atomic
            weakref.finalize(vfn, self._ws_stacks.pop, key, None)
            with self._lock:
                self.ws_stack_builds += 1
        return stacks

    # -------------------------------------------------------------- lifecycle

    def stats(self) -> dict[str, int]:
        s = self.executor.stats()
        with self._lock:
            s.update(
                backend=self.backend,
                shard_map_calls=self.shard_map_calls,
                stack_devices=tuple(sorted(self.stack_devices)),
                sharded_leaves=self.sharded_leaves,
                sharded_decoded_leaves=self.sharded_decoded_leaves,
                transfer_h2d=self.transfer_h2d,
                transfer_d2h=self.transfer_d2h,
                ws_stack_builds=self.ws_stack_builds,
                ws_donated_calls=self.ws_donated_calls,
            )
        return s

    def close(self) -> None:
        self.executor.shutdown()

    def __enter__(self) -> "ExecutionEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# process-wide default engine (all local devices on one "data" axis)
# ---------------------------------------------------------------------------

_DEFAULT_ENGINE: ExecutionEngine | None = None
_DEFAULT_LOCK = threading.Lock()


def default_engine() -> ExecutionEngine:
    """Lazily-built shared engine; what ``api.compress_pytree`` runs on."""
    global _DEFAULT_ENGINE
    with _DEFAULT_LOCK:
        if _DEFAULT_ENGINE is None:
            _DEFAULT_ENGINE = ExecutionEngine()
        return _DEFAULT_ENGINE


def set_default_engine(engine: ExecutionEngine | None) -> ExecutionEngine | None:
    """Swap the process default (tests / custom meshes); returns the old one."""
    global _DEFAULT_ENGINE
    with _DEFAULT_LOCK:
        old, _DEFAULT_ENGINE = _DEFAULT_ENGINE, engine
        return old
