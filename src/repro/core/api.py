"""Public HPDR compression API — codec registry + plan architecture.

The paper's core claim (§III-B) is that per-call context management — plans,
workspace allocations, compiled executables — dominates reduction cost at
scale.  This layer therefore separates the three phases every call used to
re-run:

  1. **Specify** — :class:`ReductionSpec` describes a reduction: method,
     shape, dtype, and the method's parameters.  It is hashable; its
     ``key()`` is the CMM context key.
  2. **Plan** — :func:`get_plan` resolves the spec through the codec registry
     (:mod:`repro.core.codecs`) and stores the resulting
     :class:`ReductionPlan` — jitted executables with static arguments bound,
     plus persistent workspace buffers (level maps, permutations) — in the
     global CMM.  The second call with an identical spec is a cache *hit*
     with a non-``None`` plan: nothing is rebuilt.
  3. **Execute** — :func:`encode`/:func:`decode` run the planned executables
     on data and produce/consume :class:`Compressed` containers (the v2 byte
     format with per-section offsets and a payload checksum; v1 streams are
     still read — see :mod:`repro.core.container`).

``compress``/``decompress`` remain as thin back-compat wrappers that build a
spec from keyword arguments and dispatch through the registry — there is no
method if/elif chain anywhere.  Higher-level entry points:

  * :func:`compress_pytree` / :func:`decompress_pytree` — batch compression
    of parameter/KV pytrees with per-leaf method selection;
  * :func:`compress_leaf` / :func:`decompress_leaf` — single-tensor policy
    helpers (dtype casting, ZFP 4³ re-blocking, lossless byte view) shared by
    the checkpoint manager and the serving engine;
  * :class:`CompressorStream` — chunked streaming compression built on the
    HDEM :class:`~repro.core.pipeline.ChunkedPipeline`, with its own framed
    byte format for multi-chunk streams.

Methods
-------
  mgard              error-bounded lossy (float arrays, 1-4D)
  mgard-progressive  error-bounded lossy refactored into precision tiers:
                     separately addressable container components, prefix
                     retrieval + incremental refinement
                     (:mod:`repro.core.progressive`)
  zfp                fixed-rate lossy (float arrays, 1-4D)
  huffman            lossless entropy coding of integer key arrays
  huffman-bytes      lossless byte-wise entropy coding of arbitrary arrays
                     (the LZ-class baseline analogue in the paper's
                     comparisons)
"""

from __future__ import annotations

import dataclasses
import io
import json
import math
import threading
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import adapters
from . import pipeline as pl
from .codecs import available_methods, get_codec
from .codecs.base import Codec, ReductionPlan, ReductionSpec  # noqa: F401
from .container import Compressed, ContainerError, _jsonable  # noqa: F401
from .context import GLOBAL_CMM, ReductionContext
from .stages.base import CallEnv, Stage, StageGraph, TransferStats  # noqa: F401
from ..runtime.spans import root, span

METHODS = ("mgard", "mgard-progressive", "zfp", "huffman", "huffman-bytes")

_STREAM_MAGIC = b"HPDS"
_STREAM_VERSION = 1


# ---------------------------------------------------------------------------
# spec / plan resolution (CMM-backed)
# ---------------------------------------------------------------------------


def make_spec(data: Any, method: str, **params: Any) -> ReductionSpec:
    """Build the canonical spec for compressing ``data`` with ``method``.

    Parameters irrelevant to the codec are dropped and omitted ones filled
    with the codec's defaults, so equivalent calls produce identical specs
    (and hit the same CMM entry).  ``backend=`` selects the device adapter
    the plan binds (``auto`` resolves to the platform default).
    """
    codec = get_codec(method)
    # NB: read dtype without materialising data — np.asarray on a device
    # array would force a full D2H copy just to inspect it.
    dtype = getattr(data, "dtype", None)
    if dtype is None:
        dtype = np.asarray(data).dtype
    return codec.make_spec(np.shape(data), dtype, **params)


def _build_context(key, codec: Codec, spec: ReductionSpec) -> ReductionContext:
    with span("hpdr.plan.build", method=spec.method):
        plan = codec.plan(spec)
    # Mirror the plan's persistent buffers into the context so CMM byte
    # accounting (ContextCache.nbytes/stats) sees them.
    return ReductionContext(key=key, plan=plan, buffers=plan.workspace)


def get_plan(spec: ReductionSpec) -> ReductionPlan:
    """CMM-cached plan for ``spec``; built by the codec on the first miss."""
    codec = get_codec(spec.method)
    key = spec.key()
    ctx = GLOBAL_CMM.get_or_create(key, lambda: _build_context(key, codec, spec))
    if ctx.plan is None:  # entry predating the plan architecture
        ctx.plan = codec.plan(spec)
        ctx.buffers = ctx.plan.workspace
    return ctx.plan


def _raw_bytes(spec: ReductionSpec) -> int:
    return math.prod(spec.shape) * jnp.dtype(spec.dtype).itemsize


def encode(spec: ReductionSpec, data: jax.Array | np.ndarray) -> Compressed:
    """Compress ``data`` according to ``spec`` (plan reused via the CMM)."""
    with root("hpdr.compress", method=spec.method, raw_bytes=_raw_bytes(spec)):
        return get_codec(spec.method).encode(get_plan(spec), data)


def decode(c: Compressed, backend: str | None = None) -> jax.Array:
    """Decompress a container (the decode-side plan is CMM-cached too).

    Any backend decodes any stream (portability contract); ``backend``
    overrides the decode-side adapter, defaulting to the platform's best.
    Streams carrying a decode chunk index run the compiled inverse pipeline
    — one fused device dispatch, H2D = compressed bytes + metadata; older
    streams fall back to the host-orchestrated decoder transparently.
    """
    codec = get_codec(c.method)
    spec = codec.decode_spec(c)
    if backend is not None:
        spec = dataclasses.replace(spec, backend=adapters.resolve_backend(backend))
    with root("hpdr.decompress", method=c.method, raw_bytes=_raw_bytes(spec)):
        return codec.decode(get_plan(spec), c)


# ---------------------------------------------------------------------------
# compress / decompress — thin wrappers over the registry
# ---------------------------------------------------------------------------


def compress(
    data: jax.Array | np.ndarray,
    method: str = "mgard",
    *,
    error_bound: float = 1e-2,
    relative: bool = True,
    rate: int = 16,
    dict_size: int = 4096,
    tiers: int = 3,
    tier_ratio: float = 8.0,
    backend: str | None = None,
    adapter: str | None = None,
) -> Compressed:
    """Compress ``data`` with the selected pipeline.

    ``error_bound`` is relative to the value range when ``relative=True``
    (the paper's evaluation convention).  This is a convenience wrapper: it
    builds a :class:`ReductionSpec` and dispatches through the codec
    registry, so repeated same-shaped calls reuse one cached plan.
    ``backend`` (alias: the legacy ``adapter`` keyword) binds the plan's
    device adapter; default ``auto``.
    """
    data = jnp.asarray(data)
    spec = make_spec(
        data, method,
        error_bound=error_bound, relative=relative, rate=rate,
        dict_size=dict_size, tiers=tiers, tier_ratio=tier_ratio,
        backend=backend or adapter or adapters.AUTO,
    )
    return encode(spec, data)


def decompress(c: Compressed) -> jax.Array:
    return decode(c)


# ---------------------------------------------------------------------------
# leaf policy helpers (shared by checkpoint + serving layers)
# ---------------------------------------------------------------------------


def as_blocked_3d(flat: np.ndarray) -> np.ndarray:
    """Flat → (n, 32, 32) (padded to 1024-multiples): ZFP blocks become 4³ so
    the per-block emax header is amortised over 64 values instead of 4."""
    x = np.asarray(flat).reshape(-1)
    pad = (-x.size) % 1024
    if pad:
        x = np.pad(x, (0, pad), mode="edge")
    return x.reshape(-1, 32, 32)


_HUFFMAN_MAX_ALPHABET = 1 << 16


def leaf_policy(
    arr: np.ndarray, method: str, params: dict | None = None
) -> tuple[np.ndarray, str, dict]:
    """Shared shape/dtype policy: ``(array, method, params)`` to compress.

    bfloat16 is cast to float32 for the lossy codecs, ZFP inputs are
    re-blocked to 4³-friendly (n, 32, 32), >4-D or 0-D MGARD inputs are
    flattened, ``huffman`` keeps genuine small-alphabet integer keys on the
    integer-key codec (data-dependent dictionary, tighter streams than the
    byte view), and anything else becomes a ``huffman-bytes`` byte view.
    Split out of :func:`compress_leaf` so the execution engine can bucket
    leaves by their *post-policy* spec before fanning out.
    """
    arr = np.asarray(arr)
    params = dict(params or {})
    if method in ("zfp", "mgard", "mgard-progressive"):
        x = arr
        if x.dtype != np.float32 and x.dtype.kind in ("f", "V"):
            x = x.astype(np.float32)
        if method == "zfp":
            x = as_blocked_3d(x)
        elif x.ndim > 4 or x.ndim == 0:
            x = x.reshape(-1)
        return x, method, params
    if (
        method == "huffman"
        and arr.dtype.kind in ("i", "u")
        and arr.size
        and int(arr.min()) >= 0
        and int(arr.max()) < _HUFFMAN_MAX_ALPHABET
    ):
        return arr, "huffman", params
    return np.ascontiguousarray(arr).view(np.uint8), "huffman-bytes", {}


def finish_leaf_meta(c: Compressed, arr: np.ndarray) -> Compressed:
    """Record the pre-policy dtype/shape for :func:`decompress_leaf`."""
    c.meta["orig_dtype"] = str(arr.dtype)
    c.meta["orig_shape"] = list(arr.shape)
    return c


def compress_leaf(arr: np.ndarray, method: str, **params: Any) -> Compressed:
    """Compress one tensor with the shared shape/dtype policy.

    The original dtype/shape ride along in ``meta`` for
    :func:`decompress_leaf`; see :func:`leaf_policy` for the policy itself.
    """
    arr = np.asarray(arr)
    x, pol_method, pol_params = leaf_policy(arr, method, params)
    c = compress(jnp.asarray(x), pol_method, **pol_params)
    return finish_leaf_meta(c, arr)


def restore_leaf(out: np.ndarray, c: Compressed) -> np.ndarray:
    """Undo :func:`leaf_policy` on a decoded array: original dtype + shape.

    Split out of :func:`decompress_leaf` so the execution engine's stacked
    decode path can restore per-leaf rows it decoded in one batch.
    """
    out = np.asarray(out)
    dtype = np.dtype(c.meta["orig_dtype"])
    shape = tuple(c.meta["orig_shape"])
    n = math.prod(shape) if shape else 1
    if c.method == "huffman-bytes":
        out = out.view(dtype) if out.dtype == np.uint8 else out.astype(dtype)
        return out.reshape(shape) if n == out.size else out
    return out.reshape(-1)[:n].astype(dtype).reshape(shape)


def decompress_leaf(c: Compressed) -> np.ndarray:
    """Inverse of :func:`compress_leaf`: restores original dtype and shape."""
    return restore_leaf(np.asarray(decode(c)), c)


# ---------------------------------------------------------------------------
# pytree / batch entry points
# ---------------------------------------------------------------------------


def _path_key(path, sep: str) -> str:
    return sep.join(str(getattr(e, "key", getattr(e, "idx", ""))) for e in path)


def default_select(key: str, arr: np.ndarray) -> tuple[str, dict] | None:
    """Default per-leaf policy: ZFP for sizable float tensors, raw otherwise."""
    del key
    if arr.dtype.kind == "f" and arr.size >= 4096:
        return "zfp", {"rate": 16}
    return None


def compress_pytree(
    tree: Any,
    select: Callable[[str, np.ndarray], tuple[str, dict] | None] | None = None,
    *,
    sep: str = "/",
    engine: Any = None,
) -> tuple[dict[str, Any], dict]:
    """Compress every selected leaf of a pytree, sharded across devices.

    ``select(key, arr)`` returns ``(method, params)`` to compress a leaf or
    ``None`` to pass it through raw.  Returns ``(flat, stats)`` where
    ``flat`` maps path keys to :class:`Compressed` or raw arrays — identical
    shapes/dtypes restore via :func:`decompress_pytree`.

    Execution runs on an :class:`~repro.core.engine.ExecutionEngine`
    (default: the process-wide engine over every local device on one
    ``data`` axis): leaves are bucketed by post-policy spec — one plan build
    per shape-dtype bucket, every further leaf a CMM hit — and buckets fan
    out over the mesh's ``data``-axis devices.
    """
    from . import engine as engine_mod  # runtime import: peer layer

    eng = engine if engine is not None else engine_mod.default_engine()
    return eng.compress_pytree(tree, select, sep=sep)


def decompress_pytree(
    comp: dict[str, Any], like: Any, *, sep: str = "/", engine: Any = None
) -> Any:
    """Rebuild the pytree ``like`` from :func:`compress_pytree` output."""
    from . import engine as engine_mod

    eng = engine if engine is not None else engine_mod.default_engine()
    return eng.decompress_pytree(comp, like, sep=sep)


# ---------------------------------------------------------------------------
# chunked streaming (HDEM pipeline)
# ---------------------------------------------------------------------------


class CompressorStream:
    """Chunked streaming compression on the lane-overlapped HDEM pipeline.

    Chunks share a spec whenever their shapes agree, so every chunk after
    the first hits the CMM plan cache — the chunk-pipelined analogue of the
    paper's per-call context reuse.  Each chunk runs as a *two-phase*
    encode: the fused ``CompiledPipeline`` segments execute on the
    executor's compute lane (phase 1, device-resident) while the previous
    chunk's D2H fetch + container serialization runs on the io lane
    (phase 2) and the next chunk stages H2D — the paper's Fig. 9 overlap,
    bounded at ``window`` in-flight chunks.  Plans with persistent
    workspace get one donated copy per window slot, recycled across the
    chunks that reuse the slot, so concurrent chunk encodes never contend
    on the plan's shared buffers.

    ``to_bytes``/``from_bytes`` frame the per-chunk containers with an
    offset index so chunks can be located (and fetched lazily)
    independently; ``to_file``/``from_file`` add an aligned, aggregated
    on-disk layout with a segment directory, so a reader ``pread``s
    exactly the chunks it needs.  Passing ``engine=`` schedules chunks
    round-robin across the engine's ``data``-axis devices and runs the
    lanes on the engine's executor.

    ``chunk_size="auto"`` and/or ``window="auto"`` hand the decision to
    the auto-tuner (``core/tuner.py``): per payload, the calibrated
    machine cost model picks the (chunk, window) with the smallest
    predicted makespan — degrading to ``window=1`` whenever pipelining
    can't pay for its staging overhead.  The resolved values feed the
    exact same schedule/spec path as explicit settings, so auto streams
    are bit-identical to explicitly configured ones and share their CMM
    plans; the decision is observable at ``result.tuned``.  An explicit
    integer ``chunk_size`` (elements) is shorthand for ``mode="fixed",
    c_fixed_elems=chunk_size``.
    """

    def __init__(
        self,
        method: str = "zfp",
        mode: str = "adaptive",
        *,
        c_init_elems: int = 1 << 20,
        c_fixed_elems: int = 8 << 20,
        c_limit_elems: int = 1 << 28,
        phi=None,
        theta=None,
        engine: Any = None,
        backend: str | None = None,
        window: int | str = 2,
        chunk_size: int | str | None = None,
        frame: bool = False,
        **params: Any,
    ):
        self.method = method
        self.params = params
        if backend is None and engine is not None:
            backend = engine.backend
        self.backend = backend or adapters.AUTO
        self.window = window if window == "auto" else max(1, int(window))
        # frame=True moves wire serialization (container v2 framing + crc32)
        # onto the io lane too: each chunk's byte frame is produced while
        # the next chunk computes, and to_bytes/to_file reuse it
        self.frame = bool(frame)
        self._slot_ws: dict[tuple, tuple] = {}
        self._slot_lock = threading.Lock()
        auto = chunk_size == "auto" or window == "auto"
        self.pipeline = pl.ChunkedPipeline(
            mode=mode,
            c_init_elems=c_init_elems,
            c_fixed_elems=c_fixed_elems,
            c_limit_elems=c_limit_elems,
            phi=phi,
            theta=theta,
            devices=engine.devices if engine is not None else None,
            compute_fn=self._compute_chunk,
            finish_fn=self._finish_chunk,
            executor=engine.executor if engine is not None else None,
            window=window,
            chunk_size=chunk_size,
            tuner=self._tuned_plan if auto else None,
        )

    def _tuned_plan(self, total_elems: int, itemsize: int, dtype: str,
                    chunk_elems: int | None):
        """Tuner binding: this stream's codec/backend/params, the payload's
        size/dtype.  Called by the pipeline when resolving ``auto``."""
        from . import tuner as tuner_mod

        return tuner_mod.plan_stream(
            total_elems, itemsize, method=self.method, dtype=dtype,
            backend=self.backend, chunk_elems=chunk_elems,
            params=self.params,
        )

    # -- two-phase chunk encode ---------------------------------------------

    def _slot_workspace(self, plan: "ReductionPlan", slot: int) -> dict | None:
        """One private workspace copy per (plan, window slot).

        Donating segment executables invalidate their input buffers, so
        concurrent in-flight chunks must not share the plan's single
        workspace; the slot copy is donated into each dispatch and the
        recycled buffer re-stored under the same slot (the stream analogue
        of the engine's per-shard stacks).  Slots are reused serially —
        chunk *i* and *i+window* share a slot, but the window bound
        guarantees chunk *i* has fully finished first.
        """
        keys = {
            k
            for seg in plan.pipeline.device_segments
            for k in seg.workspace_keys
        }
        if not keys:
            return None
        # the entry pins the plan alive, so the id() key can never be
        # recycled onto a different plan while this stream exists
        cache_key = (id(plan), slot)
        with self._slot_lock:
            entry = self._slot_ws.get(cache_key)
            if entry is not None and entry[0] is plan:
                self._slot_ws[cache_key] = self._slot_ws.pop(cache_key)  # LRU
                return entry[1]
        with plan.lock:
            ws = {k: jnp.array(plan.workspace[k], copy=True) for k in keys}
        with self._slot_lock:
            # bounded: adaptive streams see a plan per chunk shape, and
            # workspaces are input-sized — keep the few most recent plans'
            # slots instead of pinning every plan the stream ever touched.
            # Evicting an entry an in-flight chunk still holds is safe:
            # the chunk owns its dict reference exclusively; a later chunk
            # simply rebuilds a fresh copy.
            while len(self._slot_ws) >= 4 * max(1, self.pipeline.window):
                self._slot_ws.pop(next(iter(self._slot_ws)))
            self._slot_ws[cache_key] = (plan, ws)
        return ws

    def _compute_chunk(self, chunk: jax.Array, slot: int):
        """Phase 1 (compute lane): fused device segments, state stays put."""
        spec = make_spec(chunk, self.method, backend=self.backend, **self.params)
        codec = get_codec(spec.method)
        plan = get_plan(spec)
        if plan.pipeline is None:  # codec without a stage graph: one phase
            return ("container", codec.encode(plan, jnp.asarray(chunk)))
        state, env = codec.encode_begin(
            plan, chunk, workspace=self._slot_workspace(plan, slot)
        )
        # block here, on the compute lane: serialization must only see
        # finished device buffers, and lane timings must be honest
        jax.block_until_ready([v for v in state.values()])
        return ("state", codec, plan, state, env)

    def _finish_chunk(self, payload, slot: int) -> Compressed:
        """Phase 2 (io lane): exact-sized D2H fetch + container build."""
        del slot
        if payload[0] == "container":
            c = payload[1]
            for k, v in list(c.arrays.items()):
                c.arrays[k] = np.asarray(v)
        else:
            _tag, codec, plan, state, env = payload
            c = codec.encode_finish(plan, state, env)
        if self.frame:
            c._frame_bytes = c.to_bytes()
        return c

    def compress(self, data: np.ndarray) -> pl.ChunkedResult:
        return self.pipeline.run(np.asarray(data))

    @staticmethod
    def decompress(result: pl.ChunkedResult) -> np.ndarray:
        return pl.decompress_chunked(result, decode)

    # -- framed multi-chunk byte format -------------------------------------

    @staticmethod
    def _chunk_blobs(result: pl.ChunkedResult) -> list[bytes]:
        """Per-chunk wire frames (reusing io-lane frames from ``frame=True``)."""
        return [
            getattr(c, "_frame_bytes", None) or c.to_bytes()
            for c in result.chunks
        ]

    @staticmethod
    def to_bytes(result: pl.ChunkedResult) -> bytes:
        blobs = CompressorStream._chunk_blobs(result)
        offsets = []
        off = 0
        for b in blobs:
            offsets.append(off)
            off += len(b)
        header = {
            "axis": result.axis,
            "shape": list(result.shape),
            "boundaries": list(result.boundaries),
            "chunks": [
                {"offset": o, "nbytes": len(b)} for o, b in zip(offsets, blobs)
            ],
        }
        hbytes = json.dumps(header).encode()
        buf = io.BytesIO()
        buf.write(_STREAM_MAGIC)
        buf.write(np.uint32(_STREAM_VERSION).tobytes())
        buf.write(np.uint64(len(hbytes)).tobytes())
        buf.write(hbytes)
        for b in blobs:
            buf.write(b)
        return buf.getvalue()

    @staticmethod
    def from_bytes(raw: bytes, lazy: bool = True) -> pl.ChunkedResult:
        """Parse a framed stream; chunks are fetched lazily by default.

        Framing and every chunk's byte range are validated eagerly (a
        truncated stream raises here), but the per-chunk containers are only
        materialised on first access via the v2 per-section offsets — a
        reader restoring a prefix never touches the tail's bytes
        (progressive restore while the tail is still in flight).
        ``lazy=False`` restores the historical eager behaviour.
        """
        raw = bytes(raw)
        if len(raw) < 16 or raw[:4] != _STREAM_MAGIC:
            raise ContainerError("not an HPDR chunked stream")
        version = int(np.frombuffer(raw[4:8], np.uint32)[0])
        if version != _STREAM_VERSION:
            raise ContainerError(f"unsupported HPDR stream version {version}")
        hlen = int(np.frombuffer(raw[8:16], np.uint64)[0])
        if len(raw) < 16 + hlen:
            raise ContainerError("truncated HPDR chunked stream")
        try:
            header = json.loads(raw[16 : 16 + hlen].decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise ContainerError(f"corrupt HPDR stream header: {e}") from e
        base = 16 + hlen
        ranges = []
        for entry in header["chunks"]:
            lo = base + entry["offset"]
            hi = lo + entry["nbytes"]
            if hi > len(raw):
                raise ContainerError("truncated HPDR chunked stream")
            ranges.append((lo, hi))
        chunks: Sequence = LazyChunks(raw, ranges)
        if not lazy:
            chunks = list(chunks)
        return pl.ChunkedResult(
            chunks=chunks,
            boundaries=list(header["boundaries"]),
            axis=int(header["axis"]),
            shape=tuple(header["shape"]),
        )

    # -- aggregated on-disk layout (runtime/io segment directory) -----------

    @staticmethod
    def to_file(
        result: pl.ChunkedResult,
        path,
        *,
        align: int = 4096,
        parallel: bool = True,
    ) -> dict:
        """Write a framed stream to ``path`` with aligned, aggregated I/O.

        The layout is the ``to_bytes`` frame with every chunk placed at an
        ``align``-rounded offset (the header JSON is space-padded so the
        payload base is aligned too — JSON ignores trailing whitespace),
        written through :class:`repro.runtime.io.AggregatedWriter`: chunks
        coalesce into large positional writes flushed on a dedicated
        thread, and a **segment directory** trailer records every chunk's
        exact byte range + crc32.  Readers that predate the directory
        still parse the file with :meth:`from_bytes` — the header's chunk
        offsets point at the right places and the trailer is ignored.

        Returns the directory dict (``segments``, ``meta``).
        """
        from ..runtime.io import AggregatedWriter, align_up

        blobs = CompressorStream._chunk_blobs(result)
        offsets = []
        off = 0
        for b in blobs:
            offsets.append(off)
            off = align_up(off + len(b), align)
        header = {
            "axis": result.axis,
            "shape": list(result.shape),
            "boundaries": list(result.boundaries),
            "chunks": [
                {"offset": o, "nbytes": len(b)} for o, b in zip(offsets, blobs)
            ],
            "align": align,
        }
        hbytes = json.dumps(header).encode()
        # pad the header so the payload base (16 + len(hbytes)) is aligned:
        # aligned relative offsets then stay aligned absolutely
        pad = (-(16 + len(hbytes))) % align
        hbytes += b" " * pad
        meta = {k: header[k] for k in ("axis", "shape", "boundaries")}
        with AggregatedWriter(
            path, align=align, parallel=parallel, meta=meta
        ) as writer:
            writer.write_raw(_STREAM_MAGIC)
            writer.write_raw(np.uint32(_STREAM_VERSION).tobytes())
            writer.write_raw(np.uint64(len(hbytes)).tobytes())
            writer.write_raw(hbytes)
            for i, b in enumerate(blobs):
                got = writer.add(f"chunk/{i:05d}", b)
                assert got == 16 + len(hbytes) + offsets[i]
            directory = writer.close()
        return directory

    @staticmethod
    def from_file(path, lazy: bool = True) -> pl.ChunkedResult:
        """Open a :meth:`to_file` stream; chunks ``pread`` lazily on access.

        The segment directory locates every chunk, so restoring a prefix
        (or one chunk) reads exactly those byte ranges — nothing else is
        touched.  Files without a directory (e.g. raw :meth:`to_bytes`
        dumps) fall back to an in-memory parse via :meth:`from_bytes`.
        """
        from ..runtime import io as rio

        if not rio.has_directory(path):
            with open(path, "rb") as f:
                return CompressorStream.from_bytes(f.read(), lazy=lazy)
        reader = rio.AggregatedReader(path)
        # numeric sort: the zero-padded names widen past 5 digits on huge
        # streams, where a lexicographic sort would reorder chunks
        names = sorted(
            (n for n in reader.names() if n.startswith("chunk/")),
            key=lambda n: int(n.rsplit("/", 1)[1]),
        )
        chunks: Sequence = FileChunks(reader, names)
        if not lazy:
            chunks = list(chunks)
            reader.close()
        meta = reader.meta
        return pl.ChunkedResult(
            chunks=chunks,
            boundaries=list(meta["boundaries"]),
            axis=int(meta["axis"]),
            shape=tuple(meta["shape"]),
        )


class LazyChunks(Sequence):
    """Sequence of per-chunk containers, parsed on first access.

    Backed by the framed stream's byte buffer and the header's offset
    index; ``materialized`` counts how many chunks have actually been
    decoded from bytes (the observable for laziness tests).
    """

    def __init__(self, raw: bytes, ranges: list[tuple[int, int]]):
        self._raw = raw
        self._ranges = ranges
        self._cache: list[Compressed | None] = [None] * len(ranges)

    def __len__(self) -> int:
        return len(self._ranges)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        if i < 0:
            i += len(self)
        if not 0 <= i < len(self):
            raise IndexError(i)
        if self._cache[i] is None:
            lo, hi = self._ranges[i]
            self._cache[i] = Compressed.from_bytes(self._raw[lo:hi])
        return self._cache[i]

    @property
    def materialized(self) -> int:
        return sum(c is not None for c in self._cache)


class FileChunks(Sequence):
    """Sequence of per-chunk containers backed by segment-file ``pread``s.

    The file-resident sibling of :class:`LazyChunks`: nothing is read at
    construction beyond the directory the caller already parsed; accessing
    chunk *i* ``pread``s exactly that chunk's byte range (crc-checked) and
    caches the parsed container.  ``materialized`` counts parsed chunks
    and ``reader.preads`` counts actual positional reads — the observables
    for "decode touches only what it needs" tests.
    """

    def __init__(self, reader, names: list[str]):
        self.reader = reader
        self._names = list(names)
        self._cache: list[Compressed | None] = [None] * len(names)

    def __len__(self) -> int:
        return len(self._names)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        if i < 0:
            i += len(self)
        if not 0 <= i < len(self):
            raise IndexError(i)
        if self._cache[i] is None:
            self._cache[i] = Compressed.from_bytes(self.reader.read(self._names[i]))
        return self._cache[i]

    @property
    def materialized(self) -> int:
        return sum(c is not None for c in self._cache)
