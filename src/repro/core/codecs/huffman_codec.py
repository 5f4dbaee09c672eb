"""Huffman-X codecs: integer-key entropy coding + the byte-wise variant.

Two registrations of the same stage composition (paper §IV-B, Fig. 6):

  * ``huffman``        lossless entropy coding of integer key arrays — the
                       dictionary size is data-dependent, so the graph opens
                       with a device max-key scan (``alphabet_scan``) and a
                       one-scalar host bind;
  * ``huffman-bytes``  lossless byte-wise coding of arbitrary arrays (fixed
                       256-key alphabet) — the LZ-class baseline analogue.

Both share the device-resident entropy tail declared here as
:data:`ENTROPY_TAIL`: histogram (device) → canonical codebook (the single
host barrier) → code/length gather → prefix-sum + bit-packing (device).
The codebook itself stays per-call metadata, exactly like the GPU
implementations rebuild the tree per buffer while reusing the kernel plan;
decode-side tables derived from it are cached on the plan
(:func:`plan_decode_tables`) so repeated decompress calls are CMM hits.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import bitstream as bs
from .. import huffman
from .. import stages as sg
from ..container import Compressed, ContainerError
from . import register_codec
from .base import Codec, ReductionPlan, ReductionSpec

def entropy_tail_stages(
    num_bins: int | None = None, chunk_size: int = huffman.DEFAULT_CHUNK
) -> tuple:
    """The shared entropy tail, with a plan-static alphabet when known.

    ``chunk_size`` sets the self-synchronisation granularity of the packed
    stream (symbols per independently-decodable chunk) — smaller chunks
    buy more decode parallelism for more ``chunk_offsets`` overhead.
    """
    return (
        sg.HuffmanHistogram(num_bins),
        sg.CodebookBuild(chunk_size),
        sg.HuffmanEntropy(),
        sg.BitPack(chunk_size),
    )


# decode-direction graph parameters shared by every entropy-tail codec: the
# compressed sections that seed the inverse state, and the 4 KiB word-stream
# bucket that bounds inverse retraces across stream sizes (the decode
# analogue of BitPack.jit_statics)
ENTROPY_INV_INPUTS = ("words", "chunk_offsets")
ENTROPY_INV_PADS = (("words", 1024),)


def entropy_container(
    plan: ReductionPlan, env, view, method: str,
    shape: tuple, dtype, n_symbols: int,
) -> Compressed:
    """Serialise the entropy tail's pipeline state (exact-sized fetches).

    The word stream is sliced on device to ``words_needed(total_bits)``
    before the D2H copy (the exact count is host-known from
    ``freq · lengths``), so the transfer is the compressed size, never the
    padded device buffer.  Layout matches the historical host encoder
    byte-for-byte; the per-stage metadata rides in ``meta["stages"]``.
    """
    total_bits = int(env.meta["total_bits"])
    c = Compressed(
        method=method,
        meta={
            "shape": tuple(shape), "dtype": str(dtype),
            "chunk_size": int(env.meta["chunk_size"]),
            "total_bits": total_bits,
            "n_symbols": int(n_symbols),
            "num_keys": int(env.meta["num_keys"]),
        },
        arrays={
            "words": view.fetch("words", max(1, bs.words_needed(total_bits))),
            "chunk_offsets": view.fetch("chunk_offsets"),
            "length_table": np.asarray(env.meta["length_table"], np.int32),
        },
    )
    # Per-stage metadata plus the decode chunk index: the bit_pack entry
    # records the chunk layout the chunk-parallel decoder fans out over.
    # Purely additive (still container v2); readers seeing a stream without
    # it — anything written before the stacked decode path existed — take
    # the host-orchestrated fallback (see stream_decode_index).
    n_chunks = int(c.arrays["chunk_offsets"].shape[0])
    stages = [dict(s) for s in plan.meta.get("stage_graph", [])]
    for s in stages:
        if s.get("stage") == "bit_pack":
            s["decode_index"] = {
                "n_chunks": n_chunks,
                "chunk_size": int(env.meta["chunk_size"]),
                "n_symbols": int(n_symbols),
            }
    c.meta["stages"] = stages
    return c


def stream_decode_index(c: Compressed) -> dict | None:
    """The stream's decode chunk index, or None for pre-index streams."""
    for s in c.meta.get("stages", ()) or ():
        if isinstance(s, dict) and s.get("stage") == "bit_pack":
            idx = s.get("decode_index")
            return dict(idx) if isinstance(idx, dict) else None
    return None


def entropy_decode_state(
    plan: ReductionPlan, c: Compressed
) -> tuple[dict, dict] | None:
    """Inverse-pipeline state for an entropy-tail stream (None: fallback).

    The state is exactly the compressed sections — ``words`` and the
    prefix-sum ``chunk_offsets`` the encoder persisted — so staging it is an
    H2D of the compressed bytes, nothing else.  The env metadata carries
    what the decode-direction host prepares consume (length table, chunk
    geometry); old streams without the chunk index return None and decode
    through the host path.  A *present but inconsistent* index is
    corruption, not age: it raises :class:`ContainerError` instead of
    silently decoding under the wrong chunk geometry.
    """
    idx = stream_decode_index(c)
    if idx is None:
        return None
    expected = {
        "n_chunks": int(c.arrays["chunk_offsets"].shape[0]),
        "chunk_size": int(c.meta["chunk_size"]),
        "n_symbols": int(c.meta["n_symbols"]),
    }
    for key, want in expected.items():
        if key not in idx or int(idx[key]) != want:
            raise ContainerError(
                f"corrupt HPDR stream: decode_index {key}={idx.get(key)!r} "
                f"disagrees with container metadata ({want})"
            )
    state0 = {
        "words": np.asarray(c.arrays["words"], np.uint32),
        "chunk_offsets": np.asarray(c.arrays["chunk_offsets"], np.int32),
    }
    meta = {
        "length_table": np.asarray(c.arrays["length_table"], np.int32),
        "chunk_size": int(idx["chunk_size"]),
        "n_symbols": int(idx["n_symbols"]),
        "num_keys": int(c.meta["num_keys"]),
        "total_bits": int(c.meta["total_bits"]),
    }
    return state0, meta


def entropy_bucket_key(c: Compressed) -> tuple:
    """Decode-geometry group key for entropy-tail streams.

    Streams with differing ``chunk_size`` bake different statics into the
    fused inverse executable, so the engine must not stack them into one
    dispatch (the old behaviour merged statics by max and decoded the
    smaller-chunk streams as garbage — ROADMAP mixed-chunk-size item).
    """
    return ("chunk_size", int(c.meta["chunk_size"]))


def sections_to_encoded(c: Compressed) -> huffman.Encoded:
    return huffman.Encoded(
        words=jnp.asarray(c.arrays["words"]),
        total_bits=int(c.meta["total_bits"]),
        n_symbols=int(c.meta["n_symbols"]),
        chunk_size=int(c.meta["chunk_size"]),
        chunk_offsets=jnp.asarray(c.arrays["chunk_offsets"]),
        length_table=np.asarray(c.arrays["length_table"]),
        num_keys=int(c.meta["num_keys"]),
    )


# Decode tables live in core.huffman since PR 4 so the stage library's
# decode-direction prepare step shares the same per-plan cache without a
# codecs → stages import cycle; this alias keeps the historical import path.
plan_decode_tables = huffman.plan_decode_tables


@register_codec("huffman")
class HuffmanCodec(Codec):
    """Entropy coding of integer keys (alphabet sized per call).

    ``chunk_size`` is an encode-side spec parameter: the number of symbols
    per independently-decodable packed chunk.  The default
    (:data:`repro.core.huffman.DEFAULT_CHUNK`) is canonicalised *out* of
    the spec key, so default encode specs and the (parameter-free) decode
    spec keep sharing one CMM plan; a non-default chunk size gets its own
    plan.  Decode always reads the geometry from the container, so one
    decode plan serves streams of any chunk size (grouped per geometry on
    the engine's stacked path).
    """

    spec_defaults = {}

    def make_spec(self, shape, dtype, **kwargs) -> ReductionSpec:
        import dataclasses

        chunk = int(kwargs.pop("chunk_size", huffman.DEFAULT_CHUNK))
        spec = super().make_spec(shape, dtype, **kwargs)
        if chunk != huffman.DEFAULT_CHUNK:
            spec = dataclasses.replace(spec, params=(("chunk_size", chunk),))
        return spec

    def build_stages(self, spec: ReductionSpec) -> sg.StageGraph:
        chunk = int(spec.param("chunk_size", huffman.DEFAULT_CHUNK))
        return sg.StageGraph(
            stages=(sg.IntKeys(), sg.AlphabetScan(), sg.AlphabetBind())
            + entropy_tail_stages(chunk_size=chunk),
            finish_keys=("words", "chunk_offsets"),
            inv_inputs=ENTROPY_INV_INPUTS,
            inv_pads=ENTROPY_INV_PADS,
        )

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        spec = spec.resolved()
        # legacy per-stage executables stay addressable; the compiled stage
        # pipeline is what encode (and the engine's stacked path) runs
        plan = ReductionPlan(
            spec=spec,
            executables={
                "histogram": partial(huffman.histogram_op, adapter=spec.backend),
                "encode": partial(huffman.encode, adapter=spec.backend),
                "decode": huffman.decode,
            },
        )
        return self._attach_pipeline(plan)

    def encode_input(self, plan: ReductionPlan, data: Any) -> dict:
        # the pipeline uploads (and counts) host input itself
        if not jnp.issubdtype(jnp.result_type(data), jnp.integer):
            raise ValueError("huffman method expects integer keys; use huffman-bytes")
        return {"data": data}

    def finish_container(self, plan, env, view) -> Compressed:
        spec = plan.spec
        return entropy_container(
            plan, env, view, self.name, spec.shape, spec.dtype,
            n_symbols=math.prod(spec.shape),
        )

    def decode_state(self, plan: ReductionPlan, c: Compressed):
        return entropy_decode_state(plan, c)

    def decode_bucket_key(self, c: Compressed) -> tuple:
        return entropy_bucket_key(c)

    def decode(
        self, plan: ReductionPlan, c: Compressed, *,
        env=None,
    ) -> jax.Array:
        out = self._pipeline_decode(plan, c, env=env)
        if out is not None:
            return out
        # host fallback: streams without a decode chunk index
        enc = sections_to_encoded(c)
        keys = huffman.decode(enc, tables=plan_decode_tables(plan, enc.length_table))
        return keys.reshape(tuple(c.meta["shape"])).astype(jnp.dtype(c.meta["dtype"]))

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        return ReductionSpec.create(self.name, c.meta["shape"], c.meta["dtype"])


@register_codec("huffman-bytes")
class HuffmanBytesCodec(Codec):
    """Byte-wise lossless coding of arbitrary arrays (fixed 256-key alphabet)."""

    spec_defaults = {}

    def build_stages(self, spec: ReductionSpec) -> sg.StageGraph:
        return sg.StageGraph(
            stages=(sg.ByteKeys(),) + entropy_tail_stages(num_bins=256),
            finish_keys=("words", "chunk_offsets"),
            inv_inputs=ENTROPY_INV_INPUTS,
            inv_pads=ENTROPY_INV_PADS,
        )

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        spec = spec.resolved()
        plan = ReductionPlan(
            spec=spec,
            executables={
                "histogram": partial(
                    huffman.histogram_op, num_bins=256, adapter=spec.backend
                ),
                "encode": partial(huffman.encode, adapter=spec.backend),
                "decode": huffman.decode,
            },
        )
        return self._attach_pipeline(plan)

    def encode_input(self, plan: ReductionPlan, data: Any) -> dict:
        # The byte view is a host reinterpretation (no copy for contiguous
        # input); the engine's stacked path arrives here pre-viewed by
        # leaf_policy, so every execution shape — serial, stacked, and the
        # chunk-pipelined stream — feeds the pipeline identical bytes.
        return {"data": np.ascontiguousarray(np.asarray(data)).view(np.uint8)}

    def finish_container(self, plan, env, view) -> Compressed:
        spec = plan.spec
        n_symbols = math.prod(spec.shape) * np.dtype(spec.dtype).itemsize
        return entropy_container(
            plan, env, view, self.name, spec.shape, spec.dtype,
            n_symbols=n_symbols,
        )

    def decode_bucket_key(self, c: Compressed) -> tuple:
        return entropy_bucket_key(c)

    def decode_state(self, plan: ReductionPlan, c: Compressed):
        # the device-side inverse byte view is a bitcast, only expressible
        # for plain 1/2/4-byte element types — anything else (8-byte
        # doubles under 32-bit jax, structured dtypes) stays on the host
        # fallback, which reinterprets via numpy
        dt = np.dtype(plan.spec.dtype)
        if dt.kind not in "iuf" or dt.itemsize not in (1, 2, 4):
            return None
        return entropy_decode_state(plan, c)

    def decode(
        self, plan: ReductionPlan, c: Compressed, *,
        env=None,
    ) -> jax.Array:
        out = self._pipeline_decode(plan, c, env=env)
        if out is not None:
            return out
        enc = sections_to_encoded(c)
        keys = np.asarray(
            huffman.decode(enc, tables=plan_decode_tables(plan, enc.length_table))
        )
        byte_view = keys.astype(np.uint8)
        return jnp.asarray(
            byte_view.view(np.dtype(c.meta["dtype"])).reshape(tuple(c.meta["shape"]))
        )

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        return ReductionSpec.create(self.name, c.meta["shape"], c.meta["dtype"])
