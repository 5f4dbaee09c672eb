"""Concrete pipeline stages for the registered HPDR codecs.

Each stage maps one box of the paper's reduction pipelines onto the Stage
protocol (see :mod:`repro.core.stages.base`):

  device stages (fused into jitted segments, adapter-dispatched)
    * :class:`MgardDecorrelate`   multigrid decomposition (§IV-A)
    * :class:`UniformQuantize`    per-level linear quantization + escape keys
                                  + device outlier compaction
    * :class:`IntKeys` / :class:`ByteKeys`  entry normalisation to int32 keys
    * :class:`AlphabetScan`       device max-key reduction (huffman alphabet)
    * :class:`HuffmanHistogram`   DEM-global frequency histogram
    * :class:`HuffmanEntropy`     codebook gather (code, length) per key —
                                  the device-resident entropy stage, lowered
                                  through ``kernels/huffman_encode``
    * :class:`BitPack`            prefix-sum offsets + scatter-free word
                                  packing (+ self-sync chunk offsets)
    * :class:`ZfpBlockTransform`  fixed-rate block transform + bitplane pack

  host stages (the graph's explicit synchronisation points)
    * :class:`AlphabetBind`       reads the device max key → alphabet size
    * :class:`BinSchedule`        value range → error bound + bin schedule
    * :class:`CodebookBuild`      canonical codebook from the device
                                  histogram — the *only* host compute in the
                                  Huffman-family encode path

The entropy tail ``histogram → (host codebook) → entropy → pack`` is shared
verbatim by ``mgard``, ``huffman`` and ``huffman-bytes``; the codecs differ
only in the stages in front of it (see ``core/codecs/*``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import bitstream as bs
from .. import huffman
from .base import CallEnv, Stage, TraceEnv

_WORD_BUCKET = 1024  # jitted word-buffer granularity (4 KiB) — bounds retraces


# ---------------------------------------------------------------------------
# entry normalisation
# ---------------------------------------------------------------------------


class IntKeys(Stage):
    """Flatten an integer array into the int32 key stream."""

    name = "int_keys"
    reads = ("data",)
    writes = ("keys",)
    inv_reads = ("keys",)
    inv_writes = ("data",)

    def planned(self, plan) -> None:
        self._shape = tuple(plan.spec.shape)
        self._dtype = plan.spec.dtype

    def apply(self, env: TraceEnv, state: dict) -> dict:
        return {"keys": state["data"].reshape(-1).astype(jnp.int32)}

    def invert(self, env: TraceEnv, state: dict) -> dict:
        keys = state["keys"]
        return {"data": keys.reshape(self._shape).astype(jnp.dtype(self._dtype))}


class ByteKeys(IntKeys):
    """Byte view of the input as the key stream (256-key alphabet)."""

    name = "byte_keys"

    def invert(self, env: TraceEnv, state: dict) -> dict:
        # device-side inverse of the host byte view: bitcast the decoded
        # byte stream back to the element dtype (little-endian layouts
        # match numpy's .view on every supported platform)
        dt = np.dtype(self._dtype)
        raw = state["keys"].astype(jnp.uint8)
        if dt.itemsize == 1:
            data = raw.astype(jnp.dtype(self._dtype))
        else:
            data = jax.lax.bitcast_convert_type(
                raw.reshape(-1, dt.itemsize), jnp.dtype(self._dtype)
            )
        return {"data": data.reshape(self._shape)}


class AlphabetScan(Stage):
    """Device max-key reduction — sizes the data-dependent alphabet."""

    name = "alphabet_scan"
    reads = ("keys",)
    writes = ("kmax",)

    def apply(self, env: TraceEnv, state: dict) -> dict:
        return {"kmax": jnp.max(state["keys"]).astype(jnp.int32)}


class AlphabetBind(Stage):
    """Host barrier: bind the histogram width to the observed alphabet.

    The fetch is one int32 per leaf.  In a stacked batch the bound width is
    the max across leaves (`merge_static`); each leaf still records its own
    ``num_keys`` so its codebook (and stream) is identical to a serial
    encode.
    """

    name = "alphabet_bind"
    device = False
    fetches = ("kmax",)
    static_outputs = ("num_bins",)

    def host_apply(self, env: CallEnv, fetched: dict) -> None:
        num_keys = int(fetched["kmax"]) + 1
        env.meta["num_keys"] = num_keys
        env.statics["num_bins"] = num_keys

    def merge_static(self, name: str, values) -> int:
        return max(values)


# ---------------------------------------------------------------------------
# MGARD front end
# ---------------------------------------------------------------------------


class MgardDecorrelate(Stage):
    """Multigrid decomposition (+ the value-range reduction the relative
    error bound needs, so the range sync is one pair of scalars)."""

    name = "mgard_decorrelate"
    reads = ("data",)
    writes = ("coeffs", "vmin", "vmax")
    inv_reads = ("coeffs",)
    inv_writes = ("data",)

    def __init__(self, shape: tuple[int, ...]):
        self.shape = tuple(shape)

    def planned(self, plan) -> None:
        self._dtype = plan.spec.dtype

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from .. import mgard

        data = state["data"]
        return {
            "coeffs": mgard.decompose(data, shape=self.shape),
            "vmin": jnp.min(data),
            "vmax": jnp.max(data),
        }

    def invert(self, env: TraceEnv, state: dict) -> dict:
        from .. import mgard

        # the dequantized coefficients are inexact products: the barrier
        # keeps them from being FMA-contracted into the transform's adds,
        # which would round differently in a leaf's program and a stacked
        # one (see core/mgard.py)
        coeffs = jax.lax.optimization_barrier(state["coeffs"])
        out = mgard.recompose(coeffs, shape=self.shape)
        return {"data": out.astype(jnp.dtype(self._dtype))}

    def stage_meta(self, plan) -> dict:
        return {"shape": list(self.shape)}


class BinSchedule(Stage):
    """Host barrier: value range → effective bound + per-level bin sizes."""

    name = "bin_schedule"
    device = False
    fetches = ("vmin", "vmax")

    def __init__(self, eb0: float, relative: bool, L: int):
        self.eb0 = float(eb0)
        self.relative = bool(relative)
        self.L = int(L)

    def host_apply(self, env: CallEnv, fetched: dict) -> None:
        from .. import mgard

        if self.relative:
            eb = self.eb0 * float(fetched["vmax"] - fetched["vmin"])
        else:
            eb = self.eb0
        eb = eb if eb > 0 else self.eb0
        bins = mgard.level_bins(eb, self.L)
        env.meta["error_bound"] = float(eb)
        env.meta["bins"] = bins
        env.operands["bins"] = np.asarray(bins, np.float32)

    def host_prepare(self, env: CallEnv) -> None:
        # decode direction: the bin schedule was recorded in the container —
        # ship it back as the dequantize operand, no device sync needed
        env.operands["bins"] = np.asarray(env.meta["bins"], np.float32)

    def stage_meta(self, plan) -> dict:
        return {"error_bound": self.eb0, "relative": self.relative,
                "levels": self.L + 1}


class UniformQuantize(Stage):
    """Per-level linear quantization, escape keys, device outlier compaction.

    The escape path (paper: outliers stored losslessly) is compacted *on
    device* with an exclusive-scan scatter into a bounded slot buffer, so
    the host only ever fetches ``out_count`` plus the occupied slots — never
    the full quantized grid.  A leaf whose outliers overflow the cap falls
    back to fetching ``q`` (kept device-resident otherwise).
    """

    name = "uniform_quantize"
    reads = ("coeffs",)
    writes = ("q", "keys", "out_count", "out_idx", "out_val")
    operands = ("bins",)
    workspace = ("lmap",)
    donates = ("lmap",)
    inv_reads = ("keys", "out_idx", "out_val")
    inv_writes = ("coeffs",)
    inv_operands = ("bins",)
    inv_workspace = ("lmap",)
    inv_donates = ("lmap",)

    def __init__(self, padded: tuple[int, ...], dict_size: int):
        self.padded = tuple(padded)
        self.dict_size = int(dict_size)
        n = math.prod(self.padded)
        self.out_cap = max(64, n // 16)

    def planned(self, plan) -> None:
        plan.meta["out_cap"] = self.out_cap

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from .. import mgard

        q, keys, inlier = mgard._quantize_stage_impl(
            state["coeffs"], env.workspace("lmap"), env.operand("bins"),
            self.padded, self.dict_size, env.backend,
        )
        out_mask = ~inlier.reshape(-1)
        cap = self.out_cap
        pos = jnp.cumsum(out_mask.astype(jnp.int32)) - out_mask.astype(jnp.int32)
        slot = jnp.where(out_mask, jnp.minimum(pos, cap), cap)
        n = out_mask.shape[0]
        idx = jax.lax.iota(jnp.int32, n)
        out_idx = jnp.zeros(cap + 1, jnp.int32).at[slot].set(idx)[:cap]
        out_val = jnp.zeros(cap + 1, jnp.int32).at[slot].set(q.reshape(-1))[:cap]
        return {
            "q": q,
            "keys": keys,
            "out_count": jnp.sum(out_mask).astype(jnp.int32),
            "out_idx": out_idx,
            "out_val": out_val,
        }

    def invert(self, env: TraceEnv, state: dict) -> dict:
        from ..quantize import signed_to_unsigned, unsigned_to_signed
        from repro.kernels.quantize_map import ops as quantize_ops

        # zig-zag back to signed, restore escaped outliers losslessly (the
        # padded index rows carry an out-of-range sentinel and drop), then
        # dequantize through the same planned kernel the encode side used
        q = unsigned_to_signed(state["keys"].astype(jnp.uint32)).reshape(-1)
        q = q.at[state["out_idx"]].set(
            state["out_val"].astype(jnp.int32), mode="drop"
        )
        q = q.reshape(self.padded)
        coeffs = quantize_ops.dequantize(
            signed_to_unsigned(q), env.workspace("lmap"), env.operand("bins"),
            adapter=env.backend,
        ).reshape(q.shape)
        return {"coeffs": coeffs}

    def stage_meta(self, plan) -> dict:
        return {"padded": list(self.padded), "dict_size": self.dict_size,
                "outlier_cap": self.out_cap}


# ---------------------------------------------------------------------------
# Huffman entropy tail (shared by mgard / huffman / huffman-bytes)
# ---------------------------------------------------------------------------


class HuffmanHistogram(Stage):
    """DEM-global frequency histogram over the key stream."""

    name = "huffman_histogram"
    reads = ("keys",)
    writes = ("freq",)
    statics = ("num_bins",)

    def __init__(self, num_bins: int | None = None):
        self.num_bins = num_bins  # None: bound per call by AlphabetBind

    def planned(self, plan) -> None:
        if self.num_bins is not None:
            plan.meta.setdefault("statics", {})["num_bins"] = int(self.num_bins)

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from repro.kernels.histogram import ops as histogram_ops

        return {
            "freq": histogram_ops.histogram(
                state["keys"], env.static("num_bins"), adapter=env.backend
            )
        }

    def stage_meta(self, plan) -> dict:
        return {"num_bins": self.num_bins}


class CodebookBuild(Stage):
    """Host barrier: canonical two-phase codebook from the device histogram.

    This is the one genuinely sequential, metadata-scale step of Huffman-X
    (paper Fig. 6 — the same histogram→codebook sync point GPU encoders
    have).  It ships the (code, length) tables back as device operands,
    records the serialised ``length_table``, and derives the exact packed
    size host-side from ``freq · lengths`` — so no device sync is needed to
    size the output buffer.
    """

    name = "codebook_build"
    device = False
    fetches = ("freq",)
    static_outputs = ("num_words",)
    inv_static_outputs = ("chunk_size", "n_symbols")

    def __init__(self, chunk_size: int = huffman.DEFAULT_CHUNK):
        self.chunk_size = int(chunk_size)

    def host_apply(self, env: CallEnv, fetched: dict) -> None:
        freq = np.asarray(fetched["freq"])
        num_keys = int(env.meta.get("num_keys", freq.shape[0]))
        freq = freq[:num_keys]
        book = huffman.build_codebook(freq)
        total_bits = int(
            np.sum(freq.astype(np.int64) * book.lengths.astype(np.int64))
        )
        env.meta.setdefault("num_keys", num_keys)
        env.meta["total_bits"] = total_bits
        env.meta["length_table"] = np.asarray(book.lengths, np.int32)
        env.meta["chunk_size"] = self.chunk_size
        env.statics["num_words"] = max(1, bs.words_needed(total_bits))
        env.operands["codes_t"] = np.asarray(book.codes, np.uint32)
        env.operands["lens_t"] = np.asarray(book.lengths, np.int32)

    def host_prepare(self, env: CallEnv) -> None:
        """Decode direction: canonical decode tables from the serialised
        length table — the plan-cached derivation (`plan_decode_tables`),
        so repeated decodes of same-codebook streams reuse one table set.
        The tables are metadata-scale operands; nothing is fetched from the
        device, which is what keeps the whole inverse pipeline fused."""
        tables = huffman.plan_decode_tables(
            env.plan, np.asarray(env.meta["length_table"], np.int32)
        )
        fc = np.asarray(tables.first_code, np.uint32)
        ct = np.asarray(tables.count, np.int32)
        so = np.asarray(tables.sym_offset, np.int32)
        ss = np.asarray(tables.sym_sorted, np.int32)
        if tables.max_len == 0:  # degenerate empty alphabet: keep width ≥ 2
            fc, ct, so = (np.pad(a, (0, 1)) for a in (fc, ct, so))
        if ss.size == 0:
            ss = np.zeros(1, np.int32)
        env.operands["first_code"] = fc
        env.operands["count"] = ct
        env.operands["sym_offset"] = so
        env.operands["sym_sorted"] = ss
        env.statics["chunk_size"] = int(env.meta["chunk_size"])
        env.statics["n_symbols"] = int(env.meta["n_symbols"])

    def merge_static(self, name: str, values) -> int:
        # chunk_size is decode *geometry*: a stream packed with 1 KiB
        # chunks decodes garbage under a 4 KiB grid, so it must agree
        # across a stacked batch (the engine groups decode buckets by
        # chunk geometry — see Codec.decode_bucket_key — and the strict
        # base merge is the backstop).  n_symbols may safely pad to the
        # widest leaf: each chunk's decoded tail past its own symbol
        # count is sliced off per leaf.
        if name == "chunk_size":
            return super().merge_static(name, values)
        return max(values)

    def stage_meta(self, plan) -> dict:
        return {"chunk_size": self.chunk_size, "canonical": True}


class HuffmanEntropy(Stage):
    """Device-resident entropy encoding: per-key (code, length) gather.

    Lowered through the ``huffman_encode`` kernel registry — the codebook
    tables live in VMEM under the Pallas adapters — so MGARD/Huffman encode
    never stages key streams through the host.
    """

    name = "huffman_entropy"
    reads = ("keys",)
    writes = ("codes", "lens")
    operands = ("codes_t", "lens_t")
    inv_reads = ("words", "chunk_offsets")
    inv_writes = ("keys",)
    inv_operands = ("first_code", "count", "sym_offset", "sym_sorted")
    inv_statics = ("chunk_size", "n_symbols")

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from repro.kernels.huffman_encode import ops as encode_ops

        codes, lens = encode_ops.encode_lookup(
            state["keys"].reshape(-1).astype(jnp.int32),
            env.operand("codes_t"),
            env.operand("lens_t"),
            adapter=env.backend,
        )
        return {"codes": codes, "lens": lens}

    def invert(self, env: TraceEnv, state: dict) -> dict:
        # The packed stream is self-synchronising per chunk: all chunks
        # decode in parallel through the huffman_decode kernel registry
        # (the decode mirror of the encode_lookup gather above).  max_len
        # comes from the staged table width, so a stacked batch padded to
        # its widest codebook specialises one shared trace.
        from repro.kernels.huffman_decode import ops as decode_ops

        first_code = env.operand("first_code")
        syms = decode_ops.decode_chunks(
            state["words"],
            state["chunk_offsets"],
            first_code,
            env.operand("count"),
            env.operand("sym_offset"),
            env.operand("sym_sorted"),
            env.static("chunk_size"),
            max(int(first_code.shape[0]) - 1, 1),
            adapter=env.backend,
        )
        return {"keys": syms.reshape(-1)[: env.static("n_symbols")]}


class BitPack(Stage):
    """Prefix-sum offsets + scatter-free word packing (DEM global stage).

    Runs on device via the ``huffman_encode`` kernel registry's
    ``pack_stream`` op.  The jitted word-buffer size buckets to 4 KiB
    multiples (:meth:`jit_statics`) so nearby stream sizes share one trace;
    the container serialiser slices to the exact word count on device
    before the D2H copy.
    """

    name = "bit_pack"
    reads = ("codes", "lens")
    writes = ("words", "chunk_offsets", "total_bits")
    statics = ("num_words",)

    def __init__(self, chunk_size: int = huffman.DEFAULT_CHUNK):
        self.chunk_size = int(chunk_size)

    def jit_statics(self, statics: dict) -> dict:
        w = int(statics["num_words"])
        out = dict(statics)
        out["num_words"] = max(_WORD_BUCKET, -(-w // _WORD_BUCKET) * _WORD_BUCKET)
        return out

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from repro.kernels.huffman_encode import ops as encode_ops

        codes, lens = state["codes"], state["lens"]
        num_words = env.static("num_words")
        if lens.shape[0] == 0:
            return {
                "words": jnp.zeros(num_words, jnp.uint32),
                "chunk_offsets": jnp.zeros(0, jnp.int32),
                "total_bits": jnp.int32(0),
            }
        words, chunk_offsets, total_bits = encode_ops.pack_stream(
            codes, lens, num_words, self.chunk_size, adapter=env.backend
        )
        return {
            "words": words, "chunk_offsets": chunk_offsets,
            "total_bits": total_bits,
        }

    # Variable-length codes cannot be unpacked independently of the
    # codebook, so BitPack declares no inverse of its own: the decode
    # direction is fused into HuffmanEntropy.invert (self-synchronising
    # chunked scan over the packed words), and the inverse compiler treats
    # this stage as an identity.

    def stage_meta(self, plan) -> dict:
        return {"chunk_size": self.chunk_size, "word_bits": bs.WORD_BITS}


# ---------------------------------------------------------------------------
# ZFP
# ---------------------------------------------------------------------------


class ZfpBlockTransform(Stage):
    """Fixed-rate block transform + bitplane packing (paper §IV-C).

    One stage because ZFP's whole chain is shape/rate-static — it compiles
    to a single fused executable with no host barrier at all.
    """

    name = "zfp_block_transform"
    reads = ("data",)
    writes = ("payload", "emax")
    inv_reads = ("payload", "emax")
    inv_writes = ("data",)

    def __init__(self, rate: int, dims: int, shape: tuple[int, ...]):
        self.rate = int(rate)
        self.dims = int(dims)
        self.shape = tuple(shape)

    def planned(self, plan) -> None:
        self._dtype = plan.spec.dtype

    def apply(self, env: TraceEnv, state: dict) -> dict:
        from .. import zfp

        payload, emax = zfp.compress_jit(
            state["data"], rate=self.rate, dims=self.dims, shape=self.shape,
            adapter=env.backend,
        )
        return {"payload": payload, "emax": emax}

    def invert(self, env: TraceEnv, state: dict) -> dict:
        from .. import zfp

        out = zfp.decompress_jit(
            state["payload"], state["emax"], rate=self.rate,
            dims=self.dims, shape=self.shape, adapter=env.backend,
        )
        return {"data": out.astype(jnp.dtype(self._dtype))}

    def stage_meta(self, plan) -> dict:
        return {"rate": self.rate, "dims": self.dims}
