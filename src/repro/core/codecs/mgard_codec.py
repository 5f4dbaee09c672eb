"""MGARD-X codec: error-bounded lossy compression behind the registry.

Declared as the full stage graph of paper Algorithm 1:

    mgard_decorrelate → [bin_schedule] → uniform_quantize →
    huffman_histogram → [codebook_build] → huffman_entropy → bit_pack

Bracketed stages are the two host barriers — the bin schedule reads one
(vmin, vmax) scalar pair and the codebook build reads the dict-size
histogram; everything else, *including the entropy stage and the escape
(outlier) compaction*, is device-resident.  The compiled pipeline therefore
has three fused device segments, which is what lets MGARD buckets ride the
execution engine's stacked ``shard_map`` path instead of fanning out over
host futures.

The plan still carries the classic per-stage executables
(decompose/recompose/quantize/dequantize) with the level map as a donated
persistent workspace buffer — the progressive refactor path shares them via
the same CMM entries.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import huffman, mgard
from .. import stages as sg
from ..container import Compressed
from ..quantize import unsigned_to_signed
from . import register_codec
from .base import Codec, ReductionPlan, ReductionSpec
from .huffman_codec import (
    ENTROPY_INV_INPUTS,
    ENTROPY_INV_PADS,
    entropy_bucket_key,
    entropy_container,
    entropy_decode_state,
    entropy_tail_stages,
    plan_decode_tables,
    sections_to_encoded,
)

_unsigned_to_signed_jit = jax.jit(unsigned_to_signed)

# Outlier slots pad to this bucket (bounds inverse retraces across streams
# with differing escape counts) using an out-of-range index sentinel, which
# the device scatter drops — a negative fill would wrap.
_OUT_BUCKET = 64
_OUT_SENTINEL = np.int32(2**31 - 1)


@register_codec("mgard")
class MGARDCodec(Codec):
    """Multigrid error-bounded compression (paper §IV-A, Algorithm 1)."""

    spec_defaults = {"error_bound": 1e-2, "relative": True, "dict_size": 4096}

    def build_stages(self, spec: ReductionSpec) -> sg.StageGraph:
        shape = spec.shape
        dict_size = int(spec.param("dict_size", 4096))
        padded = tuple(mgard.padded_dim(n) for n in shape)
        L = mgard.total_levels(padded)
        return sg.StageGraph(
            stages=(
                sg.MgardDecorrelate(shape),
                sg.BinSchedule(
                    float(spec.param("error_bound", 1e-2)),
                    bool(spec.param("relative", True)),
                    L,
                ),
                sg.UniformQuantize(padded, dict_size),
            )
            + entropy_tail_stages(num_bins=dict_size),
            # q/keys stay device-resident; they are only fetched on the rare
            # outlier-cap overflow fallback (see finish_container)
            finish_keys=(
                "words", "chunk_offsets",
                "out_count", "out_idx", "out_val", "q", "keys",
            ),
            inv_inputs=ENTROPY_INV_INPUTS + ("out_idx", "out_val"),
            inv_pads=ENTROPY_INV_PADS,
            inv_fills=(("out_idx", int(_OUT_SENTINEL)),),
        )

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        spec = spec.resolved()
        shape = spec.shape
        dict_size = int(spec.param("dict_size", 4096))
        padded = tuple(mgard.padded_dim(n) for n in shape)
        L = mgard.total_levels(padded)
        # Classic executables (shared with core/progressive.py): the
        # quantize/dequantize Map&Process stages dispatch through the kernel
        # registry with the spec's adapter baked in; decompose/recompose
        # stay on the portable jnp path under every backend (the paper's
        # fallback rule), which also keeps streams backend-independent.
        # The level map is *donated* to the planned stages and the recycled
        # buffer re-stored — the stage pipeline's quantize segment routes
        # through the same workspace buffer and the same donation contract.
        plan = ReductionPlan(
            spec=spec,
            executables={
                "decompose": partial(mgard.decompose, shape=shape),
                "recompose": partial(mgard.recompose, shape=shape),
                "quantize": mgard.planned_quantize_stage(
                    padded, dict_size, spec.backend
                ),
                "dequantize": mgard.planned_dequantize_stage(spec.backend),
            },
            workspace={"lmap": jnp.asarray(mgard.level_map(padded))},
            meta={"padded": padded, "L": L, "dict_size": dict_size,
                  "backend": spec.backend},
        )
        return self._attach_pipeline(plan)

    def finish_container(self, plan, env, view) -> Compressed:
        spec = plan.spec
        dict_size = plan.meta["dict_size"]
        c = entropy_container(
            plan, env, view, self.name, spec.shape, spec.dtype,
            n_symbols=math.prod(plan.meta["padded"]),
        )
        # Outliers: stored losslessly (sparse), like MGARD's escape path.
        # The device compaction bounds the fetch to the occupied slots; a
        # leaf overflowing the cap falls back to a full fetch (escape keys
        # mark the outlier positions exactly).
        n_out = int(view.fetch("out_count"))
        if n_out <= plan.meta["out_cap"]:
            out_idx = view.fetch("out_idx", n_out).astype(np.int64)
            out_val = view.fetch("out_val", n_out).astype(np.int32)
        else:
            keys = view.fetch("keys").reshape(-1)
            qf = view.fetch("q").reshape(-1)
            out_idx = np.nonzero(keys == dict_size - 1)[0].astype(np.int64)
            out_val = qf[out_idx].astype(np.int32)
        c.meta.update(
            padded=plan.meta["padded"],
            error_bound=float(env.meta["error_bound"]),
            dict_size=dict_size,
        )
        c.arrays.update(
            outlier_idx=out_idx,
            outlier_val=out_val,
            bins=np.asarray(env.meta["bins"], np.float64),
        )
        return c

    def decode_bucket_key(self, c: Compressed) -> tuple:
        return entropy_bucket_key(c)

    def decode_state(self, plan: ReductionPlan, c: Compressed):
        prepared = entropy_decode_state(plan, c)
        if prepared is None:
            return None
        state0, meta = prepared
        out_idx = np.asarray(c.arrays["outlier_idx"], np.int64)
        if out_idx.size and out_idx.max(initial=0) >= int(_OUT_SENTINEL):
            return None  # grid too large for the int32 scatter: host path
        pad = (-out_idx.size) % _OUT_BUCKET
        state0["out_idx"] = np.concatenate(
            [out_idx.astype(np.int32), np.full(pad, _OUT_SENTINEL, np.int32)]
        )
        state0["out_val"] = np.concatenate(
            [np.asarray(c.arrays["outlier_val"], np.int32), np.zeros(pad, np.int32)]
        )
        meta["bins"] = np.asarray(c.arrays["bins"], np.float64)
        return state0, meta

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
        q = _unsigned_to_signed_jit(keys.astype(jnp.uint32))
        qf = np.asarray(q).reshape(-1)
        out_idx = np.asarray(c.arrays["outlier_idx"])
        if out_idx.size:
            qf = qf.copy()
            qf[out_idx] = np.asarray(c.arrays["outlier_val"])
        q = jnp.asarray(qf.reshape(plan.meta["padded"]))
        with plan.lock:
            coeffs, lmap = plan.executables["dequantize"](
                q, plan.workspace["lmap"],
                jnp.asarray(np.asarray(c.arrays["bins"]), jnp.float32),
            )
            plan.recycle("lmap", lmap)
        out = plan.executables["recompose"](coeffs)
        return out.astype(jnp.dtype(c.meta["dtype"]))

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        # Decode plans depend only on geometry + dict size: streams written
        # with any error bound share one reconstruction plan.
        return ReductionSpec.create(
            self.name, c.meta["shape"], c.meta["dtype"],
            dict_size=int(c.meta["dict_size"]),
        )
