"""ZFP-X codec: fixed-rate lossy compression behind the registry.

The stage graph is a single device stage — ZFP's whole transform chain is
shape/rate-static, so the compiled pipeline is one fused executable with no
host barrier at all (it was the first codec on the engine's stacked
``shard_map`` path for exactly that reason).  Validation (ndim ≤ 4,
rate ∈ [1, 32]) happens at plan time: an invalid spec never enters the CMM.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import zfp
from .. import stages as sg
from ..container import Compressed
from . import register_codec
from .base import Codec, ReductionPlan, ReductionSpec


@register_codec("zfp")
class ZFPCodec(Codec):
    """Fixed-rate block compression (paper §IV-C, Algorithm 3)."""

    spec_defaults = {"rate": 16}

    def build_stages(self, spec: ReductionSpec) -> sg.StageGraph:
        rate = int(spec.param("rate", 16))
        return sg.StageGraph(
            stages=(sg.ZfpBlockTransform(rate, len(spec.shape), spec.shape),),
            finish_keys=("payload", "emax"),
            inv_inputs=("payload", "emax"),
        )

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        spec = spec.resolved()
        rate = int(spec.param("rate", 16))
        dims = len(spec.shape)
        if dims > 4 or dims == 0:
            raise ValueError("zfp supports 1-4 dimensional data")
        if not 1 <= rate <= 32:
            raise ValueError("rate must be in [1, 32] bits/value")
        # The backend adapter is baked into the jitted executables here —
        # kernel dispatch happens once, at plan time.
        plan = ReductionPlan(
            spec=spec,
            executables={
                "encode": partial(
                    zfp.compress_jit, rate=rate, dims=dims, shape=spec.shape,
                    adapter=spec.backend,
                ),
                "decode": partial(
                    zfp.decompress_jit, rate=rate, dims=dims, shape=spec.shape,
                    adapter=spec.backend,
                ),
            },
            meta={"rate": rate, "dims": dims},
        )
        return self._attach_pipeline(plan)

    def finish_container(self, plan, env, view) -> Compressed:
        c = Compressed(
            method=self.name,
            meta={
                "shape": plan.spec.shape,
                "dtype": plan.spec.dtype,
                "rate": plan.meta["rate"],
            },
            arrays={"payload": view.fetch("payload"), "emax": view.fetch("emax")},
        )
        c.meta["stages"] = plan.meta.get("stage_graph", [])
        return c

    def decode_state(self, plan: ReductionPlan, c: Compressed):
        state0 = {
            "payload": np.asarray(c.arrays["payload"]),
            "emax": np.asarray(c.arrays["emax"]),
        }
        return state0, {}

    def decode(
        self, plan: ReductionPlan, c: Compressed, *,
        env=None,
    ) -> jax.Array:
        out = self._pipeline_decode(plan, c, env=env)
        if out is not None:
            return out
        out = plan.executables["decode"](
            jnp.asarray(c.arrays["payload"]), jnp.asarray(c.arrays["emax"])
        )
        return out.astype(jnp.dtype(c.meta["dtype"]))

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        # Backend deliberately defaults to auto: any backend decodes any
        # stream (portability contract), so the decode side picks the best
        # local adapter rather than whatever wrote the stream.
        return ReductionSpec.create(
            self.name, c.meta["shape"], c.meta["dtype"], rate=int(c.meta["rate"])
        )
