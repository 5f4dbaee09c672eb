"""Progressive MGARD codec: refactored precision tiers behind the registry.

``mgard-progressive`` containers hold one separately addressable section per
precision component (see :mod:`repro.core.progressive`), so a reader can
verify and decode a prefix of the payload without touching the rest — the
per-section crc32 entries container v2 records make that safe.  Registry
``decode`` reconstructs at full precision; progressive consumers open the
same bytes with :class:`repro.core.progressive.ProgressiveReader` instead.

The codec declares no stage graph of its own: every device executable it
runs comes from the geometry-keyed ``mgard`` plan and the shared Huffman
plan (both CMM entries), one per shape regardless of error bound.  The
engine's per-leaf fallback and the ``CompressorStream`` one-phase container
path handle pipeline-less codecs already, so checkpoint/serving integration
needs no special casing beyond the leaf policy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import mgard
from ..container import Compressed
from . import register_codec
from .base import Codec, ReductionPlan, ReductionSpec


@register_codec("mgard-progressive")
class ProgressiveMGARDCodec(Codec):
    """Multi-precision refactoring (HP-MDR model) as a registered codec."""

    spec_defaults = {
        "error_bound": 1e-2,
        "relative": True,
        "dict_size": 4096,
        "tiers": 3,
        "tier_ratio": 8.0,
    }

    def plan(self, spec: ReductionSpec) -> ReductionPlan:
        spec = spec.resolved()
        padded = tuple(mgard.padded_dim(n) for n in spec.shape)
        # No executables of its own: encode/decode borrow the geometry-keyed
        # mgard plan + the shared huffman plan through the CMM (see module
        # docstring), so this plan is metadata only.
        return ReductionPlan(
            spec=spec,
            meta={"padded": padded, "L": mgard.total_levels(padded),
                  "dict_size": int(spec.param("dict_size", 4096))},
        )

    def encode(
        self, plan: ReductionPlan, data: jax.Array, *,
        env=None,
    ) -> Compressed:
        from .. import progressive  # lazy: codecs package loads before it

        spec = plan.spec
        data = jnp.asarray(data)
        eb = float(spec.param("error_bound", 1e-2))
        if bool(spec.param("relative", True)):
            x = np.asarray(data)
            vrange = float(x.max() - x.min()) if x.size else 0.0
            scaled = eb * vrange
            eb = scaled if scaled > 0 else eb  # constant data: absolute bound
        stream = progressive.refactor(
            data, eb,
            tiers=int(spec.param("tiers", 3)),
            tier_ratio=float(spec.param("tier_ratio", 8.0)),
            dict_size=int(spec.param("dict_size", 4096)),
            backend=spec.backend,
        )
        c = stream.to_container()
        c.meta["dtype"] = spec.dtype
        c.meta["error_bound"] = float(spec.param("error_bound", 1e-2))
        c.meta["relative"] = bool(spec.param("relative", True))
        return c

    def decode(
        self, plan: ReductionPlan, c: Compressed, *,
        env=None,
    ) -> jax.Array:
        from .. import progressive  # lazy

        stream = progressive.ProgressiveStream.from_container(c)
        out = progressive.retrieve(stream, backend=plan.spec.backend)
        return out.astype(jnp.dtype(c.meta["dtype"]))

    def decode_spec(self, c: Compressed) -> ReductionSpec:
        # Reconstruction depends only on geometry + dictionary size; the
        # per-stream tier ladder rides in the container manifest.
        return ReductionSpec.create(
            self.name, c.meta["shape"], c.meta["dtype"],
            dict_size=int(c.meta["dict_size"]),
        )
