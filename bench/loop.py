"""The traffic generator: one closed-loop caller, as a traffic mix describes it.

A mix (``traffic/<name>.json``) says how the configuration's field is cut
(``split``, boxes in C order, box ``i`` made on chip ``i mod chips``) and
which entry point writes and reads it back (``entry``):

``api``     for each box in turn, ``api.compress`` then ``to_bytes``; read
            back with ``Compressed.from_bytes`` then ``api.decompress``;
``engine``  all boxes at once through ``ExecutionEngine.compress_pytree``
            on a ``data`` mesh over the chips, each container then
            serialised; read back with ``from_bytes`` and
            ``decompress_pytree``.

A write returns ``{box: bytes}``; a read returns ``{box: array}``, ready.
Every call into the program sits in a ``jax.profiler.TraceAnnotation`` of
its name, which the trace reduction uses to label idle gaps.
"""

from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

from bench import fields


class Caller:
    def __init__(self, config: dict, traffic: dict, devices, seed: int):
        from repro.core import api
        from repro.core.container import Compressed

        self._api, self._compressed = api, Compressed
        self.entry = traffic["entry"]
        self.method = config["codec"]
        self.params = dict(config["params"])
        self.fields = fields.subdomains(seed, config["shape"], traffic["split"],
                                        config["field"], devices)
        jax.block_until_ready(self.fields)
        self.raw_bytes = sum(x.nbytes for x in self.fields.values())
        self.engine = None
        if self.entry == "engine":
            from repro.core.engine import ExecutionEngine
            from repro.launch.mesh import make_data_mesh

            self.engine = ExecutionEngine(mesh=make_data_mesh(len(devices)),
                                          backend=self.params.get("backend", "auto"))
        elif self.entry != "api":
            raise ValueError(f"unknown entry {self.entry!r}")

    def write(self) -> dict[str, bytes]:
        if self.engine is None:
            out = {}
            for key, x in self.fields.items():
                with TraceAnnotation("compress"):
                    c = self._api.compress(x, self.method, **self.params)
                with TraceAnnotation("to_bytes"):
                    out[key] = c.to_bytes()
            return out
        with TraceAnnotation("compress_pytree"):
            comp, _ = self.engine.compress_pytree(
                self.fields, select=lambda key, arr: (self.method, self.params))
        with TraceAnnotation("to_bytes"):
            return {key: comp[key].to_bytes() for key in self.fields}

    def read(self, blobs: dict[str, bytes]) -> dict[str, jax.Array]:
        if self.engine is None:
            out = {}
            for key, raw in blobs.items():
                with TraceAnnotation("from_bytes"):
                    c = self._compressed.from_bytes(raw)
                with TraceAnnotation("decompress"):
                    out[key] = self._api.decompress(c).block_until_ready()
            return out
        with TraceAnnotation("from_bytes"):
            comp = {key: self._compressed.from_bytes(raw) for key, raw in blobs.items()}
        with TraceAnnotation("decompress_pytree"):
            out = self.engine.decompress_pytree(comp, {key: 0 for key in comp})
            return jax.block_until_ready(out)

    def close(self) -> None:
        if self.engine is not None:
            self.engine.close()
