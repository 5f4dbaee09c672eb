"""Shared fixtures. NB: no XLA_FLAGS here — tests see the real device count
(the 512-device override belongs exclusively to launch/dryrun.py)."""

import warnings
from types import SimpleNamespace

import numpy as np
import pytest

try:
    import hypothesis  # noqa: F401
except ImportError:  # offline image: run @given tests on fixed examples
    import _hypothesis_compat

    _hypothesis_compat._install()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def trace_spans(tmp_path):
    """``record(fn) -> (fn(), spans)``: run ``fn`` under a profiler trace and
    read back the program's ``hpdr.*`` spans from the host plane, each with
    ``name``, ``start``/``end`` (ns), ``thread`` and its ``stats`` dict."""
    import jax
    from jax.profiler import ProfileData

    def record(fn):
        trace_dir = tmp_path / f"trace{len(list(tmp_path.iterdir()))}"
        jax.profiler.start_trace(str(trace_dir))
        try:
            out = jax.block_until_ready(fn())
        finally:
            jax.profiler.stop_trace()
        (path,) = trace_dir.rglob("*.xplane.pb")
        with warnings.catch_warnings():  # event stats warn on introspection
            warnings.simplefilter("ignore", DeprecationWarning)
            spans = [
                SimpleNamespace(name=e.name, start=e.start_ns, end=e.end_ns,
                                thread=line.name, stats=dict(e.stats))
                for plane in ProfileData.from_file(str(path)).planes
                if plane.name.startswith("/host:")
                for line in plane.lines
                for e in line.events
                if e.name.startswith("hpdr.")
            ]
        return out, sorted(spans, key=lambda s: s.start)

    return record


def smooth_field_3d(n: int = 48, noise: float = 0.0, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    g = np.linspace(0, 4 * np.pi, n)
    x, y, z = np.meshgrid(g, g, g, indexing="ij")
    f = np.sin(x) * np.cos(y) * np.sin(z)
    if noise:
        f = f + noise * rng.normal(size=f.shape)
    return f.astype(np.float32)


def span_seconds(spans) -> dict[str, float]:
    """Seconds per stage-graph step, from ``hpdr.segment``/``hpdr.host_stage``."""
    out: dict[str, float] = {}
    for sp in spans:
        if sp.name in ("hpdr.segment", "hpdr.host_stage"):
            name = sp.stats.get("segment") or sp.stats.get("stage")
            out[name] = out.get(name, 0.0) + (sp.end - sp.start) * 1e-9
    return out
