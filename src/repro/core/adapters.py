"""Device adapters — HPDR §III-C, adapted to JAX backends.

The paper lowers its two execution models (GEM/DEM) through per-backend
*device adapters* (OpenMP / CUDA / HIP).  In JAX the portable layer is XLA
itself, so our adapters select *how a reduction op is lowered*, not a
hand-written backend:

  * ``xla``              — pure ``jnp`` program; lowers to CPU/GPU/TPU via XLA.
                           This is the portability baseline and the oracle.
  * ``pallas``           — hand-tiled TPU kernels (``pl.pallas_call`` +
                           ``BlockSpec`` VMEM staging).  Target path on TPU.
  * ``pallas_interpret`` — same kernels executed with ``interpret=True``
                           (Python/CPU), used for validation in this container.

The portability contract of the paper carries over: a bitstream produced
under any adapter decodes under any other (tested in
``tests/test_api_portability.py``).

Ops register one implementation per adapter in ``_REGISTRY``; callers go
through :func:`dispatch` so the choice is a runtime config, exactly like the
paper's pluggable adapters.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax

XLA = "xla"
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
AUTO = "auto"

ADAPTERS = (XLA, PALLAS, PALLAS_INTERPRET)

_REGISTRY: dict[tuple[str, str], Callable] = {}


def register(op: str, adapter: str) -> Callable[[Callable], Callable]:
    """Decorator: register ``fn`` as the implementation of ``op`` under ``adapter``."""
    if adapter not in ADAPTERS:
        raise ValueError(f"unknown adapter {adapter!r}; expected one of {ADAPTERS}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, adapter)] = fn
        return fn

    return deco


@functools.cache
def default_adapter() -> str:
    """Pick the best adapter for the current platform (paper: 'best processor')."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return PALLAS
    # Pallas-interpret is functionally correct everywhere but slow; XLA is the
    # fast portable path on CPU/GPU.
    return XLA


@functools.cache
def available_backends() -> tuple[str, ...]:
    """Adapters that can actually execute on the current platform.

    ``pallas`` (compiled, ``interpret=False``) needs a Mosaic/Triton lowering
    and is only runnable on TPU/GPU; ``xla`` and ``pallas_interpret`` run
    everywhere.  This is the capability probe plan building uses to bind a
    spec's ``backend`` before any kernel is traced.
    """
    platform = jax.devices()[0].platform
    if platform in ("tpu", "gpu", "cuda", "rocm"):
        return (XLA, PALLAS, PALLAS_INTERPRET)
    return (XLA, PALLAS_INTERPRET)


def resolve_backend(backend: str | None) -> str:
    """Resolve a spec-level backend request to a concrete, runnable adapter.

    ``auto``/``None`` picks the platform default; an explicit request is
    validated against :func:`available_backends` so an unsupported backend
    fails loudly at plan time instead of deep inside a kernel trace.
    """
    if backend is None or backend == AUTO:
        return default_adapter()
    if backend not in ADAPTERS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {(AUTO,) + ADAPTERS}"
        )
    if backend not in available_backends():
        raise ValueError(
            f"backend {backend!r} is not runnable on this platform "
            f"(available: {available_backends()})"
        )
    return backend


@functools.cache
def supports_donation() -> bool:
    """True where XLA implements input-output buffer aliasing (TPU/GPU)."""
    return jax.devices()[0].platform in ("tpu", "gpu", "cuda", "rocm")


def donating_jit(fn: Callable, *, donate_argnums: tuple[int, ...] = (), **jit_kwargs):
    """``jax.jit`` that donates ``donate_argnums`` only where donation exists.

    Plans route persistent workspace buffers through this so reuse is true
    in-place recycling on TPU/GPU while CPU (donation unimplemented) avoids
    a per-call "donated buffers were not usable" warning.
    """
    if supports_donation() and donate_argnums:
        return jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)
    return jax.jit(fn, **jit_kwargs)


def resolve(adapter: str | None) -> str:
    if adapter is None or adapter == AUTO:
        return default_adapter()
    if adapter not in ADAPTERS:
        raise ValueError(f"unknown adapter {adapter!r}; expected one of {ADAPTERS}")
    return adapter


# Ops that have no Pallas kernel and run their ``xla`` implementation under
# every adapter.  This is the one place where a kernel adapter may lower an
# op through XLA; every other op must register its own implementation.
#   huffman_pack_stream — the exclusive scan + segment-sum packing is already
#                         the TPU-native formulation (core/bitstream.py).
XLA_ONLY_OPS = frozenset({"huffman_pack_stream"})


def lowering(op: str, adapter: str | None = None) -> str:
    """The adapter whose implementation ``dispatch(op, adapter)`` runs."""
    a = resolve(adapter)
    if (op, a) in _REGISTRY:
        return a
    if op in XLA_ONLY_OPS and (op, XLA) in _REGISTRY:
        return XLA
    raise KeyError(f"op {op!r} has no implementation for adapter {a!r}")


def dispatch(op: str, adapter: str | None = None) -> Callable:
    """Return the registered implementation of ``op`` for ``adapter``.

    Only ops declared in :data:`XLA_ONLY_OPS` fall back to their ``xla``
    implementation; any other op without a kernel for ``adapter`` raises,
    so a missing kernel can never hide behind the XLA lowering.
    """
    return _REGISTRY[(op, lowering(op, adapter))]


def registered_ops() -> dict[tuple[str, str], Callable]:
    return dict(_REGISTRY)
