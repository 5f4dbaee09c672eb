"""Seeded fields, generated on the device.

The generator is the one ``chip_smoke.nyx_field`` uses (a red-spectrum sum
of plane waves plus small-scale noise, optionally exponentiated to a
log-normal density), with its constants read from the configuration's
``field`` entry and evaluated on any box of the full grid, so that a
domain-decomposed run can make each subdomain on its own device.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that uses all 64 bits of ``seed``.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a Python int, so
    seeds that differ above bit 31 would give the same data.
    """
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


@partial(jax.jit, static_argnames=("full", "box", "spec"))
def _make(key, origin, index, *, full, box, spec):
    spec = dict(spec)
    n_modes = int(spec["modes"])
    kdir, kphase, knoise = jax.random.split(key, 3)
    dims = len(full)
    dirs = jax.random.normal(kdir, (n_modes, dims))
    dirs = dirs / jnp.linalg.norm(dirs, axis=1, keepdims=True)
    kmag = 2.0 ** (jnp.arange(n_modes) % int(spec["octaves"]))  # cycles per box
    amp = float(spec["amplitude"]) * kmag ** float(spec["slope"])
    phase = jax.random.uniform(kphase, (n_modes,), maxval=2 * math.pi)
    axes = []
    for d in range(dims):
        g = (origin[d] + jnp.arange(box[d], dtype=jnp.float32)) * (2 * math.pi / full[d])
        axes.append(g.reshape([-1 if a == d else 1 for a in range(dims)]))
    s = jnp.zeros(box, jnp.float32)
    for m in range(n_modes):
        k = dirs[m] * kmag[m]
        s = s + amp[m] * jnp.cos(sum(k[d] * axes[d] for d in range(dims)) + phase[m])
    noise_key = jax.random.fold_in(knoise, index)
    s = s + float(spec["noise"]) * jax.random.normal(noise_key, box, jnp.float32)
    if spec["transform"] == "exp":
        return jnp.exp(s)
    if spec["transform"] != "none":
        raise ValueError(f"unknown field transform {spec['transform']!r}")
    return s


def subdomains(seed: int, shape, split, spec: dict, devices) -> dict:
    """The field of ``shape`` cut into ``split`` boxes, C order.

    Box ``i`` is generated on ``devices[i % len(devices)]``; the boxes
    together are one field (the plane waves are evaluated on the full
    grid's coordinates), and the noise of each box is drawn from its own
    fold of the seed.  Returns ``{"d<i>": array}``.
    """
    shape, split = tuple(shape), tuple(split)
    if any(n % s for n, s in zip(shape, split)):
        raise ValueError(f"shape {shape} does not split evenly into {split}")
    box = tuple(n // s for n, s in zip(shape, split))
    frozen = tuple(sorted(spec.items()))
    key = seed_key(seed)
    out = {}
    count = math.prod(split)
    for i in range(count):
        pos, rest = [], i
        for s in reversed(split):
            pos.append(rest % s)
            rest //= s
        origin = jnp.asarray([p * b for p, b in zip(reversed(pos), box)], jnp.float32)
        dev = devices[i % len(devices)]
        k, o, j = jax.device_put((key, origin, jnp.int32(i)), dev)
        out[f"d{i}"] = _make(k, o, j, full=shape, box=box, spec=frozen)
    return out
