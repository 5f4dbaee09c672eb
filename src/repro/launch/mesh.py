"""Production mesh construction (DESIGN.md §6 / brief MULTI-POD DRY-RUN).

A function, not a module-level constant: importing this module never touches
JAX device state (device count is locked on first backend init, and only
``launch/dryrun.py`` is allowed to request 512 placeholder devices).
"""

from __future__ import annotations

import os
import time
import zlib
from dataclasses import dataclass
from pathlib import Path

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis explicitly Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_data_mesh(n: int | None = None):
    """One-axis ``("data",)`` mesh over ``n`` local devices (default: all).

    The execution engine's canonical mesh: independent reductions (pytree
    leaves, stream chunks) shard over this axis.
    """
    devs = jax.devices()
    n = len(devs) if n is None else min(n, len(devs))
    return make_mesh((n,), ("data",))


def data_axis_size(mesh) -> int:
    """Size of the ``data`` axis (1 when the mesh has none)."""
    return int(dict(mesh.shape).get("data", 1))


# ---------------------------------------------------------------------------
# multi-controller host topology (paper Figs. 15/17/18 setting)
# ---------------------------------------------------------------------------

ENV_HOST_ID = "HPDR_HOST_ID"
ENV_HOST_COUNT = "HPDR_HOST_COUNT"


@dataclass(frozen=True)
class HostTopology:
    """Which controller process this is, out of how many.

    The multi-host I/O layer (per-host aggregated shard files, global
    manifest, topology-aware restore) is parameterised by exactly two
    integers; everything else — leaf ownership, shard naming, restore
    locality — derives deterministically from them, so every host computes
    the same assignment without communicating.
    """

    host_id: int = 0
    n_hosts: int = 1

    def __post_init__(self):
        if not 0 <= self.host_id < max(1, self.n_hosts):
            raise ValueError(
                f"host_id {self.host_id} out of range for {self.n_hosts} hosts"
            )

    @property
    def multi_host(self) -> bool:
        return self.n_hosts > 1

    def owner(self, key: str) -> int:
        """Deterministic leaf→host assignment (stable across processes).

        crc32 is byte-stable everywhere (unlike ``hash`` under
        ``PYTHONHASHSEED``), so every host — and every *later* process with
        the same host count — derives the identical mapping; that identity
        is what makes a same-topology restore purely shard-local.
        """
        return zlib.crc32(str(key).encode()) % max(1, self.n_hosts)

    def owns(self, key: str) -> bool:
        return self.owner(key) == self.host_id


def detect_topology() -> HostTopology:
    """This process's :class:`HostTopology`.

    Resolution order: the ``HPDR_HOST_ID`` / ``HPDR_HOST_COUNT`` environment
    override (the subprocess-simulated multi-controller setting used by the
    tests and benchmarks), then ``jax.distributed`` process indices, then
    single-host.
    """
    env_n = os.environ.get(ENV_HOST_COUNT)
    if env_n is not None:
        return HostTopology(int(os.environ.get(ENV_HOST_ID, 0)), int(env_n))
    return HostTopology(jax.process_index(), jax.process_count())


def fs_barrier(
    directory: str | Path,
    name: str,
    topology: HostTopology,
    *,
    timeout: float = 120.0,
    poll_s: float = 0.005,
    payload: str = "ok",
) -> None:
    """Shared-filesystem rendezvous: block until every host arrives.

    Each host writes ``<directory>/.barrier-<name>.<host>`` (atomically, via
    rename) and polls until all ``n_hosts`` marker files exist.  This is the
    coordinator rendezvous for the multi-controller checkpoint writer — the
    only requirement is a shared filesystem, matching the subprocess-
    simulated test setting.  Markers are left behind (names are unique per
    step) so a late arrival still sees the full barrier.
    """
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    mine = directory / f".barrier-{name}.{topology.host_id}"
    tmp = mine.with_name(mine.name + f".tmp{os.getpid()}")
    tmp.write_text(payload)
    os.replace(tmp, mine)
    deadline = time.monotonic() + timeout
    while True:
        present = {
            suffix
            for p in directory.glob(f".barrier-{name}.*")
            if (suffix := p.name.rsplit(".", 1)[-1]).isdigit()
        }
        if len(present) >= topology.n_hosts:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"fs_barrier {name!r}: {len(present)}/{topology.n_hosts} "
                f"hosts after {timeout}s (present: {sorted(present)})"
            )
        time.sleep(poll_s)


def barrier_payloads(
    directory: str | Path, name: str, topology: HostTopology
) -> dict[int, str]:
    """Read every host's barrier marker payload (post-``fs_barrier``).

    The checkpoint coordinator uses the payloads as a zero-extra-round-trip
    side channel: each host's marker carries its shard's write stats.
    """
    out: dict[int, str] = {}
    for h in range(topology.n_hosts):
        p = Path(directory) / f".barrier-{name}.{h}"
        if p.exists():
            out[h] = p.read_text()
    return out


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over however many devices exist (tests / examples)."""
    n = len(jax.devices())
    n_data = min(n_data, n)
    n_model = min(n_model, max(1, n // n_data))
    return make_mesh((n_data, n_model), ("data", "model"))
