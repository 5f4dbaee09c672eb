"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and kernel time.

Device activity is the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane:
one event per operation, with its start and duration on the host's clock.
Busy time is the union of those intervals. The benchmark's own
annotations (``jax.profiler.TraceAnnotation``) sit on the host plane's
thread lines: ``window.compress`` and ``window.decompress`` delimit the
two phases of a run, and the calls inside them (``compress``,
``to_bytes``, ``from_bytes``, ``decompress``, ``compress_pytree``,
``decompress_pytree``) label the gaps in which the device was idle.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

CALLS = ("compress", "to_bytes", "from_bytes", "decompress",
         "compress_pytree", "decompress_pytree")
PHASES = ("compress", "decompress")
OPS_LINE = "XLA Ops"
# an operation's event is named by its HLO instruction: "%name = shape kind(operands), ..."
_OP_HEAD = re.compile(r"^(%\S+ = \S+ [\w-]+)")


@dataclass
class Event:
    name: str
    start: float  # seconds
    end: float


@dataclass
class Trace:
    host: list[Event] = field(default_factory=list)          # our annotations
    devices: dict[str, list[Event]] = field(default_factory=dict)  # ops per chip

    def window(self, phase: str) -> tuple[float, float] | None:
        spans = [e for e in self.host if e.name == f"window.{phase}"]
        if not spans:
            return None
        return min(e.start for e in spans), max(e.end for e in spans)


def load(path) -> Trace:
    """Read the annotations and the device operations of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    wanted = set(CALLS) | {f"window.{p}" for p in PHASES}
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                           for e in line.events)
            if ops:
                trace.devices[plane.name] = sorted(ops, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        trace.host.append(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    return trace


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged busy intervals of ``events``, clipped to ``[lo, hi]``."""
    merged: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi))


def idle_shares(trace: Trace, phase: str) -> dict[str, float] | None:
    """Idle share of each chip over the phase's window, in percent."""
    span = trace.window(phase)
    if span is None or not trace.devices:
        return None
    lo, hi = span
    return {dev: 100.0 * (1.0 - busy_seconds(ops, lo, hi) / (hi - lo))
            for dev, ops in trace.devices.items()}


def kernel_seconds(trace: Trace, pattern: str, phase: str) -> float:
    """Summed device time of operations whose name matches ``pattern``."""
    span = trace.window(phase)
    if span is None:
        return 0.0
    lo, hi = span
    rx = re.compile(pattern)
    return sum(min(e.end, hi) - max(e.start, lo)
               for ops in trace.devices.values() for e in ops
               if e.end > lo and e.start < hi and rx.search(e.name))


def op_name(name: str) -> str:
    """An operation's instruction, shape and kind, without its operands."""
    m = _OP_HEAD.match(name)
    return m.group(1) if m else name[:120]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, summed over chips."""
    total: dict[str, float] = {}
    for ops in trace.devices.values():
        for e in ops:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                key = op_name(e.name)
                total[key] = total.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def label_at(trace: Trace, t: float) -> str:
    """The innermost benchmark annotation open at time ``t``."""
    open_ = [e for e in trace.host if e.start <= t <= e.end]
    if not open_:
        return "outside"
    return min(open_, key=lambda e: e.end - e.start).name


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest spans in ``[lo, hi]`` in which no chip ran an operation,
    each named by the annotation open at its midpoint."""
    busy = union([e for ops in trace.devices.values() for e in ops], lo, hi)
    gaps, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_at(trace, (a + b) / 2), b - a] for a, b in gaps[:n]]
