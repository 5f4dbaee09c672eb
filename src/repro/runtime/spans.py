"""Named spans of the program's own work, on the profiler's trace.

A span is a ``jax.profiler.TraceAnnotation``: when a trace runs
(``jax.profiler.start_trace`` or a profiling server), the profiler keeps
it on the host plane beside the device's operations, on the same clock;
when none runs it costs what an annotation costs (about a microsecond).
There is no other sink and no switch.

A span opened inside an entry-point call carries ``call``, that call's
id; one opened outside any (a bare ``to_bytes``) carries none.
:func:`root` opens a call (``hpdr.compress``, ``hpdr.decompress``,
the engine's pytree entry points) when none is open; spans nested inside
it, on any thread the call's work reaches through
:class:`~repro.runtime.executor.DeviceExecutor`, share its id.  Parents
are given by nesting on a thread, and across threads by ``call``.

Stats are ints or short strings: ``bytes`` for copies and transfers,
``segment``/``stage`` for stage-graph steps, ``method`` for codecs.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
from typing import Iterator

from jax.profiler import TraceAnnotation

_CALL: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "hpdr_call", default=None
)
_IDS = itertools.count(1)


@contextlib.contextmanager
def span(name: str, **stats: int | str) -> Iterator[None]:
    """Annotate the enclosed work as ``name`` with ``stats`` and the call id."""
    call = _CALL.get()
    if call is not None:
        stats["call"] = call
    with TraceAnnotation(name, **stats):
        yield


@contextlib.contextmanager
def root(name: str, **stats: int | str) -> Iterator[int]:
    """A span that opens a new call unless one is already open."""
    call = _CALL.get()
    token = None
    if call is None:
        call = next(_IDS)
        token = _CALL.set(call)
    try:
        with TraceAnnotation(name, call=call, **stats):
            yield call
    finally:
        if token is not None:
            _CALL.reset(token)
