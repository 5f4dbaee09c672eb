"""A plain reader of the HPDR byte container (version 2).

Layout: ``b"HPDR"``, uint32 version, uint64 header length ``H``, ``H``
bytes of JSON header (``method``, ``meta``, ``sections``: name → dtype,
shape, offset, nbytes; ``payload_bytes``, ``crc32``), then the payload.
"""

from __future__ import annotations

import json
import zlib

import numpy as np


class StreamError(ValueError):
    """The bytes are not a well-formed version-2 container."""


def parse(raw: bytes) -> tuple[str, dict, dict[str, np.ndarray]]:
    """``(method, meta, arrays)`` of one container; checks its crc32."""
    if len(raw) < 16 or raw[:4] != b"HPDR":
        raise StreamError("not an HPDR stream")
    version = int.from_bytes(raw[4:8], "little")
    if version != 2:
        raise StreamError(f"container version {version}, expected 2")
    hlen = int.from_bytes(raw[8:16], "little")
    header = json.loads(raw[16:16 + hlen])
    base = 16 + hlen
    payload = memoryview(raw)[base:base + int(header["payload_bytes"])]
    if len(payload) != int(header["payload_bytes"]):
        raise StreamError("truncated payload")
    if zlib.crc32(payload) & 0xFFFFFFFF != int(header["crc32"]):
        raise StreamError("payload crc32 mismatch")
    arrays = {}
    for name, sec in header["sections"].items():
        lo = int(sec["offset"])
        hi = lo + int(sec["nbytes"])
        arrays[name] = np.frombuffer(payload[lo:hi], np.dtype(sec["dtype"])).reshape(
            sec["shape"])
    return header["method"], header["meta"], arrays
