"""Share of its bytes roofline that the ``zfp_block`` compress kernel reaches (%).

Least time: the bytes the kernel must move at the chip's HBM bandwidth
(``peaks.json``), divided by its summed device time in the compress
phase. The bytes bound is used because the kernel does integer work, for
which the published sheet gives no peak. The kernel is found by the name
the trace gives it (see ``PATTERN``).
"""

from bench import trace

PATTERN = r"^%compress_blocks(\.\d+)? = .*tpu_custom_call"


def bytes_moved(meta: dict, arrays: dict) -> int:
    """One field: read its blocks as float32, write each block's payload
    words and its int32 exponent."""
    blocks, words = arrays["payload"].shape
    return 4 * blocks * (64 + words + 1)


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = trace.kernel_seconds(ctx.trace, PATTERN, "compress")
    if seconds <= 0:
        return None
    moved = sum(bytes_moved(m, a) for m, a in ctx.streams.values()) * ctx.calls["compress"]
    return 100.0 * moved / ctx.peak("hbm_bytes_per_s") / seconds
