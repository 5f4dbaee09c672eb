"""Share of the decompress phase in which the device ran no operation (%).

Mean over the chips of the cell; ``trace.idle_shares`` gives each chip's.
"""

from bench import trace


def read(ctx):
    shares = trace.idle_shares(ctx.trace, "decompress") if ctx.trace else None
    return sum(shares.values()) / len(shares) if shares else None
