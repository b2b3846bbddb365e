"""Host assembly: per driver round, the megabytes copied host to device,
the ``h2d_bytes`` of every span that ends in the stretch (the mini-batches
on ``assemble.put``, the test set on ``round.eval``).  The feeder runs a
round ahead, so the stretch may hold one put more than it has rounds."""


def read(ctx):
    moved = [s["h2d_bytes"] for s in ctx.spans
             if "h2d_bytes" in s and ctx.t0 < s["end"] <= ctx.t1]
    if not moved:
        return None
    return sum(moved) / ctx.driver_rounds / 1e6
