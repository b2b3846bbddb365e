"""Selection cascade, host side (``selection/``): per driver round, the
milliseconds of the stacked fetch and the host's selection bookkeeping."""


def read(ctx):
    total = ctx.span_total("round.fetch", "round.select", "block.fetch",
                           "pool.fetch")
    if total <= 0:
        return None
    return total / ctx.driver_rounds * 1e3
