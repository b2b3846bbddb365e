"""Round program (``core/runner.py``): per driver round, the milliseconds
of the fenced device step (``round.step``, ``block.step`` or
``pool.step``)."""


def read(ctx):
    total = ctx.span_total("round.step", "block.step", "pool.step")
    if total <= 0:
        return None
    return total / ctx.driver_rounds * 1e3
