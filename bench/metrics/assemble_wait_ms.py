"""Host assembly (``core/engine.py::assemble_round``, ``data/pipeline.py``):
per driver round, the milliseconds the driver waited for the round's
payload (the feeder queue, or assembly itself when it runs in line)."""

NAMES = ("round.feeder_wait", "pool.feeder_wait", "round.assemble",
         "block.assemble")


def read(ctx):
    if not ctx.spans:
        return None
    return ctx.span_total(*NAMES, depth=0) / ctx.driver_rounds * 1e3
