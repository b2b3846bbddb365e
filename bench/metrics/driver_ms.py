"""Driver layer (``core/protocol.py``, ``core/jobs.py``): per driver round,
the milliseconds of the window that no top-level span of the main thread
covers: History and CommMeter replay, telemetry, Python."""

TOP = ("round.feeder_wait", "round.assemble", "block.assemble",
       "pool.feeder_wait", "round.step", "block.step", "pool.step",
       "round.fetch", "block.fetch", "pool.fetch", "round.select",
       "round.eval", "round.checkpoint")


def read(ctx):
    if not ctx.spans:
        return None
    covered = ctx.span_total(*TOP, depth=0)
    return (ctx.window_s - covered) / ctx.driver_rounds * 1e3
