"""Host assembly: per driver round, the milliseconds the feeder thread
spent assembling payloads (gathering batches, keys, attack lanes, the
transfer), whether or not the driver waited for them."""


def read(ctx):
    n = ctx.span_count("feeder.assemble")
    if n == 0:
        return None
    return ctx.span_total("feeder.assemble", thread=None) \
        / ctx.driver_rounds * 1e3
