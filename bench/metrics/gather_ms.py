"""Host assembly (``core/engine.py::assemble_round_batches``): per driver
round, the milliseconds of the numpy gather that writes the round's
mini-batches into their host buffer (``assemble.gather``, on any thread)."""


def read(ctx):
    if ctx.span_count("assemble.gather") == 0:
        return None
    return ctx.span_total("assemble.gather", thread=None) \
        / ctx.driver_rounds * 1e3
