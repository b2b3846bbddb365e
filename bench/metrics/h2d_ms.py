"""Host assembly (``core/engine.py::put_batches``): per driver round, the
milliseconds of the host-to-device copy of the stacked mini-batches, host
relayout included, fenced until the copy is ready (``assemble.put``, on any
thread)."""


def read(ctx):
    if ctx.span_count("assemble.put") == 0:
        return None
    return ctx.span_total("assemble.put", thread=None) \
        / ctx.driver_rounds * 1e3
