"""Host assembly (``core/engine.py::put_idx``): per driver round, the
milliseconds of the host-to-device copy of the round's drawn mini-batch
indices, fenced until the copy is ready (``assemble.put``, on any
thread)."""


def read(ctx):
    if ctx.span_count("assemble.put") == 0:
        return None
    return ctx.span_total("assemble.put", thread=None) \
        / ctx.driver_rounds * 1e3
