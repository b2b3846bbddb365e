"""Host assembly (``core/engine.py::draw_round_idx`` and ``take_batches``):
per driver round, the milliseconds of the ``assemble.gather`` spans, on any
thread: the host's draw of the round's mini-batch indices, and the dispatch
of the device gather that takes the mini-batches from the resident client
data (not fenced, so the gather's own device time is not in it)."""


def read(ctx):
    if ctx.span_count("assemble.gather") == 0:
        return None
    return ctx.span_total("assemble.gather", thread=None) \
        / ctx.driver_rounds * 1e3
