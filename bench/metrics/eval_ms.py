"""Eval (``protocol.evaluate``): milliseconds of one test-set evaluation,
averaged over the eval rounds of the window."""


def read(ctx):
    n = ctx.span_count("round.eval")
    if n == 0:
        return None
    return ctx.span_total("round.eval") / n * 1e3
