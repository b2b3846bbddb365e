"""Round program, whole round: the model operations of the rounds that
ended in the stretch of the traced run's timed call before the profiler
starts (``flops.round_flops`` of the cell's model kind, for every job),
over that stretch's seconds on the host clock, the chips and the chip's
bf16 peak, in percent.  The
handoff re-check is not counted: the fused program reuses the validation
activations for it, so it runs no such forward."""
import math

import flops


def read(ctx):
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    if ctx.window_s <= 0 or not math.isfinite(peak):
        return None
    every = int(ctx.cell.traffic["eval_every"])
    t_last = ctx.rounds_per_job - 1
    per_job = sum(flops.round_flops(
        ctx.cell.kind, ctx.cell.cfg,
        eval_round=(t % every == 0 or t == t_last), recheck_visits=0) for t in ctx.rounds)
    return 100.0 * per_job * ctx.jobs / (ctx.window_s * peak)
