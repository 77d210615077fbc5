"""The step program's share of the chip's bf16 peak: FLOPs of the score
evaluations the requests needed in the traced slice (useful NFE times the
configuration's FLOPs per evaluation, from its shapes) over the step
program's device time times the peak. Evaluations of idle or converged
slots do not count, so the share cannot pass 100% by skipping them."""


def read(ctx):
    busy = ctx.trace["program_s"][ctx.step_program]
    if not busy or not ctx.slice["useful_nfe"]:
        return None
    flops = ctx.slice["useful_nfe"] * ctx.flop_per_eval
    return 100.0 * flops / (busy * ctx.peak_flops)
