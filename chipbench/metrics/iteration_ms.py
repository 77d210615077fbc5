"""Device time of the step program in the traced slice over the
Algorithm-1 iterations it ran there: milliseconds per iteration of the
whole slot batch (two score evaluations per slot and the solver step)."""


def read(ctx):
    it = ctx.slice["iterations"]
    busy = ctx.trace["program_s"][ctx.step_program]
    return 1e3 * busy / it if it and busy else None
