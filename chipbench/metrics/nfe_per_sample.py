"""Mean number of score evaluations of the requests delivered in the
window (the solver's own count per request)."""


def read(ctx):
    done = ctx.window["delivered"]
    return sum(r.nfe for r in done) / len(done) if done else None
