"""Share of the score evaluations issued to occupied slots, for requests
delivered in the window, that went to samples which had already
converged (frozen passengers): 1 - useful / resident NFE, from the
program's ``serve_nfe_useful_total`` and ``serve_nfe_resident_total``."""


def read(ctx):
    resident = ctx.window["resident_nfe"]
    if not resident:
        return None
    return 100.0 * (1.0 - ctx.window["useful_nfe"] / resident)
