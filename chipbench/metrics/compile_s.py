"""Seconds the backend spent compiling during set-up (JAX's
``backend_compile_duration`` events): what set-up pays for programs the
persistent cache did not hold."""


def read(ctx):
    return ctx.compile_s
