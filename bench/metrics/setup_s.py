"""Set-up: process start to the window's opening (loading, weights, warm-up,
compiles or compile-cache loads)."""


def read(ctx):
    return ctx["setup_s"]
