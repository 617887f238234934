"""Set-up time: process start to the first survey of the window (host
clock). The reference's time is not in it."""


def read(spec, ctx):
    return ctx.setup_s
