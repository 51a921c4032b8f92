"""Set-up: process start to the first due request."""


def read(ctx):
    return ctx.setup_s
