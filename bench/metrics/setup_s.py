"""Set-up: process start to the first timed request (data build, transfer
to the device, warm-up, compile or cache load)."""


def read(ctx):
    return ctx.setup_s
