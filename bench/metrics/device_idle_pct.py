"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window."""


def read(ctx):
    return ctx.idle_pct()
