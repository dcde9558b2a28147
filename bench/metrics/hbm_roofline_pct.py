"""Least bytes the window's answered queries must move, over the chip's
peak HBM bandwidth, as a share of the device's busy time."""


def read(ctx):
    return ctx.hbm_roofline_pct()
