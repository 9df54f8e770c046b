"""Least scan time the chip allows over the kernels' device time (%)."""
from chipbench import reduce


def read(ctx):
    return reduce.scan_roofline(ctx, "open_loop")
