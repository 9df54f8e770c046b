"""Mosaic scan kernels' device time per step (ms), from the trace."""
from chipbench import reduce


def read(ctx):
    return reduce.scan_device_ms(ctx, "open_loop")
