"""Device busy time per step outside the scan kernels (ms): tower, router, merge."""
from chipbench import reduce


def read(ctx):
    return reduce.nonscan_device_ms(ctx, "closed_loop")
