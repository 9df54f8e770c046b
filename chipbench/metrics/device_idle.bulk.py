"""Share of the traced window with no device operation running (%)."""
from chipbench import reduce


def read(ctx):
    return reduce.device_idle(ctx, "closed_loop")
