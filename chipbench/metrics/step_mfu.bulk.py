"""Required FLOPs of the steps over their wall time at the bf16 peak (%)."""
from chipbench import reduce


def read(ctx):
    return reduce.step_mfu(ctx, "closed_loop")
