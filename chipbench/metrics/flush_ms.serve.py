"""Mean host time of the server's calls into ``QueryEngine.query``
(ms), from the benchmark's span around each call."""
from chipbench import reduce


def read(ctx):
    st = reduce.steps(ctx, "open_loop")
    if st is None:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in st) / len(st)
