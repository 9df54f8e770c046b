"""Time per server flush with no device op running (ms): the host part of
each of the program's ``repro.flush`` spans, from the trace."""
from chipbench import program_trace, reduce


def read(ctx):
    if reduce.steps(ctx, "open_loop") is None or ctx["trace"] is None:
        return None
    return program_trace.span_idle_ms(ctx["trace"], ctx["window"],
                                      "repro.flush")
