"""Query-tower passes per server flush: the program's ``repro.dispatch``
spans (one per launched chunk of a plan that runs the tower) inside each
of its ``repro.flush`` spans."""
from chipbench import program_trace, reduce


def read(ctx):
    if reduce.steps(ctx, "open_loop") is None or ctx["trace"] is None:
        return None
    return program_trace.spans_within(ctx["trace"], ctx["window"],
                                      "repro.dispatch", "repro.flush")
