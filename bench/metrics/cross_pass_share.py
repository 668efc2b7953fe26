"""Share of the fixpoint program's device time spent outside the
segmented-cummax kernel (the cross pass: gathers and scatter-maxes of the
RAW and WAR edges, and the loop's own bookkeeping), over the complete
fixpoint executions in the trace, in %."""


def read(ctx):
    s = ctx["summary"]
    if s is None or s.fixpoint_runs == 0 or s.fixpoint_busy_s <= 0:
        return None
    return 100.0 * (s.fixpoint_busy_s - s.kernel_s) / s.fixpoint_busy_s
