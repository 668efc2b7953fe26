"""While-loop rounds per fixpoint execution: kernel calls (one per
round) over the complete fixpoint executions in the trace."""


def read(ctx):
    s = ctx["summary"]
    if s is None or s.fixpoint_runs == 0 or s.kernel_calls == 0:
        return None
    return s.kernel_calls / s.fixpoint_runs
