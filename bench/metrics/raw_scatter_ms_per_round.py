"""Device time of the cross pass's RAW half (ops in the ``cross_pass_raw``
scope: gather, add, scatter-max) per fixpoint round, in ms: over the
complete fixpoint executions of the traced part of the window, over
their kernel calls (one per round)."""
import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None or not red.kernel_calls:
        return None
    raw = red.scope_s.get("cross_pass_raw", 0.0)
    return 1e3 * raw / red.kernel_calls if raw > 0 else None
