"""Median queue wait of an interactive request, in ms: from its submit to
the block that takes its first rows (``wait_us`` of the scheduler's
``sweep.dequeue`` marker), over the requests dequeued in the traced
part of the window."""
import statistics

import spans


def read(ctx):
    red = spans.for_run(ctx)
    waits = spans.interactive_waits_ms(red) if red is not None else []
    return statistics.median(waits) if waits else None
