"""Median time of one copy of a block's fixpoint results to the host
(the ``solve.copy_back`` span: times, flags and rounds), in ms, over the
traced part of the window."""
import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None:
        return None
    return spans.median_ms(red.durations.get("solve.copy_back", []))
