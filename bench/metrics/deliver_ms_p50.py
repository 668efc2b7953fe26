"""Median time a block spends turning verdicts into results, in ms:
``sweep.materialize`` (reasons, result shells, exact fallbacks) plus
``sweep.deliver`` (one result per row to its client), over the complete
blocks in the traced part of the window."""
import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None:
        return None
    return spans.median_ms(map(spans.deliver_s, red.blocks))
