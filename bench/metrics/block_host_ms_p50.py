"""Median host time of a block, in ms: from its ``sweep.assemble`` start
to its ``sweep.deliver`` end, less the time the scheduler waited on the
device (``solve.fixpoint``), over the complete blocks in the traced part
of the window."""
import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None:
        return None
    return spans.median_ms(map(spans.block_host_s, red.blocks))
