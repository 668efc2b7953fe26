"""Device time of the cross pass's WAR half (ops in the ``cross_pass_war``
scope: the depth-dependent ``take_along_axis`` gather and the
scatter-max) per fixpoint round, in ms: over the complete fixpoint
executions of the traced part of the window, over their kernel calls
(one per round)."""
import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None or not red.kernel_calls:
        return None
    war = red.scope_s.get("cross_pass_war", 0.0)
    return 1e3 * war / red.kernel_calls if war > 0 else None
