"""Share of the traced part of the window in which no operation ran on
the device (1 - busy / window, from the profiler trace), in %."""


def read(ctx):
    s = ctx["summary"]
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
