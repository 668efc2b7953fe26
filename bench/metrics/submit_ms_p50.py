"""Median host time inside ``SweepService.submit`` of an interactive
request (fingerprint, admission, cache lookup, enqueue), on the
benchmark's host clock, in ms."""

import statistics


def read(ctx):
    xs = [x.submit_s for x in ctx["obs"]["interactive"]]
    if not xs:
        return None
    return 1e3 * statistics.median(xs)
