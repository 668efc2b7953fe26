"""Median round at which a block's fixpoint froze its last cyclic row by
the cycle exit (the ``freeze_round`` of its ``solve.fixpoint`` span), over
the complete traced blocks in which the exit froze a row
(``cyclic_rows`` > 0).  A program whose spans carry no ``freeze_round``
gives no number."""
import statistics

import spans


def read(ctx):
    red = spans.for_run(ctx)
    if red is None:
        return None
    rounds = []
    for b in red.blocks:
        fx = [e.stats for e in b.spans if e.name == "solve.fixpoint"
              and "freeze_round" in e.stats]
        if any(int(st.get("cyclic_rows", 0)) > 0 for st in fx):
            rounds.append(max(int(st["freeze_round"]) for st in fx))
    return float(statistics.median(rounds)) if rounds else None
