"""The exactness oracle for batched re-simulation, shared by the DSE and
lane tests (``from batch_oracle import assert_batch_exact``)."""
from repro.core import resimulate, simulate


def assert_batch_exact(out, builder, D, base, memo=None):
    """Every config: verdict matches looped resimulate, numbers match a
    from-scratch simulation.  ``memo``, where given, keeps each row's
    oracle (keyed by its depths) for the next outcome checked against
    the same design."""
    for k in range(len(D)):
        depths = tuple(int(d) for d in D[k])
        want = memo.get(depths) if memo is not None else None
        if want is None:
            inc = resimulate(base, depths)
            full = simulate(builder(), depths=depths)
            want = (inc.ok, inc.reason, full.cycles, full.outputs,
                    full.deadlock)
            if memo is not None:
                memo[depths] = want
        ok, reason, cycles, outputs, deadlock = want
        assert bool(out.ok[k]) == ok, (k, depths, out.reasons[k], reason)
        assert out.cycles[k] == cycles, (k, depths)
        assert out.results[k].outputs == outputs, (k, depths)
        assert out.results[k].deadlock == deadlock, (k, depths)
