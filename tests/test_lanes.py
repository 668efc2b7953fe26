"""Every registered design served on every solve lane.

Each design of :data:`repro.designs.ALL_DESIGNS` is served through
``SweepService(backend=lane)`` for each lane — ``numpy`` (Gauss-Seidel),
``reference`` (Jacobi) and ``jax`` (the sparse Pallas lane, interpreted
on the CPU) — and every row is held to the batch oracle: its reuse
verdict equals looped ``resimulate``, its cycles, outputs and deadlock
flag equal a from-scratch ``simulate``.  The rows are the base depths,
all ones and four seeded draws from [1, 2 × the largest base depth].

The verdicts are pinned where the registry makes them: the base row
reuses, all ones flips a constraint (VIOLATED) on the drop-and-poll
designs of Fig. 4 and inverts the event order (CYCLE) on the optical
flow pipeline, so no lane passes on REUSED rows alone.
"""
import numpy as np
import pytest

from batch_oracle import assert_batch_exact
from repro.core import simulate
from repro.core.dse import CYCLE, REUSED, VIOLATED
from repro.designs import ALL_DESIGNS
from repro.sweep import SweepService

LANES = ("numpy", "reference", "jax")
# registry defaults, except where the default is a benchmark-sized frame
KWARGS = {"optical_flow": dict(height=2, width=16)}
# the all-ones row's verdict, where it is not REUSED
ONES_VERDICT = {"fig4_ex4a": VIOLATED, "fig4_ex4a_d": VIOLATED,
                "fig4_ex4b": VIOLATED, "fig4_ex4b_d": VIOLATED,
                "fig4_ex5": VIOLATED, "optical_flow": CYCLE}
# each design's oracle rows, simulated once and checked on every lane
_ORACLE: dict = {}


def _rows(base, seed: int = 0) -> np.ndarray:
    """Base depths, all ones, and four draws from [1, 2 × max base]."""
    d0 = np.asarray(base.depths, np.int64)
    rng = np.random.default_rng(seed)
    hi = 2 * max(int(d0.max()), 1)
    return np.vstack([d0, np.ones_like(d0),
                      rng.integers(1, hi + 1, size=(4, len(d0)))])


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("name", sorted(ALL_DESIGNS))
def test_every_design_served_on_every_lane(name, lane):
    if lane == "jax":
        pytest.importorskip("jax")
    kwargs = KWARGS.get(name, {})
    builder = lambda: ALL_DESIGNS[name](**kwargs)
    base = simulate(builder())
    D = _rows(base)
    with SweepService(backend=lane, block=8) as svc:
        out = svc.sweep(base, D)
    assert_batch_exact(out, builder, D, base,
                       memo=_ORACLE.setdefault(name, {}))
    if not base.deadlock:
        assert out.status[0] == REUSED, out.reasons[0]
    if name in ONES_VERDICT:
        assert out.status[1] == ONES_VERDICT[name], out.reasons[1]
