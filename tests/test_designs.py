"""Paper benchmark designs: Table 3 / Table 4 reproduction.

The paper's claim: OmniSim's functional outputs match C/RTL co-simulation
*exactly* for all eleven Type B/C designs, while C-sim fails on every one.
Our co-sim stand-in is the cycle-stepped RTL oracle (DESIGN.md Sec. 7).
"""
import pytest

from repro.core import LightningSim, UnsupportedDesignError, classify, csim, \
    simulate, simulate_rtl
from repro.core.trace import simulate_hybrid
from repro.designs import PAPER_DESIGNS, ROSETTA_DESIGNS, TYPEA_DESIGNS
from repro.designs.rosetta import optical_flow

SMALL_N = 257        # keep unit tests fast; benchmarks use the full N=2025


@pytest.mark.parametrize("name", sorted(PAPER_DESIGNS))
def test_paper_design_matches_cosim(name):
    builder = PAPER_DESIGNS[name]
    r1 = simulate(builder())
    r2 = simulate_rtl(builder())
    assert r1.deadlock == r2.deadlock
    if not r1.deadlock:
        assert r1.outputs == r2.outputs
        assert r1.cycles == r2.cycles


@pytest.mark.parametrize("name", sorted(PAPER_DESIGNS))
def test_paper_design_schedule_independent(name):
    builder = PAPER_DESIGNS[name]
    base = simulate(builder())
    for seed in (0, 1):
        r = simulate(builder(), shuffle_seed=seed)
        assert r.outputs == base.outputs
        assert r.cycles == base.cycles


def test_table3_exact_paper_values():
    """Values that are analytically pinned by the designs (Table 3)."""
    assert simulate(PAPER_DESIGNS["fig4_ex2"]()).outputs["sum_out"] == 2051325
    assert simulate(PAPER_DESIGNS["fig4_ex3"]()).outputs["sum"] == 4098600
    r = simulate(PAPER_DESIGNS["fig2_timer"]())
    assert r.outputs["timer_cycles"] == 6075        # 3 cycles x 2025 items
    assert r.outputs["sink_sum"] == 2051325
    assert simulate(PAPER_DESIGNS["deadlock"]()).deadlock


def test_table3_csim_failures():
    """C-sim column of Table 3: crashes and wrong results."""
    # infinite producer loops -> array overrun -> SIGSEGV
    for name in ("fig4_ex2", "fig4_ex4a_d", "fig4_ex4b_d"):
        r = csim(PAPER_DESIGNS[name]())
        assert r.outputs.get("__crash__") == "@E Simulation failed: SIGSEGV."
    # cyclic blocking -> reads-while-empty -> sum = 0 + warnings
    r = csim(PAPER_DESIGNS["fig4_ex3"]())
    assert r.outputs["sum"] == 0
    assert sum("read while empty" in w for w in r.outputs["__warnings__"]) == 2025
    assert any("leftover" in w for w in r.outputs["__warnings__"])
    # NB writes 'always succeed' -> full (wrong) sum, Dropped = 0
    r = csim(PAPER_DESIGNS["fig4_ex4a"]())
    assert r.outputs["sum_out"] == 2051325
    r = csim(PAPER_DESIGNS["fig4_ex4b"]())
    assert r.outputs == {"sum_out": 2051325, "Dropped": 0}
    # the timer reads the done flag instantly -> counts 0 cycles
    r = csim(PAPER_DESIGNS["fig2_timer"]())
    assert r.outputs["timer_cycles"] == 0


def test_table4_design_inventory():
    """Structural properties from Table 4 (modules / FIFOs / NB / cyclic)."""
    mc = PAPER_DESIGNS["multicore"]()
    assert len(mc.modules) == 34
    assert len(mc.fifos) == 64
    r = simulate(mc)
    c = classify(mc, r)
    assert c.dtype == "C" and c.cyclic and c.has_nonblocking

    ex3 = PAPER_DESIGNS["fig4_ex3"]()
    c3 = classify(ex3, simulate(ex3))
    assert c3.dtype == "B" and c3.cyclic and not c3.has_nonblocking


@pytest.mark.parametrize("name", sorted(PAPER_DESIGNS))
def test_lightningsim_cannot_simulate_paper_designs(name):
    with pytest.raises(UnsupportedDesignError):
        LightningSim(PAPER_DESIGNS[name]()).run()


@pytest.mark.parametrize("name", sorted(TYPEA_DESIGNS))
def test_typea_all_engines_agree(name):
    builder = TYPEA_DESIGNS[name]
    r1 = simulate(builder())
    r2 = simulate_rtl(builder())
    r3 = LightningSim(builder()).run()
    assert r1.outputs == r2.outputs == r3.outputs
    assert r1.cycles == r2.cycles == r3.cycles


@pytest.mark.parametrize("name", sorted(TYPEA_DESIGNS))
def test_typea_classified_a(name):
    builder = TYPEA_DESIGNS[name]
    prog = builder()
    c = classify(prog, simulate(builder()))
    assert c.dtype == "A", f"{name}: {c}"


def test_optical_flow_inventory():
    """Rosetta's optical flow: 9 processes, 14 streams, gradient_z sized
    at the source's max_width * 4."""
    prog = ROSETTA_DESIGNS["optical_flow"](height=4, width=64)
    assert len(prog.modules) == 9
    assert len(prog.fifos) == 14
    depths = {f.name: f.depth for f in prog.fifos}
    assert depths.pop("gradient_z") == 4096
    assert set(depths.values()) == {1024}


def test_optical_flow_all_engines_agree():
    """Generator engine, trace replay, hybrid replay, the decoupled
    baseline and the RTL oracle: identical cycles and outputs."""
    def build():
        return optical_flow(height=4, width=64)
    gen = simulate(build(), trace="never")
    traced = simulate(build(), trace="auto")
    assert traced.engine == "omnisim-trace"
    runs = [traced, simulate_hybrid(build()), LightningSim(build()).run(),
            simulate_rtl(build())]
    assert not gen.deadlock
    assert len(gen.outputs) == 4 * 64            # one velocity a pixel
    for r in runs:
        assert r.cycles == gen.cycles
        assert r.outputs == gen.outputs


def test_optical_flow_starved_z_path_deadlocks():
    """The z path has to hold about 2 * width + 2 pixels while the x/y
    path's line buffers fill: gradient_z plus a frame stream with less
    room than that deadlocks; either one roomy is enough."""
    W = 64
    names = [f.name for f in optical_flow(height=4, width=W).fifos]

    def run(**over):
        depths = [over.get(n, 1024) for n in names]
        return simulate(optical_flow(height=4, width=W), depths=depths)
    assert run(gradient_z=8, frame1_a=8).deadlock
    assert not run(gradient_z=8).deadlock
    assert not run(gradient_z=2 * W + 8, frame1_a=8).deadlock
