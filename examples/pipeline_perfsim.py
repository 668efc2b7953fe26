"""Distributed-schedule DSE with the OmniSim engine.

    PYTHONPATH=src python examples/pipeline_perfsim.py

A pipeline-parallel training step is a dataflow design (stages = modules,
activation queues = FIFOs).  OmniSim predicts step time for GPipe vs 1F1B
across microbatch counts and buffer depths, using incremental
re-simulation for the depth sweep.  Tick costs are synthetic: 40 ticks
forward and 80 backward per stage and microbatch.
"""
import dataclasses

from repro.perfsim.pipeline import (PipelineSpec, buffer_depth_dse,
                                    simulate_pipeline)


def main():
    spec = PipelineSpec(stages=8, microbatches=32, fwd_ticks=40,
                        bwd_ticks=80)
    print(f"{'schedule':>9s} {'mb':>4s} {'depth':>6s} {'step(ticks)':>12s} "
          f"{'bubble':>8s}")
    for schedule in ("gpipe", "1f1b"):
        for mb in (8, 16, 32, 64):
            s = dataclasses.replace(spec, schedule=schedule, microbatches=mb)
            r = simulate_pipeline(s)
            print(f"{schedule:>9s} {mb:4d} {s.buffer_depth:6d} "
                  f"{r.step_ticks:12d} {r.bubble_fraction:7.1%}")

    print("\nbuffer-depth DSE via incremental re-simulation (gpipe, mb=32):")
    g = dataclasses.replace(spec, schedule="gpipe", microbatches=32,
                            buffer_depth=1)
    for depth, res, incr_s in buffer_depth_dse(g, [1, 2, 4, 8]):
        how = "" if incr_s is None else (
            f"  incr {abs(incr_s)*1e3:.2f} ms"
            + ("" if incr_s >= 0 else " (constraints broke -> full)"))
        print(f"  depth={depth:3d}  step={res.step_ticks:8d}  "
              f"bubble={res.bubble_fraction:6.1%}{how}")


if __name__ == "__main__":
    main()
