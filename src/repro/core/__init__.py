"""OmniSim core: coupled functionality + performance simulation of dataflow
hardware designs (Sarkar & Hao, MICRO'25), adapted to a JAX/TPU stack.

Public API:

    from repro.core import (Program, Read, Write, ReadNB, WriteNB, Empty,
                            Full, Delay, Emit, simulate, simulate_rtl,
                            simulate_traced, LightningSim, csim, resimulate,
                            resimulate_batch, classify)

See docs/architecture.md for the module map (which paper section each file
implements) and docs/api.md for the full public-API reference.
"""
from .engine import OmniSim, simulate
from .events import (Constraint, DeadlockError, NodeKind, Query, RequestType,
                     SimStats, UnsupportedDesignError)
from .graph import (SimGraph, level_schedule, longest_path_numpy,
                    longest_path_python)
from .dse import BatchOutcome, resimulate_batch
from .incremental import (CompiledGraph, IncrementalOutcome, compile_graph,
                          resimulate)
from .lightningsim import CSimCrash, LightningSim, csim
from .program import (Delay, Emit, Empty, Fifo, Full, Module, Op, Program,
                      Read, ReadNB, SimResult, Write, WriteNB)
from .rtlsim import simulate_rtl
from .taxonomy import Classification, classify, classify_dynamic
from .trace import (CompiledTrace, HybridCache, HybridSim, ModuleTrace,
                    RecordedTrace, TraceSimGraph, TraceUnsupported,
                    compile_trace, program_fingerprint, record_trace,
                    simulate_hybrid, simulate_traced)

__all__ = [
    "OmniSim", "simulate", "simulate_rtl", "LightningSim", "csim",
    "resimulate", "resimulate_batch", "BatchOutcome", "CompiledGraph",
    "compile_graph", "classify", "Classification", "IncrementalOutcome",
    "Program", "Fifo", "Module", "Op", "Read", "Write", "ReadNB", "WriteNB",
    "Empty", "Full", "Delay", "Emit", "SimResult", "SimGraph",
    "longest_path_numpy", "longest_path_python", "level_schedule",
    "Constraint", "DeadlockError", "Query", "RequestType",
    "NodeKind", "SimStats", "UnsupportedDesignError", "CSimCrash",
    "classify_dynamic",
    "TraceUnsupported", "RecordedTrace", "ModuleTrace", "CompiledTrace",
    "TraceSimGraph", "record_trace", "compile_trace", "simulate_traced",
    "HybridCache", "HybridSim", "simulate_hybrid", "program_fingerprint",
]
