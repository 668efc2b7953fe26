"""Benchmark design registry: the paper's Type B/C designs, the Type A
suite, dynamic (query-sparse Type B/C) designs beyond the paper, and the
Rosetta suite's dataflow designs."""
from .dynamic import DYNAMIC_DESIGNS
from .paper import PAPER_DESIGNS
from .rosetta import ROSETTA_DESIGNS
from .typea import TYPEA_DESIGNS

ALL_DESIGNS = {**PAPER_DESIGNS, **TYPEA_DESIGNS, **DYNAMIC_DESIGNS,
               **ROSETTA_DESIGNS}

__all__ = ["PAPER_DESIGNS", "TYPEA_DESIGNS", "DYNAMIC_DESIGNS",
           "ROSETTA_DESIGNS", "ALL_DESIGNS"]
