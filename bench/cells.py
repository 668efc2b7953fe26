"""Find a cell's parts by name: its configuration, its traffic mix and its
per-layer metric readers.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, named after it:

* ``configs/<config>.json`` — the design: its source, the builder call,
  the sizes assumed and the keys reduced;
* ``traffic/<traffic>.json`` — the parameters the row generator
  (``rows.py``) and the client (``client.py``) read;
* ``metrics/<metric>.py`` — a reader with ``read(ctx) -> float | None``.

A cell added to ``BENCHMARK.json`` with new files of these kinds needs no
edit of the harness.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Dict, List, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # metric entries this cell reports
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT, bench_dir: str = HERE) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json`` with its
    configuration and traffic read from ``bench_dir``."""
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    with open(os.path.join(bench_dir, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader(name: str, bench_dir: str = HERE):
    """``read`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_design(config: dict):
    """Zero-argument builder of a fresh copy of the configuration's design
    (``config["design"]``: module, call, args, kwargs; ``attr_kwargs``
    name attributes of the module passed by keyword; ``then`` names an
    attribute of the call's result that is the builder)."""
    d = config["design"]
    mod = importlib.import_module(d["module"])
    kwargs = dict(d.get("kwargs", {}))
    for k, attr in d.get("attr_kwargs", {}).items():
        kwargs[k] = getattr(mod, attr)
    made = getattr(mod, d["call"])
    if "then" in d:
        return getattr(made(*d.get("args", []), **kwargs), d["then"])
    return lambda: made(*d.get("args", []), **kwargs)


def peaks(device_kind: str, bench_dir: str = HERE) -> Dict[str, float]:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown kind is
    an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
