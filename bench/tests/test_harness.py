"""The harness finds a cell's parts by name, refuses a machine without
the chip before its window, and fails without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import cells

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _tiny_tree(tmp_path):
    """A repository holding one cell whose configuration, traffic and
    metric are files added beside the harness's own."""
    root = tmp_path / "repo"
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "tiny_pipe.json").write_text(json.dumps({
        "name": "tiny_pipe", "check_rows": 8,
        "design": {"module": "repro.designs.typea", "call": "skynet_like",
                   "kwargs": {"items": 16, "depth": 3}}}))
    (bench / "traffic" / "tiny_mix.json").write_text(json.dumps(
        {"service": {"backend": "jax", "block": 8},
         "streams": [{"name": "bulk", "loop": "closed", "priority": "bulk",
                      "fallback": False, "outstanding": 2,
                      "request_rows": 16, "max_rate": 10,
                      "rows": {"draw": "random_search", "lo": 1,
                               "hi": 4}}]}))
    (bench / "metrics" / "tiny_count.py").write_text(
        "def read(ctx):\n    return float(len(ctx['obs']['rows']))\n")
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.bulk", "config": "tiny_pipe",
                       "traffic": "tiny_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "other_only", "unit": "s",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "tiny_count", "unit": "rows",
                       "workloads": ["tiny.bulk"]}]}))
    return str(root), str(bench)


def test_new_files_are_found_by_name(tmp_path):
    root, bench = _tiny_tree(tmp_path)
    cell = cells.find_cell("tiny.bulk", root=root, bench_dir=bench)
    assert cell.config["name"] == "tiny_pipe"
    assert cell.traffic["service"]["block"] == 8
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["tiny_count"]
    read = cells.metric_reader("tiny_count", bench_dir=bench)
    assert read({"obs": {"rows": [1, 2, 3]}}) == 3.0
    prog = cells.build_design(cell.config)()
    assert len(prog.fifos) == 4
    with pytest.raises(KeyError):
        cells.find_cell("nope", root=root, bench_dir=bench)


def test_every_cell_of_the_benchmark_resolves():
    spec = cells.load_benchmark()
    for w in spec["workloads"]:
        cell = cells.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "multicore.bulk",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_exits_nonzero_before_the_window():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr and "set-up" not in p.stderr


def test_a_tree_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_chip_checks_refuse_cpu_and_unknown_kinds(monkeypatch):
    import jax

    import run

    with pytest.raises(run.NoChip, match="needs a TPU"):
        run.require_chip(1)

    class Fake:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(run.NoChip, match="peaks.json"):
        run.require_chip(1)
    Fake.device_kind = "TPU v5 lite"
    with pytest.raises(run.NoChip, match="4 chips"):
        run.require_chip(4)
    assert run.require_chip(1)[0].device_kind == "TPU v5 lite"

