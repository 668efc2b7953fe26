"""``chip_smoke.py`` and the platform rules it stands on, off the chip.

The smoke's phases run here at a tiny size on the CPU (Pallas interpret
mode, derived from the platform), and its entry point must refuse to run
anywhere but a TPU.  The rules it relies on are checked directly: the
platform decides interpret mode, a jax lane refuses worker processes, and
the compile cache is placed from outside.
"""
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.device import configure_compile_cache, pallas_interpret  # noqa: E402
from repro.sweep import BlockScheduler, SweepService  # noqa: E402

TINY = chip_smoke.Sizes(skynet_items=32, skynet_depth=3, corpus_scale=20,
                        watchdog_items=16, bulk_rows=48, shrunk_rows=16,
                        block=16)


def test_smoke_phases_at_tiny_size_on_cpu(capsys):
    """Every phase, every check: served rows equal the numpy lane, all
    four verdicts occur, the fallback ran, sampled rows equal a
    from-scratch simulate."""
    assert jax.default_backend() == "cpu"
    chip_smoke.run(TINY)
    out = capsys.readouterr().out
    assert out.count("all rows equal the numpy lane") == 4
    assert "Mosaic kernel in the compiled HLO: False" in out   # interpreted


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_exits_nonzero_off_the_chip(where, tmp_path):
    """On the CPU, and in a directory holding nothing of the repo but the
    script, it fails and never prints the contract line."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize("platform,requested,want", [
    ("cpu", None, True), ("tpu", None, False),
    ("cpu", True, True), ("tpu", False, False),
    ("tpu", True, ValueError), ("cpu", False, ValueError),
    ("gpu", None, RuntimeError), ("gpu", False, RuntimeError),
])
def test_interpret_mode_follows_the_platform(platform, requested, want,
                                             monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if isinstance(want, bool):
        assert pallas_interpret(requested) is want
    else:
        with pytest.raises(want):
            pallas_interpret(requested)


@pytest.mark.parametrize("backend", ["jax", "jax_dense"])
def test_process_shards_refused_for_jax_lanes(backend):
    with pytest.raises(ValueError, match="one process"):
        BlockScheduler(mode="process", shards=2, backend=backend)
    with pytest.raises(ValueError, match="one process"):
        SweepService(mode="process", shards=2, backend=backend)
    sched = BlockScheduler(mode="thread", shards=2, backend=backend)
    try:
        assert sched.mode == "thread" and sched.jax_interpret is True
    finally:
        sched.close()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placed_from_outside(env_dir, tmp_path, monkeypatch):
    default = str(tmp_path / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = configure_compile_cache(default)
        if env_dir is None:
            assert got == default
            assert jax.config.jax_compilation_cache_dir == default
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
