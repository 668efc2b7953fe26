"""Where the accelerator lane runs: the one owner of platform decisions.

* :func:`pallas_interpret` — whether Pallas kernels run compiled or in
  interpret mode, derived from ``jax.default_backend()``: a TPU runs them
  compiled, the CPU interprets them, and any other platform has no lane.
* :func:`configure_compile_cache` — where JAX keeps its persistent
  compilation cache.  Entry points (``chip_smoke.py``,
  ``benchmarks/run.py``) call it; importing the library never does.
* :func:`span` — a named host span in the JAX profiler's trace, the one
  tracing hook of the sweep service and the solve phase.
"""
from __future__ import annotations

import os
from typing import Optional

INTERPRET_BY_PLATFORM = {"cpu": True, "tpu": False}


def pallas_interpret(requested: Optional[bool] = None) -> bool:
    """Interpret mode for this process's JAX platform.

    ``requested=None`` derives it; an explicit value must agree with the
    platform (interpreting on a TPU would silently run the kernel off the
    chip, and compiled Pallas TPU kernels cannot run on the CPU).
    """
    import jax

    platform = jax.default_backend()
    if platform not in INTERPRET_BY_PLATFORM:
        raise RuntimeError(
            f"the Pallas max-plus lane runs on a TPU (compiled) or the CPU "
            f"(interpreted); JAX's default backend is {platform!r}")
    derived = INTERPRET_BY_PLATFORM[platform]
    if requested is not None and bool(requested) != derived:
        raise ValueError(
            f"jax_interpret={requested!r} on platform {platform!r}: Pallas "
            f"kernels run {'interpreted' if derived else 'compiled'} here; "
            f"leave jax_interpret unset (None) to derive it")
    return derived


def configure_compile_cache(default_dir: str) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set here; otherwise the cache goes to ``default_dir``, a fixed path
    (the path is part of the cache key, so a moving directory never hits).
    Call once, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)


def span(name: str, **attrs):
    """Context manager timing ``name`` as a host span of the JAX profiler's
    trace: ``jax.profiler.TraceAnnotation(name, **attrs)``.  The keyword
    attributes come back as the event's stats; ``set_metadata(**attrs)``
    on the entered span adds those known only at its end.

    Spans are always on: with no profiler session one costs about a
    microsecond, so they are opened per block, phase and request, never
    per row.  jax is imported on first use, so a module that opens spans
    stays importable without it.
    """
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **attrs)
