"""Jitted wrapper: fixpoint longest path over dense max-plus tiles.

``longest_path(A, base)`` iterates blocked relaxation sweeps until the time
vector stops changing (bounded by the graph diameter, itself <= N).  Used by
the OmniSim engine for device-resident incremental re-finalization of
simulation graphs that fit the dense representation (graph.to_dense_blocks
pads to the 128 tile size).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import BLK, NEG, maxplus_sweep
from .ref import maxplus_sweep_ref


@functools.partial(jax.jit, static_argnames=("max_iters", "use_pallas",
                                             "interpret"))
def longest_path(a: jnp.ndarray, base: jnp.ndarray, *, max_iters: int = 0,
                 use_pallas: bool = True, interpret: bool):
    """Fixpoint t = max(base, A (+) t).  a: [N, N] int32; base: [N] int32.

    ``interpret`` is the platform's Pallas mode
    (:func:`repro.device.pallas_interpret`).
    """
    n = a.shape[0]
    assert n % BLK == 0
    iters = max_iters or n

    def sweep(t):
        if use_pallas:
            return maxplus_sweep(a, t, base, interpret=interpret)
        return maxplus_sweep_ref(a, t, base)

    def cond(state):
        t, prev, k = state
        return jnp.logical_and(k < iters, jnp.any(t != prev))

    def body(state):
        t, _, k = state
        return sweep(t), t, k + 1

    t0 = base
    t1 = sweep(t0)
    t, _, _ = jax.lax.while_loop(cond, body, (t1, t0, jnp.int32(1)))
    return t


def finalize_times(graph, *, use_pallas: bool = True,
                   interpret: Optional[bool] = None):
    """Longest-path node times for a SimGraph via the dense-blocked kernel
    (``interpret=None`` derives the Pallas mode from the platform)."""
    import numpy as np

    from ...core.graph import to_dense_blocks
    from ...device import pallas_interpret

    interpret = pallas_interpret(interpret)
    indptr, src, wgt, base = graph.to_csr()
    a, b = to_dense_blocks(indptr, src, wgt, base, pad_to=BLK)
    # clip the int64 -INF sentinel in numpy BEFORE the int32 transfer —
    # casting -(1<<40) through int32 would wrap to 0 (a phantom edge).
    a32 = jnp.asarray(np.maximum(a, int(NEG)).astype(np.int32))
    b32 = jnp.asarray(np.maximum(b, int(NEG)).astype(np.int32))
    t = longest_path(a32, b32, use_pallas=use_pallas, interpret=interpret)
    return t[:graph.n_nodes]
