"""Compile-only checks of the sparse max-plus fixpoint for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so the
served device lane's kernel is compiled here at the widths the repo's
designs produce, for one chip of a described ``v5e:2x2`` topology.  A
kernel over the chip's VMEM limit, or a tiling the compiler refuses,
fails here instead of on the chip.  Nothing runs, so nothing about
results or times is checked.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _chain_flat(result):
    from repro.core.dse import _batch_arrays, _sparse_arrays
    from repro.core.incremental import compile_graph

    g = compile_graph(result.graph)
    return _sparse_arrays(g, _batch_arrays(g)), len(result.depths)


@pytest.fixture(scope="module")
def skynet():
    from repro.core import simulate
    from repro.designs.typea import skynet_like
    return _chain_flat(simulate(skynet_like(), trace="auto"))


@pytest.fixture(scope="module")
def corpus1000():
    """The first live 1000-module BENCH_SPEC corpus design."""
    from repro.core import simulate
    from repro.corpus import BENCH_SPEC, generate
    for seed in range(8):
        run = simulate(generate(seed, scale=1000, spec=BENCH_SPEC).builder(),
                       trace="auto")
        if not run.deadlock:
            return _chain_flat(run)
    raise AssertionError("no live 1000-module corpus seed")


@pytest.fixture(scope="module")
def optical_flow():
    """Rosetta's optical flow at 32 x 1,024 frames, four times the
    benchmark cell's height: the largest graph the device lane is given."""
    from repro.core import simulate
    from repro.designs.rosetta import optical_flow
    return _chain_flat(simulate(optical_flow(height=32, width=1024),
                                trace="auto"))


def _doubled(arr):
    """skynet's graph twice side by side: twice its padded node axis."""
    return arr._replace(
        n=arr.npad + arr.n, npad=2 * arr.npad, cw=np.tile(arr.cw, 2),
        c_seed=np.tile(arr.c_seed, 2),
        seg_start=np.concatenate([arr.seg_start,
                                  arr.seg_start + arr.npad]))


def _compile(arr, n_fifos, K, sharding):
    import jax

    from repro.kernels.maxplus import sparse as sp

    args, static = sp._fixpoint_args(arr, np.zeros((K, n_fifos), np.int64))
    shapes = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                   sharding=sharding) for a in args]
    return sp._fixpoint.lower(*shapes, **static, interpret=False).compile()


@pytest.mark.parametrize("design,K", [("skynet", 128), ("skynet", 1024),
                                      ("corpus1000", 1024),
                                      ("skynet_x2", 1024),
                                      ("optical_flow", 128)])
def test_fixpoint_compiles_for_v5e(design, K, one_chip, request):
    """skynet_like (n=102,452, chains of 4,098 nodes) at the service's
    default block and at K=1024, the 1000-module corpus design, twice
    skynet's width for headroom, and optical flow at 32 frame rows
    (n=917,522, 449 node tiles, a 15-step WAR lane) at the cell's block: the
    kernel compiles within the chip's VMEM limit, stays a Mosaic kernel,
    and the whole program fits the chip's 16 GB of HBM."""
    arr, n_fifos = request.getfixturevalue(
        "skynet" if design == "skynet_x2" else design)
    if design == "skynet_x2":
        arr = _doubled(arr)
    compiled = _compile(arr, n_fifos, K, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < 16e9, used
