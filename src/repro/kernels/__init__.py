# Pallas kernels.  ``maxplus/sparse.py`` holds the segmented-cummax
# fixpoint of the ``backend="jax"`` DSE lane (``repro.core.dse``).
