"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel package ships <name>.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (the public wrapper: blocks planned from the shapes and the
chip, padding) and ref.py (pure-jnp oracle used by the allclose test
sweeps).
"""
