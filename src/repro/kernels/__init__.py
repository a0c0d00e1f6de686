"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three files:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (layout prep, padding, dispatch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

The training path's kernels (``segment_mm``, ``embedding_bag``) take
``interpret=None``, which asks the backend (``segment_mm.default_interpret``):
they compile on TPU and run in the Pallas interpreter only on CPU, where the
tests validate them against the oracles. ``flash_attention`` is not on that
path and still defaults to the interpreter.
"""
