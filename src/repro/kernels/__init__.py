"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), with its jit wrapper in
ops.py and its pure-jnp oracle in ref.py. Validated in interpret mode on CPU;
TPU (v5e) is the compilation target and the default (``interpret=False``).
"""
from . import ops, ref

__all__ = ["ops", "ref"]
