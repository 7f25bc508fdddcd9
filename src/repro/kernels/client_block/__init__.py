"""Fused local-SGD client block of the paper's logreg.

A Pallas kernel (`kernel.py`) that runs a whole masked block of local
SGD steps for a tile of clients with ``w`` and ``U`` resident in VMEM,
its jitted wrapper with the sample-row gather (`ops.py`), and the
pure-jnp reference the CPU dispatch runs (`ref.py`).
"""
from repro.kernels.client_block.ops import client_block_sgd
from repro.kernels.client_block.ref import client_block_ref

__all__ = ["client_block_sgd", "client_block_ref"]
