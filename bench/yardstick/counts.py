"""Necessary bytes and FLOPs of a run, from the protocol's own census.

Bytes are counted from the work the protocol requires, not from the
operands a kernel is handed: a kernel that skips idle rows must still
read below 100% of its roofline.  D is the flat model width; a row is
D f32 values.

Fused tick kernels (``kernels/tick_fused``), per census count:
    deliver_rows    tick_deliver: read the broadcast row and U, write w  3
    bucket_applies  bucket_apply: read v and the bucket row, write v     3
    messages        tick_scatter: read the sent row, write U = 0         2
                    (with DP also read U, read and write w: 5)
    ring_scatters   tick_scatter: read and write one ring row            2
DP clip+noise (``kernels/cohort_dp``), per message: read U, read the
noise row, write the noised row                                          3

A whole round also needs the model's own work (its ``work(config)``,
``bench/models/<model>.py``): per client step (Sum of s_i over the
rounds completed) ``step_flops`` and ``step_rows`` rows read, per eval
``eval_flops`` and ``eval_rows``; and per message the client block reads
and writes w and U once (4 rows), per broadcast the server writes one
ring row.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

F32 = 4


def client_steps(participation: Sequence[int], sizes: Sequence[int]) -> int:
    """Sum of s_i over every round each client completed."""
    cum = np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])
    part = np.minimum(np.asarray(participation, np.int64), len(sizes))
    return int(cum[part].sum())


def tick_kernel_bytes(census: Dict, D: int, dp: bool) -> float:
    ops = census["ops"]
    rows = (3 * ops["deliver_rows"] + 3 * ops["bucket_applies"]
            + (5 if dp else 2) * census["messages"]
            + 2 * ops["ring_scatters"])
    return float(rows) * D * F32


def dp_kernel_bytes(census: Dict, D: int) -> float:
    return 3.0 * census["messages"] * D * F32


def round_work(census: Dict, D: int, dp: bool, work: Dict[str, float],
               evals: int) -> Dict[str, float]:
    """Necessary FLOPs and bytes of everything the rounds did; ``work``
    is the model's per-step and per-eval work."""
    steps = census["client_steps"]
    flops = work["step_flops"] * steps + evals * work["eval_flops"]
    rows = (work["step_rows"] * steps + 4 * census["messages"]
            + census["broadcasts"] + evals * work["eval_rows"])
    bytes_ = rows * D * F32 + tick_kernel_bytes(census, D, dp)
    if dp:
        bytes_ += dp_kernel_bytes(census, D)
    return {"flops": float(flops), "bytes": float(bytes_)}
