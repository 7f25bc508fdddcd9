"""Device time by program scope, and idle gaps by program span.

Extends ``trace.py``, and reads the same ``*.xplane.pb``.  The program
names what it does in two ways (``repro.telemetry``):

    host spans      ``cohort.engine_init``, ``cohort.compile``,
                    ``cohort.dispatch``, ``cohort.sync``, ``cohort.eval``,
                    ``cohort.report``, ... on the host planes, on the
                    device trace's clock
    device scopes   ``cohort.client_block``, ``tick_scatter``, ... in
                    the compiled segment's ``op_name`` metadata; the
                    engine's ``segment_scopes()`` maps each instruction
                    name to its scope path

A device op's event is named by its HLO text, whose instruction name
(``fusion.12``) joins it to that table.  Every time is in seconds, a
mean over the chips read, inside the given window.  Given the program's
spans beside the harness's, ``trace.reduce`` labels each idle gap by the
innermost span of either.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from . import trace

PROGRAM_PREFIX = "cohort."
KERNEL_SCOPES = ("tick_deliver", "bucket_apply", "tick_scatter",
                 "cohort_clip_noise")
CLIENT_BLOCK = "cohort.client_block"


def instruction(op: str) -> str:
    """The HLO instruction name of a device op's event (suffix kept)."""
    m = trace._HLO_NAME_RE.match(op)
    return m.group(1) if m else op


def program_spans(path: str) -> List[Tuple[str, float, float]]:
    """[(span name, start_ns, end_ns)] of the program's ``cohort.``
    spans on the host planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIX)]


def _inside(intervals: List[Tuple[float, float]], t: float) -> bool:
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t <= intervals[i][1]


def scope_times(devices, window, table: Dict[str, str], programs=None,
                module: str = "segment") -> Dict:
    """Leaf-op device time per scope path, per chip and in the mean.

    ``table``: {instruction name: scope path}.  With ``programs`` (the
    trace's ``XLA Modules`` events), only ops that ran inside a program
    whose name holds ``module`` count as the segment's; the rest (the
    evals, the uploads) sum to ``other_programs_s``.  A segment op the
    table lacks is unscoped (``unscoped_s``, and by base name in
    ``unscoped_ops_s``).  ``kernel_layout_s``: ops under a
    kernel-wrapper scope that are not the kernel itself (pads, slices,
    copies).  ``client_block_s``: ops under ``cohort.client_block``.
    ``collective_s``: collective ops of the segment."""
    w0, w1 = window
    n_dev = max(1, len(devices))
    per_scope: Dict[str, float] = {}
    per_chip: Dict[int, Dict[str, float]] = {}
    unscoped_ops: Dict[str, float] = {}
    out = {"segment_s": 0.0, "other_programs_s": 0.0, "unscoped_s": 0.0,
           "kernel_layout_s": 0.0, "client_block_s": 0.0,
           "collective_s": 0.0}
    for idx, ops in devices.items():
        chip = per_chip.setdefault(idx, {})
        seg = (sorted((s, e) for n, s, e in programs.get(idx, ())
                      if module in n) if programs else None)
        for name, s, e in trace.leaves(trace.clip(ops, w0, w1)):
            sec = (e - s) * 1e-9 / n_dev
            if seg is not None and not _inside(seg, 0.5 * (s + e)):
                out["other_programs_s"] += sec
                continue
            out["segment_s"] += sec
            path = table.get(instruction(name), "")
            chip[path] = chip.get(path, 0.0) + sec * n_dev
            per_scope[path] = per_scope.get(path, 0.0) + sec
            cls = trace.op_class(name)
            if cls == "collectives":
                out["collective_s"] += sec
            if not path:
                out["unscoped_s"] += sec
                key = trace.base_name(name)
                unscoped_ops[key] = unscoped_ops.get(key, 0.0) + sec
                continue
            parts = path.split("/")
            if CLIENT_BLOCK in parts:
                out["client_block_s"] += sec
            if (cls not in ("tick_kernels", "dp_kernel", "collectives")
                    and any(k in parts for k in KERNEL_SCOPES)):
                out["kernel_layout_s"] += sec
    out.update(per_scope_s=per_scope, per_chip_s=per_chip,
               unscoped_ops_s=unscoped_ops)
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k or "(unscoped)", v]
            for k, v in sorted(d.items(), key=lambda x: -x[1])[:n]]
