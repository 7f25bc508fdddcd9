"""Reduce a profiler trace (``*.xplane.pb``) to device times.

Read with nothing but JAX (``jax.profiler.ProfileData``).  A device
plane (``/device:TPU:<n>``) holds the programs the device ran on its
``XLA Modules`` line and their ops on its ``XLA Ops`` line.  An op's
event is named by its HLO text (``%tick_deliver.3 = (...) custom-call(...``);
a control-flow op (``while``, ``conditional``) spans the ops of its
body, so only the innermost ("leaf") ops are summed.  The host planes
hold the harness's own spans (names starting ``bench.``), which label
the device's idle gaps.  All times are nanoseconds on the trace's one
clock.

What comes out, for the chips a cell uses and inside its traced window:
per-op device time, the union of busy intervals, the idle gaps, and the
time of each class of op: the fused tick kernels, the DP kernel, the
collectives, and the rest (XLA's own fusions of the tick loop and the
client block).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

TICK_KERNELS = ("tick_deliver", "bucket_apply", "tick_scatter")
DP_KERNELS = ("cohort_clip_noise", "cohort_clip_noise_prng")
COLLECTIVE_RE = re.compile(r"all-reduce|all_reduce|allreduce|reduce-scatter|"
                           r"all-gather|collective-permute|all-to-all")
SPAN_PREFIX = "bench."
_SUFFIX_RE = re.compile(r"(\.\d+)+$")
_HLO_NAME_RE = re.compile(r"^%?([^\s=]+) = ")

Interval = Tuple[float, float]


def base_name(op: str) -> str:
    """HLO op name without its ``.<n>`` instance suffixes (from the
    event's HLO text, or from a bare name)."""
    m = _HLO_NAME_RE.match(op)
    return _SUFFIX_RE.sub("", m.group(1) if m else op)


def op_class(op: str) -> str:
    name = base_name(op)
    if name in TICK_KERNELS:
        return "tick_kernels"
    if name in DP_KERNELS:
        return "dp_kernel"
    if COLLECTIVE_RE.search(name):
        return "collectives"
    return "xla"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def read(path: str, chips: int):
    """-> (device ops, device programs, host spans).

    device ops / programs: {device index: [(name, start_ns, end_ns)]}
    from the ``XLA Ops`` / ``XLA Modules`` lines of the first ``chips``
    TPU planes; host spans: [(span name, start_ns, end_ns)] of the
    harness's ``bench.`` spans.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[str, float, float]]] = {}
    programs: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            if idx >= chips:
                continue
            for line in plane.lines:
                out = {"XLA Ops": devices,
                       "XLA Modules": programs}.get(line.name)
                if out is not None:
                    out[idx] = [(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return devices, programs, spans


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ops, w0: float, w1: float):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in ops
            if e > w0 and s < w1]


def leaves(ops):
    """The ops that contain no other op (a ``while`` or ``conditional``
    event spans the events of its body)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def reduce(devices, spans, window: Interval, programs=None) -> Dict:
    """Times in seconds, each a mean over the devices used.  Busy is the
    union of the device's programs (``programs``, else of its ops)."""
    w0, w1 = window
    n_dev = max(1, len(devices))
    per_op: Dict[str, float] = {}
    per_class = {"tick_kernels": 0.0, "dp_kernel": 0.0,
                 "collectives": 0.0, "xla": 0.0}
    busy = 0.0
    gaps: List[Interval] = []
    for idx, ops in devices.items():
        ops = clip(ops, w0, w1)
        for name, s, e in leaves(ops):
            dur = (e - s) * 1e-9 / n_dev
            key = base_name(name)
            per_op[key] = per_op.get(key, 0.0) + dur
            per_class[op_class(name)] += dur
        if programs and programs.get(idx):
            ops = clip(programs[idx], w0, w1)
        merged = union([(s, e) for _, s, e in ops])
        busy += sum(e - s for s, e in merged) * 1e-9 / n_dev
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy,
            "per_op_s": per_op, "per_class_s": per_class,
            "idle_gaps": label_gaps(gaps, spans)}


def label_gaps(gaps: Sequence[Interval], spans) -> List[Tuple[str, float]]:
    """Each idle gap with the innermost harness span over its middle
    (``bench.eval`` inside ``bench.job_run``, ...), longest first."""
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(se - ss, name) for name, ss, se in spans
                 if ss <= mid <= se]
        label = min(cover)[1] if cover else "outside spans"
        out.append((label, (e - s) * 1e-9))
    return sorted(out, key=lambda x: -x[1])


def window_of(spans, first: str, last: str) -> Interval:
    """From the start of the first ``first`` span to the end of the
    last ``last`` span."""
    starts = [s for n, s, _ in spans if n == first]
    ends = [e for n, _, e in spans if n == last]
    if not starts or not ends:
        raise ValueError(f"trace holds no {first!r} / {last!r} spans")
    return min(starts), max(ends)
