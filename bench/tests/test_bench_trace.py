"""Trace reduction: hand-computed intervals, and two small traces
recorded on a TPU v5e, read with nothing but JAX.

The fixtures are one harness job of each mix at C = 4096 (FedSGD, 3
rounds; DP, 2 rounds), traced as a ``--trace 1`` run traces, with the
host threads' events other than the harness's ``bench.`` spans dropped
to keep them small; the device planes are as recorded."""
import glob
import os

import pytest

from yardstick import trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_union_and_classes_by_hand():
    assert trace.union([(5, 20), (0, 10), (30, 40), (40, 45)]) == [
        (0, 20), (30, 45)]
    assert trace.op_class("tick_deliver.3") == "tick_kernels"
    assert trace.op_class("cohort_clip_noise") == "dp_kernel"
    assert trace.op_class("all-reduce.12") == "collectives"
    assert trace.op_class("all-reduce-start.1") == "collectives"
    assert trace.op_class("fusion.1234") == "xla"
    hlo = ("%tick_scatter.4 = (f32[16384,896]{1,0:T(8,128)}) "
           "custom-call(f32[16384,896]{1,0:T(8,128)} %broadcast.1)")
    assert trace.base_name(hlo) == "tick_scatter"
    assert trace.op_class(hlo) == "tick_kernels"


def test_leaves_skip_control_flow_parents():
    ops = [("%while.1 = (f32[4]) while(...)", 0, 100),
           ("%conditional.2 = (f32[4]) conditional(...)", 10, 60),
           ("%fusion.3 = f32[4] fusion(...)", 10, 30),
           ("%tick_deliver.4 = f32[4] custom-call(...)", 30, 60),
           ("%fusion.5 = f32[4] fusion(...)", 70, 100),
           ("%copy.6 = f32[4] copy(...)", 120, 130)]
    assert [trace.base_name(n) for n, _, _ in trace.leaves(ops)] == [
        "fusion", "tick_deliver", "fusion", "copy"]
    r = trace.reduce({0: ops}, [], (0, 200),
                     programs={0: [("jit_segment", 0, 100),
                                   ("jit_add", 120, 130)]})
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["per_class_s"]["xla"] == pytest.approx(60e-9)
    assert r["per_class_s"]["tick_kernels"] == pytest.approx(30e-9)


def test_reduce_by_hand():
    devices = {0: [("tick_deliver.3", 0, 10), ("fusion.1", 5, 20),
                   ("all-reduce.2", 30, 40), ("fusion.9", 150, 160)],
               1: [("tick_deliver.4", 0, 30)]}
    spans = [("bench.job_run", 0, 100), ("bench.eval", 20, 30),
             ("bench.job_setup", -10, 0)]
    r = trace.reduce(devices, spans, (0, 100))
    ns = 1e-9 / 2                       # a mean over two devices
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0 busy [0, 20] + [30, 40]; device 1 busy [0, 30]
    assert r["busy_s"] == pytest.approx((30 + 30) * ns)
    assert r["per_class_s"]["tick_kernels"] == pytest.approx((10 + 30) * ns)
    assert r["per_class_s"]["xla"] == pytest.approx(15 * ns)
    assert r["per_class_s"]["collectives"] == pytest.approx(10 * ns)
    assert r["per_op_s"]["tick_deliver"] == pytest.approx(40 * ns)
    # idle: device 0 [20, 30] in the eval, [40, 100] in the job's run;
    # device 1 [30, 100]
    assert r["idle_gaps"] == [("bench.job_run", pytest.approx(70e-9)),
                              ("bench.job_run", pytest.approx(60e-9)),
                              ("bench.eval", pytest.approx(10e-9))]
    assert trace.window_of(spans, "bench.job_setup", "bench.job_run") == (
        -10, 100)


def _fixture(kind):
    found = glob.glob(os.path.join(FIXTURES, f"*{kind}*.xplane.pb"))
    assert found, f"no {kind} trace fixture under {FIXTURES}"
    return found[0]


@pytest.mark.parametrize("kind,kernels", [
    ("fedsgd", ("tick_deliver", "bucket_apply", "tick_scatter")),
    ("dp", ("tick_deliver", "bucket_apply", "tick_scatter",
            "cohort_clip_noise"))])
def test_recorded_tpu_trace(kind, kernels):
    path = _fixture(kind)
    assert os.path.getsize(path) < 1 << 20
    devices, programs, spans = trace.read(path, chips=1)
    assert list(devices) == [0] and devices[0] and programs[0]
    names = {trace.base_name(n) for n, _, _ in devices[0]}
    assert set(kernels) <= names
    window = trace.window_of(spans, "bench.job_setup", "bench.job_run")
    r = trace.reduce(devices, spans, window, programs)
    # the device's ops lie inside the harness's spans on one clock
    assert 0 < r["busy_s"] < r["window_s"]
    total = sum(r["per_class_s"].values())
    assert total == pytest.approx(sum(r["per_op_s"].values()))
    # leaf ops run inside the programs, one at a time
    assert total <= r["busy_s"] * (1 + 1e-9)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    assert {label for label, _ in r["idle_gaps"]} <= {
        "bench.job_setup", "bench.job_run", "bench.eval", "outside spans"}
