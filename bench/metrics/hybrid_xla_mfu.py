"""hybrid_xla_mfu: the same necessary FLOPs as ``hybrid_step_mfu`` over
the device time of the segment's XLA ops (``per_class_s["xla"]``: the
ops that are neither Pallas kernels nor collectives) times the chips'
peak, in %: how near the compiled block runs to the peak while it runs.
Beside ``hybrid_step_mfu`` it tells idle time from slow code."""
from yardstick.spec import load_module


def read(ctx):
    t = ctx["trace"]
    f = load_module("metrics", "hybrid_step_mfu").flops(ctx)
    if t is None or f is None or ctx["peaks"] is None:
        return None
    secs = t["per_class_s"]["xla"]
    if secs <= 0:
        return None
    return 100.0 * f / (secs * ctx["peaks"]["flops_per_s"] * ctx["chips"])
