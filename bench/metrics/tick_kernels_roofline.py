"""tick_kernels_roofline: the three fused tick kernels' necessary bytes
(from the census, ``yardstick/counts.py``) over their device time times
the chip's HBM bandwidth, in %.  Each chip moves its share of the bytes."""
from yardstick.counts import tick_kernel_bytes


def read(ctx):
    t = ctx["trace"]
    secs = t["per_class_s"]["tick_kernels"] if t else 0.0
    if secs <= 0:
        return None
    need = tick_kernel_bytes(ctx["census"], ctx["D"], ctx["dp"])
    per_chip = need / ctx["chips"]
    return 100.0 * per_chip / (secs * ctx["peaks"]["hbm_bytes_per_s"])
