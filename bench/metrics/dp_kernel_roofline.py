"""dp_kernel_roofline: the DP clip+noise kernel's necessary bytes (read
U and the noise, write the noised row, for each message) over its
device time times the chip's HBM bandwidth, in %."""
from yardstick.counts import dp_kernel_bytes


def read(ctx):
    t = ctx["trace"]
    secs = t["per_class_s"]["dp_kernel"] if t else 0.0
    if secs <= 0 or not ctx["dp"]:
        return None
    per_chip = dp_kernel_bytes(ctx["census"], ctx["D"]) / ctx["chips"]
    return 100.0 * per_chip / (secs * ctx["peaks"]["hbm_bytes_per_s"])
