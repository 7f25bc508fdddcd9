"""xla_ms_per_round: device time of the segment's ops that are neither
Pallas kernels nor collectives (the compiled tick loop and the client
block), per round, mean over the chips, in ms, from the profiler
trace."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["rounds"] or t["per_class_s"]["xla"] <= 0:
        return None
    return 1e3 * t["per_class_s"]["xla"] / ctx["rounds"]
