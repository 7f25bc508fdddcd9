"""step_mfu: the whole round's share of the chips' peak: the larger of
(necessary FLOPs / peak FLOP/s) and (necessary bytes / peak HBM B/s)
over the traced window's time, in %.  The logreg round is bound by
bytes, so the bytes term is the one that counts there."""
from yardstick.counts import round_work


def read(ctx):
    t = ctx["trace"]
    if t is None or t["busy_s"] <= 0 or ctx["peaks"] is None:
        return None
    work = round_work(ctx["census"], ctx["D"], ctx["dp"], ctx["work"],
                      ctx["evals"])
    pk, n = ctx["peaks"], ctx["chips"]
    least = max(work["flops"] / (pk["flops_per_s"] * n),
                work["bytes"] / (pk["hbm_bytes_per_s"] * n))
    return 100.0 * least / t["window_s"]
