"""hybrid_step_mfu: the necessary FLOPs of the traced window over its
time times the chips' peak, in %: the census's ``client_tokens`` at the
model's FLOPs a training token, plus each eval's forward
(``bench/models/<model>.py`` ``work``; no recomputation counts).  The
whole step's share of the peak, idle time included."""


def flops(ctx):
    """Necessary FLOPs of the window's jobs, or None where the program
    counts no ``client_tokens``."""
    tokens = ctx["census"]["ops"].get("client_tokens")
    work = ctx["work"]
    if tokens is None or "token_flops" not in work:
        return None
    return tokens * work["token_flops"] + ctx["evals"] * work["eval_flops"]


def read(ctx):
    t, f = ctx["trace"], flops(ctx)
    if t is None or f is None or t["window_s"] <= 0 or ctx["peaks"] is None:
        return None
    peak = ctx["peaks"]["flops_per_s"] * ctx["chips"]
    return 100.0 * f / (t["window_s"] * peak)
