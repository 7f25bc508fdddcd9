"""eval_ms_per_round: the engine's own ``eval`` phase
(``DeviceCohortEngine.run``'s PhaseTimer: the sync and the task's
metrics after each segment), per round, in ms."""


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * sum(j["eval_s"] for j in ctx["jobs"]) / ctx["rounds"]
