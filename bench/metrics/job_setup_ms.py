"""job_setup_ms: host time of the harness's span around each job's
``make_simulator`` (the engine's constructor: sizes, scenario plan,
initial state), mean per job, in ms."""


def read(ctx):
    jobs = ctx["jobs"]
    if not jobs:
        return None
    return 1e3 * sum(j["setup_s"] for j in jobs) / len(jobs)
