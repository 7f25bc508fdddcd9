"""The system under test, driven through its public entry.

A job is ``make_simulator(FLConfig(engine="device", **traffic["fl"]),
ctask, ..., **traffic["simulator"]).run(max_rounds=R, eval_every=...)``:
``DeviceCohortEngine.run``.  The task and cohort task come from the
configuration's model (``bench/models/<model>.py``).  All jobs of a run
share one cohort task, on which the engine caches its compiled segment,
so only the first job of a run compiles.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict

from .spec import ROOT, model_of
from .traffic import job_plan

SRC = os.path.join(ROOT, "src")


def import_program():
    """Put the checkout's ``src`` first on the path and import it."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro  # noqa: F401 — ImportError when the checkout lacks it


class Program:
    """One cell's fleet over one data set: the task, the cohort task and
    the job factory."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 inputs, seed: int):
        import_program()
        from repro.configs.base import FLConfig

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.C = int(config["clients"])
        self.task, self.ctask = model_of(config).build(config, inputs,
                                                       self.seed)
        self.fl = FLConfig(engine="device", **traffic["fl"])
        self.simulator_kw = dict(traffic.get("simulator") or {})

    def job(self, j: int, rounds: int = None):
        """-> (simulator, plan) of job j (``rounds``: stop it early)."""
        from repro.cohort import make_simulator
        plan = job_plan(self.traffic, j, self.C, rounds)
        sim = make_simulator(
            self.fl, self.ctask, n_clients=self.C,
            sizes_per_client=plan["sizes"], round_stepsizes=plan["etas"],
            d=int(self.fl.d), seed=self.seed, **self.simulator_kw)
        return sim, plan

    def release(self):
        """Drop the compiled segments and the engine's device state."""
        self.ctask._segment_fns = {}
        self.ctask._block_fns = {}
