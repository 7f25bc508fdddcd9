"""The paper's logistic regression (``configs/paper_logreg.py``; paper
§4 / Supp. E), the configurations' ``"model": "logreg"``.

    inputs(seed, config)    the data set and the initial model, made on
                            the device from the seed (``yardstick/data``)
    width(config)           D, the flat model's width: features + bias
    initial_vector(inputs)  the flat initial model [w0, b = 0]
    work(config)            necessary FLOPs and f32 rows of one client
                            step and of one eval (``yardstick/counts``)
    build(config, inputs, seed)
                            the program's task and cohort task over the
                            inputs: the system under test
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from yardstick.data import make_inputs


def inputs(seed: int, config: Dict[str, Any]):
    """-> (X [N, d], y [N], w0 [d]) on the default device."""
    return make_inputs(seed, config["n_examples"], config["n_features"])


def width(config: Dict[str, Any]) -> int:
    return int(config["n_features"]) + 1


def initial_vector(inputs_) -> np.ndarray:
    w0 = np.asarray(inputs_[2])
    return np.concatenate([w0, [0.0]]).astype(np.float32)


def work(config: Dict[str, Any]) -> Dict[str, float]:
    """A client step reads one sample row and takes about 6 D FLOPs (the
    logit's dot, the gradient's scale-and-add, the two updates); an eval
    makes two passes over the data set (loss and accuracy), each reading
    N rows at 2 N D FLOPs."""
    D, N = width(config), int(config["n_examples"])
    return {"step_flops": 6.0 * D, "step_rows": 1,
            "eval_flops": 2 * 2.0 * N * D, "eval_rows": 2 * N}


def build(config: Dict[str, Any], inputs_, seed: int):
    """-> (task, cohort task): the program's logreg task over the inputs,
    its initial model the one drawn from the seed."""
    import jax.numpy as jnp
    from repro.cohort.tasks import CohortLogRegTask
    from repro.core import LogRegTask

    X, y, w0 = inputs_
    init = {"w": w0, "b": jnp.zeros((), jnp.float32)}

    class SeededLogRegTask(LogRegTask):
        """The paper's logreg task, its initial model drawn from the
        run's seed."""

        def init_model(self, key=None):
            return init

    dp = config.get("dp") or {}
    task = SeededLogRegTask(
        X, y, l2=float(config["l2"]), dp_clip=float(dp.get("clip", 0.0)),
        dp_sigma=float(dp.get("sigma", 0.0)), sample_seed=seed)
    return task, CohortLogRegTask(task, int(config["clients"]), seed=seed)
