"""A share ``slow_frac`` of the clients at speed ``slow``, the rest at
1, drawn as the program's bimodal speeds are: numpy
``default_rng(seed ^ 0x5BEED)``, one uniform per client."""
import numpy as np

SPEED_XOR = 0x5BEED


def speeds(spec, C, seed):
    rng = np.random.default_rng(seed ^ SPEED_XOR)
    return np.where(rng.random(C) < spec["slow_frac"], spec["slow"], 1.0)
