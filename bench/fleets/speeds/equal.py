"""Every client at speed 1."""
import numpy as np


def speeds(spec, C, seed):
    return np.ones(C)
