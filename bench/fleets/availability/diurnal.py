"""Diurnal windows: each client is on for ``on_frac`` of a period of
``period_s`` seconds, from a phase drawn as the program draws it (numpy
``default_rng(seed ^ 0xD1A7)``, uniform over the period), in ticks of
``dt`` seconds."""
import numpy as np

PHASE_XOR = 0xD1A7


def availability(spec, C, seed, dt):
    period_t = max(2, int(round(spec["period_s"] / dt)))
    on_t = min(period_t - 1, max(1, int(round(spec["on_frac"] * period_t))))
    phases = np.random.default_rng(seed ^ PHASE_XOR).uniform(
        0.0, spec["period_s"], C)
    phase_t = np.floor(phases / dt).astype(np.int64) % period_t

    def avail(t):
        return (t + phase_t) % period_t < on_t
    return avail
