"""Plain reference of a ``"reference": "ticks"`` mix over the logreg
model: the paper's protocol tick by tick (``yardstick/reference.py``
``ticks``), the fleet drawn from the seed by the mix's ``fleet`` kinds,
per-example clipping and the DP noise chain from the configuration's
``dp``, under the paper's own aggregation (apply on arrival)."""
from yardstick import reference
from yardstick.data import seed32


def run(config, traffic, job, inputs, v0, seed, low=False):
    if traffic["fl"].get("aggregation") not in (None, "paper"):
        raise ValueError("the ticks reference applies each update on "
                         "arrival: another aggregation needs its own")
    X, y, _ = inputs
    plan, s = job["plan"], seed32(seed)
    dp = config.get("dp") or {}
    return reference.ticks(
        X, y, v0, plan["etas"], plan["sizes"], C=int(config["clients"]),
        rounds=plan["rounds"], d_gate=int(traffic["fl"]["d"]),
        block=int(traffic["fl"]["cohort_block"]),
        fleet_spec=traffic["fleet"], seed=s, sample_seed=s,
        l2=float(config["l2"]), clip=float(dp.get("clip", 0.0)),
        noise_scale=float(dp.get("clip", 0.0)) * float(dp.get("sigma", 0.0)),
        low=low)
