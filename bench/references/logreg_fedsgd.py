"""Plain reference of a ``"reference": "fedsgd"`` mix over the logreg
model: the closed form of the protocol for one local step a round,
d = 1, one-tick latency, an always-on fleet of equal speed and no DP
(``yardstick/reference.py`` ``fedsgd``).  A mix or configuration that
states anything else is refused: it needs a reference of its own."""
from yardstick import reference
from yardstick.data import seed32


def run(config, traffic, job, inputs, v0, seed, low=False):
    fleet = traffic["fleet"]
    closed_form = (
        int(traffic["fl"]["d"]) == 1 and not config.get("dp")
        and traffic["sizes"] == {"kind": "constant", "s": 1}
        and fleet["speeds"]["kind"] == "equal"
        and fleet["availability"]["kind"] == "always"
        and int(fleet.get("latency_ticks", 1)) == 1)
    if not closed_form:
        raise ValueError("the fedsgd closed form holds only for s = 1, "
                         "d = 1, no DP and an equal, always-on fleet")
    X, y, _ = inputs
    plan = job["plan"]
    return reference.fedsgd(X, y, v0, plan["etas"],
                            C=int(config["clients"]), rounds=plan["rounds"],
                            sample_seed=seed32(seed),
                            l2=float(config["l2"]), low=low)
