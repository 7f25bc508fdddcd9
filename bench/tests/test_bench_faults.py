"""A run whose timed path is broken underneath comes out not correct:
the chip check skipped, the rest of the run as on the chip, at a small
size.  One case for each fault the cells can have."""
import io

import jax.numpy as jnp
import pytest

import repro.cohort.device as device
from yardstick.measure import run

SMALL = {"clients": 64, "n_examples": 512, "rounds_per_job": 3,
         "warmup_rounds": 1}
CELLS = ["logreg_c256k_fedsgd", "logreg_c16k_dp_fig1b"]


def unchanged(orig):
    """The server step returns its model unchanged."""
    def f(v, rows, dec, flag, **kw):
        return v
    return "bucket_apply", f


def altered(orig):
    """The server's model altered where it is produced: each applied
    update one part in a thousand too large."""
    def f(v, rows, dec, flag, **kw):
        out = orig(v, rows, dec, flag, **kw)
        return out + 1e-3 * (out - v)
    return "bucket_apply", f


def half_batch(orig):
    """Half of the clients' updates left out, the mean taken over the
    rest (the other half counted twice)."""
    def f(sent, w, U, upd, wgt, any_g, done, eta, **kw):
        keep = 2.0 * (jnp.arange(wgt.shape[1]) % 2 == 0)
        return orig(sent, w, U, upd, wgt * keep[None, :], any_g, done,
                    eta, **kw)
    return "tick_scatter", f


@pytest.mark.parametrize("fault", [unchanged, altered, half_batch],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    name = "bucket_apply" if fault is not half_batch else "tick_scatter"
    target, patched = fault(getattr(device, name))
    monkeypatch.setattr(device, target, patched)
    res = run(cell, 2 ** 31 + 31, 0.05, False, require_tpu=False,
              size=SMALL, out=io.StringIO(), compile_cache=False)
    assert res["correct"] is False, res["checks"]
    gap = res["checks"]["model_gap"]
    assert gap["value"] > gap["limit"]
