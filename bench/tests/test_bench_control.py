"""The control (the reference computed with bfloat16 rows in the client
block, put in the program's place) comes out not correct through the
run's own check, where a run of the program comes out correct, at a
size a test run can hold.  bench/control.py makes the same check on the
chip at the cells' own sizes."""
import io

import pytest

from control import readings
from yardstick.measure import run

SIZES = {
    "logreg_c256k_fedsgd": {"clients": 1024, "n_examples": 4096,
                            "rounds_per_job": 20, "warmup_rounds": 1},
    "logreg_c16k_dp_fig1b": {"clients": 256, "n_examples": 4096,
                             "rounds_per_job": 6, "warmup_rounds": 1},
}
SEEDS = [2 ** 31 + 5, 6]


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_fails_where_the_program_passes(cell):
    for seed in SEEDS:
        prog = run(cell, seed, 0.01, False, require_tpu=False,
                   size=SIZES[cell], out=io.StringIO(),
                   compile_cache=False)
        assert prog["correct"] is True, prog["checks"]
    rows = readings(cell, SEEDS, SIZES[cell], require_tpu=False)
    for r in rows:
        assert r["correct"] is False, r
        assert r["checks"]["census_mismatch"]["value"] == 0
        gap = r["checks"]["model_gap"]
        assert gap["value"] > gap["limit"], r
