"""Each cell's run drives the program at a small size through the same
code the chip runs, and the measurement entry refuses to run without a
TPU."""
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from yardstick.measure import run
from yardstick.spec import ROOT, load_benchmark

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
SMALL = {"clients": 64, "n_examples": 512, "rounds_per_job": 3,
         "warmup_rounds": 1}
SEED = 2 ** 31 + 977        # --seed may exceed 32 signed bits


def small_run(cell, trace=False, seed=SEED):
    out = io.StringIO()
    res = run(cell, seed, 0.05, trace, require_tpu=False, size=SMALL,
              out=out, compile_cache=False)
    line = out.getvalue().strip().splitlines()[-1]
    assert json.loads(line) == json.loads(json.dumps(res))
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_one_short_job_is_correct(cell):
    res = small_run(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"updates_per_s", "round_ms_p95",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["census_mismatch"]["value"] == 0


def test_traced_run_reports_host_layers():
    res = small_run(CELLS[0], trace=True)
    assert res["correct"] is True
    # no TPU plane on the CPU: device metrics are left out, not 0
    assert set(res["metrics"]) == {"job_setup_ms", "eval_ms_per_round"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res


def _entry(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(SEED), "--seconds", "1", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_entry_exits_nonzero_without_tpu():
    p = _entry(ROOT, "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _entry(tmp_path, "--trace", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
