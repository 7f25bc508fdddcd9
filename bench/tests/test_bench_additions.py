"""A cell is added with new files and new entries of BENCHMARK.json
alone.  In a copy of the benchmark, a new configuration naming a new
model, a new traffic mix with its own FLConfig and simulator settings,
a new fleet kind, a new protocol reference, a new limits file and a new
per-layer metric are added; the new cell runs through the same code,
comes out correct, reports the new metric, and hands the mix's settings
to the program; and no file that was there has changed."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from yardstick.spec import ROOT

CONFIG = {
    "name": "tiny_logreg", "source": "https://arxiv.org/abs/2007.09208",
    "model": "tiny_logreg", "n_features": 784, "n_examples": 512,
    "l2": 1.0 / 512, "clients": 64, "dp": None, "precision": "float32"}
MIX = {
    "fl": {"cohort_block": 16, "scenario": "uniform", "d": 2,
           "aggregation": "paper"},
    "simulator": {"fuse_ticks": False},
    "rounds_per_job": 3, "eval_every": 1,
    "sizes": {"kind": "constant", "s": 4},
    "step": {"kind": "constant", "server_step": [0.5]},
    "warmup_rounds": 1, "check_jobs": 1, "trace_jobs": 1,
    "reference": "ticks_again",
    "fleet": {"speeds": {"kind": "ones"},
              "availability": {"kind": "always"}, "latency_ticks": 1}}
FILES = {
    "bench/configs/tiny_logreg.json": json.dumps(CONFIG),
    "bench/traffic/tiny_mix.json": json.dumps(MIX),
    "bench/limits/tiny_cell.json": json.dumps(
        {"model_gap": {"limit": 5e-5}, "census_mismatch": {"limit": 0}}),
    "bench/models/tiny_logreg.py": (
        "from yardstick.spec import load_module\n"
        "_m = load_module('models', 'logreg')\n"
        "inputs, width, initial_vector, work, build = (\n"
        "    _m.inputs, _m.width, _m.initial_vector, _m.work, _m.build)\n"),
    "bench/references/tiny_logreg_ticks_again.py": (
        "from yardstick.spec import load_module\n"
        "run = load_module('references', 'logreg_ticks').run\n"),
    "bench/fleets/speeds/ones.py": (
        "import numpy as np\n\n\n"
        "def speeds(spec, C, seed):\n"
        "    return np.ones(C)\n"),
    "bench/metrics/tiny_jobs_traced.py": (
        "def read(ctx):\n"
        "    return float(len(ctx['jobs'])) or None\n"),
}
DRIVER = r"""
import json, sys
sys.path.insert(0, "bench")
sys.path.insert(0, "src")
import jax
jax.config.update("jax_numpy_rank_promotion", "raise")
import repro.cohort as cohort
seen = []
make = cohort.make_simulator

def spy(cfg, task, **kw):
    seen.append({"aggregation": cfg.aggregation, "scenario": cfg.scenario,
                 "block": cfg.cohort_block, "d": kw["d"],
                 "fuse_ticks": kw.get("fuse_ticks")})
    return make(cfg, task, **kw)

cohort.make_simulator = spy
from yardstick.measure import run
res = run("tiny_cell", 2 ** 31 + 7, 0.01, True, require_tpu=False,
          out=sys.stderr, compile_cache=False)
print(json.dumps({"result": res, "seen": seen}))
"""


def _digests(root):
    out = {}
    for base in ("bench",):
        for d, _, files in os.walk(os.path.join(root, base)):
            for f in files:
                if "__pycache__" in d:
                    continue
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_alone(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    before = _digests(tmp_path)
    for rel, text in FILES.items():
        assert not (tmp_path / rel).exists(), rel
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "tiny_logreg", "source": CONFIG["source"],
                          "file": "bench/configs/tiny_logreg.json",
                          "reduced": [], "why": "a throwaway"})
    bm["workloads"].append({"name": "tiny_cell", "config": "tiny_logreg",
                            "traffic": "tiny_mix", "chips": 1,
                            "why": "a throwaway"})
    bm["per_layer"].append({"name": "tiny_jobs_traced", "unit": "jobs",
                            "better": "higher", "source": "host_clock",
                            "layer": "entry point",
                            "moves": "updates_per_s",
                            "workloads": ["tiny_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", DRIVER], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    # the metrics whose ``workloads`` leave the new cell out stay out
    assert set(res["metrics"]) == {"tiny_jobs_traced"}
    assert res["metrics"]["tiny_jobs_traced"]["value"] >= 1
    assert out["seen"] and all(
        s == {"aggregation": "paper", "scenario": "uniform", "block": 16,
              "d": 2, "fuse_ticks": False} for s in out["seen"])
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        os.path.normpath(k) for k in FILES if k.startswith("bench/")}
