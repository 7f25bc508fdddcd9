"""Every configuration, traffic mix, limit file and per-layer metric
named in BENCHMARK.json resolves by name, and the file keeps to the
shape a BENCHMARK.json must have."""
import json
import os
import re

import pytest

from yardstick.spec import (BENCH_DIR, ROOT, Cell, load_benchmark, load_module,
                            metric_reader, model_of, reference_of)

BM = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_keys():
    names = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in BM[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for group in ("end_to_end", "per_layer"):
        for m in BM[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["name"] not in names
            names.add(m["name"])
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    pairs = {(w["config"], w["traffic"]) for w in BM["workloads"]}
    assert len(pairs) == len(BM["workloads"])
    assert sum(w["chips"] == 4 for w in BM["workloads"]) <= max(
        1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = Cell(cell)
    assert c.config["name"] == c.entry["config"]
    assert set(c.limits) == {"model_gap", "census_mismatch"}
    assert {m["name"] for m in c.end_to_end()} >= {"setup_s"}
    assert len(c.end_to_end()) >= 2 and c.per_layer()
    for k in ("fl", "rounds_per_job", "eval_every", "sizes", "step",
              "warmup_rounds", "check_jobs", "trace_jobs", "reference",
              "fleet"):
        assert k in c.traffic, k
    assert {"cohort_block", "scenario", "d"} <= set(c.traffic["fl"])
    # the model, the protocol's reference and the fleet's kinds resolve
    # by name to files of their own
    model = model_of(c.config)
    assert model.width(c.config) == c.config["n_features"] + 1
    assert callable(reference_of(c.config, c.traffic).run)
    fleet = c.traffic["fleet"]
    assert callable(load_module("fleets/speeds",
                                fleet["speeds"]["kind"]).speeds)
    assert callable(load_module("fleets/availability",
                                fleet["availability"]["kind"]).availability)


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda e: e["name"])
def test_config_file(entry):
    assert entry["file"].startswith("bench/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    for key in entry["reduced"]:
        assert key in cfg and key in cfg["assumed"]
        assert not key.endswith(("_dim", "_rank")) and key != "n_features"
    assert cfg["n_features"] == 784          # MNIST's width, never cut


@pytest.mark.parametrize("name", [m["name"] for m in BM["per_layer"]])
def test_metric_reader_loads(name):
    read = metric_reader(name)
    assert callable(read)
    assert os.path.exists(os.path.join(BENCH_DIR, "metrics", f"{name}.py"))


def test_unknown_name_is_an_error():
    with pytest.raises(KeyError, match="no models named"):
        load_module("models", "no_such_model")
