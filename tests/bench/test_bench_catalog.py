"""Cells, configurations, traffic mixes and metric readers are found by
name from data files, and BENCHMARK.json keeps to its contract's shape."""
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import adapter, catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(catalog.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads(name):
    cell = catalog.load_cell(name)
    assert cell.config["name"] and cell.traffic["name"]
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.read)
    # the trace must outlast the warm-up and every window
    assert cell.traffic["duration_s"] > 10 * cell.traffic["warmup_s"]


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]] \
        + [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(catalog.ROOT, c["file"]))
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(catalog.ROOT, "bench", "metrics",
                                           m["name"] + ".py"))


def test_dropped_in_files_are_found_without_code(tmp_path):
    """A new traffic mix, configuration and metric reader, added as files and
    entries only, make a cell the catalog loads."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(catalog.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    b = _bench()
    traffic = json.loads((root / "bench" / "traffic" / "steady.json").read_text())
    traffic.update(name="bursty", join_spread_s=900.0)
    (root / "bench" / "traffic" / "bursty.json").write_text(json.dumps(traffic))
    (root / "bench" / "metrics" / "decision_count.py").write_text(
        "def read(ctx):\n    return len(ctx.decisions)\n")
    b["workloads"].append({"name": "noncoop1024-bursty", "config": "paper3-noncoop-1024",
                           "traffic": "bursty", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "decision_count", "unit": "1",
                           "better": "lower", "source": "program_counter",
                           "layer": "re-solve", "moves": "decision_ms_p50",
                           "workloads": ["noncoop1024-bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = catalog.load_cell("noncoop1024-bursty", str(root))
    assert cell.traffic["join_spread_s"] == 900.0
    reader = {m.name: m for m in cell.per_layer}["decision_count"].read
    assert reader(type("Ctx", (), {"decisions": [1, 2, 3]})) == 3
    other = catalog.load_cell("noncoop1024-steady", str(root))
    assert "decision_count" not in {m.name for m in other.per_layer}
    with pytest.raises(KeyError):
        catalog.load_cell("no-such-cell", str(root))


def test_peaks_refuse_an_unknown_device():
    peaks = catalog.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes"] == 16e9 and "source" in peaks
    with pytest.raises(KeyError):
        catalog.load_peaks("cpu")


def test_warm_buckets_follow_the_tenant_count(monkeypatch):
    """The cell warms the solve programs of its tenant count's padding
    bucket and the one below, cold and warm-started, and no others."""
    from repro.core import jax_solve

    calls = []

    def solve(W, m, tau_hint=None):
        calls.append((W.shape[0], tau_hint is not None))
        return np.ones(W.shape[0]), None

    monkeypatch.setattr(jax_solve, "solve_noncoop_fast_jax", solve)
    monkeypatch.setattr(adapter, "WARM_BUCKETS", 2)
    config = catalog.load_cell("noncoop1024-steady").config
    assert adapter.warm_solver(config) == [512, 1024]
    assert sorted(calls) == [(512, False), (512, True), (1024, False), (1024, True)]
