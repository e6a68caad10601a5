"""One run end to end on the CPU, with the harness's look for a chip
skipped: the result line's keys, and the refusal to measure off a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from bench.catalog import ROOT

from benchcells import tiny_cell

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def result():
    cell = tiny_cell("noncoop1024-steady")
    log = io.StringIO()
    out = harness.measure(cell, seed=2**31 + 7, seconds=1.0, trace=False,
                          device=jax.devices()[0], t_start=time.perf_counter(),
                          log=log)
    return cell, json.loads(json.dumps(out)), log.getvalue()


def test_result_line_has_the_contract_keys(result):
    cell, out, _ = result
    assert set(out) - {"checks"} == CONTRACT_KEYS
    assert list(out)[-1] == "checks"
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_small_run_is_correct(result):
    _, out, log = result
    assert out["correct"], out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert "window: " in log


def test_traced_run_reads_span_metrics():
    cell = tiny_cell("noncoop1024-steady")
    out = harness.measure(cell, seed=11, seconds=1.0, trace=True,
                          device=jax.devices()[0], t_start=time.perf_counter(),
                          log=io.StringIO())
    # the CPU has no device plane: only the span and counter readers report
    assert {"event_loop_ms_per_event", "reuse_share", "solve_ms_p50",
            "placement_ms_p50"} <= set(out["metrics"])
    assert out["correct"], out["checks"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "noncoop1024-steady",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has no
    program to run."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
