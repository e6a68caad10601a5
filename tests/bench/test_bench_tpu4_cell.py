"""The TPU-fleet cell: its configuration's provenance, and the general
solve's per-layer metrics, read on a traced CPU run and silent on a
program that has none of their spans, counters or device programs."""
import contextlib
import io
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from bench import check, harness, phases, spans
from bench.catalog import ROOT, load_cell, load_reader
from bench.traffic.generator import generate

from benchcells import tiny_cell

CELL = "tpu4noncoop1024-steady"
METRICS = ("search_ms_p50", "crossover_ms_p50", "search_iters_per_solve",
           "search_roofline")


def test_job_types_are_the_profiling_agents_rows():
    """The 1024 rows are ProfilingAgent's, with the seed, error and order
    the file states, and the instance they make is off the staircase
    class, so the cell keeps working the general search."""
    from repro.core import oef
    from repro.core.profiler import ProfilingAgent
    from repro.core.types import TPU_FLEET
    from repro.service.traces import TPU_WORKLOADS

    with open(os.path.join(ROOT, "bench", "configs", "tpu4-noncoop-1024.json")) as f:
        config = json.load(f)
    assert config["device_types"] == [d.name for d in TPU_FLEET]
    assert config["devices_per_type"] == [768] * 4 and config["tenants"] == 1024
    assert config["reduced"] == [] and config["precision"] == "float64"
    agent = ProfilingAgent(TPU_FLEET, error_pct=0.05, seed=15)
    rows = [agent.profile(c) for c in TPU_WORKLOADS for _ in range(256)]
    assert [(j.name, list(j.speedup), j.min_demand) for j in rows] == [
        (j["name"], j["speedup"], j["min_demand"]) for j in config["job_types"]]
    cell = load_cell(CELL)
    trace = check.Trace(generate(cell.config, cell.traffic, 2**31 + 5))
    W = np.stack(list(trace.rows.values()))
    assert W.shape == (1024, 4) and oef.classify_staircase(W) is None
    assert len(np.unique(W, axis=0)) > 512  # bucket 1024 after deduplication


@pytest.fixture(scope="module")
def traced():
    from repro.core import jax_general

    cell = tiny_cell(CELL)
    jax_general.prewarm(cell.config["tenants"], len(cell.config["device_types"]))
    log = io.StringIO()
    with contextlib.redirect_stderr(log):
        out = harness.measure(cell, seed=2**31 + 21, seconds=1.0, trace=True,
                              device=jax.devices()[0],
                              t_start=time.perf_counter(), log=log)
    return out


def test_traced_run_reads_the_search_metrics(traced):
    got = traced["metrics"]
    assert traced["correct"], traced["checks"]
    assert traced["checks"]["off_tier_decisions"]["value"] == 0
    for name in ("search_ms_p50", "crossover_ms_p50", "search_iters_per_solve"):
        assert got[name]["value"] > 0, name
    # the device metrics need a TPU's trace
    assert "search_roofline" not in got


def test_readers_are_silent_on_a_program_without_the_search():
    """The parent program runs the cell through the LP: no ``search`` or
    ``crossover`` spans, no ``search_iters`` on its records, no search
    program on the device."""
    record = types.SimpleNamespace(reused=False, backend="lp")
    decision = types.SimpleNamespace(record=record, tenants=("a",) * 40,
                                     X=np.ones((40, 4)))
    window = [spans.Span("resolve", 0.0, 1.0, 0), spans.Span("solve", 0.1, 0.5, 1)]
    device = types.SimpleNamespace(ops=[["jit__solve_padded", 0.2]],
                                   busy_s=0.2, window_s=1.0)
    ctx = types.SimpleNamespace(decisions=[decision], spans=window,
                                device=device, window_s=1.0, world_events=3)
    for name in METRICS:
        assert load_reader(name)(ctx) is None, name


def test_phases_sum_per_solve():
    window = [spans.Span("solve", 0.0, 1.0, 1), spans.Span("search", 0.1, 0.2, 4),
              spans.Span("crossover", 0.3, 0.05, 4), spans.Span("search", 0.4, 0.2, 4),
              spans.Span("solve", 2.0, 0.5, 1), spans.Span("solve", 3.0, 1.0, 1),
              spans.Span("search", 3.5, 0.25, 4)]
    assert phases.per_solve(window, "search") == pytest.approx([0.4, 0.25])
    assert phases.per_solve(window, "crossover") == pytest.approx([0.05])


def test_roofline_counts_the_padded_work():
    reader = load_reader("search_roofline")
    mod = reader.__globals__
    ops, nbytes = mod["work"](1000, 4, 16)
    assert mod["bucket"](1000) == 1024 and mod["bucket"](3) == 8
    assert ops == 16 * 1024 * 4 * (13 * 16 * 6 + 16 * 13 + 2 * 4)
    assert nbytes == 16 * (1024 * 4 + 1024) * 8
    assert mod["work"](600, 4, 16) == mod["work"](1000, 4, 16)
