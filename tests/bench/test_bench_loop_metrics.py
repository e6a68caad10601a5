"""The event loop's, the decision's and the collector's metrics: read from a
traced run on the CPU, summed exactly over the window, and silent (None, no
exception) on a program that has none of their spans or counters."""
import contextlib
import io
import re
import time
import types

import jax
import pytest

from bench import harness, spans
from bench.adapter import Adapter
from bench.catalog import load_reader
from bench.traffic.generator import generate

from benchcells import tiny_cell

LOOP_METRICS = ("advance_ms_per_event", "pops_per_event",
                "jobs_advanced_per_event", "rates_ms_p50", "audit_ms_p50",
                "gc_share")


@pytest.fixture(scope="module")
def traced():
    cell = tiny_cell("noncoop1024-steady")
    log = io.StringIO()  # the harness's log and the readers' stderr
    with contextlib.redirect_stderr(log):
        out = harness.measure(cell, seed=2**31 + 13, seconds=1.0, trace=True,
                              device=jax.devices()[0],
                              t_start=time.perf_counter(), log=log)
    return out, log.getvalue()


def test_traced_run_reads_the_loop_metrics(traced):
    out, log = traced
    got = out["metrics"]
    audits = int(re.search(r"audit spans in window: (\d+)", log).group(1))
    expected = set(LOOP_METRICS) - ({"audit_ms_p50"} if audits == 0 else set())
    assert expected <= set(got)
    assert ("audit_ms_p50" in got) == (audits > 0)
    assert got["pops_per_event"]["value"] >= 1.0
    assert got["jobs_advanced_per_event"]["value"] > 0
    assert got["advance_ms_per_event"]["value"] > 0
    assert got["rates_ms_p50"]["value"] > 0
    assert 0 < got["gc_share"]["value"] < 1
    assert out["correct"], out["checks"]


def test_record_counts_sum_to_the_window():
    """The window opens and closes on a decision, so the pops and walked
    jobs of its decisions' records are exactly those between the two."""
    cell = tiny_cell("noncoop1024-steady")
    at_edges = []

    def mark():
        at_edges.append((adapter.sched.events_popped, adapter.sched.jobs_advanced))

    adapter = Adapter(cell.config, warmup_s=float(cell.traffic["warmup_s"]),
                      seconds=0.5, on_open=mark, on_close=mark)
    adapter.run(generate(cell.config, cell.traffic, 5))
    assert adapter.error is None and len(at_edges) == 2
    recs = [d.record for d in adapter.decisions]
    assert recs and None not in recs
    (pops0, walked0), (pops1, walked1) = at_edges
    assert sum(r.events_popped for r in recs) == pops1 - pops0 > 0
    assert sum(r.jobs_advanced for r in recs) == walked1 - walked0 > 0


def test_readers_are_silent_without_the_spans_and_counters():
    """A program from before these spans and counters: records without the
    fields, spans without the names."""
    ctx = types.SimpleNamespace(
        decisions=[types.SimpleNamespace(record=types.SimpleNamespace(reused=False))],
        window_s=2.0, world_events=10,
        spans=[spans.Span("resolve", 0.1, 0.5, 1),
               spans.Span("placement", 0.2, 0.3, 2)])
    for name in LOOP_METRICS:
        assert load_reader(name)(ctx) is None, name


def test_gc_share_is_the_union_of_collector_passes():
    ctx = types.SimpleNamespace(window_s=2.0, spans=[
        spans.Span("gc/gen0", 0.1, 0.1, 0),
        spans.Span("resolve", 0.5, 1.0, 0),
        spans.Span("gc/gen2", 0.6, 0.2, 1),
        spans.Span("gc/gen0", 0.7, 0.05, 2),  # inside the gen2 pass
        spans.Span("gc/gen1", 1.9, 0.1, 0),
    ])
    assert load_reader("gc_share")(ctx) == pytest.approx(0.4 / 2.0)
