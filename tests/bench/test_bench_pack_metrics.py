"""The packer's metrics: ``pack_ms_p50`` from the ``pack`` spans and
``hosts_probed_per_job`` from the decisions' records, each silent (None, no
exception) on a program that has no such span or counter."""
import types

import pytest

from bench import spans
from bench.adapter import Adapter
from bench.catalog import load_reader
from bench.traffic.generator import generate

from benchcells import tiny_cell


def _ctx(records=(), span_list=None):
    return types.SimpleNamespace(
        decisions=[types.SimpleNamespace(record=r) for r in records],
        window_s=2.0, world_events=10, spans=span_list)


def _record(**counters):
    return types.SimpleNamespace(reused=False, **counters)


def test_pack_ms_p50_is_the_median_pack_span():
    ctx = _ctx(span_list=[
        spans.Span("resolve", 0.0, 0.5, 0),
        spans.Span("placement", 0.1, 0.3, 1),
        spans.Span("pack", 0.2, 0.004, 2),
        spans.Span("resolve", 1.0, 0.5, 0),
        spans.Span("placement", 1.1, 0.3, 1),
        spans.Span("pack", 1.2, 0.002, 2),
        spans.Span("pack", 1.3, 0.010, 2),
    ])
    assert load_reader("pack_ms_p50")(ctx) == pytest.approx(4.0)


def test_pack_ms_p50_is_silent_without_pack_spans():
    reader = load_reader("pack_ms_p50")
    assert reader(_ctx(span_list=[spans.Span("placement", 0.1, 0.3, 1)])) is None
    assert reader(_ctx()) is None  # an untraced run has no spans


def test_hosts_probed_per_job_sums_over_the_window():
    ctx = _ctx([_record(hosts_probed=30, jobs_searched=12),
                None,  # a decision without a record
                _record(hosts_probed=6, jobs_searched=3)])
    assert load_reader("hosts_probed_per_job")(ctx) == pytest.approx(36 / 15)


def test_hosts_probed_per_job_is_silent_without_the_counters():
    reader = load_reader("hosts_probed_per_job")
    assert reader(_ctx([_record(), _record()])) is None  # older records
    assert reader(_ctx([_record(hosts_probed=0, jobs_searched=0)])) is None
    assert reader(_ctx()) is None


def test_packer_counters_reach_the_records():
    """A replay of the tiny paper cell: each record carries its own
    placement's probes, so the records sum to the scheduler's totals, and
    every searched job probes at least one bucket and takes one host."""
    cell = tiny_cell("noncoop1024-steady")
    adapter = Adapter(cell.config, warmup_s=float(cell.traffic["warmup_s"]),
                      seconds=0.5)
    adapter.run(generate(cell.config, cell.traffic, 5))
    assert adapter.error is None
    sched = adapter.sched
    recs = sched.metrics.solves
    assert sum(r.hosts_probed for r in recs) == sched.hosts_probed > 0
    assert sum(r.jobs_searched for r in recs) == sched.jobs_searched > 0
    ratio = load_reader("hosts_probed_per_job")(_ctx(recs))
    assert 2.0 <= ratio <= sched.devices_per_host + 2, ratio


def test_record_counts_sum_to_a_window_inside_the_trace():
    """The window opens and closes on a decision, so the pops, settles,
    probes and searched jobs of its decisions' records are exactly those
    between the two. The trace outlasts the window: at its end a decision
    with nobody active leaves no record."""
    cell = tiny_cell("noncoop1024-steady")
    cell.traffic = dict(cell.traffic, duration_s=200000.0)
    counters = ("events_popped", "jobs_advanced", "hosts_probed",
                "jobs_searched")
    at_edges = []

    def mark():
        at_edges.append([getattr(adapter.sched, c) for c in counters])

    adapter = Adapter(cell.config, warmup_s=float(cell.traffic["warmup_s"]),
                      seconds=0.5, on_open=mark, on_close=mark)
    adapter.run(generate(cell.config, cell.traffic, 5))
    assert adapter.error is None and not adapter.exhausted
    assert len(at_edges) == 2
    recs = [d.record for d in adapter.decisions]
    assert recs and None not in recs
    for i, name in enumerate(counters):
        between = at_edges[1][i] - at_edges[0][i]
        assert sum(getattr(r, name) for r in recs) == between > 0, name
