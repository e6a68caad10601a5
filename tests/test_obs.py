"""Tests for the repro.obs tracing + streaming-metrics plane.

Covers: span nesting and Chrome export, the disabled (no-tracer) fast path,
bounded-memory drop counting, instrument semantics (counter/gauge/histogram
windows), JSONL sample rows, the offline report reader (containment
reconstruction + fairness series), numpy-safe report serialization, and the
end-to-end contracts against the running service: trace/metrics artifacts
from a real run, quarantine visibility in the gauge series, tracing not
perturbing a chaos replay, and ``degraded_solves`` matching the span-level
guardrail instants exactly.
"""
from __future__ import annotations

import dataclasses
import gc
import json

import numpy as np
import pytest

from repro import obs
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.service.events import Event, EventKind
from repro.service.faults import ChaosEngine, standard_plan
from repro.service.metrics import MetricsCollector
from repro.service.scheduler import OnlineScheduler
from repro.service.traces import default_cluster, synthetic_trace
from repro.core.types import ClusterSpec


@pytest.fixture(autouse=True)
def _clean_globals():
    """Never leak a tracer/registry into other tests."""
    yield
    obs.set_tracer(None)
    obs.set_metrics(None)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_nesting_builds_paths():
    tr = obs.Tracer()
    with tr.span("outer", "t"):
        with tr.span("inner", "t"):
            pass
        with tr.span("inner", "t"):
            pass
    stats = tr.flame_stats()
    assert set(stats) == {"outer", "outer;inner"}
    assert stats["outer;inner"]["count"] == 2
    # self time excludes direct children
    assert stats["outer"]["self_s"] <= stats["outer"]["total_s"]


def test_module_level_span_is_noop_without_tracer():
    assert obs.get_tracer() is None
    assert obs_trace.span("x") is obs_trace.NULL_SPAN
    obs_trace.instant("x")  # must not raise
    with obs_trace.span("x", "cat", a=1):
        pass


def test_module_level_span_records_on_installed_tracer():
    tr = obs.Tracer()
    prev = obs.set_tracer(tr)
    assert prev is None
    with obs_trace.span("a", "svc", n=3):
        obs_trace.instant("tick", "svc", k=1)
    assert obs.set_tracer(None) is tr
    # a collector pass inside the span would be a gc/* span of its own
    (name, cat, path, _t0, dur, sim, args) = [
        s for s in tr.spans if s[1] != "gc"][0]
    assert (name, cat, path, args) == ("a", "svc", "a", {"n": 3})
    assert dur >= 0.0 and sim is None
    (iname, _icat, parent, _t, _sim, iargs) = tr.instants[0]
    assert (iname, parent, iargs) == ("tick", "a", {"k": 1})


def test_sim_clock_stamps_spans_and_instants():
    tr = obs.Tracer()
    tr.set_sim_clock(lambda: 42.5)
    with tr.span("a"):
        tr.instant("i")
    assert tr.spans[0][5] == 42.5
    assert tr.instants[0][4] == 42.5
    events = tr.chrome_events()
    assert all(e["args"]["sim_t"] == 42.5
               for e in events if e["ph"] in ("X", "i"))


def test_max_events_drops_are_counted_not_silent():
    tr = obs.Tracer(max_events=2)
    for _ in range(5):
        with tr.span("s"):
            pass
        tr.instant("i")
    assert len(tr.spans) == 2 and len(tr.instants) == 2
    assert tr.dropped == 6
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6
    assert any("dropped 6" in line for line in tr.flame_lines())


def test_collector_pass_is_spanned_under_the_open_span():
    n_hooks = len(gc.callbacks)
    tr = obs.Tracer()
    obs.set_tracer(tr)
    obs.set_tracer(tr)  # re-installing keeps one hook
    assert len(gc.callbacks) == n_hooks + 1
    with obs_trace.span("outer", "t"):
        gc.collect()
    assert obs.set_tracer(None) is tr
    assert len(gc.callbacks) == n_hooks
    passes = [s for s in tr.spans if s[0] == "gc/gen2"]
    assert len(passes) == 1 and passes[0][1] == "gc"
    assert passes[0][2] == "outer;gc/gen2"
    outer = next(s for s in tr.spans if s[0] == "outer")
    assert outer[3] <= passes[0][3] and passes[0][4] <= outer[4]
    n_spans = len(tr.spans)
    gc.collect()  # no tracer, no hook: nothing recorded
    assert len(tr.spans) == n_spans


def test_chrome_export_shape(tmp_path):
    tr = obs.Tracer()
    with tr.span("a", "svc"):
        tr.instant("blip", "guardrail")
    path = tmp_path / "t.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    assert doc["otherData"]["schema"] == obs.CHROME_SCHEMA
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert phases.count("M") == 2 and "X" in phases and "i" in phases
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["name"] == "a" and x["cat"] == "svc"
    assert x["ts"] >= 0.0 and x["dur"] >= 0.0  # µs since tracer creation
    i = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert i["s"] == "t" and i["cat"] == "guardrail"


# ---------------------------------------------------------------------------
# metrics instruments + registry
# ---------------------------------------------------------------------------


def test_counter_and_gauge_semantics():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g", "items").set(7)
    reg.gauge("g").set(3)  # get-or-create returns the same instrument
    row = reg.sample(1.0)
    assert row["counters"] == {"c": 3}
    assert row["gauges"] == {"g": 3}
    assert row["units"]["g"] == "items"


def test_histogram_buckets_and_window_quantiles():
    h = obs.Histogram("h", edges=(1.0, 10.0), window=4)
    for v in (0.5, 5.0, 50.0, 5.0, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["counts"] == [1, 3, 1]  # <=1, <=10, overflow
    # the ring holds the last 4 values: 5, 50, 5, 5
    assert snap["p50"] == 5.0 and snap["max"] == 50.0
    with pytest.raises(ValueError):
        obs.Histogram("bad", edges=(3.0, 1.0))
    with pytest.raises(ValueError):
        obs.Histogram("bad", window=0)


def test_registry_samples_accumulate_without_sink():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.sample(0.0)
    reg.sample(1.0)
    assert [r["seq"] for r in reg.samples] == [0, 1]
    assert all(r["schema"] == obs.SAMPLE_SCHEMA for r in reg.samples)


def test_jsonl_sink_writes_numpy_safe_rows(tmp_path):
    path = tmp_path / "m.jsonl"
    sink = obs.JsonlSink(str(path))
    reg = obs.MetricsRegistry(sink=sink)
    reg.counter("c").inc(np.int64(2))
    reg.gauge("g").set(np.float64(0.5))
    reg.sample(np.float64(3.0))
    sink.close()
    assert sink.rows_written == 1 and reg.samples == []
    rows = obs_report.load_metrics_jsonl(str(path))
    assert rows[0]["counters"]["c"] == 2 and rows[0]["t"] == 3.0


# ---------------------------------------------------------------------------
# json_safe / tally (shared serialization helpers)
# ---------------------------------------------------------------------------


def test_json_safe_handles_nested_numpy():
    obj = {
        np.int64(1): np.bool_(True),
        "arr": np.arange(3),
        "nest": [(np.float64(0.5), {"k": np.float32(2.0)})],
    }
    safe = obs.json_safe(obj)
    assert json.loads(json.dumps(safe)) == {
        "1": True, "arr": [0, 1, 2], "nest": [[0.5, {"k": 2.0}]]}


def test_tally_counts_like_counter():
    assert obs.tally(["a", "b", "a"]) == {"a": 2, "b": 1}
    assert obs.tally([]) == {}


def test_service_report_serializes_numpy_audits_recursively():
    # regression: property_report values are numpy scalars; before obs the
    # report serializer only coerced top-level values and a nested audit
    # (or a numpy-valued steady-state dict) crashed json.dumps.
    mc = MetricsCollector()
    mc.on_audit(10.0, {"envy_free": np.bool_(True),
                       "max_envy": np.float64(0.25),
                       "per_tenant": {"t0": np.float32(1.0)},
                       "adjacent": (np.int64(1), np.int64(2))})
    json.dumps(mc.audits)  # sanitized at ingestion, not just in to_json
    rep = mc.report(policy="oef-coop", horizon_s=1.0, jobs_unfinished=0,
                    steady_state_estimate={"t0": np.float64(0.5)})
    parsed = json.loads(rep.to_json())
    assert parsed["fairness_audits"][0]["max_envy"] == 0.25
    assert parsed["steady_state_estimate"]["t0"] == 0.5


# ---------------------------------------------------------------------------
# offline report reader
# ---------------------------------------------------------------------------


def _chrome_doc(events):
    return {"traceEvents": events, "otherData": {"schema": obs.CHROME_SCHEMA}}


def test_span_paths_rebuild_nesting_by_containment():
    doc = _chrome_doc([
        {"ph": "X", "name": "resolve", "ts": 0.0, "dur": 100.0},
        {"ph": "X", "name": "solve", "ts": 10.0, "dur": 50.0},
        {"ph": "X", "name": "dispatch", "ts": 20.0, "dur": 30.0},
        {"ph": "X", "name": "placement", "ts": 70.0, "dur": 20.0},
        {"ph": "X", "name": "resolve", "ts": 200.0, "dur": 10.0},
        {"ph": "i", "name": "ignored", "ts": 5.0},
    ])
    paths = [p for p, _ts, _dur in obs_report.span_paths(doc)]
    assert paths == ["resolve", "resolve;solve", "resolve;solve;dispatch",
                     "resolve;placement", "resolve"]
    stats = obs_report.stage_stats(obs_report.span_paths(doc))
    assert stats["resolve"]["count"] == 2
    # self time of the first resolve excludes solve + placement
    assert stats["resolve"]["self_ms"] == pytest.approx((110 - 50 - 20) / 1e3)


def test_fairness_series_one_point_per_audit():
    rows = [
        {"t": 0.0, "counters": {"service.audits": 0}, "gauges": {}},
        {"t": 1.0, "counters": {"service.audits": 1},
         "gauges": {"fairness.max_envy": 0.1}},
        {"t": 2.0, "counters": {"service.audits": 1},
         "gauges": {"fairness.max_envy": 0.1}},
        {"t": 3.0, "counters": {"service.audits": 2},
         "gauges": {"fairness.max_envy": 0.05}},
    ]
    series = obs_report.fairness_series(rows)
    assert [(p["t"], p["fairness.max_envy"]) for p in series] == [
        (1.0, 0.1), (3.0, 0.05)]


# ---------------------------------------------------------------------------
# end to end against the service
# ---------------------------------------------------------------------------

_CLUSTER2 = ClusterSpec(types=("a", "b"), m=(8, 8))


def _join(t, name, speedup, jt="train"):
    return Event(t, EventKind.TENANT_JOIN, tenant=name, payload={
        "job_types": [{"name": jt, "speedup": list(speedup)}]})


def _submit(t, name, job_id, work=1e4, workers=2, jt="train"):
    return Event(t, EventKind.JOB_SUBMIT, tenant=name, job_id=job_id,
                 payload={"job_type": jt, "workers": workers,
                          "total_work": work})


def _profile(t, name, speedup, jt="train"):
    return Event(t, EventKind.PROFILE_UPDATE, tenant=name,
                 payload={"job_type": jt, "speedup": list(speedup)})


def _run_observed(trace, *, until=None, policy="oef-coop", audit_every=2,
                  **kw):
    """Run a scheduler with a fresh tracer + (sinkless) registry installed."""
    tracer, reg = obs.Tracer(), obs.MetricsRegistry()
    obs.set_tracer(tracer)
    obs.set_metrics(reg)
    cluster = kw.pop("cluster", _CLUSTER2)
    sched = OnlineScheduler(cluster, policy, min_resolve_interval_s=1.0,
                            audit_every=audit_every, **kw)
    try:
        rep = sched.run(list(trace), until=until)
    finally:
        obs.set_tracer(None)
        obs.set_metrics(None)
    return sched, rep, tracer, reg


def test_service_run_produces_trace_and_metrics(tmp_path):
    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0"),
        _join(0.0, "t1", (1.0, 3.0)), _submit(0.0, "t1", "j1"),
        # profile drift forces fresh re-solves (and audits) past the first
        _profile(100.0, "t0", (1.2, 2.0)),
        _profile(200.0, "t1", (1.0, 3.5)),
    ]
    _sched, rep, tracer, reg = _run_observed(trace, until=600.0,
                                             audit_every=1)
    stats = tracer.flame_stats()
    resolve_paths = [p for p in stats if p.endswith(";resolve")]
    assert resolve_paths, sorted(stats)
    # the acceptance nesting: resolve -> solve -> dispatch -> backend/<n>
    assert any(";resolve;solve;dispatch;backend/" in p for p in stats), \
        sorted(stats)
    assert any(p.endswith(";resolve;placement") for p in stats)
    # sim-time stamping: spans carry the event clock, not wall time
    sims = [s[5] for s in tracer.spans if s[0] == "resolve"]
    assert sims and all(s is not None and 0.0 <= s <= 600.0 for s in sims)
    # one metrics sample per solve; final counter equals the report
    assert len(reg.samples) == rep.n_solves
    last = reg.samples[-1]
    assert last["counters"]["service.solves"] == rep.n_solves
    assert last["counters"]["service.audits"] == len(rep.fairness_audits)
    assert "service.solve_latency_ms.lp" in last["histograms"] or any(
        k.startswith("service.solve_latency_ms.") for k in last["histograms"])
    # the report reader renders both artifacts end to end
    tpath, mpath = tmp_path / "t.json", tmp_path / "m.jsonl"
    tracer.save(str(tpath))
    with open(mpath, "w") as f:
        for row in reg.samples:
            f.write(json.dumps(obs.json_safe(row)) + "\n")
    assert obs_report.classify(str(tpath)) == "trace"
    assert obs_report.classify(str(mpath)) == "metrics"
    text = "\n".join(obs_report.report_lines([str(tpath), str(mpath)]))
    assert "per-stage latency breakdown" in text
    assert "resolve;solve" in text
    assert "fairness over time" in text


def test_advance_spans_are_depth0_siblings_of_event_spans():
    """Progress is settled inside the decision, not walked per pop: each
    ``advance`` span sits under ``resolve`` beside ``placement`` and
    ``rates``, or around a tenant's or host's jobs under its event, or at
    the top for the final settle at the horizon. None is a depth-0 sibling
    of the ``event/<kind>`` spans any more, one per pop."""
    trace = [
        _join(0.0, "t0", (1.0, 2.0)), _submit(0.0, "t0", "j0"),
        _join(0.0, "t1", (1.0, 3.0)), _submit(0.0, "t1", "j1"),
        _submit(50.0, "t0", "j2"), _submit(120.0, "t1", "j3"),
        _profile(200.0, "t1", (1.0, 3.5)),
    ]
    sched, _rep, tracer, _reg = _run_observed(trace, until=3000.0,
                                              audit_every=1)
    paths = [s[2] for s in tracer.spans if s[1] != "gc"]
    advances = [p for p in paths if p.split(";")[-1] == "advance"]
    # one per decision plus the final settle: far fewer than the pops
    assert len(advances) == sched._n_solves + 1 < sched.events_popped
    assert advances[-1] == "advance"
    under_resolve = advances[:-1]
    assert under_resolve and all(p.endswith("resolve;advance")
                                 for p in under_resolve)
    # nothing but a collector pass nests under a settle
    assert not [p for p in paths if ";advance;" in p or p.startswith("advance;")]
    # decisions sit under the event that triggered them, or at the top when
    # the queue ran dry
    resolves = {p for p in paths if p.split(";")[-1] == "resolve"}
    assert resolves and all(p == "resolve" or (
        p.startswith("event/") and p.count(";") == 1) for p in resolves)
    for child in ("advance", "solve", "placement", "rates", "audit"):
        assert any(p.endswith(";resolve;" + child) or p == "resolve;" + child
                   for p in paths), child
    # in each decision the settle is the first child, before the rates
    for _n, _c, path, t0, dur, _sim, _args in tracer.spans:
        if path.split(";")[-1] != "resolve":
            continue
        names = [p.split(";")[-1] for _m, cat, p, u0, _du, _s, _a
                 in sorted(tracer.spans, key=lambda s: s[3])
                 if cat != "gc" and t0 <= u0 <= t0 + dur
                 and p.startswith(path + ";")
                 and p.count(";") == path.count(";") + 1]
        assert names[0] == "advance" and names.count("advance") == 1, names


def test_search_and_crossover_spans_nest_under_solve():
    """Off the staircase class the jax tier's device phase (``search``) and
    host recovery (``crossover``) are spanned inside the decision's
    ``solve``; the records count the search's iterations."""
    from repro.core import jax_general

    tpu = default_cluster("tpu")
    rows = [(1.0, 1.35, 2.30, 4.16), (1.0, 1.50, 3.38, 2.00),
            (1.0, 1.46, 3.26, 2.00), (1.0, 1.08, 2.14, 2.00)]
    trace = [ev for i, row in enumerate(rows)
             for ev in (_join(0.0, f"t{i}", row), _submit(0.0, f"t{i}", f"j{i}"),
                        _submit(300.0 * (i + 1), f"t{i}", f"k{i}"))]
    sched, rep, tracer, _reg = _run_observed(
        trace, until=4000.0, policy="oef-noncoop", solver_backend="jax",
        cluster=tpu)
    assert rep.fallback_count == 0 and set(rep.solver_backends) == {"jax"}
    paths = [s[2] for s in tracer.spans if s[1] != "gc"]
    searches = [p for p in paths if p.split(";")[-1] == "search"]
    crossovers = [p for p in paths if p.split(";")[-1] == "crossover"]
    assert searches and len(searches) == len(crossovers)
    for p in searches + crossovers:
        assert ";resolve;solve;" in p and ";backend/jax;" in p, p
    fresh = [r for r in sched.metrics.solves if not r.reused]
    assert sum(r.search_iters for r in fresh) \
        == len(searches) * jax_general.SEG_ITERS
    solves = [p for p in paths if p.split(";")[-1] == "solve"]
    assert len(solves) == len(sched.metrics.solves)


def test_quarantine_cycle_is_visible_in_gauge_series():
    trace = [
        _join(0.0, "good", (1.0, 2.0)), _submit(0.0, "good", "g0", work=1e5),
        _join(0.0, "sick", (1.0, 3.0)), _submit(0.0, "sick", "s0", work=1e5),
        _profile(100.0, "sick", (float("nan"), 3.0)),
        _profile(400.0, "sick", (1.0, 3.0)),  # repaired
    ]
    _sched, rep, _tracer, reg = _run_observed(trace, until=800.0)
    acts = [(e["tenant"], e["action"]) for e in rep.quarantine_events]
    assert acts == [("sick", "quarantine"), ("sick", "release")]
    # release only lands after the repairing profile update
    assert rep.quarantine_events[1]["time"] >= 400.0
    series = [(r["t"], r["gauges"]["service.quarantine_size"])
              for r in reg.samples]
    sizes = [s for _t, s in series]
    assert 1 in sizes  # the quarantine window is visible...
    assert sizes[0] == 0 and sizes[-1] == 0  # ...and bounded on both sides
    # the gauge rises only after the corrupt profile and falls after repair
    assert all(s == 0 for t, s in series if t < 100.0)
    assert all(s == 0 for t, s in series if t >= 400.0)


def _chaos_setup(seed=3):
    cluster = default_cluster("paper")
    base = synthetic_trace(6, cluster=cluster, duration_s=3600.0,
                           host_failures_per_hour=2.0, seed=seed)
    engine = ChaosEngine(standard_plan(seed=7), cluster)
    return cluster, engine, engine.chaos_trace(base)


def _view(rep):
    d = dataclasses.asdict(rep)
    d.pop("resolve_latency_ms_mean")
    d.pop("resolve_latency_ms_p95")
    return repr(d)


def test_tracing_does_not_perturb_a_chaos_replay():
    cluster, engine, trace = _chaos_setup()
    sched = OnlineScheduler(cluster, "oef-coop", solver_max_retries=1)
    with engine.installed():
        plain = sched.run(list(trace))
    cluster2, engine2, trace2 = _chaos_setup()
    obs.set_tracer(obs.Tracer())
    obs.set_metrics(obs.MetricsRegistry())
    sched2 = OnlineScheduler(cluster2, "oef-coop", solver_max_retries=1)
    try:
        with engine2.installed():
            traced = sched2.run(list(trace2))
    finally:
        obs.set_tracer(None)
        obs.set_metrics(None)
    assert _view(plain) == _view(traced)


def test_degraded_solves_match_guardrail_instants_exactly():
    """Every degraded solve contains >= 1 cat='guardrail' instant and vice
    versa: informational instants (dispatch/retry, dispatch/fallback,
    dirty/defer) never inflate the count, and no degraded transition goes
    untraced — under the full standard chaos storm."""
    cluster, engine, trace = _chaos_setup()
    tracer = obs.Tracer()
    obs.set_tracer(tracer)
    sched = OnlineScheduler(cluster, "oef-coop", solver_max_retries=1)
    try:
        with engine.installed():
            rep = sched.run(list(trace))
    finally:
        obs.set_tracer(None)
    assert rep.degraded_solves > 0  # the storm must actually degrade solves
    # every decision is a resolve span; those that solved carry a tenant count
    resolves = [(t0, t0 + dur) for (name, _c, _p, t0, dur, _s, args)
                in tracer.spans if name == "resolve" and "tenants" in args]
    assert len(resolves) == rep.n_solves
    guard_ts = [t for (_n, cat, _p, t, _s, _a) in tracer.instants
                if cat == "guardrail"]
    flagged = sum(1 for (a, b) in resolves
                  if any(a <= t <= b for t in guard_ts))
    assert flagged == rep.degraded_solves
    # and none of the guardrail instants fall outside a resolve span
    assert all(any(a <= t <= b for (a, b) in resolves) for t in guard_ts)
