"""The span reduction on a small repro.obs Chrome export."""
import pytest

from bench import spans
from repro.obs import Tracer


def _export():
    """A hand-made repro.obs export: a window from 1.0 s to 2.0 s holding
    one decision (resolve > solve, placement) and one event span; one
    decision before the window and one that crosses its close."""
    tr = Tracer()
    tr.spans = [
        ("resolve", "service", "resolve", 0.2, 0.3, None, None),
        ("resolve", "service", "resolve", 1.1, 0.4, None, None),
        ("solve", "service", "resolve;solve", 1.1, 0.1, None, None),
        ("placement", "service", "resolve;placement", 1.25, 0.2, None, None),
        ("event/job_submit", "service", "event/job_submit", 1.6, 0.05, None, None),
        ("resolve", "service", "resolve", 1.9, 0.3, None, None),
    ]
    tr.instants = [(spans.OPEN, "bench", "", 1.0, None, None),
                   (spans.CLOSE, "bench", "", 2.0, None, None)]
    tr._t_zero = 0.0
    return tr.to_chrome()


def test_window_spans_and_depth():
    doc = _export()
    assert spans.window(doc) == pytest.approx((1.0, 2.0))
    got = spans.window_spans(doc)
    assert [(s.name, s.depth) for s in got] == [
        ("resolve", 0), ("solve", 1), ("placement", 1), ("event/job_submit", 0)]
    assert got[0].t0 == pytest.approx(0.1) and got[0].dur == pytest.approx(0.4)
    assert spans.durations(got, "resolve", depth=0) == pytest.approx([0.4])


def test_self_times_cover_the_window():
    got = spans.window_spans(_export())
    self_t = spans.self_times(got, 1.0)
    assert sum(self_t.values()) == pytest.approx(1.0)
    assert self_t["solve"] == pytest.approx(0.1)
    assert self_t["placement"] == pytest.approx(0.2)
    assert self_t["resolve"] == pytest.approx(0.1)  # 0.4 less its children
    assert self_t["event/job_submit"] == pytest.approx(0.05)
    assert self_t[spans.UNSPANNED] == pytest.approx(0.55)


def test_missing_marks_are_an_error():
    doc = _export()
    doc["traceEvents"] = [e for e in doc["traceEvents"] if e.get("ph") != "i"]
    with pytest.raises(ValueError):
        spans.window(doc)
