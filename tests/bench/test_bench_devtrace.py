"""The profiler-trace reduction: busy union, idle share and idle time
attributed to what the host was doing."""
import json
import os

import pytest

from bench import devtrace, spans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "tpu_profile.json")


def _trace(ops, window=(1000.0, 11000.0)):
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[devtrace.WINDOW, window[0],
                                           window[1] - window[0]]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": devtrace.OPS_LINE, "events": ops}]},
    ]}


def test_busy_union_clips_and_merges():
    ops = [["jit_a(1)", 500.0, 1000.0],    # clipped to [1000, 1500]
           ["jit_b(2)", 1200.0, 1000.0],   # overlaps: union to 2200
           ["jit_c(3)", 5000.0, 1000.0],
           ["jit_a(1)", 10500.0, 1000.0]]  # clipped to [10500, 11000]
    s = devtrace.summarize(_trace(ops))
    assert s.window_s == pytest.approx(1e-5)
    assert s.busy_s == pytest.approx((1200 + 1000 + 500) / 1e9)
    assert dict((n, v) for n, v in s.ops) == pytest.approx(
        {"jit_a": 1000e-9, "jit_b": 1000e-9, "jit_c": 1000e-9})


def test_idle_time_goes_to_the_innermost_host_span():
    ops = [["jit_a(1)", 3000.0, 2000.0]]   # busy 2 us .. 4 us of the window
    # window clock in seconds: solve over [1 us, 4 us], loop elsewhere
    segs = [(0.0, 1e-6, spans.UNSPANNED), (1e-6, 4e-6, "solve"),
            (4e-6, 1e-5, spans.UNSPANNED)]
    s = devtrace.summarize(_trace(ops), segs)
    idle = dict((n, v) for n, v in s.idle)
    assert idle["solve"] == pytest.approx(1e-6)
    assert idle[spans.UNSPANNED] == pytest.approx(7e-6)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_no_window_or_no_device_is_an_error():
    t = _trace([])
    t["planes"][0]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        devtrace.summarize(t)
    t = _trace([])
    t["planes"] = t["planes"][:1]
    with pytest.raises(ValueError):
        devtrace.summarize(t)


def test_recorded_tpu_profile():
    """A profile recorded on a TPU v5e around three solves at 64 tenants,
    trimmed to the window and the programs: the reduction agrees with a plain
    sweep over its programs."""
    with open(FIXTURE) as f:
        trace = json.load(f)
    w0, w1 = devtrace.window_ns(trace)
    s = devtrace.summarize(trace)
    ops = [(max(a, w0), min(a + d, w1))
           for p in devtrace.device_planes(trace) for line in p["lines"]
           if line["name"] == devtrace.OPS_LINE for _, a, d in line["events"]]
    edges = sorted({x for ab in ops for x in ab if ab[1] > ab[0]})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(lo <= a and b <= hi for lo, hi in ops))
    assert s.busy_s == pytest.approx(busy / 1e9)
    assert 0.0 < s.busy_s < s.window_s
    assert [name for name, _ in s.ops] == ["jit__solve_padded"]


def test_extract_reads_a_profiler_file(tmp_path):
    """extract() on a profile this process records on the CPU."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    trace = devtrace.extract(str(path))
    w0, w1 = devtrace.window_ns(trace)
    assert w1 > w0
    assert all(isinstance(e[1], float) for p in trace["planes"]
               for line in p["lines"] for e in line["events"])
