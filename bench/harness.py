"""One run of one cell: set-up, measured window, metrics, correctness.

``measure`` does everything after the device check that ``bench/run.py``
makes; tests call it with a CPU device and small cells.
"""
from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, Optional

import numpy as np

import jax

from repro import obs

from bench import check, devtrace, spans
from bench.adapter import Adapter, warm_solver
from bench.catalog import Cell
from bench.traffic.generator import generate


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the event loop is Python: keep it out
    opts.host_tracer_level = 1
    return opts


class _Window:
    """What the adapter calls at the window's edges: a collected heap, the
    profiler and the marks that put both traces on one clock."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.dir: Optional[str] = None
        self._note = None

    def open(self) -> None:
        gc.collect()
        if self.traced:
            self.dir = tempfile.mkdtemp(prefix="bench-profile-")
            jax.profiler.start_trace(self.dir, profiler_options=_profile_options())
            self._note = jax.profiler.TraceAnnotation(devtrace.WINDOW)
            self._note.__enter__()
        obs.instant(spans.OPEN, "bench")

    def close(self) -> None:
        obs.instant(spans.CLOSE, "bench")
        if self.traced:
            self._note.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def device_trace(self) -> Dict[str, object]:
        files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one profile, found {files}")
        print(f"profile: {os.path.getsize(files[0])} bytes", file=sys.stderr)
        try:
            return devtrace.extract(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _off_tier(decisions) -> int:
    n = 0
    for d in decisions:
        r = d.record
        if r is not None and (r.backend != "jax" or r.fallback_reason
                              or r.degraded):
            n += 1
    return n


def replay(cell: Cell, *, seed: int, seconds: float, window: _Window,
           tracer=None):
    """Set-up and measured window of one run: the trace from ``seed``, the
    warm programs, and the replay up to the window's close. Returns the
    adapter, the trace and the set-up's phases (host-clock times and the
    buckets warmed)."""
    config, traffic = cell.config, cell.traffic
    phases: Dict[str, object] = {"t_harness": time.perf_counter()}
    events = generate(config, traffic, seed)
    phases["t_trace"] = time.perf_counter()
    phases["warmed"] = warm_solver(config)
    phases["t_warm"] = time.perf_counter()
    adapter = Adapter(config, warmup_s=float(traffic["warmup_s"]),
                      seconds=seconds, on_open=window.open,
                      on_close=window.close)
    previous = obs.set_tracer(tracer)
    jax.monitoring.register_event_duration_secs_listener(
        adapter.on_monitoring_event)
    try:
        adapter.run(events)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            adapter.on_monitoring_event)
        obs.set_tracer(previous)
    return adapter, events, phases


def gates(adapter: Adapter, ref_trace: check.Trace, config,
          numbers: Dict[str, float]) -> Dict[str, int]:
    """The exact checks every run must read 0 on."""
    solved = [d for d in adapter.decisions if d.record is not None]
    return {
        "compiles_in_window": adapter.compiles_in_window,
        "off_tier_decisions": _off_tier(adapter.decisions),
        "grant_violations": sum(check.grant_violations(d, ref_trace, config)
                                for d in solved),
        "decisions_missing": 0 if solved else 1,
        "shape_mismatch": int(numbers.pop("shape_mismatch", 0)),
        "replay_errors": 0 if adapter.error is None else 1,
    }


def measure(cell: Cell, *, seed: int, seconds: float, trace: bool,
            device, t_start: float, log=sys.stderr) -> Dict[str, object]:
    """Run ``cell`` once and return the result line's object."""
    config = cell.config
    window = _Window(trace)
    tracer = obs.Tracer() if trace else None
    adapter, events, phases = replay(cell, seed=seed, seconds=seconds,
                                     window=window, tracer=tracer)
    trace_times = np.asarray([ev.time for ev in events])
    memory_peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))

    decisions = adapter.decisions
    window_s = adapter.t_close - adapter.t_open
    ctx = types.SimpleNamespace(
        decisions=decisions, window_s=window_s,
        setup_s=adapter.t_open - t_start,
        world_events=adapter.world_events(trace_times),
        spans=None, device=None)
    dev_info: Dict[str, object] = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        chrome = tracer.to_chrome()
        ctx.spans = spans.window_spans(chrome)
        segments = spans.leaf_segments(ctx.spans, window_s)
        profile = window.device_trace()
        if device.platform == "tpu":
            ctx.device = devtrace.summarize(profile, segments)
            dev_info["busy_s"] = ctx.device.busy_s
            dev_info["window_s"] = ctx.device.window_s
            breakdown = {"device_ops": ctx.device.ops,
                         "idle_gaps": ctx.device.idle}
        host = sorted(spans.self_times(ctx.spans, window_s).items(),
                      key=lambda kv: -kv[1])
        print("host self time in window (s): " + json.dumps(host[:12]), file=log)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    # -- correctness: after the window, the memory reading and the replay --
    ref_trace = check.Trace(events)
    numbers, _ = check.compare(decisions, ref_trace, config, seed)
    exact = gates(adapter, ref_trace, config, numbers)
    limits = config["limits"]
    checks: Dict[str, Dict[str, float]] = {}
    for name in sorted(limits):
        checks[name] = {"value": numbers.get(name, float("inf")),
                        "limit": float(limits[name])}
    for name, value in exact.items():
        checks[name] = {"value": value, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    info = {
        "warmed_buckets": phases["warmed"], "decisions": len(decisions),
        "solved": sum(1 for d in decisions if d.record is not None),
        "reused": sum(1 for d in decisions if d.record and d.record.reused),
        "world_events": ctx.world_events, "window_s": window_s,
        "sim_window": [adapter.sim_open, adapter.sim_close],
        "trace_exhausted": adapter.exhausted,
        "setup_s": ctx.setup_s,
        # set-up by phase: start to harness (imports, device), trace
        # generation, compile or cache load, warm-up replay
        "setup_phases_s": [phases["t_harness"] - t_start,
                           phases["t_trace"] - phases["t_harness"],
                           phases["t_warm"] - phases["t_trace"],
                           adapter.t_open - phases["t_warm"]],
        "events_per_s": ctx.world_events / window_s if window_s > 0 else None,
        "unjudged": {k: v for k, v in numbers.items() if k not in limits},
    }
    print("window: " + json.dumps(info), file=log)
    out: Dict[str, object] = {
        "correct": bool(correct), "attempted": len(decisions),
        "failed": exact["off_tier_decisions"], "metrics": metrics,
        "device": dev_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
