"""The one benchmark module that touches the scheduler's internals.

``OnlineScheduler.run`` has no wall-clock stop and no public decision timer,
so this module supplies both on the instance it drives, and nothing else:

- it wraps the instance's decision entry, ``_resolve`` (solve, rounding,
  packing, rates and finish pushes), with a host-clock timer;
- after each decision it reads what the decision left behind, by reference
  and without copying: the allocation (``_prev_alloc.X``), the tenants it
  covered (``last_estimate``, in solve order), the grants
  (``_prev_assignments``) and the solve record (``metrics.solves``);
- it opens the measured window after the first decision at or past the
  traffic's warm-up sim time, and ends the replay at the first decision
  boundary past the window's length by raising :class:`WindowClosed` out of
  ``run``.

Once the program has a public decision timer and a wall-clock stop, a later
change to the benchmark moves this module onto them.
"""
from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.types import ClusterSpec
from repro.service.__main__ import build_parser, make_scheduler
from repro.service.events import Event

#: jax.monitoring events that mark a trace or a compile.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")

#: padding buckets warmed before the replay: the tenant count's bucket and
#: the one below it. Past the join burst the steady mix keeps most tenants
#: active; a bucket the replay reaches unwarmed compiles inside the window,
#: which fails the run's ``compiles_in_window`` gate.
WARM_BUCKETS = 2


class WindowClosed(Exception):
    """Raised out of ``run`` at the decision boundary that ends the window."""


@dataclasses.dataclass
class Decision:
    sim_t: float
    wall_ms: float
    tenants: Tuple[str, ...]
    X: Optional[np.ndarray]
    assignments: Optional[Dict[str, list]]
    record: Optional[object]  # the SolveRecord, None when nobody was active


class Adapter:
    """Builds the scheduler as ``python -m repro.service --backend jax``
    builds it, and drives one replay with a measured window."""

    def __init__(self, config: Mapping, *, warmup_s: float, seconds: float,
                 on_open: Callable[[], None] = lambda: None,
                 on_close: Callable[[], None] = lambda: None,
                 clock: Callable[[], float] = time.perf_counter):
        argv = ["--policy", str(config["policy"]),
                "--backend", str(config["backend"]),
                "--resolve-interval", repr(float(config["resolve_interval_s"])),
                "--audit-every", str(int(config["audit_every"]))]
        if not config["guardrails"]:
            argv.append("--no-guardrails")
        cluster = ClusterSpec(types=tuple(config["device_types"]),
                              m=tuple(int(x) for x in config["devices_per_type"]))
        self.sched = make_scheduler(build_parser().parse_args(argv), cluster)
        stated = {"devices_per_host": self.sched.devices_per_host,
                  "contention_penalty": self.sched.contention_penalty,
                  "migration_overhead_s": self.sched.migration_overhead_s}
        for key, value in stated.items():
            if float(config[key]) != float(value):
                raise ValueError(f"the scheduler runs {key}={value}, the "
                                 f"configuration states {config[key]}")
        self.warmup_s = float(warmup_s)
        self.seconds = float(seconds)
        self.on_open, self.on_close, self.clock = on_open, on_close, clock
        self.decisions: List[Decision] = []
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.sim_open = self.sim_close = 0.0
        self.finished_open = self.finished_close = 0
        self.compiles_in_window = 0
        self.exhausted = False
        self.error: Optional[str] = None
        self._decide = self.sched._resolve
        self.sched._resolve = self._timed_decision

    # -- compile counter (jax.monitoring) ----------------------------------
    def on_monitoring_event(self, event: str, duration: float, **_kw) -> None:
        if event in COMPILE_EVENTS and self.t_open is not None \
                and self.t_close is None:
            self.compiles_in_window += 1

    # -- the timed decision -------------------------------------------------
    def _timed_decision(self, now, queue) -> None:
        sched = self.sched
        n_records = len(sched.metrics.solves)
        t0 = self.clock()
        self._decide(now, queue)
        t1 = self.clock()
        if self.t_open is None:
            if now >= self.warmup_s:
                self._open(now)
            return
        solved = len(sched.metrics.solves) > n_records
        alloc = sched._prev_alloc
        self.decisions.append(Decision(
            sim_t=now, wall_ms=(t1 - t0) * 1e3,
            tenants=tuple(sched.last_estimate),
            X=alloc.X if solved and alloc is not None else None,
            assignments=sched._prev_assignments if solved else None,
            record=sched.metrics.solves[-1] if solved else None))
        if t1 - self.t_open >= self.seconds:
            self._close(now, t1)
            raise WindowClosed

    def _open(self, now: float) -> None:
        self.on_open()
        self.sim_open = now
        self.finished_open = len(self.sched.metrics.jcts)
        self.t_open = self.clock()

    def _close(self, now: float, t_close: float) -> None:
        self.t_close = t_close
        self.sim_close = now
        self.finished_close = len(self.sched.metrics.jcts)
        self.on_close()

    # -- the replay ---------------------------------------------------------
    def run(self, events: Sequence[Event]) -> None:
        """Replay ``events`` until the window closes. A replay that drains
        the trace first closes the window where it ends; one that raises
        closes it there and keeps the traceback in ``error``."""
        try:
            self.sched.run(events)
        except WindowClosed:
            return
        except Exception:  # the program failed: report it, not crash on it
            self.error = traceback.format_exc()
            print(self.error, file=sys.stderr)
            if self.t_open is None:
                self._open(self.sched._clock)
            self._close(self.sched._clock, self.clock())
            return
        if self.t_open is None:
            raise RuntimeError(
                f"the replay ended before sim time {self.warmup_s}: the "
                "traffic's horizon is shorter than its warm-up")
        self.exhausted = True
        self._close(self.sched._clock, self.clock())

    def world_events(self, trace_times: np.ndarray) -> int:
        """World events handled in the window: trace events in
        (sim_open, sim_close] plus the jobs that really finished. Every
        decision runs after all events at or before its sim time, so the
        window's boundaries split the trace cleanly."""
        lo = int(np.searchsorted(trace_times, self.sim_open, side="right"))
        hi = int(np.searchsorted(trace_times, self.sim_close, side="right"))
        return hi - lo + self.finished_close - self.finished_open


def warm_solver(config: Mapping) -> List[int]:
    """Compile the solve programs the cell reaches, through the tier's
    public entry point, before the replay starts.

    ``oef-noncoop`` pads the active tenants to a power-of-two bucket and has a
    cold and a warm-started program per bucket: warm both for the
    :data:`WARM_BUCKETS` buckets at and below the configuration's tenant
    count. Returns the buckets warmed.
    """
    from repro.core import jax_solve

    if config["policy"] != "oef-noncoop":
        raise ValueError(f"no warm-up for policy {config['policy']!r}")
    k = len(config["device_types"])
    m = np.asarray(config["devices_per_type"], dtype=np.float64)
    top = jax_solve.bucket(int(config["tenants"]))
    buckets = sorted({max(jax_solve.bucket(1), top >> i)
                      for i in range(WARM_BUCKETS)})
    # a consistently ordered (Monge) instance: the tier's own class
    a = 1.0 + np.arange(1, top + 1) / top
    c = np.linspace(0.0, 1.0, k)
    for n in buckets:
        W = np.power(a[:n, None], c[None, :])
        tau, _ = jax_solve.solve_noncoop_fast_jax(W, m)
        jax_solve.solve_noncoop_fast_jax(W, m, tau_hint=tau * 0.99)
    return buckets
