"""With the timed path broken underneath, a run's ``correct`` comes out
false; the float32 control fails the configuration's limits that the
program passes. Small cells on the CPU, the harness's look for a chip
skipped."""
import dataclasses
import io
import time

import jax
import numpy as np
import pytest

from bench import control, harness
from repro.core import oef
from repro.core.placement import RoundingPlacer

from benchcells import tiny_cell

CELL = "noncoop1024-steady"


def _run(seed=5):
    out = harness.measure(tiny_cell(CELL), seed=seed, seconds=1.0,
                          trace=False, device=jax.devices()[0],
                          t_start=time.perf_counter(), log=io.StringIO())
    return out


def _with_X(alloc, X):
    return dataclasses.replace(alloc, X=X)


def answer_altered(orig):
    def solve(W, m, **kw):
        alloc = orig(W, m, **kw)
        X = alloc.X.copy()
        X[0] *= 1.001
        return _with_X(alloc, X)
    return solve


def state_unchanged(orig):
    def solve(W, m, prev=None, **kw):
        return prev if prev is not None else orig(W, m, prev=prev, **kw)
    return solve


def half_left_out(orig):
    def solve(W, m, prev=None, **kw):
        h = max(1, W.shape[0] // 2)
        alloc = orig(W[:h], m, prev=None, **kw)
        X = np.zeros_like(W, dtype=np.float64)
        X[:h] = alloc.X
        return dataclasses.replace(alloc, X=X, W=W)
    return solve


@pytest.mark.parametrize("fault", [answer_altered, state_unchanged, half_left_out])
def test_broken_solve_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(oef, "solve_incremental", fault(oef.solve_incremental))
    out = _run()
    assert not out["correct"], out["checks"]


def test_altered_grant_is_not_correct(monkeypatch):
    place = RoundingPlacer.place

    def broken(self, *args, **kw):
        res = place(self, *args, **kw)
        for job_id, placed in sorted(res.assignments.items())[:1]:
            j, h, c = placed[0]
            res.assignments[job_id] = [(j, h, c + 1)] + list(placed[1:])
        return res

    monkeypatch.setattr(RoundingPlacer, "place", broken)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["grant_violations"]["value"] > 0


def test_control_fails_the_limits_the_program_meets():
    c = tiny_cell(CELL)
    limits = c.config["limits"]
    assert limits, "the configuration states no limits"
    rows = control.readings(c, [3, 4, 2**31 + 9], 1.0)
    for r in rows:
        assert all(v == 0 for v in r["gates"].values()), r["gates"]
        assert all(r["program"][k] <= v for k, v in limits.items()), r
        assert any(r["control"][k] > v for k, v in limits.items()), r
