"""The benchmark's copied generator replays what the program's generator
draws, and the seed changes the draw."""
import json

import pytest

from bench.catalog import load_cell
from bench.traffic.generator import GENERATOR_KEYS, JobType, generate
from bench.traffic.generator import synthetic_trace as synthetic_trace_copy
from repro.core.types import ClusterSpec
from repro.service.traces import default_job_types, synthetic_trace


def _program_trace(config, traffic, seed):
    cluster = ClusterSpec(types=tuple(config["device_types"]),
                          m=tuple(config["devices_per_type"]))
    return synthetic_trace(
        config["tenants"], job_types=default_job_types("paper"),
        cluster=cluster, duration_s=traffic["duration_s"],
        mean_interarrival_s=traffic["mean_interarrival_s"],
        jobs_at_join=traffic["jobs_at_join"], mean_work_s=traffic["mean_work_s"],
        workers_choices=traffic["workers_choices"],
        weight_choices=traffic["weight_choices"],
        join_spread_s=traffic["join_spread_s"],
        host_failures_per_hour=traffic["host_failures_per_hour"],
        mean_outage_s=traffic["mean_outage_s"],
        devices_per_host=config["devices_per_host"], seed=seed)


@pytest.mark.parametrize("traffic_over", [{}, {"host_failures_per_hour": 2.0}])
def test_generator_matches_program_generator(traffic_over):
    cell = load_cell("noncoop1024-steady")
    config = dict(cell.config, tenants=24)
    traffic = dict(cell.traffic, duration_s=6000.0, **traffic_over)
    seed = 2**31 + 12345
    ours = synthetic_trace_copy(
        config["tenants"],
        job_types=[JobType(**jt) for jt in config["job_types"]],
        device_counts=config["devices_per_type"],
        devices_per_host=config["devices_per_host"], seed=seed,
        **{k: traffic[k] for k in GENERATOR_KEYS})
    theirs = _program_trace(config, traffic, seed)
    assert len(ours) > 100
    assert ours == theirs


def _work(events):
    return sorted((ev.time, ev.kind.value, json.dumps(ev.payload, sort_keys=True))
                  for ev in events)


def test_seed_permutes_tenants_over_the_same_work():
    cell = load_cell("noncoop1024-steady")
    config = dict(cell.config, tenants=40)
    traffic = dict(cell.traffic, duration_s=6000.0, host_failures_per_hour=2.0)
    a = generate(config, traffic, 2**31 + 1)
    assert a == generate(config, traffic, 2**31 + 1)
    b = generate(config, traffic, 2**31 + 2)
    assert a != b
    assert _work(a) == _work(b)
    times = [ev.time for ev in b]
    assert times == sorted(times)
    # job ids follow their tenant
    assert all(ev.job_id.startswith(ev.tenant + "-j") for ev in b if ev.job_id)


def test_catalog_is_the_paper_catalog():
    cell = load_cell("noncoop1024-steady")
    ours = [(jt["name"], tuple(jt["speedup"]), jt["min_demand"])
            for jt in cell.config["job_types"]]
    theirs = [(jt.name, tuple(jt.speedup), jt.min_demand)
              for jt in default_job_types("paper")]
    assert ours == theirs


def test_base_seed_changes_the_work():
    """The control's readings draw each seed's work from that seed."""
    cell = load_cell("noncoop1024-steady")
    config = dict(cell.config, tenants=40)
    traffic = dict(cell.traffic, duration_s=6000.0)
    a = generate(config, dict(traffic, base_seed=2**31 + 3), 2**31 + 3)
    b = generate(config, dict(traffic, base_seed=2**31 + 4), 2**31 + 3)
    assert _work(a) != _work(b)
