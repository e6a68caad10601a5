"""Beyond-paper extensions: weighted OEF (§4.2.3) and job-level elastic OEF
(the §8 conclusion direction)."""
from __future__ import annotations

import numpy as np

from repro.core import oef
from repro.core.elastic import ElasticJob, ElasticTenant, rigid_equivalent, solve_elastic_coop
from repro.core.types import ClusterSpec, JobTypeProfile, Tenant
from .common import timed


def run() -> list:
    rows = []

    # weighted OEF: pi=2 tenant gets exactly 2x throughput (non-coop)
    cluster = ClusterSpec(types=("slow", "fast"), m=(8, 8))
    t1 = Tenant("lo", (JobTypeProfile("a", (1.0, 2.0)),), weight=1.0)
    t2 = Tenant("hi", (JobTypeProfile("b", (1.0, 5.0)),), weight=2.0)
    ta, us = timed(lambda: oef.evaluate_tenants([t1, t2], cluster, mode="noncooperative"))
    tp1 = ta.tenant_throughput("lo", {"a": np.array([1.0, 2.0])})
    tp2 = ta.tenant_throughput("hi", {"b": np.array([1.0, 5.0])})
    rows.append(("ext/weighted_oef", us,
                 f"ratio={tp2/tp1:.3f} (target 2.0) exact={'Y' if abs(tp2/tp1-2)<1e-5 else 'N'}"))

    # elastic job-level OEF vs scaling-unaware allocation
    rng = np.random.default_rng(3)
    m = np.array([6.0, 6.0, 6.0])
    tenants = []
    for i in range(4):
        speed = tuple(np.cumsum(rng.uniform(0.3, 1.0, 3)))
        tenants.append(ElasticTenant(
            f"u{i}", (ElasticJob(f"j{i}", speed, max_workers=6,
                                 alpha=float(rng.uniform(0.6, 0.9))),)))
    ea, us2 = timed(lambda: solve_elastic_coop(tenants, m, envy_free=False))
    rigid = rigid_equivalent(tenants, m)
    gain = (ea.total_utility / max(rigid, 1e-9) - 1) * 100
    rows.append(("ext/elastic_vs_rigid", us2,
                 f"elastic={ea.total_utility:.2f} rigid={rigid:.2f} gain={gain:+.1f}%"))

    ef, us3 = timed(lambda: solve_elastic_coop(tenants, m, envy_free=True))
    cost = (1 - ef.total_utility / ea.total_utility) * 100
    rows.append(("ext/elastic_ef_price", us3,
                 f"EF version {ef.total_utility:.2f} (fairness price {cost:.1f}%)"))
    return rows
