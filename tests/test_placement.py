"""Rounding placer (§4.3) properties: long-run convergence, capacity safety,
min-demand gating with redistribution, single-type preference."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.placement import JobRequest, RoundingPlacer


@given(st.integers(0, 10_000), st.integers(2, 6), st.integers(2, 4))
@settings(max_examples=25, deadline=None)
def test_rounding_long_run_convergence(seed, n, k):
    """Time-averaged integer grants converge to the fractional ideal."""
    rng = np.random.default_rng(seed)
    m = rng.integers(2, 12, k)
    # random fractional allocation with column sums <= m
    X = rng.uniform(0, 1, (n, k))
    X = X / X.sum(axis=0, keepdims=True) * (m * rng.uniform(0.6, 1.0, k))
    placer = RoundingPlacer(n, m)
    grants = []
    for _ in range(400):
        grants.append(placer.round_shares(X.copy()))
    avg = np.mean(grants, axis=0)
    assert np.max(np.abs(avg - X)) < 0.08, f"avg grants {avg} diverge from ideal {X}"


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_rounding_capacity_never_exceeded(seed):
    rng = np.random.default_rng(seed)
    n, k = int(rng.integers(2, 8)), int(rng.integers(2, 4))
    m = rng.integers(1, 10, k)
    placer = RoundingPlacer(n, m)
    for _ in range(80):
        X = rng.uniform(0, 1, (n, k))
        X = X / np.maximum(X.sum(axis=0, keepdims=True), 1e-9) * m
        real = placer.round_shares(X)
        assert np.all(real >= 0)
        assert np.all(real.sum(axis=0) <= m)


def test_min_demand_gating_and_redistribution():
    m = [4, 4]
    placer = RoundingPlacer(3, m)
    X = np.array([[1.6, 0.0], [1.4, 0.0], [1.0, 4.0]])
    real = placer.round_shares(X, min_demand=np.array([4, 4, 1]))
    # users 0/1 need 4 devices minimum -> gated to zero; their devices are
    # redistributed to user 2 (min demand 1)
    assert real[0].sum() == 0 and real[1].sum() == 0
    assert real[2].sum() >= 5
    assert real.sum(axis=0)[0] <= 4 and real.sum(axis=0)[1] <= 4


def test_gated_user_eventually_runs():
    """Deviation accumulation guarantees a starved tenant gets a turn."""
    m = [4]
    placer = RoundingPlacer(2, m)
    X = np.array([[1.0], [3.0]])
    got_turn = False
    for t in range(12):
        real = placer.round_shares(X.copy(), min_demand=np.array([2, 1]))
        if real[0, 0] >= 2:
            got_turn = True
    assert got_turn, "min-demand user starved despite deviation accumulation"


def test_single_type_preference():
    placer = RoundingPlacer(1, [4, 4], devices_per_host=4)
    real = np.array([[2, 4]])
    jobs = [JobRequest(user=0, job_id="j0", workers=4)]
    res = placer.place(real, jobs)
    types = {j for j, _, _ in res.assignments["j0"]}
    assert len(types) == 1, "job split across types despite a single-type fit"
    assert res.cross_type_workers == 0


def test_cross_type_fallback_when_unavoidable():
    placer = RoundingPlacer(1, [2, 2], devices_per_host=4)
    real = np.array([[2, 2]])
    jobs = [JobRequest(user=0, job_id="j0", workers=4)]
    res = placer.place(real, jobs)
    assert "j0" in res.assignments
    assert res.cross_type_workers == 4  # must straddle both types


def test_naive_placement_worse_or_equal_locality():
    rng = np.random.default_rng(0)
    placer = RoundingPlacer(4, [8, 8], devices_per_host=4)
    real = np.array([[2, 2], [2, 2], [2, 2], [2, 2]])
    jobs = [JobRequest(user=u, job_id=f"j{u}-{i}", workers=w)
            for u in range(4) for i, w in enumerate((4,))]
    opt = placer.place(real, jobs)
    nai = placer.place(real, jobs, naive=True)
    assert nai.cross_type_workers >= opt.cross_type_workers


def test_sticky_placement_reuses_assignment():
    placer = RoundingPlacer(1, [8], devices_per_host=4)
    real = np.array([[4]])
    jobs = [JobRequest(user=0, job_id="j0", workers=4)]
    first = placer.place(real, jobs)
    second = placer.place(real, jobs, prev=first.assignments)
    assert second.assignments["j0"] == first.assignments["j0"]


def test_round_shares_capacity_aware():
    """round_shares(capacity=) budgets against post-failure capacity."""
    placer = RoundingPlacer(2, [8])
    X = np.array([[2.0], [2.0]])
    real = placer.round_shares(X, capacity=np.array([4]))
    assert real.sum() <= 4


def test_place_raises_on_post_failure_shortfall():
    """Integer grants beyond surviving-slot capacity must fail loudly with a
    per-type shortfall message, not silently strand workers."""
    placer = RoundingPlacer(1, [8], devices_per_host=4)
    real = np.array([[8]])
    jobs = [JobRequest(user=0, job_id="j0", workers=8)]
    with pytest.raises(ValueError, match=r"type 0: granted 8 > 4 surviving"):
        placer.place(real, jobs, down_hosts={(0, 0)})
    # the error names the fix: round against the effective capacity
    real_ok = placer.round_shares(np.array([[8.0]]), capacity=np.array([4]))
    res = placer.place(real_ok, [JobRequest(user=0, job_id="j0", workers=4)],
                       down_hosts={(0, 0)})
    assert "j0" in res.assignments


# -- the bucketed packer against the loop it replaced ------------------------


def oracle_place(placer, real, jobs, *, naive=False, prev=None, down_hosts=None):
    """``RoundingPlacer.place`` as it was before the free-slot buckets: an
    argsort and list scans over every host of a type per job, with the tie
    order among equally free hosts made stable (lowest host index)."""
    from repro.core.placement import PlacementResult
    free = []  # free[j] = array of free slots per host of type j
    for j in range(placer.k):
        n_hosts = placer.hosts_per_type[j]
        slots = np.full(n_hosts, placer.devices_per_host, dtype=np.int64)
        # cap total slots at m_j
        extra = slots.sum() - placer.m[j]
        if extra > 0:
            slots[-1] -= extra
        if down_hosts:
            for h in range(n_hosts):
                if (j, h) in down_hosts:
                    slots[h] = 0
        free.append(slots)
    shortfall = {
        j: (int(real[:, j].sum()), int(free[j].sum()))
        for j in range(placer.k) if int(real[:, j].sum()) > int(free[j].sum())
    }
    if shortfall:
        detail = ", ".join(
            f"type {j}: granted {g} > {a} surviving slots (short {g - a})"
            for j, (g, a) in sorted(shortfall.items()))
        raise ValueError(
            f"integer grants exceed post-failure capacity — {detail}; "
            f"round_shares() must be given the effective capacity "
            f"(down hosts: {sorted(down_hosts) if down_hosts else []})")
    user_budget = real.copy().astype(np.int64)

    if naive:
        order = sorted(jobs, key=lambda r: r.job_id)  # FIFO, no priority
        type_order = list(range(placer.k))  # slowest types first
    else:
        order = sorted(jobs, key=lambda r: (-r.workers, -r.starvation, r.job_id))
        type_order = list(range(placer.k - 1, -1, -1))  # fastest first
    assignments: Dict[str, List[Tuple[int, int, int]]] = {}
    cross_host = 0
    cross_type = 0
    unplaced: List[str] = []
    # placement stickiness: keep a job where it already runs if the new
    # grant still covers it — avoids gratuitous checkpoint/migrate cycles
    # when the LP returns a different-but-equivalent optimum next round.
    if prev and not naive:
        for job in order:
            pa = prev.get(job.job_id)
            if not pa:
                continue
            need = sum(c for _, _, c in pa)
            if need != job.workers:
                continue
            if all(user_budget[job.user, j] >= 0 for j, _, _ in pa):
                ok = all(free[j][h] >= c for j, h, c in pa) and all(
                    user_budget[job.user, j] >= sum(c2 for j2, _, c2 in pa if j2 == j)
                    for j in sorted({j for j, _, _ in pa}))
                if ok:
                    for j, h, c in pa:
                        free[j][h] -= c
                        user_budget[job.user, j] -= c
                    assignments[job.job_id] = list(pa)
                    types_used = {j for j, _, _ in pa}
                    hosts_used = {(j, h) for j, h, _ in pa}
                    if len(hosts_used) > 1:
                        cross_host += 1
                    if len(types_used) > 1:
                        cross_type += job.workers
    for job in order:
        if job.job_id in assignments:
            continue
        need = job.workers
        if user_budget[job.user].sum() < need:
            unplaced.append(job.job_id)
            continue
        placed: List[Tuple[int, int, int]] = []
        types_used = set()
        hosts_used = set()
        job_type_order = type_order
        if not naive:
            # straggler avoidance (§4.4/§6.3.1): place the whole job in a
            # single device type when any granted type can hold it —
            # fastest such type first; only mix types as a last resort.
            whole_types = [j for j in type_order
                           if int(user_budget[job.user, j]) >= need
                           and int(free[j].sum()) >= need]
            if whole_types:
                job_type_order = whole_types + [j for j in type_order
                                                if j not in whole_types]
        for j in job_type_order:
            if need <= 0:
                break
            avail_j = int(user_budget[job.user, j])
            if avail_j <= 0:
                continue
            if naive:
                host_seq = list(range(len(free[j])))  # first-fit, no packing
            else:
                # best-fit: host with the fewest free slots that still fits
                host_order = np.argsort(free[j], kind="stable")
                # first try to fit the whole job in one host
                whole = [h for h in host_order if free[j][h] >= min(need, avail_j)]
                host_seq = (whole + [h for h in host_order if h not in whole]) if whole else list(host_order)
            for h in host_seq:
                if need <= 0 or avail_j <= 0:
                    break
                take = int(min(free[j][h], avail_j, need))
                if take <= 0:
                    continue
                free[j][h] -= take
                avail_j -= take
                user_budget[job.user, j] -= take
                need -= take
                placed.append((j, int(h), take))
                types_used.add(j)
                hosts_used.add((j, int(h)))
        if need > 0:  # rollback
            for j, h, cnt in placed:
                free[j][h] += cnt
                user_budget[job.user, j] += cnt
            unplaced.append(job.job_id)
            continue
        assignments[job.job_id] = placed
        if len(hosts_used) > 1:
            cross_host += 1
        if len(types_used) > 1:
            cross_type += job.workers
    return PlacementResult(
        real=real,
        assignments=assignments,
        cross_host_jobs=cross_host,
        cross_type_workers=cross_type,
        unplaced_jobs=unplaced,
    )


def _fleet(rng):
    """A random fleet: 1-4 types, sizes that are mostly not a multiple of
    the host size, some hosts down."""
    k = int(rng.integers(1, 5))
    dph = int(rng.choice([2, 4, 4, 8]))
    m = [int(rng.integers(dph, 12 * dph)) for _ in range(k)]
    hosts = [-(-mj // dph) for mj in m]
    down = {(j, h) for j in range(k) for h in range(hosts[j])
            if rng.random() < 0.15}
    return m, dph, down


def _surviving(m, dph, down):
    return np.array([mj - sum(min(dph, mj - h * dph) for (t, h) in down if t == j)
                     for j, mj in enumerate(m)])


def _grants(rng, n, cap):
    """Integer grants per (user, type) that fit ``cap``, often partial."""
    real = np.zeros((n, len(cap)), dtype=np.int64)
    for j, c in enumerate(cap):
        fill = int(c * rng.uniform(0.4, 1.0))
        real[:, j] = rng.multinomial(fill, rng.dirichlet(np.ones(n)))
    return real


def _jobs(rng, n, tag):
    return [JobRequest(user=u, job_id=f"{tag}{u}-{i}",
                       workers=int(rng.choice([1, 1, 2, 3, 4, 5, 8])),
                       starvation=float(rng.integers(0, 3)))
            for u in range(n) for i in range(int(rng.integers(0, 7)))]


def _rounds(seed):
    """Three placement rounds on one random fleet: fresh, then with the
    previous round's assignments as ``prev`` under new grants, a changed job
    set and more hosts down, so some sticky assignments still fit and some
    do not."""
    rng = np.random.default_rng(seed)
    m, dph, down = _fleet(rng)
    n = int(rng.integers(1, 7))
    placer = RoundingPlacer(n, m, devices_per_host=dph)
    jobs = _jobs(rng, n, "a")
    prev = None
    for rnd in range(3):
        real = _grants(rng, n, _surviving(m, dph, down))
        yield placer, real, jobs, prev, set(down)
        prev = oracle_place(placer, real, jobs, prev=prev,
                            down_hosts=down).assignments
        kept = [j for j in jobs if rng.random() < 0.7]
        jobs = [JobRequest(j.user, j.job_id, j.workers + int(rng.random() < 0.1),
                           j.starvation) for j in kept] + _jobs(rng, n, f"r{rnd}")
        down |= {(t, h) for t in range(len(m)) for h in range(-(-m[t] // dph))
                 if rng.random() < 0.05}


def _outcome(res):
    return (res.assignments, res.unplaced_jobs, res.cross_host_jobs,
            res.cross_type_workers)


@pytest.mark.parametrize("seed", range(40))
def test_bucketed_packer_matches_the_stable_tie_loop(seed):
    """Same assignments, unplaced jobs and cross counts as the replaced
    loop, in every round, on both placers; grants pass through unchanged."""
    for placer, real, jobs, prev, down in _rounds(seed):
        for naive in (False, True):
            want = oracle_place(placer, real, jobs, naive=naive, prev=prev,
                                down_hosts=down)
            got = placer.place(real, jobs, naive=naive, prev=prev,
                               down_hosts=down)
            assert _outcome(got) == _outcome(want), (seed, naive)
            assert got.real is real


def test_the_random_rounds_cover_every_packing_path():
    """Between them the cases spread a job over hosts, mix types, keep a
    sticky assignment, drop one that no longer fits, leave a job unplaced
    for want of budget, and place gangs wider than a host."""
    seen = set()
    for seed in range(40):
        for placer, real, jobs, prev, down in _rounds(seed):
            res = placer.place(real, jobs, prev=prev, down_hosts=down)
            dph = placer.devices_per_host
            for job in jobs:
                got = res.assignments.get(job.job_id)
                before = (prev or {}).get(job.job_id)
                if got is None:
                    seen.add("unplaced")
                    continue
                if len({j for j, _, _ in got}) > 1:
                    seen.add("cross_type")
                if len(got) > 1 and len({j for j, _, _ in got}) == 1:
                    seen.add("spread")
                if job.workers > dph:
                    seen.add("wider_than_a_host")
                if before is not None and sum(c for _, _, c in before) == job.workers:
                    seen.add("sticky_kept" if got == before else "sticky_moved")
            if down:
                seen.add("down_hosts")
    assert seen == {"unplaced", "cross_type", "spread", "wider_than_a_host",
                    "sticky_kept", "sticky_moved", "down_hosts"}


def test_ties_go_to_the_lowest_host_index():
    """Among equally best-fitting hosts the lowest index wins: hosts 2 and 5
    both have 2 free slots after the sticky pass, so the next 2-worker jobs
    land on 2 and then 5; a 1-worker job then takes host 0, the lowest of
    the hosts with 4 free (none has 1 to 3)."""
    placer = RoundingPlacer(1, [32], devices_per_host=4)
    real = np.array([[9]])
    prev = {"a": [(0, 5, 2)], "b": [(0, 2, 2)]}
    jobs = [JobRequest(0, "a", 2), JobRequest(0, "b", 2),
            JobRequest(0, "c", 2), JobRequest(0, "d", 2), JobRequest(0, "e", 1)]
    res = placer.place(real, jobs, prev=prev)
    assert res.assignments == {"a": [(0, 5, 2)], "b": [(0, 2, 2)],
                               "c": [(0, 2, 2)], "d": [(0, 5, 2)],
                               "e": [(0, 0, 1)]}
    assert res.jobs_searched == 3


def test_probes_per_job_do_not_grow_with_the_hosts():
    """On a 1024-host type, each searched job costs at most one probe per
    free-slot bucket and one per host it takes from. The fleet is filled to
    about 80%, a third of its jobs finish, and jobs are then placed one at a
    time among the rest (held by the sticky pass, a few finishing between
    placements): each new job's probes stay within devices_per_host + 1 +
    hosts taken."""
    rng = np.random.default_rng(7)
    dph = 4
    placer = RoundingPlacer(1, [1024 * dph], devices_per_host=dph)
    sizes = [1, 2, 3, 4, 6, 8]

    def place(running, new):
        jobs = [JobRequest(0, job_id, sum(c for _, _, c in a))
                for job_id, a in running.items()] + new
        real = np.array([[sum(j.workers for j in jobs)]])
        return placer.place(real, jobs, prev=running)

    fill = [JobRequest(0, f"f{i}", int(rng.choice(sizes))) for i in range(800)]
    running = place({}, fill).assignments
    running = {j: a for j, a in running.items() if rng.random() < 0.67}
    free_hosts = 1024 - len({h for a in running.values() for _, h, _ in a})
    assert free_hosts < 512  # fragmented: most hosts hold a job
    for i in range(200):
        for job_id in [j for j in sorted(running) if rng.random() < 0.005]:
            del running[job_id]
        new = JobRequest(0, f"j{i}", int(rng.choice(sizes)))
        res = place(running, [new])
        assert res.jobs_searched == 1
        taken = len(res.assignments[new.job_id])
        assert res.hosts_probed <= dph + 1 + taken, (i, res.hosts_probed)
        running = res.assignments
