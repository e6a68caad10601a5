"""Placement optimization (§4.3): deviation-accumulating rounding + host packing.

The fair-share evaluator emits *fractional* shares. Each scheduling round the
placer:
  1. rounds shares to whole devices with per-(user, type) deviation
     accumulation — ``real_j(t) = round(ideal_j(t) + dev_j(t))``,
     ``dev_j(t+1) = dev_j(t) + ideal_j(t) - real_j(t)`` — so long-run averages
     converge to the fractional ideal (bounded deviation, tested);
  2. zeroes a user's share when it is below their minimum job demand
     (``real_j(t) := 0 if real_j(t) < min_k demand_k``), letting deviation
     build until the user can run at least one job (anti-starvation);
  3. packs jobs onto hosts, granting placement priority to jobs with more
     workers (collective-communication contention, §4.3) and preferring
     single-type placements (straggler avoidance, §4.4).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Array = np.ndarray


@dataclasses.dataclass
class JobRequest:
    """A runnable job: ``workers`` devices wanted, owned by ``user``."""

    user: int
    job_id: str
    workers: int
    starvation: float = 0.0  # rounds since last scheduled (priority key)


@dataclasses.dataclass
class PlacementResult:
    real: Array  # (n, k) integer devices granted
    assignments: Dict[str, List[Tuple[int, int, int]]]  # job -> [(type, host, count)]
    cross_host_jobs: int
    cross_type_workers: int
    unplaced_jobs: List[str]
    #: optimized packer's work: bucket probes plus hosts taken from, over
    #: the jobs it searched (past the budget check, outside the sticky pass)
    hosts_probed: int = 0
    jobs_searched: int = 0


class RoundingPlacer:
    """Stateful rounding from fractional shares to integer device grants."""

    def __init__(self, n_users: int, m: Sequence[int], devices_per_host: int = 4):
        self.n = n_users
        self.m = np.asarray(m, dtype=np.int64)
        self.k = len(self.m)
        self.dev = np.zeros((n_users, self.k))
        self.devices_per_host = devices_per_host
        self.hosts_per_type = [
            int(np.ceil(mj / devices_per_host)) for mj in self.m
        ]

    # -- step 1+2: rounding ------------------------------------------------
    def round_shares(self, ideal: Array, min_demand: Optional[Array] = None,
                     capacity: Optional[Array] = None) -> Array:
        """Largest-remainder rounding of ``ideal + dev`` with capacity repair.

        ``min_demand[l]`` is the smallest worker count any of user l's jobs can
        run with; grants smaller than it are deferred (deviation keeps them).

        ``capacity`` is the per-type device budget to round against — the
        online service passes its post-failure effective capacity here so
        integer grants never exceed what :meth:`place` can actually pack
        after masking down hosts. Defaults to the full cluster ``m``.
        """
        ideal = np.asarray(ideal, dtype=np.float64)
        if ideal.shape != (self.n, self.k):
            raise ValueError(
                f"ideal share matrix has shape {ideal.shape}, expected "
                f"(n={self.n}, k={self.k}); rebuild the placer when the "
                f"tenant set or cluster changes"
            )
        cap = self.m if capacity is None else np.asarray(capacity, dtype=np.int64)
        if cap.shape != self.m.shape:
            raise ValueError(
                f"capacity has shape {cap.shape}, expected {self.m.shape}")
        target = ideal + self.dev
        real = np.zeros((self.n, self.k), dtype=np.int64)
        for j in range(self.k):
            col = np.clip(target[:, j], 0.0, None)
            budget = int(min(cap[j], np.floor(col.sum() + 1e-9)))
            base = np.floor(col).astype(np.int64)
            overflow = base.sum() - budget
            if overflow > 0:  # too many from floors alone (dev drift) — trim
                order = np.argsort(col - base)  # smallest remainder first
                for idx in order:
                    if overflow == 0:
                        break
                    take = min(base[idx], overflow)
                    base[idx] -= take
                    overflow -= take
            remaining = budget - base.sum()
            rema = col - np.floor(col)
            order = np.argsort(-rema, kind="stable")
            for idx in order[: max(remaining, 0)]:
                base[idx] += 1
            real[:, j] = base
        if min_demand is not None:
            md = np.asarray(min_demand, dtype=np.int64)
            too_small = (real.sum(axis=1) < md) & (real.sum(axis=1) > 0)
            real[too_small, :] = 0
            # redistribute devices freed by gating: give them to the users
            # with the largest outstanding target who can actually use them
            # (work conservation — idle grants would depress throughput).
            for j in range(self.k):
                freed = int(min(cap[j], np.floor(np.clip(target[:, j], 0, None).sum() + 1e-9))
                            ) - int(real[:, j].sum())
                while freed > 0:
                    resid = target[:, j] - real[:, j]
                    resid[too_small] = -np.inf  # gated users stay gated this round
                    cand = int(np.argmax(resid))
                    if not np.isfinite(resid[cand]):
                        break
                    real[cand, j] += 1
                    freed -= 1
                    if real[cand].sum() < md[cand]:
                        # still below their min demand — undo and stop trying j
                        real[cand, j] -= 1
                        target[cand, j] = -np.inf
                        freed += 1
                        if not np.any(np.isfinite(target[:, j])):
                            break
                        continue
        self.dev += ideal - real
        # keep deviation bounded even under persistent gating
        np.clip(self.dev, -2.0 * self.m.max(), 2.0 * self.m.max(), out=self.dev)
        return real

    # -- step 3: host packing ----------------------------------------------
    def place(
        self,
        real: Array,
        jobs: Sequence[JobRequest],
        *,
        jobs_per_user_order: Optional[Dict[int, List[str]]] = None,
        naive: bool = False,
        prev: Optional[Dict[str, List[Tuple[int, int, int]]]] = None,
        down_hosts: Optional[set] = None,
    ) -> PlacementResult:
        """Pack jobs onto hosts.

        Optimized mode (§4.3, OEF's placer): placement priority to jobs with
        more workers (network contention), each job prefers a single device
        type (fastest granted, straggler avoidance §4.4) and a single host
        when it fits: the host with the fewest free slots that holds the
        job's share of the type, the lowest host index among equals. When no
        host holds it, the share is spread over hosts in ascending order of
        free slots, lowest index first.

        ``naive=True`` models the baselines' native placers (paper §6.3.1:
        Gavel/Gandiva_fair "lack optimization strategies for placement"):
        FIFO order, types filled slowest-first, first-fit across hosts with
        no single-host/single-type preference.

        ``down_hosts`` is a set of ``(type, host)`` pairs currently failed
        (online service): their slots are masked so no job is placed there.
        When the integer grants in ``real`` exceed the surviving slots of any
        type, placement raises ``ValueError`` with the per-type shortfall —
        the caller rounded against pre-failure capacity (pass the effective
        capacity to :meth:`round_shares`) and silently dropping jobs here
        would hide the accounting bug.
        """
        free = []  # free[j][h] = free slots of host h of type j
        for j in range(self.k):
            n_hosts = self.hosts_per_type[j]
            slots = [self.devices_per_host] * n_hosts
            # cap total slots at m_j
            extra = n_hosts * self.devices_per_host - int(self.m[j])
            if extra > 0:
                slots[-1] -= extra
            if down_hosts:
                for h in range(n_hosts):
                    if (j, h) in down_hosts:
                        slots[h] = 0
            free.append(slots)
        shortfall = {
            j: (int(real[:, j].sum()), sum(free[j]))
            for j in range(self.k) if int(real[:, j].sum()) > sum(free[j])
        }
        if shortfall:
            detail = ", ".join(
                f"type {j}: granted {g} > {a} surviving slots (short {g - a})"
                for j, (g, a) in sorted(shortfall.items()))
            raise ValueError(
                f"integer grants exceed post-failure capacity — {detail}; "
                f"round_shares() must be given the effective capacity "
                f"(down hosts: {sorted(down_hosts) if down_hosts else []})")
        budget = np.asarray(real, dtype=np.int64).tolist()  # budget[user][type]
        if naive:
            return self._first_fit(real, jobs, free, budget)
        return self._best_fit(real, jobs, free, budget, prev)

    def _first_fit(self, real: Array, jobs: Sequence[JobRequest],
                   free: List[List[int]], budget: List[List[int]]) -> PlacementResult:
        """The baselines' placer: FIFO, slowest type first, first fit."""
        assignments: Dict[str, List[Tuple[int, int, int]]] = {}
        cross_host = 0
        cross_type = 0
        unplaced: List[str] = []
        for job in sorted(jobs, key=lambda r: r.job_id):
            need = job.workers
            user_budget = budget[job.user]
            if sum(user_budget) < need:
                unplaced.append(job.job_id)
                continue
            placed: List[Tuple[int, int, int]] = []
            for j in range(self.k):
                if need <= 0:
                    break
                avail_j = user_budget[j]
                if avail_j <= 0:
                    continue
                for h in range(len(free[j])):
                    if need <= 0 or avail_j <= 0:
                        break
                    take = min(free[j][h], avail_j, need)
                    if take <= 0:
                        continue
                    free[j][h] -= take
                    avail_j -= take
                    user_budget[j] -= take
                    need -= take
                    placed.append((j, h, take))
            if need > 0:  # rollback
                for j, h, cnt in placed:
                    free[j][h] += cnt
                    user_budget[j] += cnt
                unplaced.append(job.job_id)
                continue
            assignments[job.job_id] = placed
            if len(placed) > 1:
                cross_host += 1
            if len({j for j, _, _ in placed}) > 1:
                cross_type += job.workers
        return PlacementResult(real, assignments, cross_host, cross_type, unplaced)

    def _best_fit(self, real: Array, jobs: Sequence[JobRequest],
                  free: List[List[int]], budget: List[List[int]],
                  prev: Optional[Dict[str, List[Tuple[int, int, int]]]]
                  ) -> PlacementResult:
        """OEF's placer. Hosts are kept in buckets by free-slot count, one
        bitmask per (type, count) with bit ``h`` set for host ``h``, so a
        job's host is found in O(devices_per_host) bucket probes, whatever
        the number of hosts."""
        dph = self.devices_per_host
        buckets = []  # buckets[j][c]: mask of the hosts of type j with c free
        for slots in free:
            masks = [0] * (dph + 1)
            for h, c in enumerate(slots):
                masks[c] |= 1 << h
            buckets.append(masks)

        def move(j: int, h: int, delta: int) -> None:
            """Change host h's free slots by delta, keeping its bucket."""
            c = free[j][h]
            masks = buckets[j]
            masks[c] ^= 1 << h
            masks[c + delta] |= 1 << h
            free[j][h] = c + delta

        order = sorted(jobs, key=lambda r: (-r.workers, -r.starvation, r.job_id))
        type_order = list(range(self.k - 1, -1, -1))  # fastest first
        assignments: Dict[str, List[Tuple[int, int, int]]] = {}
        cross_host = 0
        cross_type = 0
        unplaced: List[str] = []
        probes = 0
        searched = 0
        # placement stickiness: keep a job where it already runs if the new
        # grant still covers it — avoids gratuitous checkpoint/migrate cycles
        # when the LP returns a different-but-equivalent optimum next round.
        if prev:
            for job in order:
                pa = prev.get(job.job_id)
                if not pa or sum(c for _, _, c in pa) != job.workers:
                    continue
                user_budget = budget[job.user]
                per_type: Dict[int, int] = {}
                for j, _, c in pa:
                    per_type[j] = per_type.get(j, 0) + c
                if all(free[j][h] >= c for j, h, c in pa) and all(
                        user_budget[j] >= c for j, c in per_type.items()):
                    for j, h, c in pa:
                        move(j, h, -c)
                        user_budget[j] -= c
                    assignments[job.job_id] = list(pa)
                    if len({(j, h) for j, h, _ in pa}) > 1:
                        cross_host += 1
                    if len(per_type) > 1:
                        cross_type += job.workers
        for job in order:
            if job.job_id in assignments:
                continue
            need = job.workers
            user_budget = budget[job.user]
            if sum(user_budget) < need:
                unplaced.append(job.job_id)
                continue
            searched += 1
            # straggler avoidance (§4.4/§6.3.1): place the whole job in a
            # single device type when any granted type can hold it —
            # fastest such type first; only mix types as a last resort. A
            # type always has the free slots for the user's grant on it: a
            # type's grants fit its surviving slots (checked in place()) and
            # every take lowers a grant and the free slots alike.
            whole_types = [j for j in type_order if user_budget[j] >= need]
            job_type_order = whole_types + [j for j in type_order
                                            if j not in whole_types]
            placed: List[Tuple[int, int, int]] = []
            for j in job_type_order:
                if need <= 0:
                    break
                avail_j = user_budget[j]
                if avail_j <= 0:
                    continue
                masks = buckets[j]
                want = min(need, avail_j)
                # best fit: the fullest host that still holds `want`
                for c in range(want, dph + 1):
                    probes += 1
                    if masks[c]:
                        h = (masks[c] & -masks[c]).bit_length() - 1
                        probes += 1
                        move(j, h, -want)
                        user_budget[j] -= want
                        need -= want
                        placed.append((j, h, want))
                        break
                else:
                    # no host holds it: spread from the fullest hosts up
                    for c in range(1, min(want, dph + 1)):
                        probes += 1
                        while masks[c] and want > 0:
                            h = (masks[c] & -masks[c]).bit_length() - 1
                            take = min(c, want)
                            probes += 1
                            move(j, h, -take)
                            user_budget[j] -= take
                            need -= take
                            want -= take
                            placed.append((j, h, take))
                        if want == 0:
                            break
            if need > 0:  # rollback
                for j, h, cnt in placed:
                    move(j, h, cnt)
                    user_budget[j] += cnt
                unplaced.append(job.job_id)
                continue
            assignments[job.job_id] = placed
            if len(placed) > 1:
                cross_host += 1
            if len({j for j, _, _ in placed}) > 1:
                cross_type += job.workers
        return PlacementResult(real, assignments, cross_host, cross_type,
                               unplaced, hosts_probed=probes,
                               jobs_searched=searched)


def long_run_share_error(placer_history: Sequence[Array], ideal: Array) -> float:
    """Mean |time-averaged real - ideal| — rounding convergence metric."""
    avg = np.mean(np.stack(placer_history, axis=0), axis=0)
    return float(np.mean(np.abs(avg - ideal)))
