"""Plain host reference of the grid simulator: NumPy and ``heapq``, one job at
a time, written from the semantics and independent of ``repro``.

A round advances the clock to the next event (the earliest arrival, job
finish or availability window edge, plus ``quantum``) and then, in order:

1. retires running jobs whose finish time has passed (a job whose site went
   into a preempting outage before it finished is not retired);
2. applies availability: per-site factor at the new clock, usable cores
   ``floor(cores * factor)``, preemption of running jobs at sites whose
   preempting outage overlaps ``(previous clock, clock]`` (back to the queue
   with a retry, or failed once retries are spent), and jobs waiting in such
   a site's queue bounce back to the central queue;
3. queues arrivals;
4. assigns each queued job to the best feasible site under the PanDA
   brokerage score (speed, free share, queue share, failure rate), lowest
   site index on ties;
5. starts, at each site, the longest prefix of its queue in
   (-priority, arrival, job index) order whose cores and memory fit;
6. prices each start: stage-in, Amdahl compute and stage-out with the site
   links shared among the jobs starting there, or, for a job with a dataset,
   a WAN read from the nearest replica over a link shared among the reads
   that start on it, with cache-on-read insertion and LRU eviction.

Floats are kept in ``ftype`` (float32, as the program states), which is
also how the lower-precision control is made.  ``tie_ulps`` numbers the
decisions that rounding could break either way (see ``__init__``).  Failure sampling is not
modelled: every configuration here has failure rate 0, which the entry
point checks.
"""
from __future__ import annotations

import bisect
import heapq

import numpy as np

PENDING, QUEUED, ASSIGNED, RUNNING, DONE, FAILED = 0, 1, 2, 3, 4, 5
SNAP_FIELDS = ("state", "site", "t_start", "t_finish", "retries", "preempted", "xfer_src")


class GridSim:
    """One scenario, advanced with ``run_until(horizon)``.

    ``jobs``, ``sites``: dicts of per-job / per-site arrays as the
    generators make them.  ``data``: ``{"bw", "latency", "size", "origin",
    "disk_cap"}`` turns on replica-aware stage-in with cache-on-read.
    ``avail``: ``{"win_start", "win_end", "win_factor", "win_preempt"}``
    per-site window calendars.
    """

    def __init__(self, jobs: dict, sites: dict, *, data: dict | None = None,
                 avail: dict | None = None, quantum: float = 0.0, max_retries: int = 3,
                 ftype=np.float32, tie_ulps: int = 0):
        if np.any(np.asarray(sites["fail_rate"]) != 0):
            raise ValueError("the reference models failure rate 0 only")
        F = self.F = np.dtype(ftype)
        self.f = lambda x: np.asarray(x, dtype=F)
        f = self.f
        self.J = J = len(jobs["arrival"])
        self.S = S = len(sites["cores"])
        self.arrival = f(jobs["arrival"])
        self.work = f(jobs["work"])
        self.cores = np.asarray(jobs["cores"], np.int64)
        self.memory = f(jobs["memory"])
        self.bytes_in = f(jobs["bytes_in"])
        self.bytes_out = f(jobs["bytes_out"])
        self.priority = f(jobs["priority"])
        self.dataset = np.asarray(jobs.get("dataset", np.full(J, -1)), np.int64)

        self.s_cores = np.asarray(sites["cores"], np.int64)
        self.s_speed = f(sites["speed"])
        self.s_memory = f(sites["memory"])
        self.s_bw_in = f(sites["bw_in"])
        self.s_bw_out = f(sites["bw_out"])
        self.s_latency = f(sites["latency"])
        self.s_gamma = f(sites["par_gamma"])
        self.s_fail = f(sites["fail_rate"])
        self.free_cores = self.s_cores.copy()
        self.free_mem = self.s_memory.copy()

        self.state = np.full(J, PENDING, np.int64)
        self.site = np.full(J, -1, np.int64)
        self.t_start = np.full(J, np.inf, F)
        self.t_finish = np.full(J, np.inf, F)
        self.retries = np.zeros(J, np.int64)
        self.preempted = np.zeros(J, np.int64)
        self.xfer_src = np.full(J, -1, np.int64)

        # decisions that turn on two floats that differ, by at most
        # ``tie_ulps`` units in the last place, are ties that rounding may
        # break either way: an assignment whose two best sites score so
        # close and, with a quantum, a finish time, arrival or window edge so
        # close to the round's clock.  Two equal floats are no tie: both
        # sides compute the same comparison of them.  Ties are numbered as
        # met; those in ``flips`` go the other way (``reference/compare.py``
        # searches them).
        self.tie_ulps = tie_ulps
        self.flips: frozenset = frozenset()
        self.n_ties = 0
        self.clock = f(0.0)
        self.rounds = 0
        self.halted = False
        self.quantum = f(quantum)
        self.max_retries = max_retries
        # pending jobs in (arrival, index) order; all before ``ptr`` arrived
        self.pend = np.argsort(self.arrival, kind="stable")
        self.ptr = 0
        self.heap: list = []                      # (t_finish, job) of RUNNING jobs
        self.queued: list = []                    # QUEUED jobs
        self.site_queue = [[] for _ in range(S)]  # ASSIGNED jobs per site
        self.busy_sites: set = set()              # sites whose queue is not empty
        self.running = [set() for _ in range(S)]  # RUNNING jobs per site
        # static start-order key within a site queue
        self.start_key = np.empty(J, np.int64)
        self.start_key[np.lexsort((np.arange(J), self.arrival, -self.priority))] = np.arange(J)

        self.data = data is not None
        if self.data:
            self.net_bw = f(data["bw"])
            self.net_lat = f(data["latency"])
            self.ds_size = f(data["size"])
            self.origin = np.asarray(data["origin"], np.int64)
            self.disk_cap = f(data["disk_cap"])
            D = len(self.ds_size)
            self.present = np.zeros((D, S), bool)
            self.present[np.arange(D), self.origin] = True
            self.is_origin = self.present.copy()
            self.disk_used = np.zeros(S, F)
            for d in range(D):  # initial replicas, summed in dataset order
                s = self.origin[d]
                self.disk_used[s] = self.disk_used[s] + self.ds_size[d]
            self.last_access = np.where(self.present, f(0.0), f(-np.inf))
        self.avail = avail is not None
        if self.avail:
            self.win_start = f(avail["win_start"])
            self.win_end = f(avail["win_end"])
            self.win_factor = f(avail["win_factor"])
            self.win_kill = np.asarray(avail["win_preempt"], bool) & (self.win_factor <= 0)
            edges = np.concatenate([self.win_start.ravel(), self.win_end.ravel()])
            self.edges = np.sort(edges[np.isfinite(edges)])
            self.edges_passed = f(-np.inf)  # edges taken as passed at a tie
            self.edge_again = None          # an edge taken as still to come

    # ------------------------------------------------------------------ run

    def active(self) -> bool:
        return (self.ptr < len(self.pend) or self._heap_top() is not None
                or bool(self.queued) or bool(self.busy_sites))

    def run_until(self, horizon: float = np.inf, max_rounds: int = 10**9) -> None:
        """Run rounds while the clock is at or before ``horizon``."""
        horizon = self.f(horizon)
        while (not self.halted and self.active() and self.rounds < max_rounds
               and self.clock <= horizon):
            self._round()

    def snapshot(self) -> dict:
        return {k: getattr(self, k).copy() for k in SNAP_FIELDS}

    # ---------------------------------------------------------------- round

    def _heap_top(self):
        while self.heap:
            t, j = self.heap[0]
            if self.state[j] == RUNNING and self.t_finish[j] == t:
                return t
            heapq.heappop(self.heap)
        return None

    def _round(self) -> None:
        f, F, S = self.f, self.F, self.S
        prev = self.clock
        t_next = f(np.inf)
        if self.ptr < len(self.pend):
            t_next = min(t_next, self.arrival[self.pend[self.ptr]])
        top = self._heap_top()
        if top is not None:
            t_next = min(t_next, top)
        if self.avail:
            i = bisect.bisect_right(self.edges, max(prev, self.edges_passed))
            if i < len(self.edges):
                t_next = min(t_next, f(self.edges[i]))
            if self.edge_again is not None:
                t_next = min(t_next, self.edge_again)
                self.edge_again = None
        t_next = f(t_next + self.quantum) if self.quantum > 0 else f(t_next)
        clock = f(max(prev, t_next)) if np.isfinite(t_next) else prev
        self.clock = clock
        progressed = False

        # 1. completions.  With a quantum, a finish or arrival time within a
        # tie of the clock may fall either side of it; without one, the clock
        # is itself an event time, and a tie in event order is not modelled.
        tol = self._tol(clock) if self.quantum > 0 else 0.0
        done, later = [], []
        while True:
            top = self._heap_top()
            if top is None or top > clock + tol:
                break
            t, j = heapq.heappop(self.heap)
            (done if self._decide(t <= clock, self._near(t, clock, tol)) else later).append(j)
        for j in later:
            heapq.heappush(self.heap, (self.t_finish[j], j))
        # so may a window edge: availability then reads the clock as just
        # past the edge (it began) or just short of it (it is still to come)
        clock_av = clock
        if self.avail and tol > 0:
            lo = bisect.bisect_left(self.edges, clock - tol)
            for e in self.edges[lo:bisect.bisect_right(self.edges, clock + tol)]:
                passed = self._decide(e <= clock, e != clock)
                if passed and e > clock:
                    clock_av = max(clock_av, f(e))
                    self.edges_passed = max(self.edges_passed, f(e))
                elif not passed and e <= clock:
                    clock_av = min(clock_av, np.nextafter(f(e), f(-np.inf)))
                    self.edge_again = f(e)
        if self.avail and done:
            keep = []
            for j in done:
                s = self.site[j]
                ws = self.win_start[s]
                if np.any(self.win_kill[s] & (ws > prev) & (ws < self.t_finish[j])):
                    heapq.heappush(self.heap, (self.t_finish[j], j))  # preempted below
                else:
                    keep.append(j)
            done = keep
        done.sort()
        self._release(done)
        for j in done:
            self.state[j] = DONE
            self.running[self.site[j]].discard(j)
        progressed |= bool(done)

        # 2. availability
        start_cores = self.free_cores.copy()
        up = np.ones(S, bool)
        speed = self.s_speed
        if self.avail:
            cover = (self.win_start <= clock_av) & (clock_av < self.win_end)
            factor = np.where(cover, self.win_factor, f(1.0)).min(axis=1).astype(F)
            hit = (self.win_start <= clock_av) & (self.win_end > prev) & self.win_kill
            preempting = np.flatnonzero(hit.any(axis=1))
            pre = sorted(j for s in preempting for j in self.running[s])
            self._release(pre)
            for j in pre:
                self.running[self.site[j]].discard(j)
                self.preempted[j] += 1
                if self.retries[j] < self.max_retries:
                    self.state[j], self.site[j] = QUEUED, -1
                    self.retries[j] += 1
                    self.t_finish[j] = np.inf
                    self.queued.append(j)
                else:
                    self.state[j] = FAILED
                    self.t_finish[j] = clock
            for s in preempting:
                for j in self.site_queue[s]:
                    self.state[j], self.site[j] = QUEUED, -1
                    self.queued.append(j)
                self.site_queue[s] = []
                self.busy_sites.discard(s)
            progressed |= bool(pre)
            eff_cap = np.floor(self.s_cores.astype(F) * factor).astype(np.int64)
            up = eff_cap > 0
            busy = self.s_cores - self.free_cores
            start_cores = np.clip(eff_cap - busy, 0, self.free_cores)
            speed = np.maximum(self.s_speed * factor, f(1e-9)).astype(F)

        # 3. arrivals
        while self.ptr < len(self.pend) and self.arrival[self.pend[self.ptr]] <= clock + tol:
            a = self.arrival[self.pend[self.ptr]]
            if not self._decide(a <= clock, self._near(a, clock, tol)):
                break
            j = self.pend[self.ptr]
            self.state[j] = QUEUED
            self.queued.append(j)
            self.ptr += 1
            progressed = True

        # 4. assignment
        if self.queued:
            self._assign(up)

        # 5. starts
        started = []
        for s in sorted(self.busy_sites):
            q = self.site_queue[s]
            q.sort(key=lambda j: self.start_key[j])
            cores = np.cumsum(self.cores[q])
            mem = np.cumsum(self.memory[q], dtype=F)
            n = int(np.sum((cores <= start_cores[s]) & (mem <= f(self.free_mem[s] + f(1e-6)))))
            started.extend(q[:n])
            self.site_queue[s] = q[n:]
            if n == len(q):
                self.busy_sites.discard(s)
        if started:
            self._start(np.array(sorted(started)), speed)
            progressed = True

        self.rounds += 1
        self.halted = (not np.isfinite(t_next)) and not progressed

    def _tol(self, x):
        """``tie_ulps`` units in the last place of float32 ``x``."""
        return self.tie_ulps * np.spacing(np.abs(np.asarray(x, np.float32)))

    @staticmethod
    def _near(a, b, tol) -> bool:
        """``a`` and ``b`` differ, by at most ``tol``: a tie."""
        return bool(tol > 0 and a != b and abs(a - b) <= tol)

    def _decide(self, default: bool, tie: bool) -> bool:
        """The outcome of one decision: ``default``, unless it is a tie that
        ``flips`` breaks the other way."""
        if not tie or self.tie_ulps == 0:
            return default
        self.n_ties += 1
        return default != ((self.n_ties - 1) in self.flips)

    def _release(self, jobs) -> None:
        """Return the cores and memory of ``jobs`` to their sites, summed per
        site in job order and then added."""
        if not len(jobs):
            return
        jobs = np.asarray(jobs)
        sites = self.site[jobs]
        np.add.at(self.free_cores, sites, self.cores[jobs])
        freed = np.zeros(self.S, self.F)
        for j, s in zip(jobs, sites):
            freed[s] = freed[s] + self.memory[j]
        touched = np.unique(sites)
        self.free_mem[touched] = (self.free_mem[touched] + freed[touched]).astype(self.F)

    def _assign(self, up) -> None:
        f, F = self.f, self.F
        q_cores = np.zeros(self.S, np.int64)
        for s, q in enumerate(self.site_queue):
            if q:
                q_cores[s] = self.cores[q].sum()
        cores_f = np.maximum(self.s_cores.astype(F), f(1.0))
        norm_speed = (self.s_speed / np.maximum(self.s_speed.max(), f(1e-9))).astype(F)
        free_frac = (self.free_cores.astype(F) / cores_f).astype(F)
        queue_frac = (q_cores.astype(F) / cores_f).astype(F)
        score = (((norm_speed + free_frac) - f(2.0) * queue_frac) - f(4.0) * self.s_fail).astype(F)
        queued = np.array(self.queued)
        feasible = (up[None, :] & (self.cores[queued][:, None] <= self.s_cores[None, :])
                    & (self.memory[queued][:, None] <= self.s_memory[None, :]))
        masked = np.where(feasible, score[None, :], -np.inf)
        best = masked.argmax(axis=1)
        ok = np.isfinite(masked.max(axis=1))
        if self.tie_ulps > 0:
            rows = np.arange(len(queued))
            top = masked[rows, best]
            rest = masked.copy()
            rest[rows, best] = -np.inf
            second = rest.argmax(axis=1)
            other = rest[rows, second]
            with np.errstate(invalid="ignore"):
                near = ok & (top != other) & (np.abs(top - other) <= self._tol(np.abs(top)))
            for pair in sorted({(int(b), int(c)) for b, c in zip(best[near], second[near])}):
                if not self._decide(True, True):
                    best = np.where(near & (best == pair[0]) & (second == pair[1]), second, best)
        self.queued = [int(j) for j in queued[~ok]]
        for j, s in zip(queued[ok], best[ok]):
            self.state[j], self.site[j] = ASSIGNED, s
            self.site_queue[s].append(int(j))
            self.busy_sites.add(int(s))

    def _start(self, started: np.ndarray, speed: np.ndarray) -> None:
        f, F = self.f, self.F
        clock = self.clock
        site = self.site[started]
        share = f(np.bincount(site, minlength=self.S)[site])
        c = self.cores[started].astype(F)
        gamma = self.s_gamma[site]
        speedup = (c / (f(1.0) + gamma * np.maximum(c - f(1.0), f(0.0)))).astype(F)
        compute = (self.work[started] / (speed[site] * np.maximum(speedup, f(1e-9)))).astype(F)
        # a site link shared equally by n jobs moves b bytes in b * n / bw
        out = (self.bytes_out[started] * np.maximum(share, f(1.0)) / self.s_bw_out[site]).astype(F)
        if not self.data:
            stage_in = (self.s_latency[site] + (self.bytes_in[started] * np.maximum(share, f(1.0))
                                                 / self.s_bw_in[site]).astype(F)).astype(F)
            t_serv = ((stage_in + compute) + out).astype(F)
        else:
            t_serv = self._data_start(started, site, compute, out)
        self.t_start[started] = clock
        self.t_finish[started] = (clock + t_serv).astype(F)
        self.state[started] = RUNNING
        for j, s, t in zip(started, site, self.t_finish[started]):
            self.running[s].add(int(j))
            heapq.heappush(self.heap, (t, int(j)))
        np.subtract.at(self.free_cores, site, self.cores[started])
        used = np.zeros(self.S, F)
        for j, s in zip(started, site):
            used[s] = used[s] + self.memory[j]
        touched = np.unique(site)
        self.free_mem[touched] = (self.free_mem[touched] - used[touched]).astype(F)

    def _data_start(self, started, site, compute, out) -> np.ndarray:
        """Service time of starting jobs with replica-aware stage-in, plus
        the catalog bookkeeping: LRU touches and cache-on-read insertion."""
        f, F, S, clock = self.f, self.F, self.S, self.clock
        d = self.dataset[started]
        has = d >= 0
        n_flat = np.bincount(site[~has], minlength=S)
        share_in = f(n_flat[site])
        in_flat = (self.s_latency[site] + (self.bytes_in[started] * np.maximum(share_in, f(1.0))
                                            / self.s_bw_in[site]).astype(F)).astype(F)
        t_serv = ((in_flat + compute) + out).astype(F)
        dc = np.clip(d, 0, len(self.ds_size) - 1)
        local = has & self.present[dc, site]
        src = np.array([self._nearest(dd, s) for dd, s in zip(dc, site)], np.int64)
        xfer = has & ~local
        link = src * S + site
        n_link = np.bincount(link[xfer], minlength=S * S)
        share = f(np.maximum(n_link[link], 1))
        bw_eff = (self.net_bw[src, site] / share).astype(F)
        t_net = (self.net_lat[src, site] + self.ds_size[dc] / np.maximum(bw_eff, f(1e-9))).astype(F)
        t_net = np.where(xfer, t_net, f(0.0)).astype(F)
        for j, dd, s, sr, x, lo in zip(started, dc, site, src, xfer, local):
            if x:
                self.last_access[dd, sr] = clock
            elif lo:
                self.last_access[dd, s] = clock
        want = {}
        for dd, s, x in zip(dc, site, xfer):
            if x and not self.present[dd, s]:
                want.setdefault(int(s), set()).add(int(dd))
        for s, ds in sorted(want.items()):
            self._insert(s, sorted(ds))
        self.xfer_src[started[has]] = src[has]
        return np.where(has, ((t_serv - in_flat) + t_net).astype(F), t_serv).astype(F)

    def _nearest(self, d: int, dst: int) -> int:
        f = self.f
        lat, bw = self.net_lat[:, dst], self.net_bw[:, dst]
        reach = self.present[d] & (bw > 0) & np.isfinite(lat)
        if not reach.any():
            return int(self.origin[d])
        cost = np.where(reach, lat + self.ds_size[d] / np.maximum(bw, f(1e-9)), np.inf)
        return int(np.argmin(cost))

    def _insert(self, s: int, new: list) -> None:
        """Insert replicas of the datasets ``new`` at site ``s``, evicting the
        least recently used non-origin replicas if the disk is full; a site
        that cannot fit them even then takes none."""
        f, F, clock = self.f, self.F, self.clock
        incoming = f(0.0)
        for d in new:
            incoming = f(incoming + self.ds_size[d])
        used, cap = self.disk_used[s], self.disk_cap[s]
        need = f(max(f(f(used + incoming) - cap), f(0.0)))
        freed, evict = f(0.0), []
        if need > 0:
            wanted = np.zeros(len(self.ds_size), bool)
            wanted[new] = True
            ev = np.flatnonzero(self.present[:, s] & ~self.is_origin[:, s] & ~wanted)
            ev = ev[np.argsort(self.last_access[ev, s], kind="stable")]
            cum = f(0.0)
            for d in ev:
                if not cum < need:
                    break
                evict.append(d)
                cum = f(cum + self.ds_size[d])
            for d in sorted(evict):  # summed in dataset order
                freed = f(freed + self.ds_size[d])
            if not f(f(used - freed) + incoming) <= f(cap + f(1e-3)):
                return
        self.present[new, s] = True
        self.last_access[new, s] = clock
        self.present[evict, s] = False
        self.last_access[evict, s] = -np.inf
        self.disk_used[s] = f(f(used - freed) + incoming)
