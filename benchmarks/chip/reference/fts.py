"""Plain host reference of FTS-style transfer queues (CERN's File Transfer
Service under Rucio) on top of ``gridsim.GridSim``: NumPy and ``heapq``,
written from the semantics and independent of ``repro``.

A WAN read no longer prices its transfer into the job's service time.  The
job starts and enters a staging gate: it holds its cores with no finish time
while its dataset moves.  Each directed link ``src -> dst`` runs at most
``max_active`` transfers; the rest wait in the link's FIFO, in enqueue
order (jobs that start in the same round in job order).  A link's bandwidth
is shared equally among its moving transfers, and a transfer's bytes are
integrated over each interval between rounds.  Its landing is an event of
the round clock.

A round, with exact event order (quantum 0):

1. advances the clock to the earliest arrival, job finish or landing;
2. retires the running jobs whose finish time has passed;
2b. moves every transfer's bytes over the elapsed interval; a transfer whose
   landing time has come lands: its job's finish time becomes ``clock +
   remainder``, the remainder being compute and stage-out plus the link's
   latency, its replica is inserted at the destination (LRU eviction), and
   ``xfer_wait``/``xfer_time`` are set.  Then each link's queue head takes
   its free slots and every moving transfer's landing time is priced anew;
3. queues arrivals; 4. assigns; 5. starts, as ``GridSim`` does;
5b. enqueues the round's WAN reads (``xfer_qdepth``: the transfers waiting
   ahead on the link), admits, and prices again.

Availability windows, faults and a failure rate above 0 are not modelled;
asking for them is refused.
"""
from __future__ import annotations

import heapq

import numpy as np

from reference.gridsim import DONE, QUEUED, SNAP_FIELDS, GridSim

FTS_FIELDS = ("xfer_wait", "xfer_time", "xfer_qdepth")


class FtsSim(GridSim):
    """One scenario with its WAN reads behind per-link FTS queues of
    ``max_active`` slots.  ``jobs``, ``sites``, ``data``: as ``GridSim``
    takes them (``data`` is required)."""

    def __init__(self, jobs: dict, sites: dict, *, data: dict, max_active: int,
                 avail: dict | None = None, quantum: float = 0.0, max_retries: int = 3,
                 ftype=np.float32, tie_ulps: int = 0):
        if data is None:
            raise ValueError("the FTS reference needs the data block: queues carry WAN reads")
        if avail is not None:
            raise ValueError("the FTS reference models no availability windows")
        if quantum != 0:
            raise ValueError("the FTS reference models exact event order (quantum 0) only")
        super().__init__(jobs, sites, data=data, max_retries=max_retries, ftype=ftype,
                         tie_ulps=tie_ulps)
        J, F = self.J, self.F
        self.max_active = int(max_active)
        self.waiting: dict = {}   # link -> jobs waiting for a slot, FIFO
        self.moving: dict = {}    # link -> jobs whose bytes are moving
        self.link = np.full(J, -1, np.int64)
        self.rem = np.zeros(J, F)
        self.t_done = np.full(J, np.inf, F)
        self.resid = np.zeros(J, F)
        self.enq_t = np.zeros(J, F)
        self.act_t = np.zeros(J, F)
        self.xfer_wait = np.zeros(J, F)
        self.xfer_time = np.zeros(J, F)
        self.xfer_qdepth = np.full(J, -1, np.int64)
        self.n_enq = self.n_landed = self.n_waited = 0
        self._reads = None  # this round's WAN reads, from _data_start to _start

    def snapshot(self) -> dict:
        return {k: getattr(self, k).copy() for k in SNAP_FIELDS + FTS_FIELDS}

    # ---------------------------------------------------------------- round

    def _round(self) -> None:
        f = self.f
        prev = self.clock
        t_next = f(np.inf)
        if self.ptr < len(self.pend):
            t_next = min(t_next, self.arrival[self.pend[self.ptr]])
        top = self._heap_top()
        if top is not None:
            t_next = min(t_next, top)
        for jobs in self.moving.values():
            for j in jobs:
                t_next = min(t_next, self.t_done[j])
        clock = f(max(prev, t_next)) if np.isfinite(t_next) else prev
        self.clock = clock

        # 1. completions (staging jobs have no finish time)
        done = []
        while True:
            top = self._heap_top()
            if top is None or top > clock:
                break
            done.append(heapq.heappop(self.heap)[1])
        done.sort()
        self._release(done)
        for j in done:
            self.state[j] = DONE
            self.running[self.site[j]].discard(j)
        progressed = bool(done)

        # 2b. transfers: bytes, landings, admission
        progressed |= self._land(prev)

        # 3. arrivals
        while self.ptr < len(self.pend) and self.arrival[self.pend[self.ptr]] <= clock:
            j = self.pend[self.ptr]
            self.state[j] = QUEUED
            self.queued.append(j)
            self.ptr += 1
            progressed = True

        # 4. assignment
        if self.queued:
            self._assign(np.ones(self.S, bool))

        # 5. starts (5b: the WAN reads join their link queues in _start)
        started = []
        for s in sorted(self.busy_sites):
            q = self.site_queue[s]
            q.sort(key=lambda j: self.start_key[j])
            cores = np.cumsum(self.cores[q])
            mem = np.cumsum(self.memory[q], dtype=self.F)
            n = int(np.sum((cores <= self.free_cores[s]) & (mem <= f(self.free_mem[s] + f(1e-6)))))
            started.extend(q[:n])
            self.site_queue[s] = q[n:]
            if n == len(q):
                self.busy_sites.discard(s)
        if started:
            self._start(np.array(sorted(started)), self.s_speed)
            progressed = True

        self.rounds += 1
        self.halted = (not np.isfinite(t_next)) and not progressed

    # ------------------------------------------------------------ transfers

    def _rate(self, link: int):
        """Bandwidth of one of the link's moving transfers: an equal share."""
        f = self.f
        n = max(len(self.moving.get(link, ())), 1)
        return f(self.net_bw.reshape(-1)[link] / f(n))

    def _land(self, prev) -> bool:
        """Step 2b; True if a transfer landed."""
        f, clock = self.f, self.clock
        dt = f(max(f(clock - prev), f(0.0)))
        landed = []
        for link, jobs in self.moving.items():
            rate = self._rate(link)
            for j in sorted(jobs):
                if self.t_done[j] <= clock:
                    landed.append(j)
                else:
                    self.rem[j] = f(max(f(self.rem[j] - f(rate * dt)), f(0.0)))
        landed.sort()
        want: dict = {}
        for j in landed:
            self.moving[self.link[j]].discard(j)
            self.rem[j], self.t_done[j] = f(0.0), f(np.inf)
            self.t_finish[j] = f(clock + self.resid[j])
            heapq.heappush(self.heap, (self.t_finish[j], int(j)))
            self.xfer_time[j] = f(clock - self.act_t[j])
            self.xfer_wait[j] = f(self.act_t[j] - self.enq_t[j])
            self.n_landed += 1
            want.setdefault(int(self.site[j]), set()).add(int(self.dataset[j]))
        for s, ds in sorted(want.items()):
            self._land_replicas(s, sorted(ds))
        self._admit()
        return bool(landed)

    def _land_replicas(self, s: int, ds: list) -> None:
        """Cache-on-read at landing.  A dataset that reached ``s`` meanwhile
        (an earlier landing) is not inserted again, and is not evicted to make
        room for the others: it is pinned for the insertion."""
        again = [d for d in ds if self.present[d, s] and not self.is_origin[d, s]]
        new = [d for d in ds if not self.present[d, s]]
        if not new:
            return
        self.is_origin[again, s] = True
        self._insert(s, new)
        self.is_origin[again, s] = False

    def _admit(self) -> None:
        """Each link's queue head takes the free slots; then every moving
        transfer's landing time is priced at its share."""
        f, clock = self.f, self.clock
        for link, q in self.waiting.items():
            moving = self.moving.setdefault(link, set())
            n = max(self.max_active - len(moving), 0)
            for j in q[:n]:
                moving.add(j)
                self.act_t[j] = clock
                self.n_waited += int(clock > self.enq_t[j])
            del q[:n]
        for link, jobs in self.moving.items():
            rate = self._rate(link)
            for j in jobs:
                self.t_done[j] = f(clock + f(self.rem[j] / max(rate, f(1e-9))))

    def _data_start(self, started, site, compute, out) -> np.ndarray:
        """Service time of starting jobs: a local read as ``GridSim`` prices
        it, a WAN read held in the staging gate (``inf``) with its transfer
        handed to ``_start``; LRU touches at the source or the local copy."""
        f, F, S, clock = self.f, self.F, self.S, self.clock
        d = self.dataset[started]
        has = d >= 0
        n_flat = np.bincount(site[~has], minlength=S)
        share_in = f(n_flat[site])
        in_flat = (self.s_latency[site] + (self.bytes_in[started] * np.maximum(share_in, f(1.0))
                                            / self.s_bw_in[site]).astype(F)).astype(F)
        t_serv = ((in_flat + compute) + out).astype(F)
        rest = (t_serv - in_flat).astype(F)
        dc = np.clip(d, 0, len(self.ds_size) - 1)
        local = has & self.present[dc, site]
        src = np.array([self._nearest(dd, s) for dd, s in zip(dc, site)], np.int64)
        xfer = has & ~local
        for dd, s, sr, x, lo in zip(dc, site, src, xfer, local):
            if x:
                self.last_access[dd, sr] = clock
            elif lo:
                self.last_access[dd, s] = clock
        resid = (np.maximum(rest, f(0.0)) + self.net_lat[src, site]).astype(F)
        self.xfer_src[started[has]] = src[has]
        self.xfer_time[started[has]] = f(0.0)
        self._reads = (started[xfer], (src * S + site)[xfer], self.ds_size[dc[xfer]], resid[xfer])
        return np.where(has, np.where(xfer, f(np.inf), rest), t_serv).astype(F)

    def _start(self, started: np.ndarray, speed: np.ndarray) -> None:
        """``GridSim._start``, then step 5b: the WAN reads join their link
        queues in job order, and the heads are admitted."""
        super()._start(started, speed)
        clock = self.clock
        for j, link, nbytes, resid in zip(*self._reads):
            q = self.waiting.setdefault(int(link), [])
            self.xfer_qdepth[j] = len(q)
            self.xfer_wait[j] = self.f(0.0)
            q.append(int(j))
            self.link[j] = link
            self.rem[j], self.resid[j] = nbytes, resid
            self.enq_t[j] = self.act_t[j] = clock
            self.n_enq += 1
        self._reads = None
        self._admit()
