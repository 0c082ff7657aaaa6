"""Buffered-async execution of the in-mesh simulator (``fl_mode=async``): a
host-side virtual arrival queue decides each flush's cohort and staleness;
the FedBuffInMesh strategy turns them into discounted weights in-mesh."""

from __future__ import annotations

import logging
from typing import Dict, List, Tuple

import numpy as np

from ...core import obs
from ...core.async_fl import VirtualArrivalQueue
from ...core.checkpoint import maybe_checkpointer

logger = logging.getLogger(__name__)


class VirtualArrivals:
    """Deterministic virtual-time schedule: per-client durations drawn once
    from ``random_seed`` (the sp FedBuffAPI idiom), a fixed cohort (the
    round-0 population draw — async cycles re-dispatch the same pool,
    matching the message-plane servers), and a flush size of
    ``async_buffer_size`` arrivals.  Each XLA round is one flush."""

    def __init__(self, args, num_clients: int, clients_per_round: int, cohort):
        if maybe_checkpointer(args) is not None:
            raise NotImplementedError(
                "fl_mode=async does not checkpoint mid-run in the XLA "
                "simulator (the virtual arrival queue is not persisted)")
        cap = int(getattr(args, "async_buffer_size", 0) or 0) or clients_per_round
        if cap > clients_per_round:
            logger.warning("async_buffer_size=%d exceeds the cohort (%d): "
                           "clamping", cap, clients_per_round)
            cap = clients_per_round
        self.cap = cap
        self.max_staleness = int(getattr(args, "async_max_staleness", 0) or 0)
        rng = np.random.RandomState(int(getattr(args, "random_seed", 0)))
        self.durations = 0.5 + rng.exponential(1.0, size=num_clients)
        self.cohort = [int(c) for c in cohort]
        self.version = 0
        self.dispatched = {c: 0 for c in self.cohort}
        self.queue = VirtualArrivalQueue()
        for c in self.cohort:
            self.queue.push(c, float(self.durations[c]))
        self.t = 0.0
        self.dropped_stale = 0

    def next_flush(self) -> Tuple[np.ndarray, Dict[int, int]]:
        """Pop arrivals off the virtual queue until one buffer's worth
        accrues; returns (cohort sorted by id, staleness by id).  Sorting
        keeps the mesh layout id-deterministic — and makes the
        full-participation constant-weight config schedule-identical to the
        sync loop (the arrival ORDER carries no weight information; the
        staleness map does)."""
        picked: List[int] = []
        stal: Dict[int, int] = {}
        v = self.version
        while len(picked) < self.cap:
            t, cid = self.queue.pop()
            self.t = t
            s = v - self.dispatched[cid]
            if s > self.max_staleness:
                # too stale to aggregate: fresh work beats idling
                self.dropped_stale += 1
                obs.counter_inc("async.dropped_stale")
                self.dispatched[cid] = v
                self.queue.push(cid, t + float(self.durations[cid]))
                continue
            picked.append(cid)
            stal[cid] = int(s)
            obs.histogram_observe("async.staleness", float(s))
            if self.max_staleness >= 1 and len(picked) < self.cap:
                # FedBuff: the client keeps training while its delta waits
                self.dispatched[cid] = v
                self.queue.push(cid, t + float(self.durations[cid]))
        return np.asarray(sorted(picked), np.int64), stal

    def flushed(self) -> None:
        """The flush applied: bump the version and re-dispatch every idle
        cohort member on the fresh global at the flush's virtual time."""
        self.version += 1
        obs.counter_inc("async.flushes", labels={"reason": "full"})
        in_flight = set(self.queue.clients())
        for c in self.cohort:
            if c not in in_flight:
                self.dispatched[c] = self.version
                self.queue.push(c, self.t + float(self.durations[c]))
