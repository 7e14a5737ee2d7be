"""Closed loop: ``clients`` callers, each sends its next request (or group)
when its last one has completed. The first cohort's outputs are cut to
(k + 1) / clients of their length, so that completions are staggered from
the start and not in lockstep."""

from __future__ import annotations

import dataclasses
import functools

from .. import serving
from ..serving import (compared, notes, release,  # noqa: F401
                       trace_started, traced_part, window)
from ..traffic_gen import closed_pool


class Feeder:
    def __init__(self, pool, clients, queued):
        self.pool, self.clients, self.queued = pool, clients, queued
        self.next = 0
        self.pending = []
        self.served = []
        self.open = {}           # id(request) -> members still running

    def _take(self, cut=1.0):
        r = self.pool[self.next % len(self.pool)]
        self.next += 1
        return dataclasses.replace(r, out_len=max(2, int(r.out_len * cut)))

    def start(self, now):
        n = self.clients + self.queued
        self.pending = [self._take(min(1.0, (k + 1) / self.clients))
                        for k in range(n)]

    def open_window(self, now):
        pass

    def due(self, now, loop):
        made = []
        for r in self.pending:
            group = loop.submit(r, now, now)
            self.open[id(r)] = len(group)
            made += group
        self.pending = []
        self.served += made
        return made

    def finished(self, served, now, loop):
        key = id(served.req)
        self.open[key] -= 1
        if self.open[key] == 0:
            del self.open[key]
            self.pending.append(self._take())

    def next_due(self):
        return None

    def attempted(self, w):
        """Every request the window finished."""
        return [s for s in self.served
                if s.done_t is not None and w.t0 <= s.done_t <= w.t1]


def make_feeder(mix, seconds, seed, vocab):
    return Feeder(closed_pool(mix, seed, vocab), int(mix["clients"]),
                  int(mix.get("queued", 0)))


prepare = functools.partial(serving.prepare, make_feeder=make_feeder)
