"""Open loop, fixed work: exactly round(rate * seconds) arrivals in every
window, due at (i + u_i) / rate whether or not earlier ones have finished."""

from __future__ import annotations

import functools
import json

from .. import serving
from ..e2e import ttft_p75_ms
from ..serving import (compared, release, trace_started,  # noqa: F401
                       traced_part, window)
from ..traffic_gen import open_schedule


class Feeder:
    def __init__(self, ramp, win):
        self.phases = [ramp, win]
        self.todo = []
        self.t0 = 0.0
        self.served = []

    def start(self, now):
        self.t0, self.todo = now, list(self.phases[0])

    def open_window(self, now):
        # what the ramp had not yet submitted is dropped: the window's own
        # schedule starts on time
        self.t0, self.todo = now, list(self.phases[1])

    def due(self, now, loop):
        made = []
        while self.todo and self.t0 + self.todo[0].due <= now:
            r = self.todo.pop(0)
            made += loop.submit(r, self.t0 + r.due, now)
        self.served += made
        return made

    def finished(self, served, now, loop):
        pass

    def attempted(self, w):
        """The measured set: due in the window, before its tail."""
        return [s for s in self.served if s.measured and s.due_t >= w.t0]

    def next_due(self):
        return self.t0 + self.todo[0].due if self.todo else float("inf")


def make_feeder(mix, seconds, seed, vocab):
    return Feeder(*open_schedule(mix, seconds, seed, vocab))


def notes(w):
    return serving.notes(w) + [
        f"ttft ms {json.dumps(ttft_p75_ms.summary(w))}"]


prepare = functools.partial(serving.prepare, make_feeder=make_feeder)
