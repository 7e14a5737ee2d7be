"""The one general traffic generator. A mix is a data file of parameters;
this turns it and ``--seed`` into requests. No JAX here.

Fixed work: a run's requests are the evenly spaced quantiles of the mix's
length distributions, so every seed offers the same count, the same multiset
of (prompt, output) lengths and the same total tokens, in the mix's own fixed
order with its own arrival jitters. The seed only rotates that order and
draws the token ids.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: List[int]
    out_len: int
    due: float = 0.0          # seconds from the start of its phase
    measured: bool = True
    group_size: int = 1


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as whole
    numbers. Kinds: ``uniform`` and ``log_uniform`` on [lo, hi], ``fixed``."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    kind = dist["kind"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if kind == "uniform":
        x = lo + q * (hi - lo)
    elif kind == "log_uniform":
        x = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.rint(x).astype(np.int64)


def trace(mix: dict, n: int, salt: int) -> tuple:
    """The mix's fixed trace of ``n`` requests: (n, 2) prompt and output
    lengths in arrival order, and (n,) arrival jitters in [0, 1). The
    lengths are the quantiles of each distribution; their pairing, their
    order and the jitters are drawn from the mix's ``pair_seed`` (and
    ``salt``, which tells the phases of one run apart), so they are the same
    for every ``--seed``: which long prompts meet in a step is part of the
    mix, not of the seed."""
    rng = np.random.default_rng([int(mix["pair_seed"]), int(salt)])
    prompts = quantiles(mix["prompt_len"], n)
    outs = quantiles(mix["output_len"], n)[rng.permutation(n)]
    order = rng.permutation(n)
    return np.stack([prompts, outs], axis=1)[order], rng.random(n)


def _requests(pairs: np.ndarray, rng: np.random.Generator, vocab: int,
              group_size: int = 1) -> List[Request]:
    return [Request(prompt=rng.integers(1, vocab, size=int(p)).tolist(),
                    out_len=int(o), group_size=group_size) for p, o in pairs]


def open_schedule(mix: dict, seconds: float, seed: int, vocab: int):
    """Open loop at a fixed rate: (ramp, window) request lists. Request i of
    a phase is due at (i + u_i) / rate, u_i in [0, 1): exactly
    round(rate * length) arrivals in every phase of every run. Three phases,
    each a fixed trace of its own: the ramp, the window's measured requests
    (due before ``seconds - tail_seconds``) and its tail. ``--seed`` draws
    the token ids and rotates the measured trace (request i is element
    (i + k) mod n of it, with that element's jitter): the same sizes and
    arrivals in another order, the same multiset measured."""
    rng = np.random.default_rng(int(seed))
    rate = float(mix["rate_per_s"])
    tail = float(mix["tail_seconds"])

    def phase(n, t0, measured, salt, turn):
        pairs, jitter = trace(mix, n, salt)
        k = int(rng.integers(n)) if turn and n else 0
        pairs, jitter = np.roll(pairs, -k, axis=0), np.roll(jitter, -k)
        reqs = _requests(pairs, rng, vocab)
        for i, r in enumerate(reqs):
            r.due = t0 + (i + float(jitter[i])) / rate
            r.measured = measured
        return reqs

    n_meas = int(round(rate * max(seconds - tail, 0.0)))
    n_tail = int(round(rate * seconds)) - n_meas
    ramp = phase(int(round(rate * float(mix["ramp_seconds"]))), 0.0, False,
                 0, False)
    window = (phase(n_meas, 0.0, True, 1, True)
              + phase(n_tail, n_meas / rate, False, 2, False))
    return ramp, window


def closed_pool(mix: dict, seed: int, vocab: int) -> List[Request]:
    """Closed loop: a pool of ``pool_size`` requests (or groups) with the
    quantile lengths in the mix's fixed order; clients take the next one on
    completion and the pool is cycled. The seed draws the token ids and
    where in the pool the clients start."""
    rng = np.random.default_rng(int(seed))
    n = int(mix["pool_size"])
    pairs, _ = trace(mix, n, 0)
    return _requests(np.roll(pairs, -int(rng.integers(n)), axis=0), rng,
                     vocab, group_size=int(mix.get("group_size", 1)))


def totals(reqs: List[Request]) -> dict:
    return {"requests": len(reqs),
            "prompt_tokens": sum(len(r.prompt) for r in reqs),
            "output_tokens": sum(r.out_len * r.group_size for r in reqs)}
