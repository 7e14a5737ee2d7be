"""What decides ``correct`` for a served model.

After the window has closed and the engine is freed, every request the
window finished is teacher-forced through the plain reference (the module
under ``reference/`` that the configuration file's ``reference`` key names,
``decoder`` where it has none): prompt and
served tokens in, log p of every served token out. The engine sampled its
tokens (temperature, top-p), and its sampling is engine-wide, so the served
token is not the reference's argmax and the contract's greedy-gap cannot be
read; what the engine reports beside every token is its log p under the
unmodified distribution, and that is compared: the largest and the mean
|engine log p - reference log p| over all of them. Each number printed
beside its limit; limits and the readings they were set from are in
``cells/<workload>.json``.

The requests go through the reference in blocks of equal padded length
(multiples of ``PAD``) holding about ``BLOCK_TOKENS`` tokens, so that it
compiles one program per padded length and nothing larger than a block is
ever live.

Two controls, never in the benchmark's own runs: ``--control fp8`` puts the
reference, rounded through the next lower precision, in the engine's place
at the same positions; ``--engine-kv-dtype fp8`` runs the engine itself with
its own lower-precision path switched on, through the timed path.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import List

import numpy as np

PAD = 128
BLOCK_TOKENS = 4096


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit

    def line(self) -> str:
        return (f"compared {self.name}: {self.value:.6g} against limit "
                f"{self.limit:.6g}: {'ok' if self.ok else 'NOT ok'}")


def blocks(finished: list) -> list:
    """(padded length, requests) blocks: requests sorted by length, grouped
    by padded length, at most ``BLOCK_TOKENS // padded length`` to a block."""
    by_len = {}
    for s in sorted(finished, key=lambda s: (len(s.req.prompt)
                                             + len(s.tokens), s.rid)):
        n = len(s.req.prompt) + len(s.tokens)
        by_len.setdefault(-(-n // PAD) * PAD, []).append(s)
    out = []
    for pad, reqs in sorted(by_len.items()):
        rows = max(1, BLOCK_TOKENS // pad)
        out += [(pad, reqs[i:i + rows]) for i in range(0, len(reqs), rows)]
    return out


def reference_logps(weights, cfg: dict, finished: list, n_pos: int,
                    quant=None) -> list:
    """The reference's log p of every served token, one array a request, in
    the order of ``blocks``."""
    served_logps = importlib.import_module(
        f"benchmark.reference.{cfg.get('reference', 'decoder')}").served_logps
    out = []
    for pad, reqs in blocks(finished):
        rows = max(1, BLOCK_TOKENS // pad)
        toks = np.zeros((rows, pad), np.int32)
        starts = np.zeros((rows,), np.int32)
        for i, s in enumerate(reqs):
            seq = list(s.req.prompt) + list(s.tokens)
            toks[i, :len(seq)] = seq
            starts[i] = len(s.req.prompt) - 1
        ref = np.asarray(served_logps(weights, cfg, toks, starts, n_pos,
                                      quant=quant))
        out += [(s, ref[i, :len(s.tokens)]) for i, s in enumerate(reqs)]
    return out


def served_model(weights, cfg: dict, finished: list, limits: dict,
                 n_pos: int, control: str = "") -> List[Compared]:
    ref = reference_logps(weights, cfg, finished, n_pos)
    diffs = np.concatenate([np.abs(r - np.asarray(s.logps, np.float32))
                            for s, r in ref]) if ref else np.array([np.inf])
    out = [Compared("served_logp_gap_mean", float(diffs.mean()),
                    float(limits["served_logp_gap_mean"])),
           Compared("served_logp_gap_max", float(diffs.max()),
                    float(limits["served_logp_gap_max"])),
           Compared("served_tokens_compared_min", -float(diffs.size),
                    -float(limits["served_tokens_min"]))]
    if control:
        low = reference_logps(weights, cfg, finished, n_pos, quant=control)
        cd = np.concatenate([np.abs(r - l)
                             for (_s, r), (_s2, l) in zip(ref, low)])
        out += [Compared(f"control_{control}_logp_gap_mean",
                         float(cd.mean()),
                         float(limits["served_logp_gap_mean"])),
                Compared(f"control_{control}_logp_gap_max", float(cd.max()),
                         float(limits["served_logp_gap_max"]))]
    return out
