"""75th percentile, over the measured requests, of (return of the step that
produced the first token - time the request was due). A measured request
with no first token by the end of the window is failed and enters at the
wait it had reached. The 90th percentile of the same waits is the per-layer
metric ``ttft_p90_ms.ttft`` (``readers/ttft_percentile.py``): over ~340
requests it rests on 34 and did not repeat within the contract's widest
bound (PERF.md, section 6)."""

import numpy as np


def waits_ms(w):
    return [1e3 * ((s.token_t[0] if s.token_t else w.t1) - s.due_t)
            for s in w.served if s.measured and s.due_t >= w.t0]


def read(w, ctx):
    return float(np.percentile(waits_ms(w), 75))


def summary(w) -> dict:
    """Other statistics of the same waits, for the log: what the sweep and
    a reviewer read beside the judged one."""
    v = np.sort(waits_ms(w))
    if not v.size:
        return {}
    out = {f"p{q}": float(np.percentile(v, q)) for q in (50, 75, 90, 95)}
    out["mean"] = float(v.mean())
    out["slowest_tenth_mean"] = float(v[-max(1, v.size // 10):].mean())
    out["n"] = int(v.size)
    return out
