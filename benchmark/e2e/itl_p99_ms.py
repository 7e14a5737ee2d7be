"""99th percentile of the gaps between successive tokens of one request,
over the gaps that end inside the window (requests of the ramp that are
still decoding included)."""

import numpy as np


def gaps_ms(w):
    out = []
    for s in w.served:
        t = np.asarray(s.token_t)
        if t.size >= 2:
            g = np.diff(t)
            out.append(1e3 * g[(t[1:] >= w.t0) & (t[1:] <= w.t1)])
    return np.concatenate(out) if out else np.zeros(0)


def read(w, ctx):
    return float(np.percentile(gaps_ms(w), 99))
