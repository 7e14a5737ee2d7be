"""Engine steps from a measured request's submit to its first token,
``args.percentile``: a count, so it repeats with the schedule."""

import numpy as np


def read(r, args):
    n = [s.first_step - s.submit_step for s in r.window.served
         if s.measured and s.due_t >= r.window.t0 and s.first_step]
    return float(np.percentile(n, args["percentile"])) if n else None
