"""``args.percentile`` of the measured requests' waits for a first token
(the waits ``e2e/ttft_p75_ms.py`` takes its percentile of)."""

import numpy as np

from ..e2e.ttft_p75_ms import waits_ms


def read(r, args):
    v = waits_ms(r.window)
    return float(np.percentile(v, args["percentile"])) if v else None
