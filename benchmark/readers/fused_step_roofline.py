"""Share of its roofline the fused step reached, over the traced part of the
window: the least time the chip could take for the steps the host recorded
there (``costs/fused_step.py``: the larger of operations over peak FLOP/s
and bytes over peak bytes/s, step by step) over the device time the trace
shows for the same program. Prefill tokens are known for the traced part as
a whole (``engine.stats()``), so they are spread evenly over its steps."""

from ..costs import fused_step
from . import module_time


def read(r, args):
    d = module_time.runs(r, args)
    steps = r.traced_steps
    if not d or not steps:
        return None
    prefill = r.traced_prefill_tokens / len(steps)
    least = sum(fused_step.least_seconds(r.config_file, r.peaks,
                                         s["decode"] + prefill,
                                         s["sampled"], s["contexts"])
                for s in steps)
    # the host may have recorded a step more or fewer than the trace holds
    scale = len(d) / len(steps)
    return 100.0 * least * scale / (sum(d) / 1e9)
