"""Share of its roofline the fused step of a model of shortcut-connected
expert blocks reached, over the traced part of the window: the least time the
chip could take for the steps recorded there (``costs/fused_step_scmoe.py``,
step by step) over the device time the trace shows for the same program.

A step's tokens in use, the held expert banks its layers touched and the
pairs they computed are the program's own, attrs ``used``,
``experts_touched`` and ``local_pairs`` of its ``engine.step`` span; the
tokens sampled and the cache held by the decoding rows are the host loop's
(``traced_steps``). The two lists are of the same steps; where one is a step
longer they are aligned at the window's end. None where the configuration
has no such block, where the run has no trace, or where the program records
no such attr (a commit from before it).
"""

from ..costs import fused_step_scmoe
from . import module_time
from .program_span import recorded


def read(r, args):
    if not {"zero_expert_num", "held_experts"} <= set(r.config_file):
        return None
    d = module_time.runs(r, args)
    spans = [s.attrs for s in recorded(r)
             if s.name == args.get("span", "engine.step")
             and {"used", "experts_touched", "local_pairs"} <= set(s.attrs)]
    n = min(len(spans), len(r.traced_steps))
    if not d or not n:
        return None
    least = sum(fused_step_scmoe.least_seconds(
        r.config_file, r.peaks, a["used"], h["sampled"], h["contexts"],
        a["experts_touched"], a["local_pairs"])
        for a, h in zip(spans[-n:], r.traced_steps[-n:]))
    # the host may have recorded a step more or fewer than the trace holds
    return 100.0 * least * (len(d) / n) / (sum(d) / 1e9)
