"""Share of its roofline the fused step of a model of gated delta-rule (KDA)
and gated GQA layers over a share of the experts reached, over the traced
part of the window: the least time the chip could take for the steps
recorded there (``costs/fused_step_kda_moe.py``, step by step) over the
device time the trace shows for the same program.

A step's tokens in use, the rows whose matrix state it advanced, the entries
its chunked form took, the held expert banks its layers touched, the pairs
they computed and the blocks its group items read once for several rows are
the program's own, attrs ``used``, ``ssm_rows``, ``kda_chunk_entries``,
``experts_touched``, ``local_pairs``, ``kv_blocks_saved`` and
``block_size`` of its ``engine.step`` span; the tokens sampled and the KV
held by the decoding rows are the host loop's (``traced_steps``). The two
lists are of the same steps; where one is a step longer they are aligned at
the window's end. None where the configuration is no such model, where the
run has no trace, or where the program records no such attr (a commit from
before it).
"""

from ..costs import fused_step_kda_moe
from . import module_time
from .program_span import recorded

ATTRS = ("used", "ssm_rows", "kda_chunk_entries", "experts_touched",
         "local_pairs")


def read(r, args):
    if r.config_file.get("model_type") != "solar_open2":
        return None
    d = module_time.runs(r, args)
    spans = [s.attrs for s in recorded(r)
             if s.name == args.get("span", "engine.step")
             and all(a in s.attrs for a in ATTRS)]
    n = min(len(spans), len(r.traced_steps))
    if not d or not n:
        return None
    least = sum(fused_step_kda_moe.least_seconds(
        r.config_file, r.peaks, a["used"], h["sampled"], h["contexts"],
        min(a.get("kv_blocks_saved", 0) * a.get("block_size", 0),
            h["contexts"]),
        a["ssm_rows"], a["kda_chunk_entries"], a["experts_touched"],
        a["local_pairs"])
        for a, h in zip(spans[-n:], r.traced_steps[-n:]))
    # the host may have recorded a step more or fewer than the trace holds
    return 100.0 * least * (len(d) / n) / (sum(d) / 1e9)
