"""Share of its roofline the fused step of a SambaY decoder (layers of five
kinds: Mamba-1 mixers, window and full differential attention, gated memory
units, cross attention over the full layer's KV) reached, over the traced
part of the window: the least time the chip could take for the steps
recorded there (``costs/fused_step_sambay.py``, step by step) over the
device time the trace shows for the same program.

A step's tokens in use, its decoding rows, the rows whose recurrent state it
advanced and the blocks its group items read once for several rows are the
program's own, attrs ``used``, ``decode_rows``, ``ssm_rows``,
``kv_blocks_saved`` and ``block_size`` of its ``engine.step`` span; the
tokens sampled and the KV held by the decoding rows are the host loop's
(``traced_steps``). A window layer's columns are taken as the window for
every decoding row, never more than the rows hold. The two lists are of
the same steps; where one is a step longer they are aligned at the window's
end. None where the configuration is no such decoder, where the run has no
trace, or where the program records no such attr (a commit from before it).
"""

from ..costs import fused_step_sambay
from . import module_time
from .program_span import recorded

ATTRS = ("used", "decode_rows", "ssm_rows", "kv_columns_window")


def read(r, args):
    if r.config_file.get("model_type") != "phi4flash":
        return None
    d = module_time.runs(r, args)
    spans = [s.attrs for s in recorded(r)
             if s.name == args.get("span", "engine.step")
             and all(a in s.attrs for a in ATTRS)]
    n = min(len(spans), len(r.traced_steps))
    if not d or not n:
        return None
    window = r.config_file["sliding_window"]
    least = sum(fused_step_sambay.least_seconds(
        r.config_file, r.peaks, a["used"], h["sampled"], h["contexts"],
        min(a.get("kv_blocks_saved", 0) * a.get("block_size", 0),
            h["contexts"]),
        min(window * a["decode_rows"], h["contexts"]), a["ssm_rows"])
        for a, h in zip(spans[-n:], r.traced_steps[-n:]))
    # the host may have recorded a step more or fewer than the trace holds
    return 100.0 * least * (len(d) / n) / (sum(d) / 1e9)
