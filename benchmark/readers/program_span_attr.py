"""One attr the program sets step by step: ``args.stat`` (``max``) of attr
``args.attr`` over the spans named ``args.span`` that the program's tracer
recorded in the traced part of the window (see ``program_span.py``). None
where no such span carries the attr (a program from before it, or a
configuration that does not set it)."""

from .program_span import recorded

STATS = {"max": max}


def read(r, args):
    v = [s.attrs[args["attr"]] for s in recorded(r)
         if s.name == args["span"] and args["attr"] in s.attrs]
    return float(STATS[args["stat"]](v)) if v else None
