"""A ratio the program counts step by step: ``args.stat`` (``median``) over
the spans named ``args.span`` in the traced part of the window (see
``program_span.py``) of attr ``args.num`` over attr ``args.den``, times the
configuration file's ``args.times_config`` where given. None where no such
span carries both attrs with a ``den`` above 0, or the configuration lacks
the key."""

import statistics

from .program_span import recorded

STATS = {"median": statistics.median}


def read(r, args):
    times = 1.0
    if "times_config" in args:
        if args["times_config"] not in r.config_file:
            return None
        times = float(r.config_file[args["times_config"]])
    v = [s.attrs[args["num"]] / s.attrs[args["den"]]
         for s in recorded(r)
         if s.name == args["span"] and args["num"] in s.attrs
         and s.attrs.get(args["den"], 0) > 0]
    return times * STATS[args["stat"]](v) if v else None
