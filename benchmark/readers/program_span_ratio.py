"""A ratio the program counts where the work is put together: 100 x the
sum of attr ``args.num`` over the sum of attr ``args.den``, over the
spans named ``args.span`` that the program's tracer recorded in the
traced part of the window (see ``program_span.py``). None where no such
span carries both."""

from .program_span import recorded


def read(r, args):
    num = den = 0.0
    for s in recorded(r):
        if (s.name == args["span"] and args["num"] in s.attrs
                and args["den"] in s.attrs):
            num += s.attrs[args["num"]]
            den += s.attrs[args["den"]]
    return 100.0 * num / den if den else None
