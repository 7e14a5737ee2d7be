"""Compiles inside the window: the delta of ``compiles`` over the program's
``obs/runtime_profile`` ledgers named in ``args.ledgers``."""


def read(r, args):
    return float(sum(r.compiles1.get(k, 0) - r.compiles0.get(k, 0)
                     for k in args["ledgers"]))
