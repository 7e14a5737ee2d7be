"""1 - busy over the traced window, %: busy is the union of the device-op
intervals, averaged over the chips used."""


def read(r, args):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
