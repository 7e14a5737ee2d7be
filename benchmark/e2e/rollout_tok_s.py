"""Output tokens emitted inside the window per second, all requests."""


def read(w, ctx):
    return sum(s[2] for s in w.steps) / (w.t1 - w.t0)
