"""Share of the traced busy time spent in the device operations of the
multi-stream residual path (manifold-constrained hyper-connections), %.

The path is plain XLA, no kernel with a name of its own, so its operations
are told by what only they produce: a result whose trailing dimensions are
the stream's, with ``n = hc_mult`` and ``C = hidden_size`` of the
configuration file, behind at least one leading (token) dimension —
``(., n, C)`` the mixed stream, ``(., n C)`` its flattened rows,
``(., n (n + 2))`` the maps' projection, ``(., n n)`` and ``(., n, n)`` the
mixing map — as ``trace_reduce`` prints a result's shape at the end of an
operation's name (``fusion.12_bf16_192_1_4_3584_``). The small ones count
only as float32. A LOWER bound: the fusions of the Sinkhorn rounds and of
the norm whose result is one value a token carry no such shape and are not
counted (PERF.md, section 7).

None where the run has no trace, the configuration has no ``hc_mult``, or
the trace has no such operation (a program from before the path)."""

import re


def patterns(n: int, c: int) -> list:
    lead = r"(?:\d+_)+"
    return [re.compile(rf"_(?:bf16|f32)_{lead}{n}_{c}_$"),
            re.compile(rf"_(?:bf16|f32)_{lead}{n * c}_$"),
            re.compile(rf"_f32_{lead}{n * (n + 2)}_$"),
            re.compile(rf"_f32_{lead}{n * n}_$"),
            re.compile(rf"_f32_{lead}{n}_{n}_$")]


def read(r, args):
    n = r.config_file.get("hc_mult")
    if r.trace is None or not n or r.trace.busy_s <= 0:
        return None
    pats = patterns(int(n), int(r.config_file["hidden_size"]))
    found = [t for name, t in r.trace.ops.items()
             if any(p.search(name) for p in pats)]
    if not found:
        return None
    return 100.0 * sum(found) / r.trace.busy_s
