"""Share of the traced busy time spent in the device operations of the
state-space mixer (conv with its window, selective scan over the rows'
states, gated norm, the two projections), %.

The mixer is plain XLA, no kernel with a name of its own, so its operations
are told by what only they produce: a result whose trailing dimensions are
the mixer's, from the configuration file's keys (``H = mamba_n_heads``,
``P = mamba_d_head``, ``N = mamba_d_state``, ``G = mamba_n_groups``,
``I = mamba_d_ssm``, ``K = mamba_d_conv``, ``C = I + 2 G N``), behind any
leading dimensions — ``(., H, P, N)`` the rows' states, ``(., I + C + H)``
the input projection, ``(., H, P)`` the scan's inputs and outputs by head,
``(., G, N)`` B and C by group, ``(., I)`` the gated norm, ``(., K-1, C)``
the conv's windows, ``(H, T, T)`` the decays between a step's entries — as
``trace_reduce`` prints a result's shape at the end of an operation's name
(``fusion.12_f32_5_56_32_128_256_``). A shape that another part of the
model has too is left out (it has to differ from the hidden size, the MLP's
width and the attention's projections), and so is every Pallas kernel
(``paged_*``: attention's pads its heads to a multiple of 16).

A LOWER bound. What it cannot see: fusions whose result is as wide as the
hidden size (the output projection with the residual add; the conv's output
where ``C`` equals the hidden size, as in Falcon-H1-34B), the step sizes
``(., H)`` and anything XLA fuses into a consumer of another shape.

None where the run has no trace, the configuration has no mixer, or the
trace has no such operation (a program from before the mixer)."""

import re


def patterns(cfg: dict) -> list:
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    g, i, k = cfg["mamba_n_groups"], cfg["mamba_d_ssm"], cfg["mamba_d_conv"]
    c = i + 2 * g * n
    taken = {cfg["hidden_size"], cfg["intermediate_size"],
             cfg["num_attention_heads"] * cfg["head_dim"],
             cfg["num_key_value_heads"] * cfg["head_dim"],
             cfg["vocab_size"]}
    lead = r"(?:\d+_)*"
    tails = [f"{h}_{p}_{n}", f"{k - 1}_{c}"]
    tails += [str(w) for w in (i + c + h, i) if w not in taken]
    if (h, p) not in ((cfg["num_attention_heads"], cfg["head_dim"]),
                      (cfg["num_key_value_heads"], cfg["head_dim"])):
        tails.append(f"{h}_{p}")
    tails.append(f"{g}_{n}")
    pats = [re.compile(rf"_(?:bf16|f32)_{lead}{t}_$") for t in tails]
    pats.append(re.compile(rf"_f32_{h}_(\d+)_\1_$"))
    return pats


def read(r, args):
    if (r.trace is None or "mamba_d_ssm" not in r.config_file
            or r.trace.busy_s <= 0):
        return None
    pats = patterns(r.config_file)
    # the attention kernel pads its heads to a multiple of 16, so its result
    # can look like the scan's (., H, P): a kernel has a name of its own
    found = [t for name, t in r.trace.ops.items()
             if not name.startswith("paged_")
             and any(p.search(name) for p in pats)]
    if not found:
        return None
    return 100.0 * sum(found) / r.trace.busy_s
