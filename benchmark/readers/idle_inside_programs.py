"""Idle time INSIDE the device's programs over the traced window, %: 100 x
(the summed durations of every program's runs on the ``XLA Modules`` line -
``busy_s``, the union of the device-op intervals) / window. Between two ops
of one program the device waits on nothing the host does, so no host change
reaches this part of ``device_idle_share``; the rest of that share is the
gaps between programs (``idle_gap_ms``). A run that straddles the window's
edge is no run of ``modules`` while its ops inside the window are busy: the
share then reads slightly under 0 (-0.13% seen once), which is that edge and
no fault of the trace. None where the run has no trace or the trace no run of
a program."""


def read(r, args):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    runs_s = sum(d for runs in r.trace.modules.values()
                 for _s, d in runs) / 1e9
    if runs_s <= 0:
        return None
    return 100.0 * (runs_s - r.trace.busy_s) / r.trace.window_s
