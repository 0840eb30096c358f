"""device_idle_pct.drain: share of the traced closed-loop window in which no
operation ran on the chip (1 - union of op intervals / window)."""


def read(run):
    if run.trace is None or run.loop != "closed":
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
