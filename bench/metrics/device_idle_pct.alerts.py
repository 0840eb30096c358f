"""device_idle_pct.alerts: share of the traced open-loop window in which no
operation ran on the chip (1 - union of op intervals / window)."""


def read(run):
    if run.trace is None or run.loop != "open":
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
