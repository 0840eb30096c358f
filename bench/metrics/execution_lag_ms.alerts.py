"""execution_lag_ms.alerts: mean over an open-loop window's executions of
the host time from its due time (the creation of its last record) to the
materialisation of its reports: the tick loop's share of the latency."""


def read(run):
    done = [e for e in run.execs if e.due is not None and e.done]
    if run.loop != "open" or not done:
        return None
    return sum(e.done - e.due for e in done) / len(done) * 1e3
