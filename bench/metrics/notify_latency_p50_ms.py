"""notify_latency_p50_ms: median over every record of an open-loop window
that notified someone, of the host time from its creation on the
generator's schedule to the materialisation of its execution."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.latencies_ms:
        return None
    return float(np.percentile(run.latencies_ms, 50))
