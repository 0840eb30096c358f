"""notify_latency_p95_ms: the 95th percentile of the same latencies as
notify_latency_p50_ms, over every notifying record of the window."""
import numpy as np


def read(run):
    if run.loop != "open" or not run.latencies_ms:
        return None
    return float(np.percentile(run.latencies_ms, 95))
