"""predicate_filter_roofline: the ingest kernel's least time on this chip
(its operations and bytes from its shapes, ``bench/kernels.py``, over the
peaks of ``bench/peaks.py``) over its measured device time, in percent. The
kernel is the only custom call inside ``jit_ingest_step``, so the trace finds
it without a name."""
from bench.kernels import least_time_s, predicate_filter_cost

PROGRAM = "jit_ingest_step"


def read(run):
    if run.trace is None or not run.execs:
        return None
    c = run.trace.custom.get(PROGRAM)
    if not c or not c[0]:
        return None
    per_call = c[1] / c[0]
    cost = predicate_filter_cost(run.execs[0].records,
                                 len(run.cfg["schema"]),
                                 len(run.cfg["channels"]))
    least, _ = least_time_s(cost, run.device_kind)
    return 100.0 * least / per_call
