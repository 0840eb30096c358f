"""plan_group.device_ms: device milliseconds of the fused plan-group program
(``jit_run``: discovery, param and spatial join, broker convert and send,
retry ring) per execution of the traced window."""

PROGRAM = "jit_run"


def read(run):
    if run.trace is None or not run.execs:
        return None
    m = run.trace.modules.get(PROGRAM)
    if not m:
        return None
    return m[1] / len(run.execs) * 1e3
