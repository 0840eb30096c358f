"""ingest.device_ms: device milliseconds of the ingest program
(``jit_ingest_step``: append plus BAD-index maintenance, ``predicate_filter``
inside) per execution of the traced window."""

PROGRAM = "jit_ingest_step"


def read(run):
    if run.trace is None or not run.execs:
        return None
    m = run.trace.modules.get(PROGRAM)
    if not m:
        return None
    return m[1] / len(run.execs) * 1e3
