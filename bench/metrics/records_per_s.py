"""records_per_s: records of every execution dispatched in a closed-loop
window over the host seconds from its first ingest to the materialisation of
its last execution, the spill drains that fell due in between included."""


def read(run):
    if run.loop != "closed":
        return None
    t0, t1 = run.window
    return run.records / (t1 - t0)
