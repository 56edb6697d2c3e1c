"""engine.retraces_in_window: traces of the cost-evaluation executables
inside the window, all executables together (``cim_engine_traces_total``,
counted by each executable's traced body: a new jobs-per-dispatch count
or shape, or a load from the persistent compile cache)."""
from _common import delta

FAMILY = "cim_engine_traces_total"


def read(run):
    keys = [k for k in run.reg1 if k.split("{")[0] == FAMILY]
    if not keys:
        return None
    return sum(delta(run, k) for k in keys)
