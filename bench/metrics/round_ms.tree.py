"""Device time per relaxation round in the traced window: the seconds of
the solve's ``sssp.round`` phase and its ``round.*`` sub-phases, over
the times ``sssp.round`` ran (``rec["phases"]``, from
``bench/trace_phases.py``)."""
from bench import trace_phases


def read(rec):
    phases = rec.get("phases")
    if rec.get("trace") is None or not phases:
        return None
    got = trace_phases.group(phases, "sssp.round")
    if got is None or not got[1]:
        return None
    return 1e3 * got[0] / got[1]
