"""Device time per step transition in the traced window: the seconds of
the solve's ``sssp.transition`` phase and its ``transition.*``
sub-phases (the pull phase among them), over the times
``sssp.transition`` ran (``rec["phases"]``, from
``bench/trace_phases.py``)."""
from bench import trace_phases


def read(rec):
    phases = rec.get("phases")
    if rec.get("trace") is None or not phases:
        return None
    got = trace_phases.group(phases, "sssp.transition")
    if got is None or not got[1]:
        return None
    return 1e3 * got[0] / got[1]
