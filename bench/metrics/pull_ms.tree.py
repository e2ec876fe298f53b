"""Device time per pull in the traced window: the seconds of the solve's
``transition.pull`` phase and its ``transition.pull.*`` sub-phases,
over the pulls that ran, which are the runs of the sub-phases
(``rec["phases"]``, from ``bench/trace_phases.py``).  ``transition.pull``
alone is no count of pulls: it also holds the branch that skips the pull
in the other transitions.  A program without the sub-phases reports
nothing."""

PULL = "transition.pull"


def read(rec):
    phases = rec.get("phases")
    if rec.get("trace") is None or not phases:
        return None
    subs = [v for p, v in phases.items() if p.startswith(PULL + ".")]
    runs = max((r for _, r in subs), default=0)
    if not runs:
        return None
    seconds = sum(s for s, _ in subs) + phases.get(PULL, [0.0])[0]
    return 1e3 * seconds / runs
