"""Host-clock time of the window's solves (each to ``block_until_ready``),
summed, over their rounds summed."""


def read(rec):
    rounds = sum(t["n_rounds"] for t in rec["trees"])
    if not rounds:
        return None
    return 1e3 * sum(t["seconds"] for t in rec["trees"]) / rounds
