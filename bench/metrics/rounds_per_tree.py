"""Mean relaxation rounds per tree (the solver's exact ``n_rounds``)."""


def read(rec):
    trees = rec["trees"]
    if not trees:
        return None
    return sum(t["n_rounds"] for t in trees) / len(trees)
