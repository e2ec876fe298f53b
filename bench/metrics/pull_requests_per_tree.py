"""Mean pull requests per tree: the solver's ``n_pull_relax``, the
requests that its pull phases created (their part of ``n_relax``).  A
program without the counter reports nothing."""


def read(rec):
    trees = rec["trees"]
    if not trees or "n_pull_relax" not in trees[0]:
        return None
    return sum(t["n_pull_relax"] for t in trees) / len(trees)
