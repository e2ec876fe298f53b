"""Mean relaxation rounds of the served answers (the batch's ``n_rounds``
as each slot reports it)."""


def read(rec):
    rounds = [q["n_rounds"] for q in rec["queries"] if q["ok"]]
    if rec["loop"] != "open" or not rounds:
        return None
    return sum(rounds) / len(rounds)
