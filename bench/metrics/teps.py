"""Input edges times trees completed, over the whole window (Graph500)."""


def read(rec):
    if rec["loop"] != "closed" or not rec["trees"]:
        return None
    return rec["edges"] * len(rec["trees"]) / rec["window_s"]
