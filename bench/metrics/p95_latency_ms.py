"""95th-percentile latency of all requests of the window, due time to
result; a request that failed or never came counts as late as the
harness waited for it."""
import numpy as np


def read(rec):
    if rec["loop"] != "open" or not rec["queries"]:
        return None
    return 1e3 * float(np.percentile([q["latency_s"] for q in rec["queries"]],
                                     95))
