"""Share of the traced window in which no op ran on the device."""


def read(rec):
    trace = rec["trace"]
    if trace is None or rec["loop"] != "open":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
