"""Share of the traced window in which no operation ran on the device; with
several chips, the idlest one's."""


def read(run, params):
    t = run["trace"]
    if not t["per_chip"] or t["window_s"] <= 0:
        return None
    busy = min(c["busy_s"] for c in t["per_chip"])
    return 100.0 * (1.0 - busy / t["window_s"])
