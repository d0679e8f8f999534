"""``photon_compiles_total`` summed over ``fn``: the window's end minus its
start."""


def read(run, params):
    return float(run["compiles_in_window"])
