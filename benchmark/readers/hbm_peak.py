"""``peak_bytes_in_use`` of the fullest device after the window, in GiB."""


def read(run, params):
    peak = run["memory_peak_bytes"]
    return peak / 2**30 if peak else None
