"""1 - union of the op intervals on the device's op line over the traced
slice, averaged over the chips, %."""


def read(run):
    r = run["reduced"]
    if r is None or not r["window_s"]:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
