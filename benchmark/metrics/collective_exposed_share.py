"""Collective time on a device during which no compute op runs there,
over the traced slice, % (averaged over the chips)."""


def read(run):
    r = run["reduced"]
    if r is None or not r["window_s"] or r["n_devices"] < 2:
        return None
    return 100.0 * r["collective_exposed_s"] / r["window_s"]
