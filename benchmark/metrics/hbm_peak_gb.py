"""The device's own counter: memory_stats()["peak_bytes_in_use"] after the
window, on the fullest chip, in GB (1e9 bytes)."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
