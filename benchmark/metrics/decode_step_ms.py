"""Device time of one execution of the engine's decode-step program (all
slots, one token each), from the device trace's program line, ms. The
program's name is in the configuration file."""
from benchmark.lib import trace


def read(run):
    r = run["reduced"]
    if r is None:
        return None
    name = run["config"]["serve"]["programs"]["decode_step"]
    count, seconds = trace.module_stats(r, name)
    return 1e3 * seconds / count if count else None
