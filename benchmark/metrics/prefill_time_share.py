"""Share of device busy time inside executions of the engine's batch-1
whole-prompt prefill programs (one per length bucket), %."""
from benchmark.lib import trace


def read(run):
    r = run["reduced"]
    if r is None or not r["busy_s"]:
        return None
    name = run["config"]["serve"]["programs"]["prefill"]
    return 100.0 * trace.module_stats(r, name)[1] / r["busy_s"]
