"""Share of device busy time the routed layer spends moving its picks'
rows between token order and expert order, %: the ops labelled
``moe_dispatch_rows`` or ``moe_combine_rows`` (``ops/moe_rows.py``, the
two row kernels, each the other's transpose, so the backward's are
counted under the same two names). A program without the kernels reads
nothing."""
from benchmark.lib import trace

ROW_KERNELS = ("moe_dispatch_rows", "moe_combine_rows")


def read(run):
    r = run["reduced"]
    if r is None or not r["busy_s"]:
        return None
    seconds = trace.kernel_seconds(r, ROW_KERNELS)
    if not seconds:
        return None
    return 100.0 * seconds / r["busy_s"]
