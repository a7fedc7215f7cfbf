"""Share of device busy time inside the three flash-attention kernels
(ops named flash_fwd, flash_dq, flash_dkv in the device trace), %."""
from benchmark.lib import flops, trace


def read(run):
    r = run["reduced"]
    if r is None or not r["busy_s"]:
        return None
    return 100.0 * trace.kernel_seconds(r, flops.FLASH_KERNELS) / r["busy_s"]
