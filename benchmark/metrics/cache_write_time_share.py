"""Share of device busy time the decode step spends writing the new K/V
rows into its cache, %: inside the executions of the engine's decode-step
program (named in the configuration file), the ops labelled ``while...``
(the serial loop over slots that XLA makes of a per-slot scatter) or
``cache_write_rows`` (the program's one batched kernel). Only inside that
program, because a prefill may hold a ``while`` of its own (a state-space
layer's scan over chunks). The decode step has no other loop."""
from benchmark.lib import trace


def read(run):
    r = run["reduced"]
    if r is None or not r["busy_s"]:
        return None
    step = run["config"]["serve"]["programs"]["decode_step"]
    devs = trace.device_planes(run["planes"])
    ns = 0
    for p in devs:
        steps = [(s, s + d) for name, s, d
                 in trace.line_events(p, trace.MODULE_LINE) if step in name]
        writes = [(s, s + d) for name, s, d
                  in trace.line_events(p, trace.OP_LINE)
                  if name.startswith("while") or "cache_write_rows" in name]
        ns += trace.overlap(writes, steps)
    return 100.0 * ns / len(devs) / 1e9 / r["busy_s"]
