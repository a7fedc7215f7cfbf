"""How unevenly a training step's routed rows fall on the experts held:
the busiest held expert's rows over the mean held expert's rows, a step
and routed layer, from the model's training counters over the whole
process (``moe_train_busiest_rows_total`` over ``moe_train_held_picks_total``
/ ``experts_held``). 1 is even; the grouped matmul's pace follows the
busiest. A program without the counters reads nothing."""


def read(run):
    c = run.get("train_counters") or {}
    held = c.get("moe_train_held_picks_total")
    if not held or not c.get("moe_train_busiest_rows_total"):
        return None
    return (c["moe_train_busiest_rows_total"]
            * run["config"]["model"]["experts_held"] / held)
