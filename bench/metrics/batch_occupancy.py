"""Requests served by the device per vmapped dispatch in the window, from
``QueryScheduler.info()`` deltas (answers from maintained views excluded)."""


def read(ctx):
    a, b = ctx["sched"]
    batches = b["batches"] - a["batches"]
    served = (b["completed"] - a["completed"]
              - (b["maintained_served"] - a["maintained_served"]))
    return served / batches if batches else None
