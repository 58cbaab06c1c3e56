"""The query scan's share of the HBM roofline, in %: the least time the
chip needs to read what the window's query dispatches must read (4 B per
live row of each fact column each query reads, plus the dimension
attribute columns; once per dispatch, however many requests it batches),
at the chip's peak HBM bandwidth, over the device's busy time."""
from benchkit.ssbq import scan_bytes


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0 or not ctx["dispatches"]:
        return None
    need = sum(scan_bytes(name, n_fact, ctx["dim_rows"])
               for _, name, _, n_fact in ctx["dispatches"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / t["busy_s"]
