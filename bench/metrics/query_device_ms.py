"""Device busy time per query completed in the traced window, in ms."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx["queries_done"]:
        return None
    return 1e3 * t["busy_s"] / ctx["queries_done"]
