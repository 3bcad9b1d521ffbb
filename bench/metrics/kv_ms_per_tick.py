"""Wall milliseconds per simulated tick the KV service stepped in the
window: the service loop's cost per step of the cluster."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ticks"):
        return None
    return 1000.0 * c["wall_s"] / c["ticks"]
