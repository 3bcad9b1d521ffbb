"""Device-busy milliseconds per simulated epoch in the traced window
(`runtime.device_epoch`: the tick scan, the in-scan digest and the
compaction, plus the control plane's small device writes)."""


def read(ctx):
    tr, epochs = ctx["trace"], ctx["counters"].get("epochs", 0)
    if tr is None or not epochs:
        return None
    return 1000.0 * tr.busy_s / epochs
