"""Milliseconds per epoch in which the device was idle: wall time of the
traced window per epoch minus the device-busy time per epoch, i.e. the
drivers, the digest fetch and the host control plane."""


def read(ctx):
    tr, epochs = ctx["trace"], ctx["counters"].get("epochs", 0)
    if tr is None or not epochs:
        return None
    return 1000.0 * (tr.window_s - tr.busy_s) / epochs
