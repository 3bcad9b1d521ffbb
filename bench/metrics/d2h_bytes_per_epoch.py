"""Device-to-host bytes the fleet fetched per epoch in the window
(`FleetSim.d2h_bytes`, the digest path's counter)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("epochs") or "d2h_bytes" not in c:
        return None
    return c["d2h_bytes"] / c["epochs"]
