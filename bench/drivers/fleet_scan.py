"""Driver: a fleet of replicates with fixed roles, run E epochs per
device dispatch (the single-dispatch multi-epoch scan), so no host
control plane runs between epochs.

Set-up runs one epoch (leadership settles; the first election stops any
secretary), leases `roles` = (secretaries, observers) once per member,
then one dispatch of E to compile the scan.  Cell parameters: `members`
(B), `roles`, `epochs_per_dispatch` (E, at least 2), `check_block`.
An operation is one member-epoch.
"""
from __future__ import annotations

import jax

import fleetcheck


class Driver(fleetcheck.FleetDriver):
    spans = ("scan_dispatch",)
    manage = False
    dispatches = 0

    def warmup(self) -> None:
        assert self.fleet.single_dispatch_eligible
        self.fleet.run(1)
        jax.block_until_ready(self.fleet.state)
        self.fleet.lease_fixed(*self.cell["roles"])
        self.step()

    def step(self) -> int:
        E = self.cell["epochs_per_dispatch"]
        with jax.profiler.TraceAnnotation("scan_dispatch"):
            self.fleet.run(E)
            jax.block_until_ready(self.fleet.state)
        self.dispatches += 1
        return E

    def schedule(self) -> list:
        E = self.cell["epochs_per_dispatch"]
        return [("epoch", False), ("lease", *self.cell["roles"])] + \
            [("epoch", False)] * (E * self.dispatches)
