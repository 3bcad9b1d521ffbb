"""Driver: a fleet of replicates stepped epoch by epoch, with the host
control plane (Algorithm 1 and MCSA leasing) at every epoch boundary.

Cell parameters: `members` (B), `check_block` (how many members the
reference replays at a time), `warmup_epochs`.  An operation is one
member-epoch.
"""
from __future__ import annotations

import jax

import fleetcheck


class Driver(fleetcheck.FleetDriver):
    spans = ("epoch",)
    manage = True
    epochs = 0

    def warmup(self) -> None:
        for _ in range(self.cell["warmup_epochs"]):
            self.step()

    def step(self) -> int:
        with jax.profiler.TraceAnnotation("epoch"):
            self.fleet.run_epoch()
            jax.block_until_ready(self.fleet.state)
        self.epochs += 1
        return 1

    def schedule(self) -> list:
        return [("epoch", True)] * self.epochs
