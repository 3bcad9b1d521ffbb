#!/usr/bin/env python
"""Quickstart: a BW-Raft cluster serving a strongly-consistent KV store.

    PYTHONPATH=src python examples/quickstart.py

Spins up the paper's 4-site geo-distributed cluster in-process, elects a
leader, leases spot secretaries/observers, then does consistent puts/gets
through the BW-KV client API (Listing 1) while spot instances fail.
"""
import numpy as np

from repro.configs.bwraft_kv import CONFIG
from repro.core.runtime import BWRaftSim
from repro.core import state as SM
from repro.kvstore.service import BWKVService
from repro import compile_cache


def main():
    compile_cache.enable()
    print("=== BW-Raft quickstart ===")
    sim = BWRaftSim(CONFIG, write_rate=2.0, read_rate=8.0, seed=0)
    svc = BWKVService(sim)

    svc._step(120)
    lid = int(SM.leader_id(sim.state, sim.static))
    print(f"leader elected: node {lid} "
          f"(site {CONFIG.sites[sim.static['site'][lid]].name})")

    sim._lease(3, 4)
    roles = np.asarray(sim.state["role"])
    print(f"leased {int((roles == SM.SECRETARY).sum())} secretaries, "
          f"{int((roles == SM.OBSERVER).sum())} observers on spot slots")

    r = svc.put("paper/title", 2022)
    print(f"put(paper/title)=2022 committed at revision {r.revision} "
          f"in {r.latency_ticks} ticks ({r.latency_ticks * 10} ms simulated)")
    v, rev = svc.get("paper/title")
    print(f"get(paper/title) -> {v} @ readindex {rev}")

    # kill every spot node — Property 3.4: consensus unaffected
    sim.set_rates(phi=1.0)
    svc._step(5)
    sim.set_rates(phi=0.0)
    spot = ~np.asarray(sim.static["is_voter"])
    alive = np.asarray(sim.state["alive"])
    assert not alive[spot].any() and alive[~spot].all()
    print(f"phi=1: every spot node revoked, all {int((~spot).sum())} "
          f"voters alive")
    r2 = svc.put("paper/venue", 42)
    v2, _ = svc.get("paper/venue")
    print(f"after revoking ALL spot instances: put/get still works -> {v2} "
          f"(BW-Raft degraded to plain Raft, then re-leases)")
    print("OK")


if __name__ == "__main__":
    main()
