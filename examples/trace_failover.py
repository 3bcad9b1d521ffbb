#!/usr/bin/env python
"""Traced failover drill (DESIGN.md §14): kill the leader with the
flight recorder armed, then read the story back three ways — the ASCII
timeline, the exact event ledger, and a Perfetto artifact you can drop
into https://ui.perfetto.dev.

The recorder runs INSIDE the compiled scan: events land in
device-resident ring buffers and cross to the host once per drain,
so arming it costs neither recompiles nor per-tick transfers.

    PYTHONPATH=src python examples/trace_failover.py [OUT.json]
"""
import sys
from collections import Counter

from repro.configs.bwraft_kv import CONFIG
from repro.market import kill_nodes, run_chaos
from repro.trace import EVENT_NAMES, timeline
from repro import compile_cache

TICKS = 160
KILL_TICK = 20


def main():
    compile_cache.enable()
    out = sys.argv[1] if len(sys.argv) > 1 else "trace_failover.json"
    faults = kill_nodes([0], KILL_TICK, n_nodes=CONFIG.max_nodes,
                        ticks=TICKS, name="leader-kill-traced")
    rep = run_chaos(CONFIG, faults, ticks=TICKS, seed=0, spot_bid=10.0,
                    check=False, trace_on=True, trace_capacity=4096,
                    trace_out=out)

    print(f"drill: {TICKS} ticks, node 0 killed at tick {KILL_TICK}")
    print(f"killed={rep.killed_total} "
          f"max_leaderless_span={rep.max_leaderless_span} "
          f"leader_uptime={rep.leader_uptime:.3f}")
    print(f"events decoded: {len(rep.events)} "
          f"(dropped: {rep.events_dropped})")
    by_code = Counter(e.code for e in rep.events)
    for code, n in sorted(by_code.items()):
        print(f"  {EVENT_NAMES[code]:<14} x{n}")

    # the trace must tell the same story the harness probed per tick
    assert rep.trace_leader_match, \
        "trace-replayed leader timeline diverged from the probe"
    print("\ntrace-replayed leader timeline == per-tick probe: OK\n")

    print(timeline.render(rep.events, ticks=TICKS))
    print(f"\nPerfetto artifact -> {out}  (open in ui.perfetto.dev; "
          f"leader tenures are the spans on track 9999)")


if __name__ == "__main__":
    main()
