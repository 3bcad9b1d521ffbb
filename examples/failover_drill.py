#!/usr/bin/env python
"""Failure drill: leader pod dies mid-commit; a new leader is elected,
the committed checkpoint record survives, observers keep serving reads.

    PYTHONPATH=src python examples/failover_drill.py
"""
from repro.configs.bwraft_kv import CONFIG
from repro.coord.coordinator import ConsensusCoordinator
from repro.coord.elastic import ElasticObserverPool
from repro import compile_cache


def main():
    compile_cache.enable()
    coord = ConsensusCoordinator(CONFIG, seed=1)
    lid = coord.wait_for_leader()
    print(f"leader: node {lid}")
    rec = coord.commit_checkpoint(100, "deadbeefcafe0123")
    print(f"checkpoint step=100 committed (rev {rec.revision})")

    pool = ElasticObserverPool(CONFIG, seed=1)
    pool.set_committed(100)
    pool.add_replicas(3)
    pool.route(24)
    print(f"serving: {pool.serve_tick()} reads via {len(pool.alive)} "
          f"observers")

    print(f"\n!!! killing leader node {lid}")
    coord.kill_pod(lid)
    new_lid = coord.wait_for_leader()
    print(f"new leader elected: node {new_lid}")
    got = coord.last_committed_checkpoint()
    assert got and got[0] == 100, got
    print(f"committed checkpoint survived failover: step={got[0]} "
          f"digest_tag={got[1]:03x}")

    pool.revoke_random(0.5)
    pool.route(24)
    print(f"after 50% observer revocation: {pool.serve_tick()} reads "
          f"served by {len(pool.alive)} survivors "
          f"(+{pool.rerouted} rerouted)")
    print("OK")


if __name__ == "__main__":
    main()
