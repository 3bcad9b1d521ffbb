"""BW-KV service semantics over the consensus core: the explicit
read-index round (leader commit fence + replica apply wait,
DESIGN.md §11), its NotLeader/Timeout raise paths, and key-hash
stability."""
import hashlib

import numpy as np
import pytest

from repro.configs.bwraft_kv import CONFIG as CC
from repro.core import state as SM
from repro.core.runtime import BWRaftSim
from repro.kvstore.service import BWKVService, NotLeader, Timeout


def fresh_service(*, seed=9, elect=True, timeout_ticks=400,
                  observers=0) -> BWKVService:
    sim = BWRaftSim(CC, write_rate=0.0, read_rate=0.0, seed=seed,
                    manage_resources=False)
    if observers:
        sim._lease(0, observers)
    s = BWKVService(sim, timeout_ticks=timeout_ticks)
    if elect:
        s._step(120)
    return s


@pytest.fixture(scope="module")
def svc():
    return fresh_service()


def test_put_get_roundtrip(svc):
    r = svc.put("hello", 42)
    assert r.revision >= 0
    v, rev = svc.get("hello")
    assert v == 42


def test_overwrite_returns_latest(svc):
    svc.put("key2", 1)
    svc.put("key2", 2)
    v, _ = svc.get("key2")
    assert v == 2


def test_reads_follow_commits(svc):
    res = svc.put("key3", 7)
    v, rev = svc.get("key3")
    assert v == 7 and rev > res.revision


# ------------------------------------------------------------------ #
# the explicit read-index round (DESIGN.md §11)
# ------------------------------------------------------------------ #
def test_put_then_get_returns_committed_revision(svc):
    """The read's revision is the leader commit fence at request time:
    at least past the put's log position, and the value is the
    committed one."""
    res = svc.put("fence", 11)
    v, rev = svc.get("fence")
    assert v == 11
    assert rev > res.revision          # fence covers the committed put
    lid = int(SM.leader_id(svc.sim.state, svc.sim.static))
    assert rev <= int(svc.sim.state["commit_len"][lid])


def test_read_index_round_records_latency(svc):
    """Every completed get records its round latency on the service AND
    in the cluster's device-resident read histogram (DESIGN.md §11)."""
    svc.put("lat", 5)
    n0 = len(svc.read_latencies)
    h0 = int(np.asarray(svc.sim.state["read_lat_hist"]).sum())
    s0 = int(svc.sim.state["reads_served"])
    v, _ = svc.get("lat")
    assert v == 5
    assert len(svc.read_latencies) == n0 + 1
    assert svc.read_latencies[-1] >= 0
    assert int(np.asarray(svc.sim.state["read_lat_hist"]).sum()) == h0 + 1
    assert int(svc.sim.state["reads_served"]) == s0 + 1


def test_observer_serves_caught_up_read():
    """With a caught-up observer wired, the round serves from it (the
    observer offload of paper §3.1 step 6)."""
    s = fresh_service(seed=11, observers=4)
    s.put("obs", 21)
    s._step(30)                        # let observers catch up
    st = s.sim.state
    role = np.asarray(st["role"])
    alive = np.asarray(st["alive"])
    lid = int(SM.leader_id(st, s.sim.static))
    readindex = int(st["commit_len"][lid])
    applied = np.asarray(st["applied_len"])
    caught = (role == SM.OBSERVER) & alive & (applied >= readindex)
    assert caught.any(), "no observer caught up — wiring broke"
    v, rev = s.get("obs")
    assert v == 21 and rev >= readindex


def test_uncommitted_log_entry_not_readable(svc):
    """A log entry that has not committed is invisible to the read-index
    round: the fence is the leader's COMMIT index, so a read served by a
    caught-up replica returns the last committed value, never log tail."""
    svc.put("dirty", 1)
    svc._step(30)                      # settle: applied reaches commit
    st = svc.sim.state
    lid = int(SM.leader_id(st, svc.sim.static))
    kid = svc._key_id("dirty")
    pos = int(st["log_len"][lid])
    # append an UNCOMMITTED overwrite directly to the leader's log
    svc.sim.state = dict(
        st,
        log_term=st["log_term"].at[lid, pos].set(st["term"][lid]),
        log_key=st["log_key"].at[lid, pos].set(kid),
        log_val=st["log_val"].at[lid, pos].set(999),
        log_len=st["log_len"].at[lid].set(pos + 1),
    )
    v, rev = svc.get("dirty")
    assert v == 1, "read returned uncommitted data"
    assert rev <= pos                  # fence stops at the commit index


# ------------------------------------------------------------------ #
# NotLeader / Timeout raise paths
# ------------------------------------------------------------------ #
def test_get_without_leader_raises_notleader():
    s = fresh_service(seed=13, elect=False)   # t=0: nobody elected yet
    assert int(SM.leader_id(s.sim.state, s.sim.static)) < 0
    with pytest.raises(NotLeader):
        s.get("anything")


def test_get_wait_for_leader_times_out():
    """`wait_for_leader=True` bounds the election wait by Timeout — a
    read during an election waits or times out, never serves."""
    s = fresh_service(seed=13, elect=False, timeout_ticks=5)
    n0 = len(s.read_latencies)
    with pytest.raises(Timeout):
        s.get("anything", wait_for_leader=True)
    assert len(s.read_latencies) == n0    # nothing served, nothing logged


def test_read_during_election_waits_or_times_out_never_stale():
    """Kill the leader mid-session.  A plain get raises NotLeader; a
    waiting get blocks through the election — and because the fresh
    leader cannot commit the old-term entry until a current-term entry
    commits (the Raft §5.4.2 rule), the session fence makes the read
    TIME OUT rather than return a value older than the acked write.
    Once a new write re-establishes the commit index, the read serves
    the acked value."""
    s = fresh_service(seed=15, timeout_ticks=120)
    s.put("ha", 77)
    floor = s.session_floor
    assert floor >= 1
    st = s.sim.state
    lid = int(SM.leader_id(st, s.sim.static))
    s.sim.state = dict(
        st,
        role=st["role"].at[lid].set(SM.DEAD),
        alive=st["alive"].at[lid].set(False),
    )
    with pytest.raises(NotLeader):
        s.get("ha")
    # waits through the election, then refuses to serve below the
    # session floor: Timeout, never the pre-write value
    with pytest.raises(Timeout):
        s.get("ha", wait_for_leader=True)
    # a current-term write re-establishes the commit fence ...
    s.timeout = 400
    s.put("nudge", 1)
    # ... and the read now serves the value acked before the failover
    v, rev = s.get("ha")
    assert v == 77
    assert rev >= floor


def test_put_without_leader_times_out():
    s = fresh_service(seed=13, elect=False, timeout_ticks=5)
    with pytest.raises(Timeout):
        s.put("k", 1)


# ------------------------------------------------------------------ #
# key-hash stability
# ------------------------------------------------------------------ #
def test_key_hash_stable_across_services_and_runs(svc):
    """The string->key-id map is a pure function of (key, key_space):
    identical across service instances, sessions, and platforms (sha1,
    not python hash()), so revisions and shard routing are replayable."""
    other = BWKVService(BWRaftSim(CC, write_rate=0.0, read_rate=0.0,
                                  seed=99, manage_resources=False))
    for key in ("hello", "key2", "a" * 100, "", "ünicode"):
        kid = svc._key_id(key)
        assert kid == other._key_id(key)
        assert 0 <= kid < CC.key_space
        want = int(hashlib.sha1(key.encode()).hexdigest(), 16) % CC.key_space
        assert kid == want


def test_key_hash_pinned_values(svc):
    """Two pinned probes guard the exact hash formula — a silent change
    would silently remap every stored key."""
    assert CC.key_space == 1024
    assert svc._key_id("hello") == int(hashlib.sha1(b"hello")
                                       .hexdigest(), 16) % 1024
    assert svc._key_id("bwraft") == int(hashlib.sha1(b"bwraft")
                                        .hexdigest(), 16) % 1024


def test_set_rates_reaches_the_service_tick():
    """`set_rates` after the service has compiled its tick takes effect
    on the next tick: phi = 1 revokes every spot node (leased dense
    secretaries/observers and digest slots alike) within one tick, and
    no voter (Property 3.4)."""
    sim = BWRaftSim(CC, write_rate=0.0, read_rate=0.0, seed=9,
                    manage_resources=False, n_observers=8)
    s = BWKVService(sim)
    s._step(120)
    sim.lease_fixed(3, 4)
    s._step(1)
    spot = ~np.asarray(sim.static["is_voter"])
    assert np.asarray(sim.state["alive"])[spot].sum() == 7
    assert np.asarray(sim.state["dobs_alive"]).sum() == 8
    sim.set_rates(phi=1.0)
    s._step(1)
    alive = np.asarray(sim.state["alive"])
    assert not alive[spot].any()
    assert not np.asarray(sim.state["dobs_alive"]).any()
    assert alive[~spot].all()
