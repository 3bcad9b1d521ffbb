"""The KV service's compiled requests against a plain host loop.

`BWKVService.put` and `get` each run one device program that steps the
cluster inside a `lax.while_loop` (DESIGN.md §11).  `HostLoop` below is
the same algorithm written the plain way: one jitted `step.tick` per
host round trip, with the key split on the host, and every index read
back before the next decision.  Two services built from one seed issue
the same requests; after each, the answers or the raised error, the
latency ticks, `read_latencies`, `session_floor`, every integer leaf of
the cluster state and the running key must be equal, and the compiled
side must have made one dispatch and one blocking read.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import state as SM
from repro.core import step
from repro.core.cluster_config import ClusterConfig, SiteConfig
from repro.core.runtime import BWRaftSim
from repro.kvstore.service import BWKVService, NotLeader, PutResult, Timeout


class HostLoop:
    """One host round trip per tick: the service's algorithm before it
    moved onto the device, kept here as the reference."""

    def __init__(self, sim: BWRaftSim, timeout: int):
        self.sim, self.timeout = sim, timeout
        self.read_latencies, self.session_floor = [], 0
        self.served_by = None         # role of the last get's replica
        static, backend = sim.static, sim.backend
        self._tick = jax.jit(lambda s, c, r: step.tick(s, static, c, r,
                                                       backend=backend))

    def _step(self, n):
        for _ in range(n):
            self.sim.rng, sub = jax.random.split(self.sim.rng)
            self.sim.state, _ = self._tick(self.sim.state, self.sim.cfg_c,
                                           sub)

    def _key_id(self, key):
        K = self.sim.cfg.key_space
        return int(hashlib.sha1(key.encode()).hexdigest(), 16) % K

    def _leader(self):
        return int(SM.leader_id(self.sim.state, self.sim.static))

    def put(self, key, value):
        kid = self._key_id(key)
        lid, waited = self._leader(), 0
        while lid < 0:
            self._step(5)
            waited += 5
            if waited > self.timeout:
                raise Timeout("no leader elected")
            lid = self._leader()
        st = self.sim.state
        pos = int(st["log_len"][lid])
        if pos >= self.sim.cfg.max_log:
            raise Timeout("log window full; run an epoch to compact")
        self.sim.state = dict(
            st,
            log_term=st["log_term"].at[lid, pos].set(st["term"][lid]),
            log_key=st["log_key"].at[lid, pos].set(kid),
            log_val=st["log_val"].at[lid, pos].set(value),
            log_len=st["log_len"].at[lid].set(pos + 1),
            entry_submit_t=st["entry_submit_t"].at[pos].set(st["tick"]))
        t0 = int(self.sim.state["tick"])
        while True:
            self._step(1)
            st = self.sim.state
            lid = self._leader()
            if lid >= 0 and int(st["commit_len"][lid]) > pos:
                self.session_floor = max(self.session_floor, pos + 1)
                return PutResult(pos, int(st["tick"]) - t0)
            if int(st["tick"]) - t0 > self.timeout:
                raise Timeout(f"put({key}) not committed "
                              f"after {self.timeout} ticks")

    def get(self, key, *, allow_observer=True, wait_for_leader=False):
        kid = self._key_id(key)
        t0, lid = int(self.sim.state["tick"]), self._leader()
        if lid < 0 and not wait_for_leader:
            raise NotLeader("no leader for readindex")
        waited = 0
        while lid < 0:
            self._step(5)
            waited += 5
            if waited > self.timeout:
                raise Timeout("read: no leader elected")
            lid = self._leader()
        st = self.sim.state
        role, alive = np.asarray(st["role"]), np.asarray(st["alive"])
        applied = np.asarray(st["applied_len"])
        fence = max(int(st["commit_len"][lid]), self.session_floor)
        node = None
        if allow_observer:
            obs = np.where((role == SM.OBSERVER) & alive &
                           (applied >= fence))[0]
            node = int(obs[0]) if obs.size else None
        if node is None:
            voter = np.where(((role == SM.FOLLOWER) | (role == SM.LEADER)) &
                             alive & (applied >= fence))[0]
            node = int(voter[0]) if voter.size else lid
        waited = 0
        while int(self.sim.state["applied_len"][node]) < fence:
            self._step(1)
            waited += 1
            if waited > self.timeout:
                raise Timeout("read: node never reached readindex")
        self.served_by = int(np.asarray(self.sim.state["role"])[node])
        value = int(self.sim.state["kv"][node, kid])
        self.session_floor = max(self.session_floor, fence)
        latency = int(self.sim.state["tick"]) - t0
        self.read_latencies.append(latency)
        st = self.sim.state
        H = st["read_lat_hist"].shape[0]
        self.sim.state = dict(
            st,
            reads_served=st["reads_served"] + 1,
            read_lat_sum=st["read_lat_sum"] + float(latency),
            read_lat_max=jnp.maximum(st["read_lat_max"], float(latency)),
            read_lat_hist=st["read_lat_hist"].at[
                min(max(latency, 0), H - 1)].add(1))
        return value, fence


def _cluster():
    sites = tuple(
        SiteConfig(f"kvp-s{i}", followers=f, rtt_intra=1,
                   rtt_inter=6 + 2 * i, on_demand_price=0.0416,
                   spot_price_mean=0.0125)
        for i, f in enumerate((2, 2, 1)))
    return ClusterConfig(name="kvp", sites=sites, max_log=128,
                         key_space=256, max_secretaries=4,
                         max_observers=8, period_ticks=20)


def _sim():
    return BWRaftSim(_cluster(), write_rate=0.0, read_rate=0.0, seed=5,
                     manage_resources=False, n_observers=8,
                     staleness_bound=12, ae_interval=2)


def _outcome(fn, *args, **kw):
    try:
        return fn(*args, **kw)
    except (NotLeader, Timeout) as e:
        return type(e), str(e)


class Pair:
    """The compiled service and the host loop over twin clusters."""

    def __init__(self, timeout=400):
        self.svc = BWKVService(_sim(), timeout_ticks=timeout)
        self.ref = HostLoop(_sim(), timeout)

    def issue(self, kind, key, *args, **kw):
        """Issue one request on both sides and compare everything it
        leaves; returns its outcome and the ticks it stepped."""
        svc, ref = self.svc, self.ref
        reads, dispatches, ticks = svc.host_reads, svc.dispatches, \
            svc.device_ticks
        tick0 = int(svc.sim.state["tick"])
        got = _outcome(getattr(svc, kind), key, *args, **kw)
        want = _outcome(getattr(ref, kind), key, *args, **kw)
        assert got == want, (kind, key, kw)
        stepped = int(svc.sim.state["tick"]) - tick0
        assert (svc.host_reads - reads, svc.dispatches - dispatches,
                svc.device_ticks - ticks) == (1, 1, stepped)
        assert svc.read_latencies == ref.read_latencies
        assert svc.session_floor == ref.session_floor
        self.same_state()
        return got, stepped

    def same_state(self):
        a, b = self.svc.sim, self.ref.sim
        assert np.array_equal(np.asarray(a.rng), np.asarray(b.rng))
        assert a.state.keys() == b.state.keys()
        for k in a.state:
            x, y = np.asarray(a.state[k]), np.asarray(b.state[k])
            if np.issubdtype(x.dtype, np.floating):
                np.testing.assert_allclose(x, y, rtol=1e-6, err_msg=k)
            else:
                assert np.array_equal(x, y), k

    def edit(self, fn):
        """Apply the same host-side edit to both clusters."""
        for sim in (self.svc.sim, self.ref.sim):
            sim.state = fn(sim.state)

    def step(self, n):
        self.svc._step(n)
        self.ref._step(n)
        self.same_state()

    def lease(self, secretaries, observers):
        for sim in (self.svc.sim, self.ref.sim):
            sim.lease_fixed(secretaries, observers)
        self.same_state()

    def set_timeout(self, ticks):
        self.svc.timeout = self.ref.timeout = ticks

    def leader(self):
        return int(SM.leader_id(self.svc.sim.state, self.svc.sim.static))


@pytest.fixture(scope="module")
def pair():
    """One twin pair for the module's requests, in file order: the
    cluster starts with no leader, as after the service starts."""
    return Pair()


def test_put_waits_for_the_election(pair):
    assert pair.leader() < 0
    put, stepped = pair.issue("put", "first", 7)
    assert isinstance(put, PutResult)
    assert stepped > put.latency_ticks > 0     # the leader wait, then commit
    put, _ = pair.issue("put", "second", 8)
    assert put.revision == 1


def test_fenced_get_served_by_an_observer(pair):
    pair.lease(2, 3)
    pair.step(30)                              # observers catch up
    (value, fence), stepped = pair.issue("get", "first")
    assert (value, stepped) == (7, 0) and fence >= 2
    assert pair.ref.served_by == SM.OBSERVER


def test_fenced_get_served_by_a_voter(pair):
    (value, _), _ = pair.issue("get", "second", allow_observer=False)
    assert value == 8
    assert pair.ref.served_by in (SM.FOLLOWER, SM.LEADER)


def test_get_waits_on_apply(pair):
    """A session floor past every replica's apply index (here: an entry
    appended at the leader and the floor raised to cover it) makes the
    read step until its replica applies the fence."""
    lid = pair.leader()

    def append(st):
        pos = int(st["log_len"][lid])
        return dict(st,
                    log_term=st["log_term"].at[lid, pos].set(st["term"][lid]),
                    log_key=st["log_key"].at[lid, pos].set(3),
                    log_val=st["log_val"].at[lid, pos].set(99),
                    log_len=st["log_len"].at[lid].set(pos + 1))
    pair.edit(append)
    floor = int(pair.svc.sim.state["log_len"][lid])
    pair.svc.session_floor = pair.ref.session_floor = floor
    (_, fence), stepped = pair.issue("get", "first")
    assert fence == floor and stepped > 0
    assert pair.svc.read_latencies[-1] == stepped


def test_put_times_out_before_commit(pair):
    pair.set_timeout(2)
    try:
        outcome, stepped = pair.issue("put", "hurried", 1)
    finally:
        pair.set_timeout(400)
    assert outcome == (Timeout, "put(hurried) not committed after 2 ticks")
    assert stepped == 3                        # the ticks stay behind


def test_get_without_a_leader_raises_notleader(pair):
    pair.issue("put", "before-kill", 5)
    lid = pair.leader()
    pair.edit(lambda st: dict(st, role=st["role"].at[lid].set(SM.DEAD),
                              alive=st["alive"].at[lid].set(False)))
    outcome, stepped = pair.issue("get", "before-kill")
    assert outcome == (NotLeader, "no leader for readindex")
    assert stepped == 0


def test_put_times_out_waiting_for_a_leader(pair):
    pair.set_timeout(3)
    try:
        outcome, stepped = pair.issue("put", "no-leader", 1)
    finally:
        pair.set_timeout(400)
    assert outcome == (Timeout, "no leader elected")
    assert stepped == 5


def test_get_waits_for_the_leader(pair):
    """A read with `wait_for_leader=True` steps through the election in
    5-tick polls; with the session floor past the fresh leader's commit
    index (Raft §5.4.2) it then waits on apply, served or timed out as
    the host loop is."""
    assert pair.leader() < 0
    pair.set_timeout(60)
    try:
        outcome, stepped = pair.issue("get", "before-kill",
                                      wait_for_leader=True)
    finally:
        pair.set_timeout(400)
    assert stepped > 0 and pair.leader() >= 0
    pair.issue("put", "nudge", 1)              # a current-term commit
    (value, _), _ = pair.issue("get", "before-kill", wait_for_leader=True)
    assert value == 5


def test_put_stops_at_a_full_log_window(pair):
    lid = pair.leader()
    L = pair.svc.sim.cfg.max_log
    pair.edit(lambda st: dict(st, log_len=st["log_len"].at[lid].set(L)))
    outcome, stepped = pair.issue("put", "late", 1)
    assert outcome == (Timeout, "log window full; run an epoch to compact")
    assert stepped == 0
