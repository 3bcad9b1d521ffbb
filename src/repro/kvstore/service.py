"""BW-KV: the paper's key-value service API over the consensus core.

Mirrors Listing 1's client surface:
    revision_id <- put(key, value)
    (value, revision_id) <- get(key)

String keys hash into the bounded integer key space of the jitted state
machine (DESIGN.md §6).  `put` submits through the leader write path and
returns once the entry commits; `get` runs an explicit read-index round
(DESIGN.md §11): fence on the leader's commit index at request time,
pick a serving replica (observer preferred), wait until its apply index
reaches the fence, then read — so a read can never return uncommitted
data, and a read issued to a caught-up replica still reflects every
write acknowledged before it.  Per-request read latency is recorded on
the service (`read_latencies`) AND folded into the cluster's device-
resident read histogram (`state["read_lat_hist"]`), the same unit-bin
digest histogram the simulator's aggregate read path samples into.
This is the host-facing service layer used by the examples; throughput-
scale experiments drive the simulator's aggregate workload instead.

Profiler spans (`trace.spans`, DESIGN.md §14): `kv.tick` around each
tick dispatch, `kv.sync` around each blocking device-to-host read,
`kv.write` around each host-issued device write; `host_reads` counts
the reads.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import state as SM
from repro.core.runtime import BWRaftSim
from repro.trace import spans as trace_spans


class NotLeader(Exception):
    pass


class Timeout(Exception):
    pass


@dataclasses.dataclass
class PutResult:
    revision: int
    latency_ticks: int


class BWKVService:
    """Synchronous client over an in-process BW-Raft cluster."""

    def __init__(self, sim: BWRaftSim, *, timeout_ticks: int = 400):
        self.sim = sim
        self.timeout = timeout_ticks
        self._tickfn = None
        # per-request read latencies (ticks), in completion order — the
        # host-side twin of the device read histogram (DESIGN.md §11)
        self.read_latencies: list = []
        # blocking device-to-host reads issued so far (`_fetch`)
        self.host_reads: int = 0
        # session fence floor: the highest log length this client has
        # been acked (writes) or served (reads).  A read-index round
        # fences at max(leader commit index, floor), so a read can never
        # return a value older than the last write acknowledged to this
        # session — even across a leader change whose fresh leader has
        # not re-established the old commit index yet (DESIGN.md §11).
        self.session_floor: int = 0

    def _fetch(self, x) -> np.ndarray:
        """One blocking device-to-host read, counted in `host_reads`."""
        with jax.profiler.TraceAnnotation(trace_spans.KV_SYNC):
            self.host_reads += 1
            return np.asarray(x)

    def _key_id(self, key: str) -> int:
        K = self.sim.cfg.key_space
        return int(hashlib.sha1(key.encode()).hexdigest(), 16) % K

    def _step(self, n: int = 1) -> None:
        """Advance the cluster `n` ticks on the sim's backend.  `cfg_c`
        is a jit argument, so `sim.set_rates(...)` reaches the next
        tick without a recompile."""
        import repro.core.step as step_mod
        if self._tickfn is None:
            static, backend = self.sim.static, self.sim.backend
            self._tickfn = jax.jit(
                lambda s, c, r: step_mod.tick(s, static, c, r,
                                              backend=backend))
        for _ in range(n):
            with jax.profiler.TraceAnnotation(trace_spans.KV_TICK):
                self.sim.rng, sub = jax.random.split(self.sim.rng)
                self.sim.state, _ = self._tickfn(self.sim.state,
                                                 self.sim.cfg_c, sub)

    def put(self, key: str, value: int) -> PutResult:
        """Submit a write through the leader; block until committed."""
        kid = self._key_id(key)
        st = self.sim.state
        lid = int(self._fetch(SM.leader_id(st, self.sim.static)))
        waited = 0
        while lid < 0:
            self._step(5)
            waited += 5
            if waited > self.timeout:
                raise Timeout("no leader elected")
            lid = int(self._fetch(SM.leader_id(self.sim.state,
                                               self.sim.static)))
        st = self.sim.state
        # append directly at the leader (bypasses the random workload gen —
        # this is the explicit-client path)
        pos = int(self._fetch(st["log_len"][lid]))
        if pos >= self.sim.cfg.max_log:
            raise Timeout("log window full; run an epoch to compact")
        with jax.profiler.TraceAnnotation(trace_spans.KV_WRITE):
            term = st["term"][lid]
            self.sim.state = dict(
                st,
                log_term=st["log_term"].at[lid, pos].set(term),
                log_key=st["log_key"].at[lid, pos].set(kid),
                log_val=st["log_val"].at[lid, pos].set(value),
                log_len=st["log_len"].at[lid].set(pos + 1),
                entry_submit_t=st["entry_submit_t"].at[pos].set(st["tick"]),
            )
        t0 = int(self._fetch(self.sim.state["tick"]))
        while True:
            self._step(1)
            st = self.sim.state
            lid_now = int(self._fetch(SM.leader_id(st, self.sim.static)))
            if lid_now >= 0 and \
                    int(self._fetch(st["commit_len"][lid_now])) > pos:
                self.session_floor = max(self.session_floor, pos + 1)
                return PutResult(
                    revision=pos,
                    latency_ticks=int(self._fetch(st["tick"])) - t0)
            if int(self._fetch(st["tick"])) - t0 > self.timeout:
                raise Timeout(f"put({key}) not committed "
                              f"after {self.timeout} ticks")

    def _record_read(self, latency_ticks: int) -> None:
        """Fold one completed read into the service's latency record and
        the cluster's device-resident read histogram — the same unit-bin
        digest histogram the aggregate read path samples into, so client
        reads and simulated reads share one percentile machinery
        (DESIGN.md §11)."""
        self.read_latencies.append(int(latency_ticks))
        st = self.sim.state
        H = st["read_lat_hist"].shape[0]
        b = min(max(int(latency_ticks), 0), H - 1)
        with jax.profiler.TraceAnnotation(trace_spans.KV_WRITE):
            self.sim.state = dict(
                st,
                reads_served=st["reads_served"] + 1,
                read_lat_sum=st["read_lat_sum"] + float(latency_ticks),
                read_lat_max=jnp.maximum(st["read_lat_max"],
                                         float(latency_ticks)),
                read_lat_hist=st["read_lat_hist"].at[b].add(1),
            )

    def get(self, key: str, *, allow_observer: bool = True,
            wait_for_leader: bool = False) -> Tuple[int, int]:
        """One explicit read-index round (paper §3.1 step 6 / §4.3,
        DESIGN.md §11):

        1. *leader fence* — find the leader and capture its commit index
           (`readindex`, floored at `session_floor` so the fence always
           covers every write already acked to this session, leader
           changes included) at request time; with no leader, raise
           `NotLeader`, or — `wait_for_leader=True` — step until one is
           elected (Timeout bounds the wait), so a read during an
           election waits or times out, never serves stale state;
        2. *replica pick* — serve from a caught-up observer when
           allowed, else a caught-up follower/leader, else fall back to
           the leader itself;
        3. *apply wait* — step until the serving replica's apply index
           reaches the fence, so the value returned reflects every
           entry committed before the read began.

        Returns ``(value, revision)`` with ``revision = readindex``; the
        round's latency (ticks from request to serve) is recorded via
        `_record_read`."""
        kid = self._key_id(key)
        t0 = int(self._fetch(self.sim.state["tick"]))
        lid = int(self._fetch(SM.leader_id(self.sim.state, self.sim.static)))
        if lid < 0 and not wait_for_leader:
            raise NotLeader("no leader for readindex")
        waited = 0
        while lid < 0:
            self._step(5)
            waited += 5
            if waited > self.timeout:
                raise Timeout("read: no leader elected")
            lid = int(self._fetch(SM.leader_id(self.sim.state,
                                               self.sim.static)))
        st = self.sim.state
        role = self._fetch(st["role"])
        alive = self._fetch(st["alive"])
        readindex = max(int(self._fetch(st["commit_len"][lid])),
                        self.session_floor)
        applied = self._fetch(st["applied_len"])
        node = None
        if allow_observer:
            obs = np.where((role == SM.OBSERVER) & alive &
                           (applied >= readindex))[0]
            if obs.size:
                node = int(obs[0])
        if node is None:
            fol = np.where(((role == SM.FOLLOWER) | (role == SM.LEADER)) &
                           alive & (applied >= readindex))[0]
            node = int(fol[0]) if fol.size else lid
        # apply-index wait: the serving replica must reach the fence
        waited = 0
        while int(self._fetch(self.sim.state["applied_len"][node])) < \
                readindex:
            self._step(1)
            waited += 1
            if waited > self.timeout:
                raise Timeout("read: node never reached readindex")
        value = int(self._fetch(self.sim.state["kv"][node, kid]))
        self.session_floor = max(self.session_floor, readindex)
        self._record_read(int(self._fetch(self.sim.state["tick"])) - t0)
        return value, readindex

    def get_stale(self, key: str) -> Tuple[int, int]:
        """Bounded-staleness read through the digest tier (DESIGN.md §13).

        No read-index fence: pick a live digest observer that is (a)
        within the configured staleness bound (``tick - dobs_synced_t <=
        staleness_bound``) and (b) not behind this session's floor
        (``dobs_applied >= session_floor``, the session-monotonicity
        contract — a session never reads a prefix shorter than one it
        already observed or wrote).  The observer holds no dense log, so
        the value is reconstructed host-side by last-wins replay of its
        follower's applied prefix ``log[:dobs_applied]`` — exactly the
        state the digest certifies (Property 3.2 prefix mirror).  Returns
        ``(value, revision)`` with ``revision = dobs_applied`` and raises
        the session floor to it.  When no digest observer qualifies
        (tier off, all stale, or all behind the floor) the read reroutes
        to the fenced `get` path, mirroring `read_step`'s in-graph
        reroute rule."""
        st = self.sim.state
        O = int(self.sim.static.get("O", 0))
        if O == 0:
            return self.get(key)
        kid = self._key_id(key)
        t0 = int(self._fetch(st["tick"]))
        alive = self._fetch(st["dobs_alive"])
        applied = self._fetch(st["dobs_applied"])
        synced = self._fetch(st["dobs_synced_t"])
        bound = int(self._fetch(self.sim.cfg_c["staleness_bound"]))
        ok = alive & (t0 - synced <= bound) & (applied >= self.session_floor)
        cand = np.where(ok)[0]
        if not cand.size:
            return self.get(key)                  # reroute: behind/stale
        # freshest qualifying observer serves
        o = int(cand[np.argmax(applied[cand])])
        revision = int(applied[o])
        fol = int(self._fetch(st["dobs_fol"][o]))
        keys = self._fetch(st["log_key"][fol][:revision])
        vals = self._fetch(st["log_val"][fol][:revision])
        hits = np.where(keys == kid)[0]
        value = int(vals[hits[-1]]) if hits.size else -1
        self.session_floor = max(self.session_floor, revision)
        self._record_read(int(self._fetch(self.sim.state["tick"])) - t0)
        return value, revision
