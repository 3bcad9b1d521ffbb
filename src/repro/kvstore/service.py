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

Each request is one compiled device program (DESIGN.md §11): its leader
wait, its append or fence and replica pick, and its wait for commit or
apply run as one `lax.while_loop` that steps the cluster one tick per
iteration, exactly as a host loop over `step.tick` would, with the same
key split off the cluster's running key each tick.  The host dispatches
the program and reads back one small answer, so a request costs one
dispatch and one blocking read however many ticks it waits.

Profiler spans (`trace.spans`, DESIGN.md §14): `kv.tick` around each
program dispatch, `kv.sync` around each blocking device-to-host read,
`kv.write` around each host-issued device write (`get_stale`'s read
record); `host_reads` counts the reads, `dispatches` the programs and
`device_ticks` the ticks stepped inside them.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import state as SM
from repro.core import step
from repro.core.runtime import BWRaftSim
from repro.trace import spans as trace_spans

# a request's outcome, the first word of its program's answer
OK, NO_LEADER, TIMEOUT_LEADER, LOG_FULL, TIMEOUT_COMMIT, TIMEOUT_APPLY = \
    range(6)
# still waiting: for a leader, or for the entry to commit or the serving
# replica to apply the fence
_LEADER_WAIT, _WAIT = -2, -1
# a request with no leader looks for one every this many ticks
_LEADER_POLL = 5


class NotLeader(Exception):
    pass


class Timeout(Exception):
    pass


@dataclasses.dataclass
class PutResult:
    revision: int
    latency_ticks: int


# ------------------------------------------------------ device programs
def _advance(static, backend, state, cfg_c, rng):
    """One tick as the service steps it: a fresh key split off the
    cluster's running key.  `cfg_c` and `rng` stay traced, so a rate
    change reaches the next tick without a recompile."""
    rng, sub = jax.random.split(rng)
    state, _ = step.tick(state, static, cfg_c, sub, backend=backend)
    return state, rng


def _ticks(static, backend, state, cfg_c, rng, n):
    """`n` ticks (traced, so one program serves every count)."""
    return lax.fori_loop(
        0, n, lambda _, c: _advance(static, backend, c[0], cfg_c, c[1]),
        (state, rng))


def _wait(static, backend, state, cfg_c, rng, timeout, status, extra,
          settle, check):
    """Step the cluster a tick at a time until the request's status is
    final (>= 0).  While `_LEADER_WAIT`, every `_LEADER_POLL` ticks the
    request times out once it has waited past `timeout`, else `settle`
    looks for a leader and, finding one, moves it on; while `_WAIT`,
    `check` decides after each tick.  `waited` restarts when the leader
    is found; timeouts count ticks stepped, which is what `tick`
    advances by, so the loop ends whatever the state holds.  Returns the
    state, the key, the status, the ticks waited since the leader was
    found, the ticks stepped and `extra`."""
    def body(c):
        s, r, status, waited, n, extra = c
        waiting = status == _WAIT
        s, r = _advance(static, backend, s, cfg_c, r)
        waited, n = waited + 1, n + 1
        poll = (status == _LEADER_WAIT) & (waited % _LEADER_POLL == 0)
        status = jnp.where(poll & (waited > timeout), TIMEOUT_LEADER, status)
        s, settled, extra = settle(s, poll & (status == _LEADER_WAIT),
                                   status, extra)
        waited = jnp.where(settled != status, 0, waited)
        status = jnp.where(waiting, check(s, waited, extra), settled)
        return s, r, status, waited, n, extra
    z = jnp.int32(0)
    return lax.while_loop(lambda c: c[2] < 0, body,
                          (state, rng, status, z, z, extra))


def _put(static, backend, max_log, state, cfg_c, rng, kid, value, timeout):
    """One put: wait for a leader, append at the end of its log (or stop
    at the log window), then wait until the leader's commit index covers
    the entry.  Answer: (status, position, ticks from the append to the
    commit, ticks stepped)."""
    def settle(s, now, status, extra):
        lid = SM.leader_id(s, static)
        found = now & (lid >= 0)
        ld = jnp.maximum(lid, 0)
        pos = s["log_len"][ld]
        go = found & (pos < max_log)
        at = jnp.where(go, pos, s["entry_submit_t"].shape[0])  # dropped
        s = dict(
            s,
            log_term=s["log_term"].at[ld, at].set(s["term"][ld],
                                                  mode="drop"),
            log_key=s["log_key"].at[ld, at].set(kid, mode="drop"),
            log_val=s["log_val"].at[ld, at].set(value, mode="drop"),
            log_len=s["log_len"].at[ld].add(go.astype(jnp.int32)),
            entry_submit_t=s["entry_submit_t"].at[at].set(s["tick"],
                                                          mode="drop"))
        status = jnp.where(found, jnp.where(go, _WAIT, LOG_FULL), status)
        return s, status, (jnp.where(found, pos, extra[0]),)

    def committed(s, waited, extra):
        lid = SM.leader_id(s, static)
        done = (lid >= 0) & (s["commit_len"][jnp.maximum(lid, 0)] > extra[0])
        return jnp.where(done, OK, jnp.where(waited > timeout,
                                             TIMEOUT_COMMIT, _WAIT))

    state, status, extra = settle(state, True, _LEADER_WAIT,
                                  (jnp.int32(-1),))
    state, rng, status, waited, n, (pos,) = _wait(
        static, backend, state, cfg_c, rng, timeout, status, extra, settle,
        committed)
    return state, rng, jnp.stack([status, pos, waited, n])


def _fold_read(state, ok, latency):
    """Fold one served read (where `ok`) into the cluster's read record:
    the count, the latency sum and maximum, and the unit-bin histogram
    the aggregate read path samples into (DESIGN.md §11)."""
    H = state["read_lat_hist"].shape[0]
    lat = jnp.asarray(latency).astype(state["read_lat_sum"].dtype)
    return dict(
        state,
        reads_served=state["reads_served"] + jnp.asarray(ok).astype(
            state["reads_served"].dtype),
        read_lat_sum=jnp.where(ok, state["read_lat_sum"] + lat,
                               state["read_lat_sum"]),
        read_lat_max=jnp.where(ok, jnp.maximum(state["read_lat_max"], lat),
                               state["read_lat_max"]),
        read_lat_hist=state["read_lat_hist"].at[jnp.where(
            ok, jnp.clip(latency, 0, H - 1), H)].add(1, mode="drop"))


def _get(static, backend, state, cfg_c, rng, kid, floor, timeout, *,
         allow_observer, wait_for_leader):
    """One read-index round (`BWKVService.get`).  Answer: (status,
    value, fence, ticks stepped), the last also the read's latency."""
    def settle(s, now, status, extra):
        lid = SM.leader_id(s, static)
        found = now & (lid >= 0)
        ld = jnp.maximum(lid, 0)
        fence = jnp.maximum(s["commit_len"][ld], floor)
        ready = s["alive"] & (s["applied_len"] >= fence)
        voter = ready & ((s["role"] == SM.FOLLOWER) |
                         (s["role"] == SM.LEADER))
        node = jnp.where(jnp.any(voter), jnp.argmax(voter), ld)
        if allow_observer:
            obs = ready & (s["role"] == SM.OBSERVER)
            node = jnp.where(jnp.any(obs), jnp.argmax(obs), node)
        caught = s["applied_len"][node] >= fence
        status = jnp.where(found, jnp.where(caught, OK, _WAIT), status)
        return s, status, (jnp.where(found, fence, extra[0]),
                           jnp.where(found, node, extra[1]).astype(jnp.int32))

    def applied(s, waited, extra):
        fence, node = extra
        return jnp.where(waited > timeout, TIMEOUT_APPLY,
                         jnp.where(s["applied_len"][node] >= fence, OK,
                                   _WAIT))

    state, status, extra = settle(state, True, _LEADER_WAIT,
                                  (jnp.int32(0), jnp.int32(0)))
    if not wait_for_leader:
        status = jnp.where(status == _LEADER_WAIT, NO_LEADER, status)
    state, rng, status, _, n, (fence, node) = _wait(
        static, backend, state, cfg_c, rng, timeout, status, extra, settle,
        applied)
    state = _fold_read(state, status == OK, n)
    return state, rng, jnp.stack([status, state["kv"][node, kid], fence, n])


# ------------------------------------------------------------- service
class BWKVService:
    """Synchronous client over an in-process BW-Raft cluster."""

    def __init__(self, sim: BWRaftSim, *, timeout_ticks: int = 400):
        self.sim = sim
        self.timeout = timeout_ticks
        static, backend = sim.static, sim.backend
        self._ticks = jax.jit(functools.partial(_ticks, static, backend))
        self._put = jax.jit(functools.partial(_put, static, backend,
                                              sim.cfg.max_log))
        self._get = jax.jit(functools.partial(_get, static, backend),
                            static_argnames=("allow_observer",
                                             "wait_for_leader"))
        # per-request read latencies (ticks), in completion order — the
        # host-side twin of the device read histogram (DESIGN.md §11)
        self.read_latencies: list = []
        # blocking device-to-host reads issued so far (`_fetch`)
        self.host_reads: int = 0
        # device programs dispatched (one per request, one per `_step`)
        # and the cluster ticks stepped inside them
        self.dispatches: int = 0
        self.device_ticks: int = 0
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
        """Advance the cluster `n` ticks on the sim's backend, in one
        dispatch.  `cfg_c` is a jit argument, so `sim.set_rates(...)`
        reaches the next tick without a recompile."""
        sim = self.sim
        with jax.profiler.TraceAnnotation(trace_spans.KV_TICK):
            sim.state, sim.rng = self._ticks(sim.state, sim.cfg_c, sim.rng,
                                             n)
        self.dispatches += 1
        self.device_ticks += n

    def _request(self, program, *args, **static_args) -> list:
        """Dispatch one request program over the cluster, keep the state
        and key it leaves (a failed request leaves the ticks it stepped
        behind), and read its answer: one dispatch, one read."""
        sim = self.sim
        with jax.profiler.TraceAnnotation(trace_spans.KV_TICK):
            sim.state, sim.rng, answer = program(
                sim.state, sim.cfg_c, sim.rng, *args, **static_args)
        self.dispatches += 1
        answer = [int(a) for a in self._fetch(answer)]
        self.device_ticks += answer[-1]
        return answer

    def put(self, key: str, value: int) -> PutResult:
        """Submit a write through the leader; block until committed.

        Waits for a leader (`Timeout` after `timeout` ticks), appends at
        the end of its log (`Timeout` if the log window is full), then
        steps until the leader's commit index covers the entry (`Timeout`
        after `timeout` ticks)."""
        status, pos, latency, _ = self._request(
            self._put, self._key_id(key), value, self.timeout)
        if status == TIMEOUT_LEADER:
            raise Timeout("no leader elected")
        if status == LOG_FULL:
            raise Timeout("log window full; run an epoch to compact")
        if status != OK:
            raise Timeout(f"put({key}) not committed "
                          f"after {self.timeout} ticks")
        self.session_floor = max(self.session_floor, pos + 1)
        return PutResult(revision=pos, latency_ticks=latency)

    def _record_read(self, latency_ticks: int) -> None:
        """Fold one read served on the host into the service's latency
        record and the cluster's device-resident read histogram, as the
        `get` program does on the device — so client reads and simulated
        reads share one percentile machinery (DESIGN.md §11)."""
        self.read_latencies.append(int(latency_ticks))
        with jax.profiler.TraceAnnotation(trace_spans.KV_WRITE):
            self.sim.state = _fold_read(self.sim.state, True,
                                        int(latency_ticks))

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
        round's latency (ticks from request to serve) is appended to
        `read_latencies` and folded into the read histogram."""
        status, value, fence, latency = self._request(
            self._get, self._key_id(key), self.session_floor, self.timeout,
            allow_observer=allow_observer, wait_for_leader=wait_for_leader)
        if status == NO_LEADER:
            raise NotLeader("no leader for readindex")
        if status == TIMEOUT_LEADER:
            raise Timeout("read: no leader elected")
        if status == TIMEOUT_APPLY:
            raise Timeout("read: node never reached readindex")
        self.read_latencies.append(latency)
        self.session_floor = max(self.session_floor, fence)
        return value, fence

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
