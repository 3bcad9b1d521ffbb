"""Plain reference of the KV client's requests against one simulated
cluster (`sim.Model`), each request one compiled loop on the device.

Semantics, as the service defines them:

  put(key, value)  wait for a leader (stepping 5 ticks at a time), append
                   the entry at the end of the leader's log, then step
                   one tick at a time until the leader's commit index
                   covers it.  Answer: the entry's log position and the
                   ticks it took.
  get(key)         fence on the leader's commit index (never below the
                   session's floor); serve from the first live observer
                   that has applied the fence, else the first live
                   follower or leader that has, else the leader, once it
                   has applied the fence.  Answer: the value and the
                   fence.  The read is folded into the read histogram.

A request that waits more than the timeout, or a get with no leader,
fails; a failed request leaves the ticks it stepped behind.  Keys are
strings hashed into the key space (first 160 bits of SHA-1, mod K).
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

from reference.sim import FOLLOWER, LEADER, OBSERVER

OK, TIMEOUT, NO_LEADER = 0, 1, 2


def key_id(key: str, n_keys: int) -> int:
    return int(hashlib.sha1(key.encode()).hexdigest(), 16) % n_keys


class Client:
    def __init__(self, model, timeout: int):
        self.m = model
        self.timeout = timeout
        self.put = jax.jit(self._put)
        self.get = jax.jit(self._get)

    def _put(self, st, rng, kid, val):
        m, T = self.m, self.timeout

        def no_leader(c):
            s, _, waited = c
            return (m.leader(s) < 0) & (waited <= T)

        def wait5(c):
            s, r, waited = c
            s, r = m.client_ticks(s, r, 5)
            return s, r, waited + 5
        st, rng, waited = jax.lax.while_loop(no_leader, wait5,
                                             (st, rng, jnp.int32(0)))
        lid = jnp.maximum(m.leader(st), 0)
        pos = st["log_len"][lid]
        can = (waited <= T) & (pos < m.L)
        put_at = jnp.where(can, pos, m.L)
        st = dict(
            st,
            log_term=st["log_term"].at[lid, put_at].set(st["term"][lid],
                                                        mode="drop"),
            log_key=st["log_key"].at[lid, put_at].set(kid, mode="drop"),
            log_val=st["log_val"].at[lid, put_at].set(val, mode="drop"),
            log_len=st["log_len"].at[lid].set(jnp.where(can, pos + 1,
                                                        pos)),
            entry_submit_t=st["entry_submit_t"].at[put_at].set(
                st["tick"], mode="drop"))
        t0 = st["tick"]

        def waiting(c):
            s, _, status = c
            return status < 0

        def step(c):
            s, r, _ = c
            s, r = m.client_ticks(s, r, 1)
            lid_now = m.leader(s)
            done = (lid_now >= 0) & \
                (s["commit_len"][jnp.maximum(lid_now, 0)] > pos)
            status = jnp.where(done, OK, jnp.where(s["tick"] - t0 > T,
                                                   TIMEOUT, -1))
            return s, r, status
        st, rng, status = jax.lax.while_loop(
            waiting, step, (st, rng, jnp.where(can, -1, TIMEOUT)))
        return st, rng, status, pos, st["tick"] - t0

    def _get(self, st, rng, kid, floor):
        m, T = self.m, self.timeout
        t0 = st["tick"]
        lid = m.leader(st)
        ld = jnp.maximum(lid, 0)
        fence = jnp.maximum(st["commit_len"][ld], floor)
        ready = st["alive"] & (st["applied_len"] >= fence)
        obs = ready & (st["role"] == OBSERVER)
        srv = ready & ((st["role"] == FOLLOWER) | (st["role"] == LEADER))
        node = jnp.where(jnp.any(obs), jnp.argmax(obs),
                         jnp.where(jnp.any(srv), jnp.argmax(srv), ld))

        def behind(c):
            s, _, waited = c
            return (s["applied_len"][node] < fence) & (waited <= T) & \
                (lid >= 0)

        def step(c):
            s, r, waited = c
            s, r = m.client_ticks(s, r, 1)
            return s, r, waited + 1
        st, rng, waited = jax.lax.while_loop(behind, step,
                                             (st, rng, jnp.int32(0)))
        status = jnp.where(lid < 0, NO_LEADER,
                           jnp.where(waited > T, TIMEOUT, OK))
        lat = st["tick"] - t0
        H = st["read_lat_hist"].shape[0]
        ok = status == OK
        st = dict(
            st,
            reads_served=st["reads_served"] + ok,
            read_lat_sum=jnp.where(ok, st["read_lat_sum"] +
                                   lat.astype(m.fdt), st["read_lat_sum"]),
            read_lat_max=jnp.where(ok, jnp.maximum(st["read_lat_max"],
                                                   lat.astype(m.fdt)),
                                   st["read_lat_max"]),
            read_lat_hist=st["read_lat_hist"].at[jnp.where(
                ok, jnp.clip(lat, 0, H - 1), H)].add(1, mode="drop"))
        return st, rng, status, st["kv"][node, kid], fence, lat
