"""Plain reference of one simulated BW-Raft cluster, written from the
deployment's semantics in `jax.numpy` and nothing else.

It covers what the benchmark's deployments run: the synthetic spot-price
walk with price-over-bid revocation and the i.i.d. kill knob (no market
traces, fault schedules or advance warnings), the closed-loop aggregate
generator, elections, the leader's write intake and budgeted fan-out
through secretaries, follower log matching, majority commit over voters,
in-order apply into the key-value table, dense observers, the digest-tier
observer rack with anti-entropy and bounded-staleness reads, cost, the
epoch digest and the epoch-boundary log compaction.

A simulated run is defined by its seed: each tick draws from the tick's
key exactly as the deployment's definition says (`tick` splits it into
market, workload, leader and election keys, and so on down).  The
reference follows that schedule so that the same seed gives the same
trajectory; every rule is written out plainly (sequential applies, the
count-per-length majority test), not in the program's fused forms.

`fdt` is the float type of every real-valued input and state leaf
(prices, rates, key CDF, cost, latency sums).  float32 is the
deployment; bfloat16 is the control that the comparison must reject.
"""
from __future__ import annotations

import copy
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

FOLLOWER, CANDIDATE, LEADER, SECRETARY, OBSERVER, DEAD = range(6)
HIST_TAIL = 64
LEADER_WINDOW = 64          # writes a leader can take in one tick, at most
MIX_POS, MIX_KEY, MIX_VAL = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D


class Model:
    """Static tables and inputs of one deployment (a config JSON)."""

    def __init__(self, config: dict, *, write_rate: float, read_rate: float,
                 phi: float, key_cdf, key_zipf: bool, fdt=jnp.float32):
        c, nm, dt = config["cluster"], config["node_model"], \
            config["digest_tier"]
        sites = c["sites"]
        self.fdt = fdt
        self.S = len(sites)
        self.V = sum(s["followers"] for s in sites)
        self.MS, self.MO = c["max_secretaries"], c["max_observers"]
        self.N = self.V + self.MS + self.MO
        self.L, self.K, self.T = c["max_log"], c["key_space"], \
            c["period_ticks"]
        self.O = dt["n_observers"]
        self.cap, self.msg_budget = nm["work_capacity"], nm["msg_budget"]
        self.entries_per_msg, self.max_ship = nm["entries_per_msg"], \
            nm["max_ship"]
        self.max_apply = nm["max_apply"]
        self.majority = self.V // 2 + 1
        self.cfg = c
        self.sites = sites

        site = np.zeros(self.N, np.int32)
        i = 0
        for s_idx, s in enumerate(sites):
            for _ in range(s["followers"]):
                site[i] = s_idx
                i += 1
        for j in range(self.V, self.N):
            site[j] = (j - self.V) % self.S
        self.site = site
        self.is_voter = np.arange(self.N) < self.V
        self.is_sec_slot = (np.arange(self.N) >= self.V) & \
            (np.arange(self.N) < self.V + self.MS)
        self.is_obs_slot = np.arange(self.N) >= self.V + self.MS

        def trip(a, b):
            if a == b:
                return sites[a]["rtt_intra"]
            return (sites[a]["rtt_inter"] + sites[b]["rtt_inter"]) // 2
        self.rtt = np.array([[trip(site[a], site[b]) for b in range(self.N)]
                             for a in range(self.N)], np.int32)
        self.site_rtt = np.array([[trip(a, b) for b in range(self.S)]
                                  for a in range(self.S)], np.int32)
        self.dobs_site = np.arange(self.O, dtype=np.int32) % self.S
        # each digest slot follows a voter of its own site, round robin
        dobs_fol = np.full(self.O, -1, np.int32)
        taken: Dict[int, int] = {}
        for o in range(self.O):
            d = int(self.dobs_site[o])
            voters = [v for v in range(self.V) if site[v] == d]
            k = taken.get(d, 0)
            dobs_fol[o] = voters[k % len(voters)] if voters else o % self.V
            taken[d] = k + 1
        self.dobs_fol = dobs_fol

        f = lambda x: jnp.asarray(np.asarray(x, np.float32), fdt)
        self.inp = {
            "write_rate": f(write_rate), "read_rate": f(read_rate),
            "phi": f(phi),
            "price_mean": f([s["spot_price_mean"] for s in sites]),
            "price_vol": f(sites[0]["spot_price_vol"]),
            "bid": f([s["spot_price_mean"] * nm["bid_over_mean"]
                      for s in sites]),
            "od_price": f([s["on_demand_price"] for s in sites]),
            "ticks_per_hour": f(nm["ticks_per_hour"]),
            "net_coef": f(nm["network_cost_coef"]),
            "key_cdf": f(key_cdf),
        }
        self.key_zipf = bool(key_zipf)
        self.staleness_bound = dt["staleness_bound"]
        self.ae_interval = max(dt["ae_interval"], 1)
        self.ae_phase = np.arange(self.O, dtype=np.int32)
        self.t_min = c["election_timeout_min"]
        self.t_max = c["election_timeout_max"]
        self.H = self.T + 1 + HIST_TAIL

    def with_inputs(self, inp: Dict) -> "Model":
        """This model reading its float inputs from `inp`.  Under `jit`,
        pass `self.inp` as an argument through this: as a closed-over
        constant, XLA folds what depends on the inputs alone on the host,
        and a Poisson draw from a folded rate can differ from one computed
        on the device (TPU v5e: 5 in 16,777,216 draws at rate 16, 6,823 at
        12.5), while the deployment computes it on the device."""
        m = copy.copy(self)
        m.inp = inp
        return m

    # ------------------------------------------------------------ state
    def init_state(self) -> Dict[str, jnp.ndarray]:
        N, L, K, O, H = self.N, self.L, self.K, self.O, self.H
        z = lambda *sh: jnp.zeros(sh, jnp.int32)
        neg = lambda *sh: jnp.full(sh, -1, jnp.int32)
        span = self.t_max - self.t_min + 1
        return {
            "tick": z(), "role": jnp.asarray(np.where(
                self.is_voter, FOLLOWER, DEAD).astype(np.int32)),
            "alive": jnp.asarray(self.is_voter), "term": z(N),
            "voted_for": neg(N), "votes_received": z(N),
            "log_term": z(N, L), "log_key": z(N, L), "log_val": z(N, L),
            "log_len": z(N), "commit_len": z(N), "applied_len": z(N),
            "kv": z(N, K),
            "election_timer": jnp.asarray(
                (self.t_min + (np.arange(N) * 7) % span).astype(np.int32)),
            "heartbeat_timer": z(N), "match_len": z(N),
            "app_arrive_t": neg(N), "app_from_len": z(N), "app_upto": z(N),
            "app_term": z(N), "app_commit": z(N),
            "ack_arrive_t": neg(N), "ack_upto": z(N),
            "vreq_t": neg(N), "vreq_from": neg(N), "vreq_term": z(N),
            "vreq_lastterm": z(N), "vreq_lastlen": z(N),
            "grant_t": neg(N), "grant_to": neg(N), "grant_term": z(N),
            "sec_of": neg(N), "obs_of": neg(N),
            "read_queue": z(N), "write_pending": z(), "leader_work": z(N),
            "entry_submit_t": neg(L), "entry_commit_t": neg(L),
            "spot_price": self.inp["price_mean"],
            "warn_timer": neg(N),
            "reads_arrived": z(), "writes_arrived": z(),
            "reads_served": z(), "writes_committed": z(),
            "read_lat_sum": jnp.zeros((), self.fdt),
            "read_lat_max": jnp.zeros((), self.fdt),
            "read_lat_hist": z(H),
            "cost_accrued": jnp.zeros((), self.fdt),
            "applied_digest": jnp.zeros((N,), jnp.uint32),
            "dobs_alive": jnp.ones((O,), bool),
            "dobs_fol": jnp.asarray(self.dobs_fol),
            "dobs_applied": z(O), "dobs_term": z(O),
            "dobs_digest": jnp.zeros((O,), jnp.uint32),
            "dobs_synced_t": z(O), "dobs_read_queue": z(O),
            "obs_reads_served": z(), "obs_rerouted": z(),
            "obs_stale_hist": z(H),
        }

    def leader(self, st):
        ids = jnp.arange(self.N)
        return jnp.max(jnp.where((st["role"] == LEADER) & st["alive"],
                                 ids, -1))

    def sec_relay_ok(self, st):
        """Per node: its wired secretary is alive and still a secretary."""
        sec = jnp.maximum(st["sec_of"], 0)
        return (st["sec_of"] >= 0) & st["alive"][sec] & \
            (st["role"][sec] == SECRETARY)

    # ------------------------------------------------------------ phases
    def market(self, st, key):
        inp = self.inp
        k_price, _, k_fail = jax.random.split(key, 3)
        noise = jax.random.normal(k_price, (self.S,), self.fdt) * \
            inp["price_vol"] * inp["price_mean"]
        price = st["spot_price"] + 0.2 * (inp["price_mean"] -
                                          st["spot_price"]) + 0.15 * noise
        price = jnp.maximum(price, 0.1 * inp["price_mean"])
        revoked = price > inp["bid"]                            # (S,)
        spot = ~self.is_voter
        fail = jax.random.uniform(k_fail, (self.N,), self.fdt) < inp["phi"]
        killed = st["alive"] & spot & (revoked[self.site] | fail)
        st = dict(st, spot_price=price, alive=st["alive"] & ~killed,
                  role=jnp.where(killed, DEAD, st["role"]))
        if self.O:
            fail_d = jax.random.uniform(jax.random.fold_in(k_fail, 1),
                                        (self.O,), self.fdt) < inp["phi"]
            killed_d = st["dobs_alive"] & (revoked[self.dobs_site] | fail_d)
            st = dict(st, dobs_alive=st["dobs_alive"] & ~killed_d)
        return st, jnp.sum(killed)

    def arrivals(self, st, key):
        k_w, k_r, _ = jax.random.split(key, 3)
        lam_w = self.inp["write_rate"].astype(jnp.float32)
        lam_r = self.inp["read_rate"].astype(jnp.float32)
        n_w = jax.random.poisson(k_w, lam_w).astype(jnp.int32)
        n_r = jax.random.poisson(k_r, lam_r).astype(jnp.int32)
        alive = st["alive"]
        is_obs = (st["role"] == OBSERVER) & alive
        is_fol = ((st["role"] == FOLLOWER) | (st["role"] == LEADER)) & alive
        n_fol = jnp.maximum(jnp.sum(is_fol), 1)
        pool = jnp.sum(is_obs)
        if self.O:
            pool = pool + jnp.sum(st["dobs_alive"])
        # up to 90% of the reads go to the observer pool, within its
        # capacity; the rest is split evenly over followers and leader
        obs_share = jnp.where(pool > 0,
                              jnp.minimum((n_r * 9) // 10, pool * self.cap),
                              0)
        per_slot = obs_share // jnp.maximum(pool, 1)
        per_fol = (n_r - obs_share) // n_fol
        out = dict(st, read_queue=st["read_queue"] +
                   jnp.where(is_obs, per_slot, 0) +
                   jnp.where(is_fol, per_fol, 0),
                   write_pending=st["write_pending"] + n_w,
                   reads_arrived=st["reads_arrived"] + n_r,
                   writes_arrived=st["writes_arrived"] + n_w)
        if self.O:
            # the digest rack also takes the remainder, one per slot
            rem = obs_share - per_slot * jnp.maximum(pool, 1)
            rank = jnp.cumsum(st["dobs_alive"].astype(jnp.int32)) - 1
            out["dobs_read_queue"] = st["dobs_read_queue"] + jnp.where(
                st["dobs_alive"], per_slot + (rank < rem), 0)
        return out

    def election(self, st, key):
        N, L = self.N, self.L
        tick, rtt = st["tick"], jnp.asarray(self.rtt)
        voter = jnp.asarray(self.is_voter)
        alive = st["alive"]
        (k_timeout,) = jax.random.split(key, 1)
        timer = st["election_timer"] - 1
        timed_out = (timer <= 0) & voter & alive & \
            ((st["role"] == FOLLOWER) | (st["role"] == CANDIDATE))
        term = jnp.where(timed_out, st["term"] + 1, st["term"])
        role = jnp.where(timed_out, CANDIDATE, st["role"])
        voted_for = jnp.where(timed_out, jnp.arange(N), st["voted_for"])
        fresh_timeout = jax.random.randint(k_timeout, (N,), self.t_min,
                                           self.t_max + 1)
        timer = jnp.where(timed_out | (timer <= 0), fresh_timeout, timer)

        # the highest-term candidate's RequestVote takes each voter's slot
        cand_term = jnp.where((role == CANDIDATE) & alive, term, -1)
        c = jnp.argmax(cand_term)
        c_len = st["log_len"][c]
        c_last_term = st["log_term"][c, jnp.clip(c_len - 1, 0, L - 1)]
        place = (jnp.max(cand_term) >= 0) & voter & alive & \
            (term[c] > st["vreq_term"])
        vreq_t = jnp.where(place, tick + rtt[c], st["vreq_t"])
        vreq_from = jnp.where(place, c, st["vreq_from"])
        vreq_term = jnp.where(place, term[c], st["vreq_term"])
        vreq_lastterm = jnp.where(place, c_last_term, st["vreq_lastterm"])
        vreq_lastlen = jnp.where(place, c_len, st["vreq_lastlen"])

        # voters answer the requests that arrived
        due = (vreq_t >= 0) & (vreq_t <= tick) & alive & voter
        higher = due & (vreq_term > term)
        term = jnp.where(higher, vreq_term, term)
        role = jnp.where(higher & ((role == LEADER) | (role == CANDIDATE)),
                         FOLLOWER, role)
        voted_for = jnp.where(higher, -1, voted_for)
        my_len = st["log_len"]
        my_last_term = st["log_term"][jnp.arange(N),
                                      jnp.clip(my_len - 1, 0, L - 1)]
        up_to_date = (vreq_lastterm > my_last_term) | \
            ((vreq_lastterm == my_last_term) & (vreq_lastlen >= my_len))
        grant = due & (vreq_term >= term) & up_to_date & \
            ((voted_for == -1) | (voted_for == vreq_from))
        voted_for = jnp.where(grant, vreq_from, voted_for)
        timer = jnp.where(grant, fresh_timeout, timer)
        grant_t = jnp.where(grant, tick + rtt[jnp.arange(N),
                                              jnp.maximum(vreq_from, 0)],
                            st["grant_t"])
        grant_to = jnp.where(grant, vreq_from, st["grant_to"])
        grant_term = jnp.where(grant, vreq_term, st["grant_term"])
        vreq_t = jnp.where(due, -1, vreq_t)

        # candidates count the grants that arrived for their term
        arrived = (grant_t >= 0) & (grant_t <= tick)
        to = jnp.maximum(grant_to, 0)
        counted = arrived & (grant_term == term[to])
        tally = jnp.zeros(N, jnp.int32).at[jnp.where(counted, to, N)].add(
            1, mode="drop")
        votes = jnp.where(timed_out, 0, st["votes_received"])
        votes = jnp.where(role == CANDIDATE, votes + tally, 0)
        win = (role == CANDIDATE) & alive & (votes + 1 >= self.majority)
        role = jnp.where(win, LEADER, role)
        grant_t = jnp.where(arrived, -1, grant_t)
        newest = jnp.max(jnp.where((role == LEADER) & alive, term, -1))
        role = jnp.where((role == LEADER) & (term < newest), FOLLOWER, role)
        # a new leader stops every secretary and starts its own tally
        any_win = jnp.any(win)
        match_len = jnp.where(any_win, 0, st["match_len"])
        was_sec = role == SECRETARY
        role = jnp.where(any_win & was_sec, DEAD, role)
        alive = alive & ~(any_win & (st["role"] == SECRETARY))
        return dict(st, alive=alive, term=term, role=role,
                    voted_for=voted_for, votes_received=votes,
                    election_timer=timer, vreq_t=vreq_t,
                    vreq_from=vreq_from, vreq_term=vreq_term,
                    vreq_lastterm=vreq_lastterm, vreq_lastlen=vreq_lastlen,
                    grant_t=grant_t, grant_to=grant_to,
                    grant_term=grant_term, match_len=match_len,
                    heartbeat_timer=jnp.where(win, 0,
                                              st["heartbeat_timer"]))

    def leader_intake_and_ship(self, st, key):
        N, L, W = self.N, self.L, LEADER_WINDOW
        lid = self.leader(st)
        has = lid >= 0
        ld = jnp.maximum(lid, 0)
        tick = st["tick"]
        start = st["log_len"][ld]
        n_take = jnp.where(has, jnp.minimum(jnp.minimum(
            st["write_pending"], self.cap), L - start), 0)
        slot = jnp.arange(W)
        take = slot < n_take
        pos = jnp.where(take, start + slot, L)
        keys_u = jax.random.randint(key, (W,), 0, self.K)
        u = jax.random.uniform(jax.random.fold_in(key, 2), (W,), self.fdt)
        keys_z = jnp.clip(jnp.searchsorted(self.inp["key_cdf"], u,
                                           side="left"),
                          0, self.K - 1).astype(jnp.int32)
        keys = keys_z if self.key_zipf else keys_u
        vals = jax.random.randint(jax.random.fold_in(key, 1), (W,), 0,
                                  2 ** 20)
        st = dict(
            st,
            log_term=st["log_term"].at[ld, pos].set(st["term"][ld],
                                                    mode="drop"),
            log_key=st["log_key"].at[ld, pos].set(keys, mode="drop"),
            log_val=st["log_val"].at[ld, pos].set(vals, mode="drop"),
            entry_submit_t=st["entry_submit_t"].at[pos].set(tick,
                                                            mode="drop"),
            log_len=st["log_len"].at[ld].set(start + n_take),
            write_pending=st["write_pending"] - n_take)

        # AppendEntries: each follower or candidate with no batch in
        # flight gets one, through its secretary when that is alive,
        # else straight from the leader within its message budget
        rtt = jnp.asarray(self.rtt)
        ids = jnp.arange(N)
        relay = jnp.where(self.sec_relay_ok(st), st["sec_of"], ld)
        target = ((st["role"] == FOLLOWER) | (st["role"] == CANDIDATE)) & \
            st["alive"] & (ids != ld)
        arrive = tick + rtt[ld, relay] * (relay != ld) + rtt[relay, ids]
        want = has & target & (st["app_arrive_t"] < 0)
        direct = want & (relay == ld)
        relayed = want & (relay != ld)
        live_secs = jnp.sum((st["role"] == SECRETARY) & st["alive"])
        sec_msgs = jnp.where(jnp.any(relayed), live_secs, 0)
        budget = jnp.maximum(self.msg_budget - sec_msgs, 0)
        log_len = st["log_len"][ld]
        pending = jnp.maximum(log_len - st["match_len"], 0)
        cost = 1 + jnp.minimum(pending, self.max_ship) // \
            self.entries_per_msg
        ship = relayed | (direct & (jnp.cumsum(jnp.where(direct, cost, 0))
                                    <= budget))
        return dict(
            st,
            app_arrive_t=jnp.where(ship, arrive, st["app_arrive_t"]),
            app_from_len=jnp.where(ship, st["match_len"],
                                   st["app_from_len"]),
            app_upto=jnp.where(ship, jnp.minimum(
                log_len, st["match_len"] + self.max_ship), st["app_upto"]),
            app_term=jnp.where(ship, st["term"][ld], st["app_term"]),
            app_commit=jnp.where(ship, st["commit_len"][ld],
                                 st["app_commit"]),
            leader_work=st["leader_work"].at[ld].add(
                jnp.sum(ship & direct) + sec_msgs))

    def follow(self, st):
        N, L, W = self.N, self.L, self.max_ship
        tick = st["tick"]
        lid = self.leader(st)
        ld = jnp.maximum(lid, 0)
        ids = jnp.arange(N)
        rtt = jnp.asarray(self.rtt)
        delivered = (st["app_arrive_t"] >= 0) & \
            (st["app_arrive_t"] <= tick) & st["alive"]
        due = delivered & (st["app_term"] >= st["term"]) & (lid >= 0)
        # log matching on the entry before the batch
        prev = st["app_from_len"] - 1
        prev_c = jnp.clip(prev, 0, L - 1)
        same_prev = st["log_term"][ids, prev_c] == \
            st["log_term"][ld, prev_c]
        accept = due & ((prev < 0) | same_prev)
        nack = due & ~((prev < 0) | same_prev)
        # adopt the leader's entries [from, min(upto, from + W))
        hi = jnp.minimum(st["app_upto"], st["app_from_len"] + W)
        p = jnp.arange(L)[None, :]
        sel = accept[:, None] & (p >= st["app_from_len"][:, None]) & \
            (p < hi[:, None])
        adopt = {k: jnp.where(sel, st[k][ld][None, :], st[k])
                 for k in ("log_term", "log_key", "log_val")}
        new_len = jnp.where(accept, hi, st["log_len"])
        new_len = jnp.where(accept & (st["log_len"] > new_len) & same_prev,
                            jnp.maximum(st["log_len"], new_len), new_len)
        term = jnp.where(due, jnp.maximum(st["term"], st["app_term"]),
                         st["term"])
        role = jnp.where(due & (st["role"] == CANDIDATE), FOLLOWER,
                         st["role"])
        commit = jnp.where(accept, jnp.maximum(
            st["commit_len"], jnp.minimum(st["app_commit"], new_len)),
            st["commit_len"])
        span = self.t_max - self.t_min + 1
        timer = jnp.where(due, self.t_min + (tick + ids * 7) % span,
                          st["election_timer"])
        # the ack goes back along the path the batch took
        relay = jnp.where(self.sec_relay_ok(st), st["sec_of"], ld)
        back = rtt[ids, relay] + rtt[relay, ld] * (relay != ld)
        return dict(
            st, **adopt, log_len=new_len, term=term, role=role,
            commit_len=commit, election_timer=timer,
            ack_arrive_t=jnp.where(accept | nack, tick + back,
                                   st["ack_arrive_t"]),
            ack_upto=jnp.where(accept, new_len,
                               jnp.where(nack, st["app_from_len"] // 2,
                                         st["ack_upto"])),
            app_arrive_t=jnp.where(delivered, -1, st["app_arrive_t"]))

    def commit(self, st):
        L = self.L
        tick = st["tick"]
        lid = self.leader(st)
        has = lid >= 0
        ld = jnp.maximum(lid, 0)
        arrived = (st["ack_arrive_t"] >= 0) & (st["ack_arrive_t"] <= tick)
        via_sec = self.sec_relay_ok(st)
        direct = arrived & ~via_sec
        ingest = (arrived & via_sec) | \
            (direct & (jnp.cumsum(direct.astype(jnp.int32)) <=
                       self.msg_budget))
        match = jnp.where(ingest, jnp.maximum(st["match_len"],
                                              st["ack_upto"]),
                          st["match_len"])
        match = jnp.where(ingest & (st["ack_upto"] < st["match_len"]),
                          st["ack_upto"], match)
        match = match.at[ld].set(jnp.where(has, st["log_len"][ld],
                                           match[ld]))
        # a length commits once a majority of live voters hold it and
        # its last entry is of the leader's term
        lens = jnp.arange(1, L + 1)
        holders = jnp.sum((match[None, :] >= lens[:, None]) &
                          (jnp.asarray(self.is_voter) &
                           st["alive"])[None, :], axis=1)
        ok = (holders >= self.majority) & \
            (st["log_term"][ld] == st["term"][ld])
        best = jnp.max(jnp.where(ok, lens, 0))
        old = st["commit_len"][ld]
        new = jnp.where(has, jnp.maximum(old, best), 0)
        idx = jnp.arange(L)
        newly = (idx >= old) & (idx < new) & has
        return dict(
            st, match_len=match,
            ack_arrive_t=jnp.where(ingest, -1, st["ack_arrive_t"]),
            commit_len=st["commit_len"].at[ld].set(jnp.where(has, new,
                                                             old)),
            entry_commit_t=jnp.where(newly & (st["entry_commit_t"] < 0),
                                     tick, st["entry_commit_t"]),
            writes_committed=st["writes_committed"] +
            jnp.where(has, new - old, 0))

    def apply(self, st):
        N, L, K, A = self.N, self.L, self.K, self.max_apply
        base = st["applied_len"]
        todo = jnp.minimum(st["commit_len"] - base, A)
        kv, digest = st["kv"], st["applied_digest"]
        rows = jnp.arange(N)
        for a in range(A):              # in log order, one entry at a time
            pos = base + a
            ok = (a < todo) & (pos < L) & st["alive"]
            pc = jnp.clip(pos, 0, L - 1)
            k = st["log_key"][rows, pc]
            v = st["log_val"][rows, pc]
            kv = kv.at[jnp.where(ok, rows, N), jnp.where(ok, k, K)].set(
                v, mode="drop")
            digest = digest ^ jnp.where(ok, entry_mix(pc, k, v),
                                        jnp.uint32(0))
        return dict(st, kv=kv, applied_digest=digest,
                    applied_len=base + jnp.maximum(todo, 0))

    def observers_mirror(self, st):
        fol = jnp.maximum(st["obs_of"], 0)
        sync = (st["role"] == OBSERVER) & st["alive"] & \
            (st["obs_of"] >= 0) & st["alive"][fol]
        out = dict(st)
        for k in ("applied_len", "commit_len", "log_len", "applied_digest"):
            out[k] = jnp.where(sync, st[k][fol], st[k])
        for k in ("kv", "log_term", "log_key", "log_val"):
            out[k] = jnp.where(sync[:, None], st[k][fol], st[k])
        return out

    def digest_source(self, st):
        """Each digest slot's source: its voter, else the first live
        voter; and whether its own voter is usable."""
        voter = jnp.asarray(self.is_voter)
        fol = jnp.clip(st["dobs_fol"], 0, self.N - 1)
        own = (st["dobs_fol"] >= 0) & st["alive"][fol] & voter[fol]
        live = voter & st["alive"]
        return jnp.where(own, fol, jnp.argmax(live)), own, jnp.any(live)

    def anti_entropy(self, st):
        if not self.O:
            return st
        src, own, any_voter = self.digest_source(st)
        due = st["dobs_alive"] & (own | any_voter) & \
            (jnp.mod(st["tick"] + jnp.asarray(self.ae_phase),
                     self.ae_interval) == 0)
        src_applied = st["applied_len"][src]
        adopt = due & (src_applied >= st["dobs_applied"])
        hop = jnp.asarray(self.site_rtt)[jnp.asarray(self.dobs_site),
                                         jnp.asarray(self.site)[src]]
        return dict(
            st,
            dobs_applied=jnp.where(adopt, src_applied, st["dobs_applied"]),
            dobs_term=jnp.where(adopt, st["term"][src], st["dobs_term"]),
            dobs_digest=jnp.where(adopt, st["applied_digest"][src],
                                  st["dobs_digest"]),
            dobs_synced_t=jnp.where(due, st["tick"] - hop,
                                    st["dobs_synced_t"]))

    def serve_reads(self, st):
        N, H, cap, fdt = self.N, self.H, self.cap, self.fdt
        tick = st["tick"]
        ld = jnp.maximum(self.leader(st), 0)
        rtt = jnp.asarray(self.rtt)
        ids = jnp.arange(N)
        is_obs = (st["role"] == OBSERVER) & st["alive"]
        is_srv = ((st["role"] == FOLLOWER) | (st["role"] == LEADER)) & \
            st["alive"]
        fresh = st["applied_len"] >= st["commit_len"][ld]
        q = st["read_queue"]
        served = jnp.where((is_obs & fresh) | is_srv, jnp.minimum(q, cap), 0)
        stale = jnp.where(is_obs & ~fresh, q, 0)
        queue = (q - served - stale).at[jnp.maximum(st["obs_of"], 0)].add(
            stale, mode="drop")
        any_sec = jnp.any((st["role"] == SECRETARY) & st["alive"])
        fence = rtt[ids, ld] * jnp.where(any_sec, 1, 2)
        lat = q // cap + 1 + jnp.where(is_obs, fence, rtt[ids, ld])
        lat_sum = jnp.sum(jnp.where(served > 0,
                                    lat.astype(fdt) * served, 0.0))
        lat_max = jnp.max(jnp.where(served > 0, lat.astype(fdt), 0.0))
        hist = st["read_lat_hist"].at[
            jnp.where(served > 0, jnp.clip(lat, 0, H - 1), H)].add(
                served, mode="drop")
        out = {}
        total = jnp.sum(served)
        if self.O:
            dq = st["dobs_read_queue"]
            age = tick - st["dobs_synced_t"]
            ok = st["dobs_alive"] & (age <= self.staleness_bound)
            d_served = jnp.where(ok, jnp.minimum(dq, cap), 0)
            d_back = jnp.where(~ok, dq, 0)
            src, _, _ = self.digest_source(st)
            queue = queue.at[jnp.where(d_back > 0, src, N)].add(
                d_back, mode="drop")
            d_lat = dq // cap + 1
            lat_sum = lat_sum + jnp.sum(jnp.where(
                d_served > 0, d_lat.astype(fdt) * d_served, 0.0))
            lat_max = jnp.maximum(lat_max, jnp.max(jnp.where(
                d_served > 0, d_lat.astype(fdt), 0.0)))
            hist = hist.at[jnp.where(d_served > 0, jnp.clip(d_lat, 0, H - 1),
                                     H)].add(d_served, mode="drop")
            total = total + jnp.sum(d_served)
            out = dict(
                dobs_read_queue=dq - d_served - d_back,
                obs_stale_hist=st["obs_stale_hist"].at[jnp.where(
                    d_served > 0, jnp.clip(age, 0, H - 1), H)].add(
                        d_served, mode="drop"),
                obs_reads_served=st["obs_reads_served"] + jnp.sum(d_served),
                obs_rerouted=st["obs_rerouted"] + jnp.sum(d_back))
        return dict(st, **out, read_queue=queue,
                    reads_served=st["reads_served"] + total,
                    read_lat_sum=st["read_lat_sum"] + lat_sum,
                    read_lat_max=jnp.maximum(st["read_lat_max"], lat_max),
                    read_lat_hist=hist)

    def cost(self, st):
        """Dollars per tick: on-demand voters plus every live spot
        instance at its site's price, times the network term."""
        inp = self.inp
        voter = jnp.asarray(self.is_voter)
        site = jnp.asarray(self.site)
        spot = jnp.sum(jnp.where(~voter & st["alive"],
                                 st["spot_price"][site], 0.0))
        n_live = jnp.sum(st["alive"])
        if self.O:
            spot = spot + jnp.sum(jnp.where(
                st["dobs_alive"],
                st["spot_price"][jnp.asarray(self.dobs_site)], 0.0))
            n_live = n_live + jnp.sum(st["dobs_alive"])
        per_tick = jnp.sum(jnp.where(voter & st["alive"],
                                     inp["od_price"][site], 0.0)) + spot
        per_tick = per_tick / inp["ticks_per_hour"]
        per_tick = per_tick * (1.0 + inp["net_coef"] * n_live)
        return dict(st, cost_accrued=st["cost_accrued"] + per_tick)

    # ------------------------------------------------------------- ticks
    def tick(self, st, key) -> Tuple[Dict, jnp.ndarray]:
        k_market, k_work, k_lead, k_elect = jax.random.split(key, 4)
        st, killed = self.market(st, k_market)
        st = self.arrivals(st, k_work)
        st = self.election(st, k_elect)
        st = self.leader_intake_and_ship(st, k_lead)
        st = self.follow(st)
        st = self.commit(st)
        st = self.apply(st)
        st = self.observers_mirror(st)
        st = self.anti_entropy(st)
        st = self.serve_reads(st)
        st = self.cost(st)
        return dict(st, tick=st["tick"] + 1), killed

    def leader_term(self, st):
        lid = self.leader(st)
        return jnp.where(lid >= 0, st["term"][jnp.maximum(lid, 0)], -1)

    def epoch(self, st, key):
        """T ticks, the epoch's digest, then log compaction."""
        cost0 = st["cost_accrued"]

        def body(carry, k):
            s, killed, no_leader, changes, prev = carry
            s, k_n = self.tick(s, k)
            lt = self.leader_term(s)
            return (s, killed + k_n, no_leader + (lt < 0),
                    changes + (lt > prev), lt), None
        z = jnp.int32(0)
        (st, killed, no_leader, changes, _), _ = jax.lax.scan(
            body, (st, z, z, z, self.leader_term(st)),
            jax.random.split(key, self.T))
        sub, com = st["entry_submit_t"], st["entry_commit_t"]
        done = (sub >= 0) & (com >= 0)
        wl = jnp.zeros(self.H, jnp.int32).at[jnp.where(
            done, jnp.clip(com - sub, 0, self.H - 1), self.H)].add(
                1, mode="drop")
        digest = {
            "reads_arrived": st["reads_arrived"],
            "writes_arrived": st["writes_arrived"],
            "reads_served": st["reads_served"],
            "read_lat_sum": st["read_lat_sum"],
            "read_lat_max": st["read_lat_max"],
            "read_lat_hist": st["read_lat_hist"],
            "write_lat_hist": wl,
            "cost_delta": st["cost_accrued"] - cost0,
            "n_secretaries": jnp.sum((st["role"] == SECRETARY) &
                                     st["alive"]),
            "n_observers": jnp.sum((st["role"] == OBSERVER) & st["alive"]),
            "killed": killed, "no_leader_ticks": no_leader,
            "leader_changes": changes,
            "role": st["role"], "alive": st["alive"],
            "spot_price": st["spot_price"],
            "obs_stale_hist": st["obs_stale_hist"],
            "obs_reads_served": st["obs_reads_served"],
            "obs_rerouted": st["obs_rerouted"],
            "n_obs_digest": jnp.sum(st["dobs_alive"]),
        }
        return compact(st), digest

    def client_ticks(self, st, rng, n):
        """`n` ticks as the KV service steps them: a fresh key per tick
        split off the cluster's running key."""
        def body(_, c):
            s, r = c
            r, k = jax.random.split(r)
            s, _ = self.tick(s, k)
            return s, r
        return jax.lax.fori_loop(0, n, body, (st, rng))


def entry_mix(pos, key, val):
    """uint32 fingerprint of one log entry at its position; a replica's
    applied digest is the XOR over its applied prefix."""
    u = lambda x: jnp.asarray(x).astype(jnp.uint32)
    return ((u(pos) + jnp.uint32(1)) * jnp.uint32(MIX_POS)
            ^ (u(key) + jnp.uint32(1)) * jnp.uint32(MIX_KEY)
            ^ (u(val) + jnp.uint32(1)) * jnp.uint32(MIX_VAL))


def compact(st):
    """Epoch boundary: the log window and the per-epoch counters reset,
    the key-value tables stay; every digest slot is leased again and
    stays stale until its next anti-entropy round."""
    zero = lambda k: jnp.zeros_like(st[k])
    neg = lambda k: jnp.full_like(st[k], -1)
    out = dict(st)
    for k in ("dobs_applied", "dobs_term", "dobs_digest", "obs_reads_served",
              "obs_rerouted", "obs_stale_hist", "log_term", "log_key",
              "log_val", "log_len", "commit_len", "applied_len",
              "applied_digest", "match_len", "reads_arrived",
              "writes_arrived", "reads_served", "writes_committed",
              "read_lat_sum", "read_lat_max", "read_lat_hist"):
        out[k] = zero(k)
    for k in ("app_arrive_t", "ack_arrive_t", "entry_submit_t",
              "entry_commit_t"):
        out[k] = neg(k)
    out["dobs_alive"] = jnp.ones_like(st["dobs_alive"])
    return out


def hist_percentile(counts, q: float) -> float:
    """numpy's linear-interpolation percentile of the integer sample that
    a unit-bin histogram encodes; NaN when it is empty."""
    counts = np.asarray(counts)
    if counts.sum() == 0:
        return float("nan")
    return float(np.percentile(np.repeat(np.arange(counts.size), counts), q))
